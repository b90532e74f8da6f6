"""Serving step builders — the ONE place jit/pjit step functions are built.

Both consumers of the serve subsystem go through this module:

  * ``serve.engine.ServeEngine`` (and ``launch/serve.py``, which drives it)
    uses :func:`build_paged_steps` — the paged continuous-batching step
    set: ONE unified ragged step (chunked prefill + batched decode in the
    same jit), the COW page copy, and the per-slot SSM-state reset.
  * ``launch/dryrun.py`` uses :func:`build_prefill` / :func:`build_decode`
    — the contiguous production-mesh cells it lowers and costs.

Every builder takes ``(cfg, mesh, params_struct)``. With ``mesh=None`` the
builders emit plain single-device ``jax.jit`` functions (lru-shared per
FULL step geometry — cfg, page, pool/slot sizes, cache dtype, chunk and
the ``paged_attention`` flag are all part of the cache key, so a late
flag flip can never reuse a stale jit). With a mesh they emit jit
functions with **explicit input/output shardings**.

The unified step contract
-------------------------
  ``step(params, tokens [B, C], arena, start [B], n_new [B], sampling)
  -> (tokens [B, C] int32, logprobs [B, C] float32, arena)``

  Lane ``b`` runs ``n_new[b]`` new tokens at absolute positions
  ``start[b] + t``: a decode lane carries one token (``n_new = 1``), a
  prefill lane carries a chunk of its prompt (``1 <= n_new <= C``), and
  an idle lane carries ``n_new = 0`` (its writes route to the null page
  and its output rows are dead). K/V scatter straight into the paged
  arena and the ragged attention read happen inside the one traced
  function — there is no contiguous prefill cache and no adopt copy any
  more. The engine drives exactly two shapes per geometry: ``C = 1``
  (decode-only rounds) and ``C = chunk`` (rounds with a prefill chunk in
  flight), which is the whole compile surface — the pow2 bucket zoo is
  gone. ``reset_state(arena, slot)`` zeroes a slot's dense SSM/conv rows
  at admission (``None`` for attention-only stacks); ``page_copy`` is
  the device half of ``PagedKVPool.cow``.

  **Sampling head** — token selection is FUSED into the step: the raw
  ``[B, C, V]`` logits never cross the jit boundary (they used to feed
  a stray out-of-jit ``jnp.argmax`` dispatch per round, invisible to
  cost attribution). ``sampling`` is a pytree of traced ``[B]`` lane
  params — ``{"temp" f32, "top_k" i32, "top_p" f32, "key" [B,2] u32}``
  (see ``serve.sampling.lane_inputs``) — so one compile per width C
  serves every parameter combo. A ``temp <= 0`` lane takes the argmax
  path bitwise (greedy stays the oracle); sampled lanes draw via
  ``jax.random.categorical`` with top-k/top-p masks, per-column keys
  folded from the lane key + the token's absolute position (layout-
  independent streams — see ``serve/sampling.py`` for the full
  contract). The returned logprobs are the model-distribution
  log-softmax at the selected token; dead columns (at or past
  ``n_new[b]``) return ``sampling.DEAD_TOKEN`` = -1, never a vocab id.

  **Verify steps** (self-speculative decode) are the SAME step at the
  same rungs: a lane verifying k draft tokens runs ``n_new = 1 + k``
  through the smallest ``width_ladder`` rung covering it — column 0
  carries the last real token, columns 1..k the draft, and the
  selected-token row doubles as the per-column verdict
  (``serve/speculative.py``). Zero new compiled shapes; the dispatch
  lands in the step's ``C<rung>`` cost row like any prefill chunk.

  **Batched page-ops** — ``apply_page_ops(arena, copy_src [S],
  copy_dst [S], table_updates [S, P], reset_mask [S])`` coalesces ALL of
  a round's page maintenance into one jitted call: every COW page copy
  (vectors padded with 0 -> 0 null-page self-copies, which are no-ops),
  the device block-table rebuild (broadcast into every group's
  ``block_tbl`` leaf), and the admission SSM/conv state resets (masked
  zeroing). The engine queues copies/resets host-side during admit and
  flushes once before the step — and skips the call entirely on rounds
  where nothing changed (pure decode), so the admit path's serialized
  per-seat device round-trips collapse to at most one per round.
  ``page_copy``/``reset_state`` remain as the single-op forms.

  **Device-resident token carry** — the step's selected-token output is
  a device array with exactly the aval a ``C = 1`` dispatch consumes, so
  a pipelined engine may feed round N's ``tok`` straight back in as
  round N+1's ``tokens`` for pure-decode rounds
  (:func:`carry_decode_tokens`) without a host round-trip; host-uploaded
  tokens remain the path for prefill chunks, verify columns, and
  admission. Dead lanes carry ``DEAD_TOKEN`` and are masked by
  ``n_new = 0``, and the sampling keys fold from absolute positions
  only, so carried and re-uploaded tokens are bitwise interchangeable.

  **Solo-lane fast path** — ``solo_step(params, tokens [1, C], arena,
  slot, start [1], n_new [1])`` runs a round with exactly one live lane
  at batch width 1: the slot's ``block_tbl``/SSM/conv rows are
  dynamic-sliced out of the arena inside the jit, the unified step body
  runs at ``B = 1``, and the recurrent rows are scattered back (page
  leaves are global and pass through). ``slot`` is a traced scalar, so
  one compile per width C serves every slot. This is what keeps a
  prefix-cache leader prefill (one miss in flight, ``max_slots - 1``
  idle lanes) from paying the full batch width in dead compute.
  Single-device engines only; mesh engines keep the batched step.

Sharding contract (what shards, what replicates)
------------------------------------------------
  * **Weights** — ``launch/sharding.py`` rules: TP dims on ``model``,
    the non-TP dim of large dense weights on ``data`` (FSDP-style);
    ShardedQTensor stream stacks shard their leading TP-shard dim on
    ``model`` and run TP-local through ``qmm_shard_map`` (the QMC
    serving format's quantize-after-shard contract).
  * **Paged KV arena** — the ``n_pages`` axis shards over ``data`` (each
    data shard owns a horizontal slice of the page pool), the fused
    ``kv_dim`` (and int8 scale head dim) over ``model``. See
    ``launch.sharding.paged_cache_spec``.
  * **Block tables** — replicated: any shard must resolve any logical
    position to a (possibly remote) page; GSPMD routes the cross-shard
    gather/scatter that results.
  * **Step batch** — tokens/positions/logits shard batch over the dp
    axes when the slot count divides (prefill chunks ride the same
    batched step, so they shard with it).
  * **SSM/conv state** — dense per-slot, batch on dp when divisible.

Arena buffers are donated on every backend (the step, state reset, page
copy and fused page ops all rewrite the arena in place): the arena passed
in is dead after the call, and callers keep only the returned one.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import runtime_context as ctx
from repro.obs import costs as obs_costs
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.launch import mesh as meshlib
from repro.launch import sharding as shd
from repro.models import kvcache as KV
from repro.models.config import ModelConfig
from repro.models.model import decode_step as _decode
from repro.models.model import forward as _forward
from repro.models.model import prefill as _prefill
from repro.serve import sampling


# ==========================================================================
# contiguous builders (dry-run cells + legacy engine)
# ==========================================================================
def cache_struct(cfg: ModelConfig, batch: int, max_len: int,
                 dtype=jnp.bfloat16):
    if cfg.is_encdec:
        return {"b0": jax.eval_shape(
            lambda: KV.init_encdec_cache(cfg, batch, max_len, dtype))}
    return jax.eval_shape(lambda: KV.init_cache(cfg, batch, max_len, dtype))


def build_prefill(cfg: ModelConfig, mesh, *, batch: int, seq: int,
                  cache_len: Optional[int] = None, params_struct=None,
                  scan_layers: bool = True):
    """Returns (fn, jit_fn). fn(params, tokens, extras...) ->

    (last_logits, cache)."""
    cache_len = cache_len or seq + cfg.n_vis_tokens

    def fn(params, tokens, extras):
        with ctx.use_mesh(mesh, meshlib.dp_axes(mesh)):
            return _prefill(cfg, params, tokens, max_len=cache_len,
                            vis_embeds=extras.get("vis_embeds"),
                            frames=extras.get("frames"),
                            scan_layers=scan_layers)

    def make_jit(params_struct, extras_struct=None):
        p_sh = shd.shard_params_tree(params_struct, mesh)
        t_sh = NamedSharding(mesh, shd.batch_spec(mesh, batch))
        c_struct = cache_struct(cfg, batch, cache_len)
        c_sh = shd.shard_cache_tree(c_struct, mesh, batch)
        l_sh = _logits2d(mesh, batch, cfg)
        e_sh = jax.tree_util.tree_map(
            lambda _: NamedSharding(mesh, shd.batch_spec(mesh, batch)),
            extras_struct or {})
        return jax.jit(fn, in_shardings=(p_sh, t_sh, e_sh),
                       out_shardings=(l_sh, c_sh))
    return fn, make_jit


def build_decode(cfg: ModelConfig, mesh, *, batch: int, cache_len: int,
                 scan_layers: bool = True):
    """Returns (fn, make_jit). fn(params, token, cache, pos) ->

    (logits, cache). Cache is donated (in-place update)."""
    def fn(params, token, cache, pos):
        with ctx.use_mesh(mesh, meshlib.dp_axes(mesh)):
            return _decode(cfg, params, token, cache, pos,
                           scan_layers=scan_layers)

    def make_jit(params_struct):
        p_sh = shd.shard_params_tree(params_struct, mesh)
        t_sh = NamedSharding(mesh, shd.batch_spec(mesh, batch))
        c_struct = cache_struct(cfg, batch, cache_len)
        c_sh = shd.shard_cache_tree(c_struct, mesh, batch)
        l_sh = _logits2d(mesh, batch, cfg)
        pos_sh = NamedSharding(mesh, P())
        return jax.jit(fn,
                       in_shardings=(p_sh, t_sh, c_sh, pos_sh),
                       out_shardings=(l_sh, c_sh),
                       donate_argnums=(2,))
    return fn, make_jit


def _logits2d(mesh, batch: int, cfg) -> NamedSharding:
    """[B, V] sharding: batch on dp when divisible; vocab on model when

    divisible (odd vocabs like 92553 replicate)."""
    bs = shd.batch_spec(mesh, batch)
    b_ax = None
    if len(bs) >= 1:
        b_ax = bs[0] if len(bs) > 0 else None
    tp_n = meshlib.axis_size(mesh, "model")
    v_ax = "model" if ("model" in mesh.axis_names
                       and cfg.vocab % tp_n == 0) else None
    return NamedSharding(mesh, P(b_ax, v_ax))


@functools.lru_cache(maxsize=None)
def contiguous_decode(cfg: ModelConfig) -> Callable:
    """Single-device contiguous decode step (the legacy per-slot engine's

    executable): one jit per ModelConfig."""
    return jax.jit(lambda p, t, c, pos: _decode(cfg, p, t, c, pos))


# ==========================================================================
# jit observability: compile / retrace counters around every serving jit
# ==========================================================================
class TracedJit:
    """Callable wrapper over a serving jit that detects (re)compiles.

    Every call snapshots the underlying jit's executable-cache size
    (``_cache_size``); growth across a call means that call traced a new
    shape — the call's wall time is attributed to compile, a
    ``jit/compile`` instant fires on the process tracer, and
    ``serve_jit_compiles_total{fn}`` increments on the process registry.

    ``expected_shapes`` declares this wrapper's compile surface — the
    number of distinct shapes ONE engine should ever drive through it
    (the unified step compiles C ∈ {1, chunk}, so 2). Compiles beyond it
    raise ``serve_jit_retraces_unexpected_total{fn}`` and a
    ``jit/unexpected_retrace`` instant: the late-flag-flip / geometry-
    drift bug class becomes a visible metric instead of a silent 10x
    round stall. Counters are per wrapper (one per
    :func:`build_paged_steps` call), so engines sharing an lru-cached
    warm jit correctly count zero compiles of their own.

    With cost capture on (``obs.costs.enable_capture``) the wrapper also
    keeps per-call-shape tables for the attribution layer: the first
    call of each shape AOT-lowers it (BEFORE execution — donated buffers
    are still live) and records ``cost_analysis()`` FLOPs/bytes in
    ``cost_by_key``; every call then lands in ``calls_by_key`` /
    ``seconds_by_key``, measured synchronously (``block_until_ready``
    inside the timed window, so the table holds device time rather than
    async dispatch time), and emits a cumulative ``cost/<fn>`` Perfetto
    counter track. ``cost_key(args, kw) -> str`` names the shape (the
    unified step keys on its token width C); default one key, "call".
    Capture keys on shapes this WRAPPER has seen, not on jit-cache
    growth, so fresh engines over an lru-warm jit still capture.
    Capture off — the default — costs one module-bool branch per call.
    """

    def __init__(self, name: str, fn: Callable,
                 expected_shapes: Optional[int] = None,
                 cost_key: Optional[Callable] = None):
        self.name = name
        self._fn = fn
        self.expected_shapes = expected_shapes
        self.calls = 0
        self.compiles = 0
        self.compile_seconds = 0.0
        self._cost_key = cost_key
        self.cost_by_key: dict = {}      # key -> {"flops", "bytes"}/call
        self.calls_by_key: dict = {}
        self.seconds_by_key: dict = {}
        self._cum_flops = 0.0
        self._cum_bytes = 0.0

    def _cache_size(self) -> Optional[int]:
        try:
            return self._fn._cache_size()
        except Exception:
            return None        # non-jit callable or a jax without the API

    def __call__(self, *args, **kw):
        capture = obs_costs.capture_enabled()
        if capture:
            try:
                key = self._cost_key(args, kw) if self._cost_key \
                    else "call"
            except Exception:
                key = "call"
            if key not in self.cost_by_key:
                self.cost_by_key[key] = obs_costs.capture_costs(
                    self._fn, args, kw)
        before = self._cache_size()
        t0 = time.perf_counter()
        out = self._fn(*args, **kw)
        if capture:
            try:
                jax.block_until_ready(out)
            except Exception:
                pass           # non-array outputs: wall stays dispatch time
        dt = time.perf_counter() - t0
        self.calls += 1
        if capture:
            self.calls_by_key[key] = self.calls_by_key.get(key, 0) + 1
            self.seconds_by_key[key] = \
                self.seconds_by_key.get(key, 0.0) + dt
            cost = self.cost_by_key[key]
            self._cum_flops += cost["flops"]
            self._cum_bytes += cost["bytes"]
            if self._cum_flops or self._cum_bytes:
                obs_trace.get_tracer().counter(
                    f"cost/{self.name}", flops=self._cum_flops,
                    bytes=self._cum_bytes)
        after = self._cache_size()
        if before is not None and after is not None and after > before:
            grew = after - before
            self.compiles += grew
            self.compile_seconds += dt
            trc = obs_trace.get_tracer()
            trc.instant("jit/compile", fn=self.name, cache_size=after,
                        seconds=dt)
            reg = obs_metrics.get_registry()
            reg.counter(
                "serve_jit_compiles_total",
                "serving-jit executable-cache growth events",
                labels=("fn",)).inc(grew, fn=self.name)
            if self.expected_shapes is not None \
                    and self.compiles > self.expected_shapes:
                over = min(grew,
                           self.compiles - self.expected_shapes)
                trc.instant("jit/unexpected_retrace", fn=self.name,
                            compiles=self.compiles,
                            expected=self.expected_shapes)
                reg.counter(
                    "serve_jit_retraces_unexpected_total",
                    "compiles beyond a step's declared compile surface",
                    labels=("fn",)).inc(over, fn=self.name)
        return out


def _step_cost_key(args, kw) -> str:
    """Call-shape key for the unified step's cost tables: its token
    width C (``tokens`` is positional arg 1) — the engine drives C = 1
    plus ``width_ladder`` rungs (prefill chunks AND speculative verify
    steps alike), so the attribution table gets one row per width."""
    return f"C{args[1].shape[1]}"


def carry_decode_tokens(prev_tok, slot=None):
    """Device-resident token carry for pipelined pure-decode rounds.

    ``prev_tok`` is the previous step's on-device selected-token output
    (``[B, 1]`` int32 from the batched step, ``[1, 1]`` from
    ``solo_step``); the returned array feeds the NEXT dispatch's
    ``tokens`` argument directly, so steady-state decode tokens never
    round-trip through host. ``slot=None`` keeps the full batch (the
    batched step reads its own lane rows; dead lanes carry
    ``sampling.DEAD_TOKEN`` and are masked by ``n_new = 0``).
    Passing ``slot`` slices lane ``slot``'s row out for a ``solo_step``
    dispatch — a no-op when the previous round was itself solo (same
    single live lane, the ``[1, 1]`` output passes straight through;
    the engine drains the pipeline on any lane-set change, so the solo
    lane's identity is stable while carried). Either way the result has
    the same aval as the host-uploaded tokens of the matching width, so
    the carry never adds a compiled shape."""
    if slot is None or prev_tok.shape[0] == 1:
        return prev_tok
    return jax.lax.dynamic_slice_in_dim(prev_tok, int(slot), 1, axis=0)


# ==========================================================================
# paged serving step set (ServeEngine + launch/serve.py)
# ==========================================================================
@dataclasses.dataclass(frozen=True)
class PagedServeSteps:
    """The jitted step functions one paged engine instance runs, plus the

    geometry they were built for (the engine validates compatibility).

      step(params, tokens [B,C], arena, start [B], n_new [B], sampling)
          -> (tok [B,C], logp [B,C], arena)
          (compiles once per C in {1} + width_ladder(chunk); token
          selection is fused — raw logits never leave the jit)
      page_copy(arena, src, dst) -> arena
      reset_state(arena, slot) -> arena    (None for attention-only cfgs)
      apply_page_ops(arena, copy_src [S], copy_dst [S],
                     table_updates [S,P], reset_mask [S]) -> arena
          (one fused call per round: COW copies + table rebuild + resets)
      solo_step(params, tokens [1,C], arena, slot, start [1], n_new [1],
                sampling) -> (tok [1,C], logp [1,C], arena)
          (single-live-lane rounds at B=1; None under a mesh — compiles
          once per C, slot is traced)
    """
    cfg: ModelConfig
    mesh: Optional[object]
    page: int
    n_pages: int                     # usable pages (arena holds +1 null)
    max_slots: int
    max_pages_per_seq: int
    cache_dtype: object
    chunk: int                       # prefill chunk width (the C > 1 shape)
    step: Callable
    page_copy: Callable
    reset_state: Optional[Callable] = None
    apply_page_ops: Optional[Callable] = None
    solo_step: Optional[Callable] = None
    paged_attention: bool = False    # attention via the ragged Pallas kernel

    def compatible_with(self, *, page, n_pages, max_slots,
                        max_pages_per_seq, cache_dtype, chunk,
                        paged_attention=False) -> bool:
        return (self.page == page and self.n_pages == n_pages
                and self.max_slots == max_slots
                and self.max_pages_per_seq == max_pages_per_seq
                and self.cache_dtype == cache_dtype
                and self.chunk == chunk
                and self.paged_attention == paged_attention)

    def jit_counters(self) -> Tuple[int, int, float]:
        """Aggregate (calls, compiles, compile_seconds) over this step
        set's :class:`TracedJit` members — the engine diffs these around
        a run to attribute compile time in ``EngineStats``."""
        calls = compiles = 0
        seconds = 0.0
        for fn in (self.step, self.page_copy, self.reset_state,
                   self.apply_page_ops, self.solo_step):
            if isinstance(fn, TracedJit):
                calls += fn.calls
                compiles += fn.compiles
                seconds += fn.compile_seconds
        return calls, compiles, seconds


def width_ladder(chunk: int) -> tuple:
    """Compiled ``C > 1`` step widths: pow2 rungs from 4 up to ``chunk``.

    A short prefill chunk — a cached-prefix suffix, a prompt tail —
    runs at the smallest rung that covers it instead of the full chunk:
    device time scales with the padded width, so the prefix cache's
    saved tokens only turn into saved wall clock if the step width
    shrinks with them. The 4 rung exists for short speculative verify
    steps (``1 + k`` columns at k < 7 used to pad all the way to 8);
    the rung floor and pow2 spacing bound the compile surface to
    log2(chunk/4) + 2 shapes per engine geometry (lru-shared across
    engines), so this stays a ladder, not a zoo."""
    if chunk <= 1:
        return ()
    w, out = 4, []
    while w < chunk:
        out.append(w)
        w *= 2
    out.append(chunk)
    return tuple(out)


def default_chunk(max_pages_per_seq: int, page: int) -> int:
    """Default prefill chunk width: the pow2 that covers the longest
    admissible sequence, so every prompt is a single chunk ("monolithic"
    prefill through the same ragged path). THE one copy of this rule —
    the builder, ``ServeEngine`` and ``launch/serve.py`` must agree or
    ``compatible_with`` rejects the step set."""
    from repro.serve.scheduler import bucket_len
    return bucket_len(max_pages_per_seq * page, page)


def default_n_pages(slots: int, max_pages_per_seq: int, mesh=None) -> int:
    """Default pool size: every slot at full length — rounded UP so the

    arena's total page count (usable + the null page) divides the mesh's
    ``data`` axis; otherwise ``paged_cache_spec`` would silently
    replicate the page axis and the sharded arena no-ops."""
    n = slots * max_pages_per_seq
    d = meshlib.axis_size(mesh, "data") if mesh is not None else 1
    if d > 1:
        n += (-(n + 1)) % d
    return n


def arena_struct(cfg: ModelConfig, *, n_pages: int, page: int,
                 max_slots: int, max_pages_per_seq: int,
                 cache_dtype=jnp.float32):
    """Abstract arena pytree (``n_pages`` usable pages + the null page)."""
    return jax.eval_shape(
        lambda: KV.paged_init_cache(cfg, n_pages + 1, page, max_slots,
                                    max_pages_per_seq, cache_dtype))


def build_paged_steps(cfg: ModelConfig, mesh=None, params_struct=None, *,
                      page: int, n_pages: int, max_slots: int,
                      max_pages_per_seq: int,
                      cache_dtype=jnp.float32,
                      chunk: Optional[int] = None,
                      paged_attention: bool = False) -> PagedServeSteps:
    """Build the full paged serving step set for one engine geometry.

    ``chunk`` is the prefill chunk width (the ``C > 1`` step shape); the
    default — the pow2 that covers a full-length sequence — makes every
    prompt a single chunk ("monolithic" prefill through the same ragged
    path), matching ``ServeEngine``'s default.

    ``mesh=None`` → plain single-device jit, lru-shared per FULL geometry
    (every keyword above is part of the cache key). With a mesh, every
    step runs under the runtime mesh context (so ShardedQTensor weights
    dispatch to ``qmm_shard_map`` and the paged gather/scatter picks up
    its sharding constraints) and carries explicit input/output shardings
    per the module-level contract; ``params_struct`` (a pytree of
    ShapeDtypeStructs matching the serving weights) is then required.

    ``paged_attention=True`` runs the step's attention through the ragged
    Pallas page-table kernel (``kernels/paged_attention.py``): only
    causally-live pages stream per lane, for decode tokens and prefill
    chunks alike. Under a mesh the kernel runs shard-local (pages over
    ``data``, KV heads over ``model``, flash-decoding softmax merge) —
    the arena geometry must divide the mesh (``shard_compatible``), which
    ``default_n_pages`` guarantees for the page axis; unsupported
    geometries fall back to the XLA gather inside the traced step.
    """
    if chunk is None:
        chunk = default_chunk(max_pages_per_seq, page)
    # one engine drives the decode width (C = 1) plus the pow2 prefill
    # width ladder (``width_ladder``) and a single shape through
    # page_copy/reset — that is each wrapper's declared compile surface
    step_shapes = len(width_ladder(chunk)) + 1
    if mesh is None:
        step, page_copy, reset, apply_ops, solo = _single_device_steps(
            cfg, page, n_pages, max_slots, max_pages_per_seq,
            cache_dtype, chunk, paged_attention)
        return PagedServeSteps(
            cfg=cfg, mesh=None, page=page, n_pages=n_pages,
            max_slots=max_slots, max_pages_per_seq=max_pages_per_seq,
            cache_dtype=cache_dtype, chunk=chunk,
            paged_attention=paged_attention,
            step=TracedJit("step", step, step_shapes,
                           cost_key=_step_cost_key),
            page_copy=TracedJit("page_copy", page_copy, 1),
            reset_state=(None if reset is None
                         else TracedJit("reset_state", reset, 1)),
            apply_page_ops=TracedJit("apply_page_ops", apply_ops, 1),
            solo_step=TracedJit("solo_step", solo, step_shapes,
                                cost_key=_step_cost_key))

    if params_struct is None:
        raise ValueError("sharded step builders need params_struct to "
                         "emit explicit input shardings")
    dp = meshlib.dp_axes(mesh)
    a_struct = arena_struct(cfg, n_pages=n_pages, page=page,
                            max_slots=max_slots,
                            max_pages_per_seq=max_pages_per_seq,
                            cache_dtype=cache_dtype)
    p_sh = shd.shard_params_tree(params_struct, mesh)
    a_sh = shd.shard_paged_cache_tree(a_struct, mesh)
    rep = NamedSharding(mesh, P())
    b_sh = NamedSharding(mesh, shd.batch_spec(mesh, max_slots))
    tok_sh = NamedSharding(mesh, P(*(tuple(shd.batch_spec(mesh, max_slots))
                                     + (None,))))
    # traced sampling lane params: [B] knobs shard with the batch, the
    # [B, 2] raw key rides the token spec; [B, C] outputs likewise
    samp_sh = {"temp": b_sh, "top_k": b_sh, "top_p": b_sh, "key": tok_sh}
    step_body = _step_body(cfg, paged_attention)

    def step_fn(params, tokens, arena, start, n_new, samp):
        with ctx.use_mesh(mesh, dp):
            return step_body(params, tokens, arena, start, n_new, samp)

    reset = None
    if any(k == "mamba" or k.startswith("hybrid") for k in cfg.pattern):
        reset = TracedJit(
            "reset_state",
            jax.jit(_reset_state_body(cfg),
                    in_shardings=(a_sh, rep), out_shardings=a_sh,
                    donate_argnums=0), 1)
    return PagedServeSteps(
        cfg=cfg, mesh=mesh, page=page, n_pages=n_pages,
        max_slots=max_slots, max_pages_per_seq=max_pages_per_seq,
        cache_dtype=cache_dtype, chunk=chunk,
        paged_attention=paged_attention,
        step=TracedJit(
            "step",
            jax.jit(step_fn,
                    in_shardings=(p_sh, tok_sh, a_sh, b_sh, b_sh, samp_sh),
                    out_shardings=(tok_sh, tok_sh, a_sh),
                    donate_argnums=2), step_shapes,
            cost_key=_step_cost_key),
        page_copy=TracedJit(
            "page_copy",
            jax.jit(_page_copy_body(cfg),
                    in_shardings=(a_sh, rep, rep),
                    out_shardings=a_sh, donate_argnums=0), 1),
        reset_state=reset,
        apply_page_ops=TracedJit(
            "apply_page_ops",
            jax.jit(_apply_page_ops_body(cfg),
                    in_shardings=(a_sh, rep, rep, rep, rep),
                    out_shardings=a_sh, donate_argnums=0), 1),
        solo_step=None)


# --------------------------------------------------------------------------
# step bodies (shared by the mesh-less lru-cached jits and the sharded
# builders above)
# --------------------------------------------------------------------------
def _step_body(cfg: ModelConfig, paged_attention: bool):
    """The ONE serving step: ragged chunked prefill + batched decode.

    ``tokens [B, C]`` are lane-local new tokens; lane ``b`` runs its
    first ``n_new[b]`` columns at absolute positions ``start[b] + t``.
    ``valid_len = start + n_new`` masks reads past each lane's bound,
    routes right-padding K/V writes to the null page, and (converted to
    a relative count inside ``blocks.apply_block``) keeps recurrent SSM
    state clean for idle and padded lanes. The fused
    ``sampling.select_tokens`` epilogue turns the logits into selected
    token ids + logprobs before anything leaves the jit."""

    def step(params, tokens, arena, start, n_new, samp):
        c = tokens.shape[1]
        positions = start[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
        valid = start + n_new
        logits, new_arena, _ = _forward(cfg, params, tokens,
                                        positions=positions, cache=arena,
                                        valid_len=valid,
                                        paged_attention=paged_attention)
        tok, logp = sampling.select_tokens(
            logits, samp["temp"], samp["top_k"], samp["top_p"],
            samp["key"], positions, n_new)
        return tok, logp, new_arena

    return step


def _page_copy_body(cfg: ModelConfig):
    """(arena, src, dst) -> arena with page dst a copy of page src in

    every attention leaf of every group — the device half of
    ``PagedKVPool.cow`` (the host half swaps the block-table entry)."""

    def _copy(arena, src, dst):
        out = {}
        for i, kind in enumerate(cfg.pattern):
            key = f"b{i}"
            grp = dict(arena[key])
            if "attn" in grp:
                attn = dict(grp["attn"])
                for name, leaf in attn.items():
                    if name.endswith("_pages"):
                        attn[name] = leaf.at[:, dst].set(leaf[:, src])
                grp["attn"] = attn
            out[key] = grp
        return out

    return _copy


def _apply_page_ops_body(cfg: ModelConfig):
    """(arena, copy_src [S], copy_dst [S], table_updates [S, P],
    reset_mask [S]) -> arena: one round's page maintenance, fused.

    Copy vectors are padded with 0 -> 0 null-page self-copies (real COW
    destinations are freshly allocated and distinct, so duplicate-index
    scatter writes only ever collide on the identity no-op).
    ``table_updates`` is the host block table, broadcast into every
    group's ``block_tbl`` leaf; ``reset_mask`` zeroes freshly admitted
    slots' dense SSM/conv rows."""

    def _apply(arena, copy_src, copy_dst, tables, reset_mask):
        out = {}
        for i, kind in enumerate(cfg.pattern):
            key = f"b{i}"
            grp = dict(arena[key])
            if "attn" in grp:
                attn = dict(grp["attn"])
                for name, leaf in attn.items():
                    if name.endswith("_pages"):
                        attn[name] = leaf.at[:, copy_dst].set(
                            leaf[:, copy_src])
                g = attn["block_tbl"].shape[0]
                attn["block_tbl"] = jnp.broadcast_to(
                    tables.astype(jnp.int32)[None],
                    (g,) + tables.shape)
                grp["attn"] = attn
            if "mamba" in grp:
                mm = dict(grp["mamba"])
                for name in ("ssm", "conv"):
                    leaf = mm[name]
                    mask = reset_mask.reshape(
                        (1, -1) + (1,) * (leaf.ndim - 2))
                    mm[name] = jnp.where(mask, jnp.zeros((), leaf.dtype),
                                         leaf)
                grp["mamba"] = mm
            out[key] = grp
        return out

    return _apply


def _solo_step_body(cfg: ModelConfig, paged_attention: bool):
    """Single-live-lane round at batch width 1 (see module docstring).

    The slot's per-slot rows (``block_tbl``, SSM, conv) are dynamic-
    sliced into a B=1 view, the unified step body runs on the view, and
    the recurrent rows scatter back; page leaves are global, so the
    step's K/V writes land in the real arena pages directly. The block
    table is read-only inside the step, so the full-width original is
    kept on the way out."""
    step = _step_body(cfg, paged_attention)

    def solo(params, tokens, arena, slot, start, n_new, samp):
        view = {}
        for i, kind in enumerate(cfg.pattern):
            key = f"b{i}"
            grp = dict(arena[key])
            if "attn" in grp:
                attn = dict(grp["attn"])
                attn["block_tbl"] = jax.lax.dynamic_slice_in_dim(
                    attn["block_tbl"], slot, 1, axis=1)
                grp["attn"] = attn
            if "mamba" in grp:
                mm = dict(grp["mamba"])
                mm["ssm"] = jax.lax.dynamic_slice_in_dim(
                    mm["ssm"], slot, 1, axis=1)
                mm["conv"] = jax.lax.dynamic_slice_in_dim(
                    mm["conv"], slot, 1, axis=1)
                grp["mamba"] = mm
            view[key] = grp
        tok, logp, stepped = step(params, tokens, view, start, n_new, samp)
        out = {}
        for i, kind in enumerate(cfg.pattern):
            key = f"b{i}"
            grp = dict(arena[key])
            sg = stepped[key]
            if "attn" in grp:
                attn = dict(grp["attn"])
                for name, leaf in sg["attn"].items():
                    if name.endswith("_pages"):
                        attn[name] = leaf
                grp["attn"] = attn
            if "mamba" in grp:
                mm = dict(grp["mamba"])
                mm["ssm"] = jax.lax.dynamic_update_slice_in_dim(
                    mm["ssm"], sg["mamba"]["ssm"], slot, axis=1)
                mm["conv"] = jax.lax.dynamic_update_slice_in_dim(
                    mm["conv"], sg["mamba"]["conv"], slot, axis=1)
                grp["mamba"] = mm
            out[key] = grp
        return tok, logp, out

    return solo


def _reset_state_body(cfg: ModelConfig):
    """(arena, slot) -> arena with the slot's dense SSM/conv rows zeroed.

    A freshly admitted slot's recurrent state must start from zero — the
    chunked prefill accumulates it in place (there is no per-admission
    contiguous cache to adopt from any more), and the row may hold a
    previous occupant's garbage."""

    def _reset(arena, slot):
        out = {}
        for i, kind in enumerate(cfg.pattern):
            key = f"b{i}"
            grp = dict(arena[key])
            if "mamba" in grp:
                mm = dict(grp["mamba"])
                mm["ssm"] = mm["ssm"].at[:, slot].set(0.0)
                mm["conv"] = mm["conv"].at[:, slot].set(0.0)
                grp["mamba"] = mm
            out[key] = grp
        return out

    return _reset


@functools.lru_cache(maxsize=None)
def _single_device_steps(cfg: ModelConfig, page: int, n_pages: int,
                         max_slots: int, max_pages_per_seq: int,
                         cache_dtype, chunk: int, paged_attention: bool):
    """Single-device jits, cached on the FULL step geometry.

    The key is exactly the tuple ``PagedServeSteps.compatible_with``
    checks — a flag (or geometry knob) passed late can never silently
    reuse a jit traced for a different configuration."""
    step = jax.jit(_step_body(cfg, paged_attention), donate_argnums=2)
    page_copy = jax.jit(_page_copy_body(cfg), donate_argnums=0)
    apply_ops = jax.jit(_apply_page_ops_body(cfg), donate_argnums=0)
    solo = jax.jit(_solo_step_body(cfg, paged_attention), donate_argnums=2)
    reset = None
    if any(k == "mamba" or k.startswith("hybrid") for k in cfg.pattern):
        reset = jax.jit(_reset_state_body(cfg), donate_argnums=0)
    return step, page_copy, reset, apply_ops, solo
