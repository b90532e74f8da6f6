"""Serving engines: paged continuous batching (default) + legacy per-slot.

``ServeEngine`` is the paged engine: every round, all active slots run in
ONE ``jax.jit`` step against a shared paged KV arena (``serve/paged_kv.py``)
— decode lanes carry one token each, prefilling lanes carry a chunk of
their prompt, and both co-schedule in the same ragged step
(``serve/steps.py``), with FIFO admission, a per-round chunk budget and
recompute-style preemption (``serve/scheduler.py``). Weights may be dense
fp or the QMC serving format (ShardedQTensor / QTensor stacks) — matmul
dispatch handles either, so the paper's eMEM-resident weights and the
LPDDR5-resident paged KV stream meet in the same step function.

``LegacyServeEngine`` keeps the original loop — N sequential batch-1 decode
calls over per-slot contiguous caches — as the parity/throughput baseline
for ``benchmarks/serving.py``.

Under greedy decoding both engines are token-identical: chunked prefill
scatters the same K/V values a one-shot contiguous prefill computes (int8
caches share one quantizer, ``models.kvcache.quantize_kv``), causal
attention makes each query's output independent of how the prompt was
chunked, and masked pages contribute exp(-1e30) = 0 to the softmax.

Token selection is FUSED into the jitted step (``serve/sampling.py``):
the paged engine's rounds move ``[B, C]`` selected token ids (+ per-token
logprobs) across the jit boundary, never the raw ``[B, C, V]`` logits.
Per-request :class:`~repro.serve.sampling.SamplingParams` ride on
``Request.sampling`` (engine-wide default via the ``sampling=`` ctor
arg); greedy — the default — takes the bitwise argmax oracle path.
``speculative_k > 0`` adds self-speculative greedy decode: a prompt-
lookup draft proposes up to k tokens per decode lane and ONE verify call
on an already-compiled ``width_ladder`` rung accepts a prefix
(``serve/speculative.py``); rejected positions roll back through
``PagedKVPool.trim`` and the fused page-op queue.

The pipelined round loop (``pipelined=True``)
---------------------------------------------
Steady-state decode runs as a device-resident loop: a pure-decode round
with an idle admission queue dispatches its step WITHOUT blocking and is
retired one round later, overlapped with the next round's host planning
and dispatch. The contract:

  * **What overlaps.** Only pure-decode rounds (no prefill chunks, no
    speculative verify, no pending admissions). The next round's input
    tokens are the previous step's on-device output, fed straight back
    in (``steps.carry_decode_tokens``) — decode tokens never round-trip
    through host. Readback starts asynchronously at dispatch
    (``copy_to_host_async``); emission, EOS checks and
    scheduling run at retire, one round behind the in-flight dispatch.
  * **What barriers.** Admission, preemption, prefill grants, and any
    allocation that needs eviction-by-preemption drain the pipeline:
    retire the in-flight round, then run the next round synchronously
    (its fused ``apply_page_ops`` flush therefore dispatches only after
    the drained round's state is final — refcount/COW invariants and
    the one-dispatch-per-round cost-attribution contract survive).
    Plain decode page growth and prefix-cache evictions are NOT
    barriers: their table flush is device-ordered behind the in-flight
    step by the arena data dependency.
  * **EOS lag.** Budget and capacity finishes are predicted at dispatch
    (``FifoScheduler.grant_decode``), so only an EOS landing during the
    one-round lag overruns — by exactly the one in-flight token, which
    is never emitted: its lane's pages are rolled back via the same
    ``PagedKVPool.trim`` used for rejected speculative drafts
    (``EngineStats.lag_trimmed_tokens``), and the lane's slot is freed
    only once the overrun round retires.
  * **Parity.** Sampling keys fold from absolute positions only and
    greedy is a bitwise argmax, so pipelined decode is token-identical
    to the synchronous loop for greedy, sampled and speculative lanes
    (speculative rounds need retired host history to draft, so they
    simply never overlap — and speculative greedy equals plain greedy).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.memsys.workload import chunk_pages_streamed
from repro.obs import costs as obs_costs
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.models.config import ModelConfig
from repro.models.model import prefill
from repro.serve import sampling as samplib
from repro.serve import speculative
from repro.serve import steps as serve_steps
from repro.serve.paged_kv import PagedKVPool, PoolExhausted, pages_for
from repro.serve.prefix_cache import PrefixCache
from repro.serve.sampling import SamplingParams
from repro.serve.scheduler import (FifoScheduler, SchedulerConfig,
                                   bucket_len)


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray               # [S] int32
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # token-selection policy; None -> the engine default (greedy unless
    # the engine was built with sampling=...)
    sampling: Optional[SamplingParams] = None
    # selected-token model logprobs, parallel to out_tokens — filled
    # only when the effective SamplingParams has logprobs=True (cleared
    # with out_tokens on preemption recompute)
    out_logprobs: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class EngineStats:
    prefills: int = 0                # prompts fully prefilled
    prefill_chunks: int = 0          # chunk executions (>= prefills)
    decode_steps: int = 0            # rounds that advanced a decode lane
    tokens_out: int = 0
    wall_s: float = 0.0
    preemptions: int = 0
    pages_peak: int = 0
    tokens_discarded: int = 0        # emitted then erased by preemption
    # per-step K/V gather work (page counts): `live` is what the ragged
    # page-table kernel streams (the bytes the DSE charges); `full` is the
    # block-table width the XLA reference gather reads. Decode lanes land
    # in kv_pages_*; prefill chunks in prefill_kv_pages_* (their stream is
    # per q block — memsys.workload.chunk_pages_streamed — and their
    # writes are page-rounded, the kv_traffic_chunked account)
    kv_pages_live: int = 0
    kv_pages_full: int = 0
    prefill_kv_pages_live: int = 0
    prefill_kv_pages_written: int = 0
    # prefix cache (all zero when caching is off)
    prompt_tokens: int = 0           # prompt tokens across admissions
    prefill_tokens: int = 0          # tokens actually prefilled (suffixes)
    prefill_tokens_padded: int = 0   # same, after chunk-width padding
    cache_hits: int = 0              # admissions served partly from cache
    cache_hit_tokens: int = 0        # prompt tokens adopted (cache+dedup)
    dedup_hits: int = 0              # admissions aliasing an in-flight
    #                                  identical prompt's live slot pages
    cow_copies: int = 0              # shared pages privatized on write
    cache_evictions: int = 0         # cached pages evicted under pressure
    # per jit round: wall seconds and tokens emitted by that round (the
    # emitted count includes tokens a later preemption discards — the jit
    # work was really done; tokens_discarded records how many). First
    # tokens land in the round their prompt's last chunk runs.
    step_seconds: List[float] = dataclasses.field(default_factory=list)
    step_tokens: List[int] = dataclasses.field(default_factory=list)
    # per request (first emission only — a preempted request's recompute
    # does not reset its clock): seconds from run() start to first token
    ttft_s: List[float] = dataclasses.field(default_factory=list)
    # --- observability (obs/) ------------------------------------------
    rounds: int = 0                  # engine rounds that ran a jit step
    # host↔device page-op round trips (the host overhead that once made
    # cached prefill slower than uncached, now fused — see below):
    # adopt_calls/device_tables_rebuilds are fed by PagedKVPool counters
    # (serve/paged_kv.py), page_copy_calls counts the engine's COW
    # page-copy dispatches (the device half of pool.cow)
    adopt_calls: int = 0
    page_copy_calls: int = 0
    device_tables_rebuilds: int = 0
    # batched page-ops (serve/steps.py apply_page_ops): flushes counts
    # fused dispatches, batched counts the individual ops they absorbed
    # (COW copies + state resets + the round's table rebuild) — the
    # difference is host↔device round trips the fusion saved vs the
    # one-dispatch-per-op admit path
    page_op_flushes: int = 0
    page_ops_batched: int = 0
    # rounds run through the B=1 solo-lane step (exactly one live lane)
    solo_rounds: int = 0
    # pipelined round loop (engine built with pipelined=True): rounds
    # whose retire was deferred behind the next dispatch (async
    # readback, device-token carry), drain events (admission /
    # preemption / prefill / alloc-pressure barriers, incl. the final
    # drain), and tokens computed past an EOS that landed during the
    # one-round lag — trimmed via PagedKVPool.trim, never emitted
    pipelined_rounds: int = 0
    pipeline_barriers: int = 0
    lag_trimmed_tokens: int = 0
    # self-speculative decode: rounds that carried a verify lane, draft
    # tokens proposed, and draft tokens the model accepted (the bonus
    # emissions beyond what plain decode would have produced)
    spec_rounds: int = 0
    spec_draft_tokens: int = 0
    spec_accepted_tokens: int = 0
    # serving-jit compiles observed during this run (TracedJit deltas
    # over the step set — nonzero on a warm engine means an unexpected
    # retrace) and the wall seconds those compiling calls took
    jit_compiles: int = 0
    jit_compile_s: float = 0.0
    # cumulative wall seconds per round phase (span names per the
    # obs/trace.py contract: round/admit .. round/emit) — recorded even
    # with tracing disabled, so benchmarks can attribute host vs device
    # vs compile share without parsing a trace file
    phase_seconds: Dict[str, float] = dataclasses.field(
        default_factory=dict)
    # per-request emission timestamps (time.monotonic), keyed by uid —
    # the source of truth for inter-token latency; a preempted request's
    # discarded emissions are dropped with its tokens
    emit_times: Dict[int, List[float]] = dataclasses.field(
        default_factory=dict)

    @property
    def tokens_per_s(self) -> float:
        return self.tokens_out / self.wall_s if self.wall_s else 0.0

    @property
    def spec_acceptance_rate(self) -> float:
        """Fraction of proposed draft tokens the model accepted."""
        return (self.spec_accepted_tokens / self.spec_draft_tokens
                if self.spec_draft_tokens else 0.0)

    @property
    def pipeline_overlap(self) -> float:
        """Fraction of rounds retired through the async pipeline."""
        return self.pipelined_rounds / self.rounds if self.rounds else 0.0

    @property
    def page_op_round_trips_saved(self) -> int:
        """Device dispatches the fused page-op path avoided."""
        return max(0, self.page_ops_batched - self.page_op_flushes)

    @property
    def hit_rate(self) -> float:
        """Fraction of admitted prompt tokens served from cached pages."""
        return (self.cache_hit_tokens / self.prompt_tokens
                if self.prompt_tokens else 0.0)

    @property
    def prefill_token_reduction(self) -> float:
        """1 - (tokens prefilled / tokens a cache-less engine prefills)."""
        return (1.0 - self.prefill_tokens / self.prompt_tokens
                if self.prompt_tokens else 0.0)

    def per_token_latencies(self) -> List[float]:
        return [s / t for s, t in zip(self.step_seconds, self.step_tokens)
                if t]

    def itl_s(self) -> List[float]:
        """Per-request inter-token latencies from emission timestamps.

        Gaps between consecutive emissions of the same request — the
        decode-lane experience — unlike ``per_token_latencies`` which
        averages a whole round over every token it emitted and so lets
        co-scheduled prefill chunks inflate decode ITL."""
        gaps: List[float] = []
        for times in self.emit_times.values():
            gaps.extend(b - a for a, b in zip(times, times[1:]))
        return gaps

    _DEVICE_PHASES = ("round/device_step", "round/dispatch",
                      "round/retire")

    def host_seconds(self) -> float:
        """Wall seconds in host-side round phases — admission, grants,
        host array prep, emission bookkeeping: the planning work only
        the host can do. Excludes every device-coupled span: the
        synchronous step, the pipelined retire (readback wait, with the
        emission bookkeeping riding inside it charged to the device
        side as a documented approximation), and the pipelined dispatch
        — nominally a pure async enqueue, but backends that bound their
        in-flight queue (CPU XLA) block the enqueue on the previous
        round's compute, so its wall is device wait too."""
        return sum(v for k, v in self.phase_seconds.items()
                   if k not in self._DEVICE_PHASES)

    def device_seconds(self) -> float:
        """Wall seconds blocked on or waiting for the device: the
        synchronous step phase plus the pipelined dispatch and retire
        spans (enqueue backpressure + readback wait). Includes jit
        compile time on cold geometries — ``jit_compile_s`` bounds that
        part."""
        return sum(self.phase_seconds.get(k, 0.0)
                   for k in self._DEVICE_PHASES)


def _finished(req: Request, pos: int, max_len: int) -> bool:
    """Termination test shared by both engines (applied after each emit):

    budget spent, EOS emitted (including at prefill), or the next decode
    would write past the cache capacity (positions 0..max_len-1 are
    writable, so the cache is full once pos == max_len)."""
    return (len(req.out_tokens) >= req.max_new_tokens
            or (req.eos_id is not None and req.out_tokens
                and req.out_tokens[-1] == req.eos_id)
            or pos >= max_len)


class _PhaseSpan:
    """Times one round phase: accumulates into ``EngineStats.
    phase_seconds``, observes the ``serve_phase_seconds{phase}``
    histogram, and (when tracing is on) records the span on the tracer.
    A plain class CM so the round loop pays two ``perf_counter`` calls
    per phase, nothing more, with tracing disabled."""

    __slots__ = ("name", "tracer", "hist", "stats", "t0")

    def __init__(self, name, tracer, hist, stats):
        self.name, self.tracer, self.hist = name, tracer, hist
        self.stats = stats

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        ph = self.stats.phase_seconds
        ph[self.name] = ph.get(self.name, 0.0) + dt
        self.hist.observe(dt, phase=self.name)
        self.tracer.complete(self.name, self.t0, dt)
        return False


# ==========================================================================
# paged continuous-batching engine (default)
# ==========================================================================
class ServeEngine:
    """Continuous batching over a paged KV pool.

    ``slots`` bounds concurrent sequences; ``max_len`` is each sequence's
    logical capacity (prompt + generated). ``n_pages`` sizes the shared
    pool — the default fits every slot at full length, so preemption only
    occurs when the caller shrinks it (memory-pressure experiments).

    ``chunk_tokens`` is the prefill chunk width: prompts are consumed in
    fixed-size chunks that scatter straight into the arena and co-schedule
    with decode lanes in the same jit step (attention-only stacks; hybrid
    stacks interleave chunk rounds and decode rounds because the SSM
    recurrence cannot mix a 1-token update into a multi-token scan
    bitwise). The default — one chunk covers the longest admissible
    prompt — is "monolithic" prefill through the very same ragged path.
    Each round runs at the smallest width on the compiled ladder that
    covers its widest grant: C = 1 for pure decode, else a pow2 rung
    from ``serve_steps.width_ladder`` — so a cached-prefix suffix or a
    short tail chunk is not padded out to the full chunk. The ladder is
    log2(chunk/4) + 2 shapes, lru-shared across engines.

    ``prefix_cache=True`` keeps finished prompts' full KV pages in a radix
    index (``serve/prefix_cache.py``): admissions whose prompt shares a
    cached page-aligned prefix adopt those pages copy-on-write and prefill
    only the uncached suffix (in chunks, straight against the arena). The
    pool and arena then persist across ``run()`` calls so a shared system
    prompt is paid for once per server, not once per batch. Requires an
    attention-only stack — KV pages cannot snapshot SSM/conv state.

    On attention-only stacks the scheduler also runs **in-flight dedup**
    (a pending-prefill table): identical prompts admitted while an earlier
    copy still occupies a slot alias that slot's full prompt pages instead
    of prefilling them again — no radix index required.

    ``paged_attention=True`` runs every step's attention through the
    ragged Pallas page-table kernel (``kernels/paged_attention.py``):
    each lane streams only its causally-live pages instead of the full
    block-table width — token-identical to the reference gather under
    greedy decoding; ``EngineStats`` records the gather-work gap either
    way.

    ``weight_plan=True`` (default) lowers QMC stream-format weights once
    at engine setup into the backend's execution form
    (``core.serving_quant.build_exec_weights``) so the per-call step
    graph is as lean as the dense engine's; ``self.params`` keeps the
    stream tree for cost attribution. Dense weights are unaffected;
    mesh engines keep TP-local stream compute regardless.

    Per round, all page maintenance (COW copies, admission state resets,
    the device block-table rebuild) is queued host-side and flushed in
    ONE fused ``apply_page_ops`` jit call before the step — pure decode
    rounds with clean tables skip the dispatch entirely — and rounds
    with exactly one live lane run the B=1 ``solo_step`` instead of the
    full-width batch (``EngineStats.solo_rounds``), which is what keeps
    a cache-miss leader prefill from paying ``slots``-wide dead compute.

    ``sampling`` sets the engine-default
    :class:`~repro.serve.sampling.SamplingParams` (greedy when omitted;
    per-request ``Request.sampling`` overrides). ``speculative_k > 0``
    turns on self-speculative greedy decode: each greedy decode lane may
    propose up to k prompt-lookup draft tokens per round
    (``serve/speculative.py``) and verify them in ONE step call on the
    smallest ``width_ladder`` rung covering ``1 + k`` — zero new
    compiled shapes; draft tokens draw on the round's prefill budget
    (``FifoScheduler.grant_verify``); rejected positions return their
    tail pages via ``PagedKVPool.trim``. Attention-only stacks only
    (SSM state cannot roll back), and sampled (``temperature > 0``)
    lanes always decode one token at a time.

    ``pipelined=True`` overlaps host and device work on steady-state
    decode per the module-docstring pipeline contract: pure-decode
    rounds dispatch without blocking, carry the previous step's
    on-device tokens as input, and retire via async readback one round
    later; mutation rounds drain the pipeline first. Token-identical to
    the default synchronous loop on every lane type; the new
    ``round/dispatch``/``round/retire`` spans and ``serve_pipeline_*``
    metrics record the overlap.

    ``mesh`` (a jax Mesh with ``data``/``model`` axes) runs every step
    sharded: the arena's page axis over ``data``, attention heads / TP
    weight dims (including ShardedQTensor stream stacks) over ``model``.
    All step functions come from ``serve/steps.py`` — the same builder
    layer ``launch/serve.py`` uses — either built here or passed in
    prebuilt via ``step_set``.

    ``tracer`` / ``metrics`` plug the engine into the obs subsystem
    (``repro.obs``): every round records phase spans (``round/admit`` /
    ``round/grant`` / ``round/host_prep`` / ``round/device_step`` /
    ``round/emit``) and request lifecycle instants per the
    ``obs/trace.py`` naming contract, plus counters/histograms per the
    ``obs/metrics.py`` contract. Both default to the process-wide
    instances (``obs.trace.get_tracer()`` is disabled until e.g.
    ``launch/serve.py --trace-out`` turns it on, so the default engine
    pays one branch per span site).
    """

    def __init__(self, cfg: ModelConfig, params, *, slots: int = 4,
                 max_len: int = 256, cache_dtype=jnp.float32,
                 page_size: int = 16, n_pages: Optional[int] = None,
                 max_prefill_tokens: Optional[int] = None,
                 chunk_tokens: Optional[int] = None,
                 prefix_cache: bool = False, mesh=None,
                 step_set: Optional[serve_steps.PagedServeSteps] = None,
                 inflight_dedup: Optional[bool] = None,
                 paged_attention: bool = False,
                 weight_plan: bool = True,
                 sampling: Optional[SamplingParams] = None,
                 speculative_k: int = 0,
                 pipelined: bool = False,
                 tracer: Optional[obs_trace.Tracer] = None,
                 metrics: Optional[obs_metrics.Registry] = None):
        if cfg.is_encdec or cfg.n_vis_tokens:
            raise NotImplementedError(
                "paged engine covers decoder-only models; use "
                "LegacyServeEngine for encdec/vlm")
        attn_only = all(k.startswith("attn") for k in cfg.pattern)
        if (prefix_cache or inflight_dedup) and not attn_only:
            raise NotImplementedError(
                "prefix caching / in-flight dedup share attention KV "
                "pages; SSM/conv state is not page-addressable — disable "
                f"them for hybrid/mamba stacks (pattern={cfg.pattern})")
        if speculative_k > 0 and not attn_only:
            raise NotImplementedError(
                "self-speculative decode rolls rejected positions back "
                "via valid_len masking + page trim; SSM/conv state has "
                "no per-position rollback — attention-only stacks only "
                f"(pattern={cfg.pattern})")
        self.cfg = cfg
        self.mesh = mesh
        if mesh is not None:
            from repro.launch import sharding as shd
            params = jax.device_put(params,
                                    shd.shard_params_tree(params, mesh))
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.cache_dtype = cache_dtype
        self.page = page_size
        self.max_pages_per_seq = pages_for(max_len, page_size)
        self.n_pages = n_pages or serve_steps.default_n_pages(
            slots, self.max_pages_per_seq, mesh)
        self.max_prefill_tokens = (max_prefill_tokens
                                   or max(512, bucket_len(max_len,
                                                          page_size)))
        self.chunk = chunk_tokens or serve_steps.default_chunk(
            self.max_pages_per_seq, page_size)
        self._widths = serve_steps.width_ladder(self.chunk)
        self.stats = EngineStats()
        self.paged_attention = paged_attention
        self._tracer = tracer          # None -> process default at run()
        self._metrics = metrics
        # token selection: the engine-wide default policy (per-request
        # Request.sampling overrides), host-side per-lane param tables
        # the step's traced sampling pytree is built from, and the max
        # draft length per verify round (0 = speculative decode off)
        self._default_sp = sampling or samplib.GREEDY
        self._samp = samplib.lane_inputs(slots)
        self._slot_sp: List[SamplingParams] = [samplib.GREEDY] * slots
        self._spec_k = int(speculative_k)
        self._pipelined = bool(pipelined)
        self._dedup = attn_only if inflight_dedup is None \
            else inflight_dedup
        # co-scheduling a 1-token decode into a C-wide step is bitwise
        # for attention (per-query independence) but not for the SSM
        # scan (s==1 takes the O(1) recurrence, s>1 the chunked SSD
        # path) — hybrid stacks run chunk rounds and decode rounds
        # separately instead
        self._co_schedule = attn_only
        if step_set is not None:
            if step_set.cfg != cfg or step_set.mesh != mesh or \
                    not step_set.compatible_with(
                        page=self.page, n_pages=self.n_pages,
                        max_slots=slots,
                        max_pages_per_seq=self.max_pages_per_seq,
                        cache_dtype=cache_dtype, chunk=self.chunk,
                        paged_attention=paged_attention):
                raise ValueError(
                    "step_set was built for a different engine geometry "
                    "(cfg/mesh/page/n_pages/slots/cache_dtype/chunk must "
                    "match)")
        self._steps = step_set
        # serving weight plan (core/serving_quant.build_exec_weights):
        # the stream-format tree stays the source of truth (self.params,
        # cost attribution); the step consumes the one-time execution
        # lowering. Single-device only — mesh engines run TP-local
        # through qmm_shard_map on the streams themselves.
        self._weight_plan = weight_plan and mesh is None
        self._exec_params = None
        if self._weight_plan:
            # build at engine setup, like the jit warm-up: run() walls
            # must measure serving, not the one-time lowering
            from repro.core.serving_quant import build_exec_weights
            self._exec_params = jax.block_until_ready(
                build_exec_weights(self.params))
        # page ops queued by seat() and flushed once per round through
        # the fused apply_page_ops jit (steps without it — a prebuilt
        # legacy step_set — keep the one-dispatch-per-op path)
        self._pending_copies: List = []
        self._pending_resets: List[int] = []
        # pool + arena (+ prefix index) persist across run() calls so
        # cached pages survive between batches, server-style
        self._use_prefix = prefix_cache
        self._pool: Optional[PagedKVPool] = None
        self._arena = None
        self.prefix_cache: Optional[PrefixCache] = None
        # filled by run() when obs.costs capture is enabled: per-step-fn
        # roofline attribution + modeled memsys cost of the last run
        self.last_cost_report: Optional[obs_costs.CostReport] = None

    def _build_steps(self) -> serve_steps.PagedServeSteps:
        p_struct = None
        if self.mesh is not None:
            p_struct = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                self.params)
        return serve_steps.build_paged_steps(
            self.cfg, self.mesh, p_struct, page=self.page,
            n_pages=self.n_pages, max_slots=self.slots,
            max_pages_per_seq=self.max_pages_per_seq,
            cache_dtype=self.cache_dtype, chunk=self.chunk,
            paged_attention=self.paged_attention)

    def _ensure_pool(self) -> PagedKVPool:
        if self._pool is None:
            self._pool = PagedKVPool(
                self.cfg, n_pages=self.n_pages, page=self.page,
                max_slots=self.slots,
                max_pages_per_seq=self.max_pages_per_seq,
                cache_dtype=self.cache_dtype)
            self._arena = self._pool.init_arena()
            if self._steps is None:
                self._steps = self._build_steps()
            if self.mesh is not None:
                from repro.launch import sharding as shd
                self._arena = jax.device_put(
                    self._arena,
                    shd.shard_paged_cache_tree(self._arena, self.mesh))
            if self._use_prefix:
                self.prefix_cache = PrefixCache(self._pool,
                                                tracer=self._tracer)
        return self._pool

    def _step_params(self):
        """Params tree the jitted step consumes: the lazily built weight
        execution plan, or the raw tree when the plan is off / mesh."""
        if not self._weight_plan:
            return self.params
        if self._exec_params is None:
            from repro.core.serving_quant import build_exec_weights
            self._exec_params = build_exec_weights(self.params)
        return self._exec_params

    def _flush_page_ops(self, pool: PagedKVPool):
        """Apply the round's queued page copies / state resets and the
        block-table rebuild in ONE fused jit call; a round with nothing
        queued and clean tables skips the dispatch entirely. Returns the
        arena the step should consume."""
        copies, resets = self._pending_copies, self._pending_resets
        if self._steps.apply_page_ops is None:   # legacy step set
            for cw in copies:
                self._arena = self._steps.page_copy(self._arena, *cw)
            for s in resets:
                self._arena = self._steps.reset_state(self._arena, s)
            copies.clear()
            resets.clear()
            return pool.install_tables(self._arena)
        if not (copies or resets or pool.tables_dirty):
            return self._arena
        pool.check_tables()
        # host buffers rewritten in place after the dispatch (the pool's
        # tables, the reset mask below) go up as copies (jnp.array): on
        # the CPU jnp.asarray maps a 64-byte-aligned host buffer without
        # copying, and the async step could read the rewrite
        tables = jnp.array(pool.block_tables)
        reset_mask = np.zeros(self.slots, bool)
        for s in resets:
            reset_mask[s] = True
        n_ops = len(copies) + len(resets) + 1
        first = True
        while first or copies:      # > slots copies drain in extra calls
            src = np.zeros(self.slots, np.int32)
            dst = np.zeros(self.slots, np.int32)
            batch, copies[:] = copies[:self.slots], copies[self.slots:]
            for i, (a, b) in enumerate(batch):
                src[i], dst[i] = a, b
            self._arena = self._steps.apply_page_ops(
                self._arena, jnp.asarray(src), jnp.asarray(dst),
                tables, jnp.array(reset_mask))
            self.stats.page_op_flushes += 1
            reset_mask[:] = False
            first = False
        pool.tables_rebuilds += 1
        pool.tables_dirty = False
        self.stats.page_ops_batched += n_ops
        resets.clear()
        return self._arena

    def _alloc(self, slot: int, n_tokens: int) -> Optional[List[int]]:
        """pool.ensure with LRU eviction of unpinned cached pages as the
        fallback; None only when eviction cannot help either."""
        while True:
            fresh = self._pool.ensure(slot, n_tokens)
            if fresh is not None:
                return fresh
            if self.prefix_cache is None or not self.prefix_cache.evict(1):
                return None
            self.stats.cache_evictions += 1

    def run(self, requests: List[Request], greedy: bool = True,
            on_token=None) -> List[Request]:
        """Process all requests to completion; returns them with outputs.

        Token selection follows each request's
        :class:`~repro.serve.sampling.SamplingParams` (``Request.
        sampling``; the engine's ``sampling=`` default otherwise, greedy
        out of the box) — the ``greedy`` flag is kept for API
        compatibility and no longer gates anything. With
        ``logprobs=True`` the selected token's model logprob lands in
        ``request.out_logprobs``, parallel to ``out_tokens`` (read
        ``request.out_logprobs[-1]`` inside ``on_token`` to stream it).

        **EOS contract** (greedy, sampled and speculative paths agree):
        a generated ``eos_id`` IS emitted — appended to ``out_tokens``,
        streamed through ``on_token``, counted in ``tokens_out`` — and
        generation stops immediately after; speculative acceptance
        truncates at the first EOS, so no tokens ever follow it.

        ``on_token(slot, token, request)`` — when given — streams every
        emitted token: once in the round a request's last prefill chunk
        produces its first token (slot is -1 if the request finished at
        prefill without ever decoding) and once per accepted token per
        active decode lane after each jitted round (a verify round can
        emit several). A preempted request re-streams from its
        first token when recomputed; consumers that must not see
        duplicates should key on ``request.uid`` and truncate.

        Stats describe this run only (a fresh EngineStats per call); the
        prefix cache and its pages persist across calls."""
        del greedy                     # per-request SamplingParams rule
        self.stats = EngineStats()
        t0 = time.monotonic()
        for r in requests:
            if len(r.prompt) > self.max_len:
                raise ValueError(f"request {r.uid}: prompt length "
                                 f"{len(r.prompt)} > max_len={self.max_len}")
        pool = self._ensure_pool()
        # observability plumbing: explicit tracer/registry or the process
        # defaults (the default tracer is disabled — every span site is
        # then a single branch)
        trc = obs_trace.active(self._tracer)
        reg = self._metrics if self._metrics is not None \
            else obs_metrics.get_registry()
        phase_hist = reg.histogram(
            "serve_phase_seconds", "per-round phase wall time",
            labels=("phase",))

        def phase(name: str) -> _PhaseSpan:
            return _PhaseSpan(name, trc, phase_hist, self.stats)

        # the pool persists across runs: release slot pages a previously
        # aborted run may have left mapped (cached pages survive), and
        # re-base cumulative counters so stats cover this run only
        for s in range(self.slots):
            if pool.slot_pages[s]:
                pool.free_slot(s)
        pool.pages_peak = pool.used_count
        self._pending_copies.clear()   # an aborted run's stale queue
        self._pending_resets.clear()
        cow0 = pool.cow_copies
        adopt0 = pool.adopt_calls
        tbl0 = pool.tables_rebuilds
        _, jitc0, jits0 = self._steps.jit_counters()
        cost0 = obs_costs.snapshot(self._steps) \
            if obs_costs.capture_enabled() else None
        admissions = {"miss": 0, "hit": 0, "dedup": 0}
        cache = self.prefix_cache
        sched = FifoScheduler(SchedulerConfig(
            page=self.page, max_prefill_tokens=self.max_prefill_tokens,
            max_len=self.max_len, chunk=self.chunk), prefix_cache=cache,
            pool=pool if self._dedup else None, tracer=trc)
        for r in requests:
            sched.enqueue(r)

        active: List[Optional[Request]] = [None] * self.slots
        pos = np.zeros(self.slots, np.int64)   # next write position
        next_tok = np.zeros(self.slots, np.int64)
        seen_first: set = set()

        def prefilling(s: int) -> bool:
            return (active[s] is not None
                    and pos[s] < len(active[s].prompt))

        def emit(s: int, tok: int, req: Request,
                 now: Optional[float] = None) -> None:
            # pipelined retires pass the readback-complete timestamp so
            # the one-round lag never skews ttft/ITL; the sync path's
            # per-token clock reads are bit-identical to before
            if now is None:
                now = time.monotonic()
            self.stats.emit_times.setdefault(req.uid, []).append(now)
            if req.uid not in seen_first:
                seen_first.add(req.uid)
                self.stats.ttft_s.append(now - t0)
                trc.instant("req/first_token", uid=req.uid, slot=s)
            if on_token is not None:
                on_token(s, tok, req)

        def publish(req: Request, s: int) -> None:
            """Index the slot's full prompt pages (prefill KV reuse)."""
            if cache is not None:
                n_full = len(req.prompt) // self.page
                if n_full:
                    cache.insert(req.prompt, pool.slot_pages[s][:n_full])

        def finish(s: int, defer_rec=None) -> None:
            req = active[s]
            req.done = True
            active[s] = None
            if defer_rec is not None and s in defer_rec["act_dec"]:
                # EOS during the pipeline lag: the in-flight round
                # already computed (and allocated for) one more token on
                # this lane — keep the slot's pages mapped until that
                # round retires, then trim the overrun and free
                defer_rec["lag_free"].add(s)
            else:
                pool.free_slot(s)
            sched.on_finish(s)
            trc.instant("req/finished", uid=req.uid, slot=s,
                        tokens=len(req.out_tokens))

        def preempt(victim: int) -> None:
            req = active[victim]
            # recompute-style eviction: drop generated state, requeue; a
            # lane preempted mid-prompt has emitted nothing and releases
            # exactly the pages its chunks wrote (plus adopted refs)
            trc.instant("req/preempted", uid=req.uid, slot=victim,
                        discarded=len(req.out_tokens))
            self.stats.tokens_out -= len(req.out_tokens)
            self.stats.tokens_discarded += len(req.out_tokens)
            # discarded emissions must not contribute inter-token gaps
            self.stats.emit_times.pop(req.uid, None)
            req.out_tokens = []
            req.out_logprobs = []
            active[victim] = None
            pool.free_slot(victim)
            sched.on_preempt(victim)
            sched.requeue_front(req)

        def seat(adm, s: int) -> bool:
            """Seat an admission: adopt cached pages, allocate the first
            chunk's pages, COW the shared page a mid-page restart writes
            into, zero recurrent state. No model step runs here — chunks
            are scheduled round by round. False when pages ran out."""
            req = adm.req
            L = len(req.prompt)
            start = adm.suffix_start
            if adm.cached_pages:
                pool.adopt(s, adm.cached_pages)
            if self._alloc(s, min(L, start + self.chunk)) is None:
                pool.free_slot(s)
                return False
            cow = pool.cow(s, start) if adm.cached_pages else None
            while cow is False:
                if cache is None or not cache.evict(1):
                    pool.free_slot(s)
                    return False
                self.stats.cache_evictions += 1
                cow = pool.cow(s, start)
            if cow is not None:
                # queued, not dispatched: the whole round's copies /
                # resets / table rebuild fuse into one apply_page_ops
                # call right before the step (_flush_page_ops)
                self._pending_copies.append(cow)
                self.stats.page_copy_calls += 1
            if self._steps.reset_state is not None:
                self._pending_resets.append(s)
            active[s] = req
            pos[s] = start
            sp = req.sampling if req.sampling is not None \
                else self._default_sp
            self._slot_sp[s] = sp
            samplib.set_lane(self._samp, s, sp, req.uid)
            sched.on_admit(s)
            sched.note_progress(s, start)
            if adm.cached_pages:
                if adm.dedup:
                    self.stats.dedup_hits += 1
                    admissions["dedup"] += 1
                else:
                    self.stats.cache_hits += 1
                    admissions["hit"] += 1
                self.stats.cache_hit_tokens += start
            else:
                admissions["miss"] += 1
                sched.note_prefill(req, s)
                if cache is not None:
                    sched.miss_open(s)
            self.stats.prompt_tokens += L
            trc.instant("req/admitted", uid=req.uid, slot=s,
                        cached_tokens=start, dedup=adm.dedup)
            return True

        def admit() -> None:
            free_slots = [s for s in range(self.slots)
                          if active[s] is None]
            while free_slots:
                capacity = pool.free_count + (cache.evictable_pages()
                                              if cache else 0)
                adm = sched.next_admission(capacity)
                if adm is None:
                    break
                s = free_slots[0]
                ok = seat(adm, s)
                if not ok and adm.cached_pages:
                    # the hit pinned its matched pages, which may be the
                    # very pages the capacity check promised as evictable;
                    # degrade to an uncached admission that can evict them
                    adm.cached_pages, adm.cached_len = [], 0
                    adm.dedup = False
                    ok = seat(adm, s)
                if not ok:          # promised pages vanished; retry later
                    sched.requeue_front(adm.req)
                    break
                free_slots.pop(0)

        # ---- dispatch/retire machinery -----------------------------
        # Every round is dispatched exactly once through dispatch() and
        # emitted exactly once through process_round(); the synchronous
        # path gathers inline, the pipelined path (pipelined=True) keeps
        # one round in flight and retires it overlapped with the next
        # round's host work (module docstring: pipeline contract).

        def process_round(rec, nxt, logp_h, now=None, defer_rec=None):
            """Emission / EOS / scheduling for one completed round (the
            emit half of the round loop). Returns the tokens emitted.
            ``now`` stamps every emission (retires pass the readback-
            complete time); ``defer_rec`` is the round still in flight,
            whose lanes defer their page frees to its own retire."""
            plan, verify = rec["plan"], rec["verify"]
            act_dec, n_new = rec["act_dec"], rec["n_new"]
            c_len = rec["c_len"]
            emitted = 0
            for s in rec["order"]:
                req = active[s]
                if req is None:
                    continue
                if s in plan:
                    n = plan[s]
                    pos[s] += n
                    sched.note_progress(s, int(pos[s]))
                    self.stats.prefill_chunks += 1
                    self.stats.prefill_tokens += n
                    self.stats.prefill_tokens_padded += c_len
                    trc.instant("req/chunk_done", uid=req.uid,
                                slot=s, pos=int(pos[s]))
                    if int(pos[s]) < len(req.prompt):
                        continue        # mid-prompt: more chunks due
                    # last chunk: the logit at the prompt's final
                    # token is the request's first generated token
                    self.stats.prefills += 1
                    publish(req, s)
                    sched.miss_closed(s)
                    tok = int(nxt[s, n - 1])
                    assert tok != samplib.DEAD_TOKEN, \
                        f"emit read a dead lane (slot {s})"
                    req.out_tokens.append(tok)
                    if self._slot_sp[s].logprobs:
                        req.out_logprobs.append(
                            float(logp_h[s, n - 1]))
                    self.stats.tokens_out += 1
                    emitted += 1
                    if _finished(req, len(req.prompt), self.max_len):
                        req.done = True  # e.g. EOS at prefill: never
                        active[s] = None  # enters a decode round
                        pool.free_slot(s)
                        sched.on_finish(s)
                        emit(-1, tok, req, now)
                        trc.instant("req/finished", uid=req.uid,
                                    slot=-1,
                                    tokens=len(req.out_tokens))
                    else:
                        next_tok[s] = tok
                        emit(s, tok, req, now)
                elif s in act_dec:
                    # plain decode is a verify round with an empty
                    # draft: accept_greedy keeps the verified draft
                    # prefix + the model's correction token, and a
                    # draft-less lane accepts exactly its one token
                    n = int(n_new[s])
                    draft = verify.get(s)
                    if draft is not None:
                        n_acc = speculative.accept_greedy(
                            draft, nxt[s, :n])
                        self.stats.spec_draft_tokens += len(draft)
                        self.stats.spec_accepted_tokens += n_acc - 1
                    else:
                        n_acc = 1
                    fin = False
                    for j in range(n_acc):
                        tok = int(nxt[s, j])
                        assert tok != samplib.DEAD_TOKEN, \
                            f"emit read a dead lane (slot {s})"
                        pos[s] += 1
                        next_tok[s] = tok
                        req.out_tokens.append(tok)
                        if self._slot_sp[s].logprobs:
                            req.out_logprobs.append(
                                float(logp_h[s, j]))
                        self.stats.tokens_out += 1
                        emitted += 1
                        emit(s, tok, req, now)
                        if _finished(req, int(pos[s]), self.max_len):
                            # accepted tokens past EOS (or past the
                            # budget) are discarded, per the EOS
                            # contract on run()
                            finish(s, defer_rec)
                            fin = True
                            break
                    if draft is not None and not fin \
                            and n_acc < n:
                        # speculative rollback: tail pages allocated
                        # for rejected draft positions go back to
                        # the pool; their garbage K/V stays masked
                        # by valid_len until real tokens overwrite
                        # those positions
                        pool.trim(s, int(pos[s]))
            self.stats.step_seconds.append(
                (time.monotonic() if now is None else now) - rec["ts"])
            self.stats.step_tokens.append(emitted)
            return emitted

        def gather(rec):
            """Materialize a round's device outputs as full-width host
            arrays (dead lanes carry DEAD_TOKEN); blocks until the
            device — and any async readback — is done."""
            s0 = rec["solo_slot"]
            if s0 is not None:
                nxt = np.full((self.slots, rec["c_len"]),
                              samplib.DEAD_TOKEN, np.int64)
                logp_h = np.zeros((self.slots, rec["c_len"]),
                                  np.float32)
                nxt[s0] = np.asarray(rec["tok_dev"])[0]
                logp_h[s0] = np.asarray(rec["logp_dev"])[0]
            else:
                nxt = np.asarray(rec["tok_dev"])
                logp_h = np.asarray(rec["logp_dev"])
            return nxt, logp_h

        def readback_async(rec):
            # start the D2H copy at dispatch time so the retire's
            # gather finds it complete (or at least in flight)
            for arr in (rec["tok_dev"], rec["logp_dev"]):
                arr.copy_to_host_async()

        def dispatch(toks_in, cache_in, start, n_new, solo_slot=None):
            """The round's ONE async step dispatch — never blocks; the
            sync path gathers inline, the pipelined path one round
            later."""
            # the sampling lanes are copied (jnp.array): set_lane
            # rewrites them in place, see _flush_page_ops
            if solo_slot is not None:
                tok_dev, logp_dev, self._arena = self._steps.solo_step(
                    self._step_params(), toks_in, cache_in,
                    np.int32(solo_slot),
                    jnp.asarray(start[solo_slot:solo_slot + 1]),
                    jnp.asarray(n_new[solo_slot:solo_slot + 1]),
                    {k: jnp.array(v[solo_slot:solo_slot + 1])
                     for k, v in self._samp.items()})
                self.stats.solo_rounds += 1
            else:
                tok_dev, logp_dev, self._arena = self._steps.step(
                    self._step_params(), toks_in, cache_in,
                    jnp.asarray(start), jnp.asarray(n_new),
                    {k: jnp.array(v) for k, v in self._samp.items()})
            return tok_dev, logp_dev

        def retire(rec, defer_rec=None):
            """Readback-complete + emission for a pipelined round. The
            lag-freed lanes (EOS during the lag) trim their overrun
            token's pages and release their slot HERE — only after the
            round that computed past the EOS has fully retired."""
            with phase("round/retire"):
                nxt, logp_h = gather(rec)
                now = time.monotonic()
                emitted = process_round(rec, nxt, logp_h, now=now,
                                        defer_rec=defer_rec)
                for s in sorted(rec["lag_free"]):
                    self.stats.lag_trimmed_tokens += int(rec["n_new"][s])
                    pool.trim(s, int(pos[s]))
                    pool.free_slot(s)
            return emitted

        inflight = None            # the dispatched-but-unretired round

        while any(a is not None for a in active) or sched.pending:
            r_t0 = time.perf_counter()
            if inflight is not None:
                # ---- pipelined fast path: grant pure-decode lanes
                # against the in-flight round's predicted state, carry
                # its on-device tokens into the next dispatch, THEN
                # retire it (emission overlaps the device step) ------
                dec: List[int] = []
                order = []
                barrier = sched.pending and any(a is None
                                                for a in active)
                if not barrier:
                    with phase("round/grant"):
                        order = sorted(
                            (s for s in range(self.slots)
                             if active[s] is not None),
                            key=lambda s: sched.admitted_at[s])
                        for s in order:
                            req = active[s]
                            if not sched.grant_decode(
                                    len(req.out_tokens),
                                    req.max_new_tokens, int(pos[s]),
                                    self.max_len, lead=1):
                                continue    # its last token retires in
                                #             a moment; nothing to grant
                            if s not in inflight["act_dec"]:
                                # no carried token for this lane: drain
                                # and let the sync path re-dispatch it
                                barrier = True
                                break
                            if self._alloc(s, int(pos[s]) + 2) is None:
                                barrier = True   # needs preemption
                                break
                            dec.append(s)
                if barrier or not dec:
                    # pipeline barrier: drain, then run the next round
                    # synchronously — admission/preemption/prefill see
                    # only retired state, and the sync round's page-op
                    # flush dispatches after this retire
                    self.stats.pipeline_barriers += 1
                    prev, inflight = inflight, None
                    retire(prev)
                    continue
                with phase("round/host_prep"):
                    start = np.zeros(self.slots, np.int32)
                    n_new = np.zeros(self.slots, np.int32)
                    for s in dec:
                        start[s] = int(pos[s]) + 1  # the in-flight
                        n_new[s] = 1                # token's position
                    ts = time.monotonic()
                    self.stats.kv_pages_live += sum(
                        pages_for(int(start[s]) + 1, self.page)
                        for s in dec)
                    self.stats.kv_pages_full += (
                        len(dec) * self.max_pages_per_seq)
                    cache_in = self._flush_page_ops(pool)
                    solo = (self._steps.solo_step is not None
                            and len(dec) == 1)
                with phase("round/dispatch"):
                    s0 = dec[0] if solo else None
                    tok_in = serve_steps.carry_decode_tokens(
                        inflight["tok_dev"], s0)
                    tok_dev, logp_dev = dispatch(
                        tok_in, cache_in, start, n_new, solo_slot=s0)
                    rec = {"order": dec, "plan": {}, "verify": {},
                           "act_dec": dec, "n_new": n_new, "c_len": 1,
                           "ts": ts, "tok_dev": tok_dev,
                           "logp_dev": logp_dev, "solo_slot": s0,
                           "lag_free": set()}
                    readback_async(rec)
                self.stats.decode_steps += 1
                self.stats.rounds += 1
                self.stats.pipelined_rounds += 1
                prev, inflight = inflight, rec
                emitted = retire(prev, defer_rec=rec)
                trc.counter("pool/pages", live=pool.used_count,
                            free=pool.free_count)
                trc.counter("sched/queue",
                            prefill_pending=sched.pending)
                trc.complete("round", r_t0,
                             time.perf_counter() - r_t0,
                             lanes=len(order), prefill_lanes=0,
                             decode_lanes=len(dec), emitted=emitted)
                continue
            with phase("round/admit"):
                sched.start_round()
                admit()
            if not any(a is not None for a in active):
                if sched.pending:
                    raise PoolExhausted(
                        f"queue head needs more than the whole pool "
                        f"({self.n_pages} pages)")
                break
            # --- plan the round: chunk grants for prefilling lanes, one
            # token per decode lane; every planned lane must own the
            # pages it writes — on exhaustion first evict unpinned cached
            # pages, then the youngest younger slot — or self, if none is
            # younger (oldest-first order makes progress certain)
            plan = {}                       # slot -> chunk tokens
            with phase("round/grant"):
                order = sorted((s for s in range(self.slots)
                                if active[s] is not None),
                               key=lambda s: sched.admitted_at[s])
                for s in order:
                    while active[s] is not None:
                        if prefilling(s):
                            n = plan.get(s)
                            if n is None:
                                n = sched.grant_chunk(
                                    len(active[s].prompt) - int(pos[s]))
                                if n == 0:
                                    break   # budget spent: idle a round
                                plan[s] = n
                            need = int(pos[s]) + n
                        else:
                            need = int(pos[s]) + 1
                        if self._alloc(s, need) is not None:
                            break
                        victim = sched.choose_victim(s)
                        if victim is not None:
                            plan.pop(victim, None)
                            preempt(victim)
                            continue
                        if not any(active[t] is not None
                                   for t in range(self.slots) if t != s):
                            raise PoolExhausted(
                                f"sequence in slot {s} needs "
                                f"{need} tokens of KV but the pool "
                                f"holds {self.n_pages} pages total")
                        plan.pop(s, None)
                        preempt(s)  # yield to older slots; retry later

                decode_lanes = [s for s in order if active[s] is not None
                                and not prefilling(s)]
                run_decode = bool(decode_lanes) and (self._co_schedule
                                                     or not plan)
                # self-speculative decode: draft up to k tokens per
                # greedy decode lane (prompt-lookup over its own
                # history) for a single verify step on an existing
                # ladder rung. Drafts are optional work: they draw on
                # the round budget after prefill grants and take extra
                # pages WITHOUT preemption — any shortfall just means
                # the lane decodes one token as usual
                verify: Dict[int, np.ndarray] = {}
                if run_decode and self._spec_k > 0:
                    for s in decode_lanes:
                        req = active[s]
                        if self._slot_sp[s].temperature > 0:
                            continue   # greedy acceptance only
                        want = min(self._spec_k, self.chunk - 1,
                                   self.max_len - int(pos[s]) - 1,
                                   req.max_new_tokens
                                   - len(req.out_tokens) - 1)
                        if want <= 0:
                            continue
                        hist = np.concatenate(
                            [np.asarray(req.prompt, np.int64),
                             np.asarray(req.out_tokens, np.int64)])
                        draft = speculative.propose(hist, want)
                        if draft.size == 0:
                            continue
                        granted = sched.grant_verify(len(draft))
                        if granted == 0:
                            continue
                        draft = draft[:granted]
                        if self._alloc(
                                s, int(pos[s]) + 1 + len(draft)) is None:
                            continue
                        verify[s] = draft
            if not plan and not run_decode:
                continue            # everything preempted/idled; re-admit

            with phase("round/host_prep"):
                max_n = max([max(plan.values(), default=0)]
                            + [1 + len(d) for d in verify.values()])
                # smallest compiled width covering the widest grant —
                # prefill chunk or speculative verify (pow2 ladder, see
                # the class docstring); pure-decode rounds stay at the
                # dedicated C = 1 shape
                c_len = 1 if max_n <= 1 else min(
                    [w for w in self._widths if w >= max_n]
                    or [self.chunk])
                toks = np.zeros((self.slots, c_len), np.int32)
                start = np.zeros(self.slots, np.int32)
                n_new = np.zeros(self.slots, np.int32)
                for s in range(self.slots):
                    if active[s] is None:
                        continue
                    start[s] = pos[s]
                    if s in plan:
                        n = plan[s]
                        n_new[s] = n
                        p0 = int(pos[s])
                        toks[s, :n] = active[s].prompt[p0:p0 + n]
                    elif not prefilling(s) and run_decode:
                        d = verify.get(s)
                        toks[s, 0] = next_tok[s]
                        if d is None:
                            n_new[s] = 1
                        else:
                            n_new[s] = 1 + len(d)
                            toks[s, 1:1 + len(d)] = d

                ts = time.monotonic()
                # gather-work accounting: decode lanes attend seq =
                # pos+n_new (the tokens being written included — n_new
                # is 1, or 1+k on a verify round); chunk lanes stream
                # per q block, page-for-page what kv_traffic_chunked
                # charges
                act_dec = decode_lanes if run_decode else []
                if verify:
                    self.stats.spec_rounds += 1
                self.stats.kv_pages_live += sum(
                    pages_for(int(pos[s]) + int(n_new[s]), self.page)
                    for s in act_dec)
                self.stats.kv_pages_full += (len(act_dec)
                                             * self.max_pages_per_seq)
                for s in plan:
                    self.stats.prefill_kv_pages_live += \
                        chunk_pages_streamed(int(pos[s]), plan[s],
                                             page=self.page)
                    self.stats.prefill_kv_pages_written += (
                        pages_for(int(pos[s]) + plan[s], self.page)
                        - int(pos[s]) // self.page)
                cache_in = self._flush_page_ops(pool)
                live = np.flatnonzero(n_new > 0)
                solo = (self._steps.solo_step is not None
                        and len(live) == 1)
            # a pure-decode round with an idle admission queue may enter
            # the pipeline: dispatch without blocking, retire one round
            # later (speculative lanes never overlap — drafting needs
            # retired host history)
            overlap = (self._pipelined and run_decode and not plan
                       and not verify and self._spec_k == 0
                       and not sched.pending)
            with phase("round/dispatch" if overlap
                       else "round/device_step"):
                # token selection runs INSIDE the jit (the sampling-head
                # epilogue): only [B, C] selected ids + logprobs cross
                # the boundary, and dead lanes come back as the
                # DEAD_TOKEN sentinel — never a forgeable vocab id
                if solo:
                    s0 = int(live[0])
                    tok_dev, logp_dev = dispatch(
                        jnp.asarray(toks[s0:s0 + 1]), cache_in,
                        start, n_new, solo_slot=s0)
                else:
                    s0 = None
                    tok_dev, logp_dev = dispatch(
                        jnp.asarray(toks), cache_in, start, n_new)
                rec = {"order": order, "plan": plan, "verify": verify,
                       "act_dec": act_dec, "n_new": n_new,
                       "c_len": c_len, "ts": ts, "tok_dev": tok_dev,
                       "logp_dev": logp_dev, "solo_slot": s0,
                       "lag_free": set()}
                if overlap:
                    readback_async(rec)
                else:
                    nxt, logp_h = gather(rec)
            if act_dec:
                self.stats.decode_steps += 1
            self.stats.rounds += 1
            if overlap:
                self.stats.pipelined_rounds += 1
                inflight = rec
                emitted = 0         # emissions land at this round's
                #                     retire, one round from now
            else:
                with phase("round/emit"):
                    emitted = process_round(rec, nxt, logp_h)
            # pool-pressure counter tracks, one sample per round — these
            # render as Perfetto counter lanes next to the phase spans
            trc.counter("pool/pages", live=pool.used_count,
                        free=pool.free_count)
            trc.counter("sched/queue", prefill_pending=sched.pending)
            trc.complete("round", r_t0, time.perf_counter() - r_t0,
                         lanes=len(order), prefill_lanes=len(plan),
                         decode_lanes=len(act_dec), emitted=emitted)

        if inflight is not None:
            # every lane finished (or deferred its free) during the lag
            # and the loop fell through: retire the last in-flight round
            retire(inflight)
            inflight = None

        self.stats.preemptions = sched.preemptions
        self.stats.pages_peak = max(self.stats.pages_peak, pool.pages_peak)
        self.stats.cow_copies = pool.cow_copies - cow0
        self.stats.adopt_calls = pool.adopt_calls - adopt0
        self.stats.device_tables_rebuilds = pool.tables_rebuilds - tbl0
        _, jitc1, jits1 = self._steps.jit_counters()
        self.stats.jit_compiles = jitc1 - jitc0
        self.stats.jit_compile_s = jits1 - jits0
        self.stats.wall_s = time.monotonic() - t0
        self._flush_metrics(reg, admissions)
        if cost0 is not None:
            report = obs_costs.attribute(
                self._steps, self.stats, cfg=self.cfg,
                params=self.params, page=self.page,
                kv_dtype_bits=jnp.dtype(self.cache_dtype).itemsize * 8,
                baseline=cost0)
            self.last_cost_report = report
            obs_costs.flush_metrics(reg, report)
        return requests

    def _flush_metrics(self, reg: obs_metrics.Registry,
                       admissions: Dict[str, int]) -> None:
        """Fold the finished run's EngineStats deltas into the registry
        (names per the ``obs/metrics.py`` contract)."""
        s = self.stats
        reg.counter("serve_rounds_total",
                    "engine rounds that ran a jit step").inc(s.rounds)
        tok = reg.counter("serve_tokens_total", "tokens emitted/discarded",
                          labels=("kind",))
        tok.inc(s.tokens_out, kind="emitted")
        tok.inc(s.tokens_discarded, kind="discarded")
        adm = reg.counter("serve_admissions_total",
                          "request admissions by prefix-cache outcome",
                          labels=("kind",))
        for kind, n in admissions.items():
            adm.inc(n, kind=kind)
        reg.counter("serve_preemptions_total",
                    "recompute-style slot evictions").inc(s.preemptions)
        ops = reg.counter("serve_page_ops_total",
                          "host<->device page-op round trips",
                          labels=("op",))
        ops.inc(s.adopt_calls, op="adopt")
        ops.inc(s.page_copy_calls, op="page_copy")
        ops.inc(s.device_tables_rebuilds, op="tables_rebuild")
        ops.inc(s.cow_copies, op="cow")
        ops.inc(s.cache_evictions, op="cache_evict")
        ops.inc(s.page_op_flushes, op="fused_flush")
        reg.counter(
            "serve_page_op_round_trips_saved_total",
            "device dispatches avoided by fused page-op batching"
        ).inc(s.page_op_round_trips_saved)
        reg.counter("serve_solo_rounds_total",
                    "rounds run through the B=1 solo-lane step"
                    ).inc(s.solo_rounds)
        pipe = reg.counter("serve_pipeline_rounds_total",
                           "pipelined-loop events by kind",
                           labels=("kind",))
        pipe.inc(s.pipelined_rounds, kind="overlapped")
        pipe.inc(s.pipeline_barriers, kind="barrier")
        reg.counter("serve_pipeline_trimmed_tokens_total",
                    "tokens computed past an EOS during the pipeline "
                    "lag, trimmed and never emitted"
                    ).inc(s.lag_trimmed_tokens)
        reg.gauge("serve_pipeline_overlap_fraction",
                  "fraction of this run's rounds retired through the "
                  "async pipeline").set(s.pipeline_overlap)
        reg.counter("serve_speculative_rounds_total",
                    "rounds that carried a speculative verify lane"
                    ).inc(s.spec_rounds)
        spec = reg.counter("serve_speculative_tokens_total",
                           "speculative draft tokens by outcome",
                           labels=("kind",))
        spec.inc(s.spec_draft_tokens, kind="drafted")
        spec.inc(s.spec_accepted_tokens, kind="accepted")
        pool = self._pool
        if pool is not None:
            reg.gauge("serve_pages_used",
                      "arena pages allocated").set(pool.used_count)
            reg.gauge("serve_pages_peak",
                      "peak arena pages this run").set(s.pages_peak)


# ==========================================================================
# legacy per-slot engine (baseline)
# ==========================================================================
class LegacyServeEngine:
    """Original continuous-batching-lite loop: per-slot batch-1 contiguous

    caches, one sequential jit decode call per active slot per token."""

    def __init__(self, cfg: ModelConfig, params, *, slots: int = 4,
                 max_len: int = 256, cache_dtype=jnp.float32):
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.cache_dtype = cache_dtype
        self.stats = EngineStats()
        self._decode = serve_steps.contiguous_decode(cfg)

    def _prefill_one(self, prompt: np.ndarray):
        tokens = jnp.asarray(prompt)[None, :]
        logits, cache = prefill(self.cfg, self.params, tokens,
                                max_len=self.max_len,
                                cache_dtype=self.cache_dtype)
        self.stats.prefills += 1
        return int(jnp.argmax(logits[0])), cache

    def run(self, requests: List[Request],
            greedy: bool = True) -> List[Request]:
        """Process all requests to completion; returns them with outputs.

        Stats describe this run only (a fresh EngineStats per call)."""
        if not greedy:
            raise NotImplementedError("only greedy decoding is implemented")
        self.stats = EngineStats()
        t0 = time.monotonic()
        queue = list(requests)
        # slot state: per-slot cache (batch dim 1) + active request
        active: List[Optional[Request]] = [None] * self.slots
        caches: List = [None] * self.slots
        positions = [0] * self.slots
        next_tok = [0] * self.slots

        def refill():
            for s in range(self.slots):
                while active[s] is None and queue:
                    req = queue.pop(0)
                    tok, cache = self._prefill_one(req.prompt)
                    req.out_tokens.append(tok)
                    self.stats.tokens_out += 1
                    if _finished(req, len(req.prompt), self.max_len):
                        req.done = True   # EOS at prefill: no decode slot
                        continue
                    active[s] = req
                    caches[s] = cache
                    positions[s] = len(req.prompt)
                    next_tok[s] = tok

        refill()
        while any(a is not None for a in active):
            for s in range(self.slots):
                req = active[s]
                if req is None:
                    continue
                ts = time.monotonic()
                tok = jnp.asarray([[next_tok[s]]], jnp.int32)
                logits, caches[s] = self._decode(
                    self.params, tok, caches[s],
                    jnp.asarray(positions[s], jnp.int32))
                positions[s] += 1
                nxt = int(jnp.argmax(logits[0]))
                next_tok[s] = nxt
                req.out_tokens.append(nxt)
                self.stats.decode_steps += 1
                self.stats.tokens_out += 1
                self.stats.step_seconds.append(time.monotonic() - ts)
                self.stats.step_tokens.append(1)
                if _finished(req, positions[s], self.max_len):
                    req.done = True
                    active[s] = None
                    caches[s] = None
            refill()
        self.stats.wall_s = time.monotonic() - t0
        return requests
