#!/usr/bin/env python3
"""Chip smoke: serve full-width StableLM-2-1.6B with QMC weights on a TPU.

  python3 chip_smoke.py               # one chip, phases (a)-(e)
  python3 chip_smoke.py --four-chips  # the 2x2 mesh phase (f) only

One process drives the main serving path through the entry points a user
calls (``repro.launch.serve``: ``load_model`` -> ``build_engine`` ->
``ServeEngine.run``), at the published widths of ``stablelm-1.6b``
(24 layers, d_model 2048, 32 heads, d_ff 5632, vocab 100352) with
random-init weights from a fixed seed, quantized by QMC PTQ:

  (a) the first JAX device must be a TPU (anything else exits nonzero);
  (b) build the QMC weights, and a reference: the prompts' last-position
      logits from the plain XLA prefill (``models.model.prefill``);
  (c) 8 requests (prompt 128, 32 new tokens, 4 slots, greedy) through the
      synchronous round loop: every request returns 32 tokens in
      [0, vocab), no dead-lane token, the compile count equals the
      declared compile surface, and each first token equals the
      reference argmax wherever the reference's top-2 margin exceeds
      ATTN_TOL;
  (d) the same requests with ``pipelined=True``: tokens identical to (c);
  (e) one full-width layer of the ragged paged-attention kernel against
      the XLA gather, then the same requests with
      ``paged_attention=True``: first tokens as in (c), plus the share of
      all tokens equal to (c).

``--four-chips`` runs only (f): the same requests on a 2x2 (data, model)
mesh with weights quantized at ``tp_shards=2``, against one chip serving
the same quantized tree without a mesh, with the XLA gather and with the
kernel under ``shard_map``.

Every phase that fails raises, and the script exits nonzero. The last
line of standard output is the JSON device record, printed only after
every phase passed. Checks run under ``jax.default_matmul_precision(
"highest")``: at the TPU's default precision f32 operands are rounded to
bf16 inside the MXU, and on near-flat random-init logits that rounding
alone flips argmaxes between two correct attention paths.

JAX's persistent compilation cache goes to ``JAX_COMPILATION_CACHE_DIR``
when set, else to ``<repo>/.jax_cache``; the compile-cache line reports
its hits and misses, so a second run shows what the cache saved.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# Attention tolerance (absolute, on outputs of magnitude <= ~1). Both the
# kernel and the XLA gather contract fp32 operands at 'highest' precision,
# so they differ only in the order of the softmax sums (online over
# 16-token pages vs one pass over up to 176 positions): fp32 roundoff of
# ~1e-7 per op grown over a few hundred terms stays below ~1e-5. 1e-4
# leaves 10x headroom and is still 20x below the 2^-9 ~ 2e-3 relative
# error a bf16-rounded contraction would show. The same bound is the
# top-2 logit margin above which two serving paths must agree on a
# request's first token.
ATTN_TOL = 1e-4

SERVE_ARGS = ["--arch", "stablelm-1.6b", "--no-reduced", "--weights", "qmc",
              "--requests", "8", "--prompt-len", "128",
              "--new-tokens", "32", "--slots", "4", "--seed", "0"]

# jit compiles a phase must show: a cold geometry compiles the step at
# C = 1 and at the 128-token prefill rung plus the fused page-ops jit; a
# phase that reuses an earlier phase's geometry compiles nothing
COLD_COMPILES = 3


class SmokeFailure(RuntimeError):
    """A check of the chip smoke failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _import_repo():
    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SmokeFailure(f"no repro package under {src}: run "
                           f"chip_smoke.py from a checkout of the repo")
    sys.path.insert(0, src)


class CacheEvents:
    """Counts persistent compilation cache hits and misses."""

    def __init__(self):
        import jax
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def peak_bytes() -> int:
    import jax
    return int(jax.devices()[0].memory_stats()["peak_bytes_in_use"])


def phase_line(name: str, t0: float, stats=None, **extra) -> None:
    parts = [f"[smoke] {name}: wall={time.monotonic() - t0:.1f}s"]
    if stats is not None:
        parts.append(f"compile={stats.jit_compile_s:.1f}s "
                     f"jit_compiles={stats.jit_compiles} "
                     f"serve_wall={stats.wall_s:.1f}s "
                     f"tokens={stats.tokens_out}")
    parts += [f"{k}={v}" for k, v in extra.items()]
    parts.append(f"peak_bytes={peak_bytes()}")
    print(" ".join(parts), flush=True)


def device_record(expect_count: int) -> dict:
    """Phase (a): the device JAX reports, refused unless it is a TPU."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    print(f"[smoke] device: platform={dev.platform} "
          f"kind={dev.device_kind} count={len(devs)}", flush=True)
    check(dev.platform == "tpu",
          f"no TPU: JAX's first device is {dev.platform}")
    check(len(devs) >= expect_count,
          f"needs {expect_count} chips, JAX sees {len(devs)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def prompts(reqs):
    import numpy as np
    return np.stack([r.prompt for r in reqs])


def prefill_logits(cfg, params, tokens, mesh=None):
    """Last-position logits of the plain XLA prefill (contiguous fp32
    cache, no paging): the reference the serving paths are held to."""
    import jax
    import jax.numpy as jnp
    from repro import runtime_context as rctx
    from repro.launch import mesh as meshlib
    from repro.models.model import prefill

    dp = meshlib.dp_axes(mesh) if mesh is not None else ()

    def fn(p, t):
        with rctx.use_mesh(mesh, dp):
            return prefill(cfg, p, t, max_len=t.shape[1],
                           cache_dtype=jnp.float32)[0]

    return jax.jit(fn)(params, jnp.asarray(tokens))


def top2_margin(logits):
    import numpy as np
    top = np.sort(np.asarray(logits, np.float64), axis=-1)[:, -2:]
    return top[:, 1] - top[:, 0]


def serve_phase(name, args, cfg, params, expect_compiles):
    """Run the requests through one engine; check the outputs and the
    compile surface. Returns the token lists."""
    from repro.kernels.paged_attention import shard_compatible
    from repro.launch import serve
    from repro.obs import metrics as obs_metrics
    from repro.serve.sampling import DEAD_TOKEN

    t0 = time.monotonic()
    eng = serve.build_engine(args, cfg, params)
    if args.paged_attention and eng.mesh is not None:
        # an arena that does not divide the mesh would quietly attend
        # through the XLA gather instead of the kernel
        check(shard_compatible(eng.mesh, eng.n_pages + 1, cfg.n_kv_heads),
              f"{name}: arena of {eng.n_pages + 1} pages x "
              f"{cfg.n_kv_heads} KV heads does not divide the mesh")
    reqs = serve.make_requests(args, cfg)
    eng.run(reqs)
    s = eng.stats
    del eng
    gc.collect()
    phase_line(name, t0, s)
    toks = [list(r.out_tokens) for r in reqs]
    for r, t in zip(reqs, toks):
        check(len(t) == args.new_tokens,
              f"{name}: request {r.uid} returned {len(t)} tokens, "
              f"expected {args.new_tokens} (no EOS is set)")
        check(DEAD_TOKEN not in t, f"{name}: request {r.uid} emitted the "
              f"dead-lane token")
        check(all(0 <= x < cfg.vocab for x in t),
              f"{name}: request {r.uid} emitted a token outside "
              f"[0, {cfg.vocab})")
    check(s.jit_compiles == expect_compiles,
          f"{name}: {s.jit_compiles} jit compiles, declared compile "
          f"surface is {expect_compiles}")
    snap = obs_metrics.get_registry().snapshot()
    retraces = sum(x["value"] for x in snap.get(
        "serve_jit_retraces_unexpected_total", {"series": []})["series"])
    check(retraces == 0, f"{name}: {retraces} unexpected retraces")
    return toks


def first_tokens_agree(name, toks, ref_first, margin):
    """Every request whose reference top-2 margin exceeds ATTN_TOL must
    start with the reference token."""
    must = [i for i, m in enumerate(margin) if m > ATTN_TOL]
    bad = [i for i in must if toks[i][0] != ref_first[i]]
    print(f"[smoke] {name}: first tokens agree on "
          f"{len(must) - len(bad)}/{len(must)} requests with top-2 margin "
          f"> {ATTN_TOL} (min margin {min(margin):.3g})", flush=True)
    check(not bad, f"{name}: first token differs on requests {bad} "
          f"(margins {[float(margin[i]) for i in bad]})")


def token_share(a, b) -> float:
    same = sum(x == y for ta, tb in zip(a, b) for x, y in zip(ta, tb))
    return same / sum(len(t) for t in a)


def layer_check():
    """Phase (e), first half: one full-width StableLM-2-1.6B attention
    layer through the kernel vs the XLA gather, decode and a 128-token
    chunk, fp32 and int8 KV."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.paged_attention import ragged_paged_attention
    from repro.models.attention import attend, paged_cache_read
    from repro.models.kvcache import quantize_kv

    n_kv = n_heads = 32
    hd, page, lanes, per_seq = 64, 16, 4, 11
    n_pages = lanes * per_seq + 1
    rng = np.random.default_rng(0)
    kf = rng.standard_normal((n_pages, page, n_kv, hd)).astype(np.float32)
    vf = rng.standard_normal((n_pages, page, n_kv, hd)).astype(np.float32)
    tbl = rng.permutation(np.arange(1, n_pages)).reshape(lanes, per_seq)
    for kv in ("fp32", "int8"):
        cache = {"block_tbl": jnp.asarray(tbl, jnp.int32)}
        if kv == "fp32":
            cache["k_pages"] = jnp.asarray(kf.reshape(n_pages, page, -1))
            cache["v_pages"] = jnp.asarray(vf.reshape(n_pages, page, -1))
        else:
            kq, ks = quantize_kv(jnp.asarray(kf))
            vq, vs = quantize_kv(jnp.asarray(vf))
            cache.update(k_pages=kq.reshape(n_pages, page, -1),
                         v_pages=vq.reshape(n_pages, page, -1),
                         k_scale_pages=ks, v_scale_pages=vs)
        for s, q_start in ((1, [175, 99, 36, 0]), (128, [0, 16, 40, 48])):
            q_start = jnp.asarray(q_start, jnp.int32)
            kv_len = q_start + s
            q = jnp.asarray(rng.standard_normal(
                (lanes, s, n_heads, hd)).astype(np.float32))
            out = ragged_paged_attention(q, cache, q_start, kv_len,
                                         n_kv=n_kv, head_dim=hd)
            k_all, v_all = paged_cache_read(cache, jnp.float32, n_kv, hd)
            t = k_all.shape[1]
            ref = attend(q, k_all, v_all,
                         q_positions=q_start[:, None]
                         + jnp.arange(s, dtype=jnp.int32)[None],
                         kv_positions=jnp.broadcast_to(
                             jnp.arange(t)[None], (lanes, t)),
                         kv_valid_len=kv_len, causal=True)
            err = float(jnp.max(jnp.abs(out - ref)))
            print(f"[smoke] layer {kv} S={s}: max |kernel - gather| = "
                  f"{err:.3g} (tol {ATTN_TOL})", flush=True)
            check(err <= ATTN_TOL, f"kernel attention {kv} S={s} differs "
                  f"from the XLA gather by {err:.3g} > {ATTN_TOL}")


def with_args(base, **kw):
    return argparse.Namespace(**{**vars(base), **kw})


def one_chip():
    import numpy as np
    from repro.launch import serve

    args = serve.parse_args(SERVE_ARGS)
    t0 = time.monotonic()
    cfg, params = serve.load_model(args)
    reqs = serve.make_requests(args, cfg)
    ref = np.asarray(prefill_logits(cfg, params, prompts(reqs)))
    check(np.isfinite(ref).all(), "reference prefill logits not finite")
    ref_first = ref.argmax(-1)
    margin = top2_margin(ref)
    phase_line("(b) build QMC engine weights + reference prefill", t0,
               layers=cfg.n_layers, d_model=cfg.d_model, vocab=cfg.vocab)

    sync = serve_phase("(c) sync loop", args, cfg, params, COLD_COMPILES)
    first_tokens_agree("(c) sync loop vs reference prefill", sync,
                       ref_first, margin)

    piped = serve_phase("(d) pipelined loop",
                        with_args(args, pipelined=True), cfg, params, 0)
    check(piped == sync, "(d) pipelined tokens differ from the sync loop")
    print("[smoke] (d) pipelined tokens identical to (c)", flush=True)

    t0 = time.monotonic()
    layer_check()
    phase_line("(e) kernel layer vs XLA gather", t0)
    paged = serve_phase("(e) paged-attention kernel",
                        with_args(args, paged_attention=True), cfg, params,
                        COLD_COMPILES)
    first_tokens_agree("(e) kernel vs sync loop", paged,
                       [t[0] for t in sync], margin)
    print(f"[smoke] (e) kernel tokens equal to (c): "
          f"{token_share(paged, sync):.4f} of all tokens", flush=True)


def four_chips():
    import jax
    import numpy as np
    from repro.launch import mesh as meshlib
    from repro.launch import serve
    from repro.launch import sharding as shd

    args4 = serve.parse_args(SERVE_ARGS + ["--data-shards", "2",
                                           "--model-shards", "2"])
    args1 = with_args(args4, data_shards=1, model_shards=1)
    t0 = time.monotonic()
    cfg, params = serve.load_model(args4)        # tp_shards=2 streams
    reqs = serve.make_requests(args4, cfg)
    tokens = prompts(reqs)
    ref = np.asarray(prefill_logits(cfg, params, tokens))
    margin = top2_margin(ref)
    mesh = meshlib.make_mesh((2, 2), ("data", "model"))
    params4 = jax.device_put(params, shd.shard_params_tree(params, mesh))
    got = np.asarray(prefill_logits(cfg, params4, tokens, mesh))
    diff = float(np.max(np.abs(got - ref)))
    phase_line("(f) prefill logits, 2x2 mesh vs one chip", t0,
               max_abs_diff=f"{diff:.3g}")
    check(np.isfinite(got).all(), "mesh prefill logits not finite")
    first_tokens_agree("(f) mesh prefill vs one chip", [[int(x)] for x in
                       got.argmax(-1)], ref.argmax(-1), margin)
    for attn in (False, True):
        label = "kernel" if attn else "XLA gather"
        one = serve_phase(f"(f) one chip, {label}",
                          with_args(args1, paged_attention=attn), cfg,
                          params, COLD_COMPILES)
        four = serve_phase(f"(f) 2x2 mesh, {label}",
                           with_args(args4, paged_attention=attn), cfg,
                           params4, COLD_COMPILES)
        first_tokens_agree(f"(f) 2x2 mesh vs one chip, {label}", four,
                           [t[0] for t in one], margin)
        print(f"[smoke] (f) {label}: mesh tokens equal to one chip: "
              f"{token_share(four, one):.4f} of all tokens", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2 mesh phase and its one-chip "
                         "comparison (needs 4 chips)")
    opts = ap.parse_args(argv)
    _import_repo()
    import jax
    from repro.launch.compile_cache import configure_compile_cache

    t_all = time.monotonic()
    cache_dir = configure_compile_cache()
    events = CacheEvents()
    record = device_record(4 if opts.four_chips else 1)
    with jax.default_matmul_precision("highest"):
        if opts.four_chips:
            four_chips()
        else:
            one_chip()
    print(f"[smoke] compile cache: dir={cache_dir} hits={events.hits} "
          f"misses={events.misses}", flush=True)
    print(f"[smoke] all phases passed in {time.monotonic() - t_all:.1f}s, "
          f"peak_bytes={peak_bytes()}", flush=True)
    print(json.dumps({"ok": True, "device": record}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
