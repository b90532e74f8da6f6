"""Serving driver CLI: train-free demo loads random-init weights, quantizes

them with QMC, and serves batched requests through the engine.

  PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b \
      --reduced --requests 8 --new-tokens 16 --weights qmc

``--no-reduced`` serves the published widths; ``chip_smoke.py`` at the
repo root drives this module's ``load_model`` / ``build_engine`` /
``make_requests`` at full StableLM-2-1.6B width on one TPU.

Sharded serving drives the SAME step-builder layer (``serve/steps.py``)
the engine uses everywhere: ``--data-shards D --model-shards M`` builds a
(D, M) ``("data", "model")`` mesh, quantizes the weights per TP shard
(``tp_shards=M`` — the QMC quantize-after-shard deployment format), builds
the paged step set explicitly, and hands it to ``ServeEngine``. Run under
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` to demo on a CPU
host. The mesh follows the physical device topology (``jax.make_mesh``).

JAX's persistent compilation cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, else to ``<repo>/.jax_cache`` (``launch/compile_cache.py``).

Observability: ``--trace-out t.json`` writes a Perfetto-loadable Chrome
trace of the run (round phase spans + request lifecycle instants, see
``repro.obs.trace``), ``--metrics-out m.json`` snapshots the ``serve_*``
metrics registry (``repro.obs.metrics``), and ``--profile DIR`` wraps the
run in ``jax.profiler.trace`` for an XLA-level TensorBoard profile.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, reduced_config
from repro.core.qconfig import QMCConfig
from repro.core.serving_quant import quantize_for_serving
from repro.launch import mesh as meshlib
from repro.launch.compile_cache import configure_compile_cache
from repro.models.model import init_params
from repro.obs import costs as obs_costs
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.serve import steps as serve_steps
from repro.serve.engine import Request, ServeEngine
from repro.serve.paged_kv import pages_for
from repro.serve.sampling import SamplingParams


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--weights", choices=["fp16", "qmc"], default="qmc")
    ap.add_argument("--rho", type=float, default=0.3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy argmax, the "
                         "bitwise oracle path)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="sample from the k highest-logit tokens "
                         "(0 = off)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling: smallest token set with "
                         "cumulative mass >= p (1.0 = off)")
    ap.add_argument("--logprobs", action="store_true",
                    help="record each selected token's model logprob in "
                         "Request.out_logprobs")
    ap.add_argument("--speculative", type=int, default=0, metavar="K",
                    help="self-speculative decode: up to K prompt-lookup "
                         "draft tokens verified per greedy decode lane "
                         "per round (0 = off)")
    ap.add_argument("--pipelined", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="overlap host and device work on steady-state "
                         "decode: async step dispatch, on-device token "
                         "carry, retire via async readback one round "
                         "later (token-identical to the sync loop)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share prompt-prefix KV pages copy-on-write")
    ap.add_argument("--paged-attention", action="store_true",
                    help="attend through the ragged Pallas page-table "
                         "kernel (streams live pages only, for decode "
                         "tokens and prefill chunks alike; "
                         "interpret-mode on the CPU backend)")
    ap.add_argument("--chunked-prefill", action="store_true",
                    help="split prompts into fixed-size chunks that "
                         "co-schedule with decode lanes in the same jit "
                         "step (default: one chunk covers the whole "
                         "prompt)")
    ap.add_argument("--chunk-tokens", type=int, default=32,
                    help="prefill chunk width with --chunked-prefill")
    ap.add_argument("--sys-prompt-len", type=int, default=0,
                    help="prepend a shared system prompt of this length "
                         "to every request (multi-tenant demo)")
    ap.add_argument("--data-shards", type=int, default=1,
                    help="mesh 'data' axis: shards the paged arena's "
                         "page pool")
    ap.add_argument("--model-shards", type=int, default=1,
                    help="mesh 'model' axis: TP over heads / FFN / "
                         "quantized weight shards")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--trace-out", metavar="PATH",
                    help="write a Chrome trace-event JSON of the run "
                         "(load in Perfetto / chrome://tracing): per-round "
                         "phase spans plus request-lifecycle and "
                         "scheduler/cache instants")
    ap.add_argument("--metrics-out", metavar="PATH",
                    help="write a JSON snapshot of the serve_* metrics "
                         "registry after the run")
    ap.add_argument("--profile", metavar="DIR",
                    help="wrap the run in jax.profiler.trace(DIR) "
                         "(TensorBoard-loadable XLA profile)")
    ap.add_argument("--cost-report", action="store_true",
                    help="capture XLA cost_analysis() per step shape and "
                         "print the per-step roofline attribution table "
                         "+ modeled memory-system cost after the run "
                         "(obs/costs.py; makes step calls synchronous)")
    return ap.parse_args(argv)


def load_model(args):
    """(cfg, params): random-init weights from ``--seed``, quantized to
    the QMC serving format (per TP shard) with ``--weights qmc``."""
    cfg = reduced_config(args.arch) if args.reduced else get_config(
        args.arch)
    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    if args.weights == "qmc":
        t0 = time.monotonic()
        params = quantize_for_serving(
            params, QMCConfig(rho=args.rho, granularity="subtile"),
            tp_shards=args.model_shards, min_dim=64)
        params = jax.block_until_ready(params)
        print(f"[serve] QMC PTQ in {time.monotonic()-t0:.1f}s "
              f"(tp_shards={args.model_shards})")
    return cfg, params


def make_requests(args, cfg) -> list:
    """``--requests`` prompts of ``--sys-prompt-len + --prompt-len``
    random tokens drawn from ``--seed`` (the same list on every call)."""
    rng = np.random.default_rng(args.seed)
    sys_prompt = rng.integers(2, cfg.vocab, size=args.sys_prompt_len)
    return [Request(uid=i,
                    prompt=np.concatenate(
                        [sys_prompt,
                         rng.integers(2, cfg.vocab, size=args.prompt_len)]
                    ).astype(np.int32),
                    max_new_tokens=args.new_tokens)
            for i in range(args.requests)]


def build_engine(args, cfg, params, tracer=None) -> ServeEngine:
    """The serving engine for ``args``: the (data, model) mesh when
    either axis exceeds 1, the paged step set built through the shared
    builder layer, and the sampling/loop options."""
    mesh = None
    if args.data_shards * args.model_shards > 1:
        mesh = meshlib.make_mesh((args.data_shards, args.model_shards),
                                 ("data", "model"))
        print(f"[serve] mesh data={args.data_shards} "
              f"model={args.model_shards} over "
              f"{args.data_shards * args.model_shards} devices")

    # build the step set through the shared builder layer (exactly what
    # the engine would build itself — passing it in pins the contract)
    max_len = (args.sys_prompt_len + args.prompt_len
               + args.new_tokens + 4)
    mpps = pages_for(max_len, args.page_size)
    n_pages = serve_steps.default_n_pages(args.slots, mpps, mesh)
    p_struct = None
    if mesh is not None:
        p_struct = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    chunk = (args.chunk_tokens if args.chunked_prefill
             else serve_steps.default_chunk(mpps, args.page_size))
    step_set = serve_steps.build_paged_steps(
        cfg, mesh, p_struct, page=args.page_size,
        n_pages=n_pages, max_slots=args.slots,
        max_pages_per_seq=mpps, chunk=chunk,
        paged_attention=args.paged_attention)
    sp = SamplingParams(temperature=args.temperature, top_k=args.top_k,
                        top_p=args.top_p, seed=args.seed,
                        logprobs=args.logprobs)
    return ServeEngine(cfg, params, slots=args.slots, max_len=max_len,
                       page_size=args.page_size, mesh=mesh,
                       step_set=step_set, chunk_tokens=chunk,
                       prefix_cache=args.prefix_cache,
                       paged_attention=args.paged_attention,
                       sampling=sp, speculative_k=args.speculative,
                       pipelined=args.pipelined, tracer=tracer)


def main(argv=None):
    args = parse_args(argv)
    configure_compile_cache()
    if args.cost_report:
        # before any step set is built, so every wrapper captures
        obs_costs.enable_capture()

    cfg, params = load_model(args)
    reqs = make_requests(args, cfg)
    tracer = None
    if args.trace_out:
        tracer = obs_trace.Tracer(enabled=True)
        # install as the process default so deep call sites (scheduler,
        # prefix cache, jit wrappers) emit into the same trace
        obs_trace.set_tracer(tracer)
    eng = build_engine(args, cfg, params, tracer)
    chunk = eng.chunk
    if args.profile:
        with jax.profiler.trace(args.profile):
            eng.run(reqs)
        print(f"[serve] XLA profile written under {args.profile}")
    else:
        eng.run(reqs)
    s = eng.stats
    if tracer is not None:
        n_ev = tracer.export(args.trace_out)
        print(f"[serve] trace: {n_ev} events -> {args.trace_out}")
    if args.metrics_out:
        obs_metrics.get_registry().write_json(args.metrics_out)
        print(f"[serve] metrics snapshot -> {args.metrics_out}")
    print(f"[serve] {s.prefills} prefills ({s.prefill_chunks} chunks of "
          f"<= {chunk} tokens), {s.decode_steps} decode steps, "
          f"{s.tokens_out} tokens in {s.wall_s:.2f}s "
          f"({s.tokens_per_s:.1f} tok/s)")
    if s.phase_seconds:
        print(f"[serve] phases: host={s.host_seconds():.2f}s "
              f"device={s.device_seconds():.2f}s over {s.rounds} rounds "
              f"({s.jit_compiles} jit compiles, ~{s.jit_compile_s:.2f}s)")
    if args.pipelined:
        print(f"[serve] pipelined: {s.pipelined_rounds}/{s.rounds} "
              f"rounds overlapped ({s.pipeline_overlap:.0%}), "
              f"{s.pipeline_barriers} drains, "
              f"{s.lag_trimmed_tokens} lag-trimmed tokens")
    if args.chunked_prefill and s.ttft_s:
        import numpy as _np
        print(f"[serve] chunked prefill: TTFT p50="
              f"{_np.percentile(s.ttft_s, 50) * 1e3:.1f}ms p95="
              f"{_np.percentile(s.ttft_s, 95) * 1e3:.1f}ms, "
              f"{s.prefill_kv_pages_live} live pages streamed / "
              f"{s.prefill_kv_pages_written} written by chunks")
    if args.paged_attention and s.kv_pages_full:
        print(f"[serve] paged-attention kernel: {s.kv_pages_live} live "
              f"pages streamed vs {s.kv_pages_full} full-width "
              f"({1 - s.kv_pages_live / s.kv_pages_full:.0%} gather work "
              f"saved)")
    if args.prefix_cache:
        print(f"[serve] prefix cache: {s.cache_hits} hits, "
              f"hit_rate={s.hit_rate:.2f}, prefill-token reduction="
              f"{s.prefill_token_reduction:.2f}, {s.cow_copies} COW copies")
    if s.dedup_hits:
        print(f"[serve] in-flight dedup: {s.dedup_hits} admissions "
              f"aliased a live identical prompt")
    if args.temperature > 0:
        print(f"[serve] sampling: temperature={args.temperature} "
              f"top_k={args.top_k} top_p={args.top_p} seed={args.seed}")
    if args.speculative > 0:
        print(f"[serve] speculative k={args.speculative}: "
              f"{s.spec_rounds} verify rounds, "
              f"{s.spec_accepted_tokens}/{s.spec_draft_tokens} drafts "
              f"accepted (rate={s.spec_acceptance_rate:.2f})")
    if args.logprobs and reqs and reqs[0].out_logprobs:
        lp = reqs[0].out_logprobs[:5]
        print(f"[serve] req 0 logprobs: "
              f"{[round(x, 3) for x in lp]}...")
    if args.cost_report and eng.last_cost_report is not None:
        print("[serve] cost attribution (measured vs roofline, "
              "obs/costs.py):")
        print(eng.last_cost_report.table())
    for r in reqs[:3]:
        print(f"  req {r.uid}: {r.out_tokens[:10]}...")


if __name__ == "__main__":
    main()
