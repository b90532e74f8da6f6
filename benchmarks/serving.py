"""Serving throughput: legacy per-slot engine vs paged continuous batching,
plus the shared-system-prompt multi-tenant prefix-cache workload, the QMC
serving-format (quantized-weights) engine variant, and the sharded paged
engine on a (2, 2) mesh.

Runs a fixed synthetic workload through both engines at slots ∈ {1, 4, 8},
prints the standard ``name,us_per_call,derived`` CSV rows, and writes
``BENCH_serving.json`` with tokens/s and p50/p95 per-token decode latency
per configuration, plus the memsys paged/prefix KV traffic summaries the
§4 DSE consumes. The prefix-cache section runs N tenants whose prompts
share one system prompt and reports hit rate, prefill-token reduction and
tokens/s with the cache on vs off. The weights section compares dense fp32
against the QMC deployment format (the paper's configuration). The sharded
section re-runs the paged engine on a (2 data, 2 model) mesh — in a child
process on four forced host devices when the bench runs on the CPU,
in-process on the accelerator's own devices otherwise (reported as not
run with fewer than four) — token parity with the single-device engine
plus the per-shard Eq. (3)/(4) traffic split. The phase-breakdown section
splits each configuration's wall clock into host / device / compile shares from
the engine's phase accounting (``repro.obs``) for fp32-vs-qmc decode and
cached-vs-uncached prefill. The cost-attribution section re-runs the
fp32-vs-qmc decode pair under ``obs.costs`` capture: per step width it
reports measured wall seconds against the XLA-cost roofline bound
(drift, arithmetic intensity) plus the Eq. (3)/(4) *modeled* bytes /
energy / latency per token — the measured-vs-modeled bridge open
roadmap item 1 is judged against. The speculative section runs
self-speculative greedy decode at k ∈ {2, 4} against the plain greedy
baseline: acceptance rate, tokens/s (paired-ratio vs greedy), token
parity, plus a sampled row (temperature > 0 through the fused
in-jit sampling head). The pipeline section runs a decode-heavy
workload through the sync round loop vs ``pipelined=True`` (dispatch/
retire overlap with on-device token carry): paired tokens/s ratio,
token parity, host-blocked wall share on both sides, and the overlap /
barrier / lag-trim counters.

  PYTHONPATH=src python -m benchmarks.serving

``BENCH_SERVING_OUT=path`` redirects the JSON; ``BENCH_SECTIONS=a,b``
runs only the named sections (CI's drift check runs a fast subset). The
JSON names the device it ran on; a section that records an error makes
the bench exit nonzero.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import numpy as np

from repro.core.qconfig import QMCConfig
from repro.core.serving_quant import quantize_for_serving
from repro.memsys.workload import (kv_traffic_chunked, kv_traffic_paged,
                                   kv_traffic_prefix, make_traffic,
                                   shard_serve_traffic)
from repro.models.config import ModelConfig
from repro.models.model import init_params
from repro.obs import costs as obs_costs
from repro.serve.engine import LegacyServeEngine, Request, ServeEngine
from repro.serve.sampling import SamplingParams

OUT = os.environ.get(
    "BENCH_SERVING_OUT",
    os.path.join(os.path.dirname(__file__), "..", "BENCH_serving.json"))


def _enabled(section: str) -> bool:
    """BENCH_SECTIONS=a,b limits the run to the named sections (default:
    all) — CI's warn-only drift step runs a fast subset this way."""
    sel = os.environ.get("BENCH_SECTIONS")
    if not sel:
        return True
    return section in {s.strip() for s in sel.split(",") if s.strip()}

CFG_KW = dict(name="serve-bench", family="dense", n_layers=2,
              d_model=128, n_heads=8, n_kv_heads=2, d_ff=256, vocab=256)
# Steady-state runs per measurement; the fastest (min-wall) run is
# reported, the timeit convention: on a shared CPU host the lower
# envelope is the repeatable number, the mean is scheduler noise. The
# ratio gates (weights qmc-vs-fp32, prefix-cache speedup) compare two
# ~50 ms walls — single-shot ratios swing ±15% run to run.
REPEATS = int(os.environ.get("BENCH_REPEATS", "3"))
CFG = ModelConfig(**CFG_KW)
N_REQ = 8
MAX_NEW = 16
MAX_LEN = 64
PAGE = 16
SYS_PROMPT_LEN = 32               # shared multi-tenant prefix (2 pages)


def _requests(seed: int = 7):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(2, CFG.vocab,
                                        size=int(L)).astype(np.int32),
                    max_new_tokens=MAX_NEW)
            for i, L in enumerate(rng.integers(8, 24, size=N_REQ))]


def _tenant_requests(seed: int = 11):
    """N tenants: one shared system prompt + a short unique user turn."""
    rng = np.random.default_rng(seed)
    sys_prompt = rng.integers(2, CFG.vocab, SYS_PROMPT_LEN)
    return [Request(uid=i,
                    prompt=np.concatenate(
                        [sys_prompt,
                         rng.integers(2, CFG.vocab, int(L))]
                    ).astype(np.int32),
                    max_new_tokens=MAX_NEW)
            for i, L in enumerate(rng.integers(6, 14, size=N_REQ))]


def _pcts(lat):
    """p50/p95 of a latency sample list; zeros (not a crash) when the
    sample is empty — callers mark those sections degenerate."""
    lat = np.asarray(lat, dtype=float).ravel()
    if lat.size == 0:
        return 0.0, 0.0
    return (float(np.percentile(lat, 50)), float(np.percentile(lat, 95)))


def _engine_row(eng, out) -> dict:
    toks = sum(len(r.out_tokens) for r in out)
    lat = eng.stats.per_token_latencies()
    p50, p95 = _pcts(lat)
    return {"tokens": toks, "tokens_per_s": toks / eng.stats.wall_s,
            "wall_s": eng.stats.wall_s, "decode_calls":
            eng.stats.decode_steps, "prefills": eng.stats.prefills,
            "p50_token_latency_us": p50 * 1e6,
            "p95_token_latency_us": p95 * 1e6,
            "latency_samples": len(lat),
            "degenerate": len(lat) == 0,
            "preemptions": eng.stats.preemptions,
            "pages_peak": eng.stats.pages_peak}


def _measure(engine_cls, params, slots: int, **kw):
    # warm-up run pays every jit compile; then REPEATS steady-state runs,
    # fastest wall reported (see REPEATS above)
    engine_cls(CFG, params, slots=slots, max_len=MAX_LEN, **kw).run(
        _requests())
    eng, out = None, None
    for _ in range(REPEATS):
        e = engine_cls(CFG, params, slots=slots, max_len=MAX_LEN, **kw)
        o = e.run(_requests())
        if eng is None or e.stats.wall_s < eng.stats.wall_s:
            eng, out = e, o
    return _engine_row(eng, out)


def _paired_ratio(make_a, make_b, reqs_fn):
    """Median of per-pair throughput ratios b/a over REPEATS interleaved
    run pairs. The two configurations execute back-to-back inside each
    pair, so slow host drift (frequency scaling, neighbour load — the
    dominant noise on ~50 ms walls) hits both sides of a pair about
    equally and cancels in the ratio; the median then rejects the odd
    pair a burst split. Independent min-walls do not get that
    cancellation. Returns ((eng_a, res_a), (eng_b, res_b), ratio) where
    each (eng, res) is that side's fastest run."""
    best = [None, None]
    ratios = []
    for r in range(REPEATS):
        tps = [0.0, 0.0]
        order = (0, 1) if r % 2 == 0 else (1, 0)   # cancel ordering bias
        for i in order:
            eng = (make_a, make_b)[i]()
            res = eng.run(reqs_fn())
            toks = sum(len(rq.out_tokens) for rq in res)
            tps[i] = toks / eng.stats.wall_s
            if best[i] is None or eng.stats.wall_s < best[i][0].stats.wall_s:
                best[i] = (eng, res)
        ratios.append(tps[1] / max(tps[0], 1e-9))
    return best[0], best[1], float(np.median(ratios))


def run() -> dict:
    params = init_params(CFG, jax.random.PRNGKey(0))
    results = {"config": {"model": CFG.name, "n_requests": N_REQ,
                          "max_new_tokens": MAX_NEW, "max_len": MAX_LEN,
                          "page": PAGE, "repeats": REPEATS}}
    if _enabled("slots"):
        results["slots"] = {}
        for slots in (1, 4, 8):
            legacy = _measure(LegacyServeEngine, params, slots)
            paged = _measure(ServeEngine, params, slots, page_size=PAGE)
            speedup = paged["tokens_per_s"] / max(legacy["tokens_per_s"],
                                                  1e-9)
            results["slots"][str(slots)] = {"legacy": legacy,
                                            "paged": paged,
                                            "speedup": speedup}
            print(f"serving/legacy_s{slots},"
                  f"{legacy['p50_token_latency_us']:.0f},"
                  f"{legacy['tokens_per_s']:.1f}tok/s")
            print(f"serving/paged_s{slots},"
                  f"{paged['p50_token_latency_us']:.0f},"
                  f"{paged['tokens_per_s']:.1f}tok/s "
                  f"speedup={speedup:.2f}x")
    if _enabled("paged_kv_traffic"):
        # batch-dependent KV stream once every request is full-length
        lens = [len(r.prompt) + MAX_NEW for r in _requests()]
        t = kv_traffic_paged(CFG, lens, page=PAGE)
        results["paged_kv_traffic"] = {
            "n_pages": t.n_pages,
            "kv_bits_per_step": t.kv_bits_per_step,
            "frag_bits_per_step": t.frag_bits_per_step,
            "utilization": t.utilization}
    if _enabled("prefix_cache"):
        results["prefix_cache"] = {
            "sys_prompt_len": SYS_PROMPT_LEN,
            "slots": {str(s): _measure_prefix(params, s) for s in (4, 8)}}
    if _enabled("weights"):
        results["weights"] = _measure_weights(params)
    if _enabled("paged_attention"):
        results["paged_attention"] = _measure_paged_attention(params)
    if _enabled("chunked_prefill"):
        results["chunked_prefill"] = _measure_chunked(params)
    if _enabled("phase_breakdown"):
        results["phase_breakdown"] = _measure_phases(params)
    if _enabled("cost_attribution"):
        results["cost_attribution"] = _measure_costs(params)
    if _enabled("speculative"):
        results["speculative"] = _measure_speculative(params)
    if _enabled("pipeline"):
        results["pipeline"] = _measure_pipeline(params)
    if _enabled("sharded"):
        results["sharded"] = _measure_sharded()
    results["device"] = {"platform": jax.devices()[0].platform,
                         "kind": jax.devices()[0].device_kind,
                         "count": len(jax.devices())}
    with open(OUT, "w") as f:
        json.dump(results, f, indent=2)
    print(f"serving/json,0,{os.path.abspath(OUT)}")
    failed = [k for k, v in results.items()
              if isinstance(v, dict) and "error" in v]
    if failed:
        raise SystemExit(f"bench sections failed: {failed}")
    return results


def _measure_prefix(params, slots: int) -> dict:
    """Shared-system-prompt tenants, prefix cache on vs off."""
    out = {}
    # warm-up pays jit compiles; the measured engines start with an
    # initially empty index (intra-batch sharing only). Interleaved
    # paired runs so the speedup ratio cancels host drift.
    for on in (False, True):
        ServeEngine(CFG, params, slots=slots, max_len=MAX_LEN,
                    page_size=PAGE, prefix_cache=on).run(_tenant_requests())

    def mk(on):
        return lambda: ServeEngine(CFG, params, slots=slots,
                                   max_len=MAX_LEN, page_size=PAGE,
                                   prefix_cache=on)
    best_off, best_on, speedup = _paired_ratio(mk(False), mk(True),
                                               _tenant_requests)
    for label, (eng, res) in (("off", best_off), ("on", best_on)):
        toks = sum(len(r.out_tokens) for r in res)
        s = eng.stats
        out[label] = {
            "tokens": toks, "tokens_per_s": toks / s.wall_s,
            "prefill_tokens": s.prefill_tokens,
            "prefill_tokens_padded": s.prefill_tokens_padded,
            "prompt_tokens": s.prompt_tokens,
            "hit_rate": s.hit_rate,
            "prefill_token_reduction": s.prefill_token_reduction,
            "cache_hits": s.cache_hits,
            "cow_copies": s.cow_copies,
            "tables_rebuilds": s.device_tables_rebuilds,
            "page_op_flushes": s.page_op_flushes,
            "page_op_round_trips_saved": s.page_op_round_trips_saved,
            "solo_rounds": s.solo_rounds}
    out["prefill_speedup"] = speedup
    # DSE views. "cold": the measured batch's prefill WRITES (the first
    # tenant publishes, the rest hit). "steady": residency once the
    # prefix is resident — every tenant aliases the shared pages,
    # including the publisher, whose copy IS the shared set (listing it
    # as a miss would double-count those pages).
    reqs = _tenant_requests()
    prompt_lens = [len(r.prompt) for r in reqs]
    sys_cached = (SYS_PROMPT_LEN // PAGE) * PAGE
    cold = kv_traffic_prefix(
        CFG, prompt_lens, [0] + [sys_cached] * (len(reqs) - 1), page=PAGE)
    steady = kv_traffic_prefix(
        CFG, prompt_lens, [sys_cached] * len(reqs), page=PAGE)
    out["dse"] = {
        "hit_rate": cold.hit_rate,
        "prefill_write_bits": cold.prefill_write_bits,
        "prefill_write_bits_nocache": cold.prefill_write_bits_nocache,
        "saved_prefill_write_bits": cold.saved_prefill_write_bits,
        "resident_bits": steady.resident_bits,
        "resident_bits_nocache": steady.resident_bits_nocache,
        "n_pages": steady.n_pages,
        "n_pages_nocache": steady.n_pages_nocache}
    print(f"serving/prefix_s{slots},0,"
          f"hit={out['on']['hit_rate']:.2f} "
          f"prefill_reduction={out['on']['prefill_token_reduction']:.2f} "
          f"speedup={speedup:.2f}x")
    return out


def _measure_weights(params) -> dict:
    """Dense fp32 vs QMC serving-format weights through the paged engine —
    the paper's deployment configuration (eMEM-resident quantized weights
    feeding the bandwidth-bound decode loop) tracked alongside dense."""
    qparams = quantize_for_serving(
        params, QMCConfig(rho=0.3, granularity="subtile"), tp_shards=1,
        min_dim=64)
    # warm-up pays jit compiles (shared: both variants lower to the same
    # dense step via the exec-weight plan) + the qmc plan build
    for p in (params, qparams):
        ServeEngine(CFG, p, slots=4, max_len=MAX_LEN,
                    page_size=PAGE).run(_requests())
    best_f, best_q, ratio = _paired_ratio(
        lambda: ServeEngine(CFG, params, slots=4, max_len=MAX_LEN,
                            page_size=PAGE),
        lambda: ServeEngine(CFG, qparams, slots=4, max_len=MAX_LEN,
                            page_size=PAGE),
        _requests)
    out = {"fp32": _engine_row(*best_f), "qmc": _engine_row(*best_q),
           "qmc_vs_fp32_tokens_per_s": ratio}
    print(f"serving/weights_qmc_s4,"
          f"{out['qmc']['p50_token_latency_us']:.0f},"
          f"{out['qmc']['tokens_per_s']:.1f}tok/s "
          f"vs_fp32={out['qmc_vs_fp32_tokens_per_s']:.2f}x")
    return out


def _measure_paged_attention(params) -> dict:
    """Pallas page-table kernel vs the XLA full-width reference gather.

    Records token parity, tokens/s (interpret-mode kernel on CPU — the
    wall-clock column is meaningful on a TPU backend only), and the
    gather-work split the kernel changes: live pages actually streamed vs
    the full block-table width the reference materializes, counted by the
    engine per decode step AND charged by the DSE
    (``kv_traffic_paged(live_only=...)``) so the two accounts are shown
    side by side."""
    def timed(**kw):
        # warm-up pays jit compiles; the timed second run also supplies
        # the parity tokens and gather-work counters (no extra runs)
        ServeEngine(CFG, params, slots=4, max_len=MAX_LEN,
                    page_size=PAGE, **kw).run(_requests())
        eng = ServeEngine(CFG, params, slots=4, max_len=MAX_LEN,
                          page_size=PAGE, **kw)
        res = eng.run(_requests())
        p50, p95 = _pcts(eng.stats.per_token_latencies())
        row = {"tokens": sum(len(r.out_tokens) for r in res),
               "tokens_per_s": eng.stats.tokens_per_s,
               "decode_calls": eng.stats.decode_steps,
               "p50_token_latency_us": p50 * 1e6,
               "p95_token_latency_us": p95 * 1e6}
        return row, [r.out_tokens for r in res], eng
    out = {}
    out["reference"], ref_toks, _ = timed()
    out["kernel"], kern_toks, eng = timed(paged_attention=True)
    s = eng.stats
    out["token_parity"] = ref_toks == kern_toks
    out["gather_work"] = {
        "kv_pages_live": s.kv_pages_live,
        "kv_pages_full_width": s.kv_pages_full,
        "live_fraction": s.kv_pages_live / max(s.kv_pages_full, 1)}
    # DSE view at the moment every request is full length
    lens = [len(r.prompt) + MAX_NEW for r in _requests()]
    mpps = eng.max_pages_per_seq
    live = kv_traffic_paged(CFG, lens, page=PAGE)
    wide = kv_traffic_paged(CFG, lens, page=PAGE, live_only=False,
                            max_pages_per_seq=mpps)
    out["dse"] = {
        "kv_bits_per_step_live": live.kv_bits_per_step,
        "kv_bits_per_step_full_width": wide.kv_bits_per_step,
        "dead_page_bits_per_step": (wide.kv_bits_per_step
                                    - live.kv_bits_per_step)}
    print(f"serving/paged_attention_s4,"
          f"{out['kernel']['p50_token_latency_us']:.0f},"
          f"parity={out['token_parity']} "
          f"live_pages={s.kv_pages_live}/{s.kv_pages_full} "
          f"({1 - out['gather_work']['live_fraction']:.0%} gather saved)")
    return out


CHUNK = 16


def _mixed_requests(seed: int = 17):
    """Long-prompt + short-decode interactive mix: the workload where
    monolithic prefill stalls in-flight decodes (TTFT/ITL jitter)."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(N_REQ):
        long_ = i % 4 == 0                 # every 4th request: long prompt
        L = 44 if long_ else int(rng.integers(4, 10))
        reqs.append(Request(
            uid=i, prompt=rng.integers(2, CFG.vocab, L).astype(np.int32),
            max_new_tokens=MAX_NEW))
    return reqs


def _measure_chunked(params) -> dict:
    """Chunked vs monolithic prefill through the ONE ragged step.

    Both modes run the same unified step (ragged Pallas kernel); only the
    chunk width differs — monolithic covers any prompt in one chunk,
    chunked splits long prompts so decode lanes keep emitting between
    chunks. Reports TTFT and ITL p50/p95 for the mixed workload, the
    live-gather page counts the prefill chunks streamed (engine counters)
    and the matching chunk-granular Eq. (3)/(4) DSE account."""
    out = {}
    toks = {}
    for label, chunk in (("monolithic", None), ("chunked", CHUNK)):
        ServeEngine(CFG, params, slots=4, max_len=MAX_LEN, page_size=PAGE,
                    chunk_tokens=chunk, paged_attention=True).run(
            _mixed_requests())         # warm-up pays the jit compiles
        eng = ServeEngine(CFG, params, slots=4, max_len=MAX_LEN,
                          page_size=PAGE, chunk_tokens=chunk,
                          paged_attention=True)
        res = eng.run(_mixed_requests())
        s = eng.stats
        ttft50, ttft95 = _pcts(s.ttft_s)
        # ITL from per-request emission timestamps: a decode lane's true
        # gap between consecutive tokens, including rounds it sat out
        # while a co-scheduled prefill chunk ran — the jitter chunking is
        # supposed to bound. The round-averaged step latency (wall/tokens
        # per round) is kept alongside: it hides exactly that jitter.
        itl = s.itl_s()
        itl50, itl95 = _pcts(itl)
        ravg50, ravg95 = _pcts(s.per_token_latencies())
        toks[label] = [r.out_tokens for r in res]
        out[label] = {
            "tokens": sum(len(r.out_tokens) for r in res),
            "tokens_per_s": s.tokens_per_s,
            "prefill_chunks": s.prefill_chunks,
            "ttft_p50_us": ttft50 * 1e6, "ttft_p95_us": ttft95 * 1e6,
            "itl_p50_us": itl50 * 1e6, "itl_p95_us": itl95 * 1e6,
            "itl_samples": len(itl),
            "itl_round_avg_p50_us": ravg50 * 1e6,
            "itl_round_avg_p95_us": ravg95 * 1e6,
            "prefill_kv_pages_live": s.prefill_kv_pages_live,
            "prefill_kv_pages_written": s.prefill_kv_pages_written}
    out["token_parity"] = toks["monolithic"] == toks["chunked"]
    # chunk-granular DSE view of the same prompts (page-for-page with the
    # engine counters — pinned by tests/test_memsys.py)
    lens = [len(r.prompt) for r in _mixed_requests()]
    t_chunk = [kv_traffic_chunked(CFG, L, chunk=CHUNK, page=PAGE)
               for L in lens]
    out["dse"] = {
        "kv_pages_read": sum(t.kv_pages_read for t in t_chunk),
        "kv_pages_written": sum(t.kv_pages_written for t in t_chunk),
        "kv_pages_read_monolithic": sum(t.kv_pages_read_monolithic
                                        for t in t_chunk),
        "prefill_kv_bits": sum(t.kv_read_bits + t.kv_write_bits
                               for t in t_chunk)}
    print(f"serving/chunked_prefill_c{CHUNK},"
          f"{out['chunked']['itl_p95_us']:.0f},"
          f"parity={out['token_parity']} "
          f"ttft_p95={out['chunked']['ttft_p95_us']:.0f}us"
          f"(mono {out['monolithic']['ttft_p95_us']:.0f}us) "
          f"chunk_pages={out['chunked']['prefill_kv_pages_live']}")
    return out


def _phase_row(cold, eng) -> dict:
    """Phase shares of one warm run + compile attribution from the cold
    run that preceded it (same engine geometry, fresh jit cache)."""
    s = eng.stats
    wall = max(s.wall_s, 1e-9)
    cold_wall = max(cold.stats.wall_s, 1e-9)
    return {
        "wall_s": s.wall_s, "rounds": s.rounds,
        "tokens_per_s": s.tokens_per_s,
        "host_s": s.host_seconds(), "device_s": s.device_seconds(),
        "host_share": s.host_seconds() / wall,
        "device_share": s.device_seconds() / wall,
        "phase_seconds": {k: round(v, 6)
                          for k, v in sorted(s.phase_seconds.items())},
        "adopt_calls": s.adopt_calls,
        "page_copy_calls": s.page_copy_calls,
        "device_tables_rebuilds": s.device_tables_rebuilds,
        "jit_compiles_warm": s.jit_compiles,
        "cold_jit_compiles": cold.stats.jit_compiles,
        "cold_compile_s": cold.stats.jit_compile_s,
        "cold_compile_share": cold.stats.jit_compile_s / cold_wall}


def _measure_phases(params) -> dict:
    """Where a round's wall time goes: host bookkeeping vs device step vs
    jit compilation, from the engine's always-on ``phase_seconds``
    accounting (no tracer needed). Two comparisons the open roadmap items
    hinge on: fp32-vs-qmc decode (is the qmc slowdown device math or host
    overhead?) and cached-vs-uncached multi-tenant prefill (how much of
    the prefix-cache regression is adopt/COW/table host round trips?)."""
    def pair(p, reqs_fn, **kw):
        cold = ServeEngine(CFG, p, slots=4, max_len=MAX_LEN,
                           page_size=PAGE, **kw)
        cold.run(reqs_fn())            # pays the jit compiles
        eng = ServeEngine(CFG, p, slots=4, max_len=MAX_LEN,
                          page_size=PAGE, **kw)
        eng.run(reqs_fn())             # steady state
        return _phase_row(cold, eng)

    qparams = quantize_for_serving(
        params, QMCConfig(rho=0.3, granularity="subtile"), tp_shards=1,
        min_dim=64)
    out = {"decode": {"fp32": pair(params, _requests),
                      "qmc": pair(qparams, _requests)},
           "prefill": {"uncached": pair(params, _tenant_requests),
                       "cached": pair(params, _tenant_requests,
                                      prefix_cache=True)}}
    d, p = out["decode"], out["prefill"]
    print(f"serving/phases_decode_s4,0,"
          f"fp32_host={d['fp32']['host_share']:.0%} "
          f"qmc_host={d['qmc']['host_share']:.0%} "
          f"qmc_device={d['qmc']['device_share']:.0%}")
    print(f"serving/phases_prefix_s4,0,"
          f"uncached_host={p['uncached']['host_share']:.0%} "
          f"cached_host={p['cached']['host_share']:.0%} "
          f"adopts={p['cached']['adopt_calls']} "
          f"tbl_rebuilds={p['cached']['device_tables_rebuilds']}")
    return out


def _measure_costs(params) -> dict:
    """fp32-vs-qmc decode under ``obs.costs`` capture at the same slot
    count: per step width, measured wall seconds against the XLA-cost
    roofline bound (drift / roofline fraction / arithmetic intensity)
    plus the Eq. (3)/(4) modeled bytes/energy/latency per token from the
    run's own engine counters — measured and modeled side by side.

    Fresh engines per label: capture keys on call shapes each TracedJit
    wrapper has seen, so it fires even over the lru-warm jit cache; the
    warm-up engine absorbs the compiles so the measured engine's
    per-shape wall tables are steady state."""
    qparams = quantize_for_serving(
        params, QMCConfig(rho=0.3, granularity="subtile"), tp_shards=1,
        min_dim=64)
    prev = obs_costs.enable_capture()
    try:
        out = {}
        for label, p in (("fp32", params), ("qmc", qparams)):
            ServeEngine(CFG, p, slots=4, max_len=MAX_LEN,
                        page_size=PAGE).run(_requests())
            eng = ServeEngine(CFG, p, slots=4, max_len=MAX_LEN,
                              page_size=PAGE)
            eng.run(_requests())
            out[label] = eng.last_cost_report.to_dict()
    finally:
        obs_costs.enable_capture(prev)
    ratio = (out["qmc"]["modeled"]["bytes_per_token"]
             / max(out["fp32"]["modeled"]["bytes_per_token"], 1e-9))
    out["qmc_vs_fp32_modeled_bytes_per_token"] = ratio
    step_rows = [r for r in out["qmc"]["fns"] if r["fn"] == "step"]
    frac = max((r["roofline_fraction"] for r in step_rows), default=0.0)
    print(f"serving/cost_attr_s4,0,"
          f"qmc_vs_fp32_modeled_bytes={ratio:.3f}x "
          f"qmc_step_roofline_frac={frac:.2e} "
          f"qmc_modeled="
          f"{out['qmc']['modeled']['bytes_per_token'] / 1e3:.1f}KB/tok")
    return out


def _spec_requests(seed: int = 23):
    """Workload for prompt-lookup speculation: half the prompts carry a
    repeated n-gram (the draft's bread and butter — instruction templates,
    code, quoted context), half are uniform-random (its worst case), so
    the acceptance rate is a blend rather than a best-case headline."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(N_REQ):
        if i % 2 == 0:
            core = rng.integers(2, CFG.vocab, 5)
            prompt = np.tile(core, 4)[:18]
        else:
            prompt = rng.integers(2, CFG.vocab, int(rng.integers(8, 24)))
        reqs.append(Request(uid=i, prompt=prompt.astype(np.int32),
                            max_new_tokens=MAX_NEW))
    return reqs


def _measure_speculative(params) -> dict:
    """Jitted sampling head + self-speculative greedy decode.

    Greedy baseline vs self-speculative at k ∈ {2, 4}: acceptance rate,
    verify-round counts, tokens/s, and token parity (speculative greedy
    must be token-identical to plain greedy at every k — acceptance only
    changes WHEN tokens appear, never WHICH). The headline ratio
    ``tokens_per_s_vs_greedy`` comes from interleaved k=4/greedy pairs
    (see ``_paired_ratio``); on this tiny CPU model the verify rung costs
    about as much as the C=1 decode step it replaces, so the ratio mostly
    reflects acceptance — on a bandwidth-bound edge target the verify
    step rereads the weights once for 1+k tokens and the same acceptance
    buys real speedup. A sampled row (temperature>0 through the fused
    sampling head) tracks the sampling epilogue's overhead vs greedy."""
    def mk(k):
        return lambda: ServeEngine(CFG, params, slots=4, max_len=MAX_LEN,
                                   page_size=PAGE, speculative_k=k)
    # warm-up pays the jit compiles (C=1 decode plus each verify rung)
    for k in (0, 2, 4):
        mk(k)().run(_spec_requests())
    best_g, best_k4, ratio = _paired_ratio(mk(0), mk(4), _spec_requests)
    g_eng, g_res = best_g
    g_toks = [r.out_tokens for r in g_res]
    out = {"greedy": _engine_row(g_eng, g_res)}
    for k in (2, 4):
        if k == 4:
            eng, res = best_k4
        else:
            eng = mk(k)()
            res = eng.run(_spec_requests())
        s = eng.stats
        out[f"k{k}"] = {
            "tokens": sum(len(r.out_tokens) for r in res),
            "tokens_per_s": s.tokens_per_s,
            "rounds": s.rounds,
            "spec_rounds": s.spec_rounds,
            "draft_tokens": s.spec_draft_tokens,
            "accepted_tokens": s.spec_accepted_tokens,
            "acceptance_rate": s.spec_acceptance_rate,
            "token_parity_vs_greedy":
                [r.out_tokens for r in res] == g_toks}
    out["tokens_per_s_vs_greedy"] = ratio
    # sampled path: same engine geometry, fused sampling head active
    sp = SamplingParams(temperature=0.8, top_k=16, top_p=0.95, seed=7)
    ServeEngine(CFG, params, slots=4, max_len=MAX_LEN, page_size=PAGE,
                sampling=sp).run(_spec_requests())
    eng = ServeEngine(CFG, params, slots=4, max_len=MAX_LEN,
                      page_size=PAGE, sampling=sp)
    res = eng.run(_spec_requests())
    out["sampled"] = {"temperature": sp.temperature, "top_k": sp.top_k,
                      "top_p": sp.top_p,
                      "tokens": sum(len(r.out_tokens) for r in res),
                      "tokens_per_s": eng.stats.tokens_per_s}
    print(f"serving/speculative_s4,0,"
          f"k4_accept={out['k4']['acceptance_rate']:.2f} "
          f"parity={out['k4']['token_parity_vs_greedy']} "
          f"vs_greedy={ratio:.2f}x")
    return out


def _pipeline_requests(seed: int = 31):
    """Decode-heavy workload: short prompts, long generations — the
    steady-state regime where round N's host planning can hide behind
    round N-1's device step + readback."""
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(2, CFG.vocab,
                                        int(L)).astype(np.int32),
                    max_new_tokens=32)
            for i, L in enumerate(rng.integers(4, 10, size=N_REQ))]


def _measure_pipeline(params) -> dict:
    """Sync vs pipelined round loop at slots=8 on the decode-heavy mix.

    The paired ratio is the headline (``tokens_per_s_vs_sync``); the
    per-side rows split each wall into host-blocked vs device share —
    pipelining is supposed to move ``block_until_ready`` wait out of
    the host-blocked column, so the pipelined side's
    ``host_blocked_share`` should drop even when the CPU backend's
    tokens/s gain is modest (device work and host work contend for the
    same cores here; on an accelerator the overlap is real
    concurrency). Token parity is asserted per run — the pipeline is a
    scheduling change, never a decoding change."""
    def mk(pipelined):
        return lambda: ServeEngine(CFG, params, slots=8, max_len=MAX_LEN,
                                   page_size=PAGE, pipelined=pipelined)
    for p in (False, True):            # warm-up pays the jit compiles
        mk(p)().run(_pipeline_requests())
    best_s, best_p, ratio = _paired_ratio(mk(False), mk(True),
                                          _pipeline_requests)

    def row(eng, res):
        s = eng.stats
        wall = max(s.wall_s, 1e-9)
        return {"tokens": sum(len(r.out_tokens) for r in res),
                "tokens_per_s": s.tokens_per_s,
                "rounds": s.rounds,
                "host_s": s.host_seconds(),
                "host_blocked_share": s.host_seconds() / wall,
                "device_s": s.device_seconds(),
                "pipelined_rounds": s.pipelined_rounds,
                "pipeline_overlap": s.pipeline_overlap,
                "pipeline_barriers": s.pipeline_barriers,
                "lag_trimmed_tokens": s.lag_trimmed_tokens}

    out = {"sync": row(*best_s), "pipelined": row(*best_p),
           "token_parity": ([r.out_tokens for r in best_s[1]]
                            == [r.out_tokens for r in best_p[1]]),
           "tokens_per_s_vs_sync": ratio}
    print(f"serving/pipeline_s8,0,"
          f"vs_sync={ratio:.2f}x "
          f"parity={out['token_parity']} "
          f"overlap={out['pipelined']['pipeline_overlap']:.0%} "
          f"host_share={out['sync']['host_blocked_share']:.0%}"
          f"->{out['pipelined']['host_blocked_share']:.0%} "
          f"trimmed={out['pipelined']['lag_trimmed_tokens']}")
    return out


def _sharded_runs(spec: dict) -> dict:
    """Single-device vs (2 data, 2 model) mesh paged engine on the same
    config and workload: tokens/s, decode calls, token parity."""
    from repro.launch import mesh as meshlib
    cfg = ModelConfig(**spec["cfg"])

    def requests():
        return [Request(uid=i, prompt=np.asarray(p, np.int32),
                        max_new_tokens=spec["max_new"])
                for i, p in enumerate(spec["prompts"])]

    params = init_params(cfg, jax.random.PRNGKey(0))
    out, toks = {}, {}
    for label, mesh in (("1dev", None),
                        ("mesh2x2", meshlib.make_mesh((2, 2),
                                                      ("data", "model")))):
        # one engine, two runs: mesh step sets are built per engine (only
        # the mesh=None builders are lru-shared), so a fresh engine would
        # pay its jit compiles inside the timed run — reuse the warmed
        # engine instead (stats reset per run(), and with no prefix cache
        # no state carries)
        eng = ServeEngine(cfg, params, slots=8, max_len=spec["max_len"],
                          page_size=spec["page"], mesh=mesh)
        eng.run(requests())               # warm-up pays jit compiles
        reqs = requests()
        eng.run(reqs)
        toks[label] = [r.out_tokens for r in reqs]
        out[label] = {"tokens_per_s": eng.stats.tokens_per_s,
                      "decode_calls": eng.stats.decode_steps}
    out["token_parity"] = toks["1dev"] == toks["mesh2x2"]
    return out


# child process of a CPU parent: the forced host device count must be set
# before jax initializes, and the parent has already initialized it
_SHARDED_CHILD = r"""
import json, os
from benchmarks.serving import _sharded_runs
print("RESULT" + json.dumps(_sharded_runs(
    json.loads(os.environ["BENCH_SHARDED_SPEC"]))))
"""


def _measure_sharded() -> dict:
    """Paged engine on a (2, 2) mesh + the per-shard DSE traffic split.

    On a CPU parent the mesh runs in a child process on four forced host
    devices. Anywhere else the parent holds the accelerator (a child
    could not reach it), so the mesh runs in-process on the parent's own
    devices, or not at all when there are fewer than four."""
    spec = {"cfg": CFG_KW, "page": PAGE, "max_len": MAX_LEN,
            "max_new": MAX_NEW,
            "prompts": [r.prompt.tolist() for r in _requests()]}
    if jax.default_backend() != "cpu":
        if len(jax.devices()) < 4:
            print("serving/sharded_2x2,0,not run: needs 4 devices")
            return {"not_run": "needs 4 devices"}
        out = _sharded_runs(spec)
    else:
        root = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "..")
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(root, "src"), root, env.get("PYTHONPATH", "")])
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env["JAX_PLATFORMS"] = "cpu"
        env["BENCH_SHARDED_SPEC"] = json.dumps(spec)
        try:
            proc = subprocess.run([sys.executable, "-c", _SHARDED_CHILD],
                                  env=env, capture_output=True, text=True,
                                  timeout=1200)
        except subprocess.TimeoutExpired:
            return {"error": "sharded subprocess timed out"}
        line = [l for l in proc.stdout.splitlines()
                if l.startswith("RESULT")]
        if proc.returncode != 0 or not line:
            return {"error": proc.stderr[-2000:]}
        out = json.loads(line[0][len("RESULT"):])
    print(f"serving/sharded_2x2,0,"
          f"{out['mesh2x2']['tokens_per_s']:.1f}tok/s "
          f"parity={out['token_parity']}")
    # per-shard Eq.(3)/(4) streams: what ONE device of the (2,2) mesh pulls
    base = make_traffic(CFG, "qmc", seq_len=MAX_LEN)
    lens = [len(r.prompt) + MAX_NEW for r in _requests()]
    paged = kv_traffic_paged(CFG, lens, page=PAGE)
    per_dev = shard_serve_traffic(paged.apply(base), data_shards=2,
                                  model_shards=2)
    out["per_shard_dse"] = {
        "name": per_dev.name,
        "weight_bits_per_step": per_dev.weight_bits,
        "kv_bits_per_step": per_dev.kv_bits,
        "act_bits_per_step": per_dev.act_bits,
        "aggregate_kv_bits_per_step": paged.kv_bits_per_step}
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    print("name,us_per_call,derived")
    run()
