"""One run of one benchmark cell: set-up, measured window, metrics, check.

Everything specific to a configuration, a traffic mix, a cell or a
per-layer metric lives in files found by name:

  bench/configs/<config>.json   sizes, QMC format, weight seed (the file
                                ``BENCHMARK.json`` names for the config)
  bench/references/<name>.py    the block's plain reference and its
                                operation and byte counts, named by the
                                config's ``"reference"`` key
  bench/traffic/<traffic>.json  parameters of the one traffic generator
  bench/cells/<workload>.json   the limit of the correctness check
  bench/metrics/<metric>.py     ``read(run)`` -> number, dict or None

so a later change adds a cell by adding files and a ``workloads`` entry.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import importlib.util
import json
import os
import re
import shutil
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from benchlib import peaks, reference, trace_reduce, traffic
from benchlib import weights as wcache

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
SRC = os.path.join(ROOT, "src")
CACHE_DIR = os.path.join(BENCH_DIR, ".cache")
# the serving step's compiled programs (the unified ragged step and its
# single-lane form), by the names XLA gives their modules
STEP_PROGRAMS = ("jit_step", "jit_solo")
# what an architecture module under bench/references/ defines (see
# benchlib/reference.py)
REFERENCE_API = ("hidden_states", "head", "logits", "matmuls", "other_bytes",
                 "state_bytes", "attn_flops", "seq_attn_flops")


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the cell: BENCHMARK.json entry plus its files
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    config_path: str
    arch: object          # the config's module under bench/references/
    mix: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_cell(workload: str, root: str = ROOT,
              bench_file: str = "BENCHMARK.json") -> Cell:
    with open(os.path.join(root, bench_file)) as f:
        bench = json.load(f)
    wl = {w["name"]: w for w in bench["workloads"]}.get(workload)
    if wl is None:
        raise KeyError(f"no workload {workload!r} in {bench_file}")
    cfg_entry = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    cfg_path = os.path.join(root, cfg_entry["file"])
    with open(cfg_path) as f:
        config = json.load(f)
    arch = reference_module(config, cfg_path, root)
    mix = traffic.load_mix(os.path.join(root, "bench", "traffic",
                                        f"{wl['traffic']}.json"))
    with open(os.path.join(root, "bench", "cells",
                           f"{workload}.json")) as f:
        limits = json.load(f)

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]

    return Cell(name=workload, chips=wl["chips"], config=config,
                config_path=cfg_path, arch=arch, mix=mix, limits=limits,
                end_to_end=[m for m in bench["end_to_end"] if mine(m)],
                per_layer=[m for m in bench["per_layer"] if mine(m)])


def reference_module(config: dict, config_path: str, root: str = ROOT):
    """The module ``bench/references/<name>.py`` that the configuration's
    ``"reference"`` key names. There is no default: a configuration
    without the key, or naming a module that is not there or lacks part
    of the interface, is refused."""
    name = config.get("reference")
    if not isinstance(name, str) or not re.fullmatch(r"\w[\w.-]*", name):
        raise ValueError(f"{config_path}: needs a \"reference\" key naming "
                         f"its module under bench/references/, got {name!r}")
    path = os.path.join(root, "bench", "references", f"{name}.py")
    if not os.path.isfile(path):
        raise ValueError(f"{config_path}: reference {name!r} has no module "
                         f"{path}")
    mod = _load_module(os.path.realpath(path), f"bench_reference_{name}")
    missing = [f for f in REFERENCE_API if not callable(getattr(mod, f, None))]
    if missing:
        raise ValueError(f"{config_path}: reference module {path} lacks "
                         f"{', '.join(missing)}")
    return mod


@functools.lru_cache(maxsize=None)
def _load_module(path: str, name: str):
    """Each module file once a process, so that its jitted functions keep
    their compiled programs from one cell to the next."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: str = ROOT):
    """``read`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(root, "bench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------


def device_record(chips: int, allow_cpu: bool = False) -> dict:
    import jax
    devs = jax.devices()
    dev = devs[0]
    rec = {"platform": dev.platform, "kind": dev.device_kind,
           "count": len(devs)}
    log(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    if not allow_cpu and (dev.platform != "tpu" or len(devs) < chips):
        raise NoAccelerator(
            f"cell needs {chips} TPU chip(s); JAX found {len(devs)} "
            f"{dev.platform} device(s)")
    return rec


def memory_peak_bytes() -> Optional[int]:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return int(peak) if peak is not None else None


def configure_compile_cache() -> str:
    """JAX's persistent compilation cache where the program keeps it
    (``repro.launch.compile_cache``: ``JAX_COMPILATION_CACHE_DIR`` when
    set, else a fixed directory inside the checkout), with every program
    cached, however small or quick to compile."""
    import jax

    from repro.launch.compile_cache import configure_compile_cache as where
    path = where()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


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


# ---------------------------------------------------------------------------
# set-up: weights, engine, warm-up
# ---------------------------------------------------------------------------


def serve_args(cell: Cell):
    """The CLI's own defaults, with the cell's model, weights and sizes."""
    from repro.launch.serve import parse_args
    cfg, mix = cell.config, cell.mix
    max_prompt, max_new = traffic.max_lengths(mix)
    argv = ["--arch", cfg["arch"],
            "--reduced" if cfg.get("reduced_preset") else "--no-reduced",
            "--weights", cfg["weights"]["format"],
            "--rho", str(cfg["weights"]["rho"]),
            "--seed", str(cfg["weight_seed"]),
            "--slots", str(mix["slots"]),
            "--prompt-len", str(max_prompt),
            "--new-tokens", str(max_new)]
    return parse_args(argv)


def check_model_matches(cell: Cell, model_cfg) -> None:
    """The program's ModelConfig must be the file's configuration."""
    want = cell.config["model"]
    for key, val in want.items():
        got = getattr(model_cfg, key)
        if got != val:
            raise ValueError(f"{cell.config_path}: {key}={val!r} but the "
                             f"program's {model_cfg.name} has {got!r}")


def warm_widths(eng, mix: dict) -> List[int]:
    """Step widths the cell's traffic can reach, besides C = 1: the ladder
    rung of each pool prompt while every round's grants fit the engine's
    prefill budget whole (no prompt is ever split), else every rung up to
    that of the longest prompt."""
    from repro.serve.steps import width_ladder
    ladder = width_ladder(eng.chunk)

    def rung(n):
        return min(w for w in ladder if w >= n)

    sizes = traffic.pool_sizes(mix)
    longest = max(p for p, _ in sizes)
    if mix["slots"] * longest <= eng.max_prefill_tokens:
        return sorted({rung(p) for p, _ in sizes})
    return [w for w in ladder if w <= rung(longest)]


def warm_up(eng, mix: dict, vocab: int, seed: int) -> int:
    """Run every width once, as one lane and as two (the solo and the
    batched step), through the engine's own ``run``. Returns the number
    of ``run`` calls made."""
    from repro.serve.engine import Request
    widths = warm_widths(eng, mix)
    # distinct prompts, so that in-flight dedup cannot alias the lanes
    toks = traffic.warmup_tokens(seed, 2 * max(widths), vocab)
    n = 0
    for w in widths:
        for k in ((1, 2) if mix["slots"] > 1 else (1,)):
            eng.run([Request(uid=-(i + 1), prompt=toks[i * w:(i + 1) * w],
                             max_new_tokens=2) for i in range(k)])
            n += 1
    return n


# ---------------------------------------------------------------------------
# the measured window
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CallRecord:
    t0: float
    t1: float
    requests: list
    stats: object


def run_window(eng, cell: Cell, seed: int, seconds: float, vocab: int,
               annotate=None) -> List[CallRecord]:
    """Closed-loop ``run()`` calls in whole cycles of the traffic pool, so
    that every seed serves the same sizes: the window ends with the cycle
    whose end lies nearest to ``seconds`` (one cycle at least)."""
    from repro.serve.engine import Request
    it = traffic.calls(cell.mix, vocab, seed)
    per_cycle = traffic.calls_per_cycle(cell.mix)
    records: List[CallRecord] = []
    uid = 0
    t_start = time.monotonic()
    while True:
        reqs = []
        for prompt, out in next(it):
            reqs.append(Request(uid=uid, prompt=prompt, max_new_tokens=out))
            uid += 1
        t0 = time.monotonic()
        if annotate is not None:
            with annotate("bench/call"):
                eng.run(reqs)
        else:
            eng.run(reqs)
        records.append(CallRecord(t0, time.monotonic(), reqs, eng.stats))
        if len(records) % per_cycle == 0:
            elapsed = time.monotonic() - t_start
            cycle = elapsed / (len(records) // per_cycle)
            if elapsed + cycle / 2 >= seconds:
                return records


def request_outcomes(records: List[CallRecord], vocab: int):
    """(ttft seconds, inter-token gaps, output tokens, failed count)."""
    ttft, itl, out_tokens, failed = [], [], 0, 0
    for rec in records:
        emit = rec.stats.emit_times
        for r in rec.requests:
            times = emit.get(r.uid, [])
            ok = (len(r.out_tokens) == r.max_new_tokens
                  and len(times) == len(r.out_tokens)
                  and all(0 <= t < vocab for t in r.out_tokens))
            if not ok:
                failed += 1
                continue
            ttft.append(times[0] - rec.t0)
            itl.extend(b - a for a, b in zip(times, times[1:]))
            out_tokens += len(r.out_tokens)
    return ttft, itl, out_tokens, failed


def end_to_end(records: List[CallRecord], vocab: int,
               setup_s: float) -> Dict[str, float]:
    ttft, itl, out_tokens, _ = request_outcomes(records, vocab)
    window = records[-1].t1 - records[0].t0
    vals = {"setup_s": setup_s,
            "output_tok_s": out_tokens / window}
    if ttft:
        vals["ttft_p95_ms"] = float(np.percentile(ttft, 95)) * 1e3
    if itl:
        vals["itl_p95_ms"] = float(np.percentile(itl, 95)) * 1e3
    return vals


# ---------------------------------------------------------------------------
# correctness: the served tokens against the plain reference
# ---------------------------------------------------------------------------


def check_sample(records: List[CallRecord], n: int, seed: int):
    """The finished request with the most served tokens, and n - 1 more
    drawn from the seed."""
    done = [r for rec in records for r in rec.requests if r.out_tokens]
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: len(done[i].out_tokens))
    rest = [i for i in range(len(done)) if i != longest]
    rng = traffic.sample_rng(seed)
    pick = [longest] + [int(i) for i in
                        rng.permutation(rest)[:max(0, n - 1)]]
    return [(np.asarray(done[i].prompt), list(done[i].out_tokens))
            for i in pick]


def check(cell: Cell, sample) -> Dict[str, float]:
    gaps = reference.served_gaps(cell.arch, cell.config["model"],
                                 cell.config["weights"],
                                 cell.config["weight_seed"], sample)
    return {"max_logit_gap": float(np.max(gaps["served"])),
            "tokens_checked": int(gaps["served"].size)}


# ---------------------------------------------------------------------------
# per-layer metrics (--trace 1)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RunView:
    """What a per-layer metric reader may look at."""
    records: List[CallRecord]
    window_s: float
    slots: int
    page: int
    model: dict
    arch: object          # the config's module under bench/references/
    weights: dict
    kv_dtype: str
    peaks: dict
    trace: Optional[dict]


def engine_spans(tracer, sync_ts_us: float, window_start_ns: float):
    """The engine's round-phase spans on the profiler's clock."""
    out = []
    for ev in tracer.events:
        if ev.get("ph") == "X" and ev["name"].startswith("round/"):
            s = window_start_ns + (ev["ts"] - sync_ts_us) * 1e3
            out.append((ev["name"], s, s + ev["dur"] * 1e3))
    return out


def traced_window(eng, tracer, cell, seed, seconds, vocab, trace_dir):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    shutil.rmtree(trace_dir, ignore_errors=True)
    with jax.profiler.trace(trace_dir, profiler_options=opts):
        with jax.profiler.TraceAnnotation("bench/window"):
            t_sync = time.perf_counter()
            tracer.complete("bench/sync", t_sync, 0.0)
            records = run_window(eng, cell, seed, seconds, vocab,
                                 annotate=jax.profiler.TraceAnnotation)
    sync_ts = [e["ts"] for e in tracer.events
               if e["name"] == "bench/sync"][0]
    pd = trace_reduce.load(trace_dir)
    summary = None
    if pd is not None:
        win = [s for n, s, _ in trace_reduce.host_spans(pd)
               if n == trace_reduce.WINDOW_SPAN]
        extra = engine_spans(tracer, sync_ts, win[0]) if win else []
        summary = trace_reduce.reduce(pd, extra)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return records, summary


def per_layer(cell: Cell, view: RunView, root: str = ROOT
              ) -> Dict[str, dict]:
    out = {}
    for m in cell.per_layer:
        val = metric_reader(m["name"], root)(view)
        if val is None:
            continue
        entry = dict(val) if isinstance(val, dict) else {"value": val}
        entry["unit"] = m["unit"]
        out[m["name"]] = entry
    return out


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


@dataclasses.dataclass
class Setup:
    cell: Cell
    device: dict
    eng: object
    params: object
    tracer: object
    vocab: int
    setup_s: float


def build(cell: Cell, seed: int, trace: bool, t_process: float, *,
          cache_dir: str = CACHE_DIR, allow_cpu: bool = False,
          persistent_cache: bool = True) -> Setup:
    """Weights, engine and warm-up: everything before the window."""
    device = device_record(cell.chips, allow_cpu)
    init_s = time.monotonic() - t_process
    if persistent_cache:
        log(f"compile_cache={configure_compile_cache()}")
    events = CacheEvents()

    from repro.configs import get_config, reduced_config
    from repro.launch.serve import build_engine
    from repro.obs import trace as obs_trace

    sargs = serve_args(cell)
    model_cfg = (reduced_config(sargs.arch) if sargs.reduced
                 else get_config(sargs.arch))
    check_model_matches(cell, model_cfg)
    key = wcache.cache_key(cell.config_path, cell.config["weight_seed"], SRC)
    params, winfo = wcache.load_or_quantize(sargs, cache_dir, key)
    t_eng = time.monotonic()
    tracer = obs_trace.Tracer(enabled=trace)
    eng = build_engine(sargs, model_cfg, params, tracer)
    engine_s = time.monotonic() - t_eng
    t_warm = time.monotonic()
    n_warm = warm_up(eng, cell.mix, model_cfg.vocab, seed)
    warm_s = time.monotonic() - t_warm
    setup_s = time.monotonic() - t_process
    log(f"setup_s={setup_s:.3f} init_s={init_s:.3f} "
        f"weights={winfo['weights']} "
        + " ".join(f"{k}={v:.3f}" for k, v in winfo.items()
                   if k != "weights")
        + f" engine_s={engine_s:.3f} warmup_s={warm_s:.3f} "
        f"warmup_calls={n_warm} widths={warm_widths(eng, cell.mix)} "
        f"compile_cache_hits={events.hits} misses={events.misses}")
    tracer.clear()
    return Setup(cell, device, eng, params, tracer, model_cfg.vocab,
                 setup_s)


def window_parts(records: List[CallRecord]) -> dict:
    """Where the window's seconds went, for the log: request starts to
    first tokens, gaps between tokens (and their excess over twice the
    median gap, which is where a host stall shows), last tokens to call
    ends, and time between calls."""
    ttft = gaps = tails = between = 0.0
    all_gaps = []
    for i, rec in enumerate(records):
        times = sorted(t for r in rec.requests
                       for t in rec.stats.emit_times.get(r.uid, []))
        if not times:
            continue
        ttft += times[0] - rec.t0
        tails += rec.t1 - times[-1]
        all_gaps.extend(b - a for a, b in zip(times, times[1:]))
        if i:
            between += rec.t0 - records[i - 1].t1
    gaps = float(sum(all_gaps))
    med = float(np.median(all_gaps)) if all_gaps else 0.0
    excess = float(sum(g - 2 * med for g in all_gaps if g > 2 * med))
    top = sorted(all_gaps)[-3:]
    return {"first_s": ttft, "gaps_s": gaps, "gap_median_ms": med * 1e3,
            "gap_excess_s": excess,
            "gap_top_ms": ",".join(f"{g * 1e3:.1f}" for g in top),
            "tails_s": tails, "between_s": between}


def window_summary(records: List[CallRecord], vocab: int) -> dict:
    attempted = sum(len(r.requests) for r in records)
    _, _, out_tok, failed = request_outcomes(records, vocab)
    info = {"window_s": records[-1].t1 - records[0].t0,
            "calls": len(records), "requests": attempted,
            "failed": failed, "output_tokens": out_tok,
            "compiles_in_window": sum(r.stats.jit_compiles
                                      for r in records)}
    log(" ".join(f"{k}={v:.3f}" if isinstance(v, float) else f"{k}={v}"
                 for k, v in info.items()))
    log("window_parts " + " ".join(
        f"{k}={v:.4f}" if isinstance(v, float) else f"{k}={v}"
        for k, v in window_parts(records).items()))
    return info


def verdict(cell: Cell, failed: int, sample, readings: dict) -> tuple:
    """(correct, the numbers compared beside their limits)."""
    limit = cell.limits["max_logit_gap"]
    correct = (failed == 0 and bool(sample)
               and readings["max_logit_gap"] <= limit)
    checks = {"max_logit_gap": {"value": readings["max_logit_gap"],
                                "limit": limit},
              "failed_requests": {"value": failed, "limit": 0}}
    return correct, checks


def run(opts, t_process: float, *, root: str = ROOT,
        bench_file: str = "BENCHMARK.json", cache_dir: str = CACHE_DIR,
        allow_cpu: bool = False, persistent_cache: bool = True) -> dict:
    """One run of one cell; returns the result line's object."""
    cell = load_cell(opts.workload, root, bench_file)
    st = build(cell, opts.seed, bool(opts.trace), t_process,
               cache_dir=cache_dir, allow_cpu=allow_cpu,
               persistent_cache=persistent_cache)
    device = st.device
    summary = None
    if opts.trace:
        trace_dir = os.path.join(cache_dir, "trace", opts.workload)
        records, summary = traced_window(st.eng, st.tracer, cell, opts.seed,
                                         opts.seconds, st.vocab, trace_dir)
    else:
        records = run_window(st.eng, cell, opts.seed, opts.seconds,
                             st.vocab)
    info = window_summary(records, st.vocab)
    device["memory_peak_bytes"] = memory_peak_bytes()

    if opts.trace:
        view = RunView(records, info["window_s"], cell.mix["slots"],
                       st.eng.page, cell.config["model"], cell.arch,
                       cell.config["weights"], cell.config["kv_dtype"],
                       None if allow_cpu else peaks.peaks_for(
                           device["kind"]), summary)
        metrics = per_layer(cell, view, root)
        if summary is not None:
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
    else:
        vals = end_to_end(records, st.vocab, st.setup_s)
        metrics = {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in vals}

    sample = check_sample(records, cell.mix["check_requests"], opts.seed)
    # free the program's state before the reference runs on the device
    del st
    gc.collect()
    t_ref = time.monotonic()
    readings = check(cell, sample)
    log(f"reference_s={time.monotonic() - t_ref:.3f} "
        f"tokens_checked={readings['tokens_checked']}")
    correct, checks = verdict(cell, info["failed"], sample, readings)
    result = {"correct": correct, "attempted": info["requests"],
              "failed": info["failed"], "metrics": metrics,
              "device": device}
    if opts.trace and summary is not None:
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["checks"] = checks
    return result


def print_result(result: dict) -> None:
    for name, c in result["checks"].items():
        log(f"check {name}={c['value']!r} limit={c['limit']!r}")
    print(json.dumps(result), flush=True)


def main(argv=None, t_process: Optional[float] = None) -> int:
    t_process = time.monotonic() if t_process is None else t_process
    opts = parse(argv)
    try:
        result = run(opts, t_process)
    except NoAccelerator as e:
        log(f"refused: {e}")
        return 2
    print_result(result)
    return 0
