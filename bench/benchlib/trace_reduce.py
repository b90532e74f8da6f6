"""Reduce a JAX profiler trace to the numbers the benchmark reports.

Input is what ``jax.profiler.ProfileData`` reads from an ``.xplane.pb``:
planes with lines with events (``name``, ``start_ns``, ``duration_ns``).
Only duck-typed attribute access is used, so tests feed small synthetic
traces through the same code.

* Device planes are ``/device:TPU:<n>``. Their ``XLA Ops`` line gives the
  intervals in which an operation ran; their ``XLA Modules`` line gives
  one event per execution of a compiled program. An op event's name is
  its HLO instruction; the op table keys it by the instruction's name and
  first operand type, and leaves out control-flow ops (``while``) whose
  span covers their body.
* The host plane carries the benchmark's own ``TraceAnnotation`` spans,
  named ``bench/...``; ``bench/window`` bounds the traced window.
* Busy time is the union of op intervals inside the window, averaged over
  the device planes; idle gaps are the holes in that union.
"""
from __future__ import annotations

import glob
import os
import re
import statistics
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench/window"
_SUFFIX = re.compile(r"\(\d+\)$")
# first operand type after the opcode: " fusion(f32[24,2048]{..."
_OPERAND = re.compile(r" [a-z][\w-]*\((\w+\[[\d,]*\])")
# control-flow ops whose event spans the ops of their body
CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)?$")


def program_name(event_name: str) -> str:
    """``jit_step(42)`` -> ``jit_step``: a program's stable name."""
    return _SUFFIX.sub("", event_name).strip()


def op_name(event_name: str) -> str:
    """``%fusion.56 = f32[8]{0} fusion(f32[24,2048]{1,0} %p, ...)`` ->
    ``fusion.56``, the instruction's name."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


def op_label(event_name: str) -> str:
    """The instruction's name with the type of its first operand, which
    says what it reads: ``fusion.56(f32[24,2048])``."""
    m = _OPERAND.search(event_name)
    return op_name(event_name) + (f"({m.group(1)})" if m else "")


def load(trace_dir: str):
    """The newest ``.xplane.pb`` under ``trace_dir``, read by JAX."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return None
    return ProfileData.from_file(max(files, key=os.path.getmtime))


def union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    """Merged [start, end] intervals, ascending."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo: float, hi: float) -> List[List[float]]:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy: Sequence[Sequence[float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """Idle (start, end) holes of a merged busy list inside [lo, hi]."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def host_spans(pd, prefix: str = "bench/") -> List[Tuple[str, float, float]]:
    """(name, start_ns, end_ns) of host events whose name has ``prefix``."""
    out = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return out


def reduce(pd, extra_spans: Sequence[Tuple[str, float, float]] = ()
           ) -> Optional[dict]:
    """The trace's summary, or None when it holds no device operation or
    no ``bench/window`` span.

    ``extra_spans`` are more host spans (name, start_ns, end_ns) on the
    trace's clock, used only to label idle gaps."""
    spans = host_spans(pd)
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not win:
        return None
    lo, hi = win[0]
    devices = []
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        ops, modules = [], []
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops += [(ev.name, ev.start_ns, ev.duration_ns)
                        for ev in line.events]
            elif line.name == MODULES_LINE:
                modules += [(program_name(ev.name), ev.start_ns,
                             ev.duration_ns) for ev in line.events]
        if ops:
            devices.append((ops, modules))
    if not devices:
        return None
    window_ns = hi - lo
    busy_ns, all_gaps = [], []
    op_time: Dict[str, float] = {}
    module_durs: Dict[str, List[float]] = {}
    for ops, modules in devices:
        merged = clip(union((s, s + d) for _, s, d in ops), lo, hi)
        busy_ns.append(sum(e - s for s, e in merged))
        all_gaps += gaps(merged, lo, hi)
        for name, s, d in ops:
            if lo <= s < hi and not CONTAINER.match(op_name(name)):
                label = op_label(name)
                op_time[label] = op_time.get(label, 0.0) + d * 1e-9
        for name, s, d in modules:
            if lo <= s < hi:
                module_durs.setdefault(name, []).append(d * 1e-9)
    labels = [(n, s, e) for n, s, e in spans if n != WINDOW_SPAN]
    labels += list(extra_spans)
    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:10]
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": sum(busy_ns) / len(busy_ns) * 1e-9,
        "module_seconds": module_durs,
        "device_ops": sorted(op_time.items(), key=lambda kv: -kv[1])[:10],
        "idle_gaps": [[label_gap(labels, s, e), (e - s) * 1e-9]
                      for s, e in longest],
    }


def label_gap(spans: Sequence[Tuple[str, float, float]], s: float,
              e: float) -> str:
    """The innermost host span covering the gap's midpoint."""
    mid = (s + e) / 2
    cover = [(ee - ss, n) for n, ss, ee in spans if ss <= mid <= ee]
    return min(cover)[1] if cover else "outside any host span"


def median_seconds(summary: dict, programs: Sequence[str]) -> Optional[float]:
    """Median device duration of the executions of ``programs``."""
    durs = [d for name in programs
            for d in summary["module_seconds"].get(name, [])]
    return statistics.median(durs) if durs else None
