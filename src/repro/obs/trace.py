"""Span tracer exporting Chrome trace-event JSON (Perfetto-loadable).

One :class:`Tracer` records the serving stack's phase structure as
complete ("X") duration events plus instant ("i") point events, in the
Chrome ``traceEvents`` format — load the exported file in Perfetto
(https://ui.perfetto.dev) or ``chrome://tracing``. Timestamps are
microseconds from the tracer's construction (``time.perf_counter``
based), one track per Python thread.

Every span an enabled tracer records also opens a
``jax.profiler.TraceAnnotation`` of the same name for its duration, so
a ``jax.profiler`` capture taken around the run holds the engine's spans
on its host plane, on the device events' clock (jax is imported on the
first traced span; this module imports without it). Pure host-side
otherwise: tracing a round costs a handful of ``perf_counter`` calls,
annotation enters/exits and dict appends; a *disabled* tracer costs one
branch per call site (the engine's acceptance bar is < 2 % tokens/s
overhead with tracing off).

Span / event naming contract (what later PRs must follow)
---------------------------------------------------------
Engine rounds — ``round``: one complete event per pass of the round
loop (``serve/engine.py``), args ``lanes`` / ``prefill_lanes`` /
``decode_lanes`` / ``emitted`` and the step's ``c_len``, ``positions``
(rows x ``c_len`` computed) and ``useful`` (sum of ``n_new``) on the
passes that launched a step; a pass that only admits, idles or drains
the pipeline carries no args.

Engine round phases — complete events, one per round, non-overlapping
and strictly inside their round's wall window, emitted by
``serve/engine.py``:

  * ``round/admit``        — scheduler round start + admissions: prefix
    /dedup matching, page adopts, COW ``page_copy`` dispatches and SSM
    ``reset_state`` dispatches for newly seated requests.
  * ``round/grant``        — chunk-budget grants + page allocation for
    every planned lane, including eviction/preemption fallout.
  * ``round/host_prep``    — building the step's host arrays (tokens /
    start / n_new), gather-work accounting and ``install_tables``
    (block-table validation + host→device upload).
  * ``round/device_step``  — the whole synchronous step: its four
    ``round/step/*`` parts below (token selection runs inside the jit);
    on a cold geometry this span absorbs the jit compile (see
    ``jit/compile`` instants).
  * ``round/emit``         — token emission, stats, streaming callbacks,
    publish/finish/requeue bookkeeping.

Pipelined engines (``ServeEngine(pipelined=True)``) add two phases and
relax the window rule for one of them:

  * ``round/dispatch``     — the async step dispatch of an overlapped
    round: device-token carry + enqueue + ``copy_to_host_async``, NO
    ``block_until_ready`` (that is the point). Backends that bound
    their in-flight queue (CPU XLA) can still block the enqueue on the
    previous round's compute, so ``EngineStats`` charges this span as
    device wait, not host work.
  * ``round/retire``       — readback-complete + emission of the
    PREVIOUS round. A pipelined retire necessarily lands inside the
    NEXT round's wall window — the one sanctioned exception to the
    "strictly inside their round" rule above; synchronous engines never
    emit these two spans and keep the original contract bit-for-bit.

Step parts — nested inside the phase that runs the step
(``round/device_step``; ``round/dispatch`` holds the first two and
``round/retire`` the last two on the pipelined path), in this order and
tiling ``round/device_step``. Their seconds accumulate in
``EngineStats.step_part_seconds``, apart from the phases':

  * ``round/step/upload``   — every device input of the step: tokens
    (or the pipelined device-token carry), ``start``, ``n_new``, the
    sampling-lane copies, and the exec-weight tree.
  * ``round/step/launch``   — the jitted ``step`` / ``solo_step`` call
    until it returns, then the enqueue of its two outputs' device→host
    copies (args: ``c_len``, ``rows``, ``solo``).
  * ``round/step/wait``     — ``block_until_ready`` on its outputs.
  * ``round/step/readback`` — finishing those copies of the selected
    tokens and logprobs into full-width host arrays.

Collector — ``round/gc``: one complete event per run of Python's cyclic
collector while an enabled tracer's :meth:`Tracer.collector_spans` is
open (``ServeEngine.run`` opens it); args ``generation``, ``collected``.
It nests inside whatever span was running when the collector started.

Request lifecycle — instant events with ``uid`` (and ``slot``) args,
emitted by ``serve/engine.py``:

  * ``req/admitted``    — seated into a slot (args: cached prompt tokens
    adopted, dedup flag).
  * ``req/chunk_done``  — one prefill chunk scattered (args: pos after).
  * ``req/first_token`` — first emission; exactly ONCE per request even
    across preemption/recompute (TTFT's clock rule).
  * ``req/preempted``   — recompute-style eviction; emitted tokens were
    discarded.
  * ``req/finished``    — terminal emission (args: n tokens out).

Scheduler / cache / jit events:

  * ``sched/dedup_wait`` — admission head waiting for an in-flight
    identical prompt's prefill (``serve/scheduler.py``).
  * ``sched/miss_wait``  — admission head serialized behind the one
    open prefix-cache miss (``serve/scheduler.py``).
  * ``cache/published``  — prefill pages inserted into the radix index
    (``serve/prefix_cache.py``; args: n new pages).
  * ``cache/evicted``    — index pages LRU-evicted under pressure
    (``serve/prefix_cache.py``; args: n pages).
  * ``jit/compile``      — a serving jit traced a new shape
    (``serve/steps.py`` TracedJit; args: fn, cache size, seconds).
  * ``jit/unexpected_retrace`` — cache growth beyond the step's declared
    compile surface: the late-flag-flip bug class, surfaced instead of
    silently stalling a round 10x.

Counter tracks — "C" events rendering as value lanes on the timeline:

  * ``pool/pages``  — per round (``serve/engine.py``): arena pages
    ``live`` / ``free`` — pool pressure next to the phase spans.
  * ``sched/queue`` — per round (``serve/engine.py``):
    ``prefill_pending`` admission-queue depth.
"""
from __future__ import annotations

import gc
import json
import os
import threading
import time
from typing import List, Optional

_TraceAnnotation = None


def _annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` of ``name`` (not yet entered);
    jax is imported on the first call."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation
        _TraceAnnotation = TraceAnnotation
    return _TraceAnnotation(name)


class _NullSpan:
    """Shared no-op context manager for disabled tracers."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("tracer", "name", "args", "t0", "ann")

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        self.tracer, self.name, self.args = tracer, name, args

    def __enter__(self):
        self.ann = _annotation(self.name)
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.ann.__exit__(*exc)
        self.tracer._complete(self.name, self.t0, t1 - self.t0, self.args)
        return False


class _CollectorSpans:
    """Records each run of Python's cyclic collector as a ``round/gc``
    span while open: a ``gc.callbacks`` hook, removed on exit."""

    __slots__ = ("tracer", "hook", "span")

    def __init__(self, tracer: "Tracer"):
        self.tracer, self.span = tracer, None
        self.hook = self._on_gc

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self.span = self.tracer.span("round/gc",
                                         generation=info["generation"])
            self.span.__enter__()
        elif self.span is not None:
            span, self.span = self.span, None
            span.args["collected"] = info["collected"]
            span.__exit__(None, None, None)

    def __enter__(self):
        gc.callbacks.append(self.hook)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self.hook)
        return False


class Tracer:
    """Chrome-trace span/instant recorder.

    ``enabled=False`` (and the module-default tracer until someone turns
    it on) makes every recording method a constant-time no-op returning
    shared objects — instrument call sites unconditionally and let the
    flag decide. All recording is in-memory (a list of small dicts);
    :meth:`export` writes the ``{"traceEvents": [...]}`` JSON object.
    Appends are guarded by a lock only on the shared event list (a
    re-entrant one: a collection that starts inside an append records
    its own span); the timestamp math is per-call-site."""

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self.events: List[dict] = []
        self._t0 = time.perf_counter()
        self._pid = os.getpid()
        self._lock = threading.RLock()

    # ---- recording -----------------------------------------------------
    def _ts(self, t: float) -> float:
        return (t - self._t0) * 1e6

    def span(self, name: str, **args):
        """Context manager timing a phase; records one "X" event and
        holds a profiler annotation of the same name while open."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, args)

    def collector_spans(self):
        """Context manager: while open, each collection of Python's
        cyclic collector is recorded as a ``round/gc`` span. A disabled
        tracer installs nothing."""
        if not self.enabled:
            return _NULL_SPAN
        return _CollectorSpans(self)

    def _complete(self, name: str, t0: float, dur_s: float,
                  args: dict) -> None:
        ev = {"name": name, "ph": "X", "ts": self._ts(t0),
              "dur": dur_s * 1e6, "pid": self._pid,
              "tid": threading.get_ident()}
        if args:
            ev["args"] = args
        with self._lock:
            self.events.append(ev)

    def annotation(self, name: str):
        """A profiler annotation of ``name`` (enter it) for a span the
        caller times itself and records with :meth:`complete`; a no-op
        on a disabled tracer."""
        if not self.enabled:
            return _NULL_SPAN
        return _annotation(name)

    def complete(self, name: str, t0: float, dur_s: float,
                 **args) -> None:
        """Record an already-measured span (``t0`` in perf_counter
        seconds). A live span timed this way holds :meth:`annotation`
        while it runs; else time it with :meth:`span`."""
        if self.enabled:
            self._complete(name, t0, dur_s, args)

    def instant(self, name: str, **args) -> None:
        """Record a point occurrence (thread-scoped "i" event)."""
        if not self.enabled:
            return
        ev = {"name": name, "ph": "i", "s": "t",
              "ts": self._ts(time.perf_counter()), "pid": self._pid,
              "tid": threading.get_ident()}
        if args:
            ev["args"] = args
        with self._lock:
            self.events.append(ev)

    def counter(self, name: str, **values) -> None:
        """Record a Chrome counter-track sample ("C" event)."""
        if not self.enabled:
            return
        ev = {"name": name, "ph": "C",
              "ts": self._ts(time.perf_counter()), "pid": self._pid,
              "args": values}
        with self._lock:
            self.events.append(ev)

    # ---- export --------------------------------------------------------
    def to_json(self) -> dict:
        return {"traceEvents": list(self.events),
                "displayTimeUnit": "ms"}

    def export(self, path: str) -> int:
        """Write the Chrome trace JSON; returns the event count."""
        with open(path, "w") as f:
            json.dump(self.to_json(), f)
        return len(self.events)

    def clear(self) -> None:
        with self._lock:
            self.events.clear()


# ---------------------------------------------------------------------------
# process-default tracer: instrumentation sites not handed an explicit
# tracer (scheduler events, steps.py jit wrappers, prefix-cache eviction)
# record here. Disabled until ``set_tracer`` installs an enabled one
# (``launch/serve.py --trace-out`` does), so by default every call site
# is a single-branch no-op.
# ---------------------------------------------------------------------------
_DEFAULT = Tracer(enabled=False)


def get_tracer() -> Tracer:
    return _DEFAULT


def set_tracer(tracer: Tracer) -> Tracer:
    """Swap the process-default tracer; returns the previous one."""
    global _DEFAULT
    prev, _DEFAULT = _DEFAULT, tracer
    return prev


def active(tracer: Optional[Tracer]) -> Tracer:
    """Resolve an instrumentation site's tracer: the explicit one it was
    handed, else the process default."""
    return tracer if tracer is not None else _DEFAULT
