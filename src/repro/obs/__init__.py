"""Serving observability: metrics, tracing, and cost attribution.

Three host-side modules the serving stack records itself through:

  * ``obs.metrics`` — counters / gauges / fixed-log-bucket histograms
    with labels, behind a get-or-create :class:`~repro.obs.metrics.
    Registry`; snapshot to JSON. The metric naming contract lives in
    its module docstring.
  * ``obs.trace`` — a span :class:`~repro.obs.trace.Tracer` (context-
    manager API, near-zero overhead when disabled, instant events for
    point occurrences, collector spans) exporting Chrome trace-event
    JSON loadable in Perfetto; each span it records is also a
    ``jax.profiler.TraceAnnotation``, so a profiler capture shows it on
    the device trace's clock. The span/event naming contract lives in
    its module docstring.
  * ``obs.costs`` — per-step cost attribution: opt-in capture of XLA
    ``cost_analysis()`` FLOPs/bytes per serving-jit call shape, roofline
    drift (measured wall vs bound), and the Eq. (3)/(4) modeled memory
    cost of the run's engine counters. Off by default (one bool branch
    per traced call); ``launch/serve.py --cost-report`` and the bench's
    ``cost_attribution`` section turn it on.

The first two keep a process-default instance (``get_registry`` /
``get_tracer``) so deep call sites — the steps.py jit-compile wrappers,
scheduler wait events — need no plumbing; engines and tests may pass
explicit instances instead. ``launch/serve.py
--trace-out/--metrics-out`` turns the defaults on and writes both files
after a run.
"""
from repro.obs.costs import (CostReport, FnCost,  # noqa: F401
                             attribute, capture_enabled, enable_capture,
                             modeled_memsys)
from repro.obs.metrics import (Counter, Gauge, Histogram,  # noqa: F401
                               Registry, get_registry, log_buckets,
                               set_registry)
from repro.obs.trace import (Tracer, active, get_tracer,  # noqa: F401
                             set_tracer)
