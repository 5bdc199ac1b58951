"""Unified telemetry layer: metrics registry, request-lifecycle tracing,
and kernel-dispatch profiling.

Three pillars, all host-side and dependency-free (no jax import at module
scope, so the kernels/core layers can hook in without cycles):

  * :mod:`repro.obs.metrics` — typed counters / gauges / bounded-reservoir
    histograms behind a :class:`MetricsRegistry`, with JSON snapshot and
    Prometheus text exposition. The serving engine, block pool, tuning
    cache, and benches all emit through it.
  * :mod:`repro.obs.trace` — span/event tracer exporting Chrome-trace /
    Perfetto JSON. Spans wrap ``jax.profiler.TraceAnnotation`` so host
    spans line up with XLA device profiles; tracing never adds a device
    round-trip.
  * :mod:`repro.obs.scopes` — the map from a compiled program's ops to the
    model's named scopes (``mpgemm``, ``attention``, ``lm_head``), which
    puts a device trace's time down to them.
  * :mod:`repro.obs.dispatch` — trace-time kernel-dispatch recorder:
    which (shape-key, fusion, blocks) actually dispatched, tuned vs
    heuristic, per jitted-program trace.

See docs/OBSERVABILITY.md for the span taxonomy, the scopes, metric
names/units, and the overhead contract as measured on the chip.
"""

from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               export_stats)
from repro.obs.trace import Tracer, validate_chrome_trace
from repro.obs import dispatch, scopes

__all__ = ["Counter", "Gauge", "Histogram", "MetricsRegistry",
           "export_stats", "Tracer", "validate_chrome_trace", "dispatch",
           "scopes"]
