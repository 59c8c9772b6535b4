"""repro.obs — unified telemetry: span tracing + metrics registry.

Stdlib-only by construction (no jax, no numpy): the streaming core, the
serve scheduler, and the fleet layer all instrument against this package,
and some of those modules must import before JAX initializes. Two halves:

* :mod:`repro.obs.trace` — bounded-ring span/instant tracer with an
  injectable clock, Chrome-trace/Perfetto JSON export, and an optional
  ``jax.profiler.TraceAnnotation`` bridge. The module-level default
  tracer is *disabled* unless ``REPRO_OBS=1`` (or ``configure``), and the
  disabled path is a preallocated no-op — safe on hot loops.
* :mod:`repro.obs.metrics` — labelled counter/gauge/histogram registry
  with per-thread accumulation, a ``snapshot()`` dict API that report
  columns derive from, and Prometheus-style text exposition.

On top of the two halves sits the judgement tier (PR 9):

* :mod:`repro.obs.slo` — declarative :class:`SloSpec` objectives and a
  multi-window burn-rate :class:`SloEngine` over registry snapshots,
  emitting ``slo_breach``/``budget_exhausted`` instants.
* :mod:`repro.obs.health` — fleet ``HealthReport`` (imported lazily by
  ``FleetScheduler.health()``/``scripts/healthz.py``; not re-exported
  here because its capacity model reaches into ``repro.core``).

See docs/ARCHITECTURE.md ("Observability layer" and "SLO & health
tier") for the span/metric taxonomy and the layering contract.
"""

from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    nearest_rank,
)
from repro.obs.slo import (
    SLO_KINDS,
    SloEngine,
    SloSpec,
    SloVerdict,
    default_serve_slos,
)
from repro.obs.trace import (
    Span,
    Tracer,
    configure,
    export_chrome,
    get_tracer,
    instant,
    span,
    validate_chrome_trace,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "nearest_rank",
    "SLO_KINDS",
    "SloEngine",
    "SloSpec",
    "SloVerdict",
    "default_serve_slos",
    "Span",
    "Tracer",
    "configure",
    "export_chrome",
    "get_tracer",
    "instant",
    "span",
    "validate_chrome_trace",
]
