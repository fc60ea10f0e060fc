"""Process-wide observability: spans, roofline attribution, export, metrics.

The layer every other layer reports into (and nothing imports *from*
the rest of the stack at module scope, so any layer may import it):

* :mod:`repro.obs.trace` — low-overhead span tracer (ring buffer,
  injectable clock, one-branch no-op when disabled);
* :mod:`repro.obs.roofline` — per-contraction flops/bytes/intensity and
  achieved-vs-roofline attribution (the hardware ceilings live here);
* :mod:`repro.obs.export` — Chrome Trace Event JSON (Perfetto) and flat
  JSONL records (predictor training data), plus schema validation;
* :mod:`repro.obs.registry` — the MetricsRegistry unifying
  ServingMetrics, dispatcher, bucket-table and program-cache counters
  behind one snapshot API.

Capture a trace from the serving launcher::

    PYTHONPATH=src python -m repro.launch.serve --arch minicpm-2b --smoke \
        --requests 4 --max-new 4 --trace out.json

then open ``out.json`` in https://ui.perfetto.dev.  See
``docs/observability.md``.
"""

from repro.obs.registry import MetricsRegistry, get_registry, set_registry
from repro.obs.trace import (
    NULL_SPAN,
    Span,
    Tracer,
    disable_tracing,
    enable_tracing,
    enabled,
    get_tracer,
    instant,
    set_tracer,
    span,
)

__all__ = [
    "Tracer", "Span", "NULL_SPAN",
    "enabled", "enable_tracing", "disable_tracing",
    "get_tracer", "set_tracer", "span", "instant",
    "MetricsRegistry", "get_registry", "set_registry",
]
