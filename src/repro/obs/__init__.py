"""Observability: tracing, metrics, and cost-drift detection.

Three pillars (docs/observability.md):

* :mod:`repro.obs.trace` — request-scoped spans over the whole
  solve→compile→serve path, emitted as thread-safe JSONL;
* :mod:`repro.obs.metrics` — counter/gauge/histogram registry with
  latency percentiles and Prometheus-style text exposition;
* :mod:`repro.obs.drift` — instrumented per-node execution of compiled
  plans, predicted-vs-observed EWMA drift scores, and targeted
  recalibration of the flagged calibration entries.

``trace`` and ``metrics`` import nothing of the program (``trace``
imports :mod:`jax` lazily) so :mod:`repro.core` can import them.
``drift`` imports back into core/serving, so it is loaded lazily here
(module ``__getattr__``) — importing :mod:`repro.obs` from inside core
never recurses.
"""
from __future__ import annotations

from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      default_registry)
from .trace import Span, Tracer, configure, get_tracer

__all__ = [
    "Span", "Tracer", "get_tracer", "configure",
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "default_registry",
    "drift", "InstrumentedNet", "DriftDetector", "plan_predictions",
]

#: names resolved from the lazily-imported drift module
_DRIFT_NAMES = ("InstrumentedNet", "DriftDetector", "DriftEntry",
                "plan_predictions")


def __getattr__(name):
    if name == "drift" or name in _DRIFT_NAMES:
        import importlib
        drift = importlib.import_module(".drift", __name__)
        if name == "drift":
            return drift
        return getattr(drift, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
