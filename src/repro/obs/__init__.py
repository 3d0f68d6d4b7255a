"""Observability plane: metrics registry, request tracing, export.

Three modules threaded through every serving tier:

- :mod:`repro.obs.metrics` — process-local thread-safe registry
  (counters / gauges / fixed-bucket log2 histograms) with pre-bound
  handles so the hot path allocates nothing; ``snapshot()`` → plain dict.
- :mod:`repro.obs.trace` — per-request spans minted at admission and
  handed down the tiers, finished records in a bounded ring, exported as
  JSON or Chrome ``trace_event``; and the pipeline's stages.
- :mod:`repro.obs.export` — the one snapshot/dump path
  (``launch/serve.py --obs-dump``), plus the registry-backed view
  ``cache_stats_view``.

``set_enabled(False)`` flips metrics, tracing and stages to cheap no-ops
(contract: obs-on serves bit-identically to obs-off). What the stages cost
with obs on and no profiler session, on one TPU v5e in ``bench/run.py``'s
cells (medians of three 20-s runs a side, against the same code without
stages): bulk screening -1.0% (IDL) and +1.1% (RH) reads/s, interactive
p95 latency -6.5%, all within the run-to-run noise.
"""

from repro.obs import export, metrics, trace
from repro.obs.export import cache_stats_view, chrome_events, dump, \
    snapshot, traces_of
from repro.obs.metrics import Counter, Gauge, Histogram, Registry, \
    counter_total, gauge_total
from repro.obs.trace import Span, TraceContext, Tracer


def set_enabled(enabled: bool) -> None:
    """Master switch for the process-local default registry + tracer."""
    metrics.set_enabled(enabled)
    trace.set_enabled(enabled)


def reset() -> None:
    """Zero the default registry and clear the default tracer's ring —
    test isolation and per-stream deltas in the benches."""
    metrics.reset()
    trace.DEFAULT.clear()
    trace.DEFAULT.close_open_spans(status="error", error="obs_reset")


__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "Registry",
    "Span",
    "TraceContext",
    "Tracer",
    "cache_stats_view",
    "chrome_events",
    "counter_total",
    "dump",
    "export",
    "gauge_total",
    "metrics",
    "reset",
    "set_enabled",
    "snapshot",
    "trace",
    "traces_of",
]
