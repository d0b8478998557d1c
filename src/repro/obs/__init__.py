"""Observability for the serving → planner → kernel stack.

Three pieces, all stdlib+numpy only:

* :mod:`repro.obs.trace` — nestable-span request tracing with a bounded
  ring buffer and Chrome trace-event export, plus the thread-local
  observation context (``attach`` / ``stage``) instrumented library code
  records into without signature changes.
* :mod:`repro.obs.explain` — per-query plan explain built from
  ``QueryPlan`` / ``CandidateSet`` internals.
* :mod:`repro.obs.profile` — per-stage latency histograms
  (:class:`StageProfiler`) and cost-model drift accounting
  (:class:`CostDrift`).

Off-by-default-cheap: with no context attached, ``stage()`` is a shared
no-op; the serving bench gates end-to-end tracing overhead at ≤5% QPS.
"""

from repro.obs.trace import (  # noqa: F401
    NULL_TRACER,
    NullTracer,
    Span,
    Trace,
    Tracer,
    attach,
    chrome_events,
    contained,
    current_profiler,
    current_trace,
    faults,
    stage,
    tally,
)
from repro.obs.explain import build_explain, cost_fields  # noqa: F401
from repro.obs.profile import CostDrift, StageProfiler  # noqa: F401


def device_pipeline_stats() -> dict:
    """Snapshot of the fused device-pipeline counters — jit compile-cache
    calls/compiles/cache_hits, staging-pool reuse/alloc, and the live
    signature/buffer gauges. Imported lazily so ``repro.obs`` stays
    importable (and the /metrics scrape path stays cheap) without pulling
    the planner's jax stack in."""
    from repro.planner import device as planner_device

    return planner_device.pipeline_stats()


__all__ = [
    "Tracer", "NullTracer", "NULL_TRACER", "Trace", "Span",
    "attach", "stage", "tally", "current_trace", "current_profiler",
    "chrome_events",
    "contained", "faults",
    "build_explain", "cost_fields",
    "StageProfiler", "CostDrift",
    "device_pipeline_stats",
]
