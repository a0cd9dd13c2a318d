"""repro.obs -- unified observability: metrics, tracing, health, export.

The operational substrate for the FLaaS server (see
``docs/observability.md``):

* :mod:`repro.obs.metrics` -- the process :class:`MetricsRegistry`
  (counters / gauges / fixed-bucket histograms, lock-safe, cheap no-op
  when disabled, ``reset()`` / ``scoped()`` for tests);
* :mod:`repro.obs.trace` -- span-based round-lifecycle tracing
  (``submit -> buffer -> flush/replay -> fold -> publish -> serve``, and
  a synchronous ``round``'s stages) on the host clock and, as
  ``obs.<stage>`` annotations, on the profiler's; spans degrade to
  no-ops under jit (the zero-retrace guarantee); and the
  ``host_syncs_total{site}`` counter of device-to-host reads and the
  ``cohort_stacks_total{strategy}`` counter of eagerly stacked cohorts;
* :mod:`repro.obs.export` -- Prometheus text format, JSON-lines, and
  the in-memory :meth:`MetricsRegistry.snapshot`;
* :mod:`repro.obs.health` -- :class:`ServiceHealth`, the one-call
  operator view over the async aggregation service and the serving
  store;
* :mod:`repro.obs.timing` -- the shared benchmark timing helpers.
"""
from .metrics import (LATENCY_BUCKETS, REGISTRY, STALENESS_BUCKETS,
                      Counter, Gauge, Histogram, MetricsRegistry,
                      get_registry, metrics_enabled, set_enabled)
from .trace import ROUND_STAGES, Span, cohort_stacks, host_syncs, span
from .export import parse_prometheus, to_prometheus, write_jsonl_snapshot
from .health import ServiceHealth
from .timing import bench_payload, block, time_fn

__all__ = [
    "MetricsRegistry", "Counter", "Gauge", "Histogram", "REGISTRY",
    "get_registry", "set_enabled", "metrics_enabled",
    "LATENCY_BUCKETS", "STALENESS_BUCKETS",
    "span", "Span", "ROUND_STAGES", "host_syncs", "cohort_stacks",
    "to_prometheus", "parse_prometheus", "write_jsonl_snapshot",
    "ServiceHealth",
    "block", "time_fn", "bench_payload",
]
