"""Span-based round-lifecycle tracing with JAX-aware timers.

The aggregation service's round lifecycle is::

    submit -> buffer -> flush/replay -> fold -> publish -> serve

and a synchronous round is ``round`` with its stages ``round.stack``,
``round.spec``, ``round.plan``, ``round.pack`` and ``round.combine``.
Each stage is wrapped in a :func:`span`: a context manager that measures
host wall time into the ``obs_span_seconds{stage=...}`` histogram and
holds the profiler annotation ``obs.<stage>`` for its whole extent, so
the same span is read from the registry and, in a profiler trace, next
to the device ops on the trace's own clock.  Spans nest on the calling
thread; the trace gives each its parent.

Two JAX rules, both hard requirements (``tests/test_obs.py`` gates
them):

* **Block only at span boundaries.**  JAX dispatch is asynchronous; a
  span measures host time, and the device's time comes from the device
  trace.  A span that must measure compute hands the stage's *result*
  to :meth:`Span.block` (or passes ``block_on=``) and the span calls
  ``jax.block_until_ready`` on its array leaves exactly once, at the
  boundary -- never inside the computation.
* **Never trace Python into jitted code.**  Spans are host-side pure
  Python; if one is (incorrectly) entered while JAX is tracing, it
  degrades to a complete no-op -- no timing call, no annotation,
  nothing staged into the jaxpr -- so instrumentation can never add a
  trace or a retrace to a compiled path (the zero-retrace guarantee).

:func:`host_syncs` is the ``host_syncs_total{site}`` counter: every
device-to-host read the host waits on, counted where it happens.
:func:`cohort_stacks` is ``cohort_stacks_total{strategy}``: every cohort
``aggregate_adapters`` still stacks leaf by leaf before planning.
"""
from __future__ import annotations

import time
from typing import Any

import jax

from .metrics import LATENCY_BUCKETS, get_registry

#: the canonical round-lifecycle stages (free-form stage names are
#: allowed; these are the ones the service emits)
ROUND_STAGES = ("submit", "buffer", "flush", "replay", "fold", "publish",
                "serve")

#: prefix of a span's profiler annotation: stage ``round.spec`` is the
#: trace event ``obs.round.spec``
ANNOTATION_PREFIX = "obs."


def _trace_clean() -> bool:
    """True when JAX is *not* currently tracing (spans may run)."""
    # imported here: repro.core's modules import repro.obs at load
    from repro.core.compat import trace_state_clean
    return trace_state_clean()


class Span:
    """One timed stage.  Use via :func:`span`."""

    __slots__ = ("stage", "meta", "_t0", "_active", "_registry",
                 "_annotation", "duration_s")

    def __init__(self, stage: str, registry, meta):
        self.stage = stage
        self.meta = meta
        self._registry = registry
        self._active = False
        self._annotation = None
        self._t0 = 0.0
        self.duration_s = None

    def block(self, tree: Any) -> Any:
        """Wait for ``tree``'s array leaves (the stage's result) so the
        span measures compute, not enqueue; returns ``tree``.  No-op on
        an inactive span (disabled metrics / under jit)."""
        if self._active:
            jax.block_until_ready(
                [x for x in jax.tree.leaves(tree)
                 if hasattr(x, "block_until_ready")])
        return tree

    def __enter__(self) -> "Span":
        self._active = self._registry.enabled and _trace_clean()
        if self._active:
            self._annotation = jax.profiler.TraceAnnotation(
                ANNOTATION_PREFIX + self.stage, **self.meta)
            self._annotation.__enter__()
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if not self._active:
            return
        self.duration_s = time.perf_counter() - self._t0
        self._annotation.__exit__(exc_type, exc, tb)
        self._annotation = None
        _span_hist(self._registry).labels(stage=self.stage).observe(
            self.duration_s)


def _span_hist(registry):
    # get-or-create is idempotent and cheap (one lock, one dict hit);
    # keying off the registry itself avoids any id-reuse bookkeeping
    return registry.histogram(
        "obs_span_seconds", "wall seconds per lifecycle stage",
        labelnames=("stage",), buckets=LATENCY_BUCKETS)


def span(stage: str, *, registry=None, block_on: Any = None,
         **meta) -> Span:
    """A timed lifecycle stage::

        with span("fold") as sp:
            out = strategy.aggregate(...)
            sp.block(out)          # JAX-aware: block at the boundary

    ``block_on`` blocks on a pytree at *entry* (isolating this stage
    from still-in-flight predecessors).  Extra keyword arguments become
    the profiler annotation's metadata (``span("round", round=k)`` is
    the trace event ``obs.round`` with ``round=k``).  When metrics are
    disabled -- or JAX is tracing -- the span is a no-op.
    """
    sp = Span(stage, registry or get_registry(), meta)
    if block_on is not None and sp._registry.enabled and _trace_clean():
        jax.block_until_ready(
            [x for x in jax.tree.leaves(block_on)
             if hasattr(x, "block_until_ready")])
    return sp


def host_syncs(site: str, registry=None):
    """The ``host_syncs_total{site=...}`` child: device-to-host reads the
    host waits on at ``site``.  Callers hold the child and make one
    ``inc(n)`` per walk."""
    return (registry or get_registry()).counter(
        "host_syncs_total",
        "device-to-host reads the host waited on, by site",
        labelnames=("site",)).labels(site=site)


def cohort_stacks(strategy: str):
    """The ``cohort_stacks_total{strategy=...}`` child: cohorts stacked
    eagerly (``stack_trees``) on the paths that still need the stacked
    ``(n, ...)`` tree."""
    return get_registry().counter(
        "cohort_stacks_total",
        "cohorts aggregate_adapters stacked leaf by leaf, by strategy",
        labelnames=("strategy",)).labels(strategy=strategy)


__all__ = ["span", "Span", "ROUND_STAGES", "ANNOTATION_PREFIX",
           "host_syncs", "cohort_stacks"]
