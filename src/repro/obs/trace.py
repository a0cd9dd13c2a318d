"""Span-based round-lifecycle tracing with JAX-aware timers.

The aggregation service's round lifecycle is::

    submit -> buffer -> flush/replay -> fold -> publish -> serve

Each stage is wrapped in a :func:`span`: a context manager that measures
wall time into the ``obs_span_seconds{stage=...}`` histogram and
(optionally) appends a JSON-serializable event to an :class:`EventLog`.

Two JAX rules, both hard requirements (``tests/test_obs.py`` gates
them):

* **Block only at span boundaries.**  JAX dispatch is asynchronous; a
  naive timer measures enqueue cost, not compute.  A span caller hands
  the stage's *result* to :meth:`Span.block` (or passes ``block_on=``)
  and the span calls ``jax.block_until_ready`` on its array leaves
  exactly once, at the boundary -- never inside the computation.
* **Never trace Python into jitted code.**  Spans are host-side pure
  Python; if one is (incorrectly) entered while JAX is tracing, it
  degrades to a complete no-op -- no timing call, no callback, nothing
  staged into the jaxpr -- so instrumentation can never add a trace or a
  retrace to a compiled path (the zero-retrace guarantee).
"""
from __future__ import annotations

import collections
import json
import threading
import time
from typing import Any

import jax

from repro.core.compat import trace_state_clean

from .metrics import LATENCY_BUCKETS, get_registry

#: the canonical round-lifecycle stages (free-form stage names are
#: allowed; these are the ones the service emits)
ROUND_STAGES = ("submit", "buffer", "flush", "replay", "fold", "publish",
                "serve")


def _trace_clean() -> bool:
    """True when JAX is *not* currently tracing (spans may run)."""
    return trace_state_clean()


class EventLog:
    """Bounded in-memory event ring with an optional JSON-lines sink.

    ``log(event)`` appends a dict; with :meth:`attach_jsonl` every event
    is also written as one JSON line (the exporter format operators tail
    into their log pipeline).  Thread-safe.
    """

    def __init__(self, maxlen: int = 4096):
        self._events: collections.deque = collections.deque(maxlen=maxlen)
        self._lock = threading.Lock()
        self._sink = None
        self._sink_path = None

    def attach_jsonl(self, path) -> None:
        """Start appending every event as a JSON line to ``path``."""
        with self._lock:
            if self._sink is not None:
                self._sink.close()
            self._sink = open(path, "a")
            self._sink_path = path

    def detach(self) -> None:
        with self._lock:
            if self._sink is not None:
                self._sink.close()
            self._sink = None
            self._sink_path = None

    def log(self, event: dict) -> None:
        with self._lock:
            self._events.append(event)
            if self._sink is not None:
                self._sink.write(json.dumps(event) + "\n")
                self._sink.flush()

    def events(self) -> list:
        with self._lock:
            return list(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()


#: process-default event log; spans write here when ``log=True``
EVENT_LOG = EventLog()


class Span:
    """One timed stage.  Use via :func:`span`."""

    __slots__ = ("stage", "meta", "_t0", "_active", "_registry", "_log",
                 "duration_s")

    def __init__(self, stage: str, registry, log, meta):
        self.stage = stage
        self.meta = meta
        self._registry = registry
        self._log = log
        self._active = False
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
        reg = self._registry
        self._active = reg.enabled and _trace_clean()
        if self._active:
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if not self._active:
            return
        self.duration_s = time.perf_counter() - self._t0
        _span_hist(self._registry).labels(stage=self.stage).observe(
            self.duration_s)
        if self._log:
            event = {"event": "span", "stage": self.stage,
                     "duration_s": self.duration_s,
                     "t_end": time.time()}
            if exc_type is not None:
                event["error"] = exc_type.__name__
            if self.meta:
                event.update(self.meta)
            EVENT_LOG.log(event)


def _span_hist(registry):
    # get-or-create is idempotent and cheap (one lock, one dict hit);
    # keying off the registry itself avoids any id-reuse bookkeeping
    return registry.histogram(
        "obs_span_seconds", "wall seconds per lifecycle stage",
        labelnames=("stage",), buckets=LATENCY_BUCKETS)


def span(stage: str, *, registry=None, block_on: Any = None,
         log: bool = False, **meta) -> Span:
    """A timed lifecycle stage::

        with span("fold") as sp:
            out = strategy.aggregate(...)
            sp.block(out)          # JAX-aware: block at the boundary

    ``block_on`` blocks on a pytree at *entry* (isolating this stage
    from still-in-flight predecessors).  ``log=True`` also appends the
    span to :data:`EVENT_LOG` (and its JSON-lines sink, when attached).
    Extra keyword arguments ride along as event metadata.  When metrics
    are disabled -- or JAX is tracing -- the span is a no-op.
    """
    sp = Span(stage, registry or get_registry(), log, meta)
    if block_on is not None and sp._registry.enabled and _trace_clean():
        jax.block_until_ready(
            [x for x in jax.tree.leaves(block_on)
             if hasattr(x, "block_until_ready")])
    return sp


__all__ = ["span", "Span", "EventLog", "EVENT_LOG", "ROUND_STAGES"]
