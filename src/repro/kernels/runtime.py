"""Shared runtime policy + counters for the Pallas kernel wrappers.

Both kernel packages (``rbla_agg``, ``lora_matmul``) used to carry their
own copy of the dispatch / trace accounting; it lives here once now,
backed by the :mod:`repro.obs` metrics registry:

* :func:`count_dispatch` -- one call per public kernel-entry dispatch,
  mirrored into the legacy ``repro.core.plan.dispatch_counter`` window so
  existing ``reset()``-based probes keep working;
* :func:`note_trace` -- called from *inside* a jitted wrapper body, so it
  fires exactly once per (re)trace; the per-entry counts are readable as
  the dict-like :data:`trace_counts` (the surface
  ``lora_matmul.ops.trace_counts`` re-exports).
"""
from __future__ import annotations

from typing import Iterator, Mapping

import jax

from repro.obs import get_registry

_KERNEL_DISPATCHES = get_registry().counter(
    "kernel_dispatches_total",
    "public kernel-entry dispatches, by entry point",
    labelnames=("entry",))
_KERNEL_TRACES = get_registry().counter(
    "kernel_traces_total",
    "jit (re)traces of kernel wrapper bodies, by entry point",
    labelnames=("entry",))


def count_dispatch(n: int = 1, kernel: str = "unknown") -> None:
    """Count ``n`` dispatches of a public kernel entry point.

    Feeds the labelled ``kernel_dispatches_total`` series and the legacy
    windowed ``plan.dispatch_counter`` (imported lazily -- plan imports
    the kernel packages, not the other way around).
    """
    from repro.core.plan import dispatch_counter
    dispatch_counter.inc(n)
    _KERNEL_DISPATCHES.labels(entry=kernel).inc(n)


def note_trace(name: str) -> None:
    """Record one jit trace of the wrapper body ``name``.  Call this from
    inside the traced function: it then runs once per (re)trace and never
    on cached-executable dispatch, which is exactly the retrace signal the
    zero-retrace CI gates watch."""
    _KERNEL_TRACES.labels(entry=name).inc()


class TraceCounts(Mapping):
    """Read-only dict view over ``kernel_traces_total`` -- the legacy
    ``lora_matmul.ops.trace_counts`` surface.  Keys appear once an entry
    has traced at least once; ``clear()`` zeroes the counts (the
    pre-registry dict supported it, so tests may rely on it)."""

    def _items(self) -> dict[str, int]:
        return {key.partition("=")[2]: int(v)
                for key, v in _KERNEL_TRACES.samples().items()}

    def __getitem__(self, name: str) -> int:
        return self._items()[name]

    def get(self, name: str, default=None):
        return self._items().get(name, default)

    def __iter__(self) -> Iterator[str]:
        return iter(self._items())

    def __len__(self) -> int:
        return len(self._items())

    def __repr__(self) -> str:
        return f"TraceCounts({self._items()!r})"

    def clear(self) -> None:
        _KERNEL_TRACES._reset()


#: the process-wide per-entry trace counts (dict-like, live)
trace_counts = TraceCounts()


def auto_interpret(interpret: bool | None) -> bool:
    """Resolve the ``interpret`` tri-state of a kernel wrapper.

    ``None`` (the default) auto-detects: compiled Pallas on TPU/GPU,
    interpreter mode on CPU (where Pallas cannot lower).  Explicit
    ``True`` / ``False`` pass through -- tests force ``True``; TPU callers
    that want a hard failure on accidental interpretation force ``False``.
    """
    if interpret is None:
        return jax.default_backend() not in ("tpu", "gpu")
    return interpret


def bench_env() -> dict:
    """The environment header every machine-readable benchmark emits
    (``BENCH_serve.json``): enough to tell whether two committed runs are
    comparable -- jax version, device kind, and whether Pallas kernels
    ran compiled or in interpreter mode."""
    dev = jax.devices()[0]
    return {
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "device_kind": dev.device_kind,
        "n_devices": len(jax.devices()),
        "pallas_interpret": auto_interpret(None),
    }
