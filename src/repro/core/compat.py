"""The JAX APIs this repo reaches through one import site.

The repo targets JAX >= 0.7 (``jax.shard_map`` with ``check_vma``;
installed: 0.9.0).  Where an API is private or has moved before, it is
wrapped here and nowhere else, so a JAX upgrade is fixed in one file.
"""
from __future__ import annotations

import jax
from jax._src import core as _jax_core

shard_map = jax.shard_map
axis_size = jax.lax.axis_size


def trace_state_clean() -> bool:
    """True when JAX is *not* tracing (code runs eagerly on the host).

    JAX 0.9 keeps the check only in ``jax._src.core``; a JAX without it
    fails at import here instead of silently reporting "not tracing"."""
    return bool(_jax_core.trace_state_clean())


def count_primitive(jaxpr, name: str) -> int:
    """Equations of primitive ``name`` in ``jaxpr`` (open or closed)
    and in every jaxpr nested in its equations' params."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    return sum((eqn.primitive.name == name)
               + sum(count_primitive(sub, name)
                     for sub in _jax_core.jaxprs_in_params(eqn.params))
               for eqn in jaxpr.eqns)


def shard_map_no_check(body, mesh, in_specs, out_specs):
    """``shard_map`` with replication checking disabled."""
    return shard_map(body, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


__all__ = ["axis_size", "count_primitive", "shard_map",
           "shard_map_no_check", "trace_state_clean"]
