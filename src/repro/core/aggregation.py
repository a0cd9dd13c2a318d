"""Leaf math of the paper's aggregation methods (its core contribution).

Three methods from the paper:

* ``rbla``      -- Rank-Based LoRA Aggregation (Eq. 7 / Alg. 1): per
                   rank-row weighted average over the clients that *own*
                   the row; unique high-rank rows are preserved verbatim.
* ``zeropad``   -- the HetLoRA-style baseline (paper Eq. 1-5): pad to
                   r_max, plain weighted average; missing rows dilute
                   toward zero.
* ``fedavg``    -- plain weighted mean, used for non-LoRA leaves and for
                   the FFT (full fine-tune) baseline.

All functions are pure, jit-able, and operate on a single stacked leaf
``(n_clients, *leaf_shape)``; ``repro.core.strategy`` maps them over
adapter pytrees.  Masks carry the delta_{i,r} indicator (see
``masks.py``); a mask of ``None`` means "fully shared leaf" (bias, norm
scale, full weight).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array

_EPS = 1e-12


def _bcast_weights(weights: Array, ndim: int) -> Array:
    """Reshape (n,) client weights to broadcast against (n, *leaf)."""
    return weights.reshape(weights.shape + (1,) * (ndim - 1))


def fedavg_leaf(stacked: Array, weights: Array) -> Array:
    """Plain weighted mean over the client axis (axis 0)."""
    w = _bcast_weights(weights.astype(jnp.float32), stacked.ndim)
    num = jnp.sum(w * stacked.astype(jnp.float32), axis=0)
    den = jnp.sum(weights.astype(jnp.float32))
    return (num / (den + _EPS)).astype(stacked.dtype)


def zeropad_leaf(stacked: Array, mask: Array | None, weights: Array) -> Array:
    """Zero-padding baseline: mask the values (zeros beyond each client's
    rank) but normalize by the *total* weight mass -- this is exactly the
    dilution the paper criticizes (Eq. 3/5)."""
    x = stacked.astype(jnp.float32)
    if mask is not None:
        x = x * mask.astype(jnp.float32)
    w = _bcast_weights(weights.astype(jnp.float32), stacked.ndim)
    num = jnp.sum(w * x, axis=0)
    den = jnp.sum(weights.astype(jnp.float32))
    return (num / (den + _EPS)).astype(stacked.dtype)


def rbla_leaf(stacked: Array, mask: Array | None, weights: Array,
              prev: Array | None = None) -> Array:
    """RBLA (paper Eq. 7): per-element masked weighted average.

        C_r = sum_i delta_ir w_i A_ir / sum_i delta_ir w_i

    Where no participating client owns a row (denominator 0) the output is
    ``prev`` (the current server value) when given, else 0.  Retaining the
    previous value matters under partial participation: a round whose
    sampled clients are all low-rank must not wipe the high-rank rows the
    server already holds -- the paper's "preserve unique layers" principle
    extended to the random-selection setting (paper Figs. 5-10 right).
    """
    x = stacked.astype(jnp.float32)
    w = _bcast_weights(weights.astype(jnp.float32), stacked.ndim)
    if mask is None:
        m = jnp.ones_like(x)
    else:
        m = jnp.broadcast_to(mask.astype(jnp.float32), x.shape)
    num = jnp.sum(w * m * x, axis=0)
    den = jnp.sum(w * m, axis=0)
    fallback = (jnp.zeros_like(num) if prev is None
                else prev.astype(jnp.float32))
    return jnp.where(den > 0, num / (den + _EPS),
                     fallback).astype(stacked.dtype)
