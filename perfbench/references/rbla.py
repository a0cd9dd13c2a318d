"""Plain RBLA (paper Eq. 7), written from the paper and independent of
the program: no import of ``repro``, and no input the program made.

For one side of one LoRA pair, with client ``i`` at rank ``k_i`` and
weight ``w_i``, row ``r`` (a row of A, a column of B) of the result is

    sum_i w_i [r < k_i] x_i[r] / sum_i w_i [r < k_i]

and the previous global's row where no client owns ``r``.  An int8
upload is dequantized first: ``x = q * scale`` with one scale per row.

A reference module of ``perfbench/references/`` is found by the
traffic's ``strategy`` and gives ``aggregate`` (one sync round) and
``folded`` (the state after a sequence of async folds).

``dtype`` is the precision of the arithmetic: float32 for the reference,
bfloat16 for the control that every limit must reject.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _rows(v, side, ndim):
    """Per-row values, ``(L, r)`` or ``(L, *mid, r)``, broadcast against
    a side of ``ndim`` axes: over every axis between the layer axis and
    the row axis (``mid``, the experts held) and on the side's row axis."""
    v = v.reshape(v.shape[:1] + (1,) * (ndim - 1 - v.ndim) + v.shape[1:])
    return v[..., :, None] if side == "A" else v[..., None, :]


@functools.partial(jax.jit, static_argnames=("side", "dtype"))
def _add_client(num, den, x, scale, w, rank, *, side: str, dtype):
    """Add one client's weighted owned rows to the running sums."""
    x = x.astype(dtype)
    if scale is not None:
        x = x * _rows(scale.astype(dtype), side, x.ndim)
    own = (jnp.arange(den.shape[-1]) < rank).astype(dtype)
    wm = jnp.broadcast_to(w.astype(dtype) * own, den.shape)
    return num + _rows(wm, side, x.ndim) * x, den + wm


@functools.partial(jax.jit, static_argnames=("side", "dtype"))
def _finish(num, den, prev, *, side: str, dtype):
    d = _rows(den, side, prev.ndim)
    return jnp.where(d > 0, num / jnp.where(d > 0, d, 1), prev.astype(dtype))


def rbla_side(xs, scales, ws, ranks, prev, *, side: str, dtype):
    """xs: per-client ``(L, *mid, r, fan_in)`` (side A) or ``(L, *mid,
    fan_out, r)`` (side B) arrays, ``mid`` the pair's own axes (the
    experts held; none for a dense pair); scales: per-client ``(L, *mid,
    r)`` or None; ws, ranks: per-client scalars, one rank for every layer
    and expert; prev: the previous global's side."""
    r = xs[0].shape[-2] if side == "A" else xs[0].shape[-1]
    num = jnp.zeros(xs[0].shape, dtype)
    den = jnp.zeros((xs[0].shape[0], r), dtype)
    for i, x in enumerate(xs):
        num, den = _add_client(num, den, x,
                               None if scales is None else scales[i],
                               ws[i], ranks[i], side=side, dtype=dtype)
    return _finish(num, den, prev, side=side, dtype=dtype)


def aggregate(pairs, ws, ranks, prev_pairs, dtype=jnp.float32):
    """RBLA over whole uploads.  ``pairs[i]`` is client i's list of pairs
    (tree order) as dicts with ``A``, ``B`` and, for int8, ``A_scale`` /
    ``B_scale``; ``prev_pairs`` the previous global's list.  Returns the
    list of ``{"A", "B"}`` results."""
    ws = jnp.asarray(ws, jnp.float32)
    ranks = jnp.asarray(ranks, jnp.int32)
    out = []
    for pi, prev in enumerate(prev_pairs):
        res = {}
        for side in ("A", "B"):
            xs = [c[pi][side] for c in pairs]
            key = side + "_scale"
            sc = ([c[pi][key] for c in pairs] if key in pairs[0][pi]
                  else None)
            res[side] = rbla_side(xs, sc, ws, ranks, prev[side], side=side,
                                  dtype=dtype)
        out.append(res)
    return out


def folded(pairs, masses, ranks, first_pairs, dtype=jnp.float32):
    """The state after folding uploads one at a time with RBLA's running
    per-row mean, starting from ``first_pairs``: the RBLA mean over the
    distinct uploads, each weighted by the sum of the (discounted) weights
    it was folded with, ``masses[i]``; an upload never folded has mass 0,
    and rows no folded upload owns keep the first state."""
    return aggregate(pairs, masses, ranks, first_pairs, dtype)
