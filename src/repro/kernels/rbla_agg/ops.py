"""jit'd wrappers: pad to tile alignment, flatten trailing dims, dispatch.

Two tiers:

* the public entry points (``axpy_fold``, ``packed_agg``,
  ``packed_robust``, ``packed_stack``) are jitted and **count as one
  tracked dispatch each** (``repro.core.plan.dispatch_counter``) -- for
  standalone use and the kernel tests;
* the ``*_inline`` variants run un-jitted for use *inside* an already
  compiled plan round (``repro.core.plan``), where a whole FL round is a
  single traced function and extra jit layers would only add overhead.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..runtime import auto_interpret, count_dispatch, note_trace
from .kernel import (_sublanes, axpy_fold_pallas, packed_agg_pallas,
                     packed_robust_pallas, packed_stack_pallas,
                     stack_row_tile)
from .ref import (axpy_fold_ref, packed_agg_ref, packed_robust_ref,
                  packed_stack_ref)


def _pad_to(v: int, mult: int) -> int:
    return (v + mult - 1) // mult * mult


def packed_agg_inline(x, masks, weights, prev=None, *,
                      norm_by: str = "mask", norm_restore: bool = False,
                      scales=None, out_dtype=None, interpret=None):
    """Un-jitted fused-bucket aggregation (the compiled plan's hot op).

    ``x``: (N, R, *dims) packed rows spanning many pairs; ``masks``:
    (N, R) per-row owner indicators; ``prev``: (R, *dims) packed previous
    global retained where no participant owns a row (``norm_by="mask"``
    only).  ``norm_restore`` fuses rbla_norm's per-row norm restoration
    (zero padding is norm-neutral).  Trailing dims flatten into D;
    padding is stripped.

    ``scales``: optional (N, R) f32 per-row dequantization scales fused
    on the load (int8 transport); padded rows get scale 1 (they have no
    owner either way).  ``out_dtype`` sets the output dtype -- required
    when ``x`` is a wire dtype; ``prev`` is staged in the *output* dtype,
    never the wire dtype.
    """
    interpret = auto_interpret(interpret)
    n, r = x.shape[:2]
    lead = x.shape[2:]
    d = 1
    for v in lead:
        d *= v
    x2 = x.reshape(n, r, d)
    rp, dp = _pad_to(max(r, 1), 8), _pad_to(max(d, 1), 128)
    x2 = jnp.pad(x2, ((0, 0), (0, rp - r), (0, dp - d)))
    m2 = jnp.pad(jnp.asarray(masks, jnp.float32), ((0, 0), (0, rp - r)))
    s2 = None
    if scales is not None:
        s2 = jnp.pad(jnp.asarray(scales, jnp.float32),
                     ((0, 0), (0, rp - r)), constant_values=1.0)
    pv = None
    if prev is not None:
        pv = jnp.pad(prev.reshape(r, d).astype(out_dtype or x2.dtype),
                     ((0, rp - r), (0, dp - d)))
    out = packed_agg_pallas(x2, m2, jnp.asarray(weights, jnp.float32), pv,
                            norm_by=norm_by, norm_restore=norm_restore,
                            scales=s2, out_dtype=out_dtype,
                            interpret=interpret)
    return out[:r, :d].reshape((r,) + lead)


@functools.partial(jax.jit, static_argnames=("norm_by", "norm_restore",
                                             "out_dtype", "interpret"))
def _packed_agg_jit(x, masks, weights, prev, scales, *, norm_by,
                    norm_restore, out_dtype, interpret):
    note_trace("packed_agg")
    return packed_agg_inline(x, masks, weights, prev, norm_by=norm_by,
                             norm_restore=norm_restore, scales=scales,
                             out_dtype=out_dtype, interpret=interpret)


def packed_agg(x, masks, weights, prev=None, *, norm_by: str = "mask",
               norm_restore: bool = False, scales=None, out_dtype=None,
               interpret=None):
    """Jitted :func:`packed_agg_inline` (standalone use and tests)."""
    count_dispatch(kernel="packed_agg")
    return _packed_agg_jit(x, masks, weights, prev, scales, norm_by=norm_by,
                           norm_restore=norm_restore, out_dtype=out_dtype,
                           interpret=interpret)


def packed_robust_inline(x, masks, weights, prev=None, *, mode: str,
                         clip_norm: float = 0.0, trim_frac: float = 0.0,
                         scales=None, out_dtype=None, interpret=None):
    """Un-jitted Byzantine-robust bucket aggregation (the compiled plan's
    hot op for the ``robustness != "none"`` strategies).

    Same packed layout as :func:`packed_agg_inline`; ``mode`` selects
    norm clipping, per-coordinate trimmed mean, or coordinate-wise
    median (see ``kernel.packed_robust_pallas``).  Padding is harmless:
    padded rows have no owner (they retain the zero-padded prev), padded
    columns are zero for every owner and cannot shift a row norm or an
    order statistic off the stripped region.  ``scales``/``out_dtype``
    as in :func:`packed_agg_inline` (dequant applied before clip/sort).
    """
    interpret = auto_interpret(interpret)
    n, r = x.shape[:2]
    lead = x.shape[2:]
    d = 1
    for v in lead:
        d *= v
    x2 = x.reshape(n, r, d)
    rp, dp = _pad_to(max(r, 1), 8), _pad_to(max(d, 1), 128)
    x2 = jnp.pad(x2, ((0, 0), (0, rp - r), (0, dp - d)))
    m2 = jnp.pad(jnp.asarray(masks, jnp.float32), ((0, 0), (0, rp - r)))
    s2 = None
    if scales is not None:
        s2 = jnp.pad(jnp.asarray(scales, jnp.float32),
                     ((0, 0), (0, rp - r)), constant_values=1.0)
    pv = None
    if prev is not None:
        pv = jnp.pad(prev.reshape(r, d).astype(out_dtype or x2.dtype),
                     ((0, rp - r), (0, dp - d)))
    out = packed_robust_pallas(x2, m2, jnp.asarray(weights, jnp.float32),
                               pv, mode=mode, clip_norm=clip_norm,
                               trim_frac=trim_frac, scales=s2,
                               out_dtype=out_dtype, interpret=interpret)
    return out[:r, :d].reshape((r,) + lead)


@functools.partial(jax.jit, static_argnames=("mode", "clip_norm",
                                             "trim_frac", "out_dtype",
                                             "interpret"))
def _packed_robust_jit(x, masks, weights, prev, scales, *, mode, clip_norm,
                       trim_frac, out_dtype, interpret):
    note_trace("packed_robust")
    return packed_robust_inline(x, masks, weights, prev, mode=mode,
                                clip_norm=clip_norm, trim_frac=trim_frac,
                                scales=scales, out_dtype=out_dtype,
                                interpret=interpret)


def packed_robust(x, masks, weights, prev=None, *, mode: str,
                  clip_norm: float = 0.0, trim_frac: float = 0.0,
                  scales=None, out_dtype=None, interpret=None):
    """Jitted :func:`packed_robust_inline` (standalone use and tests)."""
    count_dispatch(kernel="packed_robust")
    return _packed_robust_jit(x, masks, weights, prev, scales, mode=mode,
                              clip_norm=float(clip_norm),
                              trim_frac=float(trim_frac),
                              out_dtype=out_dtype, interpret=interpret)


def _stack_dims(r_in: int, out_rows: int, d: int, dtype):
    """(R_in, out_rows, D) as :func:`packed_stack_inline` pads them: rows
    to ``dtype``'s row tiling, D to lane alignment."""
    sub = _sublanes(dtype)
    return (_pad_to(max(r_in, 1), sub), _pad_to(max(out_rows, 1), sub),
            _pad_to(max(d, 1), 128))


def packed_stack_compiles(copies_x, copies_prev, *, r_in: int,
                          out_rows: int, d: int, dtype) -> bool:
    """True when :func:`packed_stack_inline` can run compiled on these
    copies: each starts and ends on ``dtype``'s row tiling and a row
    tile of the padded width fits VMEM (see ``stack_row_tile``)."""
    rp, op, dp = _stack_dims(r_in, out_rows, d, dtype)
    return stack_row_tile(copies_x, copies_prev, rp, op, dp, dtype,
                          interpret=False) > 0


def packed_stack_inline(x, scales, prev=None, *, copies_x=(),
                        copies_prev=(), out_rows: int, interpret=None):
    """Un-jitted fused stacking over a packed bucket (flora plan path).

    ``x``: (N, R_in, D); ``scales``: (S,); ``prev``: (R_prev, D) or None;
    the static ``copies_*`` describe every (pair, layer, contributor)
    placement (see ``packed_stack_pallas``).  Rows are padded to the row
    tiling and D to lane alignment, then stripped; padding never
    collides with copies.
    """
    interpret = auto_interpret(interpret)
    n, r_in, d = x.shape
    rp, op, dp = _stack_dims(r_in, out_rows, d, x.dtype)
    x2 = jnp.pad(x, ((0, 0), (0, rp - r_in), (0, dp - d)))
    pv = None
    if prev is not None:
        r_prev = prev.shape[0]
        pv = jnp.pad(prev, ((0, _stack_dims(r_prev, 1, d, prev.dtype)[0]
                             - r_prev), (0, dp - d)))
    out = packed_stack_pallas(x2, jnp.asarray(scales, jnp.float32), pv,
                              copies_x=tuple(copies_x),
                              copies_prev=tuple(copies_prev),
                              out_rows=op, interpret=interpret)
    return out[:out_rows, :d]


@functools.partial(jax.jit, static_argnames=("copies_x", "copies_prev",
                                             "out_rows", "interpret"))
def _packed_stack_jit(x, scales, prev, *, copies_x, copies_prev, out_rows,
                      interpret):
    note_trace("packed_stack")
    return packed_stack_inline(x, scales, prev, copies_x=copies_x,
                               copies_prev=copies_prev, out_rows=out_rows,
                               interpret=interpret)


def packed_stack(x, scales, prev=None, *, copies_x=(), copies_prev=(),
                 out_rows: int, interpret=None):
    """Jitted :func:`packed_stack_inline` (standalone use and tests)."""
    count_dispatch(kernel="packed_stack")
    return _packed_stack_jit(x, scales, prev, copies_x=tuple(copies_x),
                             copies_prev=tuple(copies_prev),
                             out_rows=out_rows, interpret=interpret)


def axpy_fold_inline(y, x, alpha, *, interpret=None, sr_key=None):
    """Un-jitted :func:`axpy_fold` body (for use inside compiled plans --
    the packed per-update fold runs one of these per bucket).

    ``sr_key``: optional PRNG key for *quantized accumulators* -- the
    fold runs on an fp32 view of ``y`` and the result is stochastically
    rounded back to ``y``'s storage dtype (bf16), keeping a long stream
    of low-precision folds unbiased (see
    :func:`repro.core.codec.stochastic_round`).  With ``sr_key=None``
    the fold is bit-identical to before."""
    interpret = auto_interpret(interpret)
    out_dt = y.dtype
    if sr_key is not None:
        y = y.astype(jnp.float32)
        x = x.astype(jnp.float32)
    r = y.shape[0]
    lead = y.shape[1:]
    d = 1
    for v in lead:
        d *= v
    a = jnp.broadcast_to(jnp.asarray(alpha, jnp.float32), (r,))
    y2 = y.reshape(r, d)
    x2 = x.reshape(r, d)
    rp, dp = _pad_to(max(r, 1), 8), _pad_to(max(d, 1), 128)
    y2 = jnp.pad(y2, ((0, rp - r), (0, dp - d)))
    x2 = jnp.pad(x2, ((0, rp - r), (0, dp - d)))
    a = jnp.pad(a, (0, rp - r))
    out = axpy_fold_pallas(y2, x2, a, interpret=interpret)
    out = out[:r, :d].reshape((r,) + lead)
    if sr_key is not None and out.dtype != out_dt:
        if out_dt == jnp.bfloat16:
            from repro.core.codec import stochastic_round
            out = stochastic_round(out, sr_key, out_dt)
        else:
            out = out.astype(out_dt)
    return out


@functools.partial(jax.jit, static_argnames=("interpret",))
def _axpy_fold_jit(y, x, alpha, sr_key, *, interpret):
    note_trace("axpy_fold")
    return axpy_fold_inline(y, x, alpha, interpret=interpret, sr_key=sr_key)


def axpy_fold(y, x, alpha, *, interpret=None, sr_key=None):
    """Fold one update into the live state: ``y + alpha * (x - y)``.

    y, x: (R, *dims) with the rank-row axis leading; ``alpha`` is a scalar
    (uniform server mixing, FedAsync-style) or an (R,) vector (per-row
    mixing -- RBLA's running masked mean folds only the rows the arriving
    client owns).  Trailing dims are flattened into D; sublane/lane
    padding is stripped from the result.  This is the async aggregation
    service's per-update hot path: cost is O(R*D) regardless of how many
    clients ever reported.  ``sr_key`` enables stochastic rounding back
    to a bf16 ``y`` (quantized accumulators; see
    :func:`axpy_fold_inline`).
    """
    count_dispatch(kernel="axpy_fold")
    return _axpy_fold_jit(y, x, alpha, sr_key, interpret=interpret)


__all__ = ["axpy_fold", "axpy_fold_ref", "packed_agg", "packed_agg_ref",
           "packed_robust", "packed_robust_ref", "packed_stack",
           "packed_stack_ref", "packed_agg_inline", "packed_robust_inline",
           "packed_stack_inline", "packed_stack_compiles",
           "axpy_fold_inline"]
