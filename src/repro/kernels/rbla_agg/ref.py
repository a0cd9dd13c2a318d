"""Pure-jnp oracles for the RBLA aggregation kernels (the packed forms of
the paper's Eq. 7 that ``repro.core``'s leaf math defines)."""
from __future__ import annotations

import jax.numpy as jnp


def axpy_fold_ref(y, x, alpha):
    """Oracle for the async fold kernel: y, x (R, *dims); alpha scalar or
    (R,) -> y + alpha*(x-y) with alpha broadcast over trailing dims."""
    a = jnp.asarray(alpha, jnp.float32)
    if a.ndim == 1:
        a = a.reshape((y.shape[0],) + (1,) * (y.ndim - 1))
    yf = y.astype(jnp.float32)
    return (yf + a * (x.astype(jnp.float32) - yf)).astype(y.dtype)


def packed_agg_ref(x, masks, weights, prev=None, norm_by: str = "mask",
                   norm_restore: bool = False, scales=None, out_dtype=None):
    """Oracle for the fused-bucket kernel: x (N, R, D), masks (N, R),
    weights (N,), prev (R, D) or None -> (R, D).  Matches the packed-row
    form of rbla_leaf (``norm_by="mask"``: per-row owner-mass mean with
    prev retention) / zeropad_leaf (``norm_by="weight"``: total-mass
    dilution).  ``norm_restore`` adds rbla_norm's per-row norm
    restoration (rescale each output row to the owners' weighted-mean
    row norm).

    ``scales`` (N, R) fuses int8 dequantization as an epilogue on the
    load: each client row is multiplied by its per-row scale before any
    reduction, so quantized uploads never materialize an fp32 staging
    buffer.  The mask-mass denominator stays scale-free (scales rescale
    values, not ownership).  ``out_dtype`` overrides the output dtype --
    required when ``x`` is a wire dtype (int8/bf16) but the aggregate is
    fp32."""
    xf = x.astype(jnp.float32)
    if scales is not None:
        xf = scales.astype(jnp.float32)[:, :, None] * xf
    m = masks.astype(jnp.float32)
    w = weights.astype(jnp.float32)
    num = jnp.einsum("n,nr,nrd->rd", w, m, xf)
    if norm_by == "mask":
        den = jnp.einsum("n,nr->r", w, m)[:, None]
        fb = (jnp.zeros_like(num) if prev is None
              else prev.astype(jnp.float32))
        out = jnp.where(den > 0, num / (den + 1e-12), fb)
    else:
        out = num / (jnp.sum(w) + 1e-12)
    if norm_restore:
        xm = m[:, :, None] * xf
        row_norms = jnp.sqrt(jnp.einsum("nrd,nrd->nr", xm, xm))
        w_rows = (m > 0).astype(jnp.float32) * w[:, None]
        target = (jnp.sum(w_rows * row_norms, axis=0)
                  / (jnp.sum(w_rows, axis=0) + 1e-12))
        agg = jnp.sqrt(jnp.sum(out ** 2, axis=1))
        out = out * jnp.where(agg > 1e-12, target / (agg + 1e-12),
                              1.0)[:, None]
    return out.astype(out_dtype or x.dtype)


def packed_stack_ref(x, scales, prev=None, *, copies_x=(), copies_prev=(),
                     out_rows: int):
    """Oracle for the fused FLoRA stacking kernel (plan path): x
    (N, R_in, D), scales (S,), prev (R_prev, D) or None, static
    ``copies_x`` entries ``(client, src_row, dst_row, rows, scale_idx)``
    (``copies_prev`` drop the client index and read ``prev``) ->
    (out_rows, D).  Rows no copy touches stay zero.  Because every copy
    is a static slice, this *is* a fused XLA lowering, not just a test
    oracle -- the plan layer uses it where interpreted Pallas would pay
    per-op Python overhead."""
    sc = jnp.asarray(scales, jnp.float32)
    out = jnp.zeros((out_rows, x.shape[-1]), x.dtype)
    for (src, s0, d0, nr, si) in copies_x:
        out = out.at[d0:d0 + nr, :].set(
            (sc[si] * x[src, s0:s0 + nr, :].astype(jnp.float32)
             ).astype(x.dtype))
    for (s0, d0, nr, si) in copies_prev:
        out = out.at[d0:d0 + nr, :].set(
            (sc[si] * prev[s0:s0 + nr, :].astype(jnp.float32)
             ).astype(x.dtype))
    return out


#: sentinel pushed into unowned slots before the per-coordinate sort --
#: strictly above any sane upload (breakdown tests go to ~1e6 norms) yet
#: small enough that averaging two sentinels stays finite in f32.
_SENTINEL = 1e30


def packed_robust_ref(x, masks, weights, prev=None, *, mode: str,
                      clip_norm: float = 0.0, trim_frac: float = 0.0,
                      scales=None, out_dtype=None):
    """Byzantine-robust oracle on the packed bucket layout: x (N, R, D),
    masks (N, R), weights (N,), prev (R, D) or None -> (R, D).

    ``mode="clipped"``: each client's packed row is L2-clipped to
    ``clip_norm`` (scale = min(1, clip/||row||)) and then aggregated with
    the standard masked weighted mean -- identical to ``packed_agg_ref``
    when every row norm is under the clip.

    ``mode="trimmed"`` / ``"median"``: per-coordinate order statistics
    over the row's owners, *unweighted* (example counts are
    client-reported and therefore adversary-controlled; order statistics
    on values, not masses, is what bounds the breakdown point).  Unowned
    slots sort to the top via a large sentinel, so owners occupy sorted
    positions ``[0, c)``; trimming drops ``k = min(floor(trim_frac*c),
    (c-1)//2)`` from each end, the median averages sorted positions
    ``(c-1)//2`` and ``c//2``.  Rows with no owner retain ``prev``.

    ``scales`` (N, R) dequantizes int8 uploads *before* any clip or
    order statistic -- robustness bounds apply to decoded values, so
    quantization cannot widen them.  ``out_dtype`` as in
    :func:`packed_agg_ref`."""
    xf = x.astype(jnp.float32)
    if scales is not None:
        xf = scales.astype(jnp.float32)[:, :, None] * xf
    m = masks.astype(jnp.float32)
    w = weights.astype(jnp.float32)
    fb = (jnp.zeros(x.shape[1:], jnp.float32) if prev is None
          else prev.astype(jnp.float32))
    if mode == "clipped":
        norms = jnp.sqrt(jnp.einsum("nrd,nrd->nr", xf, xf))
        scale = jnp.minimum(1.0, clip_norm / jnp.maximum(norms, 1e-12))
        num = jnp.einsum("n,nr,nrd->rd", w, m, scale[:, :, None] * xf)
        den = jnp.einsum("n,nr->r", w, m)[:, None]
        out = jnp.where(den > 0, num / (den + 1e-12), fb)
        return out.astype(out_dtype or x.dtype)
    if mode not in ("trimmed", "median"):
        raise ValueError(f"unknown robust mode {mode!r}; options: "
                         f"['clipped', 'median', 'trimmed']")
    n = x.shape[0]
    owned = m > 0
    s = jnp.sort(jnp.where(owned[:, :, None], xf, _SENTINEL), axis=0)
    c = jnp.sum(owned, axis=0).astype(jnp.int32)             # (R,)
    idx = jnp.arange(n, dtype=jnp.int32)[:, None]            # (N, 1)
    if mode == "median":
        lo = jnp.maximum((c - 1) // 2, 0)[None, :]
        hi = (c // 2)[None, :]
        sel = 0.5 * ((idx == lo).astype(jnp.float32)
                     + (idx == hi).astype(jnp.float32))      # (N, R)
        out = jnp.einsum("nr,nrd->rd", sel, s)
    else:
        k = jnp.minimum(
            jnp.floor(trim_frac * c.astype(jnp.float32)).astype(jnp.int32),
            jnp.maximum((c - 1) // 2, 0))[None, :]
        inc = ((idx >= k) & (idx < c[None, :] - k)).astype(jnp.float32)
        cnt = jnp.sum(inc, axis=0)[:, None]                  # = c - 2k
        out = jnp.einsum("nr,nrd->rd", inc, s) / jnp.maximum(cnt, 1.0)
    out = jnp.where((c > 0)[:, None], out, fb)
    return out.astype(out_dtype or x.dtype)


def packed_robust_xla(x, masks, weights, prev=None, *, mode: str,
                      clip_norm: float = 0.0, trim_frac: float = 0.0,
                      scales=None, out_dtype=None):
    """Fused XLA lowering of :func:`packed_robust_ref` for the order
    statistics: identical contract and semantics, but the per-coordinate
    sort runs a static odd-even transposition network (the same network
    the Pallas kernel uses) instead of ``jnp.sort`` -- on CPU, XLA's
    variadic sort is a serial per-lane comparison sort while the network
    is ~n^2/2 vectorized min/max sweeps over the whole bucket, ~10x
    faster at cohort sizes.  The plan layer uses this for interpret-mode
    pallas plans, where per-tile grid emulation overhead also rules out
    the real kernel; ``jnp.sort`` in ``packed_robust_ref`` stays the
    independent oracle."""
    if mode == "clipped":            # einsum path is already one fusion
        return packed_robust_ref(x, masks, weights, prev, mode=mode,
                                 clip_norm=clip_norm, trim_frac=trim_frac,
                                 scales=scales, out_dtype=out_dtype)
    if mode not in ("trimmed", "median"):
        raise ValueError(f"unknown robust mode {mode!r}; options: "
                         f"['clipped', 'median', 'trimmed']")
    n = x.shape[0]
    xf = x.astype(jnp.float32)
    if scales is not None:
        xf = scales.astype(jnp.float32)[:, :, None] * xf
    owned = masks.astype(jnp.float32) > 0                    # (N, R)
    fb = (jnp.zeros(x.shape[1:], jnp.float32) if prev is None
          else prev.astype(jnp.float32))
    vals = [jnp.where(owned[i][:, None], xf[i], _SENTINEL)
            for i in range(n)]
    c = jnp.sum(owned, axis=0).astype(jnp.int32)[:, None]    # (R, 1)
    for rnd in range(n):
        for i in range(rnd % 2, n - 1, 2):
            lo = jnp.minimum(vals[i], vals[i + 1])
            vals[i + 1] = jnp.maximum(vals[i], vals[i + 1])
            vals[i] = lo
    if mode == "median":
        lo_ix = jnp.maximum((c - 1) // 2, 0)
        hi_ix = c // 2
        out = sum(0.5 * ((lo_ix == j).astype(jnp.float32)
                         + (hi_ix == j).astype(jnp.float32)) * vals[j]
                  for j in range(n))
    else:
        k = jnp.minimum(
            jnp.floor(trim_frac * c.astype(jnp.float32)).astype(jnp.int32),
            jnp.maximum((c - 1) // 2, 0))
        cnt = jnp.maximum((c - 2 * k).astype(jnp.float32), 1.0)
        out = sum(((j >= k) & (j < c - k)).astype(jnp.float32) * vals[j]
                  for j in range(n)) / cnt
    out = jnp.where(c > 0, out, fb)
    return out.astype(out_dtype or x.dtype)
