"""RBLA masked rank-row aggregation Pallas TPU kernels (paper Eq. 7).

Given packed client rows x (N, R, D), per-row owner masks m (N, R) and
weights w (N,), ``packed_agg`` computes

    out[r, d] = sum_n w_n * m[n, r] * x[n, r, d]
              / sum_n w_n * m[n, r]          (prev[r, d] where no owner)

This is the server's hot loop: bandwidth-bound (reads N*R*D, writes R*D,
O(1) flops per element).  Grid (R/br, D/bd); the client axis is an
in-kernel loop over VMEM blocks (N is the cohort size).

The compiled plan's kernels (``packed_agg``, ``packed_robust``,
``packed_stack``, ``axpy_fold``) keep every block legal
for the TPU at published widths and cohorts up to a few hundred clients:
per-row operands ride as (R, N) / (R, 1) columns, per-client scalars in
SMEM, and one VMEM budget (:func:`_client_blocks`) sizes the client
block; ``tests/test_tpu_compile.py`` compiles them for a v5e chip.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BR = 128
DEFAULT_BD = 512


#: bytes of one streamed ``(n, br, bd)`` client block, counted at f32
#: (the kernels upcast each client slice on load, and the order-statistic
#: modes keep all n upcast slices live).  The pipeline double-buffers the
#: block, so two of these plus the live values stay well inside v5e's
#: 16 MiB of scoped VMEM at any cohort size.
_CLIENT_BLOCK_BYTES = 2 * 1024 * 1024


def _sublanes(dtype) -> int:
    """Row tiling of ``dtype`` on TPU: 8 rows of f32, 16 of bf16, 32 of
    int8 -- the multiple a row block must be."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def _client_blocks(n: int, r: int, d: int, dtype, br: int, bd: int):
    """Row/column block of a ``(n, br, bd)`` client block inside
    :data:`_CLIENT_BLOCK_BYTES`: halve the (lane-aligned) column block
    first while even one row tile overflows, then shrink the row block
    to the budget.  ``r``/``d`` are the padded bucket dims (multiples of
    8 and 128); a block equal to the whole dim is always legal."""
    sub = _sublanes(dtype)
    bd = min(bd, d)
    while (bd > 128 and bd % 256 == 0
           and n * sub * bd * 4 > _CLIENT_BLOCK_BYTES):
        bd //= 2
    fit = max(sub, _CLIENT_BLOCK_BYTES // (n * bd * 4) // sub * sub)
    br = min(br, fit)
    return (r if br >= r else br), bd


def _packed_kernel(weights_ref, masks_ref, x_ref, *rest, n_clients: int,
                   norm_by: str, has_prev: bool, has_scales: bool = False):
    """Fused whole-round aggregation over a packed bucket (plan path).

    ``x``: (N, R, D) packed rows from *every* pair of the cohort that
    shares this bucket's (width, dtype); ``masks``: (R, N) per-row owner
    indicators precomputed on the host from the cohort's rank multiset
    (delta_{i,r} in packed-row form -- layer-stacked pairs just occupy
    more rows), laid out rows-major so a row block's ``(br, N)`` tile is
    legal at any ``br``; ``weights``: (N,) in SMEM; optional ``prev``:
    (R, D) packed previous global, the fallback for rows no participant
    owns.  One launch aggregates what a per-pair kernel would spread over
    2 x n_pairs launches.

    ``has_scales`` adds an (R, N) per-row scale operand (after ``x``,
    before ``prev``): each client row is multiplied by its scale on load,
    fusing int8 upload decoding (and the clipped mode's per-row clip)
    into the same pass -- the fp32 view of the payload never hits HBM.
    """
    rest = list(rest)
    scales_ref = rest.pop(0) if has_scales else None
    prev_ref = rest.pop(0) if has_prev else None
    (o_ref,) = rest
    br = x_ref.shape[1]
    num = jnp.zeros(o_ref.shape, jnp.float32)
    den = jnp.zeros((br, 1), jnp.float32)
    wtot = jnp.zeros((), jnp.float32)
    for nix in range(n_clients):                     # static unroll
        m = masks_ref[:, nix:nix + 1]                # (br, 1)
        w = weights_ref[nix]
        xn = x_ref[nix].astype(jnp.float32)
        if has_scales:
            xn = scales_ref[:, nix:nix + 1] * xn     # fused dequant
        num = num + (w * m) * xn
        den = den + w * m
        wtot = wtot + w
    if norm_by == "mask":
        fb = (prev_ref[...].astype(jnp.float32) if has_prev
              else jnp.zeros_like(num))
        out = jnp.where(den > 0, num / jnp.where(den > 0, den, 1.0), fb)
    else:
        out = num / wtot
    o_ref[...] = out.astype(o_ref.dtype)


def _bucket_call(name, kernel, x, masks, weights, prev, scales, out_dtype, br,
                 bd, interpret):
    """Launch a packed-bucket kernel over a ``(R/br, D/bd)`` grid: the
    client axis rides whole in each block, bounded by
    :func:`_client_blocks`; masks and scales go in as ``(R, N)`` so their
    ``(br, N)`` block is legal for every row block."""
    n, r, d = x.shape
    br, bd = _client_blocks(n, r, d, x.dtype, br, bd)
    rows_by_client = pl.BlockSpec((br, n), lambda i, j: (i, 0))
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM), rows_by_client,
                pl.BlockSpec((n, br, bd), lambda i, j: (0, i, j))]
    args = [weights.astype(jnp.float32), masks.astype(jnp.float32).T, x]
    if scales is not None:
        in_specs.append(rows_by_client)
        args.append(scales.astype(jnp.float32).T)
    if prev is not None:
        in_specs.append(pl.BlockSpec((br, bd), lambda i, j: (i, j)))
        args.append(prev)
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(r, br), pl.cdiv(d, bd)),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((br, bd), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, d), out_dtype),
        interpret=interpret,
        name=name,
    )(*args)


def _check_bucket(name, x, masks, prev, scales):
    n, r, d = x.shape
    if masks.shape != (n, r):
        raise ValueError(f"{name}: masks {masks.shape} != ({n}, {r})")
    if scales is not None and scales.shape != (n, r):
        raise ValueError(f"{name}: scales {scales.shape} != ({n}, {r})")
    if prev is not None and prev.shape != (r, d):
        raise ValueError(f"{name}: prev {prev.shape} != ({r}, {d})")


def _dequant(x, scales):
    xf = x.astype(jnp.float32)
    if scales is None:
        return xf
    return scales.astype(jnp.float32)[:, :, None] * xf


def packed_agg_pallas(x, masks, weights, prev=None, *,
                      norm_by: str = "mask", norm_restore: bool = False,
                      scales=None, out_dtype=None,
                      br=DEFAULT_BR, bd=DEFAULT_BD, interpret=True):
    """x: (N, R, D); masks: (N, R) f32; weights: (N,) f32; prev: (R, D)
    or None -> (R, D).  The plan path's fused bucket reduction: an
    explicit per-row owner-mask matrix (packed rows span many pairs, so
    a single rank vector cannot describe them) with prev-global
    retention fused in.  ``scales``:
    optional (N, R) f32 per-row dequantization scales fused on the load
    (int8 transport); ``out_dtype`` overrides the output dtype when ``x``
    is a wire dtype.

    ``norm_restore`` adds rbla_norm's per-row norm restoration: each
    output row is rescaled so its L2 norm matches the owners'
    weighted-mean row norm.  A row norm spans the whole width, which no
    bounded column block holds at published widths, so the client row
    norms and the output's rescale are XLA reductions around the tiled
    kernel."""
    _check_bucket("packed_agg", x, masks, prev, scales)
    out_dtype = out_dtype or x.dtype
    n = x.shape[0]
    kernel = functools.partial(_packed_kernel, n_clients=n, norm_by=norm_by,
                               has_prev=prev is not None,
                               has_scales=scales is not None)
    if not norm_restore:
        return _bucket_call("packed_agg", kernel, x, masks, weights, prev,
                            scales, out_dtype, br, bd, interpret)
    out = _bucket_call("packed_agg", kernel, x, masks, weights, prev, scales,
                       jnp.float32, br, bd, interpret)
    m = masks.astype(jnp.float32)
    xf = _dequant(x, scales)
    row_norms = m * jnp.sqrt(jnp.sum(xf * xf, axis=-1))          # (N, R)
    own = (m > 0).astype(jnp.float32) * weights.astype(jnp.float32)[:, None]
    target = jnp.sum(own * row_norms, axis=0) / (jnp.sum(own, axis=0)
                                                 + 1e-12)
    agg = jnp.sqrt(jnp.sum(out * out, axis=1))
    out = out * jnp.where(agg > 1e-12, target / (agg + 1e-12), 1.0)[:, None]
    return out.astype(out_dtype)


#: sentinel for unowned slots in the order-statistic kernels (matches
#: ref._SENTINEL): above any sane upload, finite under f32 averaging.
_SENTINEL = 1e30


def _packed_robust_kernel(weights_ref, masks_ref, x_ref, *rest,
                          n_clients: int, mode: str, trim_frac: float,
                          has_prev: bool, has_scales: bool = False):
    """Per-coordinate order statistics over a packed bucket (plan path).

    Same layout as :func:`_packed_kernel`.  ``mode="trimmed"`` /
    ``"median"`` run order statistics over each row's owners: unowned
    slots get a large sentinel, a static odd-even transposition network
    sorts the client axis (``jnp.sort`` does not lower in Mosaic; n is
    the cohort size, so the O(n^2) compare-exchange unroll stays small),
    and a per-row owner count selects the retained positions.  Rows
    nobody owns retain ``prev``.  ``has_scales`` dequantizes *before*
    the sort, so quantized uploads cannot widen the robustness bounds.
    ``weights`` is unused: order statistics on values, not on
    client-reported masses, is what bounds the breakdown point.
    """
    del weights_ref
    rest = list(rest)
    scales_ref = rest.pop(0) if has_scales else None
    prev_ref = rest.pop(0) if has_prev else None
    (o_ref,) = rest
    br = x_ref.shape[1]
    fb = (prev_ref[...].astype(jnp.float32) if has_prev
          else jnp.zeros(o_ref.shape, jnp.float32))
    vals = []
    cnt = jnp.zeros((br, 1), jnp.int32)
    for nix in range(n_clients):
        m = masks_ref[:, nix:nix + 1]                # (br, 1)
        xn = x_ref[nix].astype(jnp.float32)
        if has_scales:
            xn = scales_ref[:, nix:nix + 1] * xn     # fused dequant
        vals.append(jnp.where(m > 0, xn, _SENTINEL))
        cnt = cnt + (m > 0).astype(jnp.int32)
    for rnd in range(n_clients):                     # odd-even sort
        for i in range(rnd % 2, n_clients - 1, 2):
            lo = jnp.minimum(vals[i], vals[i + 1])
            vals[i + 1] = jnp.maximum(vals[i], vals[i + 1])
            vals[i] = lo
    acc = jnp.zeros(o_ref.shape, jnp.float32)
    if mode == "median":
        lo_ix = jnp.maximum((cnt - 1) // 2, 0)
        hi_ix = cnt // 2
        for j in range(n_clients):
            sel = 0.5 * ((lo_ix == j).astype(jnp.float32)
                         + (hi_ix == j).astype(jnp.float32))
            acc = acc + sel * vals[j]
        out = acc
    else:                                            # trimmed
        k = jnp.minimum(
            jnp.floor(trim_frac * cnt.astype(jnp.float32)).astype(jnp.int32),
            jnp.maximum((cnt - 1) // 2, 0))
        for j in range(n_clients):
            inc = ((j >= k) & (j < cnt - k)).astype(jnp.float32)
            acc = acc + inc * vals[j]
        keep = (cnt - 2 * k).astype(jnp.float32)
        out = acc / jnp.maximum(keep, 1.0)
    o_ref[...] = jnp.where(cnt > 0, out, fb).astype(o_ref.dtype)


def packed_robust_pallas(x, masks, weights, prev=None, *, mode: str,
                         clip_norm: float = 0.0, trim_frac: float = 0.0,
                         scales=None, out_dtype=None,
                         br=DEFAULT_BR, bd=DEFAULT_BD, interpret=True):
    """x: (N, R, D); masks: (N, R) f32; weights: (N,) f32; prev: (R, D)
    or None -> (R, D).  Byzantine-robust sibling of
    :func:`packed_agg_pallas`: one fused launch per packed bucket, with
    per-client norm clipping (``mode="clipped"``), per-coordinate trimmed
    mean (``"trimmed"``), or coordinate-wise median (``"median"``) in
    place of the weighted mean.  Numerics match
    ``ref.packed_robust_ref``.  ``scales``/``out_dtype`` as in
    :func:`packed_agg_pallas` (dequant applied before clip/sort).

    Clipping is a per-(client, row) scale ``min(1, clip / ||row||)``:
    the full-width row norms are one XLA reduction, and the scales ride
    into the masked-mean kernel on the dequantization operand."""
    _check_bucket("packed_robust", x, masks, prev, scales)
    if mode not in ("clipped", "trimmed", "median"):
        raise ValueError(f"unknown robust mode {mode!r}; options: "
                         f"['clipped', 'median', 'trimmed']")
    out_dtype = out_dtype or x.dtype
    n = x.shape[0]
    if mode == "clipped":
        xf = _dequant(x, scales)
        norms = jnp.sqrt(jnp.sum(xf * xf, axis=-1))               # (N, R)
        clip = jnp.minimum(1.0, clip_norm / jnp.maximum(norms, 1e-12))
        row_scales = clip if scales is None else scales * clip
        kernel = functools.partial(_packed_kernel, n_clients=n,
                                   norm_by="mask",
                                   has_prev=prev is not None,
                                   has_scales=True)
        return _bucket_call("packed_robust", kernel, x, masks, weights,
                            prev, row_scales, out_dtype, br, bd, interpret)
    kernel = functools.partial(_packed_robust_kernel, n_clients=n, mode=mode,
                               trim_frac=float(trim_frac),
                               has_prev=prev is not None,
                               has_scales=scales is not None)
    return _bucket_call("packed_robust", kernel, x, masks, weights, prev,
                        scales, out_dtype, br, bd, interpret)


def _packed_stack_kernel(xblk_ref, pblk_ref, tag_ref, scales_ref, x_ref,
                         *rest):
    """Fused FLoRA stacking, one output row tile per grid step.  The host
    table gives each tile its source -- a tile of the packed cohort
    (``kind`` 1), of the packed previous global (2), or none (0: the cap
    padding, written as zeros) -- and the index of its scale, packed as
    ``tag = 4 * scale_idx + kind``.  The index maps read the source
    tables, so every output row is read once, scaled in VMEM and written
    once."""
    del xblk_ref, pblk_ref                    # read by the index maps
    o_ref = rest[-1]
    tag = tag_ref[pl.program_id(0)]
    kind = tag % 4
    scale = scales_ref[tag // 4]

    @pl.when(kind == 0)
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    for k, src_ref in enumerate((x_ref,) + tuple(rest[:-1]), start=1):
        @pl.when(kind == k)
        def _(src_ref=src_ref):
            o_ref[...] = (scale * src_ref[...].astype(jnp.float32)
                          ).astype(o_ref.dtype)


def stack_row_tile(copies_x, copies_prev, r_in: int, out_rows: int,
                   d: int, dtype, *, interpret: bool) -> int:
    """Rows per grid step of :func:`packed_stack_pallas`: the largest
    divisor of every copy bound (and of ``r_in`` and ``out_rows``) whose
    ``(tile, d)`` f32 block fits half the client block budget.  Compiled,
    the tile must be a multiple of ``dtype``'s row tiling; 0 means no
    such tile exists (the caller stacks in XLA)."""
    g = math.gcd(r_in, out_rows, *(v for c in copies_x for v in c[1:4]),
                 *(v for c in copies_prev for v in c[0:3]))
    sub = 1 if interpret else _sublanes(dtype)
    fits = [t for t in range(sub, g + 1, sub)
            if g % t == 0 and t * d * 4 <= _CLIENT_BLOCK_BYTES // 2]
    return max(fits, default=0)


def packed_stack_pallas(x, scales, prev=None, *, copies_x=(),
                        copies_prev=(), out_rows: int, interpret=True):
    """x: (N, R_in, D); scales: (S,) f32; prev: (R_prev, D) or None ->
    (out_rows, D).  One launch stacks every packable pair of the cohort
    (the plan path's flora bucket).  ``copies_x`` entries are
    ``(client, src_row, dst_row, rows, scale_idx)``; ``copies_prev`` drop
    the client index and read ``prev``; a later copy overwrites an
    earlier one's rows.

    The grid walks the output in row tiles (:func:`stack_row_tile`);
    compiled, every copy must start and end on ``x``'s row tiling."""
    n, r_in, d = x.shape
    for (src, s0, d0, nr, si) in copies_x:
        if not (0 <= src < n and 0 <= s0 and s0 + nr <= r_in
                and 0 <= d0 and d0 + nr <= out_rows and 0 <= si):
            raise ValueError(f"packed_stack: bad copy {(src, s0, d0, nr, si)}")
    if copies_prev and prev is None:
        raise ValueError("packed_stack: prev copies but no prev buffer")
    for (s0, d0, nr, si) in copies_prev:
        if not (0 <= s0 and s0 + nr <= prev.shape[0]
                and 0 <= d0 and d0 + nr <= out_rows):
            raise ValueError(f"packed_stack: bad prev copy {(s0, d0, nr, si)}")
    dtypes = [x.dtype] + ([prev.dtype] if prev is not None else [])
    tile = min(stack_row_tile(copies_x, copies_prev, r_in, out_rows, d, dt,
                              interpret=interpret) for dt in dtypes)
    if not tile:
        raise ValueError(
            f"packed_stack: compiled copies must start and end on "
            f"{_sublanes(x.dtype)}-row tiles")
    nblk = out_rows // tile
    xblk = np.full(nblk, -1, np.int32)
    pblk = np.full(nblk, -1, np.int32)
    tag = np.zeros(nblk, np.int32)
    for (src, s0, d0, nr, si) in copies_x:
        k = np.arange(d0 // tile, (d0 + nr) // tile)
        xblk[k] = (src * r_in + s0) // tile + (k - d0 // tile)
        tag[k] = 4 * si + 1
    for (s0, d0, nr, si) in copies_prev:
        k = np.arange(d0 // tile, (d0 + nr) // tile)
        pblk[k] = s0 // tile + (k - d0 // tile)
        tag[k] = 4 * si + 2
    # a tile that does not read a source keeps that source's previous
    # block index, so the pipeline does not fetch it again
    for blk in (xblk, pblk):
        last = 0
        for k in range(nblk):
            if blk[k] < 0:
                blk[k] = last
            last = blk[k]
    row = lambda k, xb, pb, tg: (xb[k], 0)          # noqa: E731
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((tile, d), row)]
    args = [jnp.asarray(scales, jnp.float32), x.reshape(n * r_in, d)]
    if prev is not None:
        in_specs.append(pl.BlockSpec((tile, d),
                                     lambda k, xb, pb, tg: (pb[k], 0)))
        args.append(prev)
    return pl.pallas_call(
        _packed_stack_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(nblk,), in_specs=in_specs,
            out_specs=pl.BlockSpec((tile, d),
                                   lambda k, xb, pb, tg: (k, 0))),
        out_shape=jax.ShapeDtypeStruct((out_rows, d), x.dtype),
        interpret=interpret,
        name="packed_stack",
    )(jnp.asarray(xblk), jnp.asarray(pblk), jnp.asarray(tag), *args)


def _axpy_kernel(alpha_ref, x_ref, y_ref, o_ref):
    """Staleness-weighted fold: o = y + alpha_row * (x - y).

    ``alpha`` rides along as a per-row (br, 1) f32 column so the same kernel
    serves both the scalar server-mixing fold (uniform alpha) and RBLA's
    per-rank-row running masked mean (row-dependent alpha: rows the client
    does not own get alpha 0 and pass ``y`` through untouched).
    """
    a = alpha_ref[...]                                       # (br, 1)
    y = y_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] = (y + a * (x - y)).astype(o_ref.dtype)


def axpy_fold_pallas(y, x, alpha, *, br=DEFAULT_BR, bd=DEFAULT_BD,
                     interpret=True):
    """y, x: (R, D); alpha: (R,) f32 -> (R, D) = y + alpha[:, None]*(x-y).

    The async server's hot loop: one arriving client update folded into
    the live global in a single pass.  Bandwidth-bound like ``packed_agg``
    but reads 2*R*D and writes R*D with no client axis at all -- the
    per-update cost of fully-async aggregation is independent of the
    cohort size.
    """
    r, d = y.shape
    if x.shape != y.shape:
        raise ValueError(f"axpy_fold: x {x.shape} vs y {y.shape}")
    if alpha.shape != (r,):
        raise ValueError(f"axpy_fold: alpha {alpha.shape} != ({r},)")
    br, bd = min(br, r), min(bd, d)
    grid = (pl.cdiv(r, br), pl.cdiv(d, bd))
    return pl.pallas_call(
        _axpy_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((br, bd), lambda i, j: (i, j)),
            pl.BlockSpec((br, bd), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((br, bd), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, d), y.dtype),
        interpret=interpret,
        name="axpy_fold",
    )(alpha.astype(jnp.float32).reshape(r, 1), x, y)
