"""Fused LoRA matmul Pallas TPU kernels (single- and multi-adapter).

``lora_matmul_pallas`` computes  y = x @ W + scale * (x @ A^T) @ B^T  in
ONE pass over x: the low-rank path shares x's VMEM residency with the
frozen-weight matmul instead of streaming x from HBM twice (the usual
two-matmul lowering).

Grid (i, j, k) over (M/bm, N/bn, K/bk); k innermost.  Accumulators live in
VMEM scratch:
  acc (bm, bn) f32 -- frozen-path partial sums
  axr (bm, r)  f32 -- x @ A^T partial sums (r <= 128 fits VMEM)
At the last k step the low-rank correction axr @ B_j^T is added and the
tile is written out.  Matmul dims should be multiples of 128 for MXU
alignment (ops.py pads otherwise).  VMEM working set per step:
bm*bk + bk*bn + r*bk + bn*r + bm*bn + bm*r floats -- defaults (256, 256,
512) with r<=128 stay under ~2 MB, well inside the ~16 MB v5e VMEM budget
with double buffering.

``batched_lora_matmul_pallas`` is the multi-tenant extension (the FLaaS
serving hot path): many (A, B) pairs of *heterogeneous rank* live packed
as rank-row segments of two row-major buffers, and each request row of x
selects its own segment via per-request (offset, count, scale) **data**:

  y_i = x_i @ W + scale_i * sum_p in seg_i (x_i . a_rows[p]) * b_rows[p]

Row p of ``a_rows`` and row p of ``b_rows`` belong to the same rank-one
component, so the contraction is the masked product
``(x @ a_rows^T) * seg_mask @ b_rows`` with ``seg_mask[i, p] =
off_i <= p < off_i + cnt_i`` built from a lane iota -- no gather, no
per-tenant shapes, and therefore ONE executable for every tenant mix.
The packed rank axis R_total rides whole through the grid like the
single-adapter r does; VMEM adds bm*R + 2*R*max(bk, bn) floats, so keep
R_total <= ~2048 at the default blocks (ops.py shrinks bk/bn as R
grows).

f32 operands contract at ``Precision.HIGHEST`` (Mosaic's fp32 matmul)
so a compiled f32 kernel keeps f32 accuracy; bf16 operands use the
MXU's native bf16 products with f32 accumulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..runtime import auto_interpret

DEFAULT_BM = 256
DEFAULT_BN = 256
DEFAULT_BK = 512


def _dot(a, b, dims):
    prec = (jax.lax.Precision.HIGHEST if a.dtype == jnp.float32
            and b.dtype == jnp.float32 else None)
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=prec,
                               preferred_element_type=jnp.float32)


def _kernel(x_ref, w_ref, a_ref, b_ref, scale_ref, o_ref, acc_ref, axr_ref,
            *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        axr_ref[...] = jnp.zeros_like(axr_ref)

    x = x_ref[...]
    acc_ref[...] += _dot(x, w_ref[...], ((1,), (0,)))
    axr_ref[...] += _dot(x, a_ref[...], ((1,), (1,)))

    @pl.when(k == n_k - 1)
    def _finish():
        lora = _dot(axr_ref[...], b_ref[...], ((1,), (1,)))
        y = acc_ref[...] + scale_ref[0, 0] * lora
        o_ref[...] = y.astype(o_ref.dtype)


def lora_matmul_pallas(x, w, a, b, scale, *, bm=DEFAULT_BM, bn=DEFAULT_BN,
                       bk=DEFAULT_BK, interpret=None):
    """x (M,K) @ w (K,N) + scale * ((x @ a^T) @ b^T).  a: (r,K), b: (N,r).

    scale: (1,1) f32.  Shapes must tile evenly (ops.py pads).
    ``interpret=None`` auto-detects (compiled on TPU/GPU, interpreter on
    CPU), matching the rbla_agg wrapper convention.
    """
    interpret = auto_interpret(interpret)
    m, k = x.shape
    _, n = w.shape
    r = a.shape[0]
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    n_k = pl.cdiv(k, bk)
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), n_k)

    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((r, bk), lambda i, j, kk: (0, kk)),
            pl.BlockSpec((bn, r), lambda i, j, kk: (j, 0)),
            pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, r), jnp.float32),
        ],
        interpret=interpret,
    )(x, w, a, b, scale)


def _batched_kernel(x_ref, w_ref, a_ref, b_ref, off_ref, cnt_ref,
                    scale_ref, o_ref, acc_ref, axr_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        axr_ref[...] = jnp.zeros_like(axr_ref)

    x = x_ref[...]
    acc_ref[...] += _dot(x, w_ref[...], ((1,), (0,)))
    axr_ref[...] += _dot(x, a_ref[...], ((1,), (1,)))

    @pl.when(k == n_k - 1)
    def _finish():
        # per-request segment mask over the packed rank axis: request i
        # owns rows [off_i, off_i + cnt_i) of a_rows/b_rows -- runtime
        # data, so one trace serves every tenant mix
        bm, r_tot = axr_ref.shape
        p = jax.lax.broadcasted_iota(jnp.int32, (bm, r_tot), 1)
        off = off_ref[...]                        # (bm, 1) int32
        cnt = cnt_ref[...]
        seg = (p >= off) & (p < off + cnt)
        axr = jnp.where(seg, axr_ref[...], 0.0) * scale_ref[...]
        lora = _dot(axr, b_ref[...], ((1,), (0,)))
        o_ref[...] = (acc_ref[...] + lora).astype(o_ref.dtype)


def batched_lora_matmul_pallas(x, w, a_rows, b_rows, off, cnt, scale, *,
                               bm=DEFAULT_BM, bn=DEFAULT_BN,
                               bk=DEFAULT_BK, interpret=None):
    """Multi-adapter fused LoRA matmul over packed rank-row segments.

    x: (M, K); w: (K, N); a_rows: (R, K); b_rows: (R, N) -- B transposed
    so the packed rank axis leads both factor buffers (row p of each is
    the same rank-one component).  off/cnt: (M, 1) int32 per-request
    segment bounds into R; scale: (M, 1) f32 per-request LoRA scale.
    Shapes must tile evenly (ops.py pads; R to lane alignment with
    cnt=0 padding segments).
    """
    interpret = auto_interpret(interpret)
    m, k = x.shape
    _, n = w.shape
    r_tot = a_rows.shape[0]
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    n_k = pl.cdiv(k, bk)
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), n_k)

    return pl.pallas_call(
        functools.partial(_batched_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((r_tot, bk), lambda i, j, kk: (0, kk)),
            pl.BlockSpec((r_tot, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, r_tot), jnp.float32),
        ],
        interpret=interpret,
    )(x, w, a_rows, b_rows, off, cnt, scale)
