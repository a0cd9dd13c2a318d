"""jit'd public wrappers for the fused LoRA matmul kernels.

Follows the ``rbla_agg`` ops conventions: the public entry points
(``lora_matmul``, ``batched_lora_matmul``) are jitted and **count as one
tracked dispatch each** (``repro.core.plan.dispatch_counter``); the
``*_inline`` variants run un-jitted for use *inside* an already compiled
computation (the serving engine's fused forward, compiled plan rounds);
``interpret=None`` auto-detects (compiled Pallas on TPU/GPU, interpreter
mode on CPU where Pallas cannot lower).

``batched_lora_matmul`` is the multi-tenant serving entry: one launch
applies many packed (A, B) segments of heterogeneous rank to a mixed
request batch, with per-request adapter ids resolved against per-tenant
(offset, rank, scale) tables *inside* the jitted computation -- ids and
ranks are data, so one executable serves every tenant mix.
``trace_counts`` records how many times each public entry was traced
(the serving no-retrace guard reads it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..runtime import (auto_interpret, count_dispatch, note_trace,
                       trace_counts as runtime_trace_counts)
from .kernel import batched_lora_matmul_pallas, lora_matmul_pallas
from .ref import (batched_lora_matmul_ref, batched_lora_matmul_segments,
                  lora_matmul_ref)

#: public-entry trace counts: name -> times jax retraced it.  A retrace
#: means a new executable (new shapes/dtypes/static args); serving across
#: changing tenant mixes must not move these (tests/test_serving.py).
#: Now a live dict view over the shared ``kernel_traces_total`` metric
#: (see :mod:`repro.kernels.runtime`); ``[]`` / ``.get`` keep working.
trace_counts = runtime_trace_counts


def _pad_to(v: int, mult: int) -> int:
    return (v + mult - 1) // mult * mult


def _reduction_block(dim: int, block: int) -> int:
    """The largest multiple of 128 that is at most ``block`` and divides
    ``dim`` (itself a multiple of 128).  The contraction axis is a grid
    reduction: a partial last block would add whatever lies past the
    array's end into the accumulator (the compiled kernel reads it as
    is, interpret mode as NaN)."""
    b = min(block, dim)
    while dim % b:
        b -= 128
    return b


def lora_matmul_inline(x, w, a, b, scale, *, interpret=None, bm=256,
                       bn=256, bk=512):
    """Un-jitted :func:`lora_matmul` body (for use inside compiled
    computations)."""
    interpret = auto_interpret(interpret)
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = w.shape[-1]
    r = a.shape[0]
    x2 = x.reshape(-1, k)
    m = x2.shape[0]

    mp, np_, kp = _pad_to(m, 128), _pad_to(n, 128), _pad_to(k, 128)
    rp = _pad_to(r, 128)
    x2 = jnp.pad(x2, ((0, mp - m), (0, kp - k)))
    wp = jnp.pad(w, ((0, kp - k), (0, np_ - n)))
    ap = jnp.pad(a, ((0, rp - r), (0, kp - k)))
    bp = jnp.pad(b, ((0, np_ - n), (0, rp - r)))
    sc = jnp.asarray(scale, jnp.float32).reshape(1, 1)

    y = lora_matmul_pallas(x2, wp, ap, bp, sc,
                           bm=min(bm, mp), bn=min(bn, np_),
                           bk=_reduction_block(kp, bk), interpret=interpret)
    return y[:m, :n].reshape(lead + (n,))


@functools.partial(jax.jit, static_argnames=("interpret", "bm", "bn", "bk"))
def _lora_matmul_jit(x, w, a, b, scale, *, interpret, bm, bn, bk):
    note_trace("lora_matmul")
    return lora_matmul_inline(x, w, a, b, scale, interpret=interpret,
                              bm=bm, bn=bn, bk=bk)


def lora_matmul(x, w, a, b, scale, *, interpret=None, bm=256, bn=256,
                bk=512):
    """x (..., K) @ w (K, N) + scale * (x @ a^T) @ b^T  via the Pallas
    kernel.  a: (r, K), b: (N, r), scale scalar."""
    count_dispatch(kernel="lora_matmul")
    return _lora_matmul_jit(x, w, a, b, scale, interpret=interpret,
                            bm=bm, bn=bn, bk=bk)


# ----------------------------------------------------- batched multi-adapter
def resolve_impl(impl: str | None) -> str:
    """Resolve the batched entry's ``impl`` tri-state: ``"auto"`` picks
    the fused Pallas kernel where it compiles (TPU/GPU) and the XLA
    segment lowering on CPU (interpreted Pallas is a debugging mode, not
    a serving path)."""
    if impl in (None, "auto"):
        return "xla" if auto_interpret(None) else "pallas"
    if impl not in ("pallas", "xla"):
        raise ValueError(
            f"unknown batched lora_matmul impl {impl!r}; options: "
            "auto | pallas | xla")
    return impl


def batched_lora_matmul_inline(x, w, a_rows, b_rows, adapter_ids, seg_off,
                               seg_rank, seg_scale, *, impl="auto",
                               interpret=None, bm=256, bn=256, bk=512):
    """Un-jitted :func:`batched_lora_matmul` body."""
    impl = resolve_impl(impl)
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = w.shape[-1]
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    ids = jnp.asarray(adapter_ids, jnp.int32).reshape(-1)
    # per-request segment metadata: a gather over runtime tables, traced
    # once -- changing ids / offsets / ranks never retraces
    off = jnp.asarray(seg_off, jnp.int32)[ids]
    cnt = jnp.asarray(seg_rank, jnp.int32)[ids]
    sc = jnp.asarray(seg_scale, jnp.float32)[ids]

    if impl == "xla":
        y = batched_lora_matmul_segments(x2, w, a_rows, b_rows, off, cnt,
                                         sc)
        return y.reshape(lead + (n,))

    r_tot = a_rows.shape[0]
    interpret = auto_interpret(interpret)
    mp, np_, kp = _pad_to(m, 128), _pad_to(n, 128), _pad_to(k, 128)
    rp = _pad_to(r_tot, 128)
    # keep the (bm, R) + 2 * (R, max(bk, bn)) VMEM residency bounded as
    # the packed rank axis grows
    while rp * max(bk, bn) > 2 ** 20 and max(bk, bn) > 128:
        bk, bn = max(bk // 2, 128), max(bn // 2, 128)
    x2 = jnp.pad(x2, ((0, mp - m), (0, kp - k)))
    wp = jnp.pad(w, ((0, kp - k), (0, np_ - n)))
    ap = jnp.pad(a_rows, ((0, rp - r_tot), (0, kp - k)))
    bp = jnp.pad(b_rows, ((0, rp - r_tot), (0, np_ - n)))
    # padded requests carry an empty segment (cnt = 0): pure zero rows
    off = jnp.pad(off, (0, mp - m)).reshape(-1, 1)
    cnt = jnp.pad(cnt, (0, mp - m)).reshape(-1, 1)
    sc = jnp.pad(sc, (0, mp - m)).reshape(-1, 1)
    y = batched_lora_matmul_pallas(x2, wp, ap, bp, off, cnt, sc,
                                   bm=min(bm, mp), bn=min(bn, np_),
                                   bk=_reduction_block(kp, bk),
                                   interpret=interpret)
    return y[:m, :n].reshape(lead + (n,))


@functools.partial(jax.jit, static_argnames=("impl", "interpret", "bm",
                                             "bn", "bk"))
def _batched_lora_matmul_jit(x, w, a_rows, b_rows, adapter_ids, seg_off,
                             seg_rank, seg_scale, *, impl, interpret, bm,
                             bn, bk):
    note_trace("batched_lora_matmul")
    return batched_lora_matmul_inline(
        x, w, a_rows, b_rows, adapter_ids, seg_off, seg_rank, seg_scale,
        impl=impl, interpret=interpret, bm=bm, bn=bn, bk=bk)


def batched_lora_matmul(x, w, a_rows, b_rows, adapter_ids, seg_off,
                        seg_rank, seg_scale, *, impl="auto",
                        interpret=None, bm=256, bn=256, bk=512):
    """One launch, many adapters:  for every request row i of x,

        y_i = x_i @ w + seg_scale[t] * (x_i @ A_t^T) @ B_t^T,
        t = adapter_ids[i]

    where tenant t's factors live as rank-row segment
    ``[seg_off[t], seg_off[t] + seg_rank[t])`` of the packed buffers
    ``a_rows`` (R_total, K) and ``b_rows`` (R_total, N) (B transposed so
    row p of both is the same rank-one component -- the
    :class:`~repro.serving.AdapterStore` layout).  ``adapter_ids``
    (matching x's leading dims) and all three per-tenant tables are
    runtime data: one compiled executable serves every tenant mix, rank
    multiset, and table content.  A tenant with ``seg_rank[t] == 0``
    (unregistered / evicted) gets the pure base matmul.
    """
    count_dispatch(kernel="batched_lora_matmul")
    return _batched_lora_matmul_jit(
        x, w, a_rows, b_rows, adapter_ids, seg_off, seg_rank, seg_scale,
        impl=impl, interpret=interpret, bm=bm, bn=bn, bk=bk)


def lora_dense_apply(p, x, pair, alpha: float = 16.0, interpret=None):
    """Drop-in replacement for models.common.dense on 2-D kernels with a
    LoRA pair: uses the fused kernel for the matmul + low-rank path."""
    scale = alpha / jnp.maximum(pair["rank"].astype(jnp.float32), 1.0)
    y = lora_matmul(x, p["w"], pair["A"], pair["B"], scale,
                    interpret=interpret)
    if "b" in p:
        y = y + p["b"]
    return y


__all__ = ["lora_matmul", "lora_matmul_inline", "lora_dense_apply",
           "lora_matmul_ref", "batched_lora_matmul",
           "batched_lora_matmul_inline", "batched_lora_matmul_ref",
           "batched_lora_matmul_segments", "resolve_impl",
           "trace_counts"]
