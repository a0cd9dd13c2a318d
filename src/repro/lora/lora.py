"""LoRA adapter substrate with heterogeneous-rank support.

Adapters are plain pytrees so they flow through jit/pjit/psum unchanged:

    pair = {"A": (r_max, fan_in), "B": (fan_out, r_max), "rank": ()} int32

Storage is always padded to ``r_max`` (static shapes for XLA); the live rank
is a scalar leaf.  Rows of ``A`` / columns of ``B`` at index >= rank are
zero, and stay zero under SGD/Adam because the gradient of a padded row is
itself gated by the (zero) opposite factor -- we additionally re-mask after
every optimizer step for belt-and-braces numerical hygiene.

Scaling follows HetLoRA/the paper: effective update is
``(alpha / rank) * B @ A``, so clients with different ranks produce updates
of comparable magnitude.
"""
from __future__ import annotations

from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp

from repro.core.masks import axis_mask, pad_to_rank, rank_mask

Array = jax.Array
PyTree = Any

DEFAULT_ALPHA = 16.0


def init_pair(key: Array, fan_out: int, fan_in: int, r_max: int,
              rank: int | Array, dtype=jnp.float32,
              init_scale: float = 0.01,
              leading: tuple[int, ...] = ()) -> dict:
    """A ~ N(0, init_scale) on live rows, B = 0 (standard LoRA init).

    ``leading`` adds stacked axes (scan-over-layers repeat, MoE expert
    axis): A ``(*leading, r_max, fan_in)``, B ``(*leading, fan_out,
    r_max)``, rank ``(leading[0],)`` if stacked over layers else scalar.
    """
    a = jax.random.normal(key, leading + (r_max, fan_in), dtype) * init_scale
    rank_arr = (jnp.full((leading[0],), rank, jnp.int32) if leading
                else jnp.asarray(rank, jnp.int32))
    pair = {
        "A": a,
        "B": jnp.zeros(leading + (fan_out, r_max), dtype),
        "rank": rank_arr,
    }
    return mask_pair(pair)


def is_pair(node: Any) -> bool:
    return (isinstance(node, Mapping) and "A" in node and "B" in node
            and "rank" in node)


def pair_scale(pair: Mapping, alpha: float = DEFAULT_ALPHA) -> Array:
    r = jnp.maximum(pair["rank"].astype(jnp.float32), 1.0)
    return alpha / r


def apply_pair(x: Array, pair: Mapping, alpha: float = DEFAULT_ALPHA) -> Array:
    """``(alpha/rank) * (x @ A^T) @ B^T`` -- the LoRA path of a dense layer.

    ``x``: (..., fan_in) -> (..., fan_out).  Padded rows are structurally
    zero so no masking is needed on the forward path.
    """
    ax = jnp.einsum("...i,ri->...r", x, pair["A"].astype(x.dtype))
    y = jnp.einsum("...r,or->...o", ax, pair["B"].astype(x.dtype))
    return y * pair_scale(pair, alpha).astype(x.dtype)


def merge_pair(w: Array, pair: Mapping, alpha: float = DEFAULT_ALPHA) -> Array:
    """Return ``W + (alpha/rank) B A`` (serving-time merged weights)."""
    delta = (pair["B"].astype(jnp.float32) @ pair["A"].astype(jnp.float32))
    return (w.astype(jnp.float32)
            + pair_scale(pair, alpha) * delta).astype(w.dtype)


def _rank_vec_mask(rank: Array, r_max: int, dtype=jnp.float32) -> Array:
    """(..., r_max) mask from scalar-or-vector rank."""
    rank = jnp.asarray(rank, jnp.int32)
    iota = jax.lax.iota(jnp.int32, r_max)
    return (iota < rank[..., None]).astype(dtype) if rank.ndim else \
        (iota < rank).astype(dtype)


def _pair_row_masks(pair: Mapping, dtype=jnp.float32):
    """Broadcastable masks for A (..., r_max, fan_in) / B (..., out, r_max).

    rank may be scalar or (leading,) for layer-stacked pairs; extra middle
    axes (e.g. MoE expert axis) broadcast via singleton dims.
    """
    A, B, rank = pair["A"], pair["B"], jnp.asarray(pair["rank"], jnp.int32)
    r_max = A.shape[-2]
    m = _rank_vec_mask(rank, r_max, dtype)        # rank.shape + (r_max,)
    ndim_mid_a = A.ndim - rank.ndim - 2
    ma = m.reshape(rank.shape + (1,) * ndim_mid_a + (r_max, 1))
    ndim_mid_b = B.ndim - rank.ndim - 2
    mb = m.reshape(rank.shape + (1,) * ndim_mid_b + (1, r_max))
    return ma, mb


def mask_pair(pair: Mapping) -> dict:
    """Re-zero padded rows/cols (post-optimizer hygiene)."""
    ma, mb = _pair_row_masks(pair, pair["A"].dtype)
    return {"A": pair["A"] * ma, "B": pair["B"] * mb, "rank": pair["rank"]}


def pair_masks(pair: Mapping) -> dict:
    """delta_{i,r} masks matching the pair's structure (for aggregation).

    ``rank`` itself is marked fully-shared (0-d ones) -- the server keeps
    r_max; clients re-slice per Alg. 2.
    """
    ma, mb = _pair_row_masks(pair)
    return {"A": ma, "B": mb, "rank": jnp.ones(())}


# ------------------------------------------------------------- tree ops ----
def tree_map_pairs(fn: Callable[[Mapping], Any], tree: PyTree) -> PyTree:
    """Map ``fn`` over every LoRA pair in a nested adapter tree."""
    if is_pair(tree):
        return fn(tree)
    if isinstance(tree, Mapping):
        return {k: tree_map_pairs(fn, v) for k, v in tree.items()}
    if isinstance(tree, (tuple, list)):
        return type(tree)(tree_map_pairs(fn, v) for v in tree)
    return tree


def adapter_masks(adapters: PyTree) -> PyTree:
    """Mask tree (same structure) for
    ``AggregationStrategy.aggregate_tree``."""
    return tree_map_pairs(pair_masks, adapters)


def mask_adapters(adapters: PyTree) -> PyTree:
    return tree_map_pairs(mask_pair, adapters)


def set_ranks(adapters: PyTree, rank: int | Array,
              r_storage: int | None = None) -> PyTree:
    """Client-side Alg. 2 under static shapes: set the live rank and
    re-mask (equivalent to slice + re-pad).

    ``r_storage`` re-cuts the *storage* rank: rows/cols beyond it are
    sliced off, smaller storage is zero-padded up.  This is how clients
    re-slice from a rank-growing global (e.g. flora keeps the server at
    ``stack_r_cap`` storage while clients train at ``r_max``) without
    changing their compiled shapes round to round.

    The result never aliases the input buffers: every returned array is
    freshly materialized (the re-mask multiply), so a client that
    mutates its local adapters in place (numpy-backed state, in-place
    optimizers) can never corrupt ``ServerState.adapters``.
    """
    if (r_storage is not None
            and not isinstance(rank, jax.core.Tracer)
            and int(jnp.max(jnp.asarray(rank))) > r_storage):
        raise ValueError(
            f"set_ranks: live rank {int(jnp.max(jnp.asarray(rank)))} "
            f"exceeds the target storage rank {r_storage}; the pair's "
            "rank leaf would claim rows that do not physically exist")

    def f(pair):
        A, B = jnp.asarray(pair["A"]), jnp.asarray(pair["B"])
        if r_storage is not None:
            cur = A.shape[-2]
            if cur >= r_storage:
                A = A[..., :r_storage, :]
                B = B[..., :r_storage]
            else:
                A = pad_to_rank(A, -2, r_storage)
                B = pad_to_rank(B, -1, r_storage)
        out = {"A": A, "B": B,
               "rank": jnp.full_like(jnp.asarray(pair["rank"], jnp.int32),
                                     rank)}
        # mask_pair multiplies by the rank mask, which also guarantees a
        # fresh buffer (copy, not alias, of the server's storage)
        return mask_pair(out)
    return tree_map_pairs(f, adapters)


def strip_ranks(adapters: PyTree) -> tuple[PyTree, PyTree]:
    """Split pairs into differentiable factors and int rank leaves.

    jax.grad rejects int32 inputs; ranks are data, not parameters, so the
    training loop carries them separately and reattaches via
    :func:`attach_ranks`.
    """
    factors = tree_map_pairs(lambda p: {"A": p["A"], "B": p["B"]}, adapters)
    ranks = tree_map_pairs(lambda p: p["rank"], adapters)
    return factors, ranks


def attach_ranks(factors: PyTree, ranks: PyTree) -> PyTree:
    if isinstance(factors, Mapping) and "A" in factors and "B" in factors:
        return {"A": factors["A"], "B": factors["B"], "rank": ranks}
    if isinstance(factors, (tuple, list)):
        return type(factors)(attach_ranks(f, r)
                             for f, r in zip(factors, ranks))
    return {k: attach_ranks(factors[k], ranks[k]) for k in factors}


def init_adapters(key: Array, specs: Mapping[str, tuple[int, int]],
                  r_max: int, rank: int | Array,
                  dtype=jnp.float32) -> PyTree:
    """Build an adapter tree from ``{path: (fan_out, fan_in)}`` specs."""
    keys = jax.random.split(key, max(len(specs), 1))
    return {path: init_pair(k, fo, fi, r_max, rank, dtype)
            for k, (path, (fo, fi)) in zip(keys, sorted(specs.items()))}


def count_params(adapters: PyTree) -> int:
    leaves = jax.tree.leaves(adapters)
    return sum(int(x.size) for x in leaves)
