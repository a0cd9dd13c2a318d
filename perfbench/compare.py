"""The comparison that decides ``correct``: a program's answer against
its reference, leaf by leaf."""
from __future__ import annotations

import jax.numpy as jnp

import gen


def pairs_of(tree) -> list:
    """A tree's LoRA pairs, in tree order."""
    return [p for _, p in gen.pair_list(tree)]


def worst_leaf_gap(got, want) -> float:
    """Largest ``max|got - want| / max|want|`` over the leaves of two
    lists of ``{"A", "B"}`` dicts: the worst leaf's error as a share of
    that leaf's scale."""
    worst = 0.0
    for g, w in zip(got, want, strict=True):
        for side in ("A", "B"):
            gv = jnp.asarray(g[side], jnp.float32)
            wv = jnp.asarray(w[side], jnp.float32)
            if gv.shape != wv.shape:
                return float("inf")
            gap = float(jnp.max(jnp.abs(gv - wv)) / jnp.max(jnp.abs(wv)))
            if not gap <= float("inf"):          # NaN in the answer
                return float("inf")
            worst = max(worst, gap)
    return worst


def rank_leaves_off(got_pairs, r_max: int) -> int:
    """Rank entries of an answer that are not ``r_max`` (a fixed-rank
    strategy publishes every layer at ``r_max``)."""
    return sum(int(jnp.sum(p["rank"] != r_max)) for p in got_pairs)


def judge(got_tree, want_pairs, r_max: int) -> tuple:
    """``(gap, rank leaves off)`` of one answer."""
    got = pairs_of(got_tree)
    return worst_leaf_gap(got, want_pairs), rank_leaves_off(got, r_max)
