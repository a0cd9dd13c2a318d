"""Unit + property tests for the RBLA core (the paper's Eq. 6-7, Alg. 1).

Includes the paper's Section-3 toy example (Eq. 2-3): with zero-padding the
last row of the aggregate is diluted by w1/(w1+w2); with RBLA it is
preserved verbatim from the only client that owns it.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (fedavg_leaf, get_strategy, rank_mask, axis_mask,
                        pad_to_rank, rbla_leaf, slice_to_rank,
                        stacked_rank_masks, zeropad_leaf,
                        rank_proportional_weights, rbla_norm_leaf,
                        svd_project_pair)

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------- masks ----
def test_rank_mask_basic():
    np.testing.assert_array_equal(np.asarray(rank_mask(5, 3)),
                                  [1, 1, 1, 0, 0])
    np.testing.assert_array_equal(np.asarray(rank_mask(4, 4)), [1, 1, 1, 1])
    np.testing.assert_array_equal(np.asarray(rank_mask(4, 0)), [0, 0, 0, 0])


def test_axis_mask_rows_and_cols():
    m0 = np.asarray(axis_mask((4, 3), axis=0, rank=2))
    assert m0.sum() == 2 * 3 and m0[:2].all() and not m0[2:].any()
    m1 = np.asarray(axis_mask((4, 3), axis=-1, rank=1))
    assert m1.sum() == 4 and m1[:, 0].all() and not m1[:, 1:].any()


def test_stacked_rank_masks():
    m = np.asarray(stacked_rank_masks(4, jnp.array([1, 4, 0])))
    np.testing.assert_array_equal(m, [[1, 0, 0, 0], [1, 1, 1, 1],
                                      [0, 0, 0, 0]])


def test_pad_slice_roundtrip():
    x = jnp.arange(6.0).reshape(2, 3)
    p = pad_to_rank(x, axis=0, r_max=5)
    assert p.shape == (5, 3) and np.asarray(p[2:]).sum() == 0
    np.testing.assert_array_equal(np.asarray(slice_to_rank(p, 0, 2)),
                                  np.asarray(x))


# ------------------------------------------------- paper's toy example ----
def test_paper_eq3_toy_example():
    """Paper Eq. 2-3: A (2x3) zero-padded to 3x3, aggregated with B (3x3)."""
    A = jnp.array([[1., 2., 3.], [4., 5., 6.]])
    B = jnp.array([[10., 10., 10.], [10., 10., 10.], [8., 8., 8.]])
    w = jnp.array([1.0, 1.0])
    stacked = jnp.stack([pad_to_rank(A, 0, 3), B])
    masks = jnp.stack([axis_mask((3, 3), 0, 2), axis_mask((3, 3), 0, 3)])

    zp = np.asarray(zeropad_leaf(stacked, masks, w))
    # dilution: last row halves (Eq. 3)
    np.testing.assert_allclose(zp[2], [4., 4., 4.])

    rb = np.asarray(rbla_leaf(stacked, masks, w))
    # RBLA: last row preserved from the only contributor (Eq. 7)
    np.testing.assert_allclose(rb[2], [8., 8., 8.])
    # shared rows identical between the two methods
    np.testing.assert_allclose(rb[:2], zp[:2])


def test_rbla_row_absent_everywhere_is_zero():
    stacked = jnp.ones((3, 4, 2))
    masks = stacked_rank_masks(4, jnp.array([2, 2, 1]))[:, :, None]
    out = np.asarray(rbla_leaf(stacked, masks, jnp.ones(3)))
    assert (out[2:] == 0).all() and (out[:2] == 1).all()


# -------------------------------------------------------- equivalences ----
def test_rbla_equals_fedavg_when_homogeneous():
    rng = np.random.default_rng(0)
    stacked = jnp.asarray(rng.normal(size=(5, 8, 6)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.1, 2.0, size=5), jnp.float32)
    full = stacked_rank_masks(8, jnp.full((5,), 8))[:, :, None]
    np.testing.assert_allclose(np.asarray(rbla_leaf(stacked, full, w)),
                               np.asarray(fedavg_leaf(stacked, w)),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(zeropad_leaf(stacked, full, w)),
                               np.asarray(fedavg_leaf(stacked, w)),
                               rtol=1e-5, atol=1e-6)


def test_aggregate_pytree_dispatch():
    tree = {"A": jnp.ones((2, 4, 3)), "bias": jnp.ones((2, 3))}
    masks = {"A": stacked_rank_masks(4, jnp.array([2, 4]))[:, :, None],
             "bias": jnp.ones(())}  # 0-d => fully shared
    w = jnp.ones(2)
    out = get_strategy("rbla").aggregate_tree(tree, masks, w)
    assert out["A"].shape == (4, 3) and out["bias"].shape == (3,)
    np.testing.assert_allclose(np.asarray(out["A"]), 1.0)
    with pytest.raises(ValueError):
        get_strategy("nope")


# ----------------------------------------------------- property tests  ----
leaf_shapes = st.tuples(st.integers(2, 6), st.integers(1, 8),
                        st.integers(1, 5))


@settings(max_examples=30, deadline=None)
@given(shape=leaf_shapes, seed=st.integers(0, 2 ** 16))
def test_prop_rbla_convex_per_row(shape, seed):
    """Each output element lies in the convex hull of contributing clients'
    values (masked weighted mean) -- never diluted toward 0 by absentees."""
    n, r, d = shape
    rng = np.random.default_rng(seed)
    stacked = rng.normal(size=(n, r, d)).astype(np.float32)
    ranks = rng.integers(1, r + 1, size=n)
    w = rng.uniform(0.1, 3.0, size=n).astype(np.float32)
    masks = np.asarray(stacked_rank_masks(r, jnp.asarray(ranks)))[:, :, None]
    out = np.asarray(rbla_leaf(jnp.asarray(stacked * masks),
                               jnp.asarray(masks), jnp.asarray(w)))
    for row in range(r):
        contrib = [stacked[i, row] for i in range(n) if ranks[i] > row]
        if not contrib:
            np.testing.assert_allclose(out[row], 0.0, atol=1e-6)
            continue
        lo = np.min(contrib, axis=0) - 1e-4
        hi = np.max(contrib, axis=0) + 1e-4
        assert (out[row] >= lo).all() and (out[row] <= hi).all()


@settings(max_examples=30, deadline=None)
@given(shape=leaf_shapes, seed=st.integers(0, 2 ** 16))
def test_prop_zeropad_dilutes_rbla_does_not(shape, seed):
    """|ZP row| <= |RBLA row| elementwise on rows not owned by everyone
    (with equal client weights and same-sign contributions)."""
    n, r, d = shape
    rng = np.random.default_rng(seed)
    stacked = np.abs(rng.normal(size=(n, r, d))).astype(np.float32) + 0.1
    ranks = rng.integers(1, r + 1, size=n)
    masks = np.asarray(stacked_rank_masks(r, jnp.asarray(ranks)))[:, :, None]
    w = jnp.ones(n)
    zp = np.asarray(zeropad_leaf(jnp.asarray(stacked * masks),
                                 jnp.asarray(masks), w))
    rb = np.asarray(rbla_leaf(jnp.asarray(stacked * masks),
                              jnp.asarray(masks), w))
    assert (zp <= rb + 1e-5).all()
    # and they agree exactly on rows owned by every client
    for row in range(r):
        if (ranks > row).all():
            np.testing.assert_allclose(zp[row], rb[row], rtol=1e-5, atol=1e-6)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2 ** 16))
def test_prop_rbla_idempotent_on_identical_clients(seed):
    """Aggregating N copies of the same adapter returns it unchanged."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(6, 4)).astype(np.float32)
    stacked = jnp.asarray(np.stack([x] * 4))
    masks = stacked_rank_masks(6, jnp.full((4,), 6))[:, :, None]
    out = np.asarray(rbla_leaf(stacked, masks,
                               jnp.asarray(rng.uniform(0.5, 2, 4),
                                           jnp.float32)))
    np.testing.assert_allclose(out, x, rtol=1e-4, atol=1e-5)


# ------------------------------------------------------------- variants ----
def test_rank_proportional_weights_preserve_mass():
    w = jnp.array([1., 1., 2.])
    r = jnp.array([2, 4, 8])
    out = rank_proportional_weights(w, r)
    np.testing.assert_allclose(float(jnp.sum(out)), 4.0, rtol=1e-5)
    assert float(out[2]) > float(out[1]) > float(out[0])


def test_rbla_norm_restores_magnitude():
    # two orthogonal unit rows average to norm 1/sqrt(2); variant restores ~1
    a = np.zeros((2, 1, 4), np.float32)
    a[0, 0, 0] = 1.0
    a[1, 0, 1] = 1.0
    stacked = jnp.asarray(a)
    masks = jnp.ones((2, 1, 1))
    plain = np.linalg.norm(np.asarray(rbla_leaf(stacked, masks, jnp.ones(2))))
    fixed = np.linalg.norm(np.asarray(
        rbla_norm_leaf(stacked, masks, jnp.ones(2), row_axis=0)))
    assert abs(plain - 1 / np.sqrt(2)) < 1e-5
    assert abs(fixed - 1.0) < 1e-4


def test_svd_project_exact_for_single_client():
    rng = np.random.default_rng(3)
    B = rng.normal(size=(1, 8, 3)).astype(np.float32)
    A = rng.normal(size=(1, 3, 6)).astype(np.float32)
    Bo, Ao = svd_project_pair(jnp.asarray(B), jnp.asarray(A),
                              jnp.array([3]), jnp.ones(1), r_out=3)
    np.testing.assert_allclose(np.asarray(Bo) @ np.asarray(Ao),
                               B[0] @ A[0], rtol=1e-4, atol=1e-4)


def test_rbla_prev_retention_partial_participation():
    """Under partial participation, rank-rows owned by NO participant must
    retain the server's previous value (not be zeroed) -- the regression
    behind the random-20% collapse found in SSRepro claim 3."""
    prev = jnp.full((4, 3), 7.0)
    # two low-rank participants (ranks 1 and 2): rows 2..3 unowned
    stacked = jnp.ones((2, 4, 3))
    masks = stacked_rank_masks(4, jnp.array([1, 2]))[:, :, None]
    out = np.asarray(rbla_leaf(stacked * masks, masks, jnp.ones(2),
                               prev=prev))
    np.testing.assert_allclose(out[0], 1.0)      # owned by both
    np.testing.assert_allclose(out[1], 1.0)      # owned by client 2
    np.testing.assert_allclose(out[2], 7.0)      # unowned -> retained
    np.testing.assert_allclose(out[3], 7.0)
    # without prev: unowned rows are zero (full-participation semantics)
    out0 = np.asarray(rbla_leaf(stacked * masks, masks, jnp.ones(2)))
    np.testing.assert_allclose(out0[2:], 0.0)


def test_aggregate_threads_prev_tree():
    tree = {"A": jnp.ones((2, 4, 3))}
    masks = {"A": stacked_rank_masks(4, jnp.array([1, 1]))[:, :, None]}
    prev = {"A": jnp.full((4, 3), 5.0)}
    out = get_strategy("rbla").aggregate_tree(
        jax.tree.map(lambda x, m: x * m, tree, masks), masks, jnp.ones(2),
        prev_tree=prev)
    np.testing.assert_allclose(np.asarray(out["A"][0]), 1.0)
    np.testing.assert_allclose(np.asarray(out["A"][1:]), 5.0)
