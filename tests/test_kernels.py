"""Per-kernel allclose tests: shape/dtype sweeps against the jnp oracles
(interpret=True executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import rbla_leaf
from repro.kernels import (lora_matmul, lora_matmul_ref, packed_agg,
                           packed_agg_ref)

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------- lora_matmul ----
LM_SHAPES = [
    # (m, k, n, r)
    (128, 128, 128, 8),
    (256, 512, 256, 16),
    (64, 384, 512, 64),
    (100, 200, 300, 4),      # unaligned -> padding path
    (512, 256, 128, 128),
    (64, 640, 128, 8),       # K not a multiple of the 512 k-block
]


@pytest.mark.parametrize("m,k,n,r", LM_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lora_matmul_matches_ref(m, k, n, r, dtype):
    rng = np.random.default_rng(m + k + n + r)
    x = jnp.asarray(rng.normal(size=(m, k)), dtype)
    w = jnp.asarray(rng.normal(size=(k, n)) * 0.05, dtype)
    a = jnp.asarray(rng.normal(size=(r, k)) * 0.05, dtype)
    b = jnp.asarray(rng.normal(size=(n, r)) * 0.05, dtype)
    scale = 0.25
    got = lora_matmul(x, w, a, b, scale, interpret=True)
    want = lora_matmul_ref(x, w, a, b, scale)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol * max(1.0, float(jnp.abs(want).max())))


def test_lora_matmul_batched_input():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 32, 256)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(256, 128)) * 0.05, jnp.float32)
    a = jnp.asarray(rng.normal(size=(8, 256)) * 0.05, jnp.float32)
    b = jnp.asarray(rng.normal(size=(128, 8)) * 0.05, jnp.float32)
    got = lora_matmul(x, w, a, b, 1.0, interpret=True)
    want = lora_matmul_ref(x.reshape(-1, 256), w, a, b, 1.0).reshape(
        4, 32, 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_lora_matmul_zero_b_is_base_matmul():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(128, 128)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(128, 128)), jnp.float32)
    a = jnp.asarray(rng.normal(size=(16, 128)), jnp.float32)
    b = jnp.zeros((128, 16), jnp.float32)
    got = lora_matmul(x, w, a, b, 7.0, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x @ w),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------ packed_agg ----
AGG_SHAPES = [
    # (n_clients, r_rows, d)
    (2, 8, 128),
    (5, 64, 256),
    (10, 64, 640),
    (3, 7, 100),             # unaligned
]


#: the strategy methods' normalizations: rbla divides a row by its
#: owners' weight mass (Eq. 7), zeropad by the total mass
NORM_BY = {"rbla": "mask", "zeropad": "weight"}


def _owner_masks(ranks, r):
    """(n, r) f32 rank-row owner masks of a rank vector."""
    return (np.arange(r)[None, :]
            < np.asarray(ranks)[:, None]).astype(np.float32)


@pytest.mark.parametrize("n,r,d", AGG_SHAPES)
@pytest.mark.parametrize("method", ["rbla", "zeropad"])
def test_rbla_agg_matches_ref(n, r, d, method):
    rng = np.random.default_rng(n * 100 + r + d)
    ranks = jnp.asarray(rng.integers(1, r + 1, n), jnp.int32)
    masks = _owner_masks(ranks, r)
    x = rng.normal(size=(n, r, d)).astype(np.float32) * masks[:, :, None]
    w = jnp.asarray(rng.uniform(0.5, 2.0, n), jnp.float32)
    got = packed_agg(jnp.asarray(x), jnp.asarray(masks), w,
                     norm_by=NORM_BY[method], interpret=True)
    want = packed_agg_ref(jnp.asarray(x), jnp.asarray(masks), w,
                          norm_by=NORM_BY[method])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_rbla_agg_trailing_dims():
    """(N, R, out, r2) adapter-B-like layouts flatten correctly."""
    rng = np.random.default_rng(9)
    n, r = 4, 16
    masks = jnp.asarray(_owner_masks([4, 8, 16, 2], r))
    x = jnp.asarray(rng.normal(size=(n, r, 8, 32)), jnp.float32)
    got = packed_agg(x, masks, jnp.ones(n), interpret=True)
    want = packed_agg_ref(x.reshape(n, r, -1), masks,
                          jnp.ones(n)).reshape(r, 8, 32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@settings(max_examples=15, deadline=None)
@given(n=st.integers(2, 6), r=st.integers(2, 32), d=st.integers(1, 257),
       seed=st.integers(0, 999))
def test_prop_rbla_agg_matches_core(n, r, d, seed):
    rng = np.random.default_rng(seed)
    ranks = jnp.asarray(rng.integers(1, r + 1, n), jnp.int32)
    masks = _owner_masks(ranks, r)
    x = rng.normal(size=(n, r, d)).astype(np.float32) * masks[:, :, None]
    w = jnp.asarray(rng.uniform(0.1, 2.0, n), jnp.float32)
    got = packed_agg(jnp.asarray(x), jnp.asarray(masks), w, interpret=True)
    want = rbla_leaf(jnp.asarray(x), jnp.asarray(masks)[:, :, None], w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


# -------------------------------------------------------------- ssd_scan ----
SSD_SHAPES = [
    # (b, l, h, p, n, chunk)
    (1, 32, 2, 8, 16, 8),
    (2, 64, 4, 16, 32, 16),
    (1, 128, 2, 64, 128, 32),
    (2, 48, 3, 8, 8, 16),       # chunk not power-of-two divisor path
]


@pytest.mark.parametrize("b,l,h,r,n,chunk", SSD_SHAPES)
def test_ssd_scan_matches_ref(b, l, h, r, n, chunk):
    from repro.kernels import ssd_scan, ssd_scan_ref
    rng = np.random.default_rng(b * l + h + n)
    xdt = jnp.asarray(rng.normal(size=(b, l, h, r)), jnp.float32) * 0.5
    dta = -jnp.abs(jnp.asarray(rng.normal(size=(b, l, h)),
                               jnp.float32)) * 0.5
    bm = jnp.asarray(rng.normal(size=(b, l, n)), jnp.float32) * 0.5
    cm = jnp.asarray(rng.normal(size=(b, l, n)), jnp.float32) * 0.5
    y, hlast = ssd_scan(xdt, dta, bm, cm, chunk=chunk, interpret=True)
    y_ref, h_ref = ssd_scan_ref(xdt, dta, bm, cm, chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(hlast), np.asarray(h_ref),
                               rtol=2e-3, atol=2e-3)
