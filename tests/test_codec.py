"""Property suite for the upload codecs (``repro.core.codec``).

Runs under ``tests/_hypothesis_stub.py`` (containers without hypothesis)
and under real hypothesis (the CI matrix leg installs it); only the
stub's API subset is used: ``given`` with keyword strategies,
``settings``, and ``strategies.integers / tuples / sampled_from``.

Properties:

* **int8 round-trip bound**: per coordinate,
  ``|x - decode(encode(x))| <= scale/2`` with ``scale = max|row|/127``
  on the packed-row convention (A rows, B columns), and the encoder's
  published scales equal that bound's scales exactly;
* **bf16 exactness**: values already representable in bf16 survive the
  bf16 codec bit-for-bit, and the int8 codec is exact on rows whose
  values are integer multiples of their scale;
* **codec composition**: for every registered strategy (every
  ``plan_mode``: mean, mean_norm, robust combine, svd, stack), the
  aggregate of an encoded cohort equals the aggregate of the *decoded*
  cohort (the fused-dequant plan vs the eager-decode oracle), and the
  ``none`` codec is bit-exact against the raw fp32 cohort;
* **robust breakdown point**: the trimmed / median / clipped strategies
  still bound an adversarial client's pull when every upload (attacker
  included) ships int8 -- quantization must not widen the breakdown
  bounds the robust suite already guarantees.

Stochastic rounding (the server-side half of quantized transport) is
covered here too: determinism under a fixed key, fixed points on
bf16-representable inputs, and an unbiasedness CLT bound.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from _cohorts import (R_MAX, assert_trees_close, hetero_cohort,
                      mixed_codec_cohort)
from repro.core import codec
from repro.core.strategy import get_strategy, list_strategies

jax.config.update("jax_platform_name", "cpu")

ALL_METHODS = tuple(sorted(list_strategies()))
ROBUST_METHODS = ("rbla_clipped", "rbla_trimmed", "rbla_median")
#: quantized-vs-fp32 agreement is bounded by the codec's per-row error;
#: encoded-vs-decoded agreement is a numerics identity and uses the
#: suite-wide tight tolerance instead
INT8_COHORT_ATOL = 0.05


def configured(method):
    s = get_strategy(method)
    if s.rank_contract == "stacked":
        s = s.with_options(stack_r_cap=8 * R_MAX)
    return s


# ------------------------------------------------------------ round-trip --
@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), r=st.integers(1, R_MAX))
def test_int8_round_trip_bounded_by_row_scale(seed, r):
    rng = np.random.default_rng(seed)
    pair = {"A": jnp.asarray(rng.normal(size=(r, 7)) * 3.0, jnp.float32),
            "B": jnp.asarray(rng.normal(size=(9, r)) * 0.1, jnp.float32),
            "rank": jnp.asarray(r, jnp.int32)}
    enc = codec.encode_pair(pair, "int8")
    dec = codec.decode_pair(enc)
    # published scales match the symmetric per-row definition exactly
    np.testing.assert_allclose(
        np.asarray(enc["A_scale"]),
        np.maximum(np.abs(np.asarray(pair["A"])).max(axis=-1), 0) / 127.0
        + (np.abs(np.asarray(pair["A"])).max(axis=-1) == 0) * 1.0)
    # |x - dec| <= scale/2 per coordinate, rows resp. columns
    err_a = np.abs(np.asarray(pair["A"]) - np.asarray(dec["A"]))
    assert np.all(err_a <= 0.5 * np.asarray(enc["A_scale"])[:, None] + 1e-7)
    err_b = np.abs(np.asarray(pair["B"]) - np.asarray(dec["B"]))
    assert np.all(err_b <= 0.5 * np.asarray(enc["B_scale"])[None, :] + 1e-7)
    assert enc["A"].dtype == jnp.int8 and enc["B"].dtype == jnp.int8
    # rank metadata is never quantized
    assert int(dec["rank"]) == r


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_codec_exactness_on_representable_values(seed):
    rng = np.random.default_rng(seed)
    # bf16-representable: f32 rounded through bf16 once is a fixed point
    x = jnp.asarray(rng.normal(size=(4, 6)), jnp.bfloat16).astype(
        jnp.float32)
    pair = {"A": x, "B": x.T, "rank": jnp.asarray(4, jnp.int32)}
    dec = codec.decode_pair(codec.encode_pair(pair, "bf16"))
    assert np.array_equal(np.asarray(dec["A"]), np.asarray(x))
    # int8-exact rows: integer multiples of scale = amax/127
    q = rng.integers(-127, 128, size=(4, 6)).astype(np.float32)
    q[:, 0] = 127.0     # pin every row's amax so every scale = 1/8
    xa = jnp.asarray(q / 8.0, jnp.float32)
    pair = {"A": xa, "B": xa.T, "rank": jnp.asarray(4, jnp.int32)}
    dec = codec.decode_pair(codec.encode_pair(pair, "int8"))
    np.testing.assert_allclose(np.asarray(dec["A"]), np.asarray(xa),
                               rtol=0, atol=1e-6)


def test_decode_idempotent_and_none_passthrough():
    adapters, _, _ = hetero_cohort(n=1, seed=5)
    assert codec.encode_adapters(adapters[0], "none") is adapters[0]
    once = codec.decode_adapters(adapters[0])
    assert_trees_close(once, codec.decode_adapters(once), rtol=0, atol=0)
    assert codec.tree_codec(adapters[0]) == "none"
    assert codec.cohort_codecs(adapters) is None


# ----------------------------------------------------------- composition --
@settings(max_examples=10, deadline=None)
@given(method=st.sampled_from(ALL_METHODS),
       seed=st.integers(0, 1_000),
       wire=st.sampled_from(("int8", "bf16", "uniform_mix")))
def test_codec_composes_with_every_strategy(method, seed, wire):
    """Encoded aggregate == decoded-cohort aggregate for every registered
    ``plan_mode`` (fused-dequant plan where one exists, eager decode
    elsewhere), and within codec tolerance of the raw fp32 aggregate."""
    n = 5
    names = ([wire] * n if wire != "uniform_mix"
             else [("int8", "bf16", "none")[i % 3] for i in range(n)])
    enc, dec, ranks, weights, _ = mixed_codec_cohort(n=n, seed=seed,
                                                     codecs=names)
    _, plain, _, _, _ = mixed_codec_cohort(n=n, seed=seed, codecs=["none"] * n)
    for backend in ("ref", "pallas"):
        s_enc, s_dec, s_raw = (configured(method) for _ in range(3))
        try:
            got = s_enc.aggregate_adapters(enc, weights, r_max=R_MAX,
                                           client_ranks=ranks,
                                           backend=backend)
        except NotImplementedError:
            continue                    # backend unsupported: documented
        oracle = s_dec.aggregate_adapters(dec, weights, r_max=R_MAX,
                                          client_ranks=ranks,
                                          backend=backend)
        assert_trees_close(oracle, got, rtol=1e-4, atol=1e-5,
                           msg=f"{method}/{backend}/{wire} enc-vs-dec")
        raw = s_raw.aggregate_adapters(plain, weights, r_max=R_MAX,
                                       client_ranks=ranks, backend=backend)
        assert_trees_close(raw, got, rtol=0.1, atol=INT8_COHORT_ATOL,
                           msg=f"{method}/{backend}/{wire} quant drift")


@settings(max_examples=8, deadline=None)
@given(method=st.sampled_from(ALL_METHODS), seed=st.integers(0, 1_000))
def test_none_codec_is_bit_exact(method, seed):
    adapters, ranks, weights = hetero_cohort(n=4, seed=seed)
    s_a, s_b = configured(method), configured(method)
    base = s_a.aggregate_adapters(adapters, weights, r_max=R_MAX,
                                  client_ranks=ranks, backend="ref")
    enc = [codec.encode_adapters(a, "none") for a in adapters]
    got = s_b.aggregate_adapters(enc, weights, r_max=R_MAX,
                                 client_ranks=ranks, backend="ref")
    for x, y in zip(jax.tree.leaves(base), jax.tree.leaves(got)):
        assert np.array_equal(np.asarray(x), np.asarray(y)), method


# ------------------------------------------------------- breakdown point --
@pytest.mark.parametrize("method", ROBUST_METHODS)
def test_robust_breakdown_survives_int8_uploads(method):
    """One adversarial client blowing its update up to ~1e6 must stay
    bounded when the whole cohort (attacker included) ships int8 -- the
    plan dequantizes before any clip or order statistic, so quantization
    cannot widen the robust bounds."""
    adapters, _, _ = hetero_cohort(n=5, seed=41, r_lo=R_MAX, r_hi=R_MAX)
    ranks = jnp.full((5,), R_MAX, jnp.int32)
    weights = jnp.ones((5,), jnp.float32)
    evil = [jax.tree.map(
        lambda x: x * 1e6 if x.dtype == jnp.float32 else x, adapters[0])
        ] + list(adapters[1:])
    s = get_strategy(method)
    if method == "rbla_clipped":
        s = s.with_options(clip_norm=5.0)
    if method == "rbla_trimmed":
        s = s.with_options(trim_frac=0.3)
    clean = s.aggregate_adapters(adapters, weights, r_max=R_MAX,
                                 client_ranks=ranks, backend="ref")
    enc = [codec.encode_adapters(a, "int8") for a in evil]
    s2 = get_strategy(method)
    if method == "rbla_clipped":
        s2 = s2.with_options(clip_norm=5.0)
    if method == "rbla_trimmed":
        s2 = s2.with_options(trim_frac=0.3)
    attacked = s2.aggregate_adapters(enc, weights, r_max=R_MAX,
                                     client_ranks=ranks, backend="ref")
    move = max(float(jnp.max(jnp.abs(
        jnp.asarray(x, jnp.float32) - jnp.asarray(y, jnp.float32))))
        for x, y in zip(jax.tree.leaves(clean), jax.tree.leaves(attacked)))
    assert move < 50.0, f"{method}: robust bound broken under int8 ({move})"
    # the unprotected mean, for contrast, is dragged far away
    mean_attacked = get_strategy("rbla").aggregate_adapters(
        enc, weights, r_max=R_MAX, client_ranks=ranks, backend="ref")
    mean_move = max(float(jnp.max(jnp.abs(
        jnp.asarray(x, jnp.float32) - jnp.asarray(y, jnp.float32))))
        for x, y in zip(jax.tree.leaves(clean),
                        jax.tree.leaves(mean_attacked)))
    assert mean_move > 1e4


# ------------------------------------------------------------ validation --
def test_validate_rejects_bad_scales():
    adapters, _, _ = hetero_cohort(n=1, seed=7)
    enc = codec.encode_adapters(adapters[0], "int8")
    codec.validate_encoded_adapters(enc)            # well-formed: clean
    codec.validate_encoded_adapters(adapters[0])    # plain fp32: no-op
    for poison in (jnp.nan, jnp.inf, 0.0, -1.0):
        bad = {k: dict(v) for k, v in enc.items()}
        bad["fc1"]["A_scale"] = bad["fc1"]["A_scale"].at[0].set(poison)
        with pytest.raises(ValueError, match="scale"):
            codec.validate_encoded_adapters(bad)
    big = {k: dict(v) for k, v in enc.items()}
    big["fc2"]["B_scale"] = big["fc2"]["B_scale"].at[0].set(3.0e36)
    with pytest.raises(ValueError, match="overflow"):
        codec.validate_encoded_adapters(big)


def test_validate_reads_every_scale_plane_once():
    """``validate_encoded_adapters`` checks all scale planes in one
    compiled program: ``host_syncs_total{site="validate_scale"}`` grows
    by 1 a call on device scales, faulty or not, and by 0 on a plain
    upload, which has no plane to read."""
    from repro.obs import host_syncs
    reads = host_syncs("validate_scale")
    adapters, _, _ = hetero_cohort(n=1, seed=7)
    enc = codec.encode_adapters(adapters[0], "int8")
    bad = {k: dict(v) for k, v in enc.items()}
    bad["fc2"]["B_scale"] = bad["fc2"]["B_scale"].at[0].set(-1.0)
    before = reads.value
    codec.validate_encoded_adapters(enc)
    codec.validate_encoded_adapters(adapters[0])
    with pytest.raises(ValueError, match="scale in fc2.B_scale"):
        codec.validate_encoded_adapters(bad)
    assert reads.value - before == 2


def test_unknown_codec_rejected():
    adapters, _, _ = hetero_cohort(n=1, seed=7)
    with pytest.raises(ValueError, match="unknown codec"):
        codec.encode_adapters(adapters[0], "fp4")


# ---------------------------------------------------- stochastic rounding --
@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_stochastic_round_deterministic_and_fixed_points(seed):
    key = jax.random.PRNGKey(seed)
    x = jnp.asarray(np.random.default_rng(seed).normal(size=(32, 17)),
                    jnp.float32)
    a = codec.stochastic_round(x, key)
    b = codec.stochastic_round(x, key)
    assert np.array_equal(np.asarray(a, np.float32),
                          np.asarray(b, np.float32))
    assert a.dtype == jnp.bfloat16
    # bf16-representable values never move, whatever the noise
    xr = x.astype(jnp.bfloat16).astype(jnp.float32)
    r = codec.stochastic_round(xr, jax.random.PRNGKey(seed + 1))
    assert np.array_equal(np.asarray(r, np.float32), np.asarray(xr))
    # one ulp is the hard worst case for a single rounding
    ulp = np.abs(np.asarray(xr)) * 2.0 ** -7 + 2.0 ** -126
    assert np.all(np.abs(np.asarray(a, np.float32) - np.asarray(x))
                  <= ulp + np.abs(np.asarray(x)) * 2.0 ** -8)


def test_stochastic_round_unbiased():
    """E[SR(x)] == x: the mean of many independently-keyed roundings
    converges at the CLT rate, far inside one deterministic-rounding
    ulp."""
    x = jnp.full((256,), 1.0 + 2.0 ** -9, jnp.float32)   # mid-interval
    n = 400
    keys = jax.random.split(jax.random.PRNGKey(0), n)
    acc = np.zeros(x.shape, np.float64)
    for k in keys:
        acc += np.asarray(codec.stochastic_round(x, k), np.float32)
    mean_err = abs(acc.mean() / n - float(x[0]))
    # one bf16 ulp at 1.0 is 2^-8; the CLT bound over n*256 samples is
    # ~ulp / sqrt(n*256) ~ 1.2e-5; allow 5 sigma
    assert mean_err < 5 * (2.0 ** -8) / np.sqrt(n * 256), mean_err
    # deterministic rounding of the same value is off by ~2^-9: SR wins
    det_err = abs(float(x.astype(jnp.bfloat16).astype(jnp.float32)[0])
                  - float(x[0]))
    assert mean_err < det_err / 10


def test_stochastic_round_tree_and_edge_cases():
    tree = {"w": jnp.ones((3, 3), jnp.float32) * 1.25,
            "rank": jnp.asarray(3, jnp.int32)}
    out = codec.stochastic_round_tree(tree, jax.random.PRNGKey(2))
    assert out["w"].dtype == jnp.bfloat16
    assert out["rank"].dtype == jnp.int32          # int leaves untouched
    # non-finite passthrough (ingestion rejects them; SR must not mangle)
    x = jnp.asarray([jnp.nan, jnp.inf, -jnp.inf, 0.0], jnp.float32)
    r = np.asarray(codec.stochastic_round(x, jax.random.PRNGKey(3)),
                   np.float32)
    assert np.isnan(r[0]) and np.isposinf(r[1]) and np.isneginf(r[2])
    assert r[3] == 0.0
    with pytest.raises(ValueError, match="bfloat16"):
        codec.stochastic_round(x, jax.random.PRNGKey(4), jnp.float16)
