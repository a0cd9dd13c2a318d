"""Property-based invariants for every registered aggregation strategy.

Runs under ``tests/_hypothesis_stub.py`` (containers without hypothesis)
and under real hypothesis (the CI matrix leg installs it); only the stub's
API subset is used: ``given`` with keyword strategies, ``settings``, and
``strategies.integers / tuples / sampled_from``.

Invariants, over randomized rank multisets:

* homogeneous-rank cohorts reduce to FedAvg, in the space each strategy
  *declares* (``fedavg_equivalence``: "factors" | "product" | None);
* aggregation is invariant to client permutation (product space -- flora
  permutes factor segments but not the served update);
* weights are convex: scaling every weight by the same constant changes
  nothing (scale-by-n invariance);
* output shapes match the strategy's declared rank contract
  (``rank_contract``: fixed ``r_max`` storage+rank vs. stacked);
* every (strategy x backend) pair either matches the reference path
  numerically or raises the documented ``NotImplementedError``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.strategy import get_strategy, list_strategies
from repro.lora import init_adapters, set_ranks

from _cohorts import reference_round

jax.config.update("jax_platform_name", "cpu")

SPECS = {"fc1": (9, 7), "fc2": (6, 9)}
R_MAX = 6
ALL_METHODS = ("fedavg", "flora", "rbla", "rbla_clipped", "rbla_median",
               "rbla_norm", "rbla_ranked", "rbla_trimmed", "svd",
               "zeropad")
ROBUST_METHODS = ("rbla_clipped", "rbla_trimmed", "rbla_median")
#: large enough that a cohort of <= 6 clients plus prev never hits the
#: cap -- properties about *stacking* must not silently test the SVD path
BIG_CAP = 8 * R_MAX


def configured(method):
    s = get_strategy(method)
    if s.rank_contract == "stacked":
        s = s.with_options(stack_r_cap=BIG_CAP)
    return s


def make_cohort(seed, ranks):
    """Clients with the given ranks; both factors randomized (B inits 0)."""
    rng = np.random.default_rng(seed)
    adapters = []
    keys = jax.random.split(jax.random.PRNGKey(seed), len(ranks))
    for i, r in enumerate(ranks):
        ad = init_adapters(keys[i], SPECS, R_MAX, int(r))
        ad = jax.tree.map(
            lambda x: x + jnp.asarray(rng.normal(size=x.shape), x.dtype)
            if x.dtype == jnp.float32 else x, ad)
        adapters.append(set_ranks(ad, int(r)))
    weights = jnp.asarray(rng.uniform(0.5, 2.0, len(ranks)), jnp.float32)
    return adapters, jnp.asarray(ranks, jnp.int32), weights


def random_ranks(rng_seed, n):
    return tuple(int(r) for r in
                 np.random.default_rng(rng_seed).integers(1, R_MAX + 1, n))


def effective_deltas(tree):
    """Served update per pair under the alpha/rank convention (alpha
    dropped): (1/rank) * B @ A.  The space in which rank-changing
    aggregation must be compared."""
    out = {}
    for k, pair in tree.items():
        r = max(int(np.max(np.asarray(pair["rank"]))), 1)
        out[k] = (np.asarray(pair["B"], np.float32)
                  @ np.asarray(pair["A"], np.float32)) / r
    return out


def assert_delta_close(a, b, rtol=1e-3, atol=1e-4):
    for k in a:
        np.testing.assert_allclose(a[k], b[k], rtol=rtol, atol=atol,
                                   err_msg=f"pair {k}")


def mean_effective_delta(adapters, weights):
    w = np.asarray(weights, np.float32)
    what = w / w.sum()
    out = {}
    for k in adapters[0]:
        out[k] = sum(
            what[i] * np.asarray(ad[k]["B"], np.float32)
            @ np.asarray(ad[k]["A"], np.float32) / max(int(ad[k]["rank"]), 1)
            for i, ad in enumerate(adapters))
    return out


# ------------------------------------------------------------ registration --
def test_exactly_ten_strategies_registered():
    assert tuple(list_strategies()) == ALL_METHODS


def test_every_strategy_declares_its_contracts():
    for m in ALL_METHODS:
        s = get_strategy(m)
        assert s.rank_contract in ("fixed", "stacked"), m
        assert s.fedavg_equivalence in ("factors", "product", None), m
        assert s.robustness in ("none", "clipped", "trimmed", "median"), m


def test_robustness_contracts_match_registry():
    for m in ALL_METHODS:
        want = m.removeprefix("rbla_") if m in ROBUST_METHODS else "none"
        assert get_strategy(m).robustness == want, m


# ------------------------------------------- homogeneous cohorts == FedAvg --
@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10 ** 6), n=st.integers(2, 4),
       rank=st.integers(1, R_MAX), method=st.sampled_from(ALL_METHODS))
def test_homogeneous_cohort_reduces_to_fedavg(seed, n, rank, method):
    s = configured(method)
    if s.fedavg_equivalence is None:        # rbla_norm / svd: deliberate
        return
    adapters, ranks, w = make_cohort(seed, (rank,) * n)
    out = s.aggregate_adapters(adapters, w, r_max=R_MAX,
                               client_ranks=ranks, backend="ref")
    if s.fedavg_equivalence == "factors":
        ref = get_strategy("fedavg").aggregate_adapters(
            adapters, w, r_max=R_MAX, client_ranks=ranks, backend="ref")
        for k in SPECS:
            for f in ("A", "B"):
                np.testing.assert_allclose(
                    np.asarray(out[k][f]), np.asarray(ref[k][f]),
                    rtol=1e-4, atol=1e-5, err_msg=f"{method} {k} {f}")
    else:                                   # "product": flora
        assert_delta_close(effective_deltas(out),
                           mean_effective_delta(adapters, w))


# ---------------------------------------------------- permutation in order --
@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10 ** 6), n=st.integers(2, 5),
       method=st.sampled_from(ALL_METHODS))
def test_client_order_permutation_invariance(seed, n, method):
    s = configured(method)
    ranks = random_ranks(seed + 1, n)
    adapters, rvec, w = make_cohort(seed, ranks)
    perm = np.random.default_rng(seed + 2).permutation(n)
    out = s.aggregate_adapters(adapters, w, r_max=R_MAX,
                               client_ranks=rvec, backend="ref")
    out_p = s.aggregate_adapters(
        [adapters[i] for i in perm], w[jnp.asarray(perm)], r_max=R_MAX,
        client_ranks=rvec[jnp.asarray(perm)], backend="ref")
    # product space: flora permutes rank segments, svd's factors are only
    # unique up to the truncation basis -- the served update must agree
    assert_delta_close(effective_deltas(out), effective_deltas(out_p))
    for k in SPECS:
        np.testing.assert_array_equal(np.asarray(out[k]["rank"]),
                                      np.asarray(out_p[k]["rank"]))


# ------------------------------------------------- weights stay convex ------
@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10 ** 6), n=st.integers(2, 4),
       scale=st.sampled_from([0.25, 3.0, 17.0]),
       method=st.sampled_from(ALL_METHODS))
def test_weight_scale_invariance(seed, n, scale, method):
    """Scaling every client weight by the same constant (e.g. reporting
    n_examples in different units) must not change the aggregate: the
    combination is convex."""
    s = configured(method)
    adapters, rvec, w = make_cohort(seed, random_ranks(seed + 3, n))
    out = s.aggregate_adapters(adapters, w, r_max=R_MAX,
                               client_ranks=rvec, backend="ref")
    out_s = s.aggregate_adapters(adapters, w * scale, r_max=R_MAX,
                                 client_ranks=rvec, backend="ref")
    assert_delta_close(effective_deltas(out), effective_deltas(out_s),
                       rtol=1e-3, atol=1e-5)


# ------------------------------------------------------ the rank contract --
@pytest.mark.parametrize("method", ALL_METHODS)
def test_output_matches_declared_rank_contract(method):
    s = configured(method)
    adapters, rvec, w = make_cohort(11, (1, 3, R_MAX, 2))
    out = s.aggregate_adapters(adapters, w, r_max=R_MAX,
                               client_ranks=rvec, backend="ref")
    if s.rank_contract == "fixed":
        for k, (fo, fi) in SPECS.items():
            assert out[k]["A"].shape == (R_MAX, fi)
            assert out[k]["B"].shape == (fo, R_MAX)
            assert int(out[k]["rank"]) == R_MAX
    else:
        r_sum = int(np.asarray(rvec).sum())
        assert r_sum <= BIG_CAP
        for k, (fo, fi) in SPECS.items():
            assert out[k]["A"].shape == (BIG_CAP, fi)   # storage = the cap
            assert out[k]["B"].shape == (fo, BIG_CAP)
            assert int(out[k]["rank"]) == r_sum         # live rank = sum


def test_stacked_contract_counts_prev_as_contributor():
    s = configured("flora")
    adapters, rvec, w = make_cohort(12, (2, 3))
    first = s.aggregate_adapters(adapters, w, r_max=R_MAX,
                                 client_ranks=rvec, backend="ref")
    assert int(first["fc1"]["rank"]) == 5
    second = s.aggregate_adapters(adapters, w, r_max=R_MAX,
                                  client_ranks=rvec, prev_global=first,
                                  backend="ref")
    assert int(second["fc1"]["rank"]) == 5 + 5


def test_stacked_contract_caps_to_r_max_via_svd():
    s = get_strategy("flora").with_options(stack_r_cap=R_MAX)
    adapters, rvec, w = make_cohort(13, (4, 5, 6))     # sum 15 > cap 6
    out = s.aggregate_adapters(adapters, w, r_max=R_MAX,
                               client_ranks=rvec, backend="ref")
    for k, (fo, fi) in SPECS.items():
        assert out[k]["A"].shape == (R_MAX, fi)
        assert int(out[k]["rank"]) == R_MAX
    # the re-projection is the best rank-R_MAX factorization of the
    # convex product-space combination
    want = mean_effective_delta(adapters, w)
    for k in SPECS:
        u, sv, vt = np.linalg.svd(want[k], full_matrices=False)
        trunc = (u[:, :R_MAX] * sv[:R_MAX]) @ vt[:R_MAX]
        np.testing.assert_allclose(effective_deltas(out)[k], trunc,
                                   rtol=1e-3, atol=1e-4)


# ------------------------------------------- flora stacking is noise-free --
@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10 ** 6), n=st.integers(1, 5))
def test_flora_stacking_is_product_exact(seed, n):
    """The central FLoRA claim: below the cap, stacking introduces *no*
    aggregation noise -- the served update is exactly the convex
    combination of client updates, for arbitrary heterogeneous ranks."""
    s = configured("flora")
    adapters, rvec, w = make_cohort(seed, random_ranks(seed + 7, n))
    out = s.aggregate_adapters(adapters, w, r_max=R_MAX,
                               client_ranks=rvec, backend="ref")
    assert_delta_close(effective_deltas(out),
                       mean_effective_delta(adapters, w),
                       rtol=1e-4, atol=1e-5)


# ------------------------------------- svd parity under the packed lowering --
@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10 ** 6), n=st.integers(1, 5))
def test_svd_parity_holds_under_packed_lowering(seed, n):
    """The factored-engine lowering (batched per-bucket SVD, no dense
    delta) must reproduce the per-leaf oracle exactly, and agree with
    the explicit dense fallback in product space, over random rank
    multisets."""
    s = get_strategy("svd").with_options()
    adapters, rvec, w = make_cohort(seed, random_ranks(seed + 9, n))
    got = s.aggregate_adapters(adapters, w, r_max=R_MAX,
                               client_ranks=rvec, backend="ref")
    want = reference_round(s, adapters, w, r_max=R_MAX, client_ranks=rvec)
    for k in SPECS:
        for f in ("A", "B", "rank"):
            np.testing.assert_allclose(
                np.asarray(got[k][f], np.float32),
                np.asarray(want[k][f], np.float32),
                rtol=1e-4, atol=1e-5, err_msg=f"plan vs oracle {k} {f}")
    # the dense fallback is the binding oracle in product space (factors
    # are only unique up to the truncation basis)
    dense = get_strategy("svd").with_options(
        svd_method="dense").aggregate_adapters(
        adapters, w, r_max=R_MAX, client_ranks=rvec, backend="ref")
    assert_delta_close(effective_deltas(got), effective_deltas(dense),
                       rtol=1e-3, atol=1e-4)


# ----------------------------------- every backend: parity or loud refusal --
@pytest.mark.parametrize("method", ALL_METHODS)
@pytest.mark.parametrize("backend", ["pallas", "distributed"])
def test_backend_parity_or_documented_refusal(method, backend):
    s = configured(method)
    adapters, rvec, w = make_cohort(21, (2, 4, R_MAX))
    ref = s.aggregate_adapters(adapters, w, r_max=R_MAX,
                               client_ranks=rvec, backend="ref")
    supported = (s.supports_pallas if backend == "pallas"
                 else s.supports_distributed)
    if not supported:
        with pytest.raises(NotImplementedError, match=method):
            s.aggregate_adapters(adapters, w, r_max=R_MAX,
                                 client_ranks=rvec, backend=backend)
        return
    got = s.aggregate_adapters(adapters, w, r_max=R_MAX,
                               client_ranks=rvec, backend=backend)
    for k in SPECS:
        for f in ("A", "B", "rank"):
            np.testing.assert_allclose(
                np.asarray(ref[k][f], np.float32),
                np.asarray(got[k][f], np.float32),
                rtol=1e-4, atol=1e-5, err_msg=f"{method}/{backend} {k} {f}")


# --------------------------------------------- the robustness contract ------
def scale_client(adapters, i, factor):
    """Return the cohort with client ``i``'s float factors scaled."""
    out = list(adapters)
    out[i] = jax.tree.map(
        lambda x: x * factor if x.dtype == jnp.float32 else x, out[i])
    return out


def max_factor_dist(a, b):
    return max(float(np.max(np.abs(np.asarray(a[k][f], np.float32)
                                   - np.asarray(b[k][f], np.float32))))
               for k in SPECS for f in ("A", "B"))


@pytest.mark.parametrize("method", ROBUST_METHODS)
def test_breakdown_single_adversary_moves_global_boundedly(method):
    """One malicious client uploading 1e6x-norm factors moves the robust
    aggregate by a bounded amount; the mean family follows the adversary
    to ~1e5.  Homogeneous full-rank cohort: every row has 5 owners, so
    trimming (k >= 1) and the median (majority honest) both exclude the
    outlier, and clipping caps its mass contribution."""
    s = configured(method)
    adapters, rvec, w = make_cohort(41, (R_MAX,) * 5)
    honest = s.aggregate_adapters(adapters, w, r_max=R_MAX,
                                  client_ranks=rvec, backend="ref")
    attacked_cohort = scale_client(adapters, 0, 1e6)
    attacked = s.aggregate_adapters(attacked_cohort, w, r_max=R_MAX,
                                    client_ranks=rvec, backend="ref")
    move = max_factor_dist(honest, attacked)
    assert move < 50.0, f"{method} moved {move} under one adversary"
    mean = get_strategy("rbla")
    mean_move = max_factor_dist(
        mean.aggregate_adapters(adapters, w, r_max=R_MAX,
                                client_ranks=rvec, backend="ref"),
        mean.aggregate_adapters(attacked_cohort, w, r_max=R_MAX,
                                client_ranks=rvec, backend="ref"))
    assert mean_move > 1e4, "the mean family should follow the adversary"


def test_breakdown_bound_holds_on_every_supported_backend():
    s = configured("rbla_median")
    adapters, rvec, w = make_cohort(43, (R_MAX,) * 5)
    attacked_cohort = scale_client(adapters, 1, 1e6)
    for backend in ("ref", "pallas"):
        honest = s.aggregate_adapters(adapters, w, r_max=R_MAX,
                                      client_ranks=rvec, backend=backend)
        attacked = s.aggregate_adapters(attacked_cohort, w, r_max=R_MAX,
                                        client_ranks=rvec, backend=backend)
        assert max_factor_dist(honest, attacked) < 50.0, backend


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10 ** 6), n=st.integers(2, 5))
def test_clipped_with_loose_clip_matches_rbla(seed, n):
    """Honest-case parity: while every rank-row norm is under the clip,
    rbla_clipped IS rbla -- heterogeneous ranks, prev retention and all."""
    ranks = random_ranks(seed + 13, n)
    adapters, rvec, w = make_cohort(seed, ranks)
    prev, _, _ = make_cohort(seed + 1, (R_MAX,))
    prev = get_strategy("rbla").aggregate_adapters(
        prev, jnp.ones((1,), jnp.float32), r_max=R_MAX,
        client_ranks=jnp.asarray([R_MAX], jnp.int32), backend="ref")
    s = get_strategy("rbla_clipped").with_options(clip_norm=1e9)
    got = s.aggregate_adapters(adapters, w, r_max=R_MAX, client_ranks=rvec,
                               prev_global=prev, backend="ref")
    want = get_strategy("rbla").aggregate_adapters(
        adapters, w, r_max=R_MAX, client_ranks=rvec, prev_global=prev,
        backend="ref")
    for k in SPECS:
        for f in ("A", "B"):
            np.testing.assert_allclose(
                np.asarray(got[k][f]), np.asarray(want[k][f]),
                rtol=1e-5, atol=1e-6, err_msg=f"{k} {f}")


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10 ** 6), n=st.integers(2, 5))
def test_trimmed_without_trimming_matches_unweighted_rbla(seed, n):
    """Honest-case parity: trim_frac=0 + uniform weights reduce the
    trimmed mean to the plain per-row owner mean (= rbla with uniform
    weights)."""
    adapters, rvec, w = make_cohort(seed, random_ranks(seed + 17, n))
    ones = jnp.ones_like(w)
    got = get_strategy("rbla_trimmed").with_options(
        trim_frac=0.0).aggregate_adapters(
        adapters, ones, r_max=R_MAX, client_ranks=rvec, backend="ref")
    want = get_strategy("rbla").aggregate_adapters(
        adapters, ones, r_max=R_MAX, client_ranks=rvec, backend="ref")
    for k in SPECS:
        for f in ("A", "B"):
            np.testing.assert_allclose(
                np.asarray(got[k][f]), np.asarray(want[k][f]),
                rtol=1e-5, atol=1e-6, err_msg=f"{k} {f}")


@pytest.mark.parametrize("method", ROBUST_METHODS)
def test_identical_uploads_match_mean_family(method):
    """Honest-case parity: when every client uploads the same adapters,
    any robust reduction returns that common value, exactly like rbla."""
    s = configured(method)
    one, _, _ = make_cohort(47, (3,))
    adapters = [one[0]] * 4
    rvec = jnp.asarray([3] * 4, jnp.int32)
    w = jnp.asarray([0.5, 1.0, 2.0, 1.5], jnp.float32)
    got = s.aggregate_adapters(adapters, w, r_max=R_MAX, client_ranks=rvec,
                               backend="ref")
    want = get_strategy("rbla").aggregate_adapters(
        adapters, w, r_max=R_MAX, client_ranks=rvec, backend="ref")
    for k in SPECS:
        for f in ("A", "B"):
            np.testing.assert_allclose(
                np.asarray(got[k][f]), np.asarray(want[k][f]),
                rtol=1e-5, atol=1e-6, err_msg=f"{method} {k} {f}")


@pytest.mark.parametrize("method", ROBUST_METHODS)
def test_partial_round_dropout_is_deterministic_and_retains_prev(method):
    """A dropout round (only some of the cohort reports) is well-defined:
    aggregating the survivors twice is bitwise identical, and rank rows
    no survivor owns retain the previous global."""
    s = configured(method)
    adapters, rvec, w = make_cohort(53, (2, 4, R_MAX, 3))
    prev = s.aggregate_adapters(adapters, w, r_max=R_MAX,
                                client_ranks=rvec, backend="ref")
    keep = jnp.asarray([0, 3])                 # survivors: ranks 2 and 3
    survivors = [adapters[0], adapters[3]]
    out1 = s.aggregate_adapters(survivors, w[keep], r_max=R_MAX,
                                client_ranks=rvec[keep], prev_global=prev,
                                backend="ref")
    out2 = s.aggregate_adapters(survivors, w[keep], r_max=R_MAX,
                                client_ranks=rvec[keep], prev_global=prev,
                                backend="ref")
    for k in SPECS:
        for f in ("A", "B"):
            np.testing.assert_array_equal(np.asarray(out1[k][f]),
                                          np.asarray(out2[k][f]),
                                          err_msg=f"{method} {k} {f}")
        # the survivors have ranks 2 and 3, so rows >= 3 have no owner
        np.testing.assert_array_equal(
            np.asarray(out1[k]["A"])[3:], np.asarray(prev[k]["A"])[3:],
            err_msg=f"{method} {k} prev retention")


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10 ** 6), n=st.integers(1, 6),
       d=st.sampled_from([3, 17, 130]),
       mode=st.sampled_from(["clipped", "trimmed", "median"]))
def test_packed_robust_kernel_matches_ref(seed, n, d, mode):
    from repro.kernels import (packed_robust, packed_robust_ref,
                               packed_robust_xla)
    rng = np.random.default_rng(seed)
    r = R_MAX
    x = jnp.asarray(rng.normal(size=(n, r, d)), jnp.float32)
    masks = jnp.asarray(rng.random((n, r)) < 0.7, jnp.float32)
    w = jnp.asarray(rng.uniform(0.5, 2.0, n), jnp.float32)
    prev = jnp.asarray(rng.normal(size=(r, d)), jnp.float32)
    want = packed_robust_ref(x, masks, w, prev, mode=mode, clip_norm=2.5,
                             trim_frac=0.25)
    got = packed_robust(x, masks, w, prev, mode=mode, clip_norm=2.5,
                        trim_frac=0.25, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    # the fused-XLA network lowering (interpret-mode plan path) obeys
    # the same oracle
    got_xla = packed_robust_xla(x, masks, w, prev, mode=mode,
                                clip_norm=2.5, trim_frac=0.25)
    np.testing.assert_allclose(np.asarray(got_xla), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------- packed_stack kernel oracle --
@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10 ** 6), n=st.integers(1, 5),
       d=st.sampled_from([3, 17, 130]))
def test_packed_stack_kernel_matches_ref(seed, n, d):
    """flora's stacking as packed copies: contributor i's leading
    ``segs[i]`` rows land, scaled, at the running offset."""
    from repro.kernels import packed_stack, packed_stack_ref
    rng = np.random.default_rng(seed)
    r_st = R_MAX
    segs = tuple(int(v) for v in rng.integers(1, r_st + 1, n))
    out_rows = sum(segs) + int(rng.integers(0, 4))
    x = jnp.asarray(rng.normal(size=(n, r_st, d)), jnp.float32)
    scales = jnp.asarray(rng.uniform(0.1, 2.0, n), jnp.float32)
    offs = np.concatenate([[0], np.cumsum(segs)[:-1]])
    copies = tuple((i, 0, int(offs[i]), segs[i], i) for i in range(n))
    got = packed_stack(x, scales, copies_x=copies, out_rows=out_rows,
                       interpret=True)
    want = packed_stack_ref(x, scales, copies_x=copies, out_rows=out_rows)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
