"""Strategy registry tests: every registered method's tree (reference),
distributed (shard_map psum), and Pallas (kernel) paths must agree
numerically on heterogeneous-rank fixtures, and unknown names must fail
with an actionable error."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.strategy import (AggregationStrategy, ClientUpdate,
                                 ServerState, get_strategy, list_strategies,
                                 register_strategy, resolve_backend,
                                 stack_trees)
from repro.lora import init_adapters, set_ranks

from _cohorts import R_MAX, SPECS, assert_trees_close, hetero_cohort

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------- registry --
def test_all_six_methods_registered():
    assert {"rbla", "zeropad", "fedavg", "rbla_ranked", "rbla_norm",
            "svd"} <= set(list_strategies())


def test_fft_alias_resolves_to_fedavg():
    assert get_strategy("fft") is get_strategy("fedavg")


def test_unknown_strategy_error_names_options():
    with pytest.raises(ValueError, match="unknown aggregation strategy"):
        get_strategy("definitely_not_a_method")
    with pytest.raises(ValueError, match="rbla"):
        get_strategy("definitely_not_a_method")


def test_register_custom_strategy_in_a_few_lines():
    @register_strategy
    class _Median(AggregationStrategy):
        name = "test_median"
        supports_distributed = False

        def leaf(self, stacked, mask, weights, prev=None):
            return jnp.median(stacked, axis=0)

    try:
        adapters, ranks, w = hetero_cohort(3)
        out = get_strategy("test_median").aggregate_adapters(
            adapters, w, r_max=R_MAX, backend="ref")
        assert out["fc1"]["A"].shape == (R_MAX, 16)
        assert int(out["fc1"]["rank"]) == R_MAX
    finally:
        from repro.core import strategy as _s
        _s._REGISTRY.pop("test_median", None)


def test_register_duplicate_name_raises():
    with pytest.raises(ValueError, match="already registered"):
        @register_strategy
        class _Clash(AggregationStrategy):
            name = "rbla"                  # collides with the paper method

    with pytest.raises(ValueError, match="already registered"):
        @register_strategy
        class _AliasClash(AggregationStrategy):
            name = "totally_new"
            aliases = ("fedavg",)          # alias collides with a name
    # the failed alias registration must not leave the primary name behind
    with pytest.raises(ValueError, match="unknown aggregation strategy"):
        get_strategy("totally_new")


def test_with_options_returns_configured_copy():
    s = get_strategy("flora")
    s2 = s.with_options(stack_r_cap=32, prev_weight=0.5)
    assert s2 is not s and s2.stack_r_cap == 32 and s2.prev_weight == 0.5
    assert s.stack_r_cap is None            # the singleton is untouched
    with pytest.raises(ValueError, match="no option"):
        s.with_options(not_a_knob=1)
    with pytest.raises(ValueError, match="no option"):
        get_strategy("rbla").with_options(stack_r_cap=8)


def test_flora_rank_cap_validation():
    adapters, ranks, w = hetero_cohort(3, seed=10, r_lo=4, r_hi=R_MAX)
    low = get_strategy("flora").with_options(stack_r_cap=2)
    with pytest.raises(ValueError, match="stack_r_cap"):
        low.aggregate_adapters(adapters, w, r_max=R_MAX,
                               client_ranks=ranks, backend="ref")
    with pytest.raises(ValueError, match="stack_r_cap"):
        low.server_storage_rank(R_MAX)


def test_flora_leafwise_distributed_hook_refuses():
    """The base make_distributed_aggregator is a masked psum -- on flora
    it would silently average stacked factors instead of concatenating,
    so the hook must refuse and point at the ragged-concat path."""
    with pytest.raises(NotImplementedError, match="ragged"):
        get_strategy("flora").make_distributed_aggregator(None)


def test_set_ranks_rejects_live_rank_beyond_storage():
    from repro.lora import init_adapters, set_ranks
    ad = init_adapters(jax.random.PRNGKey(0), SPECS, R_MAX, R_MAX)
    with pytest.raises(ValueError, match="storage"):
        set_ranks(ad, R_MAX, r_storage=2)


def test_pallas_backend_on_cpu_falls_back_to_interpret():
    """backend='pallas' must work on CPU (auto_interpret runs the kernel
    in interpreter mode) and agree with the reference path."""
    assert jax.default_backend() == "cpu"
    adapters, ranks, w = hetero_cohort(4, seed=11)
    for method in ("rbla", "flora"):
        s = get_strategy(method)
        if s.rank_contract == "stacked":
            s = s.with_options(stack_r_cap=int(ranks.sum()) + R_MAX)
        ref = s.aggregate_adapters(adapters, w, r_max=R_MAX,
                                   client_ranks=ranks, backend="ref")
        pal = s.aggregate_adapters(adapters, w, r_max=R_MAX,
                                   client_ranks=ranks, backend="pallas")
        assert_trees_close(ref, pal)


def test_resolve_backend_auto_is_ref_on_cpu():
    s = get_strategy("rbla")
    assert resolve_backend("auto", s) == "ref"
    assert resolve_backend("pallas", s) == "pallas"
    with pytest.raises(ValueError, match="unknown backend"):
        resolve_backend("cuda", s)


def test_unsupported_paths_raise_actionable_errors():
    from repro.core.strategy import AggregationStrategy

    from repro.core.plan import build_cohort_spec

    class NoKernel(AggregationStrategy):        # default: no Pallas path
        name = "no_kernel"

    adapters, ranks, _ = hetero_cohort(2, seed=3)
    spec = build_cohort_spec(stack_trees(adapters), kind="pallas",
                             r_max=R_MAX, client_ranks=ranks)
    with pytest.raises(NotImplementedError, match="Pallas"):
        NoKernel().plan(None, spec)
    # svd's distributed collective is gathered factors, not the base
    # masked psum: the leafwise aggregator hook refuses with guidance
    with pytest.raises(NotImplementedError, match="distributed"):
        get_strategy("svd").make_distributed_aggregator(None)
    with pytest.raises(NotImplementedError, match="distributed"):
        get_strategy("rbla_norm").aggregate_tree_distributed(
            {}, {}, jnp.ones(2))


# ------------------------------------------------- backend parity (tree) ----
PARITY_METHODS = ["rbla", "zeropad", "fedavg", "rbla_ranked", "flora"]


@pytest.mark.parametrize("method", PARITY_METHODS)
def test_ref_vs_pallas_parity(method):
    adapters, ranks, w = hetero_cohort(5, seed=1)
    s = get_strategy(method)
    ref = s.aggregate_adapters(adapters, w, r_max=R_MAX, client_ranks=ranks,
                               backend="ref")
    pal = s.aggregate_adapters(adapters, w, r_max=R_MAX, client_ranks=ranks,
                               backend="pallas")
    assert_trees_close(ref, pal)


@pytest.mark.parametrize("method", PARITY_METHODS)
def test_ref_vs_distributed_parity(method):
    adapters, ranks, w = hetero_cohort(4, seed=2)
    s = get_strategy(method)
    ref = s.aggregate_adapters(adapters, w, r_max=R_MAX, client_ranks=ranks,
                               backend="ref")
    dist = s.aggregate_adapters(adapters, w, r_max=R_MAX,
                                client_ranks=ranks, backend="distributed")
    assert_trees_close(ref, dist)


@pytest.mark.parametrize("method", ["rbla_norm", "svd"])
def test_pair_structured_methods_run_on_ref(method):
    adapters, ranks, w = hetero_cohort(4, seed=3)
    out = get_strategy(method).aggregate_adapters(
        adapters, w, r_max=R_MAX, client_ranks=ranks, backend="ref")
    for pair in out.values():
        assert pair["A"].shape == (R_MAX, pair["A"].shape[-1])
        assert np.isfinite(np.asarray(pair["A"])).all()
        assert int(pair["rank"]) == R_MAX


# -------------------------------------------- prev_global retention parity --
@pytest.mark.parametrize("backend", ["ref", "pallas", "distributed"])
def test_rbla_prev_retention_across_backends(backend):
    """A cohort of all-low-rank clients must not wipe the high-rank rows
    the server already holds -- on every backend."""
    adapters, ranks, w = hetero_cohort(4, seed=4, r_lo=2, r_hi=3)
    prev = init_adapters(jax.random.PRNGKey(99), SPECS, R_MAX, R_MAX)
    prev = jax.tree.map(
        lambda x: x + 1.0 if x.dtype == jnp.float32 else x, prev)
    out = get_strategy("rbla").aggregate_adapters(
        adapters, w, r_max=R_MAX, client_ranks=ranks, prev_global=prev,
        backend=backend)
    top = slice(int(ranks.max()), R_MAX)      # rows no participant owns
    for name in SPECS:
        np.testing.assert_allclose(
            np.asarray(out[name]["A"][top]),
            np.asarray(prev[name]["A"][top]), rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(out[name]["B"][:, top]),
            np.asarray(prev[name]["B"][:, top]), rtol=1e-6)


def test_flora_prev_as_contributor_parity_across_backends():
    """flora retains the previous global by stacking it as one more
    contributor; all three backends must place it identically (prev
    first, then the cohort in order)."""
    adapters, ranks, w = hetero_cohort(3, seed=6, r_lo=1, r_hi=3)
    s = get_strategy("flora").with_options(stack_r_cap=64)
    prev = s.aggregate_adapters(adapters, w, r_max=R_MAX,
                                client_ranks=ranks, backend="ref")
    outs = {b: s.aggregate_adapters(adapters, w, r_max=R_MAX,
                                    client_ranks=ranks, prev_global=prev,
                                    backend=b)
            for b in ("ref", "pallas", "distributed")}
    r_prev = int(prev["fc1"]["rank"])
    want_rank = r_prev + int(ranks.sum())
    for b, out in outs.items():
        assert int(out["fc1"]["rank"]) == want_rank, b
        assert_trees_close(outs["ref"], out)
    # prev-first: the leading A rows of the new global are the old one's
    np.testing.assert_allclose(
        np.asarray(outs["ref"]["fc1"]["A"][:r_prev]),
        np.asarray(prev["fc1"]["A"][:r_prev]), rtol=1e-6)


def test_zeropad_does_not_retain_prev():
    adapters, ranks, w = hetero_cohort(4, seed=5, r_lo=2, r_hi=3)
    prev = init_adapters(jax.random.PRNGKey(7), SPECS, R_MAX, R_MAX)
    out = get_strategy("zeropad").aggregate_adapters(
        adapters, w, r_max=R_MAX, client_ranks=ranks, prev_global=prev)
    top = slice(int(ranks.max()), R_MAX)
    for name in SPECS:
        np.testing.assert_allclose(np.asarray(out[name]["A"][top]), 0.0,
                                   atol=1e-6)


# ------------------------------------------------------------ svd strategy --
def test_svd_single_client_preserves_effective_update():
    """One rank-r client: serving the aggregate at r_max must reproduce
    the client's effective delta (1/r_max) * B A == (1/r) * B_c A_c."""
    (ad,), ranks, _ = hetero_cohort(1, seed=6, r_lo=3, r_hi=3)
    out = get_strategy("svd").aggregate_adapters(
        [ad], jnp.ones(1), r_max=R_MAX, client_ranks=ranks)
    r = float(ranks[0])
    for name in SPECS:
        got = (np.asarray(out[name]["B"], np.float32)
               @ np.asarray(out[name]["A"], np.float32)) / R_MAX
        want = (np.asarray(ad[name]["B"], np.float32)
                @ np.asarray(ad[name]["A"], np.float32)) / r
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# -------------------------------------------------------- high-level round --
def test_server_state_round_with_client_updates():
    adapters, ranks, w = hetero_cohort(3, seed=8)
    base = [{"b": jnp.full((4,), float(i))} for i in range(3)]
    state = ServerState(
        adapters=init_adapters(jax.random.PRNGKey(0), SPECS, R_MAX, R_MAX),
        base_trainable={"b": jnp.zeros((4,))}, round=0, r_max=R_MAX)
    updates = [ClientUpdate(adapters=a, base_trainable=b, rank=int(r))
               for a, b, r in zip(adapters, base, ranks)]
    nxt = get_strategy("rbla").aggregate(state, updates, w)
    assert nxt.round == 1
    np.testing.assert_array_equal(np.asarray(nxt.client_ranks),
                                  np.asarray(ranks))
    # base is plain weighted mean of the uploads
    want = float(jnp.sum(w * jnp.asarray([0., 1., 2.])) / jnp.sum(w))
    np.testing.assert_allclose(np.asarray(nxt.base_trainable["b"]), want,
                               rtol=1e-5)
    # adapters keep padded storage shapes and reset live rank to r_max
    assert nxt.adapters["fc1"]["A"].shape == (R_MAX, 16)
    assert int(nxt.adapters["fc1"]["rank"]) == R_MAX


def test_aggregate_defaults_weights_to_n_examples():
    state = ServerState(adapters=None, base_trainable={"w": jnp.zeros(2)})
    updates = [ClientUpdate(adapters=None,
                            base_trainable={"w": jnp.full((2,), float(i))},
                            n_examples=n)
               for i, n in enumerate([1.0, 3.0])]
    nxt = get_strategy("fedavg").aggregate(state, updates)
    np.testing.assert_allclose(np.asarray(nxt.base_trainable["w"]), 0.75,
                               rtol=1e-6)


def test_aggregate_without_adapters_is_fedavg_only():
    state = ServerState(adapters=None, base_trainable={"w": jnp.zeros(3)},
                        round=4)
    updates = [ClientUpdate(adapters=None,
                            base_trainable={"w": jnp.ones(3) * i})
               for i in range(2)]
    nxt = get_strategy("fft").aggregate(state, updates, jnp.ones(2))
    assert nxt.adapters is None and nxt.round == 5
    np.testing.assert_allclose(np.asarray(nxt.base_trainable["w"]), 0.5,
                               rtol=1e-6)


# ------------------------------------------ ranked weights need the ranks --
def test_ranked_via_legacy_shims_never_silently_downgrades():
    """rbla_ranked must never quietly run as plain rbla when the rank
    vector is unavailable: the reference tree path without
    ``client_ranks`` and the per-shard allreduce both refuse."""
    s = get_strategy("rbla_ranked")
    tree = {"t": jnp.ones((2, 4, 3))}
    masks = {"t": jnp.ones(())}
    with pytest.raises(ValueError, match="client_ranks"):
        s.aggregate_tree(tree, masks, jnp.ones(2))
    with pytest.raises(NotImplementedError, match="rank_proportional"):
        s.allreduce_leaf(tree["t"][0], None, jnp.float32(1.0), "clients")
