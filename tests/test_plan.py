"""Compiled AggregationPlan tests: packing parity, caching, donation.

The parity/property suites already drive every strategy through
``aggregate_adapters`` (which routes through plans); this file covers the
plan machinery itself: cache hit/miss keying, re-planning on
``with_options``, buffer donation (no-use-after-donate), the fused
layer-stacked path, the packed kernels against their oracles, the packed
per-update fold, dispatch accounting, and the in-jit fallback.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.plan import (CohortSpec, PlanUnavailable, build_cohort_spec,
                             dispatch_counter)
from repro.core.strategy import (ClientUpdate, ServerState, get_strategy,
                                 stack_trees)
from repro.lora import init_adapters, init_pair, mask_pair

from _cohorts import (R_MAX, SPECS, assert_trees_close, hetero_cohort,
                      reference_round)

jax.config.update("jax_platform_name", "cpu")


def fresh(method, **options):
    """A configured copy with its own (empty) plan cache."""
    s = get_strategy(method)
    if s.rank_contract == "stacked" and "stack_r_cap" not in options:
        options["stack_r_cap"] = 64
    return s.with_options(**options) if options else s.with_options()


def layer_stacked_cohort(n=4, L=3, r=8, fo=12, fi=16, seed=0):
    rng = np.random.default_rng(seed)
    ranks = rng.integers(1, r + 1, n)
    cohort = []
    for i in range(n):
        p = init_pair(jax.random.PRNGKey(i), fo, fi, r, int(ranks[i]),
                      leading=(L,))
        p = {"A": p["A"] + jnp.asarray(rng.normal(size=p["A"].shape),
                                       jnp.float32),
             "B": p["B"] + jnp.asarray(rng.normal(size=p["B"].shape),
                                       jnp.float32),
             "rank": p["rank"]}
        cohort.append({"blk": mask_pair(p)})
    w = jnp.asarray(rng.uniform(0.5, 2.0, n), jnp.float32)
    return cohort, jnp.asarray(ranks, jnp.int32), w


# ------------------------------------------------------------ plan caching --
def test_plan_cache_hits_on_same_cohort_spec():
    s = fresh("rbla")
    adapters, ranks, w = hetero_cohort(4, seed=1)
    for _ in range(3):
        s.aggregate_adapters(adapters, w, r_max=R_MAX, client_ranks=ranks,
                             backend="ref")
    assert s.plan_stats["hits"] == 2 and s.plan_stats["misses"] == 1


def test_plan_cache_misses_on_rank_multiset_change():
    s = fresh("rbla")
    a1, r1, w1 = hetero_cohort(4, seed=1, r_lo=1, r_hi=3)
    a2, r2, w2 = hetero_cohort(4, seed=2, r_lo=4, r_hi=R_MAX)
    s.aggregate_adapters(a1, w1, r_max=R_MAX, client_ranks=r1,
                         backend="ref")
    s.aggregate_adapters(a2, w2, r_max=R_MAX, client_ranks=r2,
                         backend="ref")
    # different rank multisets are different specs -> two plans...
    assert s.plan_stats["hits"] == 0 and s.plan_stats["misses"] == 2
    # ...and re-running either cohort hits its cached plan
    s.aggregate_adapters(a1, w1, r_max=R_MAX, client_ranks=r1,
                         backend="ref")
    assert s.plan_stats["hits"] == 1 and s.plan_stats["misses"] == 2


def test_plan_cache_keys_on_backend_and_prev():
    s = fresh("rbla")
    adapters, ranks, w = hetero_cohort(3, seed=3)
    prev = init_adapters(jax.random.PRNGKey(5), SPECS, R_MAX, R_MAX)
    s.aggregate_adapters(adapters, w, r_max=R_MAX, client_ranks=ranks,
                         backend="ref")
    s.aggregate_adapters(adapters, w, r_max=R_MAX, client_ranks=ranks,
                         backend="pallas")
    s.aggregate_adapters(adapters, w, r_max=R_MAX, client_ranks=ranks,
                         prev_global=prev, backend="ref")
    assert s.plan_stats["misses"] == 3 and s.plan_stats["hits"] == 0


def test_with_options_drops_compiled_plans():
    s = fresh("flora")
    adapters, ranks, w = hetero_cohort(3, seed=4)
    s.aggregate_adapters(adapters, w, r_max=R_MAX, client_ranks=ranks,
                         backend="ref")
    assert s.plan_stats["misses"] == 1
    s2 = s.with_options(stack_r_cap=48)
    assert "_plan_cache" not in s2.__dict__
    s2.aggregate_adapters(adapters, w, r_max=R_MAX, client_ranks=ranks,
                          backend="ref")
    assert s2.plan_stats == {"hits": 0, "misses": 1}
    assert s.plan_stats["misses"] == 1       # original cache untouched


def test_plan_api_direct_and_unsupported_backend_raises():
    s = fresh("rbla")
    adapters, ranks, w = hetero_cohort(3, seed=5)
    stacked = stack_trees(adapters)
    spec = build_cohort_spec(stacked, kind="ref", r_max=R_MAX,
                            client_ranks=ranks)
    round_ = s.plan(None, spec)
    out = round_(stacked, w)
    want = reference_round(s, adapters, w, r_max=R_MAX, client_ranks=ranks)
    assert_trees_close(out, want)
    # rbla_norm packs on pallas now; its missing path is distributed
    with pytest.raises(NotImplementedError, match="rbla_norm"):
        bad = build_cohort_spec(stacked, kind="distributed", r_max=R_MAX,
                                client_ranks=ranks)
        get_strategy("rbla_norm").plan(None, bad)


def test_cohort_spec_is_hashable_and_value_keyed():
    adapters, ranks, w = hetero_cohort(3, seed=6)
    stacked = stack_trees(adapters)
    s1 = build_cohort_spec(stacked, kind="ref", r_max=R_MAX,
                           client_ranks=ranks)
    s2 = build_cohort_spec(stack_trees(adapters), kind="ref", r_max=R_MAX,
                           client_ranks=ranks)
    assert isinstance(s1, CohortSpec) and s1 == s2 and hash(s1) == hash(s2)


def test_spec_build_unavailable_under_tracing_and_on_bare_leaves():
    adapters, ranks, w = hetero_cohort(2, seed=7)
    stacked = stack_trees(adapters)
    with pytest.raises(PlanUnavailable):
        build_cohort_spec({"t": jnp.ones((2, 4, 3))}, kind="ref")

    def traced(tree):
        return build_cohort_spec(tree, kind="ref")
    with pytest.raises(PlanUnavailable):
        jax.eval_shape(lambda t: (traced(t), t)[1], stacked)


@pytest.mark.parametrize("backend", ["ref", "pallas"])
@pytest.mark.parametrize("method", ["rbla", "zeropad", "fedavg",
                                    "rbla_norm"])
def test_aggregate_adapters_inside_jit_falls_back_to_reference(method,
                                                               backend):
    """Under jit tracing the cohort cannot be described host-side; the
    round must build no plan, run the reference math inside the trace
    and agree with it, on either backend."""
    adapters, ranks, w = hetero_cohort(3, seed=8)
    s = fresh(method)

    @jax.jit
    def round_(ads, wv):
        return s.aggregate_adapters(ads, wv, r_max=R_MAX,
                                    client_ranks=ranks, backend=backend)
    got = round_(adapters, w)
    assert not s.__dict__.get("_plan_cache")
    want = reference_round(s, adapters, w, r_max=R_MAX, client_ranks=ranks)
    assert_trees_close(got, want)


def test_mean_executor_shared_across_rank_multisets():
    """A new rank multiset is a new (cheap) plan but NOT a new XLA
    compile: mean-mode executors key on shapes only -- owner masks and
    ranks enter as runtime data.  A long-lived service with random
    cohort selection must not recompile every round."""
    s = fresh("rbla")
    for seed, (lo, hi) in enumerate([(1, 3), (4, R_MAX), (2, 5)]):
        a, r, w = hetero_cohort(4, seed=seed, r_lo=lo, r_hi=hi)
        s.aggregate_adapters(a, w, r_max=R_MAX, client_ranks=r,
                             backend="ref")
    assert s.plan_stats["misses"] == 3          # three plans...
    assert len(s.__dict__["_plan_exec_cache"]) == 1   # ...one executor


def test_plan_cache_is_bounded_lru():
    from repro.core import strategy as strategy_mod
    s = fresh("rbla")
    old = strategy_mod.PLAN_CACHE_SIZE
    strategy_mod.PLAN_CACHE_SIZE = 2
    try:
        for seed in range(4):
            a, r, w = hetero_cohort(3, seed=seed)
            s.aggregate_adapters(a, w, r_max=R_MAX, client_ranks=r,
                                 backend="ref")
        assert len(s.__dict__["_plan_cache"]) <= 2
    finally:
        strategy_mod.PLAN_CACHE_SIZE = old


def test_flora_fold_rejects_nonuniform_layer_ranks():
    """fold enforces the same uniform-per-layer contract the one-shot
    path does, with the same actionable error (not a shape crash)."""
    from repro.lora import init_pair, mask_pair
    s = fresh("flora", stack_r_cap=64)
    L, r, fo, fi = 2, 8, 12, 16
    state_pair = init_pair(jax.random.PRNGKey(0), fo, fi, r, 4,
                           leading=(L,))
    upd_pair = dict(init_pair(jax.random.PRNGKey(1), fo, fi, r, 3,
                              leading=(L,)))
    upd_pair["rank"] = jnp.asarray([3, 1], jnp.int32)   # non-uniform
    state = ServerState(adapters={"blk": mask_pair(state_pair)},
                        base_trainable={}, r_max=r)
    upd = ClientUpdate(adapters={"blk": mask_pair(upd_pair)},
                       base_trainable={}, n_examples=1.0)
    with pytest.raises(NotImplementedError, match="uniform"):
        s.fold(state, upd, backend="ref")


# -------------------------------------------------- weight-only plan reuse --
def test_same_cohort_reuses_packed_buffers_weight_only():
    """Satellite gate: when the same cohort re-participates (identical
    upload buffers resubmitted on consecutive rounds), the host-side
    re-stacking and re-packing are skipped -- only the combine re-runs
    with the new weights -- and the saving is visible in plan_stats.
    The payloads are kept only from the second sighting on (one-shot
    cohorts must not pin cohort-sized buffers)."""
    s = fresh("rbla")
    adapters, ranks, w = hetero_cohort(4, seed=30)
    out1 = s.aggregate_adapters(adapters, w, r_max=R_MAX,
                                client_ranks=ranks, backend="ref")
    s.aggregate_adapters(adapters, w, r_max=R_MAX,
                         client_ranks=ranks, backend="ref")
    w2 = w * jnp.asarray(np.linspace(0.5, 2.0, 4), jnp.float32)
    out2 = s.aggregate_adapters(adapters, w2, r_max=R_MAX,
                                client_ranks=ranks, backend="ref")
    assert s.plan_stats["pack_reuses"] >= 1
    assert s.plan_stats["pack_runs"] <= 2
    # the weight-only update is numerically the full round
    want = reference_round(s, adapters, w2, r_max=R_MAX,
                           client_ranks=ranks)
    assert_trees_close(out2, want)
    # different weights must really change the result (no stale cache)
    with pytest.raises(AssertionError):
        assert_trees_close(out1, out2)


def test_mutable_numpy_uploads_are_never_memoized():
    """Regression: identity fingerprints are only sound for immutable
    jax arrays.  A caller that reuses preallocated numpy buffers and
    mutates them in place between rounds must get the fresh aggregate,
    not a stale memoized one."""
    s = fresh("fedavg")
    rng = np.random.default_rng(40)
    uploads = [{k: {"A": rng.normal(size=(R_MAX, fi)).astype(np.float32),
                    "B": rng.normal(size=(fo, R_MAX)).astype(np.float32),
                    "rank": np.int32(R_MAX)}
                for k, (fo, fi) in SPECS.items()} for _ in range(3)]
    w = jnp.ones(3, jnp.float32)
    ranks = jnp.full((3,), R_MAX, jnp.int32)
    out1 = s.aggregate_adapters(uploads, w, r_max=R_MAX,
                                client_ranks=ranks, backend="ref")
    for u in uploads:                       # in-place round-2 deltas
        for k in SPECS:
            u[k]["A"] *= 2.0
            u[k]["B"] *= 2.0
    out2 = s.aggregate_adapters(uploads, w, r_max=R_MAX,
                                client_ranks=ranks, backend="ref")
    for k in SPECS:
        np.testing.assert_allclose(np.asarray(out2[k]["A"]),
                                   2.0 * np.asarray(out1[k]["A"]),
                                   rtol=1e-5, atol=1e-6)


def test_stack_memo_releases_payload_when_uploads_die():
    """The per-client pack memo must not pin cohort-sized buffers for
    the process lifetime: a one-shot cohort leaves only a fingerprint
    behind, a repeat keeps the packed buckets, and once the repeating
    cohort's uploads die the payload is released eagerly -- without
    waiting for the same plan to execute again."""
    import gc
    s = fresh("rbla")
    adapters, ranks, w = hetero_cohort(4, seed=41)
    s.aggregate_adapters(adapters, w, r_max=R_MAX, client_ranks=ranks,
                         backend="ref")
    rd, = s.__dict__["_plan_cache"].values()
    assert rd.spec.codecs == ("none",) * 4      # planned per client
    memo = rd.pack_memo
    assert memo._entry is None         # first sight: fingerprint only
    assert memo._candidate is not None
    s.aggregate_adapters(adapters, w, r_max=R_MAX, client_ranks=ranks,
                         backend="ref")
    assert memo._entry is not None     # repeat: payload kept
    del adapters
    gc.collect()
    # eager release: the upload finalizers fired, nothing pinned, even
    # though no further aggregate call has touched this plan
    assert memo._entry is None


def test_buffer_memo_invariants():
    import gc
    from repro.core.plan import BufferMemo
    m = BufferMemo()
    a, b = jnp.arange(3.0), jnp.arange(4.0)
    m.store([a, b], "payload")
    assert m.lookup([a, b]) == "payload"
    assert m.lookup([b, a]) is None              # order is identity
    m.store([np.arange(3.0)], "nope")            # mutable: refused
    assert m.lookup([a, b]) == "payload"         # ...and left intact
    del b
    gc.collect()
    assert m._entry is None                      # eager release

    # require_repeat: payload kept only for a repeated fingerprint
    m2 = BufferMemo(require_repeat=True)
    c = jnp.arange(5.0)
    m2.store([c], "one")
    assert m2.lookup([c]) is None and m2._entry is None
    m2.store([c], "two")
    assert m2.lookup([c]) == "two"


def test_new_cohort_arrays_repack():
    s = fresh("rbla")
    a1, ranks, w = hetero_cohort(4, seed=31)
    a2, _, _ = hetero_cohort(4, seed=31)     # equal values, NEW buffers
    s.aggregate_adapters(a1, w, r_max=R_MAX, client_ranks=ranks,
                         backend="ref")
    s.aggregate_adapters(a2, w, r_max=R_MAX, client_ranks=ranks,
                         backend="ref")
    assert s.plan_stats["pack_runs"] == 2
    assert s.plan_stats.get("pack_reuses", 0) == 0


# ------------------------------------------------------- svd packed plans --
def test_svd_plan_is_packed_batched_and_matches_oracle():
    """The tentpole gate: svd lowers to a packed plan (one batched
    factored SVD per same-shape bucket), not the old whole-round jit,
    and matches the per-leaf oracle."""
    s = fresh("svd")
    adapters, ranks, w = hetero_cohort(4, seed=32)
    got = s.aggregate_adapters(adapters, w, r_max=R_MAX,
                               client_ranks=ranks, backend="ref")
    want = reference_round(s, adapters, w, r_max=R_MAX, client_ranks=ranks)
    assert_trees_close(got, want)
    rd = next(iter(s.__dict__["_plan_cache"].values()))
    assert rd.kind == "packed"
    # SPECS' two pairs have distinct shapes -> two buckets; same-shape
    # pairs share one batched launch (see the layer-stacked test below)
    assert rd.n_kernel_launches == 2


def test_svd_same_shape_pairs_share_one_batched_bucket():
    cohort, ranks, w = layer_stacked_cohort(seed=33)
    cohort = [{"x": c["blk"], "y": jax.tree.map(lambda v: v, c["blk"])}
              for c in cohort]
    s = fresh("svd")
    got = s.aggregate_adapters(cohort, w, r_max=8, client_ranks=ranks,
                               backend="ref")
    rd = next(iter(s.__dict__["_plan_cache"].values()))
    assert rd.kind == "packed"
    assert rd.n_kernel_launches == 1       # both pairs: same shapes
    want = reference_round(s, cohort, w, r_max=8, client_ranks=ranks)
    assert_trees_close(got, want)


def test_svd_executor_shared_across_rank_multisets():
    """Like the mean mode: a new rank multiset is a new (cheap) plan but
    not a new XLA compile -- scales enter as runtime data."""
    s = fresh("svd")
    for seed, (lo, hi) in enumerate([(1, 3), (4, R_MAX), (2, 5)]):
        a, r, w = hetero_cohort(4, seed=seed, r_lo=lo, r_hi=hi)
        s.aggregate_adapters(a, w, r_max=R_MAX, client_ranks=r,
                             backend="ref")
    assert s.plan_stats["misses"] == 3
    assert len(s.__dict__["_plan_exec_cache"]) == 1


def test_svd_dense_method_knob_matches_factored_in_product_space():
    s_auto = fresh("svd")
    s_dense = fresh("svd", svd_method="dense")
    adapters, ranks, w = hetero_cohort(3, seed=34, r_lo=1, r_hi=2)
    a = s_auto.aggregate_adapters(adapters, w, r_max=R_MAX,
                                  client_ranks=ranks, backend="ref")
    d = s_dense.aggregate_adapters(adapters, w, r_max=R_MAX,
                                   client_ranks=ranks, backend="ref")
    for k in SPECS:
        np.testing.assert_allclose(
            np.asarray(a[k]["B"], np.float32)
            @ np.asarray(a[k]["A"], np.float32),
            np.asarray(d[k]["B"], np.float32)
            @ np.asarray(d[k]["A"], np.float32), rtol=1e-3, atol=1e-4)


# ------------------------------------------------- rbla_norm pallas plans --
def test_rbla_norm_packs_on_pallas_and_matches_ref():
    """Satellite gate: the mean_norm lowering runs the packed kernel on
    the pallas backend (norm restore fused) and agrees with ref."""
    s = fresh("rbla_norm")
    adapters, ranks, w = hetero_cohort(4, seed=35)
    ref = s.aggregate_adapters(adapters, w, r_max=R_MAX,
                               client_ranks=ranks, backend="ref")
    pal = s.aggregate_adapters(adapters, w, r_max=R_MAX,
                               client_ranks=ranks, backend="pallas")
    assert_trees_close(ref, pal)
    rd = next(r for r in s.__dict__["_plan_cache"].values()
              if r.spec.kind == "pallas")
    assert rd.kind == "packed" and rd.n_fallback_pairs == 0
    # and both agree with the float32 reference
    want = reference_round(s, adapters, w, r_max=R_MAX, client_ranks=ranks)
    assert_trees_close(want, pal)


def test_packed_agg_kernel_norm_restore_matches_oracle():
    from repro.kernels import packed_agg, packed_agg_ref
    rng = np.random.default_rng(36)
    n, r, d = 4, 16, 21
    x = jnp.asarray(rng.normal(size=(n, r, d)), jnp.float32)
    masks = jnp.asarray(rng.integers(0, 2, (n, r)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.5, 2.0, n), jnp.float32)
    got = packed_agg(x, masks, w, norm_by="mask", norm_restore=True,
                     interpret=True)
    want = packed_agg_ref(x, masks, w, norm_by="mask", norm_restore=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------- donation --
def test_donated_prev_buffers_are_consumed():
    s = fresh("rbla")
    adapters, ranks, w = hetero_cohort(4, seed=9, r_lo=2, r_hi=3)
    prev = init_adapters(jax.random.PRNGKey(11), SPECS, R_MAX, R_MAX)
    keep = jax.tree.map(lambda x: np.asarray(x), prev)   # host copy
    out = s.aggregate_adapters(adapters, w, r_max=R_MAX,
                               client_ranks=ranks, prev_global=prev,
                               backend="ref", donate=True)
    want = reference_round(s, adapters, w, r_max=R_MAX, client_ranks=ranks,
                           prev=jax.tree.map(jnp.asarray, keep))
    assert_trees_close(out, want)
    # the no-use-after-donate guard: donated A/B buffers are dead, and
    # touching them afterwards raises instead of reading stale memory
    donated = prev["fc1"]["A"]
    if donated.is_deleted():                 # backend supports donation
        with pytest.raises(RuntimeError):
            np.asarray(donated)


def test_non_donating_call_leaves_prev_alive():
    s = fresh("rbla")
    adapters, ranks, w = hetero_cohort(4, seed=10, r_lo=2, r_hi=3)
    prev = init_adapters(jax.random.PRNGKey(12), SPECS, R_MAX, R_MAX)
    s.aggregate_adapters(adapters, w, r_max=R_MAX, client_ranks=ranks,
                         prev_global=prev, backend="ref")
    assert not prev["fc1"]["A"].is_deleted()
    np.asarray(prev["fc1"]["A"])             # still readable


# -------------------------------------------------- layer-stacked packing --
@pytest.mark.parametrize("method", ["rbla", "zeropad", "fedavg"])
def test_layer_stacked_pairs_run_fused_on_pallas(method):
    """The acceptance criterion: layer-stacked (leading-dim) pairs no
    longer fall back to reference leaf math inside the Pallas backend --
    they pack into buckets like everything else."""
    cohort, ranks, w = layer_stacked_cohort()
    s = fresh(method)
    want = reference_round(s, cohort, w, r_max=8, client_ranks=ranks)
    got = s.aggregate_adapters(cohort, w, r_max=8, client_ranks=ranks,
                               backend="pallas")
    assert_trees_close(want, got, msg=method)
    rd = next(r for r in s.__dict__["_plan_cache"].values()
              if r.spec.kind == "pallas")
    assert rd.kind == "packed" and rd.n_fallback_pairs == 0


def test_layer_stacked_flora_packs_into_stack_buckets():
    cohort, ranks, w = layer_stacked_cohort(seed=3)
    s = fresh("flora", stack_r_cap=64)
    want = reference_round(s, cohort, w, r_max=8, client_ranks=ranks)
    got = s.aggregate_adapters(cohort, w, r_max=8, client_ranks=ranks,
                               backend="pallas")
    assert_trees_close(want, got)
    rd = next(r for r in s.__dict__["_plan_cache"].values()
              if r.spec.kind == "pallas")
    assert rd.kind == "packed" and rd.n_fallback_pairs == 0


def test_flora_over_cap_pairs_fall_back_inside_the_plan():
    adapters, ranks, w = hetero_cohort(4, seed=13, r_lo=4, r_hi=R_MAX)
    s = fresh("flora", stack_r_cap=R_MAX)    # sum(ranks) certainly > cap
    want = reference_round(s, adapters, w, r_max=R_MAX, client_ranks=ranks)
    got = s.aggregate_adapters(adapters, w, r_max=R_MAX,
                               client_ranks=ranks, backend="pallas")
    assert_trees_close(want, got, rtol=1e-3, atol=1e-4)
    rd = next(r for r in s.__dict__["_plan_cache"].values()
              if r.spec.kind == "pallas")
    assert rd.n_fallback_pairs == len(SPECS)


# ------------------------------------------------------ dispatch counting --
def test_plan_round_is_one_tracked_dispatch():
    s = fresh("rbla")
    adapters, ranks, w = hetero_cohort(4, seed=14)
    s.aggregate_adapters(adapters, w, r_max=R_MAX, client_ranks=ranks,
                         backend="pallas")              # build plan
    dispatch_counter.reset()
    s.aggregate_adapters(adapters, w, r_max=R_MAX, client_ranks=ranks,
                         backend="pallas")
    assert dispatch_counter.reset() == 1


# --------------------------------------------------------- packed kernels --
def test_packed_agg_kernel_matches_oracle():
    from repro.kernels import packed_agg, packed_agg_ref
    rng = np.random.default_rng(0)
    n, r, d = 5, 24, 40
    x = jnp.asarray(rng.normal(size=(n, r, d)), jnp.float32)
    masks = jnp.asarray(rng.integers(0, 2, (n, r)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.5, 2.0, n), jnp.float32)
    prev = jnp.asarray(rng.normal(size=(r, d)), jnp.float32)
    for norm_by, pv in (("mask", prev), ("mask", None), ("weight", None)):
        got = packed_agg(x, masks, w, pv, norm_by=norm_by, interpret=True)
        want = packed_agg_ref(x, masks, w, pv, norm_by=norm_by)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5,
                                   err_msg=f"{norm_by}/prev={pv is not None}")


def test_packed_stack_kernel_places_and_scales():
    from repro.kernels import packed_stack
    rng = np.random.default_rng(1)
    n, r_in, d = 3, 8, 17
    x = jnp.asarray(rng.normal(size=(n, r_in, d)), jnp.float32)
    prev = jnp.asarray(rng.normal(size=(4, d)), jnp.float32)
    scales = jnp.asarray([1.0, 0.5, 2.0], jnp.float32)
    #          (client, src_row, dst_row, rows, scale_idx)
    copies_x = ((0, 0, 2, 3, 1), (2, 1, 5, 2, 2))
    copies_prev = ((1, 0, 2, 0),)
    out = packed_stack(x, scales, prev, copies_x=copies_x,
                       copies_prev=copies_prev, out_rows=9, interpret=True)
    want = np.zeros((9, d), np.float32)
    want[2:5] = 0.5 * np.asarray(x)[0, 0:3]
    want[5:7] = 2.0 * np.asarray(x)[2, 1:3]
    want[0:2] = 1.0 * np.asarray(prev)[1:3]
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-6, atol=1e-6)


def test_packed_stack_row_tiles_match_ref():
    """Copies on 8-row tiles: the kernel walks the output in the tiles it
    uses compiled, zero-fills the cap padding, and a later copy wins."""
    from repro.kernels import packed_stack, packed_stack_ref
    from repro.kernels.rbla_agg.kernel import stack_row_tile
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(3, 32, 20)), jnp.float32)
    prev = jnp.asarray(rng.normal(size=(16, 20)), jnp.float32)
    scales = jnp.asarray([1.0, 0.5, 2.0, -3.0], jnp.float32)
    #          (client, src_row, dst_row, rows, scale_idx)
    copies_x = ((0, 0, 8, 16, 1), (2, 8, 24, 8, 2), (1, 16, 40, 8, 3))
    copies_prev = ((8, 0, 8, 0), (0, 40, 8, 2))     # overwrites client 1
    kw = dict(copies_x=copies_x, copies_prev=copies_prev, out_rows=56)
    assert stack_row_tile(copies_x, copies_prev, 32, 56, 128, jnp.float32,
                          interpret=False) == 8
    got = packed_stack(x, scales, prev, interpret=True, **kw)
    want = packed_stack_ref(x, scales, prev, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    assert not np.asarray(got)[32:40].any() and not np.asarray(got)[48:].any()


@pytest.mark.parametrize("rank,kernel", [(8, True), (4, False)])
def test_flora_bf16_plan_takes_kernel_on_f32_row_tiles(rank, kernel):
    """The stack plan packs a bf16 cohort in f32, so rank-8 copies sit on
    f32's 8-row tiles and the compiled plan takes the kernel; rank-4
    copies do not, and that bucket stacks in XLA."""
    s = fresh("flora")
    adapters, _, w = hetero_cohort(4, seed=21, r_lo=rank, r_hi=rank)
    adapters = [jax.tree.map(lambda x: x.astype(jnp.bfloat16)
                             if x.dtype == jnp.float32 else x, a)
                for a in adapters]
    ranks = np.full(4, rank)
    spec = build_cohort_spec(stack_trees(adapters), kind="pallas",
                             r_max=R_MAX, client_ranks=ranks,
                             interpret=False)
    rd = s.plan(None, spec)
    assert rd.n_fallback_pairs == 0
    assert rd.n_pallas_launches == (rd.n_kernel_launches if kernel else 0)
    got = s.aggregate_adapters(adapters, w, r_max=R_MAX, client_ranks=ranks,
                               backend="pallas")
    want = fresh("flora").aggregate_adapters(adapters, w, r_max=R_MAX,
                                             client_ranks=ranks,
                                             backend="ref")
    assert_trees_close(got, want, 1e-2, 1e-2)


def test_packed_stack_rejects_bad_copies():
    from repro.kernels import packed_stack
    x = jnp.ones((2, 4, 8), jnp.float32)
    with pytest.raises(ValueError, match="bad copy"):
        packed_stack(x, jnp.ones(1), copies_x=((0, 0, 0, 9, 0),),
                     out_rows=4, interpret=True)
    with pytest.raises(ValueError, match="no prev"):
        packed_stack(x, jnp.ones(1), copies_prev=((0, 0, 2, 0),),
                     out_rows=4, interpret=True)


# -------------------------------------------------------- packed fold path --
def test_rbla_packed_fold_matches_ref_fold_and_launch_count():
    s = get_strategy("rbla")
    adapters, ranks, w, bases = hetero_cohort(4, seed=15, with_bases=True)

    def mk():
        return ServerState(
            adapters=init_adapters(jax.random.PRNGKey(2), SPECS, R_MAX,
                                   R_MAX),
            base_trainable={"b": jnp.zeros(4)}, r_max=R_MAX)
    st_r, fs_r = mk(), s.init_fold(mk())
    st_p, fs_p = mk(), s.init_fold(mk())
    for i in range(4):
        u = ClientUpdate(adapters=adapters[i], base_trainable=bases[i],
                         n_examples=float(w[i]), rank=int(ranks[i]))
        st_r, fs_r = s.fold(st_r, u, fold_state=fs_r, backend="ref")
        st_p, fs_p = s.fold(st_p, u, fold_state=fs_p, backend="pallas")
    assert_trees_close(st_r.adapters, st_p.adapters, 1e-4, 1e-5)
    assert_trees_close(fs_r.row_mass, fs_p.row_mass, 1e-5, 1e-6)
    # the packed fold buckets SPECS' two widths x (A, B) into <= 4 fused
    # launches per fold, vs 2 launches per pair on the legacy path
    entry = next(iter(s.__dict__["_fold_plan_cache"].values()))
    assert entry[1] <= 2 * len(SPECS)


def test_flora_streaming_fold_is_exact_below_cap_nonuniform():
    """Satellite gate: flora's fold streams the one-shot stack exactly
    below the cap -- non-uniform masses included (the old fold was only
    exact for uniform ones)."""
    s = fresh("flora", stack_r_cap=256)
    adapters, ranks, w, bases = hetero_cohort(5, seed=16, with_bases=True)
    updates = [ClientUpdate(adapters=adapters[i], base_trainable=bases[i],
                            n_examples=float(w[i]), rank=int(ranks[i]))
               for i in range(5)]

    def mk():
        rs = s.server_storage_rank(R_MAX)
        return ServerState(
            adapters=init_adapters(jax.random.PRNGKey(6), SPECS, rs, R_MAX),
            base_trainable={"b": jnp.zeros(4)}, r_max=R_MAX)
    st, fs = mk(), s.init_fold(mk())
    for u in updates:
        st, fs = s.fold(st, u, fold_state=fs, backend="ref")
    want = s.aggregate(mk(), updates, weights=w, backend="ref")
    assert_trees_close(st.adapters, want.adapters, 2e-5, 2e-6)
    assert_trees_close(st.base_trainable, want.base_trainable, 2e-5, 2e-6)


def test_flora_streaming_fold_cap_crossing_reprojects():
    s = fresh("flora", stack_r_cap=12)
    adapters, ranks, w, bases = hetero_cohort(4, seed=17, r_lo=3, r_hi=6,
                                              with_bases=True)

    def mk():
        rs = s.server_storage_rank(R_MAX)
        return ServerState(
            adapters=init_adapters(jax.random.PRNGKey(8), SPECS, rs, R_MAX),
            base_trainable={"b": jnp.zeros(4)}, r_max=R_MAX)
    st, fs = mk(), s.init_fold(mk())
    crossed = False
    for i in range(4):
        u = ClientUpdate(adapters=adapters[i], base_trainable=bases[i],
                         n_examples=float(w[i]), rank=int(ranks[i]))
        before = int(np.max(np.asarray(st.adapters["fc1"]["rank"])))
        st, fs = s.fold(st, u, fold_state=fs, backend="ref")
        after = int(np.max(np.asarray(st.adapters["fc1"]["rank"])))
        if after < before + int(ranks[i]):
            crossed = True
            assert after == R_MAX        # re-projected back to r_max
    assert crossed, "cohort never crossed the cap; fixture broken"
    assert np.isfinite(np.asarray(st.adapters["fc1"]["A"])).all()
    for leaf in jax.tree.leaves(st.adapters):
        assert np.isfinite(np.asarray(leaf, np.float32)).all()


# ---------------------------------------------------- codec-aware caching --
def test_codec_mix_in_executor_cache_key():
    """Same codec mix across rank multisets shares one jitted executor
    (masks, ranks, payloads, and scales are all runtime data); changing
    the mix is a different wire layout and must build a new one."""
    from repro.core import codec
    s = fresh("rbla")
    for seed, (lo, hi) in enumerate([(1, 3), (4, R_MAX)]):
        a, r, w = hetero_cohort(4, seed=seed, r_lo=lo, r_hi=hi)
        enc = [codec.encode_adapters(x, "int8") for x in a]
        s.aggregate_adapters(enc, w, r_max=R_MAX, client_ranks=r,
                             backend="ref")
    assert s.plan_stats["misses"] == 2          # two plans...
    assert len(s.__dict__["_plan_exec_cache"]) == 1   # ...one executor
    a, r, w = hetero_cohort(4, seed=9)
    mix = ("int8", "bf16", "int8", "bf16")
    enc = [codec.encode_adapters(x, c) for x, c in zip(a, mix)]
    s.aggregate_adapters(enc, w, r_max=R_MAX, client_ranks=r,
                         backend="ref")
    assert s.plan_stats["misses"] == 3
    assert len(s.__dict__["_plan_exec_cache"]) == 2


def test_codec_change_replans_while_rank_repeat_hits():
    """The codec mix is part of the plan key: a repeat cohort under the
    same mix hits, the same cohort under a different mix re-plans, and
    the LRU keeps both warm."""
    from repro.core import codec
    s = fresh("rbla")
    a, r, w = hetero_cohort(4, seed=2)
    int8 = [codec.encode_adapters(x, "int8") for x in a]
    for _ in range(2):
        s.aggregate_adapters(int8, w, r_max=R_MAX, client_ranks=r,
                             backend="ref")
    assert s.plan_stats == {
        "hits": 1, "misses": 1, **{k: v for k, v in s.plan_stats.items()
                                   if k not in ("hits", "misses")}}
    mixed = [codec.encode_adapters(x, "bf16" if i == 0 else "int8")
             for i, x in enumerate(a)]
    s.aggregate_adapters(mixed, w, r_max=R_MAX, client_ranks=r,
                         backend="ref")
    assert s.plan_stats["misses"] == 2
    s.aggregate_adapters(int8, w, r_max=R_MAX, client_ranks=r,
                         backend="ref")
    assert s.plan_stats["hits"] == 2 and s.plan_stats["misses"] == 2


def test_encoded_plan_matches_decoded_oracle_with_prev():
    """Fused-dequant plan vs eager decode, with prev-retention in play
    (unowned rows fall back to the dequantized-path prev identically)."""
    from repro.core import codec
    from _cohorts import mixed_codec_cohort
    enc, dec, ranks, w, _ = mixed_codec_cohort(n=5, seed=11, r_lo=1,
                                               r_hi=3)
    prev = init_adapters(jax.random.PRNGKey(77), SPECS, R_MAX, R_MAX)
    s_enc, s_dec = fresh("rbla"), fresh("rbla")
    got = s_enc.aggregate_adapters(enc, w, r_max=R_MAX, client_ranks=ranks,
                                   prev_global=prev, backend="ref")
    want = s_dec.aggregate_adapters(dec, w, r_max=R_MAX,
                                    client_ranks=ranks, prev_global=prev,
                                    backend="ref")
    assert_trees_close(want, got, 1e-5, 1e-6)
    assert s_enc.plan_stats["misses"] == 1      # planned, not eager


# ------------------------------------------- plain cohorts, per client --
MEAN_FAMILY = ("rbla", "zeropad", "fedavg", "rbla_ranked", "rbla_norm",
               "rbla_clipped", "rbla_trimmed", "rbla_median")


def _no_stack(*_, **__):
    raise AssertionError("stack_trees called on the per-client path")


@pytest.mark.parametrize("donate", [False, True])
@pytest.mark.parametrize("with_prev", [False, True])
@pytest.mark.parametrize("method", MEAN_FAMILY)
def test_mean_family_plans_plain_cohorts_per_client(method, with_prev,
                                                    donate, monkeypatch):
    """A plain f32 cohort of the mean family on ref is packed per client
    inside the plan: ``stack_trees`` never runs and
    ``cohort_stacks_total`` stays put.  The round equals the per-leaf
    oracle, and bit for bit the stacked plan over the same buckets."""
    from repro.core import strategy as strategy_mod
    from repro.obs import cohort_stacks
    adapters, ranks, w = hetero_cohort(4, seed=44, r_lo=2, r_hi=6)

    def prev():                       # fresh buffers: donation eats them
        return (init_adapters(jax.random.PRNGKey(45), SPECS, R_MAX, R_MAX)
                if with_prev else None)
    s = fresh(method)
    want = reference_round(s, adapters, w, r_max=R_MAX, client_ranks=ranks,
                           prev=prev())
    spec = build_cohort_spec(stack_trees(adapters), kind="ref", r_max=R_MAX,
                             client_ranks=ranks,
                             prev_tree=prev() if s.retains_prev else None)
    stacked = fresh(method).plan(None, spec)(
        stack_trees(adapters), w, prev() if s.retains_prev else None)
    monkeypatch.setattr(strategy_mod, "stack_trees", _no_stack)
    stacks = cohort_stacks(s.name)
    before = stacks.value
    got = s.aggregate_adapters(adapters, w, r_max=R_MAX, client_ranks=ranks,
                               prev_global=prev(), backend="ref",
                               donate=donate)
    assert stacks.value == before
    rd, = s.__dict__["_plan_cache"].values()
    assert rd.kind == "packed" and rd.spec.codecs == ("none",) * 4
    assert_trees_close(got, want, msg=method)
    for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(stacked)):
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_stacking_paths_count_their_cohort_stacks():
    """Paths that need the ``(n, ...)`` tree still stack, once a cohort,
    and say so in ``cohort_stacks_total``: the stack plan (flora), the
    svd plan and the distributed plan."""
    from repro.obs import cohort_stacks
    adapters, ranks, w = hetero_cohort(3, seed=46)
    for method, kw in (("flora", dict(backend="ref")),
                       ("svd", dict(backend="ref")),
                       ("rbla", dict(backend="distributed"))):
        s = fresh(method)
        stacks = cohort_stacks(s.name)
        before = stacks.value
        s.aggregate_adapters(adapters, w, r_max=R_MAX, client_ranks=ranks,
                             **kw)
        assert stacks.value == before + 1, (method, kw)


# ------------------------------------------------- host syncs, no blocking --
SYNC_SITES = ("cohort_spec", "state_spec", "fold_rank", "validate_finite",
              "validate_scale")


def _host_syncs():
    from repro.obs import host_syncs
    return {site: host_syncs(site).value for site in SYNC_SITES}


@pytest.mark.parametrize("case", ["f32_round", "int8_round", "fold",
                                  "fold_unranked", "int8_fold"])
def test_host_syncs_count_every_read_the_host_waits_on(case):
    """``host_syncs_total{site}`` against the reads the code makes on a
    tiny cohort: a round reads each client's rank leaves and the
    previous global's; an upload reads its validation flags once (every
    finiteness and scale check in one program), and its fold the
    state's rank leaves (and the upload's, when it names no rank).
    Numpy values (the client ranks here) are no reads."""
    from repro.core import codec
    from repro.fl import AsyncAggregator
    n, pairs = 4, len(SPECS)
    adapters, ranks, w = hetero_cohort(n, seed=41)
    prev = init_adapters(jax.random.PRNGKey(8), SPECS, R_MAX, R_MAX)
    before = _host_syncs()
    if case.endswith("round"):
        cohort = (adapters if case == "f32_round"
                  else [codec.encode_adapters(a, "int8") for a in adapters])
        fresh("rbla").aggregate_adapters(
            cohort, w, r_max=R_MAX, client_ranks=np.asarray(ranks),
            prev_global=prev, backend="pallas", interpret=True)
        # one rank leaf per client and pair, plain or encoded
        want = {"cohort_spec": n * pairs + pairs}
    else:
        upload = (codec.encode_adapters(adapters[0], "int8")
                  if case == "int8_fold" else adapters[0])
        agg = AsyncAggregator(
            fresh("rbla"), ServerState(adapters=prev, base_trainable={},
                                       r_max=R_MAX),
            backend="pallas", interpret=True)
        agg.submit(ClientUpdate(
            adapters=upload, base_trainable={}, n_examples=2.0,
            rank=None if case == "fold_unranked" else int(ranks[0])))
        # one read an upload, f32 or int8; submit adds no scale reads
        want = {"validate_finite": 1, "state_spec": pairs}
        if case == "fold_unranked":
            want["fold_rank"] = pairs
    after = _host_syncs()
    assert {k: after[k] - before[k] for k in SYNC_SITES} == {
        **dict.fromkeys(SYNC_SITES, 0), **want}


@pytest.mark.parametrize("wire", ["none", "int8"])
@pytest.mark.parametrize("with_prev", [False, True])
def test_cohort_walk_reads_every_rank_leaf_in_one_device_get(
        wire, with_prev, monkeypatch):
    """The per-client walk fetches all its rank leaves (the previous
    global's too) with ONE ``jax.device_get`` a round, warm or cold,
    while ``host_syncs_total{site="cohort_spec"}`` still counts each
    array read: ``n x pairs`` (+ ``pairs`` with a previous global)."""
    from repro.core import codec
    from repro.obs import host_syncs
    n, pairs = 4, len(SPECS)
    adapters, ranks, w = hetero_cohort(n, seed=48)
    if wire != "none":
        adapters = [codec.encode_adapters(a, wire) for a in adapters]
    prev = (init_adapters(jax.random.PRNGKey(49), SPECS, R_MAX, R_MAX)
            if with_prev else None)
    calls = []
    get = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: calls.append(1) or get(x))
    reads = host_syncs("cohort_spec")
    s = fresh("rbla")
    for rnd in (1, 2):
        before = reads.value
        s.aggregate_adapters(adapters, w, r_max=R_MAX,
                             client_ranks=np.asarray(ranks),
                             prev_global=prev, backend="ref")
        assert len(calls) == rnd
        assert reads.value - before == n * pairs + pairs * with_prev


def test_aggregate_adapters_never_blocks(monkeypatch):
    """Spans measure host time: nothing inside ``aggregate_adapters``
    waits for the device, cold or warm, stacked or encoded."""
    from repro.core import codec
    calls = []
    block = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: calls.append(1) or block(x))
    adapters, ranks, w = hetero_cohort(4, seed=43)
    enc = [codec.encode_adapters(a, "int8") for a in adapters]
    prev = init_adapters(jax.random.PRNGKey(9), SPECS, R_MAX, R_MAX)
    s = fresh("rbla")
    for cohort in (adapters, adapters, enc, enc):
        s.aggregate_adapters(cohort, w, r_max=R_MAX, client_ranks=ranks,
                             prev_global=prev, backend="pallas",
                             interpret=True)
    assert calls == []
