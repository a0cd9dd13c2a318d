"""repro.serving suite: batched multi-adapter kernel parity, the
no-retrace guard, store paging/growth/eviction, hot-swap atomicity,
publish donation safety, and the AsyncAggregator publish hook."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ClientUpdate, ServerState
from repro.fl import AsyncAggregator
from repro.kernels import (batched_lora_matmul, batched_lora_matmul_inline,
                           batched_lora_matmul_ref)
from repro.kernels.lora_matmul.ops import resolve_impl, trace_counts
from repro.lora import DEFAULT_ALPHA, init_adapters, set_ranks, strip_ranks
from repro.serving import AdapterStore, ServingEngine, merged_reference

from tests._cohorts import R_MAX, SPECS, assert_trees_close, hetero_cohort

# engine base weights for the shared SPECS: W is (fan_in, fan_out)
WEIGHTS = {p: jnp.asarray(
    np.random.default_rng(hash(p) % 2**31).normal(size=(fi, fo)) * 0.1,
    jnp.float32) for p, (fo, fi) in SPECS.items()}


def packed_case(m=12, k=16, n=10, n_slots=6, r_max=4, seed=0,
                dtype=jnp.float32):
    """Random packed buffers + tables + a mixed id batch.

    Slot 0 has rank 0 (the null adapter); rows outside live segments are
    deliberately garbage -- the segment mask must never read them.
    """
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(m, k)), dtype)
    w = jnp.asarray(rng.normal(size=(k, n)) * 0.2, dtype)
    a_rows = jnp.asarray(rng.normal(size=(n_slots * r_max, k)), dtype)
    b_rows = jnp.asarray(rng.normal(size=(n_slots * r_max, n)), dtype)
    off = np.arange(n_slots, dtype=np.int32) * r_max
    rank = rng.integers(1, r_max + 1, n_slots).astype(np.int32)
    rank[0] = 0
    scale = (DEFAULT_ALPHA / np.maximum(rank, 1)).astype(np.float32)
    ids = jnp.asarray(rng.integers(0, n_slots, m), jnp.int32)
    return x, w, a_rows, b_rows, jnp.asarray(off), jnp.asarray(rank), \
        jnp.asarray(scale), ids


def ref_out(x, w, a_rows, b_rows, off, rank, scale, ids):
    idn = np.asarray(ids)
    return batched_lora_matmul_ref(
        x, w, a_rows, b_rows, np.asarray(off)[idn], np.asarray(rank)[idn],
        np.asarray(scale)[idn])


# ---------------------------------------------------------------- kernel --
@pytest.mark.parametrize("impl,interpret", [("xla", None),
                                            ("pallas", True)])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 3e-2)])
def test_batched_matches_ref(impl, interpret, dtype, tol):
    case = packed_case(dtype=dtype)
    x, w, a_rows, b_rows, off, rank, scale, ids = case
    got = batched_lora_matmul_inline(x, w, a_rows, b_rows, ids, off, rank,
                                     scale, impl=impl, interpret=interpret)
    want = ref_out(*case)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 3e-2)])
def test_batched_kernel_k_axis_not_a_multiple_of_the_block(dtype, tol):
    """fan_in 640 is no multiple of the 512-wide k-block: the kernel must
    still reduce exactly over the real K (a partial k-block reads past
    the array's end -- NaN in interpret mode, garbage on the chip)."""
    case = packed_case(m=16, k=640, n=128, dtype=dtype)
    x, w, a_rows, b_rows, off, rank, scale, ids = case
    got = batched_lora_matmul_inline(x, w, a_rows, b_rows, ids, off, rank,
                                     scale, impl="pallas", interpret=True)
    want = np.asarray(ref_out(*case), np.float32)
    # 640-term sums of O(1) products: the tolerance scales with them
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=tol, atol=tol * np.abs(want).max())


@pytest.mark.parametrize("impl,interpret", [("xla", None),
                                            ("pallas", True)])
def test_adapter_id_permutation_equivariance(impl, interpret):
    """Permuting (rows, ids) together permutes the output -- adapter
    resolution is strictly per request row."""
    x, w, a_rows, b_rows, off, rank, scale, ids = packed_case(seed=3)
    perm = np.random.default_rng(7).permutation(x.shape[0])
    y = batched_lora_matmul_inline(x, w, a_rows, b_rows, ids, off, rank,
                                   scale, impl=impl, interpret=interpret)
    yp = batched_lora_matmul_inline(x[perm], w, a_rows, b_rows, ids[perm],
                                    off, rank, scale, impl=impl,
                                    interpret=interpret)
    np.testing.assert_allclose(np.asarray(yp), np.asarray(y)[perm],
                               rtol=1e-5, atol=1e-5)


def test_rank0_slot_serves_base_model():
    x, w, a_rows, b_rows, off, rank, scale, _ = packed_case()
    ids = jnp.zeros(x.shape[0], jnp.int32)        # slot 0: rank 0
    y = batched_lora_matmul_inline(x, w, a_rows, b_rows, ids, off, rank,
                                   scale, impl="xla")
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w),
                               rtol=1e-5, atol=1e-5)


def test_no_retrace_across_tenant_mixes():
    """Ids, offsets, ranks, scales, and table *contents* are runtime
    data: the public jitted entry traces once for a given geometry."""
    x, w, a_rows, b_rows, off, rank, scale, ids = packed_case(seed=11)
    jax.block_until_ready(batched_lora_matmul(
        x, w, a_rows, b_rows, ids, off, rank, scale))
    before = trace_counts["batched_lora_matmul"]
    rng = np.random.default_rng(12)
    for s in range(4):                    # new mix + mutated tables
        ids2 = jnp.asarray(rng.integers(0, off.shape[0], x.shape[0]),
                           jnp.int32)
        rank2 = jnp.asarray(rng.integers(0, 5, off.shape[0]), jnp.int32)
        got = batched_lora_matmul(x, w, a_rows, b_rows, ids2, off, rank2,
                                  scale)
        want = ref_out(x, w, a_rows, b_rows, off, rank2, scale, ids2)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
    assert trace_counts["batched_lora_matmul"] == before


def test_resolve_impl():
    assert resolve_impl("auto") in ("xla", "pallas")
    assert resolve_impl("xla") == "xla"
    assert resolve_impl("pallas") == "pallas"
    with pytest.raises(ValueError, match="unknown batched"):
        resolve_impl("tpu")


# ----------------------------------------------------------------- store --
def one_tenant_adapters(rank, seed=0):
    ad = init_adapters(jax.random.PRNGKey(seed), SPECS, R_MAX, rank)
    rng = np.random.default_rng(seed)
    ad = jax.tree.map(
        lambda v: v + jnp.asarray(rng.normal(size=v.shape), v.dtype)
        if v.dtype == jnp.float32 else v, ad)
    return set_ranks(ad, rank)


def test_store_put_get_roundtrip():
    store = AdapterStore(SPECS, r_max=R_MAX)
    ad = one_tenant_adapters(3, seed=4)
    store.put("t0", ad)
    assert_trees_close(store.get("t0"), ad, msg="put/get roundtrip")


def test_store_paths_share_geometry_bucket():
    store = AdapterStore({"p": (8, 16), "q": (8, 16), "r": (8, 12)},
                         r_max=4)
    snap = store.snapshot()
    assert snap.bucket_of["p"] == snap.bucket_of["q"]
    assert snap.bucket_of["p"] != snap.bucket_of["r"]


def test_store_page_growth_and_remove():
    store = AdapterStore(SPECS, r_max=R_MAX, init_pages=1,
                         init_tenant_capacity=2)
    slots = [store.register(f"t{i}", rank=2 + i % 3) for i in range(5)]
    assert len(set(slots)) == 5 and 0 not in slots
    # each tenant owns a distinct page per path (distinct offsets)
    for p in SPECS:
        offs = [int(store.snapshot().table(p).off[s]) for s in slots]
        assert len(set(offs)) == 5
    store.remove("t2")
    assert store.n_tenants == 4
    evicted = slots[2]
    assert int(store.snapshot().table("fc1").rank[evicted]) == 0
    # freed page and slot are reused
    s_new = store.register("t9", rank=1)
    assert s_new == evicted


def test_store_rank_validation():
    store = AdapterStore(SPECS, r_max=R_MAX)
    with pytest.raises(ValueError, match="r_max"):
        store.register("t", rank=R_MAX + 1)
    with pytest.raises(ValueError, match="does not match"):
        bad = one_tenant_adapters(2)
        bad["fc1"]["A"] = bad["fc1"]["A"][:, :-1]
        store.put("t", bad)


def test_publish_reslices_per_tenant_rank():
    """publish() writes min(tenant_rank, global_rank) rows of the global
    into every segment -- the Alg. 2 re-slice, server-side."""
    store = AdapterStore(SPECS, r_max=R_MAX)
    store.register("lo", rank=2)
    store.register("hi", rank=R_MAX)
    glob = one_tenant_adapters(5, seed=8)       # global rank 5
    store.publish(glob)
    assert_trees_close(store.get("lo"), set_ranks(glob, 2),
                       msg="rank-2 tenant gets the first 2 global rows")
    # the rank-8 tenant keeps its registered rank (its table entry) but
    # only the 5 global rows carry signal -- rows 5.. are zeroed
    hi_factors, hi_ranks = strip_ranks(store.get("hi"))
    want_factors, _ = strip_ranks(set_ranks(glob, 5))
    assert_trees_close(hi_factors, want_factors,
                       msg="rank-8 tenant gets all 5; rows 5.. zeroed")
    assert all(int(r) == R_MAX for r in jax.tree.leaves(hi_ranks))


def test_snapshot_pins_buffers_across_publish():
    """Hot-swap atomicity: a pinned snapshot's bytes never change, and
    writes under a live pin copy instead of donating."""
    store = AdapterStore(SPECS, r_max=R_MAX)
    store.register("t", rank=4)
    store.publish(one_tenant_adapters(4, seed=1))
    snap = store.snapshot()
    frozen = {p: (np.asarray(snap.pair_buffers(p)[0]).copy(),
                  np.asarray(snap.pair_buffers(p)[1]).copy())
              for p in SPECS}
    v0 = snap.version
    store.publish(one_tenant_adapters(4, seed=2))
    for p in SPECS:
        a_rows, b_rows = snap.pair_buffers(p)
        assert not a_rows.is_deleted() and not b_rows.is_deleted()
        np.testing.assert_array_equal(np.asarray(a_rows), frozen[p][0])
        np.testing.assert_array_equal(np.asarray(b_rows), frozen[p][1])
    new = store.snapshot()
    assert new.version > v0
    assert any(not np.array_equal(np.asarray(new.pair_buffers(p)[0]),
                                  frozen[p][0]) for p in SPECS)


def test_publish_donates_when_unpinned():
    """With no live snapshot, publish updates buckets in place: the old
    buffer is donated into the scatter (freed, not copied)."""
    store = AdapterStore(SPECS, r_max=R_MAX)
    store.register("t", rank=4)
    store.publish(one_tenant_adapters(4, seed=1))
    snap = store.snapshot()
    old = {p: snap.pair_buffers(p) for p in SPECS}
    del snap                                    # drop the only pin
    store.publish(one_tenant_adapters(4, seed=2))
    assert all(a.is_deleted() and b.is_deleted()
               for a, b in old.values()), "unpinned buffers must donate"
    assert_trees_close(store.get("t"), set_ranks(
        one_tenant_adapters(4, seed=2), 4), msg="donated publish content")


# ---------------------------------------------------------------- engine --
def engine_with_tenants(n=6, seed=0):
    store = AdapterStore(SPECS, r_max=R_MAX)
    engine = ServingEngine(WEIGHTS, store)
    adapters, ranks, _ = hetero_cohort(n=n, seed=seed)
    ids = [store.put(f"t{i}", adapters[i]) for i in range(n)]
    return store, engine, ids


def test_engine_parity_vs_merged_reference():
    store, engine, slots = engine_with_tenants()
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.choice(slots + [0], 16), jnp.int32)
    for path, (fo, fi) in SPECS.items():
        x = jnp.asarray(rng.normal(size=(16, fi)), jnp.float32)
        got = engine.apply(path, x, ids)
        want = merged_reference(engine, path, x, ids)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


def test_engine_forward_chains_one_snapshot():
    store, engine, slots = engine_with_tenants(seed=5)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(8, SPECS["fc1"][1])), jnp.float32)
    ids = jnp.asarray(rng.choice(slots, 8), jnp.int32)
    got = engine.forward(x, ids, paths=["fc1", "fc2"])
    h = merged_reference(engine, "fc1", x, ids)
    want = merged_reference(engine, "fc2", h, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_in_flight_batch_sees_one_version():
    """A batch pinned to a snapshot is immune to concurrent publishes;
    the next unpinned batch picks up the new version -- and neither side
    of the swap retraces the serving executable."""
    store, engine, slots = engine_with_tenants(seed=9)
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(8, SPECS["fc1"][1])), jnp.float32)
    ids = jnp.asarray(rng.choice(slots, 8), jnp.int32)
    snap = engine.snapshot()
    before_swap = np.asarray(engine.apply("fc1", x, ids, snapshot=snap))
    jax.block_until_ready(before_swap)
    traces0 = trace_counts["batched_lora_matmul"]
    engine.publish(one_tenant_adapters(R_MAX, seed=77))   # mid-flight
    in_flight = np.asarray(engine.apply("fc1", x, ids, snapshot=snap))
    np.testing.assert_array_equal(in_flight, before_swap)
    fresh = np.asarray(engine.apply("fc1", x, ids))
    assert not np.array_equal(fresh, before_swap)
    np.testing.assert_allclose(
        fresh, np.asarray(merged_reference(engine, "fc1", x, ids)),
        rtol=1e-4, atol=1e-4)
    assert trace_counts["batched_lora_matmul"] == traces0


# ------------------------------------------------------- async publish hook --
def test_async_aggregator_on_publish():
    """AsyncAggregator(on_publish=engine.publisher()) hot-swaps each
    folded global into the store at the configured cadence."""
    store = AdapterStore(SPECS, r_max=R_MAX)
    engine = ServingEngine(WEIGHTS, store)
    store.register("t", rank=3)
    adapters, ranks, weights, bases = hetero_cohort(n=4, seed=2,
                                                    with_bases=True)
    state = ServerState(
        adapters=init_adapters(jax.random.PRNGKey(0), SPECS, R_MAX, R_MAX),
        base_trainable=bases[0], r_max=R_MAX)
    agg = AsyncAggregator("rbla", state, backend="ref",
                          on_publish=engine.publisher(), publish_every=2)
    v0 = store.version
    for i in range(4):
        agg.submit(ClientUpdate(adapters=adapters[i],
                                base_trainable=bases[i],
                                n_examples=float(weights[i]),
                                rank=int(ranks[i])))
    assert agg.n_published == 2          # publish_every=2 over 4 folds
    assert store.version > v0
    # the served segment is the live global re-sliced to the tenant rank
    assert_trees_close(store.get("t"), set_ranks(agg.state.adapters, 3),
                       msg="store serves the last published global")


def test_async_publish_every_validation():
    state = ServerState(
        adapters=init_adapters(jax.random.PRNGKey(0), SPECS, R_MAX, 2),
        base_trainable={}, r_max=R_MAX)
    with pytest.raises(ValueError, match="publish_every"):
        AsyncAggregator("rbla", state, publish_every=0)
