"""Crash-recovery suite: checkpoint hardening, WAL, idempotency, chaos.

The load-bearing guarantee is **bit-identical recovery**: crash the
service at any WAL/checkpoint boundary, reconstruct it from disk, finish
the workload -- and the final adapters equal the uninterrupted run's
exactly (for ``supports_incremental`` strategies; within the parity
tolerance for replay-from-anchor ones).  Plus: the hardened checkpoint
io rejects corruption/shape/dtype drift loudly, the WAL tolerates torn
tails but refuses mid-stream corruption, the dedup window makes
at-least-once ingestion fold exactly once in every buffering mode, and
the chaos-injected simulator runs to completion deterministically.

Property tests run under ``tests/_hypothesis_stub.py`` (containers
without hypothesis) and real hypothesis alike -- zero-arg wrappers, so
no pytest fixtures inside (tempfile instead of tmp_path).
"""
import dataclasses
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.checkpoint import (CheckpointError, load_blob, pack_obj, restore,
                              save, save_blob, unpack_obj)
from repro.core.strategy import ClientUpdate, ServerState, get_strategy
from repro.fl import (AsyncAggregator, AsyncFLConfig, DedupWindow,
                      DurableAggregator, FaultPlan, RetryPolicy,
                      WriteAheadLog, run_async_simulation)
from repro.fl.chaos import flaky
from repro.lora import init_adapters

from _cohorts import R_MAX, SPECS, assert_trees_close, hetero_cohort

jax.config.update("jax_platform_name", "cpu")


def make_state(strategy, seed=99):
    r_storage = strategy.server_storage_rank(R_MAX) or R_MAX
    prev = init_adapters(jax.random.PRNGKey(seed), SPECS, r_storage, R_MAX)
    base = {"b": jnp.zeros((4,), jnp.float32)}
    return ServerState(adapters=prev, base_trainable=base, r_max=R_MAX)


def make_updates(n=8, seed=3):
    adapters, ranks, w, bases = hetero_cohort(n, seed=seed, with_bases=True)
    return [ClientUpdate(adapters=adapters[i], base_trainable=bases[i],
                         n_examples=float(w[i]), rank=int(ranks[i]))
            for i in range(n)]


def assert_trees_equal(a, b, msg=""):
    """Bit-exact tree equality (recovery's contract, not a tolerance)."""
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb), msg
    for x, y in zip(la, lb):
        assert np.array_equal(np.asarray(x), np.asarray(y)), msg


# ------------------------------------------------ checkpoint io hardening --
def test_checkpoint_roundtrips_bf16_scalars_and_keys(tmp_path):
    """bf16 (uint16 view + tag), python scalars, strings and typed PRNG
    keys all survive save/restore bit-exactly."""
    tree = {
        "w": jnp.asarray([[1.5, -2.25], [0.125, 3e-2]], jnp.bfloat16),
        "n": 7, "lr": 0.3, "on": True, "name": "rbla",
        "key": jax.random.key(42),
        "x": jnp.arange(6, dtype=jnp.int32).reshape(2, 3),
    }
    path = str(tmp_path / "ck")
    save(path, tree)
    back = restore(path, tree)
    assert back["w"].dtype == jnp.bfloat16
    assert np.array_equal(np.asarray(back["w"], np.float32),
                          np.asarray(tree["w"], np.float32))
    assert back["n"] == 7 and back["lr"] == 0.3
    assert back["on"] is True and back["name"] == "rbla"
    assert np.array_equal(jax.random.key_data(back["key"]),
                          jax.random.key_data(tree["key"]))
    assert np.array_equal(back["x"], tree["x"])


def test_restore_rejects_shape_and_dtype_mismatch(tmp_path):
    path = str(tmp_path / "ck")
    save(path, {"a": jnp.zeros((2, 3), jnp.float32)})
    with pytest.raises(CheckpointError, match="shape"):
        restore(path, {"a": jnp.zeros((3, 2), jnp.float32)})
    with pytest.raises(CheckpointError, match="dtype"):
        restore(path, {"a": jnp.zeros((2, 3), jnp.int32)})
    with pytest.raises(CheckpointError):
        restore(path, {"b": jnp.zeros((2, 3), jnp.float32)})


def test_restore_detects_bit_rot(tmp_path):
    path = str(tmp_path / "ck")
    save(path, {"a": jnp.ones((16, 16), jnp.float32)})
    data = [n for n in os.listdir(path) if n.startswith("data-")]
    assert len(data) == 1            # stale blobs from prior saves pruned
    fp = os.path.join(path, data[0])
    raw = bytearray(open(fp, "rb").read())
    raw[len(raw) // 2] ^= 0xFF
    open(fp, "wb").write(bytes(raw))
    with pytest.raises(CheckpointError, match="checksum|corrupt"):
        restore(path, {"a": jnp.ones((16, 16), jnp.float32)})


def test_blob_roundtrip_and_corruption(tmp_path):
    obj = {"replay": [[{"A": np.arange(4.0)}, 1.5]],
           "ids": ("u1", "u2"), "none": None, "raw": b"\x00\xff",
           "bf": jnp.asarray([1.5, -0.25], jnp.bfloat16)}
    path = str(tmp_path / "blob.bin")
    save_blob(path, obj)
    back = load_blob(path)
    assert back["ids"] == ("u1", "u2")     # tuple stays a tuple
    assert back["none"] is None and back["raw"] == b"\x00\xff"
    assert back["bf"].dtype == jnp.bfloat16
    assert np.array_equal(np.asarray(back["bf"], np.float32),
                          np.asarray(obj["bf"], np.float32))
    assert np.array_equal(back["replay"][0][0]["A"], obj["replay"][0][0]["A"])
    # truncation (torn write) and bit rot both fail loudly
    raw = open(path, "rb").read()
    open(path, "wb").write(raw[:len(raw) - 3])
    with pytest.raises(CheckpointError):
        load_blob(path)
    flipped = bytearray(raw)
    flipped[-1] ^= 0x01
    open(path, "wb").write(bytes(flipped))
    with pytest.raises(CheckpointError):
        load_blob(path)


def test_pack_obj_preserves_dict_order():
    obj = {"z": 1, "a": 2, "m": 3}
    assert list(unpack_obj(pack_obj(obj))) == ["z", "a", "m"]


# ----------------------------------------------------------------- the WAL --
def test_wal_roundtrip_and_torn_tail(tmp_path):
    d = str(tmp_path)
    wal = WriteAheadLog(d, fsync=False)
    for i in range(5):
        wal.append("submit", {"i": i})
    wal.close()
    seg = [os.path.join(d, n) for n in sorted(os.listdir(d))][0]
    with open(seg, "ab") as f:
        f.write(b"\x40\x00\x00\x00\xde\xad")   # crash mid-append
    wal2 = WriteAheadLog(d, fsync=False)
    recs = list(wal2.records())
    assert [b["i"] for _, _, b in recs] == [0, 1, 2, 3, 4]
    assert wal2.last_seq == 5 and wal2.n_torn >= 1
    # appends continue past the discarded torn frame
    assert wal2.append("submit", {"i": 5}) == 6


def test_wal_mid_stream_corruption_refuses(tmp_path):
    d = str(tmp_path)
    wal = WriteAheadLog(d, fsync=False)
    first = None
    for i in range(4):
        wal.append("submit", {"payload": "x" * 64, "i": i})
    wal.close()
    seg = [os.path.join(d, n) for n in sorted(os.listdir(d))][0]
    raw = bytearray(open(seg, "rb").read())
    raw[40] ^= 0xFF                    # inside the FIRST record's payload
    open(seg, "wb").write(bytes(raw))
    # a second, clean segment makes the corrupt one non-final: that is
    # silent record loss, not a torn tail -- refuse, don't skip
    wal2 = WriteAheadLog.__new__(WriteAheadLog)
    wal2.dir, wal2.fsync, wal2._fh, wal2._segment = d, False, None, None
    wal2.n_torn = wal2.bytes_written = wal2.n_records = wal2.last_seq = 0
    wal2._open_segment(100)
    wal2.append("submit", {"i": 99})
    wal2.close()
    with pytest.raises(CheckpointError, match="mid-stream"):
        list(wal2.records())


def test_wal_rotation_prunes_covered_segments(tmp_path):
    d = str(tmp_path)
    wal = WriteAheadLog(d, fsync=False)
    for i in range(3):
        wal.append("submit", {"i": i})
    wal.rotate(covered_seq=3)
    for i in range(3, 6):
        wal.append("submit", {"i": i})
    # the fully covered first segment is gone; only the live one remains
    assert [b["i"] for _, _, b in wal.records()] == [3, 4, 5]
    assert len([n for n in os.listdir(d) if n.startswith("wal-")]) == 1
    wal.close()


# ------------------------------------------------------- dedup and retries --
def test_dedup_window_slides():
    w = DedupWindow(3)
    for uid in ("a", "b", "c"):
        w.add(uid)
    assert "a" in w and len(w) == 3
    w.add("d")                         # evicts oldest
    assert "a" not in w and "b" in w and "d" in w
    w2 = DedupWindow(3)
    w2.load_state_dict(w.state_dict())
    assert "b" in w2 and "a" not in w2
    with pytest.raises(ValueError):
        DedupWindow(0)


def test_retry_policy_deterministic_bounded():
    p = RetryPolicy(base=0.5, factor=2.0, max_delay=4.0, max_retries=3,
                    jitter=0.2, seed=7)
    a = [p.delay(i, salt=11) for i in range(6)]
    b = [p.delay(i, salt=11) for i in range(6)]
    assert a == b                      # seeded: replays identically
    assert p.delay(0, salt=1) != p.delay(0, salt=2)   # clients decorrelate
    for i, d in enumerate(a):
        assert 0 < d <= 4.0 * 1.2
    assert not p.give_up(2) and p.give_up(3)
    with pytest.raises(ValueError):
        RetryPolicy(base=0.0)
    with pytest.raises(ValueError):
        RetryPolicy(jitter=1.5)


# ----------------------------------------- idempotent at-least-once folding --
@pytest.mark.parametrize("mode", ["streaming", "buffered", "replay_anchor"])
def test_same_update_id_folds_exactly_once(mode):
    """The regression the dedup window exists for: redeliver every upload
    and the state must match a clean exactly-once run bit-for-bit, in
    all three fold paths (streaming incremental, buffered semi-async,
    and replay-from-anchor for non-incremental strategies)."""
    method = "rbla_median" if mode == "replay_anchor" else "rbla"
    buffer_size = 3 if mode == "buffered" else 1
    s = get_strategy(method)
    if mode == "replay_anchor":
        assert not s.supports_incremental
    updates = make_updates(6)

    clean = AsyncAggregator(s, make_state(s), buffer_size=buffer_size)
    dup = AsyncAggregator(s, make_state(s), buffer_size=buffer_size)
    for i, u in enumerate(updates):
        clean.submit(u, now=float(i), update_id=f"u{i}")
        dup.submit(u, now=float(i), update_id=f"u{i}")
        # at-least-once transport: every upload redelivered immediately
        assert dup.submit(u, now=float(i), update_id=f"u{i}") is False
    # ... and a late redelivery of the first id, many folds later
    assert dup.submit(updates[0], now=99.0, update_id="u0") is False
    clean.flush(now=100.0)
    dup.flush(now=100.0)
    assert dup.version == clean.version
    assert dup.n_received == clean.n_received
    assert_trees_equal(dup.state.adapters, clean.state.adapters,
                       f"{mode}: duplicate delivery changed the state")
    assert_trees_equal(dup.state.base_trainable, clean.state.base_trainable)


# ---------------------------------------------------------- crash recovery --
def run_to(agg, updates, stop, start=0, **kw):
    for i in range(start, stop):
        agg.submit(updates[i], model_version=0, now=float(i),
                   update_id=f"u{i}", **kw)


def test_crash_recovery_bit_identical(tmp_path):
    """Kill after 5 accepted uploads (checkpoint at 3 + WAL tail),
    recover, finish -- bit-identical to never having crashed, including
    the bf16 accumulators, stochastic-rounding PRNG stream, momentum
    and the dedup window."""
    s = get_strategy("rbla")
    kw = dict(accum_dtype="bfloat16", seed=7, server_momentum=0.5,
              buffer_size=2, deadline=5.0)
    oracle = AsyncAggregator(s, make_state(s), **kw)
    updates = make_updates(8)
    run_to(oracle, updates, 8)
    oracle.maybe_flush(now=100.0)

    d = str(tmp_path)
    first = DurableAggregator(s, make_state(s), dir=d, checkpoint_every=3,
                              wal_fsync=False, **kw)
    run_to(first, updates, 5)
    first.close()                      # crash: no clean shutdown

    second = DurableAggregator(s, make_state(s), dir=d, checkpoint_every=3,
                               wal_fsync=False, **kw)
    assert second.n_recoveries == 1 and second.n_replayed == 2
    # the restored dedup window still rejects a pre-crash id
    assert second.submit(updates[1], now=1.0, update_id="u1") is False
    run_to(second, updates, 8, start=5)
    second.maybe_flush(now=100.0)
    assert_trees_equal(second.state.adapters, oracle.state.adapters,
                       "recovered run diverged from the uninterrupted one")
    assert_trees_equal(second.state.base_trainable,
                       oracle.state.base_trainable)
    assert second.version == oracle.version
    assert second.n_received == oracle.n_received


def test_durable_submit_validates_an_upload_once(tmp_path):
    """A live submit validates before it journals, and the base submit
    reuses that result: one ``submit.validate`` span and one
    ``validate_finite`` read an upload.  A replayed record is validated
    once, by the base submit."""
    from repro.obs import MetricsRegistry
    s = get_strategy("rbla")

    def validations(agg):
        reg = agg.obs_registry
        return (reg.get("obs_span_seconds").labels(
                    stage="submit.validate").count,
                reg.get("host_syncs_total").labels(
                    site="validate_finite").value)

    d = str(tmp_path)
    live = DurableAggregator(s, make_state(s), dir=d, wal_fsync=False,
                             registry=MetricsRegistry())
    live.submit(make_updates(1)[0], update_id="u0")
    assert live.n_received == 1 and validations(live) == (1, 1)
    live.close()
    again = DurableAggregator(s, make_state(s), dir=d, wal_fsync=False,
                              registry=MetricsRegistry())
    assert again.n_replayed == 1 and again.n_received == 1
    assert validations(again)[0] == 1


def test_recovery_falls_back_past_corrupt_checkpoint(tmp_path):
    """A checkpoint torn by bit rot is skipped: recovery restores the
    previous snapshot and replays a longer WAL tail -- same final bits
    (the WAL pruning policy keeps every record the oldest retained
    checkpoint still needs)."""
    s = get_strategy("rbla")
    updates = make_updates(8)
    oracle = AsyncAggregator(s, make_state(s))
    run_to(oracle, updates, 7)

    d = str(tmp_path)
    first = DurableAggregator(s, make_state(s), dir=d, checkpoint_every=3,
                              keep_checkpoints=2, wal_fsync=False)
    run_to(first, updates, 7)          # checkpoints at 3 and 6
    first.close()
    ckpts = sorted(n for n in os.listdir(d) if n.startswith("ckpt-"))
    assert len(ckpts) == 2
    fp = os.path.join(d, ckpts[-1])
    raw = bytearray(open(fp, "rb").read())
    raw[len(raw) // 2] ^= 0xFF
    open(fp, "wb").write(bytes(raw))

    second = DurableAggregator(s, make_state(s), dir=d, checkpoint_every=3,
                               keep_checkpoints=2, wal_fsync=False)
    assert second.n_replayed == 4      # records 4..7 re-driven
    assert_trees_equal(second.state.adapters, oracle.state.adapters)


@settings(max_examples=6, deadline=None)
@given(spec=st.tuples(st.integers(1, 7), st.integers(1, 4),
                      st.sampled_from(["rbla", "rbla_median"])))
def test_crash_consistency_property(spec):
    """Property: for ANY crash point x checkpoint cadence x strategy,
    recover-and-finish equals the uninterrupted run -- bit-identical for
    exact-incremental strategies, within the parity tolerance for
    replay-from-anchor ones (their fold recomputes a joint aggregate
    whose float reassociation the contract does not pin)."""
    cut, every, method = spec
    s = get_strategy(method)
    updates = make_updates(8)
    oracle = AsyncAggregator(s, make_state(s), seed=11)
    run_to(oracle, updates, 8)

    with tempfile.TemporaryDirectory() as d:
        first = DurableAggregator(s, make_state(s), dir=d, seed=11,
                                  checkpoint_every=every, wal_fsync=False)
        run_to(first, updates, cut)
        first.close()
        second = DurableAggregator(s, make_state(s), dir=d, seed=11,
                                   checkpoint_every=every, wal_fsync=False)
        run_to(second, updates, 8, start=cut)
    assert second.version == oracle.version
    if s.supports_incremental:
        assert_trees_equal(second.state.adapters, oracle.state.adapters,
                           f"{method} cut={cut} every={every}")
    else:
        assert_trees_close(second.state.adapters, oracle.state.adapters,
                           msg=f"{method} cut={cut} every={every}")
    assert_trees_equal(second.state.base_trainable,
                       oracle.state.base_trainable)


# ------------------------------------------------------------------- chaos --
def test_fault_plan_is_deterministic_and_validated():
    p1 = FaultPlan(seed=5, p_drop=0.3, p_corrupt=0.2, crash_at=(10,))
    p2 = FaultPlan(seed=5, p_drop=0.3, p_corrupt=0.2, crash_at=(10,))
    draws1 = [(p1.drop(i), p1.corrupt(i)) for i in range(50)]
    assert draws1 == [(p2.drop(i), p2.corrupt(i)) for i in range(50)]
    assert any(d for d, _ in draws1) and not all(d for d, _ in draws1)
    assert p1.crash_now(10) and not p1.crash_now(9)
    # independent streams: a drop draw says nothing about a corrupt draw
    assert draws1 != [(c, d) for d, c in draws1]
    with pytest.raises(ValueError):
        FaultPlan(p_drop=1.5)


def test_corrupt_and_truncate_bounce_off_front_door():
    s = get_strategy("rbla")
    agg = AsyncAggregator(s, make_state(s))
    u = make_updates(1)[0]
    plan = FaultPlan(seed=0, p_corrupt=1.0)
    with pytest.raises(ValueError, match="non-finite"):
        agg.submit(plan.corrupt_update(u))
    with pytest.raises(ValueError, match="truncated|malformed"):
        agg.submit(plan.truncate_update(u))
    assert agg.version == 0            # nothing reached the fold


@pytest.mark.slow
def test_chaos_simulation_completes_and_is_deterministic(tmp_path):
    """The full gauntlet: drops + retries, duplicates, reordering,
    corruption, truncation, stale pulls and two crash-restarts -- the
    run completes, and an identical plan over a fresh directory lands on
    the identical accuracy trajectory."""
    cfg = AsyncFLConfig(
        n_clients=3, r_max=8, n_per_class=8, n_test_per_class=4,
        batch_size=8, total_updates=10, eval_every=5, buffer_size=2,
        buffer_deadline_s=3.0, wal_dir=str(tmp_path / "a"),
        checkpoint_every=4, retry_base_s=0.2)
    plan = FaultPlan(seed=1, p_drop=0.25, p_duplicate=0.25, p_reorder=0.2,
                     p_corrupt=0.1, p_truncate=0.1, p_stale_pull=0.2,
                     crash_at=(4, 7))
    h1 = run_async_simulation(cfg, fault_plan=plan)
    cfg2 = dataclasses.replace(cfg, wal_dir=str(tmp_path / "b"))
    h2 = run_async_simulation(cfg2, fault_plan=plan)
    assert len(h1.test_acc) == 2
    assert h1.test_acc == h2.test_acc
    assert h1.mean_staleness == h2.mean_staleness


def test_publish_failure_keeps_serving_last_snapshot():
    """Graceful serving degradation: a failing hot-swap quarantines the
    pending state, readers keep the last committed snapshot, and the
    retry (with backoff) publishes the NEWEST pending tree."""
    from repro.serving import AdapterStore, ServingEngine

    store = AdapterStore({"l0": (8, 6)}, r_max=4)
    rng = np.random.default_rng(0)
    weights = {"l0": jnp.asarray(rng.normal(size=(6, 8)), jnp.float32)}
    eng = ServingEngine(weights, store, interpret=True)

    def tree(seed):
        r = np.random.default_rng(seed)
        return {"l0": {"A": jnp.asarray(r.normal(size=(4, 6)), jnp.float32),
                       "B": jnp.asarray(r.normal(size=(8, 4)), jnp.float32),
                       "rank": jnp.asarray(4, jnp.int32)}}

    eng.publish(tree(0))
    v0 = store.version
    orig, broken = store.publish, {"on": True}

    def flaky_publish(t):
        if broken["on"]:
            raise RuntimeError("injected publish fault")
        return orig(t)

    store.publish = flaky_publish
    pub = eng.publisher(max_backoff=4)
    state = dataclasses.make_dataclass("S", ["adapters"])

    pub(state(tree(1)))                # fails -> quarantined, skip 1
    assert store.version == v0 and eng.n_publish_failures == 1
    x = jnp.ones((3, 6), jnp.float32)
    y = eng.apply("l0", x, jnp.zeros((3,), jnp.int32))
    assert bool(jnp.all(jnp.isfinite(y)))     # still serving v0
    pub(state(tree(2)))                # inside backoff: skipped
    assert eng.n_publish_failures == 1
    pub(state(tree(3)))                # retry -> fails again, skip 2
    assert eng.n_publish_failures == 2 and store.version == v0
    broken["on"] = False
    pub(state(tree(4)))                # skipped (backoff 2)
    pub(state(tree(5)))                # skipped
    pub(state(tree(6)))                # retry succeeds, newest tree wins
    assert store.version == v0 + 1
    assert eng._publish_pending is None and eng._publish_fail_streak == 0


def test_flaky_wrapper_follows_plan():
    plan = FaultPlan(seed=3, p_publish_fail=0.5)
    calls = {"n": 0}

    def fn():
        calls["n"] += 1

    wrapped = flaky(fn, plan)
    outcomes = []
    for i in range(20):
        try:
            wrapped()
            outcomes.append(True)
        except RuntimeError:
            outcomes.append(False)
    assert outcomes == [not plan.publish_fail(i) for i in range(20)]
    assert calls["n"] == sum(outcomes)


# -------------------------------------------------- durability observability --
def test_health_reports_durability_section(tmp_path):
    from repro.obs import ServiceHealth

    s = get_strategy("rbla")
    agg = DurableAggregator(s, make_state(s), dir=str(tmp_path),
                            checkpoint_every=2, wal_fsync=False)
    run_to(agg, make_updates(3), 3)
    view = ServiceHealth(aggregator=agg).snapshot()
    dur = view["durability"]
    assert dur["wal_last_seq"] == 3
    assert dur["n_checkpoints"] == 1
    assert dur["replay_backlog"] == 1          # one record past the snapshot
    # the registry is process-global: earlier tests also checkpointed
    assert dur["checkpoint_latency"]["count"] >= 1
    # plain aggregators have no durability section
    plain = AsyncAggregator(s, make_state(s))
    assert "durability" not in ServiceHealth(aggregator=plain).snapshot()
