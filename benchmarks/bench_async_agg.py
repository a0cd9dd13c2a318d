"""Sync-round vs async-fold aggregation, and the quantized upload path.

Three questions, all on CPU-runnable synthetic cohorts:

1. **Server cost**: what does one synchronous cohort ``aggregate`` cost
   vs folding the same updates one at a time (``AsyncAggregator``,
   streaming fold or replay)?  Async folding trades one big reduction
   for N small ones -- the per-update cost is what an FLaaS server
   actually pays per arrival.

2. **Time-to-aggregate**: with log-normal client report latencies (a
   heavy straggler tail), when does each client's update actually land
   in the served global?  A sync round incorporates *everything* at
   ``max(latency) + t_agg``; the async server incorporates each update
   at ``latency_i + t_fold``.  We report the mean/median incorporation
   time and the time until 50% / 90% of the cohort's update mass is
   serving -- the straggler tail hits sync rounds directly, async barely.

3. **Quantized transport** (``repro.core.codec``): per upload codec, the
   wire bytes a client ships, the reduction vs fp32, the end-to-end
   parity of the fused-dequant aggregate against the fp32 baseline, and
   whether alternating codec mixes re-traces warm plans.

Plus a fourth, the **durability leg** (``docs/durability.md``): the WAL
overhead per fold, a checkpoint's write cost, and a crash recovery's
restore+replay cost, with every upload redelivered and the server killed
mid-stream along the way.

``--json PATH`` writes the machine-readable ``BENCH_async.json`` so the
wire-cost trajectory is tracked across PRs; ``--smoke`` runs a tiny case
and exits non-zero if (a) the quantized aggregate drifts past its
codec's tolerance from the fp32 baseline (``none`` must be bit-exact),
(b) int8 cuts upload bytes by less than 3.5x at 128 clients, (c)
alternating between two warm codec mixes adds plan misses or executor
retraces -- the codec is only free if the plan cache survives it -- (d)
running the same warm fold loop with metrics enabled adds jitted
executors or more than ``OBS_OVERHEAD_FRAC`` wall overhead vs metrics
disabled (the ``repro.obs`` overhead guarantee; see
``docs/observability.md``) -- or the **chaos gate** trips: a redelivered
upload double-folds, crash recovery is not bit-exact, recovery re-traces
a warm fold executor, or a failed publish tears the serving snapshot.

Run: ``PYTHONPATH=src python benchmarks/bench_async_agg.py``
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import codec
from repro.core.strategy import ClientUpdate, ServerState, get_strategy
from repro.fl import AsyncAggregator, DurableAggregator
from repro.fl.comm import tree_bytes
from repro.fl.selection import ClientLatencyModel
from repro.launch.cache import enable_compile_cache
from repro.lora import init_adapters, set_ranks
from repro.obs import bench_payload, set_enabled, time_fn

FULL_SPECS = {f"blk{i}": (1024, 1024) for i in range(4)}
FULL_R_MAX = 64
#: smoke tree is tiny but wide enough that int8's per-row fp32 scale
#: overhead (4 bytes per rank row) stays under the 3.5x reduction gate
SMOKE_SPECS = {"blk0": (96, 128), "blk1": (128, 96)}
SMOKE_R_MAX = 8
METHODS = ("rbla", "zeropad", "fedavg", "rbla_ranked", "flora")
N_CLIENTS = 10
N_WIRE_CLIENTS = 128           # cohort size for the wire-reduction gate
SEED = 0

#: end-to-end aggregate tolerance per codec (relative Frobenius vs the
#: fp32 baseline): bf16 has ~2^-8 relative error, int8 ~1/254 per row
#: before averaging; ``none`` must be bit-exact
CODEC_TOL = {"none": 0.0, "bf16": 1e-2, "int8": 2e-2}
WIRE_GATE_REDUCTION = 3.5
#: metrics-enabled wall overhead bound vs disabled, plus a small absolute
#: slack so a 1-vCPU CI box's scheduler jitter cannot flake a
#: milliseconds-long smoke loop
OBS_OVERHEAD_FRAC = 0.05
OBS_OVERHEAD_ABS_S = 2e-3


def make_cohort(n, seed, specs, r_max):
    rng = np.random.default_rng(seed)
    ranks = rng.integers(max(r_max // 16, 2), r_max + 1, n)
    updates = []
    for i in range(n):
        ad = init_adapters(jax.random.PRNGKey(seed + i), specs, r_max,
                           int(ranks[i]))
        ad = jax.tree.map(
            lambda x: x + jnp.asarray(0.01 * rng.normal(size=x.shape),
                                      x.dtype)
            if x.dtype == jnp.float32 else x, ad)
        updates.append(ClientUpdate(adapters=set_ranks(ad, int(ranks[i])),
                                    base_trainable={},
                                    n_examples=float(rng.integers(50, 500)),
                                    rank=int(ranks[i])))
    return updates, ranks


def make_state(strategy, specs, r_max):
    r_storage = strategy.server_storage_rank(r_max) or r_max
    adapters = init_adapters(jax.random.PRNGKey(999), specs, r_storage,
                             r_max)
    return ServerState(adapters=adapters, base_trainable={}, r_max=r_max)


def bench_method(method, updates, specs, r_max):
    s = get_strategy(method)
    if s.rank_contract == "stacked":
        # wide cap: pure stacking, no SVD re-projection mid-bench
        s = s.with_options(stack_r_cap=int(sum(u.rank for u in updates))
                           + r_max)
    weights = [u.n_examples for u in updates]
    state0 = make_state(s, specs, r_max)   # built once: only agg is timed

    # return the adapters tree (arrays), not the ServerState dataclass --
    # block_until_ready must see array leaves to measure compute
    t_sync = time_fn(lambda: s.aggregate(state0, updates, weights=weights,
                                         backend="ref").adapters)

    def fold_all():
        agg = AsyncAggregator(s, state0, staleness="constant",
                              backend="ref")
        for u in updates:
            agg.submit(u)
        return agg.state.adapters
    t_async_total = time_fn(fold_all)
    return t_sync, t_async_total / len(updates)


def time_to_quality(latencies, weights, t_sync, t_fold):
    """When is X% of the cohort's update mass serving, per mode?"""
    order = np.argsort(latencies)
    lat, w = latencies[order], weights[order] / weights.sum()
    # async: update i serves at latency_i + fold time (folds are short;
    # queueing is negligible at these rates)
    async_t = lat + t_fold
    mass = np.cumsum(w)
    t50_async = float(async_t[np.searchsorted(mass, 0.5)])
    t90_async = float(async_t[np.searchsorted(mass, 0.9)])
    # sync: nothing serves until the slowest client + one aggregate
    t_round = float(lat.max() + t_sync)
    return t50_async, t90_async, t_round


# ----------------------------------------------------- quantized uploads --
def _rel_err(a, b):
    """Relative Frobenius distance over the adapters' float leaves."""
    num = den = 0.0
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        if not jnp.issubdtype(jnp.asarray(la).dtype, jnp.floating):
            continue
        d = jnp.asarray(la, jnp.float32) - jnp.asarray(lb, jnp.float32)
        num += float(jnp.sum(d * d))
        den += float(jnp.sum(jnp.asarray(la, jnp.float32) ** 2))
    return (num / max(den, 1e-30)) ** 0.5


def bench_codecs(updates, specs, r_max):
    """One buffered flush per codec through the full service path; the
    fp32 run is the parity baseline.  Wire bytes come from the service's
    own intake accounting (post-codec, pre-decode)."""
    s = get_strategy("rbla")
    n = len(updates)
    rows, baseline = [], None
    for name in codec.CODECS:
        enc = [codec.encode_update(u, name) for u in updates]
        agg = AsyncAggregator("rbla", make_state(s, specs, r_max),
                              buffer_size=n, backend="ref")
        t0 = time.time()
        for u in enc:
            agg.submit(u)
        jax.block_until_ready(jax.tree.leaves(agg.state.adapters))
        flush_ms = (time.time() - t0) * 1e3
        if baseline is None:
            baseline = agg
        rows.append({
            "codec": name,
            "wire_bytes_per_client": agg.wire_bytes_received // n,
            "reduction_vs_fp32": (baseline.wire_bytes_received
                                  / max(agg.wire_bytes_received, 1)),
            "parity_rel_err": _rel_err(baseline.state.adapters,
                                       agg.state.adapters),
            "flush_ms": flush_ms,
        })
    return rows


def wire_reduction_at_scale(specs, r_max, n=N_WIRE_CLIENTS):
    """Upload-byte reduction of int8 vs fp32 over an n-client cohort
    (pure accounting -- no aggregation)."""
    updates, _ = make_cohort(n, SEED + 1, specs, r_max)
    plain = sum(tree_bytes(u.adapters) + tree_bytes(u.base_trainable)
                for u in updates)
    quant = sum(tree_bytes(codec.encode_adapters(u.adapters, "int8"))
                + tree_bytes(u.base_trainable) for u in updates)
    return plain / max(quant, 1), plain, quant


def retrace_check(updates, specs, r_max):
    """Warm two codec mixes, then alternate: the per-(width, dtype,
    codec-mix) plan cache must absorb every repeat -- zero new misses,
    zero new jitted executors."""
    s = get_strategy("rbla")
    n = len(updates)
    half = ["int8" if i % 2 else "bf16" for i in range(n)]
    mixes = [["int8"] * n, half]
    agg = AsyncAggregator(s, make_state(s, specs, r_max), buffer_size=n,
                          backend="ref")
    for mix in mixes:                                   # warm both
        for u, c in zip(updates, mix):
            agg.submit(codec.encode_update(u, c))
    strat = agg.strategy
    stats0 = dict(strat.__dict__.get("plan_stats", {}))
    execs0 = len(strat.__dict__.get("_plan_exec_cache", {}))
    for _ in range(2):                                  # alternate, warm
        for mix in mixes:
            for u, c in zip(updates, mix):
                agg.submit(codec.encode_update(u, c))
    stats1 = dict(strat.__dict__.get("plan_stats", {}))
    execs1 = len(strat.__dict__.get("_plan_exec_cache", {}))
    return {
        "new_plan_misses": stats1.get("misses", 0) - stats0.get("misses", 0),
        "new_executors": execs1 - execs0,
        "plan_hits": stats1.get("hits", 0) - stats0.get("hits", 0),
    }


def obs_overhead_check(updates, specs, r_max, iters=5):
    """The observability overhead gate: the same warm fold loop with
    metrics enabled must add zero jitted executors and no more than
    ``OBS_OVERHEAD_FRAC`` wall time (plus ``OBS_OVERHEAD_ABS_S`` noise
    slack) over metrics disabled.  Min-over-iters on both sides -- same
    1-vCPU-noise reasoning as every other timing here."""
    s = get_strategy("rbla")
    n = len(updates)

    def run():
        agg = AsyncAggregator(s, make_state(s, specs, r_max),
                              buffer_size=n, backend="ref")
        for _ in range(3):              # 3 flushes: a timeable region
            for u in updates:
                agg.submit(u)
        return agg.state.adapters

    prev = set_enabled(False)
    try:
        t_off = time_fn(run, iters=iters)
        set_enabled(True)
        execs0 = len(s.__dict__.get("_plan_exec_cache", {}))
        t_on = time_fn(run, iters=iters)
        execs1 = len(s.__dict__.get("_plan_exec_cache", {}))
    finally:
        set_enabled(prev)
    return {
        "t_disabled_ms": t_off * 1e3,
        "t_enabled_ms": t_on * 1e3,
        "overhead_frac": t_on / max(t_off, 1e-12) - 1.0,
        "new_executors": execs1 - execs0,
    }


# --------------------------------------------------------- crash recovery --
def recovery_check(updates, specs, r_max):
    """Durability leg: what the WAL + checkpoint layer costs per upload,
    what one snapshot and one crash recovery cost, and the chaos
    invariants the ``--smoke`` gate enforces -- redeliver every upload
    (zero double-folds), crash mid-stream and recover (bit-exact state),
    and recovery must reuse the warm fold executors (zero retraces: the
    registry strategy singleton keeps its plan cache across service
    incarnations)."""
    s = get_strategy("rbla")
    n = len(updates)
    ids = [f"u{i}" for i in range(n)]

    oracle = AsyncAggregator(s, make_state(s, specs, r_max), backend="ref")
    t0 = time.time()
    for u, uid in zip(updates, ids):
        oracle.submit(u, update_id=uid)
    jax.block_until_ready(jax.tree.leaves(oracle.state.adapters))
    plain_ms = (time.time() - t0) * 1e3 / n

    with tempfile.TemporaryDirectory() as d:
        agg = DurableAggregator(s, make_state(s, specs, r_max), dir=d,
                                checkpoint_every=0, wal_fsync=False,
                                backend="ref")
        cut = n // 2
        double_folds = 0
        t0 = time.time()
        for u, uid in zip(updates[:cut], ids[:cut]):
            v0 = agg.version
            agg.submit(u, update_id=uid)
            v1 = agg.version
            # at-least-once transport: redeliver every upload -- the
            # dedup window must fold it exactly once
            agg.submit(u, update_id=uid)
            double_folds += int(agg.version != v1 or v1 != v0 + 1)
        durable_ms = (time.time() - t0) * 1e3 / cut
        t0 = time.time()
        agg.checkpoint()
        checkpoint_ms = (time.time() - t0) * 1e3
        for u, uid in zip(updates[cut:], ids[cut:]):       # the WAL tail
            agg.submit(u, update_id=uid)
            agg.submit(u, update_id=uid)
        wal_bytes = agg.wal.bytes_written
        execs0 = len(s.__dict__.get("_plan_exec_cache", {}))
        agg.close()                                        # crash

        t0 = time.time()
        recovered = DurableAggregator(s, make_state(s, specs, r_max),
                                      dir=d, checkpoint_every=0,
                                      wal_fsync=False, backend="ref")
        restore_ms = (time.time() - t0) * 1e3
        execs1 = len(s.__dict__.get("_plan_exec_cache", {}))

    bit_exact = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(recovered.state.adapters),
                        jax.tree.leaves(oracle.state.adapters)))
    double_folds += int(recovered.version != oracle.version)
    return {
        "plain_fold_ms_per_update": plain_ms,
        "durable_fold_ms_per_update": durable_ms,
        "wal_overhead_frac": durable_ms / max(plain_ms, 1e-9) - 1.0,
        "checkpoint_ms": checkpoint_ms,
        "restore_ms": restore_ms,
        "n_replayed": recovered.n_replayed,
        "wal_bytes": wal_bytes,
        "bit_exact_recovery": bit_exact,
        "double_folds": double_folds,
        "new_executors": execs1 - execs0,
    }


def serving_chaos_check(specs, r_max):
    """No torn serving snapshots under publish failures: hot-swaps that
    raise must leave readers on the last committed snapshot (outputs
    bit-identical before/after the failed attempt), and the retried
    publish must land the newest pending tree."""
    from repro.serving import AdapterStore, ServingEngine

    rng = np.random.default_rng(SEED)
    store = AdapterStore(specs, r_max=r_max)
    weights = {p: jnp.asarray(rng.normal(size=(fi, fo)) * 0.1, jnp.float32)
               for p, (fo, fi) in specs.items()}
    eng = ServingEngine(weights, store, interpret=True)

    def tree(seed):
        ad = init_adapters(jax.random.PRNGKey(seed), specs, r_max, r_max)
        return jax.tree.map(
            lambda x: x + jnp.asarray(
                rng.normal(size=x.shape), x.dtype)
            if x.dtype == jnp.float32 else x, ad)

    eng.publish(tree(0))
    path = next(iter(specs))
    x = jnp.asarray(rng.normal(size=(4, specs[path][1])), jnp.float32)
    tid = jnp.zeros((4,), jnp.int32)
    y_before = eng.apply(path, x, tid)

    orig, broken = store.publish, {"on": True}

    def flaky_publish(t):
        if broken["on"]:
            raise RuntimeError("injected publish fault")
        return orig(t)

    store.publish = flaky_publish
    pub = eng.publisher(max_backoff=2)

    class _S:
        def __init__(self, adapters):
            self.adapters = adapters

    pub(_S(tree(1)))                       # fails -> quarantined
    y_during = eng.apply(path, x, tid)     # readers: last committed snap
    torn = not np.array_equal(np.asarray(y_before), np.asarray(y_during))
    failures = eng.n_publish_failures
    broken["on"] = False
    pub(_S(tree(2)))                       # backoff skip
    pub(_S(tree(3)))                       # retry lands the newest tree
    recovered = store.version == 2
    store.publish = orig
    return {"publish_failures": failures, "torn_snapshot": torn,
            "recovered_publish": recovered}


def main(argv=None):
    enable_compile_cache()
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--smoke", action="store_true",
                   help="tiny case + hard gates (CI)")
    p.add_argument("--json", metavar="PATH", default=None,
                   help="write machine-readable results (BENCH_async.json)")
    args = p.parse_args(argv)

    specs = SMOKE_SPECS if args.smoke else FULL_SPECS
    r_max = SMOKE_R_MAX if args.smoke else FULL_R_MAX
    n = 6 if args.smoke else N_CLIENTS
    updates, ranks = make_cohort(n, SEED, specs, r_max)
    weights = np.asarray([u.n_examples for u in updates])
    lat_model = ClientLatencyModel(n, median_s=30.0, sigma=0.25,
                                   straggler_sigma=1.0, seed=SEED)
    latencies = np.asarray([lat_model.sample(i) for i in range(n)])

    print(f"# cohort: n={n} clients, ranks {ranks.min()}.."
          f"{ranks.max()}, {len(specs)} pairs of {list(specs.values())[0]}"
          f" at r_max={r_max}")
    print(f"# latency: log-normal, median 30s, straggler_sigma 1.0 -> "
          f"min {latencies.min():.0f}s max {latencies.max():.0f}s")
    print("# method, sync_round_ms, async_fold_ms_per_update, "
          "t50_async_s, t90_async_s, t_sync_round_s, speedup_t90, "
          "wire_bytes_per_client")
    method_rows = []
    plain_wire = (tree_bytes(updates[0].adapters)
                  + tree_bytes(updates[0].base_trainable))
    for method in METHODS:
        t_sync, t_fold = bench_method(method, updates, specs, r_max)
        t50a, t90a, t_round = time_to_quality(latencies, weights,
                                              t_sync, t_fold)
        print(f"async_agg/{method},{t_sync * 1e3:.1f},{t_fold * 1e3:.1f},"
              f"{t50a:.1f},{t90a:.1f},{t_round:.1f},"
              f"{t_round / max(t90a, 1e-9):.2f}x,{plain_wire}")
        method_rows.append({"method": method, "sync_ms": t_sync * 1e3,
                            "fold_ms": t_fold * 1e3, "t90_async_s": t90a,
                            "t_sync_round_s": t_round,
                            "wire_bytes_per_client": plain_wire})

    print("# codec, wire_bytes_per_client, reduction_vs_fp32, "
          "parity_rel_err, flush_ms")
    codec_rows = bench_codecs(updates, specs, r_max)
    for row in codec_rows:
        print(f"async_agg/codec/{row['codec']},"
              f"{row['wire_bytes_per_client']},"
              f"{row['reduction_vs_fp32']:.2f}x,"
              f"{row['parity_rel_err']:.2e},{row['flush_ms']:.1f}")

    reduction, plain_b, quant_b = wire_reduction_at_scale(specs, r_max)
    print(f"# wire @ {N_WIRE_CLIENTS} clients: fp32 {plain_b} B, "
          f"int8 {quant_b} B -> {reduction:.2f}x reduction")
    retrace = retrace_check(updates, specs, r_max)
    print(f"# codec-mix alternation: {retrace['plan_hits']} plan hits, "
          f"{retrace['new_plan_misses']} new misses, "
          f"{retrace['new_executors']} new executors")
    obs_row = obs_overhead_check(updates, specs, r_max)
    print(f"# obs overhead: metrics off {obs_row['t_disabled_ms']:.1f}ms, "
          f"on {obs_row['t_enabled_ms']:.1f}ms "
          f"({obs_row['overhead_frac'] * 100:+.1f}%), "
          f"{obs_row['new_executors']} new executors")
    rec = recovery_check(updates, specs, r_max)
    print(f"# durability: fold {rec['plain_fold_ms_per_update']:.1f}ms -> "
          f"{rec['durable_fold_ms_per_update']:.1f}ms/update with WAL "
          f"({rec['wal_overhead_frac'] * 100:+.0f}%), checkpoint "
          f"{rec['checkpoint_ms']:.1f}ms, recover {rec['restore_ms']:.1f}ms "
          f"({rec['n_replayed']} replayed), bit_exact="
          f"{rec['bit_exact_recovery']}, double_folds={rec['double_folds']},"
          f" new_executors={rec['new_executors']}")
    serve_chaos = serving_chaos_check(specs, r_max)
    print(f"# publish chaos: {serve_chaos['publish_failures']} injected "
          f"failures, torn_snapshot={serve_chaos['torn_snapshot']}, "
          f"recovered_publish={serve_chaos['recovered_publish']}")

    if args.json:
        payload = bench_payload(
            "async_agg", smoke=bool(args.smoke),
            case={"specs": {k: list(v) for k, v in specs.items()},
                  "r_max": r_max, "n_clients": n,
                  "n_wire_clients": N_WIRE_CLIENTS},
            results={
                "methods": method_rows,
                "codecs": codec_rows,
                "wire_reduction_int8_at_scale": reduction,
                "retrace": retrace,
                "obs_overhead": obs_row,
                "recovery": rec,
                "serving_chaos": serve_chaos,
            })
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {args.json}")

    if args.smoke:
        failures = []
        for row in codec_rows:
            tol = CODEC_TOL[row["codec"]]
            if row["parity_rel_err"] > tol:
                failures.append(
                    f"{row['codec']} parity {row['parity_rel_err']:.2e} "
                    f"> tol {tol:g}")
        if reduction < WIRE_GATE_REDUCTION:
            failures.append(
                f"int8 wire reduction {reduction:.2f}x < "
                f"{WIRE_GATE_REDUCTION}x at {N_WIRE_CLIENTS} clients")
        if retrace["new_plan_misses"] or retrace["new_executors"]:
            failures.append(
                f"codec-mix alternation re-traced: "
                f"{retrace['new_plan_misses']} misses, "
                f"{retrace['new_executors']} executors")
        if obs_row["new_executors"]:
            failures.append(
                f"metrics-enabled fold loop added "
                f"{obs_row['new_executors']} jitted executors")
        allowed = (obs_row["t_disabled_ms"] * OBS_OVERHEAD_FRAC
                   + OBS_OVERHEAD_ABS_S * 1e3)
        if obs_row["t_enabled_ms"] - obs_row["t_disabled_ms"] > allowed:
            failures.append(
                f"metrics overhead {obs_row['overhead_frac'] * 100:.1f}% "
                f"(+{obs_row['t_enabled_ms'] - obs_row['t_disabled_ms']:.2f}"
                f"ms) past {OBS_OVERHEAD_FRAC * 100:.0f}% "
                f"+ {OBS_OVERHEAD_ABS_S * 1e3:.0f}ms")
        # chaos gate (docs/durability.md): exactly-once, bit-exact,
        # no torn serving, no recovery retraces
        if rec["double_folds"]:
            failures.append(
                f"{rec['double_folds']} redelivered uploads double-folded "
                "past the dedup window")
        if not rec["bit_exact_recovery"]:
            failures.append(
                "crash recovery diverged from the uninterrupted run "
                "(must be bit-exact for incremental strategies)")
        if rec["new_executors"]:
            failures.append(
                f"crash recovery re-traced {rec['new_executors']} fold "
                "executors (registry singleton must keep plans warm)")
        if serve_chaos["torn_snapshot"]:
            failures.append(
                "a failed publish tore the serving snapshot (readers must "
                "stay on the last committed version)")
        if not serve_chaos["recovered_publish"]:
            failures.append(
                "publish retry never landed after the fault cleared")
        if failures:
            for msg in failures:
                print(f"# SMOKE FAIL: {msg}")
            return 1
        print("# smoke gate OK: codec parity within tolerance, int8 wire "
              f"reduction >= {WIRE_GATE_REDUCTION}x, zero retraces on "
              "codec-mix alternation, metrics overhead within "
              f"{OBS_OVERHEAD_FRAC * 100:.0f}%, chaos gate clean "
              "(exactly-once, bit-exact recovery, no torn serving "
              "snapshots, zero recovery retraces)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
