"""Multi-tenant adapter serving throughput: one executable vs jit-per-adapter.

The FLaaS read path (``repro.serving``) packs every tenant's (A, B) pair
into the paged :class:`~repro.serving.AdapterStore` and serves a mixed
request batch with ONE launch of the batched multi-adapter kernel
(:func:`~repro.kernels.batched_lora_matmul`): adapter ids, offsets, ranks,
and scales are runtime data, so a single compiled executable covers every
tenant mix.  The baseline is what naive FLaaS serving does instead --
group the batch by tenant and run a **jit-per-adapter** LoRA matmul per
group (one dispatch per tenant present, one executable per distinct
(group size, rank) shape).

The bench runs the whole loop continuously: an
:class:`~repro.fl.AsyncAggregator` folds client updates (rbla), its
``on_publish`` hook hot-swaps each advanced global into the live store,
and serving keeps drawing mixed batches -- verifying along the way that
neither tenant-mix churn nor ``publish()`` ever retraces the serving
executable.

Reported per case:

* batched and per-tenant baseline requests/sec and the speedup,
* serving executable trace count across the run (must stay at its
  post-warmup value: the no-retrace gate),
* publish latency and the version delta across the run,
* batched-vs-reference numerical parity (the CI smoke gate).

``--smoke`` runs a reduced case and exits non-zero if parity breaks, the
speedup at 128 tenants falls under 4x, the serving executable retraces,
or a publish forces a recompile.  ``--json PATH`` writes the
machine-readable ``BENCH_serve.json``, headed by the environment it ran
in (``repro.obs.bench_payload``).
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ClientUpdate, ServerState
from repro.fl import AsyncAggregator
from repro.kernels import lora_matmul_ref
from repro.kernels.lora_matmul.ops import trace_counts
from repro.launch.cache import enable_compile_cache
from repro.lora import init_adapters, set_ranks
from repro.obs import bench_payload, block
from repro.serving import AdapterStore, ServingEngine, merged_reference

PATH = "proj"


def _pow2(v: int) -> int:
    return 1 << max(int(np.ceil(np.log2(max(v, 1)))), 0)


def build_rig(n_tenants, width, r_max, seed=0):
    """Store + engine with ``n_tenants`` heterogeneous-rank tenants, all
    serving re-slices of one global (the steady FLaaS state)."""
    rng = np.random.default_rng(seed)
    specs = {PATH: (width, width)}
    weights = {PATH: jnp.asarray(rng.normal(size=(width, width)) * 0.05,
                                 jnp.float32)}
    store = AdapterStore(specs, r_max=r_max,
                         init_pages=_pow2(n_tenants),
                         init_tenant_capacity=_pow2(n_tenants + 1))
    engine = ServingEngine(weights, store)
    ranks = rng.integers(1, r_max + 1, n_tenants)
    for t in range(n_tenants):
        store.register(f"tenant-{t}", rank=int(ranks[t]))
    glob = init_adapters(jax.random.PRNGKey(seed), specs, r_max, r_max)
    glob = jax.tree.map(
        lambda x: x + jnp.asarray(rng.normal(size=x.shape) * 0.1, x.dtype)
        if x.dtype == jnp.float32 else x, glob)
    engine.publish(glob)
    return store, engine, glob, ranks


def make_batches(n_batches, batch, width, n_tenants, seed=1):
    """Pre-drawn mixed request batches -- every batch a different tenant
    mix (ids are slots 1..n_tenants; slot 0 is the null adapter)."""
    rng = np.random.default_rng(seed)
    xs = [jnp.asarray(rng.normal(size=(batch, width)), jnp.float32)
          for _ in range(n_batches)]
    ids = [jnp.asarray(rng.integers(1, n_tenants + 1, batch), jnp.int32)
           for _ in range(n_batches)]
    return xs, ids


def bench_batched(engine, xs, ids, iters):
    """Requests/sec through the single batched executable."""
    y = engine.apply(PATH, xs[0], ids[0])          # compile / warm
    jax.block_until_ready(y)
    t0 = time.perf_counter()
    done = 0
    for it in range(iters):
        for x, i in zip(xs, ids):
            y = engine.apply(PATH, x, i)
            done += x.shape[0]
    jax.block_until_ready(y)
    return done / (time.perf_counter() - t0)


def bench_per_tenant(engine, xs, ids, iters):
    """The jit-per-adapter baseline: slice the batch per tenant and run
    one jitted single-adapter LoRA matmul per group.  Group sizes pad to
    powers of two so the jit cache warms to O(log batch x distinct
    ranks) executables instead of churning every batch."""
    snap = engine.snapshot()
    a_rows, b_rows = snap.pair_buffers(PATH)
    tbl = snap.table(PATH)
    off = np.asarray(tbl.off)
    rank = np.asarray(tbl.rank)
    scale = np.asarray(tbl.scale)
    w = engine.weights[PATH]
    per = jax.jit(lora_matmul_ref)

    def serve_batch(x, id_arr):
        id_np = np.asarray(id_arr)
        outs = []
        for t in np.unique(id_np):
            sel = np.nonzero(id_np == t)[0]
            xg = x[jnp.asarray(sel)]
            pad = _pow2(len(sel))
            xg = jnp.pad(xg, ((0, pad - len(sel)), (0, 0)))
            a_t = jax.lax.dynamic_slice_in_dim(a_rows, int(off[t]),
                                               int(rank[t])) \
                if rank[t] else a_rows[:1] * 0
            b_t = jax.lax.dynamic_slice_in_dim(b_rows, int(off[t]),
                                               int(rank[t])) \
                if rank[t] else b_rows[:1] * 0
            outs.append(per(xg, w, a_t, jnp.swapaxes(b_t, 0, 1),
                            float(scale[t]))[:len(sel)])
        return outs

    out = serve_batch(xs[0], ids[0])               # compile / warm
    jax.block_until_ready(out)
    for x, i in zip(xs, ids):                      # warm every shape
        jax.block_until_ready(serve_batch(x, i))
    t0 = time.perf_counter()
    done = 0
    for it in range(iters):
        for x, i in zip(xs, ids):
            out = serve_batch(x, i)
            done += x.shape[0]
    jax.block_until_ready(out)
    return done / (time.perf_counter() - t0)


def publish_loop(engine, store, glob, r_max, rounds, serve_fn):
    """aggregate -> publish -> serve continuously: fold client updates
    through an AsyncAggregator whose on_publish hook hot-swaps the live
    store; serve between folds.  Returns (mean publish seconds, versions
    advanced)."""
    state = ServerState(adapters=glob, base_trainable={}, r_max=r_max)
    agg = AsyncAggregator("rbla", state, backend="ref",
                          on_publish=engine.publisher())
    rng = np.random.default_rng(5)
    v0 = store.version
    t_pub = 0.0
    n_pub = 0
    width = glob[PATH]["A"].shape[-1]
    for rnd in range(rounds):
        r = int(rng.integers(1, r_max + 1))
        upd = init_adapters(jax.random.PRNGKey(100 + rnd),
                            {PATH: (width, width)}, r_max, r)
        upd = jax.tree.map(
            lambda x: x + jnp.asarray(rng.normal(size=x.shape) * 0.05,
                                      x.dtype)
            if x.dtype == jnp.float32 else x, upd)
        upd = set_ranks(upd, r)
        t0 = time.perf_counter()
        agg.submit(ClientUpdate(adapters=upd, base_trainable={},
                                n_examples=1.0, rank=r))
        block([b for pair in store.snapshot().buffers.values()
               for b in pair])
        t_pub += time.perf_counter() - t0
        n_pub += 1
        serve_fn()
    return t_pub / max(n_pub, 1), store.version - v0


def run_case(n_tenants, width, r_max, batch, n_batches, iters, rounds,
             tol):
    failures = []
    store, engine, glob, ranks = build_rig(n_tenants, width, r_max)
    xs, ids = make_batches(n_batches, batch, width, n_tenants)

    # parity vs the per-request reference before anything is timed
    got = engine.apply(PATH, xs[0], ids[0])
    want = merged_reference(engine, PATH, xs[0], ids[0])
    diff = float(jnp.abs(jnp.asarray(got, jnp.float32)
                         - want).max())
    scale_ref = max(float(jnp.abs(want).max()), 1e-12)
    rel = diff / scale_ref
    if rel > tol:
        failures.append(f"batched vs reference rel diff {rel:.2e} > "
                        f"tol {tol:.0e}")

    batched_rps = bench_batched(engine, xs, ids, iters)
    traces_mid = trace_counts.get("batched_lora_matmul", 0)
    per_tenant_rps = bench_per_tenant(engine, xs, ids, iters)

    # continuous aggregate -> publish -> serve; serving must not retrace
    idx = [0]

    def serve_once():
        x, i = xs[idx[0] % len(xs)], ids[idx[0] % len(ids)]
        jax.block_until_ready(engine.apply(PATH, x, i))
        idx[0] += 1

    publish_s, versions = publish_loop(engine, store, glob, r_max, rounds,
                                       serve_once)
    traces_end = trace_counts.get("batched_lora_matmul", 0)
    if traces_end != traces_mid:
        failures.append(
            f"serving retraced: {traces_mid} -> {traces_end} executables "
            "across tenant-mix churn + publishes")
    # post-publish parity: serving reflects the newest published global
    got2 = engine.apply(PATH, xs[0], ids[0])
    want2 = merged_reference(engine, PATH, xs[0], ids[0])
    rel2 = float(jnp.abs(jnp.asarray(got2, jnp.float32) - want2).max()) \
        / max(float(jnp.abs(want2).max()), 1e-12)
    if rel2 > tol:
        failures.append(f"post-publish rel diff {rel2:.2e} > {tol:.0e}")

    speedup = batched_rps / max(per_tenant_rps, 1e-9)
    row = {
        "case": {"n_tenants": n_tenants, "width": width, "r_max": r_max,
                 "batch": batch, "n_batches": n_batches,
                 "rank_multiset": sorted(int(v) for v in ranks)[:8]
                 + (["..."] if n_tenants > 8 else [])},
        "batched_rps": round(batched_rps, 1),
        "per_tenant_rps": round(per_tenant_rps, 1),
        "speedup": round(speedup, 2),
        "serving_traces": traces_end,
        "publish_ms": round(publish_s * 1e3, 2),
        "versions_published": versions,
        "parity_rel_diff": rel,
        "post_publish_rel_diff": rel2,
    }
    print(f"serve/batched/t{n_tenants}_w{width}_b{batch},"
          f"{1e6 / max(batched_rps, 1e-9) * batch:.0f},"
          f"{batched_rps:.0f}rps")
    print(f"serve/per_tenant/t{n_tenants}_w{width}_b{batch},"
          f"{1e6 / max(per_tenant_rps, 1e-9) * batch:.0f},"
          f"{per_tenant_rps:.0f}rps")
    print(f"serve/publish/t{n_tenants}_w{width},{publish_s * 1e6:.0f},"
          f"{versions}swaps")
    return row, failures


def main(argv=None):
    enable_compile_cache()
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--smoke", action="store_true",
                   help="reduced case + hard parity/speedup/no-retrace "
                        "gate (CI)")
    p.add_argument("--json", metavar="PATH", default=None,
                   help="write machine-readable results "
                        "(BENCH_serve.json)")
    p.add_argument("--tenants", type=int, default=None)
    p.add_argument("--width", type=int, default=None)
    p.add_argument("--batch", type=int, default=None)
    p.add_argument("--iters", type=int, default=3)
    p.add_argument("--rounds", type=int, default=4,
                   help="aggregate->publish->serve rounds")
    p.add_argument("--tol", type=float, default=5e-4,
                   help="max relative batched-vs-reference deviation")
    args = p.parse_args(argv)

    n_tenants = args.tenants or 128
    width = args.width or (128 if args.smoke else 512)
    batch = args.batch or (256 if args.smoke else 512)
    r_max = 8
    n_batches = 4 if args.smoke else 8

    row, failures = run_case(n_tenants, width, r_max, batch, n_batches,
                             args.iters, args.rounds, args.tol)
    summary = {
        "speedup_vs_jit_per_adapter": row["speedup"],
        "serving_traces": row["serving_traces"],
        "publish_ms": row["publish_ms"],
        "max_rel_diff": max(row["parity_rel_diff"],
                            row["post_publish_rel_diff"]),
    }
    print(f"# summary: {json.dumps(summary)}")

    if args.json:
        payload = bench_payload(
            "serve", smoke=bool(args.smoke),
            case=row["case"], results=row, summary=summary)
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {args.json}")

    if failures:
        for msg in failures:
            print(f"# SERVE GATE FAILURE: {msg}")
        raise SystemExit(1)
    if args.smoke:
        if n_tenants >= 128 and row["speedup"] < 4:
            print(f"# SERVE SPEEDUP GATE FAILURE: {row}")
            raise SystemExit(1)
        print("# smoke gate OK: batched==reference, "
              f">=4x over jit-per-adapter at {n_tenants} tenants, "
              "zero serving retraces across tenant mixes and publishes")


if __name__ == "__main__":
    main()
