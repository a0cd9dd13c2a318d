"""Server aggregation throughput: compiled plans vs the per-leaf path.

One FL round used to walk the adapter tree in Python, issuing one device
computation (two Pallas launches) per LoRA pair -- O(pairs x clients)
host dispatch.  The compiled :class:`~repro.core.plan.CompiledRound`
packs the cohort into (width, dtype) buckets and lowers the whole round
into one jitted call with one fused launch per bucket.  This bench
measures both paths on a transformer-sized adapter tree with a mixed-rank
cohort and reports, per strategy x backend:

* round latency (legacy vs plan) and the speedup,
* tracked dispatches per round (legacy pallas: 2 x pairs; plan: 1 call)
  and the reduction factor,
* plan-cache hit rate and the plan's fused-launch count,
* a plan-vs-legacy numerical parity check (the CI smoke gate).

A separate **svd leg** gates the factored low-rank engine
(``repro.core.lowrank``): at (m, n, sum r) = (768, 768, 32) the
strategy's factored path must match the explicit dense fallback in
product space and beat it by >= 5x wall-clock on CPU.

``--json PATH`` writes the machine-readable ``BENCH_agg.json`` so the
perf trajectory is tracked across PRs; ``--smoke`` runs a tiny case and
exits non-zero if the plan path and the legacy shim disagree beyond
tolerance, the dispatch reduction falls under 5x, the factored svd
speedup falls under 5x, or the plan path is slower than the legacy shim
(geomean speedup < 1.0) on any backend -- the plan is only worth its
complexity if it wins everywhere it claims to.
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import get_strategy, list_strategies
from repro.core.plan import dispatch_counter
from repro.launch.cache import enable_compile_cache
from repro.lora import init_adapters, set_ranks
from repro.obs import bench_payload, time_fn

BENCH_METHODS = ("rbla", "zeropad", "fedavg", "rbla_ranked", "flora",
                 "svd", "rbla_clipped", "rbla_trimmed", "rbla_median")

#: the factored-SVD gate case: min(m, n) = 768 >= 8 * sum(ranks) = 256,
#: where the dense O(m*n*min(m,n)) SVD is far off the factored
#: O((m+n)*k^2 + k^3) engine -- the smoke gate requires >= 5x
SVD_GATE_SPECS = {"proj": (768, 768)}
SVD_GATE_CLIENTS = 4
SVD_GATE_RANK = 8                      # sum(r_i) = 32

#: transformer-sized adapter tree: {path: (fan_out, fan_in)}
FULL_SPECS = {
    "attn_q": (512, 512), "attn_k": (512, 512), "attn_v": (512, 512),
    "attn_o": (512, 512), "mlp_up": (2048, 512), "mlp_gate": (2048, 512),
    "mlp_down": (512, 2048), "head": (512, 512),
}
SMOKE_SPECS = {"fc1": (24, 16), "fc2": (16, 24), "fc3": (24, 16),
               "fc4": (16, 24)}


def build_cohort(specs, n, r_max, seed=0):
    """n clients, mixed ranks in [1, r_max], both factors randomized."""
    rng = np.random.default_rng(seed)
    ranks = rng.integers(1, r_max + 1, n)
    keys = jax.random.split(jax.random.PRNGKey(seed), n)
    cohort = []
    for i in range(n):
        ad = init_adapters(keys[i], specs, r_max, int(ranks[i]))
        ad = jax.tree.map(
            lambda x: x + jnp.asarray(rng.normal(size=x.shape) * 0.1,
                                      x.dtype)
            if x.dtype == jnp.float32 else x, ad)
        cohort.append(set_ranks(ad, int(ranks[i])))
    w = jnp.asarray(rng.uniform(0.5, 2.0, n), jnp.float32)
    return cohort, jnp.asarray(ranks, jnp.int32), w


def bench_us(fn, iters=3):
    # min-over-iters timing lives in repro.obs.timing now; this shim
    # just converts to the microseconds the report rows use
    return time_fn(fn, iters=iters, reduce="min") * 1e6


def count_dispatches(fn):
    dispatch_counter.reset()
    out = fn()
    jax.block_until_ready(out)
    return dispatch_counter.reset(), out


def max_abs_diff(a, b):
    return max((float(jnp.max(jnp.abs(
        jnp.asarray(x, jnp.float32) - jnp.asarray(y, jnp.float32))))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))),
        default=0.0)


def configured(method, ranks, r_max):
    # always a with_options copy: each strategy x backend row gets its
    # own (empty) plan cache, so the reported hit/miss stats are per-row
    # rather than contaminated across rows / earlier in-process use
    s = get_strategy(method)
    if s.rank_contract == "stacked":
        return s.with_options(
            stack_r_cap=int(np.asarray(ranks).sum()) + r_max)
    return s.with_options()


def run_case(specs, n, r_max, iters, tol):
    cohort, ranks, w = build_cohort(specs, n, r_max)
    results, failures = [], []
    for method in BENCH_METHODS:
        for backend in ("ref", "pallas"):
            s = configured(method, ranks, r_max)

            def legacy():
                return s.aggregate_adapters(
                    cohort, w, r_max=r_max, client_ranks=ranks,
                    backend=backend, use_plan=False)

            def plan():
                return s.aggregate_adapters(
                    cohort, w, r_max=r_max, client_ranks=ranks,
                    backend=backend)

            legacy_disp, legacy_out = count_dispatches(legacy)
            plan_disp, plan_out = count_dispatches(plan)
            diff = max_abs_diff(legacy_out, plan_out)
            legacy_us = bench_us(legacy, iters)
            plan_us = bench_us(plan, iters)
            rounds = list(s.__dict__.get("_plan_cache", {}).values())
            rd = next(r for r in rounds if r.spec.kind == backend)
            stats = dict(s.__dict__.get("plan_stats",
                                        {"hits": 0, "misses": 0}))
            row = {
                "strategy": method, "backend": backend,
                "legacy_us": round(legacy_us, 1),
                "plan_us": round(plan_us, 1),
                "speedup": round(legacy_us / max(plan_us, 1e-9), 2),
                "legacy_dispatches": legacy_disp or None,
                "plan_dispatches": plan_disp,
                "dispatch_reduction": (
                    round(legacy_disp / max(plan_disp, 1), 1)
                    if legacy_disp else None),
                "plan_kind": rd.kind,
                "kernel_launches": rd.n_kernel_launches,
                "fallback_pairs": rd.n_fallback_pairs,
                "plan_cache": stats,
                "max_abs_diff": diff,
            }
            results.append(row)
            mode = ("pallas" if jax.default_backend() in ("tpu", "gpu")
                    else "pallas-interpret") if backend == "pallas" \
                else "core-ref"
            print(f"agg/{method}/{backend}/n{n}_r{r_max}_p{len(specs)},"
                  f"{plan_us:.0f},plan-{mode}")
            print(f"agg/{method}/{backend}/n{n}_r{r_max}_p{len(specs)},"
                  f"{legacy_us:.0f},legacy-{mode}")
            if diff > tol:
                failures.append(
                    f"{method}/{backend}: plan vs legacy diff {diff:.2e} "
                    f"> tol {tol:.0e}")
    return results, failures


def run_svd_factored_case(iters, tol):
    """The lowrank-engine leg: the svd strategy's factored path vs the
    explicit dense fallback at (m, n, sum r) = (768, 768, 32).

    Gates (hard in ``--smoke``): the served products must agree (factors
    are only unique up to the truncation basis, so parity is checked in
    product space) and the factored round must be >= 5x faster than the
    dense one on CPU.
    """
    rng = np.random.default_rng(7)
    cohort = []
    keys = jax.random.split(jax.random.PRNGKey(7), SVD_GATE_CLIENTS)
    for i in range(SVD_GATE_CLIENTS):
        ad = init_adapters(keys[i], SVD_GATE_SPECS, SVD_GATE_RANK,
                           SVD_GATE_RANK)
        ad = jax.tree.map(
            lambda x: x + jnp.asarray(rng.normal(size=x.shape) * 0.1,
                                      x.dtype)
            if x.dtype == jnp.float32 else x, ad)
        cohort.append(ad)
    ranks = jnp.full((SVD_GATE_CLIENTS,), SVD_GATE_RANK, jnp.int32)
    w = jnp.asarray(rng.uniform(0.5, 2.0, SVD_GATE_CLIENTS), jnp.float32)
    factored = get_strategy("svd").with_options()          # auto->factored
    dense = get_strategy("svd").with_options(svd_method="dense")

    def run(s):
        return s.aggregate_adapters(cohort, w, r_max=SVD_GATE_RANK,
                                    client_ranks=ranks, backend="ref")

    out_f = run(factored)
    out_d = run(dense)
    # product-space parity, normalized by the served update's own scale
    delta_f = np.asarray(out_f["proj"]["B"], np.float32) @ np.asarray(
        out_f["proj"]["A"], np.float32)
    delta_d = np.asarray(out_d["proj"]["B"], np.float32) @ np.asarray(
        out_d["proj"]["A"], np.float32)
    scale = max(float(np.abs(delta_d).max()), 1e-12)
    rel_diff = float(np.abs(delta_f - delta_d).max()) / scale
    factored_us = bench_us(lambda: run(factored), iters)
    dense_us = bench_us(lambda: run(dense), iters)
    speedup = dense_us / max(factored_us, 1e-9)
    m, n = next(iter(SVD_GATE_SPECS.values()))
    k = SVD_GATE_CLIENTS * SVD_GATE_RANK
    print(f"agg/svd_factored/m{m}_n{n}_k{k},{factored_us:.0f},"
          "lowrank-factored")
    print(f"agg/svd_dense/m{m}_n{n}_k{k},{dense_us:.0f},dense-fallback")
    row = {
        "case": {"m": m, "n": n, "sum_ranks": k},
        "dense_us": round(dense_us, 1),
        "factored_us": round(factored_us, 1),
        "speedup": round(speedup, 2),
        "product_rel_diff": rel_diff,
    }
    failures = []
    if rel_diff > tol:
        failures.append(
            f"svd factored-vs-dense product diff {rel_diff:.2e} > "
            f"tol {tol:.0e}")
    return row, failures


def main(argv=None):
    enable_compile_cache()
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--smoke", action="store_true",
                   help="tiny case + hard parity/dispatch gate (CI)")
    p.add_argument("--json", metavar="PATH", default=None,
                   help="write machine-readable results (BENCH_agg.json)")
    p.add_argument("--iters", type=int, default=3)
    p.add_argument("--tol", type=float, default=5e-4,
                   help="max abs plan-vs-legacy deviation tolerated")
    args = p.parse_args(argv)

    specs = SMOKE_SPECS if args.smoke else FULL_SPECS
    n = 6 if args.smoke else 32
    r_max = 8 if args.smoke else 32
    print(f"# registered strategies: {','.join(list_strategies())}")
    results, failures = run_case(specs, n, r_max, args.iters, args.tol)
    svd_row, svd_failures = run_svd_factored_case(args.iters, args.tol)
    failures += svd_failures

    pallas_rows = [r for r in results
                   if r["backend"] == "pallas" and r["dispatch_reduction"]]
    ref_rows = [r for r in results if r["backend"] == "ref"]
    # per-backend geomean of plan-vs-legacy speedups: the regression
    # gate -- a plan that loses to the per-leaf shim anywhere is a bug
    backend_speedup = {
        b: round(float(np.exp(np.mean(np.log(
            [r["speedup"] for r in results if r["backend"] == b])))), 2)
        for b in ("ref", "pallas")}
    summary = {
        "min_dispatch_reduction": min(
            (r["dispatch_reduction"] for r in pallas_rows), default=None),
        "mean_ref_wall_clock_speedup": round(float(np.mean(
            [r["speedup"] for r in ref_rows])), 2) if ref_rows else None,
        "plan_speedup_by_backend": backend_speedup,
        "max_abs_diff": max(r["max_abs_diff"] for r in results),
        "svd_factored_speedup": svd_row["speedup"],
    }
    print(f"# summary: {json.dumps(summary)}")

    if args.json:
        # shared payload shape (env header + obs snapshot) keeps this
        # file comparable with BENCH_serve.json runs from other machines
        payload = bench_payload(
            "agg_throughput", smoke=bool(args.smoke),
            case={"n_clients": n, "r_max": r_max, "n_pairs": len(specs)},
            results=results, svd_factored=svd_row, summary=summary)
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {args.json}")

    if failures:
        for msg in failures:
            print(f"# PARITY FAILURE: {msg}")
        raise SystemExit(1)
    if args.smoke:
        bad = [r for r in pallas_rows if r["dispatch_reduction"] < 5]
        if bad:
            print(f"# DISPATCH GATE FAILURE: {bad}")
            raise SystemExit(1)
        if svd_row["speedup"] < 5:
            print(f"# SVD FACTORED GATE FAILURE: {svd_row}")
            raise SystemExit(1)
        slow = {b: v for b, v in backend_speedup.items() if v < 1.0}
        if slow:
            print("# PLAN SPEEDUP GATE FAILURE: plan slower than legacy "
                  f"on {slow}")
            raise SystemExit(1)
        print("# smoke gate OK: plan==shim within tolerance, "
              "dispatch reduction >= 5x, factored svd >= 5x over dense, "
              "plan >= legacy on every backend")


if __name__ == "__main__":
    main()
