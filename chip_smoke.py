#!/usr/bin/env python3
"""One FLaaS round on a TPU, end to end, at h2o-danube-3-4b width.

    python chip_smoke.py                # one chip: every phase below
    python chip_smoke.py --four-chips   # one rbla round on a 4-chip mesh

Phases, in order, in this one process (a chip serves one process):

device   exit non-zero unless JAX's first device is a TPU
client   3 local LoRA steps through ``repro.launch.train --preset full``
         (24 layers, d_model 3840, every q/k/v/o/gate/up/down adapted)
release  drop the launcher's bf16 base weights before the cohort lands
sync     a cohort of 8 full-target-set uploads at ranks {8,8,16,16,32,32,
         64,64} (one is the trained adapter) over a previous global:
         rbla on f32 uploads, rbla on int8 uploads, flora, rbla_median,
         each through ``aggregate_adapters(backend="pallas",
         interpret=False)`` and compared with ``backend="ref"``
async    ``AsyncAggregator`` folds the same uploads one at a time
         (``axpy_fold``); rbla's fold is exact, so it must match sync rbla
serve    layer 0 of the published global into an ``AdapterStore`` with
         tenants at ranks 8-64; mixed-tenant batches through
         ``batched_lora_matmul`` (``impl="pallas"``) against
         ``merged_reference``

Every phase prints its parity, the kernel launches its plan made and the
device's ``peak_bytes_in_use``.  A phase that fails its parity, or a
round that should have run a Pallas kernel and ran none, stops the
script with a non-zero exit.  The last line of a passing run is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}, ...}``.

``--four-chips`` runs only the rbla cohort over a 4-chip client mesh
through ``aggregate_adapters(backend="distributed", mesh=...)`` (the
shard_map masked psum) against ``backend="ref"``, and prints which
clients' rows of each packed buffer the collective received on each
chip.

Weights and uploads are random, made from ``--seed``.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

ARCH = "h2o-danube-3-4b"
#: cohort ranks as fractions of the architecture's lora_r_max (64):
#: {8, 8, 16, 16, 32, 32, 64, 64}; client 2 is the trained upload
RANK_EIGHTHS = (1, 1, 2, 2, 4, 4, 8, 8)
TRAINED = 2
#: serving tenants' ranks in eighths of r_max: 8, 16, 24, 32, 48, 64
TENANT_EIGHTHS = (1, 2, 3, 4, 6, 8)
#: the repo's plan parity tolerance (tests/_cohorts.assert_trees_close)
# and the serving kernel's f32 tolerance (tests/test_serving.py)
PLAN_TOL = dict(rtol=1e-4, atol=1e-5)
SERVE_TOL = dict(rtol=1e-5, atol=1e-5)


class SmokeFailure(RuntimeError):
    """A phase produced a wrong result or skipped its kernel."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def device_info():
    """(platform, device_kind, count) of the devices JAX sees."""
    import jax
    devs = jax.devices()
    return devs[0].platform, devs[0].device_kind, len(devs)


def report(phase: str, **fields) -> dict:
    """One line per phase: its fields plus the device's peak bytes."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    fields["peak_bytes_in_use"] = stats.get("peak_bytes_in_use", "n/a")
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)
    return fields


def parity(got, want, *, rtol: float, atol: float):
    """(max |got - want|, max |got - want| / (atol + rtol |want|)) over
    every leaf; a ratio <= 1 is inside the tolerance.  Non-finite output
    or a different tree structure fails outright."""
    import jax
    import jax.numpy as jnp
    lg, tg = jax.tree.flatten(got)
    lw, tw = jax.tree.flatten(want)
    check(tg == tw, f"tree structure differs: {tg} vs {tw}")
    worst_abs = worst_ratio = 0.0
    for x, y in zip(lg, lw):
        x = jnp.asarray(x, jnp.float32)
        y = jnp.asarray(y, jnp.float32)
        check(bool(jnp.all(jnp.isfinite(x))), "non-finite output")
        d = jnp.abs(x - y)
        worst_abs = max(worst_abs, float(jnp.max(d)))
        worst_ratio = max(worst_ratio,
                          float(jnp.max(d / (atol + rtol * jnp.abs(y)))))
    return worst_abs, worst_ratio


def synthetic_upload(model, key, rank: int):
    """An upload of the full target set: ``Model.init_adapters`` at
    ``rank`` plus seeded noise on B's live columns (B inits to zero)."""
    import itertools

    import jax
    from repro.lora import mask_pair, tree_map_pairs
    pair_ix = itertools.count()

    def noisy(pair):
        k = jax.random.fold_in(key, next(pair_ix))
        b = pair["B"] + 0.01 * jax.random.normal(k, pair["B"].shape,
                                                 pair["B"].dtype)
        return mask_pair({"A": pair["A"], "B": b, "rank": pair["rank"]})
    return tree_map_pairs(noisy, model.init_adapters(key, rank=rank))


def make_cohort(model, r_max: int, seed: int, trained=None):
    """Eight uploads, their ranks and weights, and a previous global."""
    import jax
    import numpy as np
    ranks = [r_max * e // 8 for e in RANK_EIGHTHS]
    key = jax.random.PRNGKey(seed)
    uploads = [trained if (i == TRAINED and trained is not None)
               else synthetic_upload(model, jax.random.fold_in(key, i), r)
               for i, r in enumerate(ranks)]
    prev = synthetic_upload(model, jax.random.fold_in(key, 99), r_max)
    w = np.random.default_rng(seed).uniform(0.5, 2.0, len(ranks))
    return uploads, ranks, w.astype(np.float32), prev


def sync_round(tag, name, uploads, w, ranks, prev, *, r_max, interpret,
               require_kernels, options=None):
    """One round on the pallas backend against the same round on ref."""
    import jax
    import jax.numpy as jnp
    from repro.core import get_strategy
    options = options or {}
    kw = dict(r_max=r_max, client_ranks=jnp.asarray(ranks),
              prev_global=prev)
    t0 = time.perf_counter()
    strategy = get_strategy(name).with_options(**options)
    got = strategy.aggregate_adapters(uploads, w, backend="pallas",
                                      interpret=interpret, **kw)
    jax.block_until_ready(got)
    rd = next(r for r in strategy.__dict__["_plan_cache"].values()
              if r.spec.kind == "pallas")
    del strategy                        # its plan and packed buffers
    with jax.default_matmul_precision("highest"):
        want = get_strategy(name).with_options(**options).aggregate_adapters(
            uploads, w, backend="ref", **kw)
    err, ratio = parity(got, want, **PLAN_TOL)
    del want
    fields = report(tag, parity_max_abs=err, parity_ratio=ratio,
                    plan=rd.kind, launches=rd.n_kernel_launches,
                    pallas_launches=rd.n_pallas_launches,
                    fallback_pairs=rd.n_fallback_pairs,
                    wall_s=round(time.perf_counter() - t0, 1))
    check(ratio <= 1.0, f"{tag}: pallas vs ref outside tolerance")
    if require_kernels:
        check(bool(rd.n_pallas_launches), f"{tag}: no Pallas kernel ran")
    return got, fields


def run_single_chip(*, preset: str, seq: int, seed: int, interpret: bool,
                    impl: str, require_kernels: bool) -> dict:
    """Every one-chip phase; returns the per-phase fields."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.core import ClientUpdate, ServerState, codec
    from repro.fl import AsyncAggregator
    from repro.launch import train
    from repro.models.model import make_model
    from repro.obs import get_registry
    from repro.serving import AdapterStore, ServingEngine, merged_reference

    phases = {}
    cfg = get_config(ARCH)
    if preset == "reduced":
        cfg = cfg.reduced()
    r_max = cfg.lora_r_max
    model = make_model(cfg)

    # ---- client: local LoRA steps through the training launcher -------
    t0 = time.perf_counter()
    trained = train.main(["--arch", ARCH, "--preset", preset, "--steps",
                          "3", "--batch", "1", "--seq", str(seq), "--rank",
                          str(r_max // 4), "--method", "rbla",
                          "--agg-backend", "pallas"])
    jax.block_until_ready(trained)
    phases["client"] = report("client", steps=3, seq=seq, rank=r_max // 4,
                              wall_s=round(time.perf_counter() - t0, 1))

    # ---- release: the base weights go before the cohort lands ----------
    gc.collect()
    jax.clear_caches()
    live = sum(x.nbytes for x in jax.live_arrays())
    phases["release"] = report("release", live_bytes=live)
    check(live < 2 * 2 ** 30, f"release: {live} bytes still live")

    # ---- sync server ---------------------------------------------------
    uploads, ranks, w, prev = make_cohort(model, r_max, seed, trained)
    del trained
    common = dict(r_max=r_max, interpret=interpret,
                  require_kernels=require_kernels)
    g_rbla, phases["sync_rbla_f32"] = sync_round(
        "sync rbla f32", "rbla", uploads, w, ranks, prev, **common)
    # later rounds' results are dropped at once: HBM holds the cohort
    # three times over (uploads, stacked, packed) while a round runs
    enc = [codec.encode_adapters(u, "int8") for u in uploads]
    phases["sync_rbla_int8"] = sync_round(
        "sync rbla int8", "rbla", enc, w, ranks, prev, **common)[1]
    del enc
    phases["sync_flora"] = sync_round(
        "sync flora", "flora", uploads, w, ranks, prev,
        options={"stack_r_cap": 6 * r_max}, **common)[1]
    phases["sync_rbla_median"] = sync_round(
        "sync rbla_median", "rbla_median", uploads, w, ranks, prev,
        **common)[1]

    # ---- async: one fold per upload --------------------------------------
    t0 = time.perf_counter()
    folds = get_registry().get("kernel_dispatches_total").labels(
        entry="axpy_fold")
    before = folds.value
    service = AsyncAggregator(
        "rbla", ServerState(adapters=prev, base_trainable={}, r_max=r_max),
        staleness="constant", backend="pallas", interpret=interpret)
    for u, wi, ri in zip(uploads, w, ranks):
        service.submit(ClientUpdate(adapters=u, base_trainable={},
                                    n_examples=float(wi), rank=ri))
    folded = service.state.adapters
    jax.block_until_ready(folded)
    err, ratio = parity(folded, g_rbla, **PLAN_TOL)
    launches = int(folds.value - before)
    phases["async"] = report("async fold", parity_max_abs=err,
                             parity_ratio=ratio, folds=service.n_folded,
                             axpy_fold_launches=launches,
                             wall_s=round(time.perf_counter() - t0, 1))
    check(ratio <= 1.0, "async: fold differs from the sync rbla round")
    check(service.n_folded == len(uploads), "async: not every upload folded")
    if require_kernels:
        check(launches > 0, "async: no axpy_fold kernel ran")
    del service, folded, uploads

    # ---- publish and serve layer 0 of the global --------------------------
    t0 = time.perf_counter()
    blk = g_rbla["stages"][0]["b0"]
    layer0 = {p: {"A": v["A"][0], "B": v["B"][0], "rank": v["rank"][0]}
              for p, v in blk.items()}
    specs = {p: (v["B"].shape[0], v["A"].shape[1])
             for p, v in layer0.items()}
    store = AdapterStore(specs, r_max=r_max)
    slots = [store.register(f"tenant{i}", rank=r_max * e // 8)
             for i, e in enumerate(TENANT_EIGHTHS)]
    key = jax.random.PRNGKey(seed + 1)
    weights = {p: jax.random.normal(jax.random.fold_in(key, i), (fi, fo))
               * fi ** -0.5 for i, (p, (fo, fi)) in enumerate(specs.items())}
    engine = ServingEngine(weights, store, impl=impl, interpret=interpret)
    version = engine.publish(layer0)
    dispatches = get_registry().get("kernel_dispatches_total").labels(
        entry="batched_lora_matmul")
    before = dispatches.value
    rng = np.random.default_rng(seed)
    worst_abs = worst_ratio = 0.0
    for b in range(3):
        ids = jnp.asarray(rng.choice([0] + slots, 16), jnp.int32)
        for i, (p, (fo, fi)) in enumerate(specs.items()):
            x = jax.random.normal(jax.random.fold_in(key, 100 * b + i),
                                  (16, fi))
            y = engine.apply(p, x, ids)
            want = merged_reference(engine, p, x, ids)
            err, ratio = parity(y, want, **SERVE_TOL)
            worst_abs, worst_ratio = max(worst_abs, err), max(worst_ratio,
                                                              ratio)
    launches = int(dispatches.value - before)
    phases["serve"] = report("serve", parity_max_abs=worst_abs,
                             parity_ratio=worst_ratio, store_version=version,
                             tenants=len(slots), batches=3,
                             batched_lora_matmul_launches=launches,
                             wall_s=round(time.perf_counter() - t0, 1))
    check(worst_ratio <= 1.0, "serve: kernel differs from merged_reference")
    check(launches == 3 * len(specs), "serve: kernel launches missing")
    return phases


def run_four_chips(*, preset: str, seed: int, interpret: bool) -> dict:
    """The rbla cohort over a 4-chip client mesh against ``ref``, through
    ``aggregate_adapters(backend="distributed", mesh=...)`` as a user
    calls it; prints where the plan's packed client buffers -- what its
    shard_map collective receives -- sit on the chips."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from repro.configs import get_config
    from repro.core import get_strategy
    from repro.models.model import make_model

    devs = jax.devices()
    check(len(devs) == 4, f"--four-chips needs 4 devices, have {len(devs)}")
    cfg = get_config(ARCH)
    if preset == "reduced":
        cfg = cfg.reduced()
    r_max = cfg.lora_r_max
    uploads, ranks, w, prev = make_cohort(make_model(cfg), r_max, seed)
    homes = sorted({d.id for u in uploads for x in jax.tree.leaves(u)
                    for d in x.sharding.device_set})
    print(f"[four-chip placement] uploads on devices {homes}", flush=True)

    t0 = time.perf_counter()
    mesh = Mesh(np.asarray(devs), ("clients",))
    strategy = get_strategy("rbla").with_options()
    got = strategy.aggregate_adapters(
        uploads, w, r_max=r_max, client_ranks=jnp.asarray(ranks),
        prev_global=prev, backend="distributed", mesh=mesh,
        client_axis="clients", interpret=interpret)
    jax.block_until_ready(got)
    rd = next(r for r in strategy.__dict__["_plan_cache"].values()
              if r.spec.kind == "distributed")
    check(rd.input_shardings is not None, "four-chip: no packed inputs seen")
    for bi, (shape, sharding) in enumerate(rd.input_shardings):
        where = sorted((d.id, idx[0].start or 0, idx[0].stop or shape[0])
                       for d, idx in sharding.devices_indices_map(
                           shape).items())
        print(f"[four-chip placement] bucket {bi} {shape} " + " ".join(
            f"chip{c}:clients[{a}:{b}]" for c, a, b in where), flush=True)
        check(len({c for c, _, _ in where}) == 4
              and all(b - a == shape[0] // 4 for _, a, b in where),
              f"four-chip: bucket {bi} is not split over the 4 chips")
    out = jax.tree.leaves(got)[0]
    print(f"[four-chip placement] output on devices "
          f"{sorted(d.id for d in out.sharding.device_set)}", flush=True)
    per_device = [(d.memory_stats() or {}).get("peak_bytes_in_use", "n/a")
                  for d in devs]
    got = jax.device_put(got, devs[0])
    del strategy
    with jax.default_matmul_precision("highest"):
        want = get_strategy("rbla").with_options().aggregate_adapters(
            uploads, w, r_max=r_max, client_ranks=jnp.asarray(ranks),
            prev_global=prev, backend="ref")
    err, ratio = parity(got, want, **PLAN_TOL)
    fields = report("four-chip rbla", parity_max_abs=err,
                    parity_ratio=ratio, plan=rd.kind,
                    launches=rd.n_kernel_launches,
                    peak_bytes_per_device=per_device,
                    wall_s=round(time.perf_counter() - t0, 1))
    check(ratio <= 1.0, "four-chip: distributed vs ref outside tolerance")
    return {"four_chip_rbla": fields}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the distributed rbla round on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    platform, kind, count = device_info()
    print(f"device: platform={platform} kind={kind} count={count}",
          flush=True)
    if platform != "tpu":
        print("chip_smoke: no TPU found; this script measures nothing on "
              f"{platform}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    try:
        if args.four_chips:
            phases = run_four_chips(preset="full", seed=args.seed,
                                    interpret=False)
        else:
            phases = run_single_chip(preset="full", seq=512, seed=args.seed,
                                     interpret=False, impl="pallas",
                                     require_kernels=True)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {"platform": platform,
                                             "kind": kind, "count": count},
                      "phases": phases}, default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main())
