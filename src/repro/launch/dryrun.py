import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# Multi-pod dry-run (deliverable e): lower + compile every
# (architecture x input shape) on the production meshes, record
# memory/cost/collective analysis for the roofline (deliverable g).
#
# The two lines above MUST precede any jax import: jax locks the device
# count at first init.  Do not move them.

import argparse          # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import ARCHS, INPUT_SHAPES, get_config       # noqa: E402
from repro.launch.mesh import make_pod_mesh                     # noqa: E402
from repro.lora import attach_ranks, strip_ranks                # noqa: E402
from repro.models.model import make_model                       # noqa: E402
from repro.optim import adam, apply_updates                     # noqa: E402
from repro.roofline.analysis import (active_params,             # noqa: E402
                                     collective_bytes_from_hlo,
                                     model_flops_estimate, Roofline,
                                     scan_correction)
from repro.sharding import rules                                # noqa: E402

DEFAULT_OUT = "benchmarks/artifacts/dryrun"


# ------------------------------------------------------------- skip rules ---
def skip_reason(cfg, shape) -> str | None:
    if shape.kind == "decode" and shape.name == "long_500k" and \
            not cfg.subquadratic:
        return ("pure full-attention architecture: long_500k requires "
                "sub-quadratic attention (DESIGN.md long_500k rule)")
    return None


# -------------------------------------------------------------- input specs -
def input_specs(cfg, shape, mesh):
    """ShapeDtypeStruct stand-ins for every model input (no allocation)."""
    b, s = shape.global_batch, shape.seq_len
    batch = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
    if cfg.is_encdec:
        batch["frames"] = jax.ShapeDtypeStruct(
            (b, cfg.encoder_seq, cfg.frontend_dim), jnp.float32)
    if cfg.frontend == "vision_patches":
        batch["patches"] = jax.ShapeDtypeStruct(
            (b, cfg.n_prefix_tokens, cfg.frontend_dim), jnp.float32)
    specs = rules.batch_specs(batch, mesh)
    return rules.shaped(batch, rules.to_shardings(specs, mesh))


def decode_input_specs(cfg, shape, mesh, model, seq_shard_model=False):
    b, s = shape.global_batch, shape.seq_len
    n_prefix = cfg.n_prefix_tokens if cfg.frontend == "vision_patches" else 0
    cache_shapes = jax.eval_shape(lambda: model.init_cache(b, s + n_prefix))
    cspecs = rules.cache_specs(cache_shapes, mesh, b,
                               seq_shard_model=seq_shard_model)
    caches = rules.shaped(cache_shapes, rules.to_shardings(cspecs, mesh))
    tok_spec = rules.batch_specs(
        {"t": jax.ShapeDtypeStruct((b,), jnp.int32)}, mesh)["t"]
    token = jax.ShapeDtypeStruct(
        (b,), jnp.int32,
        sharding=rules.to_shardings(tok_spec, mesh))
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    return caches, token, pos


def model_state_specs(cfg, mesh, model, fsdp=False):
    params_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pspecs = rules.param_specs(params_shapes, mesh, fsdp=fsdp)
    params = rules.shaped(params_shapes,
                          rules.to_shardings(pspecs, mesh))
    ad_shapes = jax.eval_shape(
        lambda k: model.init_adapters(k, rank=cfg.lora_r_max),
        jax.random.PRNGKey(1))
    aspecs = rules.adapter_specs(ad_shapes, mesh)
    adapters = rules.shaped(ad_shapes, rules.to_shardings(aspecs, mesh))
    return params, adapters, pspecs, aspecs


# ------------------------------------------------------------ step builders -
def build_train_step(model, cfg):
    opt = adam(1e-4)

    def train_step(params, adapters, opt_state, batch):
        factors, ranks = strip_ranks(adapters)

        def loss_fn(f):
            return model.loss(params, attach_ranks(f, ranks), batch)

        loss, grads = jax.value_and_grad(loss_fn)(factors)
        updates, opt_state = opt.update(grads, opt_state, factors)
        factors = apply_updates(factors, updates)
        return attach_ranks(factors, ranks), opt_state, loss

    return train_step, opt


def build_prefill_step(model):
    def prefill_step(params, adapters, batch):
        return model.prefill(params, adapters, batch)
    return prefill_step


def build_decode_step(model):
    def serve_step(params, adapters, caches, token, pos):
        return model.decode_step(params, adapters, caches, token, pos)
    return serve_step


# ------------------------------------------------------------------ runner --
def run_one(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
            remat: bool = True, mla_absorbed: bool = False,
            fsdp: bool = False, tag: str = "",
            cfg_overrides: dict | None = None,
            seq_shard_model: bool = False) -> dict:
    cfg = get_config(arch)
    if cfg_overrides:
        import dataclasses
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = INPUT_SHAPES[shape_name]
    mesh_name = "pod2" if multi_pod else "pod1"
    rec: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                 "kind": shape.kind, "remat": remat, "fsdp": fsdp,
                 "mla_absorbed": mla_absorbed, "tag": tag,
                 "cfg_overrides": cfg_overrides or {}}
    reason = skip_reason(cfg, shape)
    if reason:
        rec["skipped"] = reason
        _write(out_dir, rec, tag)
        print(f"[skip] {arch} x {shape_name} x {mesh_name}: {reason}")
        return rec

    mesh = make_pod_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    model = make_model(cfg, remat=remat, mla_absorbed=mla_absorbed)
    rec["remat"] = str(remat)
    t0 = time.time()
    with mesh:
        params, adapters, _, _ = model_state_specs(cfg, mesh, model,
                                                   fsdp=fsdp)
        if shape.kind == "train":
            step, opt = build_train_step(model, cfg)
            factors, _ = strip_ranks_shapes(adapters)
            opt_state = jax.eval_shape(opt.init, factors)
            ospecs = rules.adapter_specs(opt_state, mesh)
            opt_state = rules.shaped(
                opt_state, rules.to_shardings(ospecs, mesh))
            batch = input_specs(cfg, shape, mesh)
            lowered = jax.jit(step).lower(params, adapters, opt_state,
                                          batch)
        elif shape.kind == "prefill":
            step = build_prefill_step(model)
            batch = input_specs(cfg, shape, mesh)
            lowered = jax.jit(step).lower(params, adapters, batch)
        else:  # decode
            step = build_decode_step(model)
            caches, token, pos = decode_input_specs(
                cfg, shape, mesh, model, seq_shard_model=seq_shard_model)
            lowered = jax.jit(step).lower(params, adapters, caches, token,
                                          pos)
        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)

    cost = compiled.cost_analysis() or {}
    rec["flops_per_device"] = float(cost.get("flops", 0.0))
    rec["bytes_per_device"] = float(cost.get("bytes accessed", 0.0))
    try:
        mem = compiled.memory_analysis()
        rec["memory_analysis"] = {
            k: int(getattr(mem, k))
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes",
                      "alias_size_in_bytes")
            if hasattr(mem, k)}
    except Exception as e:  # CPU backend may not implement it
        rec["memory_analysis"] = {"error": str(e)}
    hlo = compiled.as_text()
    coll = collective_bytes_from_hlo(hlo)
    rec["collectives"] = coll

    n_active = active_params(cfg)
    mf = model_flops_estimate(cfg, shape, n_active, shape.kind)
    corr = scan_correction(cfg)
    rec["scan_correction"] = corr
    roof = Roofline(flops=rec["flops_per_device"] * corr,
                    hbm_bytes=rec["bytes_per_device"] * corr,
                    collective_bytes=float(sum(coll.values())) * corr,
                    chips=chips, model_flops=mf, collectives=coll)
    rec["roofline"] = roof.as_dict()
    _write(out_dir, rec, tag)
    print(f"[ok]   {arch} x {shape_name} x {mesh_name}"
          f" lower={rec['lower_s']}s compile={rec['compile_s']}s"
          f" dominant={roof.dominant}")
    return rec


def strip_ranks_shapes(adapters):
    """strip_ranks over ShapeDtypeStruct trees (no jnp ops involved)."""
    return strip_ranks(adapters)


def _write(out_dir, rec, tag=""):
    os.makedirs(out_dir, exist_ok=True)
    suffix = f"_{tag}" if tag else ""
    path = os.path.join(
        out_dir, f"{rec['arch']}_{rec['shape']}_{rec['mesh']}{suffix}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all",
                    help="arch id or 'all' (also accepts comma list)")
    ap.add_argument("--shape", default="all",
                    help="input shape or 'all' (comma list ok)")
    ap.add_argument("--mesh", default="both", choices=["pod1", "pod2",
                                                       "both"])
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--remat-policy", default="full",
                    choices=["full", "dots"])
    ap.add_argument("--cache-seq-shard", action="store_true")
    ap.add_argument("--mla-absorbed", action="store_true")
    ap.add_argument("--fsdp", action="store_true",
                    help="shard frozen base over data axes too")
    ap.add_argument("--tag", default="", help="artifact filename suffix")
    ap.add_argument("--set", default="", dest="overrides",
                    help="cfg overrides, e.g. capacity_factor=1.0,"
                         "n_experts=48")
    args = ap.parse_args()
    overrides = {}
    for kv in filter(None, args.overrides.split(",")):
        k, v = kv.split("=")
        overrides[k] = (float(v) if "." in v else int(v)) \
            if v.replace(".", "").lstrip("-").isdigit() else v

    archs = sorted(ARCHS) if args.arch == "all" else args.arch.split(",")
    shapes = (list(INPUT_SHAPES) if args.shape == "all"
              else args.shape.split(","))
    meshes = {"pod1": [False], "pod2": [True],
              "both": [False, True]}[args.mesh]

    failures = []
    for arch in archs:
        for shape in shapes:
            for multi_pod in meshes:
                try:
                    run_one(arch, shape, multi_pod, args.out,
                            remat=(False if args.no_remat
                                   else args.remat_policy),
                            mla_absorbed=args.mla_absorbed,
                            fsdp=args.fsdp, tag=args.tag,
                            cfg_overrides=overrides or None,
                            seq_shard_model=args.cache_seq_shard)
                except Exception:
                    failures.append((arch, shape, multi_pod))
                    print(f"[FAIL] {arch} x {shape} x "
                          f"{'pod2' if multi_pod else 'pod1'}")
                    traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} dry-run combos failed: "
                         f"{failures}")
    print("all dry-run combos compiled")


if __name__ == "__main__":
    main()
