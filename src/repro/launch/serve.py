"""Serving launcher: batched prefill + decode with sharded KV caches.

Identical code path to the decode dry-run; --preset reduced runs it on
one CPU device, --preset full at published widths on a mesh over the
devices present.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_device_mesh, make_test_mesh
from repro.models.model import make_model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="h2o-danube-3-4b")
    ap.add_argument("--preset", default="reduced",
                    choices=["reduced", "full"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new", type=int, default=16)
    ap.add_argument("--rank", type=int, default=8)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.preset == "reduced":
        cfg = cfg.reduced()
        mesh = make_test_mesh((1, 1))
    else:
        mesh = make_device_mesh()

    model = make_model(cfg, remat=False)
    with mesh:
        params = model.init(jax.random.PRNGKey(0))
        adapters = model.init_adapters(jax.random.PRNGKey(1),
                                       rank=args.rank)
        rng = np.random.default_rng(0)
        total = args.prompt_len + args.new
        batch = {"tokens": jnp.asarray(
            rng.integers(0, cfg.vocab_size,
                         (args.batch, args.prompt_len)), jnp.int32)}
        if cfg.is_encdec:
            batch["frames"] = jnp.asarray(rng.normal(
                size=(args.batch, cfg.encoder_seq, cfg.frontend_dim)),
                jnp.float32)
        n_prefix = 0
        if cfg.frontend == "vision_patches":
            batch["patches"] = jnp.asarray(rng.normal(
                size=(args.batch, cfg.n_prefix_tokens, cfg.frontend_dim)),
                jnp.float32)
            n_prefix = cfg.n_prefix_tokens

        t0 = time.time()
        logits, caches = jax.jit(
            lambda p, a, b: model.prefill(p, a, b,
                                          capacity=total + n_prefix)
        )(params, adapters, batch)
        print(f"prefill: {time.time() - t0:.2f}s")

        decode = jax.jit(model.decode_step)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        t0 = time.time()
        for i in range(args.new - 1):
            pos = jnp.asarray(args.prompt_len + n_prefix + i, jnp.int32)
            logits, caches = decode(params, adapters, caches, tok, pos)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
        jax.block_until_ready(tok)
        dt = time.time() - t0
        print(f"decode: {args.new - 1} steps, "
              f"{(args.new - 1) * args.batch / max(dt, 1e-9):.1f} tok/s")


if __name__ == "__main__":
    main()
