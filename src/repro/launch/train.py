"""Training launcher: sharded LoRA fine-tuning loop, then the cohort upload.

``--preset full`` trains the architecture at its published widths on a
mesh over the devices present (``python -m repro.launch.train``; one v5e
chip holds h2o-danube-3-4b in bf16); ``--preset reduced`` runs the same
code path on a reduced config on one CPU device.

The loop is the pod-side of FLaaS: one client cohort's local steps.  The
FL simulator (repro.fl) drives many such loops + RBLA aggregation.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save as ckpt_save
from repro.configs import get_config, INPUT_SHAPES
from repro.core.strategy import get_strategy, list_strategies
from repro.data import make_lm_dataset
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_device_mesh, make_test_mesh
from repro.lora import attach_ranks, strip_ranks
from repro.models.model import make_model
from repro.optim import adam, apply_updates
from repro.sharding import rules


def main(argv=None):
    """Run the launcher on ``argv`` (default: the command line); returns
    the trained adapters (ranks attached).  Raises on a non-finite loss
    and when the strategy cannot aggregate the cohort upload."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="h2o-danube-3-4b")
    ap.add_argument("--preset", default="reduced",
                    choices=["reduced", "full"])
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--rank", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--method", default="rbla",
                    help="server aggregation strategy for the cohort "
                         f"upload: one of {list_strategies()}")
    ap.add_argument("--agg-backend", default="auto",
                    choices=["auto", "ref", "pallas", "distributed"])
    args = ap.parse_args(argv)
    strategy = get_strategy(args.method)   # fail fast on typos
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.preset == "reduced":
        cfg = cfg.reduced()
        mesh = make_test_mesh((1, 1))
    else:
        mesh = make_device_mesh()

    model = make_model(cfg, remat=args.preset == "full")
    with mesh:
        params_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        pspecs = rules.param_specs(params_shapes, mesh)
        params = jax.jit(model.init,
                         out_shardings=rules.to_shardings(pspecs, mesh))(
            jax.random.PRNGKey(0))
        adapters = model.init_adapters(jax.random.PRNGKey(1),
                                       rank=args.rank)
        factors, ranks = strip_ranks(adapters)
        opt = adam(args.lr)
        opt_state = opt.init(factors)

        data = make_lm_dataset(cfg.vocab_size, args.seq + 1,
                               n_seqs=args.batch * 32, seed=42)

        # the base weights are an argument, never a closure: a jitted
        # closure embeds them as constants of the compiled program
        @jax.jit
        def step(params, factors, opt_state, tokens):
            def loss_fn(f):
                return model.loss(params, attach_ranks(f, ranks),
                                  {"tokens": tokens})
            loss, grads = jax.value_and_grad(loss_fn)(factors)
            updates, opt_state = opt.update(grads, opt_state, factors)
            return apply_updates(factors, updates), opt_state, loss

        rng = np.random.default_rng(0)
        t0 = time.time()
        losses = []                 # on the device: the loop never syncs
        for i in range(args.steps):     # but on its logging steps
            ix = rng.integers(0, len(data), args.batch)
            factors, opt_state, loss = step(params, factors, opt_state,
                                            jnp.asarray(data[ix]))
            losses.append(loss)
            if i % max(1, args.steps // 10) == 0:
                print(f"step {i:4d} loss {float(loss):.4f} "
                      f"({(time.time() - t0) / (i + 1):.2f}s/step)",
                      flush=True)
        bad = [i for i, v in enumerate(jax.device_get(losses))
               if not np.isfinite(v)]
        if bad:
            raise FloatingPointError(f"non-finite loss at steps {bad}")
        # the pod-side round ends like the FLaaS server: the cohort's
        # adapter upload goes through the registered strategy (one cohort
        # here; the FL simulator drives many).
        # r_max=args.rank keeps the live rank (and the alpha/rank forward
        # scale) identical to the model that was just trained
        trained = attach_ranks(factors, ranks)
        global_adapters = strategy.aggregate_adapters(
            [trained], jnp.ones(1), r_max=args.rank,
            client_ranks=jnp.asarray([args.rank]),
            backend=args.agg_backend)
        print(f"aggregated cohort upload via strategy={strategy.name} "
              f"backend={args.agg_backend}")
        if args.ckpt:
            ckpt_save(args.ckpt, global_adapters)
            print(f"saved aggregated adapters to {args.ckpt}")
    return trained


if __name__ == "__main__":
    main()
