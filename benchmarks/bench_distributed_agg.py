"""Distributed RBLA (shard_map masked psum) vs host aggregation: a CPU
mesh rehearsal.

Runs in a SUBPROCESS pinned to the CPU with 8 forced host devices (so the
parent process / other benches keep seeing their own devices, and the
child never reaches for an accelerator the parent may hold), checks
numerical equivalence with the single-host core implementation, and
times both on XLA:CPU -- not a device measurement.
"""
from __future__ import annotations

import os
import subprocess
import sys

CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import time
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import get_strategy, stacked_rank_masks

strategy = get_strategy("rbla")
n, r, d = 8, 64, 2048
rng = np.random.default_rng(0)
ranks = jnp.asarray(rng.integers(1, r + 1, n), jnp.int32)
masks = stacked_rank_masks(r, ranks)[:, :, None]
x = jnp.asarray(rng.normal(size=(n, r, d)), jnp.float32) * masks
w = jnp.asarray(rng.uniform(0.5, 2.0, n), jnp.float32)

mesh = Mesh(np.asarray(jax.devices()).reshape(8), ("clients",))
agg = strategy.make_distributed_aggregator(mesh, client_axis="clients")
sh = NamedSharding(mesh, P("clients"))
xd = jax.device_put(x, sh)
md = jax.device_put(jnp.broadcast_to(masks, x.shape), sh)
wd = jax.device_put(w, sh)

out = agg(xd, md, wd)
want = strategy.aggregate_tree({"t": x}, {"t": masks}, w)["t"]
np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                           rtol=1e-5, atol=1e-6)

def bench(f, *a, iters=10):
    f(*a); t0 = time.time()
    for _ in range(iters):
        o = f(*a)
    jax.block_until_ready(o)
    return (time.time() - t0) / iters * 1e6

us_dist = bench(agg, xd, md, wd)
host = jax.jit(lambda x, m, w: strategy.aggregate_tree({"t": x}, {"t": m},
                                                       w)["t"])
us_host = bench(host, x, masks, w)
print(f"agg/distributed_psum/8dev_n{n}_r{r}_d{d},{us_dist:.0f},"
      f"equivalent=True")
print(f"agg/host_jit/n{n}_r{r}_d{d},{us_host:.0f},reference")
"""


def main():
    env = dict(os.environ)
    env["PYTHONPATH"] = env.get("PYTHONPATH", "src")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", CHILD], env=env,
                          capture_output=True, text=True, timeout=600)
    sys.stdout.write(proc.stdout)
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr)
        raise SystemExit("distributed aggregation bench failed")


if __name__ == "__main__":
    main()
