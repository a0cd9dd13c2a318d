"""Heterogeneous ranks under the hood: Alg. 2 slicing, delta masks, and the
difference between zero-padding and RBLA on a single adapter -- then the
same aggregation as a distributed shard_map psum on 8 simulated CPU
devices (a mesh rehearsal: it never touches an accelerator).

    PYTHONPATH=src python examples/heterogeneous_ranks.py
"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import get_strategy, rbla_leaf, stacked_rank_masks, \
    zeropad_leaf
from repro.lora import init_pair, set_ranks, pair_masks

R_MAX, FAN_IN, FAN_OUT = 8, 16, 12
N_CLIENTS = 8

print("== Alg. 2: clients slice the server adapter to their rank ==")
server_pair = init_pair(jax.random.PRNGKey(0), FAN_OUT, FAN_IN, R_MAX,
                        R_MAX)
for rank in (2, 5, 8):
    client = set_ranks(server_pair, rank)
    live_rows = int((np.abs(np.asarray(client["A"])).sum(-1) > 0).sum())
    print(f"  client rank {rank}: live A rows = {live_rows}/{R_MAX}")

print("\n== zero-padding dilution vs RBLA preservation (paper Sec. 3) ==")
rng = np.random.default_rng(42)
ranks = jnp.asarray(rng.integers(1, R_MAX + 1, N_CLIENTS), jnp.int32)
masks = stacked_rank_masks(R_MAX, ranks)[:, :, None]
stacked = jnp.asarray(rng.normal(size=(N_CLIENTS, R_MAX, FAN_IN)),
                      jnp.float32) * masks + masks  # mean ~1 on live rows
w = jnp.ones(N_CLIENTS)
zp = zeropad_leaf(stacked, masks, w)
rb = rbla_leaf(stacked, masks, w)
owners = np.asarray(masks[:, :, 0]).sum(0)
for row in range(R_MAX):
    print(f"  row {row}: owners={int(owners[row])}  "
          f"|zp|={float(jnp.abs(zp[row]).mean()):.3f}  "
          f"|rbla|={float(jnp.abs(rb[row]).mean()):.3f}")
print("  (zero-padding shrinks scarce rows by owners/n; RBLA does not)")

print("\n== FLoRA stacking: rank-growing, noise-free aggregation ==")
# the flora strategy concatenates client factors instead of averaging
# rows: the served update is *exactly* the convex combination of the
# clients' effective updates, at the price of a growing global rank
from repro.lora import init_adapters, set_ranks as _set_ranks

SPECS = {"fc": (FAN_OUT, FAN_IN)}
cohort, keys = [], jax.random.split(jax.random.PRNGKey(3), 3)
cranks = (2, 3, 5)
for k, r in zip(keys, cranks):
    ad = init_adapters(k, SPECS, R_MAX, r)
    ad = jax.tree.map(lambda x: x + 0.1 if x.dtype == jnp.float32 else x,
                      ad)           # randomize B too (it inits to zero)
    cohort.append(_set_ranks(ad, r))
flora = get_strategy("flora").with_options(stack_r_cap=32)
wf = jnp.ones(len(cohort))
glob = flora.aggregate_adapters(cohort, wf, r_max=R_MAX,
                                client_ranks=jnp.asarray(cranks))
print(f"  client ranks {cranks} -> stacked global rank "
      f"{int(glob['fc']['rank'])} (storage {glob['fc']['A'].shape[-2]})")
eff = np.asarray(glob["fc"]["B"] @ glob["fc"]["A"]) / int(glob["fc"]["rank"])
want = sum(np.asarray(c["fc"]["B"] @ c["fc"]["A"]) / r
           for c, r in zip(cohort, cranks)) / len(cohort)
print(f"  served update == mean client update: max |diff| = "
      f"{np.abs(eff - want).max():.2e}  (stacking is noise-free)")
nxt = flora.aggregate_adapters(cohort, wf, r_max=R_MAX,
                               client_ranks=jnp.asarray(cranks),
                               prev_global=glob)
print(f"  next round stacks the previous global as one more contributor: "
      f"rank {int(glob['fc']['rank'])} -> {int(nxt['fc']['rank'])}")
capped = flora.with_options(stack_r_cap=R_MAX).aggregate_adapters(
    cohort, wf, r_max=R_MAX, client_ranks=jnp.asarray(cranks))
print(f"  with stack_r_cap={R_MAX} the same cohort SVD-reprojects back "
      f"to rank {int(capped['fc']['rank'])}")

print("\n== the same aggregation as a pod-level collective ==")
# every registered strategy carries its own distributed shard_map path:
mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(8), ("clients",))
agg = get_strategy("rbla").make_distributed_aggregator(
    mesh, client_axis="clients")
sh = NamedSharding(mesh, P("clients"))
out = agg(jax.device_put(stacked, sh),
          jax.device_put(jnp.broadcast_to(masks, stacked.shape), sh),
          jax.device_put(w, sh))
np.testing.assert_allclose(np.asarray(out), np.asarray(rb), rtol=1e-5,
                           atol=1e-6)
print(f"  masked-psum over {len(jax.devices())} devices matches the "
      "host result (max |diff| = "
      f"{float(jnp.abs(out - rb).max()):.2e})")
