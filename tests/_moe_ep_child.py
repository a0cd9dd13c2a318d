import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.models.moe import moe_init, moe_forward
from repro.models.moe_ep import moe_forward_ep

# granite: softmax routing, every routed expert held (n_routed_experts 0:
# the router's width is n_experts); a deepseek-v3 share: sigmoid group-
# limited routing over 16 experts, experts 4..11 held, 2 on each of the 4
# model shards
granite = get_config("granite-moe-3b-a800m").reduced(
    d_model=64, n_experts=8, experts_per_token=2, moe_d_ff=32,
    capacity_factor=8.0)
share = get_config("deepseek-v3-671b").reduced(
    d_model=64, n_routed_experts=16, n_experts=8, expert_offset=4,
    experts_per_token=4, router_groups=4, router_topk_groups=2,
    moe_d_ff=32, capacity_factor=8.0)
assert granite.n_routed == granite.n_experts == 8
assert share.n_routed == 16 and share.n_experts == 8

mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4), ("data", "model"))
from repro.core.compat import shard_map_no_check

for name, cfg in (("granite", granite), ("share", share)):
    key = jax.random.PRNGKey(0)
    p = moe_init(key, cfg)
    assert p["router"]["w"].shape == (64, cfg.n_routed)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 16, 64)), jnp.float32)

    # reference: single-device sort path, one group per data shard (same
    # capacity math)
    y_ref = moe_forward(p, None, x, cfg, n_groups=8)

    pspec = jax.tree.map(lambda _: P(), p)
    pspec["experts"] = {k: {"w": P("model", None, None)} for k in
                        ("gate", "up", "down")}

    def body(p_local, x_local, cfg=cfg):
        return moe_forward_ep(p_local, None, x_local, cfg,
                              model_axis="model")

    fn = jax.jit(shard_map_no_check(
        body, mesh, in_specs=(pspec, P(("data", "model"), None, None)),
        out_specs=P(("data", "model"), None, None)))
    with mesh:
        pd = jax.device_put(p, jax.tree.map(
            lambda s: NamedSharding(mesh, s), pspec,
            is_leaf=lambda v: isinstance(v, P)))
        xd = jax.device_put(x, NamedSharding(
            mesh, P(("data", "model"), None, None)))
        y = fn(pd, xd)
    print(name, "max diff", float(jnp.abs(y - y_ref).max()),
          "ref scale", float(jnp.abs(y_ref).max()))
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)
print("EP_OK")
