"""Distributed aggregation + small-mesh dry-run integration tests.

These spawn SUBPROCESSES with forced host device counts so the rest of the
suite keeps its single-device jax runtime.
"""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_child(code: str, devices: int = 8, timeout: int = 900):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout,
                          cwd=ROOT)


@pytest.mark.slow
def test_distributed_rbla_matches_host():
    code = r"""
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.core import get_strategy, stacked_rank_masks

n, r, d = 8, 32, 512
rng = np.random.default_rng(0)
ranks = jnp.asarray(rng.integers(1, r + 1, n), jnp.int32)
masks = stacked_rank_masks(r, ranks)[:, :, None]
x = jnp.asarray(rng.normal(size=(n, r, d)), jnp.float32) * masks
w = jnp.asarray(rng.uniform(0.5, 2.0, n), jnp.float32)
mesh = Mesh(np.asarray(jax.devices()).reshape(8), ("clients",))
for method in ("rbla", "zeropad"):
    agg = get_strategy(method).make_distributed_aggregator(mesh, "clients")
    sh = NamedSharding(mesh, P("clients"))
    out = agg(jax.device_put(x, sh),
              jax.device_put(jnp.broadcast_to(masks, x.shape), sh),
              jax.device_put(w, sh))
    want = get_strategy(method).aggregate_tree({"t": x}, {"t": masks},
                                               w)["t"]
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
print("OK")
"""
    res = run_child(code)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "OK" in res.stdout


@pytest.mark.slow
def test_dryrun_small_mesh_lowers():
    """The dry-run machinery on a 4-device (2,2) mesh with a reduced arch:
    proves the sharded train/prefill/decode lowering path end to end
    without the 512-device cost."""
    code = r"""
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from repro.configs import get_config
from repro.models.model import make_model
from repro.sharding import rules
from repro.launch.dryrun import (build_train_step, build_decode_step,
                                 input_specs, decode_input_specs,
                                 model_state_specs)
from repro.configs.base import InputShape
from repro.lora import strip_ranks
from repro.optim import adam

mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
            ("data", "model"))
cfg = get_config("granite-moe-3b-a800m").reduced(
    vocab_size=512, n_experts=4, experts_per_token=2)
model = make_model(cfg, remat=True)
shape = InputShape("t", 64, 8, "train")
with mesh:
    params, adapters, _, _ = model_state_specs(cfg, mesh, model)
    step, opt = build_train_step(model, cfg)
    factors, _ = strip_ranks(adapters)
    opt_state = jax.eval_shape(opt.init, factors)
    opt_state = rules.shaped(
        opt_state, rules.to_shardings(rules.adapter_specs(opt_state, mesh),
                                      mesh))
    batch = input_specs(cfg, shape, mesh)
    compiled = jax.jit(step).lower(params, adapters, opt_state,
                                   batch).compile()
    assert compiled.cost_analysis() is not None

    dshape = InputShape("d", 128, 8, "decode")
    serve = build_decode_step(model)
    caches, token, pos = decode_input_specs(cfg, dshape, mesh, model)
    jax.jit(serve).lower(params, adapters, caches, token, pos).compile()
print("OK")
"""
    res = run_child(code, devices=4)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "OK" in res.stdout


@pytest.mark.slow
def test_fl_round_spmd():
    """FLaaS round as one SPMD program: 8 clients on 8 devices run a local
    LoRA step and RBLA-aggregate via masked psum -- the pod-scale FL path."""
    code = r"""
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.core import get_strategy
from repro.core.compat import shard_map_no_check
from repro.lora import (adapter_masks, attach_ranks, init_adapters,
                        strip_ranks, set_ranks)

mesh = Mesh(np.asarray(jax.devices()).reshape(8), ("clients",))

specs = {"fc1": (16, 8)}
server = init_adapters(jax.random.PRNGKey(0), specs, r_max=8, rank=8)

def client_round(adapters, rank, x):
    ad = set_ranks(adapters, rank[0])
    # fake local update: push A toward the data mean (stands in for SGD)
    upd = jax.tree.map(lambda a: a, ad)
    upd["fc1"] = dict(upd["fc1"])
    upd["fc1"]["A"] = upd["fc1"]["A"] + 0.1 * jnp.mean(x)
    ad = set_ranks(upd, rank[0])   # re-mask
    masks = adapter_masks(ad)
    rbla = get_strategy("rbla")
    return jax.tree.map(
        lambda x, m: rbla.allreduce_leaf(x, m, jnp.float32(1.0), "clients"),
        ad, masks)

ranks = jnp.arange(1, 9, dtype=jnp.int32)        # heterogeneous ranks
xs = jnp.arange(8, dtype=jnp.float32)[:, None] * jnp.ones((8, 4))
fn = shard_map_no_check(client_round, mesh,
                        in_specs=(P(), P("clients"), P("clients")),
                        out_specs=P())
out = fn(server, ranks, xs)
A = np.asarray(out["fc1"]["A"])
# row 7 owned only by the rank-8 client (client 7): preserved verbatim
base = np.asarray(server["fc1"]["A"])
np.testing.assert_allclose(A[7], base[7] + 0.1 * 7.0, rtol=1e-5)
# row 0 owned by all: mean of all client updates
np.testing.assert_allclose(A[0], base[0] + 0.1 * np.mean(np.arange(8)),
                           rtol=1e-5)
print("OK")
"""
    res = run_child(code)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "OK" in res.stdout


@pytest.mark.slow
def test_moe_ep_a2a_matches_pjit_path():
    """Explicit expert-parallel all-to-all dispatch (moe_ep) against the
    sort/pjit path on a (data=2, model=4) mesh with 8 experts held:
    granite's softmax router over those 8, and a DeepSeek-V3 share of 8
    of 16 sigmoid-routed experts."""
    with open("/dev/null"):
        pass
    code = open(os.path.join(ROOT, "tests", "_moe_ep_child.py")).read()
    res = run_child(code, devices=8)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "EP_OK" in res.stdout
