"""Sharding rules unit tests (no multi-device needed: specs are data)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import get_config
from repro.models.model import make_model
from repro.sharding import rules


@pytest.fixture(scope="module")
def mesh():
    # a FAKE mesh object is enough for spec computation: rules only use
    # axis names/sizes
    dev = np.asarray(jax.devices() * 1)[:1].reshape(1, 1)
    m = Mesh(dev, ("data", "model"))
    return m


class FakeMesh:
    axis_names = ("pod", "data", "model")
    shape = {"pod": 2, "data": 16, "model": 16}


class FakeMesh1:
    axis_names = ("data", "model")
    shape = {"data": 16, "model": 16}


def test_maybe_divisibility():
    m = FakeMesh1()
    assert rules.maybe(m, 64, "model") == "model"
    assert rules.maybe(m, 50280, "model") is None   # mamba vocab: uneven
    assert rules.axis_size(FakeMesh(), ("pod", "data")) == 32


def test_param_specs_yi():
    cfg = get_config("yi-34b")
    model = make_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = rules.param_specs(shapes, FakeMesh1())
    # embedding sharded on vocab (64000 % 16 == 0)
    assert specs["embed"]["table"] == P("model", None)
    st = specs["stages"][0]["b0"]
    # fused q (L, d, H*hd): column-parallel on fan-out
    assert st["mix"]["q"]["w"] == P(None, None, "model")
    # o: row-parallel on fan-in
    assert st["mix"]["o"]["w"] == P(None, "model", None)
    assert st["ffn"]["down"]["w"] == P(None, "model", None)
    # norms replicated
    assert st["mix"]["ln"]["scale"] == P(None, None)


@pytest.mark.parametrize("held,expert_spec", [(256, "model"), (8, None)])
def test_param_specs_moe_expert_axis(held, expert_spec):
    """The registry's every routed expert, and one chip's share of 8: the
    router keeps its published width of ``n_routed_experts``."""
    cfg = dataclasses.replace(get_config("deepseek-v3-671b"), n_experts=held)
    model = make_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = rules.param_specs(shapes, FakeMesh1())
    moe = specs["stages"][1]["b0"]["ffn"]
    moe_shapes = shapes["stages"][1]["b0"]["ffn"]
    assert moe_shapes["router"]["w"].shape[-1] == cfg.n_routed_experts == 256
    assert moe_shapes["experts"]["gate"]["w"].shape[1] == held
    # experts (L, E, d, f): E sharded over model where E % 16 == 0
    assert moe["experts"]["gate"]["w"] == P(None, expert_spec, None, None)
    # router and its selection bias replicated
    assert moe["router"]["w"] == P(None, None, None)
    assert moe["router"]["select_bias"] == P(None, None)


def test_param_specs_fsdp_shards_contracting_dim():
    cfg = get_config("yi-34b")
    model = make_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = rules.param_specs(shapes, FakeMesh1(), fsdp=True)
    st = specs["stages"][0]["b0"]
    assert st["mix"]["q"]["w"] == P(None, ("data",), "model")
    assert st["ffn"]["down"]["w"] == P(None, "model", ("data",))


def test_batch_and_cache_specs():
    m = FakeMesh()
    batch = {"tokens": jax.ShapeDtypeStruct((256, 4096), jnp.int32)}
    specs = rules.batch_specs(batch, m)
    assert specs["tokens"] == P(("pod", "data"), None)
    # batch=1 long-context: shard cache time axis instead
    cfg = get_config("gemma2-9b")
    model = make_model(cfg)
    cache_shapes = jax.eval_shape(lambda: model.init_cache(1, 524288))
    cspecs = rules.cache_specs(cache_shapes, m, global_batch=1)
    # global layer (b1) kv cache: (L, B, T, KV, hd) -> T sharded
    leaf = cspecs[0]["b1"]["k"]
    assert leaf[2] == ("pod", "data")
    # windowed layer (b0): T=4096 also divisible -> sharded is fine too
    dec = rules.cache_specs(cache_shapes, m, global_batch=128)
    assert dec[0]["b1"]["k"][1] == ("pod", "data")


@pytest.mark.parametrize("held,expert_spec", [(256, "model"), (8, None)])
def test_adapter_specs_expert_axis(held, expert_spec):
    cfg = dataclasses.replace(get_config("deepseek-v3-671b"), n_experts=held)
    model = make_model(cfg)
    shapes = jax.eval_shape(
        lambda k: model.init_adapters(k, rank=8), jax.random.PRNGKey(0))
    specs = rules.adapter_specs(shapes, FakeMesh1())
    pair = specs["stages"][1]["b0"]["ffn/experts/gate"]
    # the adapter's expert axis is the experts held
    assert shapes["stages"][1]["b0"]["ffn/experts/gate"]["A"].shape[1] == held
    assert pair["A"] == P(None, expert_spec, None, None)
    # non-expert adapters replicated
    q = specs["stages"][1]["b0"]["mix/q_a"]
    assert q["A"] == P(None, None, None)
