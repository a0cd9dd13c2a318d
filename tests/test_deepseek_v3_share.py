"""DeepSeek-V3 at one chip's expert-parallel share against the plain
float32 reference (``tests/reference_deepseek_v3.py``), at small sizes on
the CPU: the expert layer that knows which experts it holds, V3's router,
YaRN in MLA, and the share's adapters through a client fit and an RBLA
round."""
import dataclasses
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_deepseek_v3 as ref
from repro.configs import get_config
from repro.core import get_strategy
from repro.fl.client import make_local_fit
from repro.lora import init_pair, mask_adapters, tree_map_pairs
from repro.models.attention import mla_softmax_scale
from repro.models.common import rope_freqs
from repro.models.model import make_model
from repro.models.moe import _route, moe_forward, moe_init, router_logits
from repro.models.transformer import block_lora_specs, stage_forward
from repro.obs import get_registry
from repro.optim import sgd

jax.config.update("jax_platform_name", "cpu")

#: f32 program against the f32 reference over two layers: both sum the
#: same products in another order (einsum against @, a scan against a
#: loop), so they part at a few float32 ulps of the largest entry; a
#: program in bfloat16 reads about 1e-2 (asserted below).
F32_TOL = 2e-5


def published(cfg, held=None, offset=0) -> dict:
    """The reference's config: the published ``config.json`` keys of the
    program's architecture, and the share."""
    y = cfg.rope_scaling
    return {
        "num_attention_heads": cfg.n_heads, "q_lora_rank": cfg.q_lora_rank,
        "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_dim,
        "qk_rope_head_dim": cfg.qk_rope_dim, "v_head_dim": cfg.v_head_dim,
        "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
        "rope_scaling": None if y is None else dataclasses.asdict(y),
        "n_routed_experts": cfg.n_routed, "n_group": cfg.router_groups,
        "topk_group": cfg.router_topk_groups,
        "num_experts_per_tok": cfg.experts_per_token,
        "routed_scaling_factor": cfg.router_scale,
        "norm_topk_prob": True,
        "experts_held": cfg.n_experts if held is None else held,
        "expert_offset": cfg.expert_offset if held is None else offset,
    }


def small_share(**kw):
    """16 routed experts top-4 in 4 groups of 4, the top 2 groups, d 64:
    capacity factor 8 gives every held expert a slot per token (no drop)."""
    base = dict(d_model=64, n_routed_experts=16, n_experts=16,
                experts_per_token=4, router_groups=4, router_topk_groups=2,
                moe_d_ff=32)
    base.update(kw)
    return get_config("deepseek-v3-671b").reduced(**base)


def live(pair, key, scale=0.2):
    """A pair with random A and B on its live rows and columns."""
    ka, kb = jax.random.split(key)
    out = dict(pair, A=jax.random.normal(ka, pair["A"].shape) * scale,
               B=jax.random.normal(kb, pair["B"].shape) * scale)
    return mask_adapters(out)


def random_adapters(model, key, rank):
    ad = model.init_adapters(key, rank=rank)
    keys = iter(jax.random.split(jax.random.fold_in(key, 1), 256))
    return tree_map_pairs(lambda p: live(p, next(keys)), ad)


def moe_lora(cfg, key, rank=4):
    """Flat per-expert and shared adapters of one MoE layer (no layer
    axis), as the program's block passes them to ``moe_forward``."""
    spec = cfg.stages[-1].unit[0]
    out = {}
    for i, (path, (fo, fi, extra)) in enumerate(
            sorted(block_lora_specs(cfg, spec).items())):
        if path.startswith("ffn/"):
            pair = init_pair(jax.random.fold_in(key, i), fo, fi,
                             cfg.lora_r_max, rank, leading=extra)
            pair = dict(pair, rank=jnp.asarray(rank, jnp.int32))
            out[path[4:]] = live(pair, jax.random.fold_in(key, 100 + i))
    return out


def share_of(p, lora, lo, hi):
    """Experts ``[lo, hi)`` of a layer's weights and expert adapters."""
    p = dict(p, experts={k: {"w": v["w"][lo:hi]}
                         for k, v in p["experts"].items()})
    lora = {k: (dict(v, A=v["A"][lo:hi], B=v["B"][lo:hi])
                if k.startswith("experts/") else v) for k, v in lora.items()}
    return p, lora


def ref_moe(p, lora, x, config):
    """The reference MoE layer on a layer's params and flat adapters."""
    full = {"ffn/" + k: v for k, v in lora.items()}
    with jax.default_matmul_precision("highest"):
        return ref.moe(x, p, full, config)


def gap(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.fixture(scope="module")
def layer():
    cfg = small_share()
    p = moe_init(jax.random.PRNGKey(0), cfg)
    lora = moe_lora(cfg, jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, cfg.d_model))
    return cfg, p, lora, x


# (a) ------------------------------------------------- shares add up ----
def test_four_shares_add_up_to_the_uncut_layer(layer):
    """Four shares of 4 held experts each route over all 16 and compute
    their experts' part; with the shared expert (which every share
    computes) counted once, they add up to the reference's whole layer."""
    cfg, p, lora, x = layer
    parts = []
    for i in range(4):
        cfg_i = dataclasses.replace(cfg, n_experts=4, expert_offset=4 * i)
        p_i, lora_i = share_of(p, lora, 4 * i, 4 * i + 4)
        parts.append(moe_forward(p_i, lora_i, x, cfg_i))
    no_experts = dict(published(cfg), experts_held=0)
    shared = ref_moe(p, lora, x, no_experts)
    total = sum(parts) - 3 * shared
    want = ref_moe(p, lora, x, published(cfg))
    assert gap(total, want) < F32_TOL
    assert gap(moe_forward(p, lora, x, cfg), want) < F32_TOL


@pytest.mark.parametrize("offset", [0, 4, 8, 12])
def test_a_share_computes_its_experts_part(layer, offset):
    cfg, p, lora, x = layer
    cfg_i = dataclasses.replace(cfg, n_experts=4, expert_offset=offset)
    p_i, lora_i = share_of(p, lora, offset, offset + 4)
    got = moe_forward(p_i, lora_i, x, cfg_i)
    want = ref_moe(p_i, lora_i, x, published(cfg, held=4, offset=offset))
    assert gap(got, want) < F32_TOL
    # the router keeps its published width; the share holds its experts
    assert p_i["router"]["w"].shape == (cfg.d_model, 16)
    assert p_i["experts"]["gate"]["w"].shape[0] == 4


# (b) ---------------------------------------------------------- router ----
def test_router_ids_and_weights_equal_the_reference(layer):
    cfg, p, _, _ = layer
    h = jax.random.normal(jax.random.PRNGKey(3), (512, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        logits = h @ p["router"]["w"]
        w, ix = _route(cfg, logits, p["router"]["select_bias"])
        w_ref, ix_ref = ref.route(h, p["router"], published(cfg))
    np.testing.assert_array_equal(np.asarray(ix), np.asarray(ix_ref))
    np.testing.assert_allclose(np.asarray(w), np.asarray(w_ref), rtol=1e-6)
    # group-limited: every token's choices lie in 2 of the 4 groups
    groups = np.asarray(ix) // 4
    assert max(len(set(g)) for g in groups) <= 2
    # weights: the sigmoid at the chosen ids, normalised, times 2.5
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-6)


def test_the_selection_bias_chooses_and_does_not_weigh(layer):
    cfg, p, _, _ = layer
    h = jax.random.normal(jax.random.PRNGKey(4), (256, cfg.d_model))
    logits = h @ p["router"]["w"]
    big = jnp.zeros((16,)).at[5].set(10.0)        # expert 5 always chosen
    w, ix = _route(cfg, logits, big)
    assert bool(jnp.all(jnp.any(ix == 5, -1)))
    s = jax.nn.sigmoid(logits)
    picked = jnp.take_along_axis(s, ix, -1)
    np.testing.assert_allclose(
        np.asarray(w), np.asarray(picked / picked.sum(-1, keepdims=True)
                                  * 2.5), rtol=1e-6)


# (c) ------------------------------------------------- two blocks ----
def program_blocks(model, params, adapters, x):
    """The program's stages over hidden states x (its normal path less the
    embedding and the head)."""
    for i, stage in enumerate(model.cfg.stages):
        x, _ = stage_forward(params["stages"][i], adapters["stages"][i], x,
                             model.cfg, stage, mode="full",
                             positions=jnp.arange(x.shape[1]),
                             alpha=model.alpha)
    return x


def reference_layers(params, adapters):
    """[(params, adapters)] of each layer of each stage, layer axis
    sliced off."""
    out = []
    for sp, sa in zip(params["stages"], adapters["stages"]):
        n = jax.tree.leaves(sp)[0].shape[0]
        for j in range(n):
            out.append((jax.tree.map(lambda v: v[j], sp["b0"]),
                        jax.tree.map(lambda v: v[j], sa["b0"])))
    return out


@pytest.fixture(scope="module")
def two_blocks():
    # held experts 4..7 of 8, so the share is not the first
    cfg = get_config("deepseek-v3-671b").reduced(expert_offset=4)
    assert [b.ffn for s in cfg.stages for b in s.unit] == ["dense", "moe"]
    model = make_model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    adapters = random_adapters(model, jax.random.PRNGKey(1), rank=5)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, cfg.d_model))
    want = jax.jit(lambda x, layers: ref.forward(x, layers, published(cfg)))(
        x, reference_layers(params, adapters))
    return cfg, model, params, adapters, x, want


def test_dense_and_moe_blocks_match_the_reference(two_blocks):
    cfg, model, params, adapters, x, want = two_blocks
    assert cfg.lora_r_max == 8 and cfg.rope_scaling is not None
    got = program_blocks(model, params, adapters, x)
    assert gap(got, want) < F32_TOL


def test_the_program_in_bfloat16_fails_the_tolerance(two_blocks):
    """The control: the same blocks with bfloat16 weights and activations
    read far above the f32 tolerance, so the comparison can see
    precision."""
    cfg, _, params, adapters, x, want = two_blocks
    bf = make_model(dataclasses.replace(cfg, dtype="bfloat16"), remat=False)
    p16 = jax.tree.map(lambda v: v.astype(jnp.bfloat16)
                       if v.dtype == jnp.float32 else v, params)
    got = program_blocks(bf, p16, adapters, x.astype(jnp.bfloat16))
    assert gap(got, want) > 100 * F32_TOL


# (d) ----------------------------------------------------------- YaRN ----
def test_yarn_frequencies_and_mla_scale_equal_the_closed_form():
    cfg = get_config("deepseek-v3-671b")
    y = cfg.rope_scaling
    assert (y.factor, y.original_max_position_embeddings, y.beta_fast,
            y.beta_slow, y.mscale, y.mscale_all_dim) == (40, 4096, 32, 1, 1,
                                                        1)
    got = np.asarray(rope_freqs(64, 10_000.0, y), np.float64)

    def d(n):
        return 64 * math.log(4096 / (2 * math.pi * n)) / (2 * math.log(1e4))

    low, high = math.floor(d(32)), math.ceil(d(1))
    assert (low, high) == (10, 23)
    i = np.arange(32)
    extra = 10_000.0 ** (-2 * i / 64)
    m = 1 - np.clip((i - low) / (high - low), 0, 1)
    np.testing.assert_allclose(got, extra / 40 * (1 - m) + extra * m,
                               rtol=1e-6)
    np.testing.assert_allclose(got[:11], extra[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], extra[23:] / 40, rtol=1e-6)
    np.testing.assert_allclose(got, np.asarray(ref.yarn_inv_freq(
        64, 1e4, dataclasses.asdict(y))), rtol=1e-6)
    mscale = 0.1 * math.log(40) + 1
    assert mla_softmax_scale(cfg) == pytest.approx(192 ** -0.5 * mscale ** 2,
                                                   rel=1e-12)


def test_rope_without_scaling_is_unchanged():
    cfg = get_config("h2o-danube-3-4b")
    assert cfg.rope_scaling is None
    np.testing.assert_array_equal(
        np.asarray(rope_freqs(64, 1e4)),
        np.asarray(1.0 / (1e4 ** (jnp.arange(0, 64, 2, dtype=jnp.float32)
                                  / 64))))
    plain = dataclasses.replace(get_config("deepseek-v3-671b"),
                                rope_scaling=None)
    assert mla_softmax_scale(plain) == 192 ** -0.5


# (e) ------------------------------------------------ fit and aggregate --
class LastToken:
    """The share as a classifier of the next token at the last position,
    for the client fit (``apply`` is what ``make_local_fit`` calls)."""

    def __init__(self, model):
        self.model = model

    def apply(self, params, adapters, xb, train=True, rng=None):
        logits, _ = self.model.forward(params["all"], adapters,
                                       {"tokens": xb})
        return logits[:, -1]


def test_three_clients_fit_and_aggregate_to_the_per_row_mean():
    cfg = get_config("deepseek-v3-671b").reduced()
    model = make_model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    fit = make_local_fit(LastToken(model), sgd(0.5), batch_size=2,
                         n_steps=2)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 16)), jnp.int32)
    y = jnp.asarray(rng.integers(0, cfg.vocab_size, (4,)), jnp.int32)
    ranks, weights = (2, 4, 8), jnp.asarray([30.0, 50.0, 20.0])
    clients = []
    for i, r in enumerate(ranks):
        start = random_adapters(model, jax.random.PRNGKey(10 + i), rank=r)
        res = fit({"all": params}, {}, start, x, y, jnp.asarray(4),
                  jax.random.PRNGKey(20 + i))
        assert np.isfinite(float(res.loss))
        moved = [float(jnp.abs(a - b).max()) for a, b in zip(
            jax.tree.leaves(res.adapters), jax.tree.leaves(start))]
        assert max(moved) > 0           # the fit trained the share
        clients.append(res.adapters)
    got = get_strategy("rbla").aggregate_adapters(clients, weights,
                                                  r_max=cfg.lora_r_max)

    def per_row_mean(side, rows_axis, *pairs):
        r_max = pairs[0][side].shape[rows_axis]
        num, den = 0.0, np.zeros(r_max)
        for pair, r, w in zip(pairs, ranks, np.asarray(weights)):
            own = (np.arange(r_max) < r) * w
            shape = [1] * pair[side].ndim
            shape[rows_axis] = r_max
            num = num + np.asarray(pair[side]) * own.reshape(shape)
            den = den + own
        shape = [1] * pairs[0][side].ndim
        shape[rows_axis] = r_max
        return num / np.where(den > 0, den, 1).reshape(shape)

    n_expert_pairs = 0
    flat = []
    tree_map_pairs(lambda p: flat.append(p), got)
    cl = [[] for _ in clients]
    for i, c in enumerate(clients):
        tree_map_pairs(lambda p, i=i: cl[i].append(p), c)
    for k, g in enumerate(flat):
        pairs = [c[k] for c in cl]
        np.testing.assert_allclose(np.asarray(g["A"]),
                                   per_row_mean("A", -2, *pairs),
                                   rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(np.asarray(g["B"]),
                                   per_row_mean("B", -1, *pairs),
                                   rtol=1e-5, atol=1e-7)
        n_expert_pairs += g["A"].ndim == 4
    assert n_expert_pairs == 3          # gate, up, down of the held experts


# (f) ----------------------------------------------- softmax unchanged ----
def test_granite_softmax_routing_is_unchanged():
    cfg = get_config("granite-moe-3b-a800m").reduced()
    assert cfg.n_routed_experts == 0 and cfg.n_routed == cfg.n_experts
    assert cfg.router_scoring == "softmax" and cfg.router_groups == 1
    p = moe_init(jax.random.PRNGKey(0), cfg)
    assert p["router"].keys() == {"w"}
    assert p["router"]["w"].shape == (cfg.d_model, cfg.n_experts)
    logits = jax.random.normal(jax.random.PRNGKey(1), (64, cfg.n_experts))
    w, ix = _route(cfg, logits)
    # the routing as it was: softmax, top-k, renormalised over the k
    probs = jax.nn.softmax(logits, -1)
    w_old, ix_old = jax.lax.top_k(probs, cfg.experts_per_token)
    w_old = w_old / (jnp.sum(w_old, -1, keepdims=True) + 1e-9)
    np.testing.assert_array_equal(np.asarray(ix), np.asarray(ix_old))
    np.testing.assert_array_equal(np.asarray(w), np.asarray(w_old))


# ------------------------------------------------------------ the gauge ----
def test_make_model_reports_the_experts_held():
    cfg = dataclasses.replace(get_config("deepseek-v3-671b"), n_experts=8)
    model = make_model(cfg)
    shapes = jax.eval_shape(lambda k: moe_init(k, cfg),
                            jax.random.PRNGKey(0))
    assert shapes["router"]["w"].shape == (7168, 256)
    assert shapes["router"]["select_bias"].shape == (256,)
    assert shapes["experts"]["gate"]["w"].shape == (8, 7168, 2048)
    gauge = get_registry().snapshot()["gauges"]["moe_experts_held"]
    assert gauge["routed=256"] == 8
    assert model.cfg.n_routed == 256


def test_a_share_past_the_routed_experts_is_refused():
    cfg = get_config("deepseek-v3-671b")
    with pytest.raises(ValueError):
        dataclasses.replace(cfg, n_experts=8, expert_offset=252)
    with pytest.raises(ValueError):
        dataclasses.replace(cfg, router_topk_groups=9)


def test_moe_stages_carry_named_scopes(layer):
    cfg, p, lora, x = layer
    text = jax.jit(lambda p, x: moe_forward(p, lora, x, cfg)).lower(
        p, x).as_text(debug_info=True)
    for scope in ("moe.route", "moe.dispatch", "moe.experts",
                  "moe.combine"):
        assert scope in text, scope


def test_the_router_matmul_runs_at_full_f32_precision():
    h = jnp.ones((4, 8), jnp.float32)
    router = {"w": jnp.ones((8, 16), jnp.float32)}
    text = jax.jit(router_logits).lower(router, h).as_text()
    assert "HIGHEST" in text


# ------------------------------------------- registry = published config ----
def test_the_registry_holds_the_published_router_and_yarn():
    """Read from the committed benchmark configuration, the published
    ``config.json`` of DeepSeek-V3, so a wrong registry constant cannot
    pass through the reference built from the program's own config."""
    root = Path(__file__).resolve().parent.parent
    pub = json.loads((root / "perfbench" / "configs" /
                      "deepseek-v3.l5e8.json").read_text())
    cfg = get_config("deepseek-v3-671b")
    assert cfg.n_routed == pub["reduced"]["n_routed_experts"]["published"]
    assert cfg.router_groups == pub["n_group"]
    assert cfg.router_topk_groups == pub["topk_group"]
    assert cfg.router_scale == pub["routed_scaling_factor"]
    assert cfg.router_scoring == pub["scoring_func"]
    assert cfg.experts_per_token == pub["num_experts_per_tok"]
    # the program always normalises the k weights
    assert pub["norm_topk_prob"] is True
    y = dict(pub["rope_scaling"])
    assert y.pop("type") == "yarn"
    assert dataclasses.asdict(cfg.rope_scaling) == y
    assert cfg.rope_theta == pub["rope_theta"]
