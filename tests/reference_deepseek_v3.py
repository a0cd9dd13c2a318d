"""Plain float32 reference of DeepSeek-V3's layers at one chip's
expert-parallel share, with LoRA, for comparison with the program.

Straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
capacity, no scan.  It imports nothing of the program.  It follows the
published model (arXiv:2412.19437 and the ``modeling_deepseek.py`` that
ships with https://huggingface.co/deepseek-ai/DeepSeek-V3) and reads the
published ``config.json`` keys (``hidden_size``, ``q_lora_rank``,
``n_group``, ``rope_scaling`` ...), plus two keys of the share:
``experts_held`` and ``expert_offset``.

Weights are passed in the program's tree of one layer (``mix/q_a/w``,
``ffn/router/w``, ``ffn/experts/gate/w`` of shape (held, d, f) ...) and
adapters as its flat pairs (``"mix/q_a": {"A", "B", "rank"}``): that is a
naming of the same numbers, not a computation.

Departures from the published model, each immaterial with random weights
or outside what the share computes:

* Rope rotates the two halves of the rope dimensions (``rotate_half``);
  the published checkpoint stores them interleaved and its modeling code
  permutes them first.  The two differ by a fixed permutation of the
  columns of ``q_b`` and ``kv_a``.
* The MoE layer is computed dropless, as V3 is published, as a dense loop
  over the experts held: experts outside ``[expert_offset, expert_offset
  + experts_held)`` are masked out of the result, which is the share's
  part of the layer (the shared expert is added in full).
* ``e_score_correction_bias`` is ``ffn/router/select_bias``; the top
  groups and experts are taken with a descending sort, so exact ties may
  break otherwise than in the published ``topk`` (they do not occur with
  random weights).
* Normalising the top-k weights adds no epsilon (the published code adds
  1e-20, below float32's resolution of the sum).
* The MTP module, the embedding and the head are not part of the share.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ALPHA = 16.0


def yarn_mscale(factor: float, mscale: float) -> float:
    if factor <= 1:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, base: float, rope_scaling: dict) -> jnp.ndarray:
    """DeepSeek's ``DeepseekV3YarnRotaryEmbedding`` inverse frequencies."""
    factor = rope_scaling["factor"]
    orig = rope_scaling["original_max_position_embeddings"]

    def correction_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(rope_scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope_scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    freq_extra = 1.0 / base ** (2 * i / dim)
    freq_inter = freq_extra / factor
    mask = 1.0 - jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return freq_inter * (1.0 - mask) + freq_extra * mask


def rope_tables(config: dict, positions: jnp.ndarray):
    """cos, sin of shape (S, rope_dim / 2)."""
    dim, base = config["qk_rope_head_dim"], float(config["rope_theta"])
    rs = config.get("rope_scaling")
    if rs:
        inv_freq = yarn_inv_freq(dim, base, rs)
        m = (yarn_mscale(rs["factor"], rs.get("mscale", 1))
             / yarn_mscale(rs["factor"], rs.get("mscale_all_dim", 0)))
    else:
        inv_freq = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32)
                                  / dim)
        m = 1.0
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def softmax_scale(config: dict) -> float:
    scale = (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]) ** -0.5
    rs = config.get("rope_scaling")
    if rs and rs.get("mscale_all_dim"):
        scale *= yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return scale


def rotate(x, cos, sin):
    """x (B, S, H, r): halves (x1, x2) -> (x1 cos - x2 sin, x1 sin + x2 cos)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def linear(x, w, pair=None):
    """x @ w, plus (alpha / rank) * x A^T B^T for a LoRA pair."""
    y = x @ w
    if pair is not None:
        r = jnp.maximum(jnp.asarray(pair["rank"], jnp.float32), 1.0)
        y = y + (ALPHA / r) * ((x @ pair["A"].T) @ pair["B"].T)
    return y


def mla(x, p, lora, config, positions, q_block=None):
    """Multi-head latent attention, causal, over x (B, S, d)."""
    b, s, _ = x.shape
    h = config["num_attention_heads"]
    nope, rope_d = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    vd, kvr = config["v_head_dim"], config["kv_lora_rank"]
    eps = config["rms_norm_eps"]
    hx = rms_norm(x, p["ln"]["scale"], eps)
    cq = rms_norm(linear(hx, p["q_a"]["w"], lora.get("mix/q_a")),
                  p["q_ln"]["scale"], eps)
    q = linear(cq, p["q_b"]["w"], lora.get("mix/q_b")).reshape(
        b, s, h, nope + rope_d)
    ckv = linear(hx, p["kv_a"]["w"], lora.get("mix/kv_a"))
    latent = rms_norm(ckv[..., :kvr], p["kv_ln"]["scale"], eps)
    kv = linear(latent, p["kv_b"]["w"], lora.get("mix/kv_b")).reshape(
        b, s, h, nope + vd)
    cos, sin = rope_tables(config, positions)
    q_rope = rotate(q[..., nope:], cos, sin)
    k_rope = rotate(ckv[..., None, kvr:], cos, sin)
    q = jnp.concatenate([q[..., :nope], q_rope], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (b, s, h, rope_d))], -1)
    v = kv[..., nope:]
    scale = softmax_scale(config)
    q_block = q_block or s
    outs = []
    for i in range(0, s, q_block):
        qi = q[:, i:i + q_block]
        scores = jnp.einsum("bqhd,bthd->bhqt", qi, k) * scale
        causal = positions[None, :] <= positions[i:i + q_block, None]
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        outs.append(jnp.einsum("bhqt,bthd->bqhd", probs, v))
    out = jnp.concatenate(outs, axis=1).reshape(b, s, h * vd)
    return linear(out, p["o"]["w"], lora.get("mix/o"))


def swiglu(x, gate, up, down, lora, prefix):
    return linear(jax.nn.silu(linear(x, gate, lora.get(prefix + "gate")))
                  * linear(x, up, lora.get(prefix + "up")),
                  down, lora.get(prefix + "down"))


def route(hx, router, config):
    """V3's ``noaux_tc`` gate over all routed experts: (weights (T, k),
    ids (T, k)) for tokens hx (T, d)."""
    n_routed = config["n_routed_experts"]
    groups, topk_groups = config["n_group"], config["topk_group"]
    k = config["num_experts_per_tok"]
    scores = jax.nn.sigmoid(hx @ router["w"])
    choice = scores + router["select_bias"]
    grouped = choice.reshape(-1, groups, n_routed // groups)
    group_score = jnp.sort(grouped, axis=-1)[..., -2:].sum(-1)
    best = jnp.argsort(-group_score, axis=-1)[:, :topk_groups]
    kept = (best[:, :, None] == jnp.arange(groups)[None, None]).any(1)
    masked = jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(
        -1, n_routed)
    ids = jnp.argsort(-masked, axis=-1)[:, :k]
    w = jnp.take_along_axis(scores, ids, axis=-1)
    if config["norm_topk_prob"]:
        w = w / w.sum(-1, keepdims=True)
    return w * config["routed_scaling_factor"], ids


def moe(x, p, lora, config):
    """The share's part of the MoE layer, dropless: every held expert over
    every token, weighted by its routing weight (0 where not chosen), plus
    the shared expert."""
    b, s, d = x.shape
    hx = rms_norm(x, p["ln"]["scale"], config["rms_norm_eps"]).reshape(-1, d)
    w, ids = route(hx, p["router"], config)
    y = swiglu(hx, p["shared"]["gate"]["w"], p["shared"]["up"]["w"],
               p["shared"]["down"]["w"], lora, "ffn/shared/")
    ex = p["experts"]
    for j in range(config["experts_held"]):
        expert = config["expert_offset"] + j
        weight = jnp.sum(jnp.where(ids == expert, w, 0.0), -1)
        one = {name: {"A": pair["A"][j], "B": pair["B"][j],
                      "rank": pair["rank"]}
               for name, pair in lora.items()
               if name.startswith("ffn/experts/")}
        out = swiglu(hx, ex["gate"]["w"][j], ex["up"]["w"][j],
                     ex["down"]["w"][j], one, "ffn/experts/")
        y = y + weight[:, None] * out
    return y.reshape(b, s, d)


def block(x, p, lora, config, positions, q_block=None):
    """One decoder layer: x + MLA, then + the dense FFN or the MoE share."""
    x = x + mla(x, p["mix"], lora, config, positions, q_block)
    ffn = p["ffn"]
    if "router" in ffn:
        return x + moe(x, ffn, lora, config)
    hx = rms_norm(x, ffn["ln"]["scale"], config["rms_norm_eps"])
    return x + swiglu(hx, ffn["gate"]["w"], ffn["up"]["w"], ffn["down"]["w"],
                      lora, "ffn/")


def forward(x, layers, config, q_block=None):
    """x (B, S, d) through ``layers``: [(params, adapters), ...] of one
    layer each, positions 0..S-1."""
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(x, jnp.float32)
        positions = jnp.arange(x.shape[1])
        for p, lora in layers:
            x = block(x, p, lora, config, positions, q_block)
        return x
