"""Mixture-of-Experts FFN with two dispatch strategies.

* ``sort``   -- capacity-based sort/scatter routing under plain pjit
             (global semantics; XLA SPMD inserts the collectives).  The
             baseline for every MoE arch.
* ``ep_a2a`` -- explicit expert-parallel all-to-all dispatch inside
             shard_map (tokens sharded on the data axis, experts on the
             model axis).  The SSPerf hillclimb variant for deepseek-v3;
             see ``repro/models/moe_ep.py``.

The layer holds ``cfg.n_experts`` routed experts, ids ``[expert_offset,
expert_offset + n_experts)`` of the router's ``cfg.n_routed``: one chip's
expert-parallel share holds a slice, a whole model all of them.  Every
token is routed over all routed experts; the layer computes its held
experts' part of the result and the shared experts' whole.  The stages
run under the named scopes ``moe.route``, ``moe.dispatch``,
``moe.experts`` and ``moe.combine``.

Per-expert LoRA: each held expert's gate/up/down kernels (E, d, f) carry
an adapter with a leading expert axis -- A (E, r, d), B (E, f, r).  RBLA
masks broadcast over the expert axis unchanged.
"""
from __future__ import annotations

import math
from typing import Mapping

import jax
import jax.numpy as jnp

from .common import dense, dense_init, norm, norm_init

Array = jax.Array


def moe_init(key, cfg) -> dict:
    d, f = cfg.d_model, cfg.moe_d_ff or cfg.d_ff
    # the experts held; the physical count may be padded so it divides the
    # model axis (padded experts are never routed to -- dead weights,
    # EP-shardable)
    e = cfg.n_experts + cfg.moe_pad_experts
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 6)
    s = (1.0 / d) ** 0.5
    router = {"w": jax.random.normal(ks[0], (d, cfg.n_routed),
                                     jnp.float32) * s}
    if cfg.router_scoring == "sigmoid":
        # DeepSeek-V3's e_score_correction_bias: added to the scores for
        # the choice of experts only, never to the weights
        router["select_bias"] = jax.random.normal(
            jax.random.fold_in(key, 7), (cfg.n_routed,), jnp.float32) * 0.05
    p = {
        "ln": norm_init(cfg),
        "router": router,
        "experts": {
            "gate": {"w": jax.random.normal(ks[1], (e, d, f), dt) * s},
            "up": {"w": jax.random.normal(ks[2], (e, d, f), dt) * s},
            "down": {"w": jax.random.normal(ks[3], (e, f, d), dt) *
                     (1.0 / f) ** 0.5},
        },
    }
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        p["shared"] = {
            "gate": dense_init(ks[4], d, fs, dt),
            "up": dense_init(ks[5], d, fs, dt),
            "down": dense_init(ks[4], fs, d, dt),
        }
    if cfg.post_block_norm:
        p["post_ln"] = norm_init(cfg)
    return p


MOE_LORA_TARGETS = ("experts/gate", "experts/up", "experts/down")


def expert_dense(w: Array, x: Array, lora_pair: Mapping | None = None,
                 alpha: float = 16.0) -> Array:
    """x: (G, E, C, in), w: (E, in, out) -> (G, E, C, out) with per-expert
    LoRA (A (E, r, in), B (E, out, r))."""
    y = jnp.einsum("geci,eio->geco", x, w)
    if lora_pair is not None:
        scale = alpha / jnp.maximum(lora_pair["rank"].astype(jnp.float32),
                                    1.0)
        ax = jnp.einsum("geci,eri->gecr", x, lora_pair["A"].astype(x.dtype))
        y = y + jnp.einsum("gecr,eor->geco", ax,
                           lora_pair["B"].astype(x.dtype)) * scale.astype(
                               x.dtype)
    return y


def router_logits(router: Mapping, h: Array) -> Array:
    """Router logits (..., n_routed) of h (..., d) in float32.  The matmul
    runs at full f32 precision: the default rounds its operands through
    bfloat16 on the TPU's MXU, which moves scores enough to flip routes
    (DeepSeek-V3 publishes an f32 gate)."""
    return jnp.einsum("...d,de->...e", h.astype(jnp.float32), router["w"],
                      precision=jax.lax.Precision.HIGHEST)


def _route(cfg, logits: Array, select_bias: Array | None = None):
    """Top-k routing over all ``cfg.n_routed`` experts.  Returns (weights
    (..., K), expert ids (..., K)).

    ``softmax`` scoring picks the top-k probabilities.  ``sigmoid``
    scoring (DeepSeek-V3's ``noaux_tc``) picks by the sigmoid plus
    ``select_bias``, within the ``router_topk_groups`` of
    ``router_groups`` groups whose top two such scores sum highest, and
    weights by the sigmoid alone.  Weights are normalised over the k and
    scaled by ``router_scale``.
    """
    k = cfg.experts_per_token
    logits = logits.astype(jnp.float32)
    if cfg.router_scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    choice = scores if select_bias is None else scores + select_bias
    if cfg.router_groups > 1:
        grouped = choice.reshape(choice.shape[:-1] + (cfg.router_groups, -1))
        top2 = jax.lax.top_k(grouped, min(2, grouped.shape[-1]))[0]
        _, best = jax.lax.top_k(top2.sum(-1), cfg.router_topk_groups)
        kept = jnp.any(best[..., None] == jnp.arange(cfg.router_groups),
                       axis=-2)
        choice = jnp.where(kept[..., None], grouped,
                           -jnp.inf).reshape(choice.shape)
    _, ix = jax.lax.top_k(choice, k)
    w = jnp.take_along_axis(scores, ix, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-9)
    return w * cfg.router_scale, ix


def _capacity(cfg, n_tokens: int) -> int:
    """Slots per held expert: its share of ``n_tokens`` routing choices
    over all routed experts, times ``capacity_factor``."""
    return int(math.ceil(n_tokens * cfg.experts_per_token / cfg.n_routed
                         * cfg.capacity_factor))


def _slots(cfg, ix: Array, cap: int):
    """Capacity slots of one group's choices ``ix`` (n, K) in the experts
    held, ids ``[expert_offset, expert_offset + n_experts)``.  Choices of
    other experts, and those past an expert's ``cap``, get no slot
    (``keep`` False).  Returns (rows, cols, keep, token_of, order) over the
    n*K choices sorted by held expert."""
    n, k = ix.shape
    e = cfg.n_experts + cfg.moe_pad_experts
    local = ix.reshape(-1) - cfg.expert_offset
    held = (local >= 0) & (local < cfg.n_experts)
    ae = jnp.where(held, local, e)               # not held: sorted last
    order = jnp.argsort(ae)
    ae_sorted = ae[order]
    pos_in_expert = jnp.arange(n * k) - jnp.searchsorted(
        ae_sorted, ae_sorted, side="left")
    keep = held[order] & (pos_in_expert < cap)
    rows = jnp.where(keep, ae_sorted, e - 1)
    cols = jnp.where(keep, pos_in_expert, cap - 1)
    return rows, cols, keep, order // k, order


def _swiglu_experts(p: Mapping, lora: Mapping, einp: Array,
                    alpha: float) -> Array:
    """The held experts' SwiGLU over their slots (G, E, C, d)."""
    eg = expert_dense(p["experts"]["gate"]["w"], einp,
                      lora.get("experts/gate"), alpha)
    eu = expert_dense(p["experts"]["up"]["w"], einp,
                      lora.get("experts/up"), alpha)
    return expert_dense(p["experts"]["down"]["w"], jax.nn.silu(eg) * eu,
                        lora.get("experts/down"), alpha)


def _shared(p: Mapping, lora: Mapping, flat: Array, alpha: float) -> Array:
    """The shared experts' SwiGLU over every token (0 where none)."""
    if "shared" not in p:
        return jnp.zeros_like(flat)
    sh = p["shared"]
    return dense(sh["down"],
                 jax.nn.silu(dense(sh["gate"], flat, lora.get("shared/gate"),
                                   alpha)) *
                 dense(sh["up"], flat, lora.get("shared/up"), alpha),
                 lora.get("shared/down"), alpha)


def moe_forward(p: Mapping, lora: Mapping | None, x: Array, cfg,
                alpha: float = 16.0, n_groups: int = 32) -> Array:
    """Capacity-based sort routing with group-local dispatch.

    Tokens are split into ``n_groups`` routing groups (GShard-style); each
    group routes/scatters independently, so under pjit the scatter stays
    local to the data shard holding the group -- the (g, E, C, d) dispatch
    tensor is sharded on g (data axes) and sliced on E (model axis) by the
    expert matmul.  Every token is routed over all routed experts; only
    the held experts' part of the result is computed, plus the shared
    experts once.  x: (B, S, d).
    """
    lora = lora or {}
    b, s, d = x.shape
    n = b * s
    g = max(1, min(n_groups, n))
    while n % g:
        g -= 1
    ng = n // g
    cap = _capacity(cfg, ng)

    with jax.named_scope("moe.route"):
        h = norm(p["ln"], x, cfg.norm_eps)
        flat = h.reshape(g, ng, d)
        logits = router_logits(p["router"], flat)
        w, ix = _route(cfg, logits, p["router"].get("select_bias"))

    with jax.named_scope("moe.dispatch"):
        rows, cols, keep, token_of, order = jax.vmap(
            lambda ix_g: _slots(cfg, ix_g, cap))(ix)

        def scatter(flat_g, rows_g, cols_g, keep_g, token_of_g):
            """One group's scatter into (E, C, d) expert slots."""
            vals = flat_g[token_of_g] * keep_g[:, None].astype(flat_g.dtype)
            return jnp.zeros((cfg.n_experts + cfg.moe_pad_experts, cap, d),
                             flat_g.dtype).at[rows_g, cols_g].add(vals)

        einp = jax.vmap(scatter)(flat, rows, cols, keep, token_of)

        if cfg.moe_mode == "ep_hint":
            # expert-parallel hint: pin the dispatch tensor's expert axis
            # to the 'model' mesh axis.  XLA SPMD then moves slots to
            # their expert owners with all-to-all instead of all-gathering
            # the whole (g, E, C, d) tensor (SSPerf iteration A6).
            from jax.sharding import PartitionSpec as P
            U = P.UNCONSTRAINED
            einp = jax.lax.with_sharding_constraint(
                einp, P(U, "model", U, U))

    with jax.named_scope("moe.experts"):
        eo = _swiglu_experts(p, lora, einp, alpha)          # (g,E,C,d)
        shared = _shared(p, lora, flat.reshape(n, d), alpha)

    with jax.named_scope("moe.combine"):
        def combine(eo_g, rows_g, cols_g, keep_g, token_of_g, w_g,
                    order_g):
            gathered = eo_g[rows_g, cols_g] * keep_g[:, None].astype(
                eo_g.dtype)
            wflat = w_g.reshape(-1)[order_g]
            contrib = gathered * wflat[:, None].astype(eo_g.dtype)
            return jnp.zeros((ng, d), eo_g.dtype).at[token_of_g].add(
                contrib)

        y = jax.vmap(combine)(eo, rows, cols, keep, token_of, w, order)
        y = (y.reshape(n, d) + shared).reshape(b, s, d)
    if cfg.post_block_norm:
        y = norm(p["post_ln"], y, cfg.norm_eps)
    return y
