"""Expert-parallel MoE dispatch with explicit all-to-all (mode 'ep_a2a').

The pjit 'sort' baseline leaves collective choice to XLA, which tends to
all-gather the (g, E, C, d) dispatch tensor across the model axis.  This
module instead expresses the GShard-style expert parallelism explicitly
inside ``shard_map``:

  1. each data shard routes its tokens locally into per-expert capacity
     slots (E experts, C_local capacity each),
  2. ``all_to_all`` over the model axis swaps the expert dimension for the
     shard dimension: each model shard receives the slots destined for
     ITS E/ep experts from every data peer,
  3. local expert matmuls,
  4. the inverse all_to_all returns outputs to token owners.

Per-device a2a volume = 2 * C_local * E * d * bytes -- independent of the
expert count replication that the all-gather pays.  Used as the SSPerf
iteration A6 for deepseek-v3 (``ArchConfig.moe_mode = 'ep_a2a'``).

Restrictions (checked): the experts held (``n_experts`` plus padding)
divisible by the model-axis size,
tokens divisible by the data sharding; LoRA per-expert adapters must be
sharded over 'model' (rules.adapter_specs does this).
"""
from __future__ import annotations

from typing import Mapping

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.compat import axis_size, shard_map_no_check

from .common import norm
from .moe import (_capacity, _route, _shared, _slots, _swiglu_experts,
                  router_logits)

Array = jax.Array


def moe_forward_ep_wrapped(p: Mapping, lora: Mapping | None, x: Array,
                           cfg, alpha: float = 16.0) -> Array:
    """pjit-callable wrapper: nests a shard_map over the ambient mesh.

    Tokens are resharded over (data..., model) for the dispatch (that
    reshard is part of the measured cost), expert weights stay on their
    'model' shards, everything else is replicated inside the region.
    """
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.get_abstract_mesh()
    axes = tuple(mesh.axis_names)
    da = tuple(a for a in axes if a != "model")
    tok_axes = da + ("model",)

    def spec_for(path_leaf):
        return P()

    pspec = jax.tree.map(lambda _: P(), p)
    pspec["experts"] = {k: {"w": P("model", None, None)}
                        for k in ("gate", "up", "down")}
    lspec = None
    if lora:
        lspec = {}
        for k, v in lora.items():
            if k.startswith("experts/"):
                lspec[k] = {"A": P("model", None, None),
                            "B": P("model", None, None), "rank": P()}
            else:
                lspec[k] = jax.tree.map(lambda _: P(), v)

    def body(p_l, lora_l, x_l):
        return moe_forward_ep(p_l, lora_l, x_l, cfg, model_axis="model",
                              alpha=alpha)

    fn = shard_map_no_check(body, mesh,
                            in_specs=(pspec, lspec,
                                      P(tok_axes, None, None)),
                            out_specs=P(tok_axes, None, None))
    return fn(p, lora, x)


def moe_forward_ep(p: Mapping, lora: Mapping | None, x: Array, cfg, *,
                   model_axis: str = "model", alpha: float = 16.0) -> Array:
    """shard_map body: x is the LOCAL shard (b_local, s, d); expert weights
    in ``p`` are the LOCAL expert slice (E/ep, d, f): model shard ``i``
    holds experts ``expert_offset + i * E/ep`` onwards.  Tokens are routed
    over all ``n_routed`` experts with the same router as the sort path.
    Must run inside a shard_map over (data..., model) with tokens sharded
    on data and experts on model."""
    lora = lora or {}
    ep = axis_size(model_axis)
    e = cfg.n_experts + cfg.moe_pad_experts
    if e % ep:
        raise ValueError(f"{e} experts held over {ep} model shards")
    b, s, d = x.shape
    n = b * s
    cap = _capacity(cfg, n)

    with jax.named_scope("moe.route"):
        h = norm(p["ln"], x, cfg.norm_eps)
        flat = h.reshape(n, d)
        # router weights are replicated; logits over ALL routed experts
        logits = router_logits(p["router"], flat)
        w, ix = _route(cfg, logits, p["router"].get("select_bias"))

    with jax.named_scope("moe.dispatch"):
        # local capacity dispatch into every held expert's slots (the sort
        # path's slots), then an a2a over the model axis: each peer
        # receives the slots destined for ITS local experts from every
        # peer.  tiled semantics:
        # (e, cap, d) --split ax0 / concat ax1--> (e/ep, ep*cap, d)
        rows, cols, keep, token_of, order = _slots(cfg, ix, cap)
        vals = flat[token_of] * keep[:, None].astype(flat.dtype)
        einp = jnp.zeros((e, cap, d), flat.dtype).at[rows, cols].add(vals)
        einp = lax.all_to_all(einp, model_axis, split_axis=0,
                              concat_axis=1, tiled=True)

    with jax.named_scope("moe.experts"):
        eo = _swiglu_experts(p, lora, einp[None], alpha)  # (1,e/ep,ep*cap,d)
        shared = _shared(p, lora, flat, alpha)

    with jax.named_scope("moe.combine"):
        # inverse a2a back to token owners:
        # (e/ep, ep*cap, d) --split ax1 / concat ax0--> (e, cap, d)
        eo = lax.all_to_all(eo[0], model_axis, split_axis=1, concat_axis=0,
                            tiled=True)
        gathered = eo[rows, cols] * keep[:, None].astype(eo.dtype)
        wflat = w.reshape(-1)[order]
        y = jnp.zeros((n, d), eo.dtype).at[token_of].add(
            gathered * wflat[:, None].astype(eo.dtype))
        y = (y + shared).reshape(b, s, d)
    if cfg.post_block_norm:
        y = norm(p["post_ln"], y, cfg.norm_eps)
    return y
