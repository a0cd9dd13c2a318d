"""Top-level Model: config -> params/adapters/caches + train & serve fns.

The one class every launcher, test, benchmark and dry-run goes through.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Mapping

import jax
import jax.numpy as jnp
from jax import lax

from repro.fl.client import softmax_xent  # reuse CE impl
from repro.lora import init_pair
from repro.obs import get_registry
from .common import (dense, dense_init, dtype_of, embed, embed_init, norm,
                     norm_init, softcap, unembed)
from .transformer import (block_init_cache, stage_forward, stage_init,
                          stage_lora_init)

Array = jax.Array
PyTree = Any


@dataclass(frozen=True)
class Model:
    cfg: Any
    remat: Any = True            # False | True/"full" | "dots"
    mla_absorbed: bool = False   # perf variant (EXPERIMENTS.md SSPerf)
    alpha: float = 16.0

    # ------------------------------------------------------------ params ----
    def init(self, key: Array) -> PyTree:
        cfg = self.cfg
        dt = dtype_of(cfg)
        keys = jax.random.split(key, 8 + len(cfg.stages)
                                + len(cfg.encoder_stages))
        p: dict = {"embed": embed_init(keys[0], cfg.vocab_size, cfg.d_model,
                                       dt)}
        kix = 1
        p["stages"] = tuple(
            stage_init(keys[kix + i], cfg, s)
            for i, s in enumerate(cfg.stages))
        kix += len(cfg.stages)
        p["final_ln"] = norm_init(cfg)
        if not cfg.tie_embeddings:
            p["lm_head"] = dense_init(keys[kix], cfg.d_model,
                                      cfg.vocab_size, dt)
        kix += 1
        if cfg.is_encdec:
            p["enc"] = {
                "stages": tuple(stage_init(keys[kix + i], cfg, s)
                                for i, s in enumerate(cfg.encoder_stages)),
                "final_ln": norm_init(cfg),
                "pos": jax.random.normal(
                    keys[kix + len(cfg.encoder_stages)],
                    (cfg.encoder_seq, cfg.d_model), dt) * 0.02,
            }
            kix += len(cfg.encoder_stages)
        if cfg.frontend != "none":
            p["frontend"] = {"proj": dense_init(
                jax.random.fold_in(keys[-1], 1), cfg.frontend_dim,
                cfg.d_model, dt)}
        if cfg.mtp_depth:
            from .transformer import block_init
            spec = cfg.stages[-1].unit[-1]
            p["mtp"] = {
                "proj": dense_init(jax.random.fold_in(keys[-1], 2),
                                   2 * cfg.d_model, cfg.d_model, dt),
                "block": block_init(jax.random.fold_in(keys[-1], 3), cfg,
                                    spec),
                "ln": norm_init(cfg),
            }
        return p

    # ---------------------------------------------------------- adapters ----
    def init_adapters(self, key: Array, r_max: int | None = None,
                      rank: int | None = None) -> PyTree:
        cfg = self.cfg
        r_max = r_max or cfg.lora_r_max
        rank = rank if rank is not None else r_max
        ad: dict = {"stages": tuple(
            stage_lora_init(jax.random.fold_in(key, i), cfg, s, r_max, rank)
            for i, s in enumerate(cfg.stages))}
        if cfg.is_encdec:
            ad["enc"] = {"stages": tuple(
                stage_lora_init(jax.random.fold_in(key, 100 + i), cfg, s,
                                r_max, rank)
                for i, s in enumerate(cfg.encoder_stages))}
        if cfg.frontend != "none":
            ad["frontend"] = {"proj": init_pair(
                jax.random.fold_in(key, 200), cfg.d_model, cfg.frontend_dim,
                r_max, rank)}
        return ad

    # ----------------------------------------------------------- encoder ----
    def _encode(self, params, adapters, frames):
        cfg = self.cfg
        enc = params["enc"]
        x = dense(params["frontend"]["proj"],
                  frames.astype(dtype_of(cfg)),
                  (adapters or {}).get("frontend", {}).get("proj"),
                  self.alpha)
        s = x.shape[1]
        x = x + enc["pos"][:s][None]
        enc_lora = (adapters or {}).get("enc")
        for i, stage in enumerate(cfg.encoder_stages):
            slora = enc_lora["stages"][i] if enc_lora else None
            x, _ = stage_forward(enc["stages"][i], slora, x, cfg, stage,
                                 mode="full", positions=jnp.arange(s),
                                 alpha=self.alpha, remat=self.remat)
        return norm(enc["final_ln"], x, cfg.norm_eps)

    # ----------------------------------------------------------- forward ----
    def _embed_inputs(self, params, adapters, batch):
        """Token embeddings (+ VLM patch prefix). Returns (x, n_prefix)."""
        cfg = self.cfg
        x = embed(params["embed"], batch["tokens"])
        n_prefix = 0
        if cfg.frontend == "vision_patches":
            proj = dense(params["frontend"]["proj"],
                         batch["patches"].astype(dtype_of(cfg)),
                         (adapters or {}).get("frontend", {}).get("proj"),
                         self.alpha)
            x = jnp.concatenate([proj.astype(x.dtype), x], axis=1)
            n_prefix = proj.shape[1]
        return x, n_prefix

    def forward(self, params, adapters, batch, mode: str = "full",
                capacity: int | None = None):
        """Full-sequence forward.  Returns (logits, caches or None)."""
        cfg = self.cfg
        enc_out = None
        if cfg.is_encdec:
            enc_out = self._encode(params, adapters, batch["frames"])
        x, n_prefix = self._embed_inputs(params, adapters, batch)
        s = x.shape[1]
        positions = jnp.arange(s)
        caches = [] if mode == "prefill" else None
        for i, stage in enumerate(cfg.stages):
            slora = adapters.get("stages")[i] if adapters else None
            x, c = stage_forward(params["stages"][i], slora, x, cfg, stage,
                                 mode=mode, positions=positions,
                                 enc_out=enc_out, alpha=self.alpha,
                                 remat=self.remat,
                                 mla_absorbed=self.mla_absorbed,
                                 capacity=capacity)
            if mode == "prefill":
                caches.append(c)
        x = norm(params["final_ln"], x, cfg.norm_eps)
        if n_prefix:
            x = x[:, n_prefix:]
        logits = (unembed(params["embed"], x) if cfg.tie_embeddings
                  else dense(params["lm_head"], x))
        logits = softcap(logits, cfg.final_softcap)
        return logits, (tuple(caches) if caches is not None else None)

    # -------------------------------------------------------------- loss ----
    def loss(self, params, adapters, batch) -> Array:
        cfg = self.cfg
        logits, _ = self.forward(params, adapters, batch, mode="full")
        tok = batch["tokens"]
        lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), -1)
        nll = -jnp.take_along_axis(lp, tok[:, 1:, None], axis=-1)[..., 0]
        main = jnp.mean(nll)
        if cfg.mtp_depth:
            main = main + 0.3 * self._mtp_loss(params, adapters, batch,
                                               logits)
        return main

    def _mtp_loss(self, params, adapters, batch, logits) -> Array:
        """DeepSeek-V3 multi-token prediction (depth 1): predict t+2 from
        hidden(t) combined with embedding(t+1)."""
        cfg = self.cfg
        from .transformer import block_forward
        tok = batch["tokens"]
        h = embed(params["embed"], tok)          # cheap re-embed (stop-grad)
        nxt = embed(params["embed"], tok[:, 1:])
        cat = jnp.concatenate([norm(params["mtp"]["ln"], h[:, :-1],
                                    cfg.norm_eps), nxt], -1)
        x = dense(params["mtp"]["proj"], cat)
        spec = cfg.stages[-1].unit[-1]
        x, _ = block_forward(params["mtp"]["block"], None, x, cfg, spec,
                             mode="full",
                             positions=jnp.arange(x.shape[1]))
        mlogits = (unembed(params["embed"], x) if cfg.tie_embeddings
                   else dense(params["lm_head"], x))
        lp = jax.nn.log_softmax(mlogits[:, :-1].astype(jnp.float32), -1)
        nll = -jnp.take_along_axis(lp, tok[:, 2:, None], axis=-1)[..., 0]
        return jnp.mean(nll)

    # ------------------------------------------------------------- serve ----
    def init_cache(self, batch_size: int, seq_len: int) -> PyTree:
        cfg = self.cfg
        dt = dtype_of(cfg)
        caches = []
        for stage in cfg.stages:
            unit = {}
            for i, spec in enumerate(stage.unit):
                c1 = block_init_cache(cfg, spec, batch_size, seq_len, dt)
                unit[f"b{i}"] = jax.tree.map(
                    lambda x: jnp.broadcast_to(
                        x[None], (stage.repeat,) + x.shape), c1)
            caches.append(unit)
        return tuple(caches)

    def prefill(self, params, adapters, batch,
                capacity: int | None = None):
        logits, caches = self.forward(params, adapters, batch,
                                      mode="prefill", capacity=capacity)
        return logits[:, -1], caches

    def decode_step(self, params, adapters, caches, token: Array,
                    pos: Array):
        """token: (B,) int32; pos: scalar int32 (absolute position)."""
        cfg = self.cfg
        x = embed(params["embed"], token[:, None])
        new_caches = []
        for i, stage in enumerate(cfg.stages):
            slora = adapters.get("stages")[i] if adapters else None
            x, c = stage_forward(params["stages"][i], slora, x, cfg, stage,
                                 mode="decode", caches=caches[i], pos=pos,
                                 alpha=self.alpha, remat=False,
                                 mla_absorbed=self.mla_absorbed)
            new_caches.append(c)
        x = norm(params["final_ln"], x, cfg.norm_eps)
        logits = (unembed(params["embed"], x) if cfg.tie_embeddings
                  else dense(params["lm_head"], x))
        logits = softcap(logits, cfg.final_softcap)
        return logits[:, 0], tuple(new_caches)


_EXPERTS_HELD = get_registry().gauge(
    "moe_experts_held", "routed experts each expert layer of the last MoE "
    "model built holds, by the router's width", labelnames=("routed",))


def make_model(cfg, remat=True, mla_absorbed: bool = False) -> Model:
    if any(b.ffn == "moe" for s in cfg.stages for b in s.unit):
        _EXPERTS_HELD.labels(routed=str(cfg.n_routed)).set(cfg.n_experts)
    return Model(cfg=cfg, remat=remat, mla_absorbed=mla_absorbed)
