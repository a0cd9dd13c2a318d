"""Shared building blocks for the big-model zoo.

Conventions (differ from the small paper nets, chosen for TPU einsums):
* dense kernels are stored ``(..., fan_in, fan_out)`` and applied with
  ``einsum('...i,io->...o')`` -- leading dims are scan/stack axes.
* LoRA pairs keep the ``repro.lora`` layout: A ``(..., r_max, fan_in)``,
  B ``(..., fan_out, r_max)``.
* activations/matmuls run in the config dtype (bf16), softmax/norms in f32.
"""
from __future__ import annotations

import math
from typing import Any, Mapping

import jax
import jax.numpy as jnp
from jax import lax

from repro.lora import DEFAULT_ALPHA

Array = jax.Array
PyTree = Any


def dtype_of(cfg) -> jnp.dtype:
    return jnp.dtype(cfg.dtype)


# ----------------------------------------------------------------- dense ----
def dense_init(key, fan_in: int, fan_out: int, dtype, *, bias: bool = False,
               scale: float | None = None) -> dict:
    s = (1.0 / fan_in) ** 0.5 if scale is None else scale
    p = {"w": jax.random.normal(key, (fan_in, fan_out), dtype) * s}
    if bias:
        p["b"] = jnp.zeros((fan_out,), dtype)
    return p


def dense(p: Mapping, x: Array, lora_pair: Mapping | None = None,
          alpha: float = DEFAULT_ALPHA) -> Array:
    y = jnp.einsum("...i,io->...o", x, p["w"])
    if "b" in p:
        y = y + p["b"]
    if lora_pair is not None:
        scale = alpha / jnp.maximum(
            lora_pair["rank"].astype(jnp.float32), 1.0)
        ax = jnp.einsum("...i,ri->...r", x, lora_pair["A"].astype(x.dtype))
        y = y + jnp.einsum("...r,or->...o", ax,
                           lora_pair["B"].astype(x.dtype)) * scale.astype(
                               x.dtype)
    return y


# ----------------------------------------------------------------- norms ----
def rmsnorm_init(dim: int, dtype=jnp.float32) -> dict:
    return {"scale": jnp.ones((dim,), dtype)}


def rmsnorm(p: Mapping, x: Array, eps: float = 1e-6) -> Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    return out.astype(x.dtype)


def layernorm_init(dim: int, dtype=jnp.float32) -> dict:
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,),
                                                                dtype)}


def norm_init(cfg, dim: int | None = None) -> dict:
    dim = dim or cfg.d_model
    if cfg.mlp_act == "gelu_plain":      # whisper family uses LayerNorm
        return layernorm_init(dim)
    return rmsnorm_init(dim)


def norm(p: Mapping, x: Array, eps: float = 1e-6) -> Array:
    if "bias" in p:                      # LayerNorm
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mean) * lax.rsqrt(var + eps) * p["scale"].astype(
            jnp.float32) + p["bias"].astype(jnp.float32)
        return out.astype(x.dtype)
    return rmsnorm(p, x, eps)


def softcap(x: Array, cap: float) -> Array:
    if not cap:
        return x
    return (cap * jnp.tanh(x.astype(jnp.float32) / cap)).astype(x.dtype)


# ------------------------------------------------------------------ rope ----
def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature ``0.1 m ln s + 1`` (1 for s <= 1)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_freqs(head_dim: int, theta: float, scaling=None) -> Array:
    """Rope's inverse frequencies; with ``scaling`` (a ``YaRN``) those of
    DeepSeek's YaRN: interpolated by ``factor`` below the dimension where
    ``beta_slow`` rotations fit the original context, kept above the one
    where ``beta_fast`` do, and ramped linearly between."""
    extra = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                             / head_dim))
    if scaling is None:
        return extra

    def dim_of(rotations):
        return (head_dim * math.log(scaling.original_max_position_embeddings
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(scaling.beta_fast)), 0)
    high = min(math.ceil(dim_of(scaling.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return extra / scaling.factor * ramp + extra * (1.0 - ramp)


def apply_rope(x: Array, positions: Array, theta: float,
               kind: str = "full", scaling=None) -> Array:
    """x: (..., seq, heads, head_dim); positions: broadcastable (..., seq).
    ``scaling``: None or a ``YaRN``, whose cos and sin are also scaled by
    ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``."""
    if kind == "none":
        return x
    hd = x.shape[-1]
    rot = hd if kind == "full" else hd // 2
    xr, xp = x[..., :rot], x[..., rot:]
    freqs = rope_freqs(rot, theta, scaling)                  # (rot/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs   # (..., s, rot/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    if scaling is not None:
        m = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(xr.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                          axis=-1).astype(x.dtype)
    return jnp.concatenate([out, xp], axis=-1) if kind == "half" else out


# ------------------------------------------------------------- embedding ----
def embed_init(key, vocab: int, dim: int, dtype) -> dict:
    return {"table": jax.random.normal(key, (vocab, dim), dtype) * 0.02}


def embed(p: Mapping, ids: Array) -> Array:
    return jnp.take(p["table"], ids, axis=0)


def unembed(p: Mapping, x: Array) -> Array:
    return jnp.einsum("...d,vd->...v", x, p["table"])
