"""Attention token mixers: GQA (with SWA windows, softcaps, QKV bias),
MLA (deepseek latent attention), and encoder-decoder cross attention.

Three execution modes share one parameter set:
* full   -- training / encoder forward over a whole sequence.
* prefill -- full + returns the KV cache for subsequent decode.
* decode -- one new token against the cache (ring buffer for SWA layers;
            latent cache for MLA).

Full-sequence attention is query-chunked (scan over query blocks) so the
score matrix never materializes at (S, S) -- the TPU-native flash-style
formulation (the Pallas kernel in ``repro.kernels`` covers the fused LoRA
matmul; chunked attention here stays in jnp for XLA fusion).
"""
from __future__ import annotations

from typing import Any, Mapping

import jax
import jax.numpy as jnp
from jax import lax

from .common import (apply_rope, dense, dense_init, norm, norm_init, softcap,
                     yarn_mscale)

Array = jax.Array

NEG_INF = -2.0 ** 30  # large-negative in f32, safe under bf16 casts


def _choose_q_chunk(s: int, target: int = 1024) -> int:
    if s <= target:
        return s
    for c in range(target, 0, -1):
        if s % c == 0:
            return c
    return s


# =================================================================== GQA ====
def gqa_init(key, cfg, block, d_model: int | None = None) -> dict:
    d = d_model or cfg.d_model
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 6)
    dt = jnp.dtype(cfg.dtype)
    p = {
        "ln": norm_init(cfg, d),
        "q": dense_init(ks[0], d, h * hd, dt, bias=cfg.qkv_bias),
        "k": dense_init(ks[1], d, kv * hd, dt, bias=cfg.qkv_bias),
        "v": dense_init(ks[2], d, kv * hd, dt, bias=cfg.qkv_bias),
        "o": dense_init(ks[3], h * hd, d, dt),
    }
    if block.cross_attn:
        p["xk"] = dense_init(ks[4], d, kv * hd, dt, bias=cfg.qkv_bias)
        p["xv"] = dense_init(ks[5], d, kv * hd, dt, bias=cfg.qkv_bias)
        p["xq"] = dense_init(ks[4], d, h * hd, dt, bias=cfg.qkv_bias)
        p["xo"] = dense_init(ks[5], h * hd, d, dt)
        p["xln"] = norm_init(cfg, d)
    if cfg.post_block_norm:
        p["post_ln"] = norm_init(cfg, d)
    return p


def gqa_lora_targets(block) -> tuple[str, ...]:
    t = ("q", "k", "v", "o")
    return t + ("xq", "xk", "xv", "xo") if block.cross_attn else t


def _split_heads(x: Array, n: int) -> Array:
    return x.reshape(x.shape[:-1] + (n, x.shape[-1] // n))


def _attend_chunked(q: Array, k: Array, v: Array, *, causal: bool,
                    window: int, q_positions: Array, k_positions: Array,
                    scale: float, cap: float) -> Array:
    """q: (B,S,K,G,D); k/v: (B,T,K,D); positions give absolute indices.

    Scans over query chunks; masks built from absolute positions so the
    same path serves training (q_pos == k_pos) and chunked prefill.
    """
    b, s, kh, g, d = q.shape
    qc = _choose_q_chunk(s)
    nq = s // qc
    q = q.reshape(b, nq, qc, kh, g, d)
    qpos = q_positions.reshape(nq, qc)

    def one_chunk(carry, inp):
        qi, qp = inp                               # (B,qc,K,G,D), (qc,)
        scores = jnp.einsum("bqkgd,btkd->bkgqt", qi, k) * scale
        scores = softcap(scores, cap)
        mask = jnp.ones((qc, k.shape[1]), bool)
        if causal:
            mask &= k_positions[None, :] <= qp[:, None]
        if window > 0:
            mask &= k_positions[None, :] > (qp[:, None] - window)
        scores = jnp.where(mask[None, None, None], scores.astype(jnp.float32),
                           NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        out = jnp.einsum("bkgqt,btkd->bqkgd", probs, v)
        return carry, out

    _, outs = lax.scan(one_chunk, None, (jnp.moveaxis(q, 1, 0), qpos))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, s, kh, g, v.shape[-1])
    return out


def _attend_decode(q: Array, k: Array, v: Array, valid: Array,
                   scale: float, cap: float) -> Array:
    """q: (B,1,K,G,D); k/v: (B,T,K,D); valid: (T,) bool."""
    scores = jnp.einsum("bqkgd,btkd->bkgqt", q, k) * scale
    scores = softcap(scores, cap)
    scores = jnp.where(valid[None, None, None, None],
                       scores.astype(jnp.float32), NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgqt,btkd->bqkgd", probs, v)


def gqa_forward(p: Mapping, lora: Mapping | None, x: Array, cfg, block, *,
                mode: str, positions: Array | None = None,
                cache: Mapping | None = None, pos: Array | None = None,
                enc_out: Array | None = None, alpha: float = 16.0,
                capacity: int | None = None):
    """Returns (y, new_cache or None).

    mode: 'full' | 'prefill' | 'decode'.  ``positions``: (S,) absolute
    positions for full/prefill.  ``pos``: scalar current index for decode.
    ``capacity``: prefill cache buffer length (>= S) so decode can continue
    in place.
    """
    lora = lora or {}
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g = h // kv
    scale = cfg.query_scale if cfg.query_scale is not None else hd ** -0.5
    hx = norm(p["ln"], x, cfg.norm_eps)

    def proj(name, inp):
        return dense(p[name], inp, lora.get(name), alpha)

    new_cache = {}
    if mode in ("full", "prefill"):
        s = x.shape[1]
        positions = (jnp.arange(s) if positions is None else positions)
        q = _split_heads(proj("q", hx), h)
        kk = _split_heads(proj("k", hx), kv)
        vv = _split_heads(proj("v", hx), kv)
        q = apply_rope(q, positions[None], cfg.rope_theta, cfg.rope_kind,
                       cfg.rope_scaling)
        kk = apply_rope(kk, positions[None], cfg.rope_theta, cfg.rope_kind,
                        cfg.rope_scaling)
        qg = q.reshape(q.shape[:2] + (kv, g, hd))
        out = _attend_chunked(qg, kk, vv, causal=block.causal,
                              window=block.window, q_positions=positions,
                              k_positions=positions, scale=scale,
                              cap=cfg.attn_softcap)
        out = out.reshape(x.shape[:2] + (h * hd,))
        y = dense(p["o"], out, lora.get("o"), alpha)
        if mode == "prefill":
            t_cap = capacity or s
            if block.window > 0:
                w = min(block.window, t_cap)
                # keep the last `w` positions in ring order slot = pos % w
                tail_k, tail_v, _ = _ring_from_tail(kk, vv, positions, w)
                new_cache = {"k": tail_k, "v": tail_v}
            else:
                pad = [(0, 0), (0, t_cap - s), (0, 0), (0, 0)]
                new_cache = {"k": jnp.pad(kk, pad), "v": jnp.pad(vv, pad)}
    else:  # decode
        q = _split_heads(proj("q", hx), h)
        kk = _split_heads(proj("k", hx), kv)
        vv = _split_heads(proj("v", hx), kv)
        posb = jnp.full((1, 1), pos)
        q = apply_rope(q, posb, cfg.rope_theta, cfg.rope_kind,
                       cfg.rope_scaling)
        kk = apply_rope(kk, posb, cfg.rope_theta, cfg.rope_kind,
                        cfg.rope_scaling)
        t = cache["k"].shape[1]
        # ring buffer slot; cache may be smaller than the window when the
        # serving context itself is shorter (t == min(window, seq_len))
        slot = (pos % t) if block.window > 0 else pos
        ck = lax.dynamic_update_slice_in_dim(cache["k"], kk, slot, axis=1)
        cv = lax.dynamic_update_slice_in_dim(cache["v"], vv, slot, axis=1)
        iota = jnp.arange(t)
        if block.window > 0:
            valid = iota < jnp.minimum(pos + 1, t)
        else:
            valid = iota <= pos
        qg = q.reshape(q.shape[:2] + (kv, g, hd))
        out = _attend_decode(qg, ck, cv, valid, scale, cfg.attn_softcap)
        out = out.reshape(x.shape[:2] + (h * hd,))
        y = dense(p["o"], out, lora.get("o"), alpha)
        new_cache = {"k": ck, "v": cv}

    # ---------------- cross attention (encoder-decoder) ----------------
    if block.cross_attn:
        if mode in ("full", "prefill"):
            assert enc_out is not None, "cross_attn requires encoder output"
            xk = _split_heads(proj("xk", enc_out), kv)
            xv = _split_heads(proj("xv", enc_out), kv)
            if mode == "prefill":
                new_cache["xk"], new_cache["xv"] = xk, xv
        else:
            xk, xv = cache["xk"], cache["xv"]
            new_cache["xk"], new_cache["xv"] = xk, xv
        hx2 = norm(p["xln"], x + y, cfg.norm_eps)
        xq = _split_heads(proj("xq", hx2), h)
        xqg = xq.reshape(xq.shape[:2] + (kv, g, hd))
        enc_t = xk.shape[1]
        xout = _attend_decode(xqg, xk, xv, jnp.ones((enc_t,), bool), scale,
                              cfg.attn_softcap)
        xout = xout.reshape(x.shape[:2] + (h * hd,))
        y = y + dense(p["xo"], xout, lora.get("xo"), alpha)

    if cfg.post_block_norm:
        y = norm(p["post_ln"], y, cfg.norm_eps)
    return y, (new_cache or None)


def _ring_from_tail(kk, vv, positions, w):
    """Arrange the last ``w`` timesteps of (B,T,KV,D) into ring order."""
    t = kk.shape[1]
    if t <= w:
        pad = [(0, 0), (0, w - t), (0, 0), (0, 0)]
        return (jnp.pad(kk, pad), jnp.pad(vv, pad), positions)
    last_pos = positions[-1]
    # positions kept: last_pos-w+1 .. last_pos ; slot = pos % w
    kept_k, kept_v = kk[:, -w:], vv[:, -w:]
    kept_pos = positions[-w:]
    slots = kept_pos % w
    order = jnp.argsort(slots)
    return kept_k[:, order], kept_v[:, order], kept_pos


def gqa_init_cache(cfg, block, batch: int, seq_len: int, dtype) -> dict:
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    t = min(block.window, seq_len) if block.window > 0 else seq_len
    c = {"k": jnp.zeros((batch, t, kv, hd), dtype),
         "v": jnp.zeros((batch, t, kv, hd), dtype)}
    if block.cross_attn:
        c["xk"] = jnp.zeros((batch, cfg.encoder_seq, kv, hd), dtype)
        c["xv"] = jnp.zeros((batch, cfg.encoder_seq, kv, hd), dtype)
    return c


# =================================================================== MLA ====
def mla_init(key, cfg, block) -> dict:
    d = cfg.d_model
    h = cfg.n_heads
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 6)
    qk_dim = cfg.qk_nope_dim + cfg.qk_rope_dim
    p = {
        "ln": norm_init(cfg, d),
        "q_a": dense_init(ks[0], d, cfg.q_lora_rank, dt),
        "q_ln": norm_init(cfg, cfg.q_lora_rank),
        "q_b": dense_init(ks[1], cfg.q_lora_rank, h * qk_dim, dt),
        "kv_a": dense_init(ks[2], d, cfg.kv_lora_rank + cfg.qk_rope_dim, dt),
        "kv_ln": norm_init(cfg, cfg.kv_lora_rank),
        "kv_b": dense_init(ks[3], cfg.kv_lora_rank,
                           h * (cfg.qk_nope_dim + cfg.v_head_dim), dt),
        "o": dense_init(ks[4], h * cfg.v_head_dim, d, dt),
    }
    return p


MLA_LORA_TARGETS = ("q_a", "q_b", "kv_a", "kv_b", "o")


def mla_softmax_scale(cfg) -> float:
    """``qk_dim ** -0.5``; under YaRN times ``mscale(factor,
    mscale_all_dim) ** 2``, as DeepSeek-V3 publishes it."""
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    yarn = cfg.rope_scaling
    if yarn is not None and yarn.mscale_all_dim:
        scale *= yarn_mscale(yarn.factor, yarn.mscale_all_dim) ** 2
    return scale


def mla_forward(p: Mapping, lora: Mapping | None, x: Array, cfg, block, *,
                mode: str, positions: Array | None = None,
                cache: Mapping | None = None, pos: Array | None = None,
                enc_out=None, alpha: float = 16.0,
                absorbed: bool = False, capacity: int | None = None):
    """DeepSeek-V3 Multi-head Latent Attention.

    Baseline decode re-expands K/V from the latent cache each step
    (paper-faithful to the reference implementation); ``absorbed=True``
    switches to the absorbed formulation (q projected into latent space) --
    a beyond-paper perf iteration, see EXPERIMENTS.md SSPerf.
    """
    del enc_out
    lora = lora or {}
    h = cfg.n_heads
    nope, rope_d, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    qk_dim = nope + rope_d
    scale = mla_softmax_scale(cfg)
    yarn = cfg.rope_scaling
    hx = norm(p["ln"], x, cfg.norm_eps)

    def proj(name, inp):
        return dense(p[name], inp, lora.get(name), alpha)

    # query path
    cq = norm(p["q_ln"], proj("q_a", hx), cfg.norm_eps)
    q = proj("q_b", cq).reshape(hx.shape[:2] + (h, qk_dim))
    q_nope, q_rope = q[..., :nope], q[..., nope:]

    # latent kv path
    ckv_full = proj("kv_a", hx)
    ckv, k_rope = ckv_full[..., :cfg.kv_lora_rank], \
        ckv_full[..., cfg.kv_lora_rank:]
    ckv = norm(p["kv_ln"], ckv, cfg.norm_eps)

    if mode in ("full", "prefill"):
        s = x.shape[1]
        positions = jnp.arange(s) if positions is None else positions
        q_rope = apply_rope(q_rope, positions[None], cfg.rope_theta, "full",
                            yarn)
        k_rope_r = apply_rope(k_rope[..., None, :], positions[None],
                              cfg.rope_theta, "full", yarn)[..., 0, :]
        kv = proj("kv_b", ckv).reshape(hx.shape[:2] + (h, nope + vd))
        k_nope, v = kv[..., :nope], kv[..., nope:]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope_r[..., None, :],
                                      k_nope.shape[:-1] + (rope_d,))], -1)
        qq = jnp.concatenate([q_nope, q_rope], -1)
        qg = qq.reshape(qq.shape[:2] + (h, 1, qk_dim))
        out = _attend_chunked(qg, k, v, causal=block.causal, window=0,
                              q_positions=positions, k_positions=positions,
                              scale=scale, cap=0.0)
        out = out.reshape(x.shape[:2] + (h * vd,))
        y = dense(p["o"], out, lora.get("o"), alpha)
        new_cache = None
        if mode == "prefill":
            t_cap = capacity or s
            pad = [(0, 0), (0, t_cap - s), (0, 0)]
            new_cache = {"ckv": jnp.pad(ckv, pad),
                         "kr": jnp.pad(k_rope_r, pad)}
        return y, new_cache

    # ---------------------------- decode --------------------------------
    posb = jnp.full((1, 1), pos)
    q_rope = apply_rope(q_rope, posb, cfg.rope_theta, "full", yarn)
    k_rope_new = apply_rope(k_rope[..., None, :], posb, cfg.rope_theta,
                            "full", yarn)[..., 0, :]
    ckv_c = lax.dynamic_update_slice_in_dim(cache["ckv"], ckv, pos, axis=1)
    kr_c = lax.dynamic_update_slice_in_dim(cache["kr"], k_rope_new, pos,
                                           axis=1)
    t = ckv_c.shape[1]
    valid = jnp.arange(t) <= pos
    if absorbed:
        # fold kv_b's K-half into the query: q_lat = q_nope @ W_bk^T
        wkb = p["kv_b"]["w"].reshape(cfg.kv_lora_rank, h, nope + vd)
        wk, wv = wkb[..., :nope], wkb[..., nope:]
        q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope, wk)       # (B,1,H,R)
        s_lat = jnp.einsum("bqhr,btr->bhqt", q_lat, ckv_c)
        s_rope = jnp.einsum("bqhd,btd->bhqt", q_rope, kr_c)
        scores = (s_lat + s_rope) * scale
        scores = jnp.where(valid[None, None, None],
                           scores.astype(jnp.float32), NEG_INF)
        probs = jax.nn.softmax(scores, -1).astype(x.dtype)
        ctx_lat = jnp.einsum("bhqt,btr->bqhr", probs, ckv_c)   # (B,1,H,R)
        out = jnp.einsum("bqhr,rhv->bqhv", ctx_lat, wv)
    else:
        kv = proj("kv_b", ckv_c).reshape(ckv_c.shape[:2] + (h, nope + vd))
        k_nope, v = kv[..., :nope], kv[..., nope:]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(kr_c[..., None, :],
                                      k_nope.shape[:-1] + (rope_d,))], -1)
        qq = jnp.concatenate([q_nope, q_rope], -1)
        qg = qq.reshape(qq.shape[:2] + (h, 1, qk_dim))
        out = _attend_decode(qg, k, v, valid, scale, 0.0)
    out = out.reshape(x.shape[:2] + (h * vd,))
    y = dense(p["o"], out, lora.get("o"), alpha)
    return y, {"ckv": ckv_c, "kr": kr_c}


def mla_init_cache(cfg, block, batch: int, seq_len: int, dtype) -> dict:
    return {"ckv": jnp.zeros((batch, seq_len, cfg.kv_lora_rank), dtype),
            "kr": jnp.zeros((batch, seq_len, cfg.qk_rope_dim), dtype)}
