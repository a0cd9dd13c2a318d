"""Roofline terms from a compiled dry-run artifact.

  compute_s    = HLO_FLOPs / (chips * peak)
  memory_s     = HLO_bytes / (chips * hbm_bw)
  collective_s = collective_bytes / (chips * ici_bw)

``cost_analysis`` FLOPs/bytes from XLA are for the *per-device* partitioned
module; we treat them as per-chip and normalize accordingly (chips factor
already applied by SPMD partitioning).  Collective bytes are not in
cost_analysis -- ``collective_bytes_from_hlo`` parses the post-SPMD HLO
text and sums the output-shape bytes of every collective op.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

from . import hw

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.  %ag = bf16[2,512,128]{2,1,0} all-gather(%x) or
#       (f32[8,16]{1,0}, f32[8,16]{1,0}) all-reduce-start(...)
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(?P<out>\([^)]*\)|[\w\[\],{}: ]+?)\s+"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?P<suffix>-start|-done)?\(", re.M)

_SHAPE_RE = re.compile(r"(?P<dt>\w+?)\[(?P<dims>[\d,]*)\]")


def _shape_bytes(txt: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(txt):
        dt = m.group("dt")
        if dt not in _DTYPE_BYTES:
            continue
        dims = m.group("dims")
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes_from_hlo(hlo_text: str) -> dict[str, int]:
    """Per-collective-op output bytes (per device), summed by op kind."""
    out: dict[str, int] = {}
    for m in _OP_RE.finditer(hlo_text):
        if m.group("suffix") == "-done":
            continue  # counted at -start
        op = m.group("op")
        out[op] = out.get(op, 0) + _shape_bytes(m.group("out"))
    return out


@dataclass
class Roofline:
    flops: float                 # per-device HLO flops
    hbm_bytes: float             # per-device bytes accessed
    collective_bytes: float      # per-device collective bytes
    chips: int
    model_flops: float = 0.0     # 6*N*D useful flops (global)
    collectives: dict = field(default_factory=dict)

    @property
    def compute_s(self) -> float:
        return self.flops / hw.PEAK_FLOPS_BF16

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes / hw.HBM_BW

    @property
    def collective_s(self) -> float:
        return self.collective_bytes / hw.ICI_BW

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / (HLO flops across all chips)."""
        total = self.flops * self.chips
        return self.model_flops / total if total else 0.0

    def as_dict(self) -> dict:
        return {
            "flops_per_device": self.flops,
            "hbm_bytes_per_device": self.hbm_bytes,
            "collective_bytes_per_device": self.collective_bytes,
            "collectives": self.collectives,
            "chips": self.chips,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "model_flops": self.model_flops,
            "useful_flops_ratio": self.useful_flops_ratio,
        }


def model_flops_estimate(cfg, shape, n_params_active: float,
                         kind: str) -> float:
    """6*N*D for training, 2*N*D for inference forward (per step)."""
    if kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_params_active * tokens
    if kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_params_active * tokens
    # decode: one token per sequence
    return 2.0 * n_params_active * shape.global_batch


def scan_correction(cfg) -> float:
    """Trip-count correction for XLA CPU cost_analysis.

    The CPU backend counts a ``while``-lowered ``lax.scan`` body ONCE
    (verified empirically: scan-of-10 matmuls reports exactly 1/10 the
    flops of the unrolled version).  Our models scan over layer stacks, so
    raw cost_analysis numbers undercount by roughly the layer count.  We
    correct with a parameter-weighted trip-count multiplier:

        c = sum_s R_s * W_s / sum_s W_s

    over stages s (repeat R_s, per-unit params W_s) plus a non-scanned
    pseudo-stage (embedding/head, R=1).  Exact when per-param cost is
    uniform; applied to flops, bytes and collective bytes alike.
    """
    units = []
    d = cfg.d_model
    embed_w = cfg.vocab_size * d * (1 if cfg.tie_embeddings else 2)
    units.append((1, embed_w))
    for stage in cfg.stages + cfg.encoder_stages:
        w = sum(_block_params(cfg, spec) for spec in stage.unit)
        units.append((stage.repeat, w))
    num = sum(r * w for r, w in units)
    den = sum(w for _, w in units)
    return num / den if den else 1.0


def _block_params(cfg, spec) -> float:
    d = cfg.d_model
    n = 0.0
    if spec.kind == "mamba":
        d_in = cfg.ssm_expand * d
        h = d_in // cfg.ssm_head_dim
        n += d * (2 * d_in + 2 * cfg.ssm_state + h) + d_in * d
    elif spec.kind == "mla":
        qk = cfg.qk_nope_dim + cfg.qk_rope_dim
        n += d * cfg.q_lora_rank
        n += cfg.q_lora_rank * cfg.n_heads * qk
        n += d * (cfg.kv_lora_rank + cfg.qk_rope_dim)
        n += cfg.kv_lora_rank * cfg.n_heads * (cfg.qk_nope_dim +
                                               cfg.v_head_dim)
        n += cfg.n_heads * cfg.v_head_dim * d
    else:
        hd = cfg.head_dim
        n += d * hd * (cfg.n_heads + 2 * cfg.n_kv_heads)
        n += cfg.n_heads * hd * d
        if spec.cross_attn:
            n *= 2
    if spec.ffn == "dense":
        mult = 2 if cfg.mlp_act == "gelu_plain" else 3
        n += mult * d * cfg.d_ff
    elif spec.ffn == "moe":
        f = cfg.moe_d_ff or cfg.d_ff
        # dispatched compute ~ active experts x capacity factor
        n += (3 * d * f * cfg.experts_per_token * cfg.capacity_factor
              + 3 * d * f * cfg.n_shared_experts + d * cfg.n_routed)
    return n


def active_params(cfg) -> float:
    """Approximate active (per-token) parameter count from the config."""
    d = cfg.d_model
    total = cfg.vocab_size * d * (1 if cfg.tie_embeddings else 2)
    for stage in cfg.stages + cfg.encoder_stages:
        for spec in stage.unit:
            n = 0.0
            if spec.kind == "mamba":
                d_in = cfg.ssm_expand * d
                h = d_in // cfg.ssm_head_dim
                n += d * (2 * d_in + 2 * cfg.ssm_state + h) + d_in * d
            elif spec.kind == "mla":
                qk = cfg.qk_nope_dim + cfg.qk_rope_dim
                n += d * cfg.q_lora_rank
                n += cfg.q_lora_rank * cfg.n_heads * qk
                n += d * (cfg.kv_lora_rank + cfg.qk_rope_dim)
                n += cfg.kv_lora_rank * cfg.n_heads * (cfg.qk_nope_dim +
                                                       cfg.v_head_dim)
                n += cfg.n_heads * cfg.v_head_dim * d
            else:
                hd = cfg.head_dim
                n += d * hd * (cfg.n_heads + 2 * cfg.n_kv_heads)
                n += cfg.n_heads * hd * d
                if spec.cross_attn:
                    n *= 2
            if spec.ffn == "dense":
                mult = 2 if cfg.mlp_act == "gelu_plain" else 3
                n += mult * d * cfg.d_ff
            elif spec.ffn == "moe":
                f = cfg.moe_d_ff or cfg.d_ff
                n += 3 * d * f * cfg.experts_per_token      # active experts
                n += 3 * d * f * cfg.n_shared_experts
                n += d * cfg.n_routed                       # router
            total += n * stage.repeat
    return total
