"""deepseek-v3-671b [moe] -- MLA latent attention, 1 shared + 256 routed
experts top-8, dense prefix, MTP head. [arXiv:2412.19437]

61L d_model=7168 128H (MLA) vocab=129280, as the published config.json
(huggingface.co/deepseek-ai/DeepSeek-V3): the first 3 layers dense
(``intermediate_size`` 18432), then 58 MoE layers of 256 routed experts of
width 2048 (``moe_intermediate_size``) and one shared expert of the same
width.  The router is V3's ``noaux_tc``: sigmoid scores, a selection-only
bias, the top 4 of 8 groups by their top-2 sum, the top 8 experts inside
them, weights normalised over the 8 and scaled by 2.5.  Rope is YaRN
(factor 40 over 4096 positions).

The registry holds every routed expert (``n_experts`` 256).  One chip's
expert-parallel share sets ``n_experts`` to the experts it holds and
``expert_offset`` to the first of them; the router keeps its 256.
"""
from .base import ArchConfig, BlockSpec, Stage, YaRN

CONFIG = ArchConfig(
    name="deepseek-v3-671b",
    arch_type="moe",
    source="arXiv:2412.19437",
    d_model=7168,
    n_heads=128,
    n_kv_heads=128,
    head_dim=128,                 # v head dim; qk dims below (MLA)
    d_ff=18432,                   # dense-prefix MLP width
    vocab_size=129280,
    stages=(
        Stage(unit=(BlockSpec(kind="mla", ffn="dense"),), repeat=3),
        Stage(unit=(BlockSpec(kind="mla", ffn="moe"),), repeat=58),
    ),
    rope_kind="full",
    rope_theta=10_000.0,
    rope_scaling=YaRN(factor=40.0, original_max_position_embeddings=4096,
                      beta_fast=32.0, beta_slow=1.0, mscale=1.0,
                      mscale_all_dim=1.0),
    # MLA geometry
    q_lora_rank=1536,
    kv_lora_rank=512,
    qk_nope_dim=128,
    qk_rope_dim=64,
    v_head_dim=128,
    # MoE: 256 routed top-8 + 1 shared, expert width 2048
    n_experts=256,
    n_routed_experts=256,
    n_shared_experts=1,
    experts_per_token=8,
    moe_d_ff=2048,
    router_scoring="sigmoid",
    router_groups=8,
    router_topk_groups=4,
    router_scale=2.5,
    mlp_act="silu",
    mtp_depth=1,                  # one MTP module (paper's D=1 deployment)
)
