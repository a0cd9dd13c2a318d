"""Configurations cut to tiny widths for the CPU tests: the committed
configurations' targets at toy widths, and a two-stage MLA + MoE tree of
the registry's ``deepseek-v3-671b``, one dense layer and four MoE layers
with 8 experts held (so the expert axis differs from the depth)."""

D, F = 128, 256
TINY = {
    "hidden_size": D, "num_hidden_layers": 1,
    "adapter": {"arch": "h2o-danube-3-4b", "layers_key": "num_hidden_layers",
                "r_max": 8,
                "targets": {"mix/q": [D, D], "mix/k": [64, D],
                            "mix/v": [64, D], "mix/o": [D, D],
                            "ffn/gate": [F, D], "ffn/up": [F, D],
                            "ffn/down": [D, F]}},
    "program_overrides": {"d_model": D, "n_heads": 2, "n_kv_heads": 1,
                          "head_dim": 64, "d_ff": F},
}

G, GF = 128, 192
TINY_GLM = {
    "hidden_size": G, "num_layers": 2,
    "adapter": {"arch": "chatglm3-6b", "layers_key": "num_layers",
                "r_max": 16,
                "targets": {"mix/q": [G, G], "mix/k": [64, G],
                            "mix/v": [64, G], "mix/o": [G, G],
                            "ffn/gate": [GF, G], "ffn/up": [GF, G],
                            "ffn/down": [G, GF]}},
    "program_overrides": {"d_model": G, "n_heads": 4, "n_kv_heads": 2,
                          "head_dim": 32, "d_ff": GF},
}

# MLA: q_lora_rank, kv_lora_rank, nope, rope and v head dims, heads
Q, KV, NOPE, ROPE, V, H = 64, 32, 16, 16, 16, 2
FE = 64                                  # routed and shared expert width
MLA = {"mix/q_a": [Q, D], "mix/q_b": [H * (NOPE + ROPE), Q],
       "mix/kv_a": [KV + ROPE, D], "mix/kv_b": [H * (NOPE + V), KV],
       "mix/o": [D, H * V]}
EXPERT_TARGETS = ("ffn/experts/gate", "ffn/experts/up", "ffn/experts/down")
TINY_MOE = {
    "hidden_size": D, "num_hidden_layers": 5, "first_k_dense_replace": 1,
    "n_routed_experts": 8,
    "adapter": {"arch": "deepseek-v3-671b", "r_max": 8, "stages": [
        {"depth": ["first_k_dense_replace"],
         "targets": {**MLA, "ffn/gate": [F, D], "ffn/up": [F, D],
                     "ffn/down": [D, F]}},
        {"depth": ["num_hidden_layers", "first_k_dense_replace"],
         "targets": {**MLA, "ffn/experts/gate": [FE, D],
                     "ffn/experts/up": [FE, D], "ffn/experts/down": [D, FE],
                     "ffn/shared/gate": [FE, D], "ffn/shared/up": [FE, D],
                     "ffn/shared/down": [D, FE]},
         "lead": {t: ["n_routed_experts"] for t in EXPERT_TARGETS}}]},
    "program_overrides": {"d_model": D, "n_heads": H, "n_kv_heads": H,
                          "head_dim": V, "d_ff": F, "q_lora_rank": Q,
                          "kv_lora_rank": KV, "qk_nope_dim": NOPE,
                          "qk_rope_dim": ROPE, "v_head_dim": V,
                          "moe_d_ff": FE, "n_experts": 8},
}
