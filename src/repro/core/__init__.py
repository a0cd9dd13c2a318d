"""RBLA core: rank-based aggregation of heterogeneous LoRA adapters.

This package is the paper's primary contribution (Eq. 6-7, Alg. 1-2) plus
its distributed (shard_map collective) form, beyond-paper variants, and the
pluggable :class:`~repro.core.strategy.AggregationStrategy` registry: each
method's compiled plan (``repro.core.plan``) on every backend, and its
float32 reference tree path.
"""
from .masks import (axis_mask, pad_to_rank, rank_mask, slice_to_rank,
                    stacked_rank_masks)
from .aggregation import fedavg_leaf, rbla_leaf, zeropad_leaf
from .variants import (rank_proportional_weights, rbla_norm_leaf,
                       svd_project_pair)
from .lowrank import (dense_svd, factored_svd, product_factors,
                      randomized_svd, randomized_svd_product,
                      svd_project_stacked, truncated_svd_product)
from .strategy import (AggregationStrategy, ClientUpdate, FoldState,
                       ServerState, BACKENDS, adapter_live_ranks,
                       get_strategy, list_strategies, register_strategy,
                       resolve_backend, stack_trees)
from .plan import (CohortSpec, CompiledRound, PlanUnavailable,
                   build_cohort_spec, build_encoded_cohort_spec,
                   dispatch_counter)
from .codec import (CODECS, cohort_codecs, decode_adapters, decode_pair,
                    decode_update, encode_adapters, encode_pair,
                    encode_update, stochastic_round, stochastic_round_tree,
                    tree_codec, validate_encoded_adapters)

__all__ = [
    "axis_mask", "pad_to_rank", "rank_mask", "slice_to_rank",
    "stacked_rank_masks", "fedavg_leaf", "rbla_leaf", "zeropad_leaf",
    "rank_proportional_weights",
    "rbla_norm_leaf", "svd_project_pair",
    "dense_svd", "factored_svd", "product_factors", "randomized_svd",
    "randomized_svd_product", "svd_project_stacked",
    "truncated_svd_product",
    "AggregationStrategy",
    "ClientUpdate", "FoldState", "ServerState", "BACKENDS",
    "adapter_live_ranks",
    "CohortSpec", "CompiledRound", "PlanUnavailable", "build_cohort_spec",
    "build_encoded_cohort_spec", "dispatch_counter",
    "CODECS", "cohort_codecs", "decode_adapters", "decode_pair",
    "decode_update", "encode_adapters", "encode_pair", "encode_update",
    "stochastic_round", "stochastic_round_tree", "tree_codec",
    "validate_encoded_adapters",
    "get_strategy",
    "list_strategies", "register_strategy", "resolve_backend",
    "stack_trees",
]
