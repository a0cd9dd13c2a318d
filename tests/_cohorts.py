"""Shared heterogeneous-rank cohort fixtures for the strategy suites.

One place builds the noisy hetero-rank adapter cohorts and compares
pytrees, so tolerance semantics and cohort construction cannot silently
diverge between `tests/test_strategy.py` and `tests/test_async_agg.py`.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.lora import init_adapters, set_ranks

SPECS = {"fc1": (12, 16), "fc2": (10, 12)}
R_MAX = 8


def hetero_cohort(n=5, seed=0, r_lo=1, r_hi=R_MAX, with_bases=False):
    """n clients with random ranks in [r_lo, r_hi], noisy A and B.

    Returns ``(adapters, ranks, weights)`` -- plus a list of small
    non-LoRA base-trainable trees when ``with_bases`` (the async suite
    folds those too).
    """
    rng = np.random.default_rng(seed)
    ranks = rng.integers(r_lo, r_hi + 1, n)
    adapters, keys = [], jax.random.split(jax.random.PRNGKey(seed), n)
    for i in range(n):
        ad = init_adapters(keys[i], SPECS, R_MAX, int(ranks[i]))
        ad = jax.tree.map(     # B inits to zero: randomize both factors
            lambda x: x + jnp.asarray(rng.normal(size=x.shape), x.dtype)
            if x.dtype == jnp.float32 else x, ad)
        adapters.append(set_ranks(ad, int(ranks[i])))   # re-mask padding
    weights = jnp.asarray(rng.uniform(0.5, 2.0, n), jnp.float32)
    if with_bases:
        bases = [{"b": jnp.asarray(rng.normal(size=(4,)), jnp.float32)}
                 for _ in range(n)]
        return adapters, jnp.asarray(ranks, jnp.int32), weights, bases
    return adapters, jnp.asarray(ranks, jnp.int32), weights


def mixed_codec_cohort(n=5, seed=0, codecs=None, **kw):
    """A hetero cohort with per-client upload codecs applied.

    ``codecs`` is a per-client name sequence (cycled over
    ``("int8", "bf16", "none")`` by default).  Returns ``(encoded,
    decoded, ranks, weights, codecs)`` -- ``decoded`` is the fp32 oracle
    cohort (``decode_adapters`` of each encoded client, so int8 oracle
    comparisons see the same quantization error).
    """
    from repro.core import codec
    adapters, ranks, weights = hetero_cohort(n=n, seed=seed, **kw)
    if codecs is None:
        codecs = [("int8", "bf16", "none")[i % 3] for i in range(n)]
    codecs = tuple(codecs)
    encoded = [codec.encode_adapters(a, c)
               for a, c in zip(adapters, codecs)]
    decoded = [codec.decode_adapters(a) for a in encoded]
    return encoded, decoded, ranks, weights, codecs


def reference_round(strategy, uploads, w, *, r_max, client_ranks,
                    prev=None):
    """One round on the float32 reference path: decode the uploads, stack
    them, build the stacked mask tree (``adapter_masks``) and run the
    strategy's per-leaf ``aggregate_tree`` + ``finalize_tree`` -- the
    oracle every compiled plan must reproduce."""
    from repro.core import codec, get_strategy, stack_trees
    from repro.lora import adapter_masks
    s = get_strategy(strategy)
    if codec.cohort_codecs(uploads) is not None:
        uploads = [codec.decode_adapters(a) for a in uploads]
    stacked = stack_trees(uploads)
    out = s.aggregate_tree(stacked, adapter_masks(stacked),
                           jnp.asarray(w, jnp.float32),
                           prev if s.retains_prev else None, r_max=r_max,
                           client_ranks=client_ranks)
    return s.finalize_tree(out, r_max)


def assert_trees_close(a, b, rtol=1e-4, atol=1e-5, msg=""):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb), msg
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32),
                                   rtol=rtol, atol=atol, err_msg=msg)
