"""Ahead-of-time compiles of the plan's Pallas kernels for a TPU v5e chip
at h2o-danube-3-4b widths: d_model 3840, and 1536 packed rows per pair
side (24 layers x r_max 64).

Nothing runs.  The TPU compiler installed with JAX compiles for a
described ``v5e:2x2`` topology and refuses what the chip would refuse --
VMEM overflow, block shapes off the (8, 128) tiling, operand layouts
Mosaic cannot take -- none of which interpret mode can see.  Every case
asserts that the compiled program contains the kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

R, D = 1536, 3840


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2 host, with JAX's persistent
    compile cache off (a compile for a described chip is written to it
    but cannot be read back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")    # no compiler logs in /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _case(name: str, chip):
    """(function, argument shapes) of one kernel at real widths."""
    from repro.kernels.lora_matmul.ops import batched_lora_matmul_inline
    from repro.kernels.rbla_agg.ops import (axpy_fold_inline,
                                            packed_agg_inline,
                                            packed_robust_inline,
                                            packed_stack_inline)

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def bucket(n, dtype=jnp.float32):
        return [s((n, R, D), dtype), s((n, R)), s((n,)), s((R, D))]

    if name in ("packed_agg_n8", "packed_agg_n32"):
        return (lambda x, m, w, p: packed_agg_inline(x, m, w, p,
                                                     interpret=False),
                bucket(int(name[len("packed_agg_n"):])))
    if name == "packed_agg_int8_n128":
        return (lambda x, m, w, p, sc: packed_agg_inline(
                    x, m, w, p, scales=sc, out_dtype=jnp.float32,
                    interpret=False),
                bucket(128, jnp.int8) + [s((128, R))])
    if name == "packed_agg_norm_restore":
        return (lambda x, m, w, p: packed_agg_inline(
                    x, m, w, p, norm_restore=True, interpret=False),
                bucket(32))
    if name == "packed_robust_clipped":
        return (lambda x, m, w, p: packed_robust_inline(
                    x, m, w, p, mode="clipped", clip_norm=1.0,
                    interpret=False),
                bucket(32))
    if name == "packed_robust_median_n32":
        return (lambda x, m, w, p: packed_robust_inline(
                    x, m, w, p, mode="median", interpret=False),
                bucket(32))
    if name == "packed_stack":
        # flora over 8 clients at ranks 8..64 plus a rank-64 previous
        # global, every layer of one pair side: 216 copies
        ranks = (8, 8, 16, 16, 32, 32, 64, 64)
        cap, cx, cp = 384, [], []
        for layer in range(24):
            dst = layer * cap
            cp.append((layer * 64, dst, 64, 1))
            dst += 64
            for i, r in enumerate(ranks):
                cx.append((i, layer * 64, dst, r, 2 + i))
                dst += r
        return (lambda x, sc, p: packed_stack_inline(
                    x, sc, p, copies_x=tuple(cx), copies_prev=tuple(cp),
                    out_rows=24 * cap, interpret=False),
                [s((8, R, D)), s((10,)), s((R, D))])
    if name == "flora_plan_bf16_rank8":
        # flora's whole compiled round on a bf16 cohort at rank 8, every
        # projection of one layer: the plan packs in f32, so its copies
        # sit on the kernel's 8-row tiles
        import numpy as np
        from repro.core import get_strategy, stack_trees
        from repro.core.plan import build_cohort_spec
        from repro.lora import init_adapters
        specs = {"q": (D, D), "k": (D, 960), "gate": (D, 10240),
                 "down": (10240, D)}
        cohort = [jax.tree.map(
            lambda x: x.astype(jnp.bfloat16) if x.dtype == jnp.float32
            else x, init_adapters(jax.random.PRNGKey(i), specs, 8, 8))
            for i in range(8)]
        stacked = stack_trees(cohort)
        rd = get_strategy("flora").with_options(stack_r_cap=64).plan(
            None, build_cohort_spec(stacked, kind="pallas", r_max=8,
                                    client_ranks=np.full(8, 8),
                                    interpret=False))
        assert rd.n_pallas_launches == rd.n_kernel_launches > 0
        return (lambda st, w: rd(st, w),
                [jax.tree.map(lambda x: s(x.shape, x.dtype), stacked),
                 s((8,))])
    if name == "axpy_fold":
        return (lambda y, x, a: axpy_fold_inline(y, x, a, interpret=False),
                [s((R, D)), s((R, D)), s((R,))])
    if name == "batched_lora_matmul":
        # the gate projection: 3840 -> 10240, 8 tenant pages of 64 rows
        return (lambda x, w, a, b, ids, off, rk, sc:
                batched_lora_matmul_inline(x, w, a, b, ids, off, rk, sc,
                                           impl="pallas", interpret=False),
                [s((256, D)), s((D, 10240)), s((512, D)),
                 s((512, 10240)), s((256,), jnp.int32),
                 s((8,), jnp.int32), s((8,), jnp.int32), s((8,))])
    raise ValueError(name)


@pytest.mark.parametrize("case", [
    "packed_agg_n8", "packed_agg_n32", "packed_agg_int8_n128",
    "packed_agg_norm_restore", "packed_robust_clipped",
    "packed_robust_median_n32", "packed_stack", "flora_plan_bf16_rank8",
    "axpy_fold", "batched_lora_matmul"])
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, shapes = _case(case, one_chip)
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("case,kernel", [("packed_agg_n8", "packed_agg"),
                                         ("axpy_fold", "axpy_fold")])
def test_compiled_kernel_carries_its_name(one_chip, case, kernel):
    """The Mosaic custom call is named after its kernel, so a trace names
    the kernel and not only the program that runs it."""
    fn, shapes = _case(case, one_chip)
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls and all(f"%{kernel}" in line for line in calls), calls
