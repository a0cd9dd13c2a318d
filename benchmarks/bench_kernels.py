"""Kernel micro-benchmarks: fused LoRA matmul vs unfused jnp reference.

interpret=True on CPU: correctness-oriented; wall numbers document harness
overhead, not TPU performance.  The derived column reports the HBM-traffic
model that motivates the fusion: the fused kernel reads x once instead of
twice (base + LoRA paths).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import lora_matmul, lora_matmul_ref
from repro.launch.cache import enable_compile_cache

CASES = [
    # (m, k, n, r)
    (1024, 1024, 1024, 16),
    (4096, 1024, 1024, 64),
    (1024, 4096, 1024, 64),
]


def bench(fn, *args, iters=3):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / iters * 1e6


def traffic_model(m, k, n, r, bytes_per=2):
    """bytes moved: fused reads x once; unfused reads it twice."""
    fused = (m * k + k * n + r * k + n * r + m * n) * bytes_per
    unfused = (2 * m * k + k * n + r * k + n * r + 2 * m * n) * bytes_per
    return fused, unfused


def main():
    enable_compile_cache()
    rng = np.random.default_rng(0)
    for m, k, n, r in CASES:
        x = jnp.asarray(rng.normal(size=(m, k)), jnp.bfloat16)
        w = jnp.asarray(rng.normal(size=(k, n)) * 0.05, jnp.bfloat16)
        a = jnp.asarray(rng.normal(size=(r, k)) * 0.05, jnp.bfloat16)
        b = jnp.asarray(rng.normal(size=(n, r)) * 0.05, jnp.bfloat16)

        ref = jax.jit(lambda *s: lora_matmul_ref(*s, 0.25))
        us_ref = bench(ref, x, w, a, b)
        us_ker = bench(lambda *s: lora_matmul(*s, 0.25, interpret=True),
                       x, w, a, b)
        fused, unfused = traffic_model(m, k, n, r)
        print(f"kernel/lora_matmul_ref/m{m}k{k}n{n}r{r},{us_ref:.0f},"
              f"model_bytes={unfused}")
        print(f"kernel/lora_matmul_pallas/m{m}k{k}n{n}r{r},{us_ker:.0f},"
              f"model_bytes={fused} ({100*(1-fused/unfused):.0f}% less"
              " traffic)")


if __name__ == "__main__":
    main()
