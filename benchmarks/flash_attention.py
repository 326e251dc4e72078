"""Pallas flash-attention kernel on real TPU vs dense attention.

Proves the hand-written MXU kernel (ops/pallas_kernels.py) compiles and
runs on hardware (the test suite exercises interpret mode only), matches
dense numerics, and unlocks sequence lengths whose O(T^2) score matrix
cannot fit in HBM (flash alone runs to T=16384 on one chip; dense would
need ~8.6GB of scores). The kernel picks its tiles from (T, D, dtype)
(`flash_tiles`; pass `block_q=` / `block_k=` to pin one) and feeds the MXU
the type it is given: the float32 inputs here stay float32 operands, so
this script times the float32 caller; the bf16 numbers at the OLMoE
cell's shape are in PERF.md (PR 28). Measured on the v5e (chip run,
PR 28): T=2048 flash 0.32 ms vs dense 29.25 ms; T=8192 1.72 ms; T=16384
5.5 ms. Prints ONE JSON line.
"""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from mxnet_tpu.ops.pallas_kernels import flash_attention  # noqa: E402


def dense(q, k, v):
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    T = q.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


def _time(f, *args, reps=10):
    float(f(*args))  # compile + complete (scalar fetch closes the call)
    t0 = time.perf_counter()
    for _ in range(reps):
        r = f(*args)
    float(r)
    return (time.perf_counter() - t0) / reps


def main():
    out = {"device": str(jax.devices()[0].device_kind)}

    # head-to-head at a size dense still fits
    B, T, H, D = 2, 2048, 4, 128
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
               for _ in range(3))
    f_flash = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True).astype(jnp.float32).mean())
    f_dense = jax.jit(
        lambda q, k, v: dense(q, k, v).astype(jnp.float32).mean())
    assert abs(float(f_flash(q, k, v)) - float(f_dense(q, k, v))) < 1e-5
    out["T2048_flash_ms"] = round(_time(f_flash, q, k, v) * 1000, 2)
    out["T2048_dense_ms"] = round(_time(f_dense, q, k, v) * 1000, 2)
    out["speedup"] = round(out["T2048_dense_ms"] / out["T2048_flash_ms"], 2)

    # long-context scaling, flash only (dense's scores would not fit)
    for T in (8192, 16384):
        rng = np.random.RandomState(0)
        q, k, v = (jnp.asarray(rng.randn(1, T, 8, 128), jnp.float32)
                   for _ in range(3))
        f = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=True).astype(jnp.float32).mean())
        out["T%d_flash_ms" % T] = round(_time(f, q, k, v, reps=5) * 1000, 2)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
