"""Pallas flash-attention kernel on real TPU vs dense attention.

Proves the hand-written MXU kernel (ops/kernels/flash.py) compiles and
runs on hardware (the test suite exercises interpret mode only), matches
dense numerics, and unlocks sequence lengths whose O(T^2) score matrix
cannot fit in HBM (flash alone runs to T=16384 on one chip; dense would
need ~8.6GB of scores). The kernel picks its tiles from (T, D, dtype)
(`flash_tiles`; pass `block_q=` / `block_k=` to pin one) and feeds the MXU
the type it is given: the float32 inputs here stay float32 operands, so
this script times the float32 caller; the bf16 numbers at the OLMoE
cell's shape are in PERF.md (PR 28). Measured on the v5e (chip run,
PR 28): T=2048 flash 0.32 ms vs dense 29.25 ms; T=8192 1.72 ms; T=16384
5.5 ms. Under "cells" it times the kernels alone at the three LM cells'
attention calls in bf16: forward, the split backward and the one-pass
backward (PERF.md section 7, PR 34). Prints ONE JSON line.
"""
import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from mxnet_tpu.ops import kernels as pk  # noqa: E402
from mxnet_tpu.ops.kernels import flash_attention  # noqa: E402


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


# the benchmark's three LM cells' attention calls (PERF.md section 4):
# (T, query heads, key/value heads, D, Dv, window), bf16, causal
CELL_CALLS = {
    "kanana2_8k": (8192, 32, 32, 192, 128, 0),
    "olmoe_4k": (4096, 16, 16, 128, 128, 0),
    "mimo_full_4k": (4096, 8, 1, 192, 128, 0),
    "mimo_window_4k": (4096, 8, 1, 192, 128, 128),
}
# live tile pairs a call (causal; the window's band at 256 x 256 tiles)
LIVE_PAIRS = {"kanana2_8k": 32 * 36, "olmoe_4k": 16 * 10,
              "mimo_full_4k": 8 * 10, "mimo_window_4k": 8 * 31}
BWD_FORMS = {"split": False, "fused": True}


def _ms(f, *args, reps=20):
    jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        r = f(*args)
    jax.block_until_ready(r)
    return round((time.perf_counter() - t0) / reps * 1000, 3)


def cell_kernels():
    """Forward, the split backward (dq and dkv) and the one-pass
    backward, kernel calls alone (no layout change round them), in ms a
    call at the cells' shapes; the two backward forms' gradients against
    each other."""
    out = {}
    for name, (t, h, g, d, dv, window) in CELL_CALLS.items():
        rng = np.random.RandomState(0)
        q, k, v, do = (
            jnp.asarray(rng.randn(*shape), jnp.bfloat16)
            for shape in ((h, t, d), (g, t, d), (g, t, dv), (h, t, dv)))
        bq, bk = pk.flash_tiles(t, max(d, dv), q.dtype, window)
        kw = dict(t_real=t, scale=d ** -0.5, causal=True, window=window,
                  block_q=bq, block_k=bk, interpret=False)
        fwd = jax.jit(functools.partial(pk.flash.fwd_call, **kw))
        o, lse = fwd(q, k, v)
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1, keepdims=True)
        row = {"tiles": [bq, bk], "fwd_ms": _ms(fwd, q, k, v)}
        ref = None
        for form, fused in BWD_FORMS.items():
            bwd = jax.jit(functools.partial(pk.flash.bwd_call, fused=fused,
                                            **kw))
            row["bwd_%s_ms" % form] = _ms(bwd, q, k, v, do, lse, delta)
            row["bwd_%s_us_a_pair" % form] = round(
                row["bwd_%s_ms" % form] * 1000 / LIVE_PAIRS[name], 2)
            got = [np.asarray(x, np.float32)
                   for x in bwd(q, k, v, do, lse, delta)]
            if ref is None:
                ref = got
            else:
                row["bwd_%s_max_rel_diff" % form] = float(max(
                    np.abs(a - b).max() / (np.abs(b).max() + 1e-9)
                    for a, b in zip(got, ref)))
        out[name] = row
    return out


def main():
    out = {"device": str(jax.devices()[0].device_kind),
           "cells": cell_kernels()}

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
