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
5.5 ms. Under "cells" it times the kernels alone at the LM cells'
attention calls in bf16: forward, the split backward and the one-pass
backward (PERF.md section 7, PR 34), each with its cut tiles whole and,
where `flash.cut_half` takes the call, by quarters (`..._quarters_ms`;
PR 58). Prints ONE JSON line.

    chiprun -- python3 benchmarks/flash_attention.py
    python3 benchmarks/flash_attention.py --rehearse-cpu

The platform rule, the clock and the output file are `alone.py`'s.
"""
import functools

import alone

import jax
import jax.numpy as jnp
import numpy as np

from mxnet_tpu.ops import kernels as pk
from mxnet_tpu.ops.kernels import flash_attention


def dense(q, k, v):
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    T = q.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


# the LM cells' attention calls (PERF.md section 4): (T, query heads,
# key/value heads, D, Dv, window), bf16, causal
CELL_CALLS = {
    "kanana2_8k": (8192, 32, 32, 192, 128, 0),
    "lfm2_8k": (8192, 32, 8, 64, 64, 0),
    "olmoe_4k": (4096, 16, 16, 128, 128, 0),
    "mimo_full_4k": (4096, 8, 1, 192, 128, 0),
    "mimo_window_4k": (4096, 8, 1, 192, 128, 128),
}
TOY_CALLS = {"toy_full": (256, 2, 1, 64, 64, 0),
             "toy_window": (256, 2, 1, 64, 64, 32)}
BWD_FORMS = {"split": False, "fused": True}


def live_pairs(t, heads, bq, bk, window):
    """The (q tile, k tile) pairs a causal call visits: the triangle, or
    the window's band."""
    def k_tiles(i):
        first = max(0, i * bq - window + 1) if window else 0
        return ((i + 1) * bq - 1) // bk - first // bk + 1
    return heads * sum(k_tiles(i) for i in range(t // bq))


def cell_kernels(run):
    """Forward, the split backward (dq and dkv) and the one-pass
    backward, kernel calls alone (no layout change round them), in ms a
    call at the cells' shapes, cut tiles whole and by quarters; every
    backward's gradients against the whole split form's."""
    def ms(f, *args):
        return round(run.host_ms(f, *args), 3)

    out = {}
    for name, (t, h, g, d, dv, window) in (
            TOY_CALLS if run.rehearse else CELL_CALLS).items():
        rng = np.random.RandomState(0)
        q, k, v, do = (
            jnp.asarray(rng.randn(*shape), jnp.bfloat16)
            for shape in ((h, t, d), (g, t, d), (g, t, dv), (h, t, dv)))
        bq, bk = pk.flash_tiles(t, max(d, dv), q.dtype, window)
        kw = dict(t_real=t, scale=d ** -0.5, causal=True, window=window,
                  block_q=bq, block_k=bk, interpret=run.rehearse)
        # the toy tiles are under the floor: a rehearsal runs their
        # quarters all the same, where the window's edge allows
        edge = (bq // 2 if run.rehearse and window % (bq // 2) == 0
                else pk.flash.cut_half(bq, bk, True, window))
        o, lse = pk.flash.fwd_call(q, k, v, **kw)
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1, keepdims=True)
        row = {"tiles": [bq, bk], "edge": edge}
        pairs = live_pairs(t, h, bq, bk, window)
        ref = None
        for cut, e in [("", 0)] + [("quarters_", edge)] * bool(edge):
            fwd = jax.jit(functools.partial(pk.flash.fwd_call, edge=e, **kw))
            row["fwd_%sms" % cut] = ms(fwd, q, k, v)
            for form, fused in BWD_FORMS.items():
                bwd = jax.jit(functools.partial(
                    pk.flash.bwd_call, fused=fused, edge=e, **kw))
                key = "bwd_%s_%s" % (form, cut)
                row[key + "ms"] = ms(bwd, q, k, v, do, lse, delta)
                row[key + "us_a_pair"] = round(
                    row[key + "ms"] * 1000 / pairs, 2)
                got = [np.asarray(x, np.float32)
                       for x in bwd(q, k, v, do, lse, delta)]
                if ref is None:
                    ref = got
                else:
                    row[key + "max_rel_diff"] = float(max(
                        np.abs(a - b).max() / (np.abs(b).max() + 1e-9)
                        for a, b in zip(got, ref)))
        out[name] = row
    return out


def main():
    run = alone.Run(__file__)
    out = {"device": run.kind, "cells": cell_kernels(run)}

    def scalar(attention):
        return jax.jit(lambda q, k, v: attention(q, k, v).astype(
            jnp.float32).mean())

    def draw(*shape):
        rng = np.random.RandomState(0)
        return [jnp.asarray(rng.randn(*shape), jnp.float32)
                for _ in range(3)]

    f_flash = scalar(functools.partial(flash_attention, causal=True))
    # head-to-head at a size dense still fits
    B, T, H, D = (1, 256, 2, 64) if run.rehearse else (2, 2048, 4, 128)
    qkv = draw(B, T, H, D)
    f_dense = scalar(dense)
    assert abs(float(f_flash(*qkv)) - float(f_dense(*qkv))) < 1e-5
    out["T%d_flash_ms" % T] = round(run.host_ms(f_flash, *qkv, reps=10), 2)
    out["T%d_dense_ms" % T] = round(run.host_ms(f_dense, *qkv, reps=10), 2)
    out["speedup"] = round(
        out["T%d_dense_ms" % T] / out["T%d_flash_ms" % T], 2)

    # long-context scaling, flash only (dense's scores would not fit)
    for T in (512,) if run.rehearse else (8192, 16384):
        out["T%d_flash_ms" % T] = round(
            run.host_ms(f_flash, *draw(1, T, 8, 128), reps=5), 2)
    run.row(**out)
    run.save()


if __name__ == "__main__":
    main()
