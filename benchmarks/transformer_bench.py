#!/usr/bin/env python
"""Transformer-LM training MFU (scan-row device rate) on one chip.

The ResNet bench chases the reference's CNN headline; this row shows
the framework's matmul-path ceiling on the workload TPUs are built for:
the flagship transformer (models/transformer.py, Pallas flash
attention) with bf16 compute, one K-step lax.scan dispatch so the wall
rate IS the device rate, and cost_analysis FLOPs so the MFU numerator
is the compiled graph's own count.

Run:    python benchmarks/transformer_bench.py
Smoke:  TLM_SMOKE=1 python benchmarks/transformer_bench.py
Env:    TLM_BATCH (8) TLM_SEQ (2048) TLM_LAYERS (12) TLM_DMODEL (1024)
        TLM_SCAN_K (8) TLM_REPS (3)
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SMOKE = os.environ.get("TLM_SMOKE") == "1"
BATCH = int(os.environ.get("TLM_BATCH", "2" if SMOKE else "8"))
SEQ = int(os.environ.get("TLM_SEQ", "128" if SMOKE else "2048"))
LAYERS = int(os.environ.get("TLM_LAYERS", "2" if SMOKE else "12"))
DMODEL = int(os.environ.get("TLM_DMODEL", "128" if SMOKE else "1024"))
SCAN_K = int(os.environ.get("TLM_SCAN_K", "2" if SMOKE else "8"))
REPS = int(os.environ.get("TLM_REPS", "1" if SMOKE else "3"))
VOCAB = 1000 if SMOKE else 32000
PEAK_TFLOPS = 197.0  # v5e bf16 spec


def main():
    import jax
    import jax.numpy as jnp

    if SMOKE:
        jax.config.update("jax_platforms", "cpu")
    dev = jax.devices()[0]
    from mxnet_tpu.models.transformer import transformer_lm

    init_fn, apply_fn = transformer_lm(
        vocab=VOCAB, d_model=DMODEL, n_heads=max(DMODEL // 64, 1),
        n_layers=LAYERS, d_ff=4 * DMODEL)
    params = jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), init_fn(0))
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, VOCAB, (BATCH, SEQ)), jnp.int32)

    def loss_fn(ps, toks):
        ps_b = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16), ps)
        logits = apply_fn(ps_b, toks[:, :-1])
        tgt = toks[:, 1:]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(
            logp, tgt[..., None], axis=-1).mean()

    def train_step(ps, toks):
        loss, grads = jax.value_and_grad(loss_fn)(ps, toks)
        ps = jax.tree_util.tree_map(
            lambda p, g: p - 1e-4 * g.astype(jnp.float32), ps, grads)
        return ps, loss

    def k_steps(ps, toks):
        def body(carry, _):
            ps, _ = carry
            return train_step(ps, toks), None
        (ps, loss), _ = jax.lax.scan(
            body, (ps, jnp.asarray(0.0, jnp.float32)), None,
            length=SCAN_K)
        return ps, loss

    step = jax.jit(k_steps, donate_argnums=(0,))
    single = jax.jit(train_step, donate_argnums=(0,))
    flops = None
    try:
        ca = single.lower(params, tokens).compile().cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        flops = float(ca.get("flops", 0.0)) or None
    except Exception as e:
        print("cost_analysis unavailable: %s" % e, file=sys.stderr)

    ps, loss = step(params, tokens)
    float(loss)  # compile + warm, forced
    t0 = time.perf_counter()
    for _ in range(REPS):
        ps, loss = step(ps, tokens)
    float(loss)
    dt = time.perf_counter() - t0
    step_ms = 1000.0 * dt / (REPS * SCAN_K)
    toks_s = BATCH * (SEQ - 1) * REPS * SCAN_K / dt
    out = {
        "model": "transformer_lm d%d L%d heads%d vocab%d" % (
            DMODEL, LAYERS, max(DMODEL // 64, 1), VOCAB),
        "batch": BATCH, "seq": SEQ, "scan_k": SCAN_K,
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", "?"),
        "step_ms": round(step_ms, 2),
        "tokens_per_sec": round(toks_s, 1),
    }
    if flops:
        out["tflops_per_step"] = round(flops / 1e12, 3)
        mfu = (flops / (step_ms / 1000.0)) / (PEAK_TFLOPS * 1e12)
        if dev.platform == "tpu" and mfu <= 1.0:
            out["mfu"] = round(mfu, 4)
    tag = os.environ.get("TLM_TAG", "smoke" if SMOKE else "v5e_r4")
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "results", "transformer_bench_%s.json" % tag)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
