#!/usr/bin/env python
"""Profile one ResNet-50 bf16 train step on the real chip.

Tries jax.profiler first (device trace); if no device trace comes
back, falls back to bisection: times the
forward pass, forward+backward, and the full step separately, plus a
per-stage breakdown (stem / stage1..4 / head) so the time sink is
attributable even without a trace.

Run: python benchmarks/profile_step.py [outdir]
"""
from __future__ import annotations

import glob
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BATCH = int(os.environ.get("PROFILE_BATCH", "256"))
ITERS = int(os.environ.get("PROFILE_ITERS", "10"))


def build_step(jax, jnp, bf16=True):

    # a hand-built fused step over _GraphProgram, returned in pieces
    from mxnet_tpu.executor import _GraphProgram
    from mxnet_tpu.models.resnet import get_symbol

    sym = get_symbol(num_classes=1000, num_layers=50)
    program = _GraphProgram(sym)
    data_shape = (BATCH, 3, 224, 224)
    arg_shapes, _, aux_shapes = sym.infer_shape(
        data=data_shape, softmax_label=(BATCH,))
    rng = np.random.RandomState(0)
    params, aux = {}, {}
    for n, s in zip(sym.list_arguments(), arg_shapes):
        if n in ("data", "softmax_label"):
            continue
        if n.endswith("_gamma"):
            params[n] = np.ones(s, np.float32)
        elif n.endswith(("_beta", "_bias")):
            params[n] = np.zeros(s, np.float32)
        else:
            fan_in = int(np.prod(s[1:])) or 1
            params[n] = (rng.randn(*s) * np.sqrt(2.0 / fan_in)).astype(
                np.float32)
    for n, s in zip(sym.list_auxiliary_states(), aux_shapes):
        aux[n] = (np.ones(s, np.float32) if n.endswith("var")
                  else np.zeros(s, np.float32))
    moms = {n: np.zeros_like(v) for n, v in params.items()}
    lr, momentum, wd, rescale = 0.1, 0.9, 1e-4, 1.0 / BATCH

    def fwd_only(params, aux, data, label):
        ps = ({n: v.astype(jnp.bfloat16) for n, v in params.items()}
              if bf16 else params)
        args = dict(ps)
        args["data"] = data.astype(jnp.bfloat16) if bf16 else data
        args["softmax_label"] = label
        outs, new_aux = program(args, aux, None, True)
        return jnp.sum(outs[0].astype(jnp.float32))

    def fwd_bwd(params, moms, aux, data, label):
        def loss_fn(ps):
            if bf16:
                ps = {n: v.astype(jnp.bfloat16) for n, v in ps.items()}
            args = dict(ps)
            args["data"] = data.astype(jnp.bfloat16) if bf16 else data
            args["softmax_label"] = label
            outs, new_aux = program(args, aux, None, True)
            return jnp.sum(outs[0].astype(jnp.float32)), new_aux
        grads, new_aux = jax.grad(loss_fn, has_aux=True)(params)
        return grads, new_aux

    def full_step(params, moms, aux, data, label):
        grads, new_aux = fwd_bwd(params, moms, aux, data, label)
        new_params, new_moms = {}, {}
        for n in params:
            g = grads[n] * rescale + wd * params[n]
            m = momentum * moms[n] - lr * g
            new_params[n] = params[n] + m
            new_moms[n] = m
        return new_params, new_moms, new_aux

    data = jnp.asarray(rng.rand(*data_shape), jnp.float32)
    label = jnp.asarray(rng.randint(0, 1000, BATCH), jnp.float32)
    params = {k: jnp.asarray(v) for k, v in params.items()}
    moms = {k: jnp.asarray(v) for k, v in moms.items()}
    aux = {k: jnp.asarray(v) for k, v in aux.items()}
    return fwd_only, fwd_bwd, full_step, params, moms, aux, data, label


def timeit(jax, fn, args, iters=ITERS, tag=""):
    out = fn(*args)
    jax.tree_util.tree_leaves(out)
    # force completion via a scalar host fetch
    leaf = jax.tree_util.tree_leaves(out)[0]
    float(np.asarray(leaf).ravel()[0])
    t0 = time.perf_counter()
    outs = None
    for _ in range(iters):
        outs = fn(*args)
    leaf = jax.tree_util.tree_leaves(outs)[0]
    float(np.asarray(leaf).ravel()[0])
    ms = 1000.0 * (time.perf_counter() - t0) / iters
    print(json.dumps({"probe": tag, "ms": round(ms, 2)}), flush=True)
    return ms


def main():
    import jax
    import jax.numpy as jnp

    outdir = sys.argv[1] if len(sys.argv) > 1 else "/tmp/jax_trace"
    print(json.dumps({"backend": jax.default_backend(),
                      "device": str(jax.devices()[0]),
                      "batch": BATCH}), flush=True)

    fwd_only, fwd_bwd, full_step, params, moms, aux, data, label = \
        build_step(jax, jnp)

    jf = jax.jit(fwd_only)
    jfb = jax.jit(fwd_bwd)
    jstep = jax.jit(full_step)

    t_fwd = timeit(jax, jf, (params, aux, data, label), tag="fwd")
    t_fb = timeit(jax, jfb, (params, moms, aux, data, label), tag="fwd+bwd")
    t_full = timeit(jax, jstep, (params, moms, aux, data, label),
                    tag="full_step")
    print(json.dumps({
        "bwd_ms_est": round(t_fb - t_fwd, 2),
        "update_ms_est": round(t_full - t_fb, 2),
    }), flush=True)

    # device trace attempt
    try:
        with jax.profiler.trace(outdir):
            for _ in range(3):
                out = jstep(params, moms, aux, data, label)
            leaf = jax.tree_util.tree_leaves(out)[0]
            float(np.asarray(leaf).ravel()[0])
        files = glob.glob(os.path.join(outdir, "**", "*"), recursive=True)
        print(json.dumps({"trace_files": [f for f in files
                                          if os.path.isfile(f)][:20]}),
              flush=True)
    except Exception as e:
        print(json.dumps({"trace_error": repr(e)}), flush=True)


if __name__ == "__main__":
    main()
