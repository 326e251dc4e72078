#!/usr/bin/env python
"""Per-shape conv-BACKWARD table: XLA vs the taps lever vs the Pallas pair.

Scores the gradient convs themselves, per ResNet-50 shape, through the
REAL ops/nn.py dispatch (env-gated elif chain) — so the "pallas" leg
also exercises the per-shape dispatch table, and the untuned row doubles
as the fallback proof (plan None => the leg compiles to the same XLA
program as the baseline). Emitted standalone by this script and as
benchmark_score.py's `conv` section under SCORE_CONV=1.

On the v5e the Pallas pair is refused by Mosaic at most ResNet-50 shapes
(scoped VMEM, see PERF.md); such a leg lands as a `pallas_error` row.

Run: python benchmarks/conv_bwd_experiments.py
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (name, dshape, wshape, stride, pad). The stride-1 3x3 body convs are
# the tuned envelope; the stride-2 projection is deliberately OUTSIDE it.
_SCORE_SHAPES = [
    ("r50_3x3_56x56x64", (32, 64, 56, 56), (64, 64, 3, 3),
     (1, 1), (1, 1)),
    ("r50_3x3_28x28x128", (32, 128, 28, 28), (128, 128, 3, 3),
     (1, 1), (1, 1)),
    ("r50_3x3_14x14x256", (32, 256, 14, 14), (256, 256, 3, 3),
     (1, 1), (1, 1)),
    ("r50_3x3_7x7x512", (32, 512, 7, 7), (512, 512, 3, 3),
     (1, 1), (1, 1)),
    ("r50_proj_1x1s2_untuned", (32, 256, 56, 56), (512, 256, 1, 1),
     (2, 2), (0, 0)),
]
_SCORE_SHAPES_SMOKE = [
    ("smoke_3x3_14x14x16", (4, 16, 14, 14), (16, 16, 3, 3),
     (1, 1), (1, 1)),
    ("smoke_1x1s2_untuned", (4, 16, 14, 14), (32, 16, 1, 1),
     (2, 2), (0, 0)),
]

_CONV_LEG_ENVS = {
    "xla": {"MXTPU_CONV_KERNEL": None, "MXNET_CONV_WGRAD": None,
            "MXNET_CONV_BWD_LAYOUT": None, "MXNET_CONV_S2D": None},
    "taps": {"MXTPU_CONV_KERNEL": None, "MXNET_CONV_WGRAD": "taps",
             "MXNET_CONV_BWD_LAYOUT": None, "MXNET_CONV_S2D": None},
    "pallas": {"MXTPU_CONV_KERNEL": "pallas", "MXNET_CONV_WGRAD": None,
               "MXNET_CONV_BWD_LAYOUT": None, "MXNET_CONV_S2D": None},
}


def _time_conv_bwd(jax, jnp, dshape, wshape, stride, pad, reps, dtype):
    """Wall ms of one backward (dgrad+wgrad) of the conv the CURRENT env
    dispatches, jitted, min over reps."""
    import numpy as np

    from mxnet_tpu.ops import nn as _nn

    attrs = {"kernel": tuple(wshape[2:]), "stride": tuple(stride),
             "pad": tuple(pad), "no_bias": True,
             "num_filter": wshape[0]}
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(*dshape), dtype)
    w = jnp.asarray(rng.randn(*wshape) * 0.1, dtype)

    def fwd(x, w):
        return _nn._convolution(attrs, [x, w], True)[0]

    ct = jnp.asarray(rng.randn(*jax.eval_shape(fwd, x, w).shape), dtype)

    @jax.jit
    def bwd(x, w, ct):
        _, vjp = jax.vjp(fwd, x, w)
        gd, gw = vjp(ct)
        # tiny outputs: the read below blocks on the grads without
        # timing a device->host transfer of the full tensors
        return gd.ravel()[0].astype(jnp.float32), \
            gw.ravel()[0].astype(jnp.float32)

    g0, g1 = bwd(x, w, ct)  # compile + warm
    float(g0), float(g1)
    best = None
    for _ in range(reps):
        t0 = time.perf_counter()
        g0, g1 = bwd(x, w, ct)
        float(g0), float(g1)
        dt = 1000.0 * (time.perf_counter() - t0)
        best = dt if best is None else min(best, dt)
    return best


def run_conv_score(jax, jnp, smoke=None, reps=None, dtype=None):
    """The per-shape XLA-vs-Pallas-vs-taps conv-backward table.

    Returns {"dtype", "platform", "kernels_ran", "rows": [...]} where each
    row carries per-leg backward ms, the dispatch plan for the shape
    (None = fell back to XLA), and speedups vs the XLA leg."""
    from mxnet_tpu.ops import kernels as _pk

    if smoke is None:
        smoke = jax.default_backend() != "tpu"
    if reps is None:
        reps = int(os.environ.get("SCORE_CONV_REPS", "3" if smoke else "10"))
    dtype = dtype or (jnp.float32 if jax.default_backend() != "tpu"
                      else jnp.bfloat16)
    shapes = _SCORE_SHAPES_SMOKE if smoke else _SCORE_SHAPES
    rows = []
    for name, dshape, wshape, stride, pad in shapes:
        plan = _pk.conv_bwd_plan(dshape, wshape, stride, pad, (1, 1),
                                 jnp.dtype(dtype).name)
        row = {"shape": name, "dshape": list(dshape),
               "wshape": list(wshape), "stride": list(stride),
               "pad": list(pad), "plan": plan}
        for leg, env in _CONV_LEG_ENVS.items():
            saved = {k: os.environ.get(k) for k in env}
            for k, v in env.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
            try:
                row["%s_ms" % leg] = round(_time_conv_bwd(
                    jax, jnp, dshape, wshape, stride, pad, reps,
                    dtype), 3)
            except Exception as e:  # noqa: BLE001 — keep scoring
                row["%s_error" % leg] = str(e)[:200]
            finally:
                for k, v in saved.items():
                    if v is None:
                        os.environ.pop(k, None)
                    else:
                        os.environ[k] = v
        if row.get("xla_ms"):
            for leg in ("pallas", "taps"):
                if row.get("%s_ms" % leg):
                    row["speedup_%s_vs_xla" % leg] = round(
                        row["xla_ms"] / row["%s_ms" % leg], 3)
        rows.append(row)
        print(json.dumps(row), file=sys.stderr)
    return {"dtype": jnp.dtype(dtype).name,
            "platform": jax.default_backend(),
            # off the TPU the pallas leg is the pair's plain form, XLA's
            # gradient convs again (ops/kernels/conv.py): such rows prove
            # the dispatch table; TPU rows are the perf numbers
            "kernels_ran": jax.default_backend() == "tpu",
            "reps": reps,
            "rows": rows}


def main():
    import jax
    import jax.numpy as jnp

    # SCORE_CONV_FULL=1 forces the real ResNet shapes even off-TPU
    # (the dispatch table and speedup table then cover the tuned
    # envelope, not the smoke stand-ins)
    score = run_conv_score(
        jax, jnp,
        smoke=(False if os.environ.get("SCORE_CONV_FULL") == "1"
               else None))
    res_dir = os.environ.get("EXP_RESULTS_DIR") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "results")
    os.makedirs(res_dir, exist_ok=True)
    path = os.path.join(
        res_dir, "conv_score_%s.json"
        % os.environ.get("EXP_TAG", score["platform"]))
    with open(path + ".tmp", "w") as f:
        json.dump(score, f, indent=1)
    os.replace(path + ".tmp", path)
    print(json.dumps({"written": path, "rows": len(score["rows"])}))


if __name__ == "__main__":
    main()
