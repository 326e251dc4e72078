#!/usr/bin/env python
"""Dispatch-overhead A/B on the REAL Module.fit loop.

A small-batch step can pay as much host dispatch as device time (share
on the chip not measured; PERF.md). MXNET_FIT_MULTISTEP=K groups K
batches into ONE XLA dispatch (lax.scan over the fused step,
module.Module.update_multi); this script measures the actual fit() wall
throughput — Speedometer-visible img/s, synthetic data, a dp=1 mesh
with kvstore 'device' so the fused path engages on one device — at K=1
vs K>1 and emits one JSON line with both rows. Off the chip it fails
unless FITB_SMOKE=1 asks for the CPU smoke.

Reference frame: the reference hides the same overhead with its
threaded engine (src/engine/threaded_engine_perdevice.cc:26-136 — the
python thread never waits on the device); here the dispatch itself is
amortized inside XLA instead.

Run:    python benchmarks/fit_dispatch_bench.py
Smoke:  FITB_SMOKE=1 python benchmarks/fit_dispatch_bench.py
Env:    FITB_BATCH (32) FITB_K (8) FITB_MEASURE (64 batches)
        FITB_WARM (16 batches) FITB_DTYPE (bfloat16) FITB_TAG
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SMOKE = os.environ.get("FITB_SMOKE") == "1"
BATCH = int(os.environ.get("FITB_BATCH", "8" if SMOKE else "32"))
K = int(os.environ.get("FITB_K", "2" if SMOKE else "8"))
WARM = int(os.environ.get("FITB_WARM", "4" if SMOKE else "16"))
MEASURE = int(os.environ.get("FITB_MEASURE", "8" if SMOKE else "64"))
DTYPE = os.environ.get("FITB_DTYPE", "bfloat16")
NUM_LAYERS = int(os.environ.get("FITB_LAYERS", "18" if SMOKE else "50"))


def _iter(num_batches):
    import numpy as np

    import mxnet_tpu as mx

    shape = (3, 32, 32) if SMOKE else (3, 224, 224)
    rng = np.random.RandomState(0)
    X = rng.rand(BATCH, *shape).astype(np.float32)
    y = rng.randint(0, 1000, BATCH).astype(np.float32)
    inner = mx.io.NDArrayIter(X, y, batch_size=BATCH)
    return mx.io.ResizeIter(inner, num_batches)


def measure_fit(k):
    """One fit() epoch; returns wall img/s over the post-warmup batches.

    Timing via batch_end_callback timestamps: warm-up (compile +
    first dispatches) ends at nbatch==WARM-1, measurement ends at the
    final batch. Both boundaries are multiples of K so callback bursts
    (K fire back-to-back after each dispatch) can't split a group
    across the boundary."""
    import mxnet_tpu as mx

    if k > 1:
        os.environ["MXNET_FIT_MULTISTEP"] = str(k)
    else:
        os.environ.pop("MXNET_FIT_MULTISTEP", None)
    try:
        from mxnet_tpu.models.resnet import get_symbol

        sym = get_symbol(num_classes=1000, num_layers=NUM_LAYERS,
                         dtype=DTYPE,
                         image_shape="3,32,32" if SMOKE else "3,224,224")
        total = WARM + MEASURE
        it = _iter(total)
        from mxnet_tpu.parallel import make_mesh

        mod = mx.mod.Module(sym, context=mx.cpu() if SMOKE else mx.tpu(),
                            mesh=make_mesh(dp=1))
        marks = {}

        def cb(param):
            if param.nbatch in (WARM - 1, total - 1):
                # force completion of the dispatch this batch rode in on
                outs = mod.get_outputs()
                if outs:
                    outs[0].asnumpy()
                marks[param.nbatch] = time.perf_counter()

        mod.fit(it, optimizer="sgd",
                optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
                kvstore="device", num_epoch=1,
                initializer=mx.init.Xavier(rnd_type="gaussian",
                                           factor_type="in", magnitude=2),
                batch_end_callback=cb)
        dt = marks[total - 1] - marks[WARM - 1]
        img_s = MEASURE * BATCH / dt
        return {"k": k, "images_per_sec": round(img_s, 2),
                "step_ms": round(1000.0 * dt / MEASURE, 2)}
    finally:
        os.environ.pop("MXNET_FIT_MULTISTEP", None)


def main():
    import jax

    if SMOKE:
        jax.config.update("jax_platforms", "cpu")
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not SMOKE:
        print(json.dumps({"error": "no TPU", "platform": dev.platform}))
        return 2
    rows = []
    for k in (1, K):
        try:
            rows.append(measure_fit(k))
            print(json.dumps(rows[-1]), flush=True)
        except Exception as e:  # noqa: BLE001 — record, run the other row
            rows.append({"k": k, "error": str(e)[:300]})
    out = {
        "bench": "fit_dispatch", "batch": BATCH,
        "model": "resnet-%d %s" % (NUM_LAYERS, DTYPE),
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", "?"),
        "warm_batches": WARM, "measured_batches": MEASURE,
        "rows": rows,
    }
    ok = [r for r in rows if "images_per_sec" in r]
    if len(ok) == 2:
        out["speedup_k%d_vs_k1" % K] = round(
            ok[1]["images_per_sec"] / ok[0]["images_per_sec"], 3)
    tag = os.environ.get("FITB_TAG", "smoke" if SMOKE else dev.platform)
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "results", "fit_dispatch_%s.json" % tag)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0 if len(ok) == 2 else 1


if __name__ == "__main__":
    sys.exit(main())
