#!/usr/bin/env python
"""Async dispatch pipeline A/B: per-step HOST overhead, sync vs async
(ISSUE 3 acceptance bench).

The r5 TPU capture pays ~11 ms host dispatch against a 28 ms step at
batch 32 — host-side Python staging (asnumpy + device_put per batch)
plus the blocking per-batch metric fetch. The async pipeline removes
both from the hot loop:

- DeviceFeedIter stages the NEXT batch's device transfer while the
  current step computes (MXTPU_DEVICE_FEED, default on), and
  Module._make_fused_batch adopts the staged buffers by sharding
  equality — no per-step asnumpy/device_put;
- fit keeps one fused step in flight (both legs: it has no switch), so
  the metric's np.asarray waits with the next step already enqueued;
- _GraphProgram.dispatch_plan caches the per-(shape,dtype,sharding)
  canonicalization, so steady-state steps skip the dict churn.

This bench runs the REAL Module.fit on a synthetic convnet (CPU-
friendly; fixed seed) in both modes and compares the telemetry
histogram ``module.stage_host_seconds`` — the input-staging slice of a
step, the host work the feed removes. The full-window
``module.dispatch_host_seconds`` (staging + enqueue) and wall time are
recorded as context: on the CPU backend the enqueue itself BLOCKS on
donated in-flight buffers (jax CPU-client artifact — dispatching a
donating jit whose donated input is still computing waits for it;
measured 40-70 ms vs 0.01 ms undonated), so enqueue can never go
sub-compute on CPU the way it does on TPU. Warm-up epoch (compiles,
first dispatches) is excluded via histogram deltas at the epoch
boundary.

Asserts (exit 1 on failure, DOB_NO_ASSERT=1 to only record):
- async per-step staging overhead < 2 ms (the TPU-round target)
- sync/async staging-overhead ratio >= 3x

Run:    JAX_PLATFORMS=cpu python benchmarks/dispatch_overlap_bench.py
Smoke:  DOB_SMOKE=1 ... (tiny sizes; asserts skipped)
Env:    DOB_BATCH (256) DOB_STEPS (20 measured/epoch)
        DOB_TAG DOB_NO_ASSERT
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SMOKE = os.environ.get("DOB_SMOKE") == "1"
BATCH = int(os.environ.get("DOB_BATCH", "32" if SMOKE else "256"))
STEPS = int(os.environ.get("DOB_STEPS", "6" if SMOKE else "20"))
NDEV = int(os.environ.get("DOB_DEVICES", "4"))

# the fused mesh path needs a multi-device context; on CPU use the test
# suite's virtual-device rig (must run before jax initializes)
if os.environ.get("JAX_PLATFORMS", "cpu") == "cpu":
    from __graft_entry__ import _force_cpu_mesh_platform  # noqa: E402

    _force_cpu_mesh_platform(NDEV)
_ENV_KNOBS = ("MXTPU_DEVICE_FEED", "MXNET_FIT_MULTISTEP")


def _convnet():
    import mxnet_tpu as mx

    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, num_filter=8, kernel=(3, 3),
                             pad=(1, 1), name="conv1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2),
                         pool_type="max")
    net = mx.sym.Convolution(net, num_filter=16, kernel=(3, 3),
                             pad=(1, 1), name="conv2")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, global_pool=True, kernel=(2, 2),
                         pool_type="avg")
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=10, name="fc")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _iter():
    import mxnet_tpu as mx

    rng = np.random.RandomState(0)
    n = BATCH * STEPS
    X = rng.rand(n, 3, 32, 32).astype(np.float32)
    y = rng.randint(0, 10, n).astype(np.float32)
    return mx.io.NDArrayIter(X, y, batch_size=BATCH)


def _anatomy_summary(jl_path):
    """Digest the {"type": "anatomy"} interval records of one fit run:
    per-step phase breakdown over the steady (post-warmup) intervals,
    the explicit unattributed remainder, and the record invariant that
    named phases + unattributed == measured wall (ISSUE 6 acceptance)."""
    from mxnet_tpu import telemetry

    telemetry.flush()
    from tools.trace_summary import load_anatomy

    recs = load_anatomy(jl_path)
    if not recs:
        return None
    sums_ok = all(
        abs(sum(r["phases"].values()) + r["unattributed_seconds"]
            - r["wall_seconds"]) < 1e-6
        for r in recs)
    steady = recs[1:] if len(recs) > 1 else recs
    steps = sum(r["steps"] for r in steady) or 1
    out = {
        "intervals": len(recs),
        "steady_step_ms": round(
            1000.0 * sum(r["wall_seconds"] for r in steady) / steps, 4),
        "phases_ms_per_step": {
            k: round(1000.0 * sum(r["phases"].get(k, 0.0)
                                  for r in steady) / steps, 4)
            for k in recs[0]["phases"]},
        "unattributed_ms_per_step": round(
            1000.0 * sum(r["unattributed_seconds"] for r in steady)
            / steps, 4),
        "phases_plus_unattributed_equals_wall": sums_ok,
        "recompiles": sum(r.get("recompiles", 0) for r in recs),
    }
    mfus = [r["mfu"] for r in recs if r.get("mfu") is not None]
    if mfus:
        out["mfu_last"] = round(mfus[-1], 4)
    bounds = [r.get("roofline", {}).get("bound") for r in steady]
    if any(bounds):
        out["roofline_bound"] = bounds[-1]
    return out


def measure(mode):
    """Two fit epochs (warm + measured); returns per-step host dispatch
    ms over the measured epoch plus the final Train metric for the
    parity record."""
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry

    for k in _ENV_KNOBS:
        os.environ.pop(k, None)
    if mode == "sync":
        os.environ["MXTPU_DEVICE_FEED"] = "0"
    else:
        os.environ["MXTPU_DEVICE_FEED"] = "1"
    try:
        telemetry.reset()
        # JSONL sink so the anatomy layer's per-interval step records
        # land on disk; epoch boundaries force-close an interval, so a
        # 2-epoch fit yields (warmup, measured) records
        jl_path = os.path.join(
            tempfile.mkdtemp(prefix="dob_anatomy_"), mode + ".jsonl")
        telemetry.enable(jsonl=jl_path)
        stage = telemetry.histogram("module.stage_host_seconds")
        hist = telemetry.histogram("module.dispatch_host_seconds")
        mx.random.seed(0)
        np.random.seed(0)
        it = _iter()
        mod = mx.mod.Module(_convnet(),
                            context=[mx.cpu(i) for i in range(NDEV)])
        metric = mx.metric.Accuracy()
        marks = []

        def epoch_cb(epoch, sym, arg, aux):
            marks.append((stage.sum(), stage.count(),
                          hist.sum(), hist.count()))

        t0 = time.perf_counter()
        mod.fit(it, eval_metric=metric, optimizer="sgd",
                optimizer_params={"learning_rate": 0.05},
                kvstore="device", num_epoch=2,
                initializer=mx.init.Uniform(0.05),
                epoch_end_callback=epoch_cb)
        wall = time.perf_counter() - t0
        assert mod._fused_trainer is not None, "fused path did not engage"
        (ss1, sc1, ds1, dc1), (ss2, sc2, ds2, dc2) = marks[0], marks[1]
        assert sc2 > sc1, "no measured-epoch dispatches recorded"
        return {
            "mode": mode,
            "stage_host_ms_per_step":
                round(1000.0 * (ss2 - ss1) / (sc2 - sc1), 4),
            "dispatch_host_ms_per_step":
                round(1000.0 * (ds2 - ds1) / (dc2 - dc1), 4),
            "measured_steps": sc2 - sc1,
            "train_metric": metric.get()[1],
            "wall_s": round(wall, 2),
            "anatomy": _anatomy_summary(jl_path),
        }
    finally:
        for k in _ENV_KNOBS:
            os.environ.pop(k, None)
        from mxnet_tpu import telemetry as _t

        _t.reset()
        _t.disable()


def main():
    import jax

    dev = jax.devices()[0]
    rows = [measure("sync"), measure("async")]
    for r in rows:
        print(json.dumps(r), file=sys.stderr)
    sync_ms = rows[0]["stage_host_ms_per_step"]
    async_ms = rows[1]["stage_host_ms_per_step"]
    out = {
        "bench": "dispatch_overlap",
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", "?"),
        "batch": BATCH, "steps_per_epoch": STEPS,
        "rows": rows,
        "host_overhead_reduction_x": round(sync_ms / async_ms, 2)
        if async_ms else None,
        "async_under_2ms": bool(async_ms < 2.0),
        "metric_parity": rows[0]["train_metric"] == rows[1]["train_metric"],
        "anatomy_sum_matches_wall": all(
            r["anatomy"] is not None
            and r["anatomy"]["phases_plus_unattributed_equals_wall"]
            for r in rows),
        "target": "<2 ms/step host staging, >=3x reduction vs sync "
                  "(ISSUE 3 acceptance; dispatch_host_ms and wall_s "
                  "recorded as context — CPU enqueue blocks on donated "
                  "in-flight buffers, TPU does not)",
    }
    tag = os.environ.get("DOB_TAG", "smoke" if SMOKE else "cpu_r6")
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "results", "dispatch_overlap_%s.json" % tag)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    if SMOKE or os.environ.get("DOB_NO_ASSERT") == "1":
        return 0
    ok = (out["async_under_2ms"]
          and out["host_overhead_reduction_x"] is not None
          and out["host_overhead_reduction_x"] >= 3.0
          and out["metric_parity"])
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
