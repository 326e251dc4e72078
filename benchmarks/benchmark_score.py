#!/usr/bin/env python
"""Inference throughput (images/sec) for the model zoo — the analog of
the reference's example/image-classification/benchmark_score.py, which
feeds random batches through a bound forward-only executor and reports
img/s per (network, batch size).

TPU redesign: the forward is ONE jitted XLA program; a K-step lax.scan
wraps it so each dispatch amortizes the host dispatch and the wall
rate approaches the device rate. bf16
inference is the default on TPU (the MXU's native rate); f32 rows via
SCORE_F32=1.

Run:       python benchmarks/benchmark_score.py
Smoke:     SCORE_SMOKE=1 python benchmarks/benchmark_score.py
"""
from __future__ import annotations

import glob
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SMOKE = os.environ.get("SCORE_SMOKE") == "1"
NETWORKS = os.environ.get(
    "SCORE_NETS", "resnet-50" if not SMOKE else "resnet-18").split(",")
BATCHES = [int(b) for b in os.environ.get(
    "SCORE_BATCHES", "1,32,256" if not SMOKE else "2").split(",")]
SCAN_K = int(os.environ.get("SCORE_SCAN_K", "2" if SMOKE else "16"))
REPS = int(os.environ.get("SCORE_REPS", "1" if SMOKE else "3"))

from mxnet_tpu import telemetry as _tm  # noqa: E402
from mxnet_tpu.telemetry import costmodel  # noqa: E402

_H_DISPATCH = _tm.histogram(
    "bench.dispatch_seconds",
    "benchmark_score per-dispatch host enqueue time (async: excludes "
    "device compute)")


def get_symbol(name):
    if name.startswith("resnet-"):
        from mxnet_tpu.models.resnet import get_symbol as f

        return f(num_classes=1000, num_layers=int(name.split("-")[1]))
    if name == "inception-bn":
        from mxnet_tpu.models.inception_bn import get_symbol as f

        return f(num_classes=1000)
    if name == "inception-v3":
        from mxnet_tpu.models.inception_v3 import get_symbol as f

        return f(num_classes=1000)
    raise ValueError("unknown network %s" % name)


def score(jax, jnp, name, batch, bf16):
    from mxnet_tpu.executor import _GraphProgram

    sym = get_symbol(name)
    program = _GraphProgram(sym)
    data_shape = (batch, 3, 224, 224)
    arg_shapes, _, aux_shapes = sym.infer_shape(
        data=data_shape, softmax_label=(batch,))
    rng = np.random.RandomState(0)
    dt = jnp.bfloat16 if bf16 else jnp.float32
    params = {}
    for n, s in zip(sym.list_arguments(), arg_shapes):
        if n in ("data", "softmax_label"):
            continue
        if n.endswith("_gamma"):
            params[n] = jnp.ones(s, dt)
        elif n.endswith(("_beta", "_bias")):
            params[n] = jnp.zeros(s, dt)
        else:
            fan = int(np.prod(s[1:])) or 1
            params[n] = jnp.asarray(
                rng.randn(*s) * np.sqrt(2.0 / fan), dt)
    aux = {n: (jnp.ones(s, jnp.float32) if n.endswith("var")
               else jnp.zeros(s, jnp.float32))
           for n, s in zip(sym.list_auxiliary_states(), aux_shapes)}
    label = jnp.zeros((batch,), jnp.float32)

    def fwd(x):
        args = dict(params)
        args["data"] = x.astype(dt)
        args["softmax_label"] = label
        outs, _ = program(args, aux, None, False)
        return outs[0]

    def k_scan(x):
        def body(c, _):
            y = fwd(c)
            # fold a whiff of the output back in: keeps every iteration
            # live without changing what is measured
            return c + 1e-6 * y.mean().astype(c.dtype), None
        out, _ = jax.lax.scan(body, x, None, length=SCAN_K)
        return out

    run = jax.jit(k_scan)
    x = jnp.asarray(rng.rand(*data_shape), jnp.float32)
    # per-image FLOPs from both cost models (telemetry/costmodel.py):
    # XLA's own accounting of the program we actually run, and the
    # hand-counted conv/FC MACs — BENCH jsons carry both so the MFU can
    # be cross-checked against the classical number
    flops = {}
    try:
        # XLA's HloCostAnalysis sums each loop BODY once (trip count is
        # not multiplied in), so the K-scan program's cost is already
        # one forward pass: divide by batch only
        cost = costmodel.extract_cost(run.lower(x).compile())
        if cost["flops"]:
            flops["xla_flops_per_image"] = cost["flops"] / batch
        if cost["bytes_accessed"]:
            flops["xla_bytes_per_image"] = cost["bytes_accessed"] / batch
    except Exception:  # noqa: BLE001 — accounting must not break scoring
        pass
    try:
        flops["analytic_flops_per_image"] = (
            costmodel.analytic_forward_flops(
                sym, data=data_shape, softmax_label=(batch,)) / batch)
    except Exception:  # noqa: BLE001
        pass
    out = run(x)
    float(out.ravel()[0].astype(jnp.float32))  # compile + warm
    t0 = time.perf_counter()
    for _ in range(REPS):
        out = run(out)
    float(out.ravel()[0].astype(jnp.float32))
    dtime = time.perf_counter() - t0
    # host dispatch overhead: wall time to ENQUEUE one async dispatch
    # (the jitted call returns before the device computes; blocking
    # happens at the float() read above) — the same host component the
    # async-pipeline telemetry tracks for training
    # (module.dispatch_host_seconds)
    disp = []
    for _ in range(max(3, REPS)):
        d0 = time.perf_counter()
        out = run(out)
        disp.append(time.perf_counter() - d0)
        _H_DISPATCH.observe(disp[-1])
    out.block_until_ready()
    n_img = batch * SCAN_K * REPS
    return (n_img / dtime, 1000.0 * dtime / (SCAN_K * REPS),
            1000.0 * min(disp), flops)


def main():
    import jax
    import jax.numpy as jnp

    if SMOKE:
        jax.config.update("jax_platforms", "cpu")
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    rows = []
    for name in NETWORKS:
        for batch in BATCHES:
            for bf16 in ([True, False] if (on_tpu and
                         os.environ.get("SCORE_F32") == "1")
                         else [on_tpu]):
                img_s, step_ms, disp_ms, flops = score(
                    jax, jnp, name, batch, bf16)
                row = {
                    "network": name, "batch": batch,
                    "dtype": "bf16" if bf16 else "f32",
                    "images_per_sec": round(img_s, 1),
                    "fwd_ms": round(step_ms, 3),
                    # BENCH_* rounds track this next to img/s: the
                    # async-pipeline target is <2 ms (ISSUE 3)
                    "dispatch_overhead_ms": round(disp_ms, 3),
                }
                for k, v in flops.items():
                    row[k] = round(v, 1)
                fx = flops.get("xla_flops_per_image")
                fa = flops.get("analytic_flops_per_image")
                if fx and fa:
                    # the anatomy acceptance gate: XLA's accounting and
                    # the hand count should agree within ~10% on convnets
                    row["flops_xla_vs_analytic"] = round(fx / fa, 4)
                peak = costmodel.peak_flops_for_kind(
                    getattr(dev, "device_kind", ""),
                    dtype=row["dtype"])
                fl = fx or fa
                if peak and fl:
                    # forward-only MFU at the measured wall rate — the
                    # scaling model (scaling_model_r5.json) tracks this
                    # toward the 70% target
                    row["mfu"] = round(img_s * fl / peak, 4)
                rows.append(row)
                print(json.dumps(rows[-1]), file=sys.stderr)
    _peak = costmodel.peak_flops_for_kind(getattr(dev, "device_kind", ""))
    out = {
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", "?"),
        "anatomy": {
            "peak_tflops": _peak / 1e12 if _peak else None,
            "flops_convention": "2 MACs per multiply-add, forward only; "
                                "xla_* fields are cost_analysis() of the "
                                "scanned program divided back per image",
        },
        "scan_k": SCAN_K,
        "reference_anchor": "example/image-classification/"
                            "benchmark_score.py (K80 CUDA 7.5: resnet-50 "
                            "~48 img/s fwd at batch 32 per its README era)",
        "rows": rows,
    }
    if os.environ.get("SCORE_SHARDED_AB", "0") == "1":
        # ISSUE 5 rider: sharded-vs-replicated weight-update A/B
        # (update_host_ms, comm_bytes_per_step) on a small MLP — the
        # same harness kvstore_overlap_bench.py runs at full size
        from benchmarks.sharded_ab import run_sharded_ab

        ab_dev = min(8, jax.device_count())
        out["sharded_update_ab"] = run_sharded_ab(
            ndev=ab_dev, batch=16 * ab_dev, in_dim=256, n_hidden=256,
            n_layers=3, reps=3 if SMOKE else 10)
        print(json.dumps(out["sharded_update_ab"]), file=sys.stderr)
    if os.environ.get("SCORE_AMP", "0") == "1":
        # ISSUE 8 rider: bf16-AMP vs fp32 A/B over the sharded update
        # (update+collective time, images/sec, per-dtype collective
        # bytes, convergence gate) — full size in benchmarks/amp_ab.py
        from benchmarks.amp_ab import run_amp_ab

        ab_dev = min(8, jax.device_count())
        out["amp_ab"] = run_amp_ab(
            ndev=ab_dev, batch=32 * ab_dev, in_dim=512,
            n_hidden=256 if SMOKE else 512,
            n_layers=3 if SMOKE else 6, reps=3 if SMOKE else 10)
        print(json.dumps(out["amp_ab"]), file=sys.stderr)
    if os.environ.get("SCORE_CONV", "0") == "1":
        # ISSUE 17 rider: per-shape XLA-vs-Pallas-vs-taps conv-backward
        # table through the real ops/nn.py dispatch — the tuned-envelope
        # speedup AND the untuned-shape fallback proof land in the same
        # artifact (full sweep: benchmarks/conv_bwd_experiments.py)
        from benchmarks.conv_bwd_experiments import run_conv_score

        out["conv"] = run_conv_score(jax, jnp, smoke=SMOKE or not on_tpu)
        print(json.dumps({"conv_rows": len(out["conv"]["rows"])}),
              file=sys.stderr)
    if os.environ.get("SCORE_INPUT", "0") == "1":
        # ISSUE 18 rider: host input-pipeline A/B — thread-pool decode
        # (preprocess_threads) vs the streaming process pool
        # (MXTPU_INPUT_WORKERS), one input_img_s row per setting, with
        # the io.decode_seconds / io.queue_depth / io.bytes_read
        # backpressure telemetry in the same BENCH artifact. The
        # acceptance gate reads process_vs_thread_speedup (>= 2x at
        # workers=4 on an 8-core host).
        from benchmarks.input_pipeline import run_input_bench

        out["input_pipeline"] = run_input_bench(
            n_images=32 if SMOKE else 256,
            image_size=64 if SMOKE else 224,
            threads=(1, 4) if SMOKE else (1, 4, 8),
            workers=(2,) if SMOKE else (2, 4),
            epochs=1 if SMOKE else 2)
        print(json.dumps({"input_pipeline": out["input_pipeline"]["rows"],
                          "speedup": out["input_pipeline"].get(
                              "process_vs_thread_speedup")}),
              file=sys.stderr)
    if os.environ.get("SCORE_SERVE", "0") == "1":
        # ISSUE 20 rider: serving-path leg — continuous batching vs
        # sequential dispatch (>= 3x gate at max_batch=8), open-loop
        # Poisson p50/p99 latency, KV-cached decode tokens/s, int8
        # parity, and the zero-steady-state-recompile proof, all in the
        # same BENCH artifact (full run in benchmarks/serving_bench.py)
        from benchmarks.serving_bench import run_serving_bench

        out["serving"] = run_serving_bench(smoke=SMOKE)
        print(json.dumps({
            "serving_speedup": out["serving"]["closed_loop"]["speedup"],
            "p50_ms": out["serving"]["open_loop"]["latency_p50_ms"],
            "p99_ms": out["serving"]["open_loop"]["latency_p99_ms"],
            "tokens_per_sec": out["serving"]["decode"]["tokens_per_sec"],
            "recompiles": out["serving"]["steady_state_recompiles"],
        }), file=sys.stderr)
    run_dir = os.environ.get("MXTPU_RUN_DIR")
    if run_dir and glob.glob(os.path.join(run_dir, "telemetry_r*.jsonl")):
        # ISSUE 16 rider: fleet skew next to MFU — when the bench ran
        # under a launcher that left per-rank telemetry in MXTPU_RUN_DIR,
        # fold the cross-rank skew decomposition into the same BENCH_*
        # artifact so regressions in straggler behavior are tracked with
        # the same cadence as throughput. Best-effort: a broken run dir
        # must never fail the benchmark itself.
        try:
            from mxnet_tpu.telemetry.fleet import FleetAggregator

            fsum = FleetAggregator(run_dir).refresh().summary()
            out["fleet"] = {
                "ranks": len(fsum.get("per_rank", {})),
                "max_skew_ms": fsum.get("max_skew_ms"),
                "median_skew_ms": fsum.get("median_skew_ms"),
                "straggler": fsum.get("straggler"),
                "bottleneck": fsum.get("bottleneck"),
                # histogram of which rank was slowest per interval
                "straggler_counts": fsum.get("straggler_counts", {}),
            }
            print(json.dumps({"fleet": out["fleet"]}), file=sys.stderr)
        except Exception as e:  # noqa: BLE001 — fleet view is advisory
            out["fleet"] = {"error": str(e)}
    tag = os.environ.get("SCORE_TAG", "smoke" if SMOKE else "v5e_r4")
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "results", "benchmark_score_%s.json" % tag)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"written": path, "rows": len(rows)}))


if __name__ == "__main__":
    main()
