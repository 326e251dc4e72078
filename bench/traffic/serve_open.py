"""Traffic kind ``serve_open``: single-example requests against a
``ServingEngine`` in an open loop, Poisson arrivals from ``--seed`` at a
rate fixed in the mix.

Parameters (the cell's ``traffic`` object):
  rate_rps         offered rate; fixed, never searched for in a run (how
                   the knee is found again: bench/README.md)
  max_batch        ``ServingEngine(max_batch=...)``; its other settings
                   stay at their defaults
  pool             seeded f32 images the requests are drawn from
  check_requests   requests compared with solo ``Predictor`` dispatch and
                   with the float32 ``highest``-precision evaluation
  trace_slice_s    seconds of traffic under the profiler (``--trace 1``)
  result_timeout_s a request not answered by then has failed

A request's latency runs from the instant it was DUE, not from the
actual submit, to its result in the client's hands: a stall of the
sender or the engine is charged to every request it delays. How late
the sender ran against its schedule is reported beside it.

Copied in structure from ``benchmarks/serving_bench.py::_open_loop`` (PR
12), whose clock started at the actual submit after a cumulative sleep.
"""
from __future__ import annotations

import os
import queue
import threading
import time

import numpy as np

import lib


def schedule(seed, rate_rps, seconds, pool):
    """Due offsets (seconds from the window's opening, ascending, all
    below ``seconds``) and the pool index of each request. The same seed
    gives the same schedule."""
    rng = np.random.default_rng(seed)
    n = int(rate_rps * seconds * 1.5) + 16
    due = np.cumsum(rng.exponential(1.0 / rate_rps, n))
    due = due[due < seconds]
    return due, rng.integers(0, pool, len(due))


def drive(submit, due, picks, t0, timeout, annotate):
    """Send request i at ``t0 + due[i]`` and collect every result.
    Returns (latency from due time in s, or None where the request
    failed) and how late each send was. One sender thread and one
    collector thread; results are read in send order, which is the order
    one dispatcher completes them in."""
    sent = queue.Queue()
    late = np.zeros(len(due))
    latency = [None] * len(due)

    def sender():
        for i, (offset, pick) in enumerate(zip(due, picks)):
            target = t0 + offset
            while True:
                wait = target - time.perf_counter()
                if wait <= 0:
                    break
                time.sleep(wait if wait > 2e-3 else 0)
            late[i] = time.perf_counter() - target
            try:
                with annotate("bench.submit"):
                    sent.put((i, target, submit(pick)))
            except Exception as e:  # refused: counts as failed
                sent.put((i, target, e))
        sent.put(None)

    def collector():
        while True:
            item = sent.get()
            if item is None:
                return
            i, target, fut = item
            if isinstance(fut, Exception):
                continue
            try:
                with annotate("bench.result"):
                    fut.result(timeout)
                latency[i] = time.perf_counter() - target
            except Exception:  # failed or timed out: a miss
                pass

    threads = [threading.Thread(target=sender, name="bench-sender"),
               threading.Thread(target=collector, name="bench-collector")]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return latency, late


def setup(cfg, cell, seed):
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import predict
    from mxnet_tpu.serving.engine import ServingEngine

    p = cell["traffic"]
    if p.get("rate_rps") is None:
        raise lib.BenchError("traffic mix %s has no rate_rps: find the knee "
                             "first (bench/README.md)" % p["name"])
    ctx = mx.cpu(0) if cfg.get("rehearsal") else mx.tpu(0)
    shape = tuple(cfg["input_shape"])
    sym = lib.resolve(cfg["factory"])(**cfg["kwargs"])
    # weights random from the seed, initialised as a user's would be
    mx.random.seed(seed)
    np.random.seed(seed)
    mod = mx.mod.Module(sym, context=mx.cpu(0))
    mod.bind(data_shapes=[("data", (1,) + shape)],
             label_shapes=[("softmax_label", (1,))], for_training=False)
    mod.init_params(lib.resolve(p["initializer"])(**p["initializer_kwargs"]))
    arg_params, aux_params = mod.get_params()
    out_dir = os.path.join(lib.ROOT, ".bench_out", cell["name"])
    os.makedirs(out_dir, exist_ok=True)
    bundle = os.path.join(out_dir, "model.pred")
    predict.export_bundle(bundle, sym, arg_params, aux_params)
    try:
        pred = predict.load_bundle(bundle, {"data": (1,) + shape}, ctx=ctx)
        with jax.default_matmul_precision("highest"):
            exact = predict.load_bundle(bundle, {"data": (1,) + shape},
                                        ctx=ctx)
            rng = np.random.default_rng(seed)
            pool = rng.random((p["pool"],) + shape, dtype=np.float32)
            n = p["check_requests"]
            exact_rows = [exact.predict(data=pool[i][None])[0][0]
                          for i in range(n)]
    finally:
        os.remove(bundle)
    engine = ServingEngine(pred, max_batch=p["max_batch"]).start()
    futures = [engine.submit(data=pool[i]) for i in range(n)]
    rows = [f.result(p["result_timeout_s"])[0] for f in futures]
    solo = [pred.predict(data=pool[i][None])[0][0] for i in range(n)]
    expect = cell["expect"]
    d_engine = max(float(np.max(np.abs(a - b))) for a, b in zip(rows, solo))
    d_exact = max(float(np.max(np.abs(a - b)))
                  for a, b in zip(solo, exact_rows))
    apart = min(float(np.max(np.abs(a - b)))
                for i, a in enumerate(solo) for b in solo[i + 1:])
    checks = [
        ("engine_rows_equal_solo", d_engine <= expect["engine_vs_solo_atol"],
         "max abs diff %.3g, tol %s; different requests differ by >= %.3g"
         % (d_engine, expect["engine_vs_solo_atol"], apart)),
        ("solo_equals_f32_highest", d_exact <= expect["solo_vs_f32_atol"],
         "max abs diff %.3g, tol %s" % (d_exact, expect["solo_vs_f32_atol"])),
    ]
    return {"engine": engine, "pool": pool, "cell": cell, "seed": seed,
            "checks": checks}


def run(state, seconds, trace):
    """``trace`` is the harness's ``lib.Session``. With ``trace.tracing``
    a slice of the same traffic runs under the profiler first and is
    not counted; the window opens once the profiler has stopped."""
    from mxnet_tpu import telemetry

    p = state["cell"]["traffic"]
    engine, pool = state["engine"], state["pool"]

    def submit(pick):
        return engine.submit(data=pool[pick])

    slice_batches = None
    if trace.tracing:
        due, picks = schedule(state["seed"] + 1, p["rate_rps"],
                              p["trace_slice_s"], len(pool))
        before = telemetry.snapshot()
        trace.start_slice()
        drive(submit, due, picks, time.perf_counter(),
              p["result_timeout_s"], trace.annotate)
        trace.stop_slice()
        delta = lib.telemetry_delta(before, telemetry.snapshot())
        slice_batches = delta.get("serve.batches", {}).get("value")

    due, picks = schedule(state["seed"], p["rate_rps"], seconds, len(pool))
    open_t = time.perf_counter()
    trace.window_open()
    latency, late = drive(submit, due, picks, open_t,
                          p["result_timeout_s"], trace.annotate)
    close_t = time.perf_counter()
    trace.window_close()
    engine.drain()

    good = np.array([v for v in latency if v is not None])
    failed = len(latency) - len(good)
    metrics = {}
    if len(good):
        metrics = {"serve_p50_ms": float(np.percentile(good, 50)) * 1e3,
                   "serve_p99_ms": float(np.percentile(good, 99)) * 1e3}
    checks = state["checks"] + [
        ("all_answered", failed == 0, "%d of %d failed" % (
            failed, len(latency)))]
    return {
        "open_t": open_t, "window_s": close_t - open_t,
        "attempted": len(latency), "failed": failed,
        "metrics": metrics, "checks": checks,
        "samples_s": len(good) / (close_t - open_t), "batch": p["max_batch"],
        "flops_multiplier": 1,  # forward only
        "slice_batches": slice_batches,
        "offered_rps": p["rate_rps"],
        "completed_rps": len(good) / (close_t - open_t),
        "gen_late_ms_p50": float(np.percentile(late, 50)) * 1e3,
        "gen_late_ms_p99": float(np.percentile(late, 99)) * 1e3,
        "report": ("attempted", "failed", "offered_rps", "completed_rps",
                   "window_s", "gen_late_ms_p50", "gen_late_ms_p99"),
    }
