"""Traffic kind ``fit``: one ``mx.mod.Module(...).fit(...)`` call holds
warm-up, the traced slice (``--trace 1`` only) and the measured window.

Parameters (the cell's ``traffic`` object):
  batch          global batch size
  feed           ``resident``: one seeded batch made on the device in
                 set-up and handed out again every step (the reference's
                 ``--benchmark 1``); ``host``: ``mx.io.NDArrayIter`` over
                 ``host_batches`` seeded f32 host batches, cycled
  host_batches   how many host batches the ``host`` feed cycles over
  warmup_steps   steps before anything is measured (the first compiles)
  trace_steps    steps inside the profiler's slice (``--trace 1``)
  optimizer, optimizer_params, kvstore, eval_metric: handed to ``fit``

The window opens at a batch-end callback and closes at the first
callback past ``seconds``. ``fit`` fetches each step's metric to the host
before the callback runs, so every stamp is closed by a host fetch and
no enqueued work counts as done. ``train_samples_s`` is the batch over
the MEDIAN time between two callbacks of the window; the mean over the
window is printed beside it. The iterator ends the epoch once the
window has closed; the batches the program had already staged still run
and are not counted.
"""
from __future__ import annotations

import math
import time

import numpy as np

import lib


def _make_iter(mx, next_batch, window, trace, data_shape, label_shape):
    """A ``DataIter`` that hands out ``next_batch()`` (a (data, label)
    NDArray pair) until ``window['closed']`` ends its epoch."""

    class ClockIter(mx.io.DataIter):
        def __init__(self):
            super().__init__()
            self.batch_size = data_shape[0]
            self.provide_data = [mx.io.DataDesc("data", data_shape)]
            self.provide_label = [
                mx.io.DataDesc("softmax_label", label_shape)]

        def next(self):
            with trace.annotate("bench.iter_next"):
                if window["closed"]:
                    raise StopIteration
                data, label = next_batch()
                return mx.io.DataBatch(data=[data], label=[label], pad=0)

    return ClockIter()


def setup(cfg, cell, seed):
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.mesh import dp_sharding

    p = cell["traffic"]
    chips = cell["chips"]
    batch = p["batch"]
    sym = lib.resolve(cfg["factory"])(**cfg["kwargs"])
    data_shape = (batch,) + tuple(cfg["input_shape"])
    label_shape = (batch,)
    classes = cfg["num_classes"]
    mesh = make_mesh(dp=chips, devices=jax.devices()[:chips])
    # the rehearsal is the only place a host context is ever named
    ctx = mx.cpu if cfg.get("rehearsal") else mx.tpu
    context = [ctx(i) for i in range(chips)]
    mod = mx.mod.Module(sym, context=context if chips > 1 else context[0],
                        mesh=mesh)
    if p["feed"] == "resident":
        # made on the device in one jitted call, already laid out as the
        # fused step takes its batch, so that handing it out copies nothing
        sharding = dp_sharding(mesh)

        def make(key):
            kd, kl = jax.random.split(key)
            return (jax.random.uniform(kd, data_shape, jnp.float32),
                    jax.random.randint(kl, label_shape, 0, classes)
                    .astype(jnp.float32))

        data, label = jax.jit(make, out_shardings=(sharding, sharding))(
            jax.random.PRNGKey(seed))
        jax.block_until_ready((data, label))
        pair = (mx.nd.NDArray(data), mx.nd.NDArray(label))

        def next_batch():
            return pair
    elif p["feed"] == "host":
        n = p["host_batches"]
        rng = np.random.default_rng(seed)
        X = rng.random((n * batch,) + data_shape[1:], dtype=np.float32)
        y = rng.integers(0, classes, n * batch).astype(np.float32)
        host_iter = mx.io.NDArrayIter(X, y, batch_size=batch)

        def next_batch():
            try:
                b = host_iter.next()
            except StopIteration:
                host_iter.reset()
                b = host_iter.next()
            return b.data[0], b.label[0]
    else:
        raise lib.BenchError("fit: unknown feed %r" % p["feed"])
    return {"mx": mx, "mod": mod, "next_batch": next_batch, "cfg": cfg,
            "cell": cell, "seed": seed, "data_shape": data_shape,
            "label_shape": label_shape, "classes": classes}


def run(state, seconds, trace):
    """``trace`` is the harness's ``lib.Session``. Returns the samples of
    the window. With ``trace.tracing`` the profiler's slice comes first,
    right after warm-up, and the window opens once the profiler has
    stopped, so that no counter of the window holds the profiler's cost."""
    mx = state["mx"]
    p = state["cell"]["traffic"]
    warmup = p["warmup_steps"]
    trace_steps = p["trace_steps"] if trace.tracing else 0
    window = {"closed": False, "open_t": None, "close_t": None, "steps": 0}
    losses, stamps = [], []

    def on_batch(param):
        with trace.annotate("bench.batch_end"):
            losses.append(param.eval_metric.get()[1])
            param.eval_metric.reset()
        n = len(losses)
        if trace.tracing and n == warmup:
            trace.start_slice()
        if trace.tracing and n == warmup + trace_steps:
            trace.stop_slice()
        t = time.perf_counter()
        stamps.append(t)
        if n == warmup + trace_steps:
            window["open_t"] = t
            trace.window_open()
        elif window["open_t"] is not None and not window["closed"]:
            window["steps"] += 1
            if t - window["open_t"] >= seconds:
                window["close_t"] = t
                window["closed"] = True
                trace.window_close()

    it = _make_iter(mx, state["next_batch"], window, trace,
                    state["data_shape"], state["label_shape"])
    mx.random.seed(state["seed"])
    np.random.seed(state["seed"])
    state["mod"].fit(
        it, eval_metric=p["eval_metric"], optimizer=p["optimizer"],
        optimizer_params=dict(p["optimizer_params"]), kvstore=p["kvstore"],
        num_epoch=1, initializer=lib.resolve(p["initializer"])(
            **p["initializer_kwargs"]),
        batch_end_callback=on_batch)
    if not window["closed"]:
        raise lib.BenchError("fit returned before the window closed")

    batch = p["batch"]
    window_s = window["close_t"] - window["open_t"]
    steps = window["steps"]
    in_window = np.diff([s for s in stamps
                         if window["open_t"] <= s <= window["close_t"]])
    # the typical step, not the mean: on a one-chip machine that shares
    # its host's cores a few steps of a window run late, and the mean
    # spreads five times as widely from run to run as the median does
    # (PERF.md section 2). What the slow steps cost is stall_share.
    samples_s = batch / float(np.median(in_window))
    samples_s_mean = steps * batch / window_s
    expect = state["cell"]["expect"]
    first, last = losses[:4], losses[-4:]
    checks = [
        ("loss_finite", bool(np.all(np.isfinite(losses))),
         "%d losses" % len(losses)),
        ("first_loss_near_ln_classes",
         abs(losses[0] - math.log(state["classes"]))
         <= expect["first_loss_tol"],
         "first %.4f, ln(%d) %.4f, tol %s" % (
             losses[0], state["classes"], math.log(state["classes"]),
             expect["first_loss_tol"])),
        ("loss_falls",
         float(np.mean(first) - np.mean(last)) >= expect["loss_fall_min"],
         "mean first four %.4f, last four %.4f, margin %s" % (
             np.mean(first), np.mean(last), expect["loss_fall_min"])),
    ]
    return {
        "open_t": window["open_t"], "window_s": window_s,
        "attempted": steps, "failed": 0,
        "metrics": {"train_samples_s": samples_s},
        "checks": checks,
        "steps": steps, "batch": batch, "samples_s": samples_s,
        "samples_s_mean": samples_s_mean,
        "stall_share": 1.0 - samples_s_mean / samples_s,
        "trace_steps": trace_steps, "flops_multiplier": 3,  # fwd + 2x bwd
        "step_ms_median": float(np.median(in_window)) * 1e3,
        "step_ms_p90": float(np.percentile(in_window, 90)) * 1e3,
        "series": {"losses": [float(v) for v in losses],
                   "step_ms": [round(float(v) * 1e3, 3)
                               for v in np.diff(stamps)]},
        "first_step_t": stamps[0],
        "report": ("steps", "batch", "window_s", "step_ms_median",
                   "step_ms_p90", "samples_s_mean", "stall_share"),
    }
