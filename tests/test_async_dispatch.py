"""Async dispatch pipeline: DeviceFeedIter double-buffering, fit's
one-step lookahead (step N+1 is enqueued before step N's metric fetch
and batch-end callbacks run), the dispatch-plan fast path, and the r5
satellite fixes that ride with them.

The contract under test is PARITY FIRST: everything here is a pure
scheduling change — the fused step receives bitwise-identical inputs,
the metric accumulates in the same order and callback N sees the metric
through step N — so metrics must be EXACTLY equal and parameters
array-equal between the synchronous loop and fit.
"""
import logging
import re

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry


def _mlp():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _blob_iter(batch_size=32, n=128, seed=0):
    rng = np.random.RandomState(seed)
    centers = rng.randn(4, 8) * 3
    x = np.concatenate(
        [c + rng.randn(n // 4, 8) * 0.3 for c in centers]
    ).astype("f")
    y = np.repeat(np.arange(4), n // 4).astype("f")
    perm = rng.permutation(n)
    return mx.io.NDArrayIter(x[perm], y[perm], batch_size=batch_size)


FOUR_DEV = [mx.cpu(i) for i in range(4)]


def _set_knobs(monkeypatch, feed):
    monkeypatch.setenv("MXTPU_DEVICE_FEED", "1" if feed else "0")


FIT_KW = dict(optimizer="sgd", kvstore="device",
              optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
              initializer=mx.init.Uniform(0.1))


def _seeded_module(context=FOUR_DEV):
    mx.random.seed(0)
    np.random.seed(0)
    return mx.mod.Module(_mlp(), context=context)


def _params(mod):
    return {n: v.asnumpy() for n, v in mod.get_params()[0].items()}


def _fit(monkeypatch, feed, num_epoch=2, batch_end_callback=None,
         **fit_kw):
    """Fixed-seed fused fit; returns (final Train metric, params)."""
    _set_knobs(monkeypatch, feed)
    mod = _seeded_module()
    eval_metric = mx.metric.Accuracy()
    mod.fit(_blob_iter(), eval_metric=eval_metric, num_epoch=num_epoch,
            batch_end_callback=batch_end_callback, **dict(FIT_KW, **fit_kw))
    assert mod._fused_trainer is not None, "fused path did not engage"
    return eval_metric.get()[1], _params(mod)


def _hand_loop(callback, num_epoch=2):
    """The synchronous loop, driven by hand: per step forward_backward,
    update, update_metric, callback. Returns (metric, params)."""
    mod = _seeded_module()
    it = _blob_iter()
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(initializer=FIT_KW["initializer"])
    mod.init_optimizer(kvstore="device", optimizer="sgd",
                       optimizer_params=FIT_KW["optimizer_params"])
    assert mod._fused_trainer is not None
    eval_metric = mx.metric.Accuracy()
    for epoch in range(num_epoch):
        eval_metric.reset()
        for nbatch, batch in enumerate(it):
            mod.forward_backward(batch)
            mod.update()
            mod.update_metric(eval_metric, batch.label)
            callback(mx.model.BatchEndParam(
                epoch=epoch, nbatch=nbatch, eval_metric=eval_metric,
                locals=dict(self=mod, data_batch=batch)))
        it.reset()
    return eval_metric, _params(mod)


def _log_enqueues(monkeypatch):
    """The list that every ``Module.update`` (a step's enqueue) appends
    ``"enqueue"`` to, for tests of what runs before and after it."""
    events = []
    real_update = mx.mod.Module.update
    monkeypatch.setattr(
        mx.mod.Module, "update",
        lambda self: (events.append("enqueue"), real_update(self))[1])
    return events


class _Recorder:
    """A batch-end callback that reads the metric like the benchmark's
    (``get()`` every batch, ``reset()`` every ``reset_every``) and keeps
    what a callback can see of its step."""

    def __init__(self, reset_every):
        self.reset_every = reset_every
        self.seen, self.outputs, self.labels = [], [], []

    def __call__(self, param):
        self.seen.append((param.epoch, param.nbatch,
                          param.eval_metric.get()))
        mod = param.locals["self"]
        self.outputs.append(mod.get_outputs()[0].asnumpy())
        self.labels.append(param.locals["data_batch"].label[0].asnumpy())
        assert param.locals.get("nbatch", param.nbatch) == param.nbatch
        if self.reset_every and param.nbatch % self.reset_every == 0:
            param.eval_metric.reset()


# ---------------------------------------------------------------------
# DeviceFeedIter: ordering / staging / reset
# ---------------------------------------------------------------------
def _pair_iters(batch_size=8, n=32, seed=3):
    rng = np.random.RandomState(seed)
    x = rng.rand(n, 5).astype("f")
    y = rng.randint(0, 4, n).astype("f")
    return (mx.io.NDArrayIter(x, y, batch_size=batch_size),
            mx.io.NDArrayIter(x, y, batch_size=batch_size))


def _one_dev_sharding():
    import jax

    return jax.sharding.SingleDeviceSharding(jax.devices()[0])


def test_feed_iter_preserves_order_and_places():
    """The wrapped stream is batch-for-batch identical to the plain
    iterator, and every staged array already carries the target
    sharding (the equality Module's fast path keys on)."""
    inner, ref = _pair_iters()
    shard = _one_dev_sharding()
    feed = mx.io.DeviceFeedIter(inner, shard)
    n = 0
    for rb in ref:
        fb = feed.next()
        np.testing.assert_array_equal(fb.data[0].asnumpy(),
                                      rb.data[0].asnumpy())
        np.testing.assert_array_equal(fb.label[0].asnumpy(),
                                      rb.label[0].asnumpy())
        assert fb.data[0]._data.sharding == shard
        assert fb.label[0]._data.sharding == shard
        assert fb.pad == rb.pad
        n += 1
    assert n == 4
    with pytest.raises(StopIteration):
        feed.next()


def test_feed_iter_stages_to_depth():
    inner, _ = _pair_iters()
    feed = mx.io.DeviceFeedIter(inner, _one_dev_sharding(), depth=3)
    assert len(feed._staged) == 3  # pre-filled at construction
    feed.next()
    assert len(feed._staged) == 3  # refilled behind the handover
    with pytest.raises(Exception):
        mx.io.DeviceFeedIter(_pair_iters()[0], _one_dev_sharding(),
                             depth=0)


def test_feed_iter_reset_restarts_epoch():
    """reset() mid-epoch abandons staged transfers and restarts the
    inner iterator from the first batch."""
    inner, ref = _pair_iters()
    feed = mx.io.DeviceFeedIter(inner, _one_dev_sharding(), depth=2)
    feed.next()
    feed.next()
    feed.reset()
    seen = [b.data[0].asnumpy() for b in feed]
    want = [b.data[0].asnumpy() for b in ref]
    assert len(seen) == len(want) == 4
    for s, w in zip(seen, want):
        np.testing.assert_array_equal(s, w)
    # and a second full epoch after exhaustion
    feed.reset()
    assert len([1 for _ in feed]) == 4


# ---------------------------------------------------------------------
# the one-step lookahead: same numbers as the synchronous loop, bitwise
# ---------------------------------------------------------------------
@pytest.mark.parametrize("feed", [False, True])
@pytest.mark.parametrize("reset_every", [0, 1, 2])
def test_lookahead_fit_equals_hand_driven_synchronous_loop(
        monkeypatch, feed, reset_every):
    """(a) and (c): parameters, the final metric, and per batch the
    metric, the outputs and the batch a callback sees are those of a
    loop that fetches every step before it dispatches the next."""
    _set_knobs(monkeypatch, feed)
    want = _Recorder(reset_every)
    m_want, p_want = _hand_loop(want)
    got = _Recorder(reset_every)
    mod = _seeded_module()
    m_got = mx.metric.Accuracy()
    mod.fit(_blob_iter(), eval_metric=m_got, num_epoch=2,
            batch_end_callback=got, **FIT_KW)
    assert mod._fused_trainer is not None
    assert [s[:2] for s in got.seen] == [(e, n) for e in range(2)
                                         for n in range(4)]
    # nan-aware and exact: a metric read right after reset() is nan
    np.testing.assert_equal(got.seen, want.seen)
    np.testing.assert_equal(m_got.get(), m_want.get())
    assert (m_got.num_inst, m_got.sum_metric) == (
        m_want.num_inst, m_want.sum_metric)
    for step, (o_got, o_want) in enumerate(zip(got.outputs, want.outputs)):
        np.testing.assert_array_equal(o_got, o_want, err_msg=str(step))
    for l_got, l_want in zip(got.labels, want.labels):
        np.testing.assert_array_equal(l_got, l_want)
    p_got = _params(mod)
    assert set(p_got) == set(p_want)
    for name in p_want:
        np.testing.assert_array_equal(p_got[name], p_want[name],
                                      err_msg=name)
    # after fit the module serves the LAST step's outputs
    np.testing.assert_array_equal(mod.get_outputs()[0].asnumpy(),
                                  want.outputs[-1])


def test_async_metric_and_param_parity(monkeypatch):
    """Host-staged batches against the double-buffered device feed, both
    under the lookahead: same metric, same parameters."""
    m_sync, p_sync = _fit(monkeypatch, feed=False)
    m_async, p_async = _fit(monkeypatch, feed=True)
    assert m_sync == m_async
    assert set(p_sync) == set(p_async)
    for name in p_sync:
        np.testing.assert_array_equal(p_sync[name], p_async[name],
                                      err_msg=name)


def test_executor_path_is_synchronous(monkeypatch):
    """kvstore='local' has no snapshot to lag behind (its output arrays
    are reused across steps): each callback runs before the next step
    is dispatched, exactly as before the lookahead existed."""
    _set_knobs(monkeypatch, feed=True)
    events = _log_enqueues(monkeypatch)
    mod = _seeded_module(context=mx.cpu(0))
    mod.fit(_blob_iter(), eval_metric="acc", num_epoch=1,
            batch_end_callback=lambda p: events.append(("cb", p.nbatch)),
            **dict(FIT_KW, kvstore="local"))
    assert mod._fused_trainer is None
    assert mod._metric_snapshot() is None
    assert events == [e for n in range(4) for e in ("enqueue", ("cb", n))]


def test_lookahead_order_of_enqueues_and_callbacks(monkeypatch):
    """(b): callback N runs after enqueue N+1 and before enqueue N+2,
    every nbatch exactly once, the last in the epoch-end drain before
    epoch_end_callback."""
    _set_knobs(monkeypatch, feed=True)
    events = _log_enqueues(monkeypatch)
    mod = _seeded_module()
    mod.fit(_blob_iter(), eval_metric="acc", num_epoch=2,
            batch_end_callback=lambda p: events.append(("cb", p.nbatch)),
            epoch_end_callback=lambda *a: events.append("epoch_end"),
            **FIT_KW)
    epoch = ["enqueue", "enqueue", ("cb", 0), "enqueue", ("cb", 1),
             "enqueue", ("cb", 2), ("cb", 3), "epoch_end"]
    assert events == epoch + epoch


@pytest.mark.parametrize("path,lookahead", [
    ("fused", 6), ("executor", 0), ("monitor", 0)])
def test_lookahead_counter_says_when_it_engages(monkeypatch, path,
                                                lookahead):
    """(d): fit.lookahead_steps counts steps minus one per epoch on the
    fused path and nothing on the executor path or under a monitor;
    the gauge fit.dispatch_depth reads 1 in a fused fit's callbacks and
    0 once fit has drained."""
    _set_knobs(monkeypatch, feed=True)
    telemetry.reset()
    telemetry.enable()
    try:
        depth = telemetry.gauge("fit.dispatch_depth")
        depths = []
        kw = dict(FIT_KW)
        if path == "executor":
            kw["kvstore"] = "local"
        if path == "monitor":
            kw["monitor"] = mx.monitor.Monitor(100)
        mod = _seeded_module()
        mod.fit(_blob_iter(), eval_metric="acc", num_epoch=2,
                batch_end_callback=lambda p: depths.append(depth.value()),
                **kw)
        assert (mod._fused_trainer is not None) == (path == "fused")
        assert telemetry.counter(
            "fit.lookahead_steps").value() == lookahead
        # the last callback of an epoch runs in the drain, nothing queued
        assert depths == ([1, 1, 1, 0] * 2 if path == "fused" else [0] * 8)
        assert depth.value() == 0
    finally:
        telemetry.reset()
        telemetry.disable()


def test_callback_exception_propagates_and_trains_nothing_twice(
        monkeypatch):
    """(e): callback 1 raises with step 2 already enqueued; the error
    reaches the caller, three steps ran, and they are the first three
    steps of the synchronous loop."""
    _set_knobs(monkeypatch, feed=True)

    class Boom(Exception):
        pass

    def explode(param):
        if param.nbatch == 1:
            raise Boom("callback 1")

    enqueued = _log_enqueues(monkeypatch)
    mod = _seeded_module()
    with pytest.raises(Boom):
        mod.fit(_blob_iter(), eval_metric="acc", num_epoch=1,
                batch_end_callback=explode, **FIT_KW)
    assert len(enqueued) == 3 and mod._fused_owner._fused_t == 3
    got = _params(mod)
    want = {}

    def three_steps(param):
        if param.nbatch == 2:
            want.update(_params(param.locals["self"]))
            raise Boom("enough")

    with pytest.raises(Boom):
        _hand_loop(three_steps)
    assert set(want) == set(got)
    for name in want:
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)


def _speedometer_lines(caplog):
    pat = re.compile(r"Epoch\[(\d+)\] Batch \[(\d+)\].*Train-accuracy=(\S+)")
    return [(int(m[1]), int(m[2]), float(m[3])) for m in (
        pat.search(r.getMessage()) for r in caplog.records) if m]


def test_speedometer_auto_reset_logs_every_steps_metric(monkeypatch,
                                                        caplog):
    """A callback that reads AND resets the metric (Speedometer) logs
    the synchronous loop's numbers: its callback lags together with
    the metric, so it never reads a stale or empty one."""
    _set_knobs(monkeypatch, feed=True)
    with caplog.at_level(logging.INFO):
        _hand_loop(mx.callback.Speedometer(32, frequent=1))
        want = _speedometer_lines(caplog)
        caplog.clear()
        _fit(monkeypatch, feed=True,
             batch_end_callback=mx.callback.Speedometer(32, frequent=1))
        got = _speedometer_lines(caplog)
    assert len(want) >= 6 and all(np.isfinite(v) for _, _, v in want)
    assert got == want


# ---------------------------------------------------------------------
# dispatch fast paths: plan cache + feed adoption counters
# ---------------------------------------------------------------------
def test_dispatch_fastpath_counters(monkeypatch):
    telemetry.reset()
    telemetry.enable()
    try:
        _fit(monkeypatch, feed=True)
        hits = telemetry.counter("executor.dispatch_plan_hits").value()
        misses = telemetry.counter("executor.dispatch_plan_misses").value()
        # 8 steps (4 batches x 2 epochs): first dispatch builds the
        # plan, steady state must hit the cache
        assert misses >= 1
        assert hits >= 6, (hits, misses)
        # the fused module adopted pre-placed feed buffers...
        assert telemetry.counter("module.feed_fastpath_hits").value() >= 8
        # ...and the feed recorded its (cheap) handover waits
        assert telemetry.histogram("io.feed_wait_seconds").count() >= 8
    finally:
        telemetry.reset()
        telemetry.disable()
