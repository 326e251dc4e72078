"""MXNET_FIT_MULTISTEP=K: fit() groups K batches into ONE XLA dispatch
(lax.scan over the fused step — Module.update_multi /
ShardedTrainStep.compile_multi).

Scanning K steps per dispatch amortizes the per-step host dispatch
(its share on the chip is not measured; PERF.md) the way the
reference's threaded engine hides dispatch
(threaded_engine_perdevice.cc:26-136). These tests pin the contract
that matters: identical numerics to K separate update() calls,
identical lr-schedule advancement, and per-batch metric/callback
semantics (Speedometer still sees every batch).
"""
import numpy as np
import pytest

import mxnet_tpu as mx


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


def _fit_params(k, num_epoch=2, monkeypatch=None, callbacks=None,
                sched=None):
    if monkeypatch is not None:
        if k > 1:
            monkeypatch.setenv("MXNET_FIT_MULTISTEP", str(k))
        else:
            monkeypatch.delenv("MXNET_FIT_MULTISTEP", raising=False)
    net = _mlp()
    it = _blob_iter()
    mod = mx.mod.Module(net, context=FOUR_DEV)
    mx.random.seed(0)
    np.random.seed(0)
    opt_params = {"learning_rate": 0.1, "momentum": 0.9}
    if sched is not None:
        opt_params["lr_scheduler"] = sched
    mod.fit(it, optimizer="sgd", optimizer_params=opt_params,
            kvstore="device", num_epoch=num_epoch,
            initializer=mx.init.Uniform(0.1),
            batch_end_callback=callbacks)
    assert mod._fused_trainer is not None
    return mod, {n: v.asnumpy() for n, v in mod.get_params()[0].items()}


@pytest.mark.parametrize("k", [2, 4])
def test_multistep_matches_single(monkeypatch, k):
    """K-grouped fit == plain fit, parameter-exact (same step math; 128
    samples / batch 32 = 4 batches per epoch, so k=4 is one dispatch
    per epoch and k=2 is two)."""
    _, base = _fit_params(1, monkeypatch=monkeypatch)
    _, multi = _fit_params(k, monkeypatch=monkeypatch)
    assert set(base) == set(multi)
    for n in base:
        np.testing.assert_allclose(multi[n], base[n], rtol=2e-4,
                                   atol=2e-5, err_msg=n)


def test_multistep_partial_group(monkeypatch):
    """4 batches/epoch with K=3: one scan dispatch + a single-step tail;
    numerics must still match plain fit exactly."""
    _, base = _fit_params(1, monkeypatch=monkeypatch)
    _, multi = _fit_params(3, monkeypatch=monkeypatch)
    for n in base:
        np.testing.assert_allclose(multi[n], base[n], rtol=2e-4,
                                   atol=2e-5, err_msg=n)


def test_multistep_callbacks_per_batch(monkeypatch):
    """Speedometer semantics: batch_end_callback fires once per BATCH
    (not per dispatch), with the true nbatch sequence, and the metric
    it observes reflects every batch seen so far."""
    seen = []

    def cb(param):
        seen.append((param.epoch, param.nbatch,
                     dict(param.eval_metric.get_name_value())))

    _fit_params(2, num_epoch=2, monkeypatch=monkeypatch, callbacks=cb)
    assert [(e, n) for e, n, _ in seen] == [
        (0, 0), (0, 1), (0, 2), (0, 3),
        (1, 0), (1, 1), (1, 2), (1, 3)]
    # accuracy is a real number on every callback (metric updated
    # per-batch from the per-step scan outputs)
    assert all(0.0 <= m["accuracy"] <= 1.0 for _, _, m in seen)


def test_multistep_lr_schedule_advances_per_step(monkeypatch):
    """The lr schedule advances per MICRO-step inside the scan: with
    FactorScheduler(step=2) and K=4, steps see lrs [0.5,0.5,0.05,0.05]
    — matching plain fit's post-increment query sequence."""
    sched1 = mx.lr_scheduler.FactorScheduler(step=2, factor=0.1)
    _, base = _fit_params(1, num_epoch=1, monkeypatch=monkeypatch,
                          sched=sched1)
    sched2 = mx.lr_scheduler.FactorScheduler(step=2, factor=0.1)
    _, multi = _fit_params(4, num_epoch=1, monkeypatch=monkeypatch,
                           sched=sched2)
    for n in base:
        np.testing.assert_allclose(multi[n], base[n], rtol=2e-4,
                                   atol=2e-5, err_msg=n)


def test_multistep_rng_net_trains(monkeypatch):
    """Dropout net under K=2: per-micro-step rng keys are stacked into
    the scan; numerics differ from single-step (different key stream)
    but training must run and converge on the blob problem."""
    monkeypatch.setenv("MXNET_FIT_MULTISTEP", "2")
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=32, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Dropout(net, p=0.3)
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    it = _blob_iter()
    mod = mx.mod.Module(net, context=FOUR_DEV)
    mod.fit(it, optimizer="sgd",
            optimizer_params={"learning_rate": 0.2, "momentum": 0.9},
            kvstore="device", num_epoch=8,
            initializer=mx.init.Uniform(0.1))
    val = _blob_iter(seed=0)
    acc = dict(mod.score(val, mx.metric.Accuracy()))["accuracy"]
    assert acc >= 0.9, acc
