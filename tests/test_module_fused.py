"""Module.fit -> fused mesh path (kvstore='device').

VERDICT round-1 item 3: ctx=[...multiple devices...] + kvstore 'device'
must route updates through ShardedTrainStep (one XLA program per step:
forward, backward, psum gradient sync, optimizer) and produce the SAME
numerics as the single-device executor path — the reference proves its
multi-device path the same way (tests/nightly/multi_lenet.py parity of
convergence; tests/python/unittest/test_module.py).

Optimizer generality matters: the fused step traces through the real
Optimizer.update, so every registered optimizer must work unmodified.
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


def _train_params(ctx, kvstore, optimizer, optimizer_params, n_batches=3,
                  seed=0):
    net = _mlp()
    it = _blob_iter()
    mod = mx.mod.Module(net, context=ctx)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mx.random.seed(seed)
    np.random.seed(seed)
    mod.init_params(mx.init.Uniform(0.1))
    mod.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                       optimizer_params=optimizer_params)
    it.reset()
    for i, batch in enumerate(it):
        if i >= n_batches:
            break
        mod.forward(batch)
        mod.backward()
        mod.update()
    args, auxs = mod.get_params()
    return mod, {k: v.asnumpy() for k, v in args.items()}


FOUR_DEV = [mx.cpu(i) for i in range(4)]


@pytest.mark.parametrize("optimizer,opt_params", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9}),
    ("adam", {"learning_rate": 0.01}),
    ("rmsprop", {"learning_rate": 0.01}),
    ("nag", {"learning_rate": 0.1, "momentum": 0.9}),
    ("adagrad", {"learning_rate": 0.1}),
])
def test_fused_matches_single_device(optimizer, opt_params):
    mod_f, fused = _train_params(FOUR_DEV, "device", optimizer, opt_params)
    assert mod_f._fused_trainer is not None, "fused path not taken"
    mod_s, single = _train_params(mx.cpu(), "local", optimizer, opt_params)
    assert mod_s._fused_trainer is None
    for k in single:
        np.testing.assert_allclose(
            fused[k], single[k], rtol=2e-4, atol=2e-5, err_msg=k
        )


def test_fused_lr_scheduler():
    """Scheduled lr enters the fused program as a traced input: lr changes
    take effect WITHOUT recompilation. Expected schedule for
    FactorScheduler(step=2, factor=0.1) at base 0.5 over 4 steps:
    [0.5, 0.5, 0.05, 0.05] (post-increment query — the reference's
    per-param Updater staggers the first param by one batch, an
    interleaving artifact the fused step does not reproduce)."""
    sched = mx.lr_scheduler.FactorScheduler(step=2, factor=0.1)
    mod_f, fused = _train_params(
        FOUR_DEV, "device", "sgd",
        {"learning_rate": 0.5, "lr_scheduler": sched}, n_batches=4)
    assert mod_f._fused_trainer is not None

    # single-device reference applying the same explicit lr sequence
    net = _mlp()
    it = _blob_iter()
    mod_s = mx.mod.Module(net, context=mx.cpu())
    mod_s.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mx.random.seed(0)
    np.random.seed(0)
    mod_s.init_params(mx.init.Uniform(0.1))
    mod_s.init_optimizer(kvstore="local", optimizer="sgd",
                         optimizer_params={"learning_rate": 0.5})
    it.reset()
    for i, batch in enumerate(it):
        if i >= 4:
            break
        mod_s._optimizer.lr = [0.5, 0.5, 0.05, 0.05][i]
        mod_s.forward(batch)
        mod_s.backward()
        mod_s.update()
    single = {k: v.asnumpy() for k, v in mod_s.get_params()[0].items()}
    for k in single:
        np.testing.assert_allclose(
            fused[k], single[k], rtol=2e-4, atol=2e-5, err_msg=k
        )


def test_fused_fit_and_score():
    """End-to-end fit on the mesh, then score through the synced
    executor path."""
    net = _mlp()
    it = _blob_iter()
    val = _blob_iter(seed=0)  # same blob centers; score on-distribution
    mod = mx.mod.Module(net, context=FOUR_DEV)
    mod.fit(it, optimizer="sgd",
            optimizer_params={"learning_rate": 0.2, "momentum": 0.9},
            kvstore="device", num_epoch=8)
    assert mod._fused_trainer is not None
    acc = dict(mod.score(val, mx.metric.Accuracy()))["accuracy"]
    assert acc >= 0.95, acc


def test_fused_checkpoint_roundtrip(tmp_path):
    net = _mlp()
    it = _blob_iter()
    mod = mx.mod.Module(net, context=FOUR_DEV)
    mod.fit(it, optimizer="adam", optimizer_params={"learning_rate": 0.01},
            kvstore="device", num_epoch=2)
    prefix = str(tmp_path / "fused")
    mod.save_checkpoint(prefix, 2, save_optimizer_states=True)

    mod2 = mx.mod.Module.load(prefix, 2, load_optimizer_states=True,
                              context=FOUR_DEV)
    it.reset()
    mod2.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod2.init_optimizer(kvstore="device", optimizer="adam",
                        optimizer_params={"learning_rate": 0.01})
    assert mod2._fused_t == mod._fused_t  # resumed Adam step count
    # one more step trains without error and changes params
    batch = next(iter(it))
    before = {k: v.asnumpy().copy() for k, v in mod2.get_params()[0].items()}
    mod2.forward(batch)
    mod2.backward()
    mod2.update()
    after = mod2.get_params()[0]
    changed = any(
        not np.allclose(before[k], after[k].asnumpy()) for k in before
    )
    assert changed


@pytest.mark.parametrize("ndev", [1, 2])
def test_fused_keeps_reduced_precision_weights(ndev):
    """A bf16 symbol (cast-in/cast-out, bf16 weights — resnet's
    dtype="bfloat16") through the fused step: lr and t enter the program
    as traced f32 scalars, so the update computes in f32 and must cast
    back. It used to hand f32 weights to the step's own second call — a
    retrace, then a dtype clash in the first bf16 op (what stopped
    ResNet-50 bf16 on the chip). ndev=1 is the dp=1 mesh (legacy per-key
    update), ndev=2 the flat sharded update."""
    from mxnet_tpu.parallel import make_mesh

    data = mx.sym.Variable("data")
    net = mx.sym.Cast(data, dtype="bfloat16")
    net = mx.sym.FullyConnected(net, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    net = mx.sym.Cast(net, dtype="float32")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.RandomState(0)
    X = rng.randn(16, 8).astype(np.float32)
    y = rng.randint(0, 4, 16).astype(np.float32)
    it = mx.io.ResizeIter(mx.io.NDArrayIter(X, y, batch_size=16), 4)
    mod = mx.mod.Module(
        net, context=[mx.cpu(i) for i in range(ndev)],
        mesh=make_mesh(dp=1) if ndev == 1 else None)
    metric = mx.metric.create("ce")
    mod.fit(it, eval_metric=metric, kvstore="device", optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            initializer=mx.init.Uniform(0.1), num_epoch=1)
    trainer = mod._fused_trainer
    assert trainer is not None
    assert (trainer.flat_mode is None) == (ndev == 1)
    assert {str(v.dtype) for v in mod._fused_params.values()} == {"bfloat16"}
    assert np.isfinite(metric.get()[1])


def test_fused_dropout_keys_advance_per_step():
    """Each fused step draws its own rng key: the same batch through two
    consecutive steps sees two different Dropout masks (an input-side
    Dropout exposed as a second, gradient-blocked output), and a Dropout
    net still trains the blob problem through ``fit``."""
    data = mx.sym.Variable("data")
    drop = mx.sym.Dropout(data, p=0.5, name="drop")
    net = mx.sym.FullyConnected(drop, num_hidden=4, name="fc")
    net = mx.sym.Group([mx.sym.SoftmaxOutput(net, name="softmax"),
                        mx.sym.BlockGrad(drop, name="mask")])
    it = _blob_iter()
    mod = mx.mod.Module(net, context=FOUR_DEV)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.init.Uniform(0.1))
    mod.init_optimizer(kvstore="device", optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    assert mod._fused_trainer is not None, "fused path not taken"
    batch = next(iter(it))
    kept = []
    for _ in range(2):
        mod.forward_backward(batch)
        mod.update()
        kept.append(mod.get_outputs()[1].asnumpy() != 0)
    # p=0.5 over 32 x 8 elements: both masks drop something, and differ
    assert not kept[0].all() and not kept[1].all()
    assert (kept[0] != kept[1]).any(), "two steps drew the same mask"

    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=32, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Dropout(net, p=0.3)
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=FOUR_DEV)
    mod.fit(_blob_iter(), optimizer="sgd",
            optimizer_params={"learning_rate": 0.2, "momentum": 0.9},
            kvstore="device", num_epoch=8,
            initializer=mx.init.Uniform(0.1))
    assert mod._fused_trainer is not None, "fused path not taken"
    acc = dict(mod.score(_blob_iter(), mx.metric.Accuracy()))["accuracy"]
    assert acc >= 0.9, acc


def test_fused_partial_last_batch_matches_executor_path():
    """112 samples in batches of 32: the last batch is padded with 16
    wrapped-around rows. The fused path trains on it exactly as the
    executor path on the same devices does: same parameters, same
    epoch metric."""
    def run(kvstore):
        it = _blob_iter(n=112)
        pads = [b.pad for b in it]
        assert pads == [0, 0, 0, 16], pads
        it.reset()
        mx.random.seed(0)
        np.random.seed(0)
        mod = mx.mod.Module(_mlp(), context=FOUR_DEV)
        metric = mx.metric.Accuracy()
        mod.fit(it, eval_metric=metric, optimizer="sgd",
                optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
                kvstore=kvstore, num_epoch=2,
                initializer=mx.init.Uniform(0.1))
        return mod, metric.get()[1], {
            k: v.asnumpy() for k, v in mod.get_params()[0].items()}

    mod_f, acc_f, fused = run("device")
    assert mod_f._fused_trainer is not None, "fused path not taken"
    mod_e, acc_e, plain = run("local")
    assert mod_e._fused_trainer is None
    assert acc_f == acc_e, (acc_f, acc_e)
    for k in plain:
        np.testing.assert_allclose(
            fused[k], plain[k], rtol=2e-4, atol=2e-5, err_msg=k)
