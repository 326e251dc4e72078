"""Mixed-precision training (ISSUE 8 tentpole acceptance).

MXTPU_AMP=bf16 on the flat fused-update path: bf16 forward/backward and
collectives, fp32 master-weight slabs, dynamic loss scaling, and the
slab rule that updates them. These tests pin the contracts:

- the working params are exactly bf16(masters) at every step boundary;
- a non-finite gradient skips the step bitwise-cleanly (params, masters,
  optimizer state, step count all unchanged), halves the scale, and
  training continues;
- the scale doubles after MXTPU_LOSS_SCALE_WINDOW consecutive finite
  steps;
- the flat update on the plan's whole-tile shards matches the slab rule
  on the plain arrays across device counts and optimizers, and its trace
  pads and slices nothing round the update;
- kvstore gradient buckets group by dtype, the byte cap counts actual
  itemsize, and MXTPU_BUCKET_REDUCE_DTYPE upcasts only the sum;
- checkpoints are dtype-portable (AMP <-> fp32 both directions,
  including SIGKILL crash-resume through resilience checkpoints), and
  an AMP->AMP resume is bitwise-identical to an uninterrupted run.
"""
import os
import shutil
import signal
import subprocess
import sys
import textwrap

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import mxnet_tpu as mx
from mxnet_tpu.resilience import checkpoint as ck
from mxnet_tpu.resilience import fault

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mlp_net(num_hidden=16, num_classes=4):
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=num_hidden, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=num_classes, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _lenet_net():
    """lenet-shaped convnet scaled for an 8x8 synthetic task."""
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, kernel=(3, 3), num_filter=8,
                             name="conv1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, pool_type="max", kernel=(2, 2),
                         stride=(2, 2))
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=2, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _fit_mlp(ndev, optname="sgd", num_epoch=2):
    np.random.seed(0)
    mx.random.seed(0)
    rng = np.random.RandomState(42)
    X = rng.randn(128, 8).astype(np.float32)
    y = rng.randint(0, 4, 128).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=16)
    mod = mx.mod.Module(_mlp_net(),
                        context=[mx.cpu(i) for i in range(ndev)])
    metric = mx.metric.create("acc")
    opt_params = {"learning_rate": 0.1, "rescale_grad": 1.0 / 16}
    if optname == "sgd":
        opt_params["momentum"] = 0.9
    mod.fit(it, eval_metric=metric, kvstore="device", optimizer=optname,
            optimizer_params=opt_params,
            initializer=mx.init.Uniform(0.1), num_epoch=num_epoch)
    assert mod._fused_trainer is not None, "fused path did not engage"
    return mod, metric


def _fit_lenet(ndev, num_epoch=4):
    """Separable conv task: class = (left-half mean > right-half mean)."""
    np.random.seed(0)
    mx.random.seed(0)
    rng = np.random.RandomState(7)
    X = rng.randn(128, 1, 8, 8).astype(np.float32)
    y = (X[:, 0, :, :4].mean(axis=(1, 2))
         > X[:, 0, :, 4:].mean(axis=(1, 2))).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=32)
    mod = mx.mod.Module(_lenet_net(),
                        context=[mx.cpu(i) for i in range(ndev)])
    metric = mx.metric.create("acc")
    mod.fit(it, eval_metric=metric, kvstore="device", optimizer="sgd",
            optimizer_params={"learning_rate": 0.3, "momentum": 0.9,
                              "rescale_grad": 1.0 / 32},
            initializer=mx.init.Uniform(0.1), num_epoch=num_epoch)
    assert mod._fused_trainer is not None
    return mod, metric


def _masters(mod):
    owner = mod._fused_owner
    return owner._fused_trainer.master_params_named(owner._fused_opt)


# ---------------------------------------------------------------------------
# AMP lifecycle invariants through Module.fit
# ---------------------------------------------------------------------------

def test_amp_engages_and_master_invariant(monkeypatch):
    """MXTPU_AMP=bf16: bf16 working params, fp32 masters, and
    params == bf16(masters) exactly at the post-fit boundary; the host
    view (get_params) is the fp32 truth."""
    import jax.numpy as jnp

    monkeypatch.setenv("MXTPU_AMP", "bf16")
    mod, metric = _fit_mlp(4, "sgd")
    tr = mod._fused_owner._fused_trainer
    assert tr.amp and tr.flat_mode is not None
    assert np.isfinite(metric.get()[1])
    masters = _masters(mod)
    for name, p in mod._fused_owner._fused_params.items():
        assert p.dtype == jnp.bfloat16, (name, p.dtype)
        m = masters[name]
        assert np.asarray(m).dtype == np.float32, name
        np.testing.assert_array_equal(
            np.asarray(p), np.asarray(jnp.asarray(m, jnp.bfloat16)),
            err_msg="%s != bf16(master)" % name)
    arg, _ = mod.get_params()
    for name, v in arg.items():
        assert v.asnumpy().dtype == np.float32, name
        np.testing.assert_array_equal(v.asnumpy(),
                                      np.asarray(masters[name]))
    # scaler state lives in opt_state as replicated scalars
    scale = float(np.asarray(
        mod._fused_owner._fused_opt[tr.AMP_SCALE_KEY]))
    assert scale >= 1.0


@pytest.mark.parametrize("mesh", [False, True])
def test_amp_that_cannot_engage_raises(monkeypatch, mesh):
    """dp=1 has no flat path — through the per-key executor path (one
    context) or the fused step on a dp=1 mesh. AMP must refuse: it used
    to log a warning and train fp32, a silent wrong answer. An unknown
    value is refused the same way."""
    from mxnet_tpu.parallel import make_mesh

    rng = np.random.RandomState(42)
    X = rng.randn(64, 8).astype(np.float32)
    y = rng.randint(0, 4, 64).astype(np.float32)

    def fit():
        it = mx.io.NDArrayIter(X, y, batch_size=16)
        mod = mx.mod.Module(_mlp_net(), context=[mx.cpu(0)],
                            mesh=make_mesh(dp=1) if mesh else None)
        mod.fit(it, kvstore="device", optimizer="sgd",
                initializer=mx.init.Uniform(0.1), num_epoch=1)
        return mod

    monkeypatch.setenv("MXTPU_AMP", "bf16")
    with pytest.raises(mx.MXNetError, match="MXTPU_AMP=bf16 cannot engage"):
        fit()
    if mesh:
        monkeypatch.setenv("MXTPU_AMP", "fp8")
        with pytest.raises(mx.MXNetError, match="not understood"):
            fit()
    monkeypatch.delenv("MXTPU_AMP")
    assert (fit()._fused_trainer is not None) == mesh


def test_amp_lenet_convergence_gate(monkeypatch):
    """The acceptance convergence gate: bf16-AMP lenet must land within
    tolerance of the fp32 run on the same separable task."""
    monkeypatch.delenv("MXTPU_AMP", raising=False)
    _, met_f32 = _fit_lenet(4)
    acc_f32 = met_f32.get()[1]

    monkeypatch.setenv("MXTPU_AMP", "bf16")
    mod, met_amp = _fit_lenet(4)
    assert mod._fused_owner._fused_trainer.amp
    acc_amp = met_amp.get()[1]
    assert acc_f32 > 0.7, acc_f32  # the task is learnable at all
    assert acc_amp >= acc_f32 - 0.05, (acc_amp, acc_f32)


# ---------------------------------------------------------------------------
# loss scaler: overflow skip + growth (direct trainer stepping)
# ---------------------------------------------------------------------------

def _direct_trainer(ndev, batch=16, in_dim=8):
    import jax

    from jax.sharding import Mesh
    from mxnet_tpu import optimizer as opt
    from mxnet_tpu.parallel import ShardedTrainStep

    net = _mlp_net()
    mesh = Mesh(np.asarray(jax.devices()[:ndev]), ("dp",))
    o = opt.create("sgd", learning_rate=0.1, momentum=0.9,
                   rescale_grad=1.0 / batch)
    trainer = ShardedTrainStep(net, mesh, optimizer=o).compile()
    shapes = {"data": (batch, in_dim), "softmax_label": (batch,)}
    arg_shapes, _, _ = net.infer_shape(**shapes)
    shapes_by_name = dict(zip(net.list_arguments(), arg_shapes))
    params, aux, state = trainer.init(shapes_by_name,
                                      mx.initializer.Uniform(0.1))
    return trainer, params, aux, state


def _place_batch(trainer, X, y):
    import jax

    return {"data": jax.device_put(X, trainer.batch_sharding()),
            "softmax_label": jax.device_put(y, trainer.batch_sharding())}


def _host_tree(d):
    return {k: np.asarray(v) for k, v in d.items()}


def test_amp_overflow_skips_bitwise_and_recovers(monkeypatch):
    """A batch that produces non-finite gradients must leave params,
    masters, and optimizer state bitwise untouched, halve the scale,
    reset the good-step count — and the next finite batch must train
    normally at the reduced scale."""
    monkeypatch.setenv("MXTPU_AMP", "bf16")
    monkeypatch.setenv("MXTPU_SHARD_UPDATE", "1")
    trainer, params, aux, state = _direct_trainer(2)
    assert trainer.amp
    rng = np.random.RandomState(3)
    X = rng.randn(16, 8).astype(np.float32)
    y = rng.randint(0, 4, 16).astype(np.float32)

    params, aux, state, _ = trainer(
        params, aux, state, _place_batch(trainer, X, y), t=1)
    snap_p = _host_tree(params)
    snap_s = _host_tree({k: v for k, v in state.items()})
    scale0 = float(np.asarray(state[trainer.AMP_SCALE_KEY]))
    good0 = float(np.asarray(state[trainer.AMP_GOOD_KEY]))
    assert good0 == 1.0

    # bf16 shares fp32's exponent range, so ordinary activations cannot
    # overflow it — poison the data to force non-finite grads
    X_bad = X.copy()
    X_bad[0, 0] = np.inf
    params, aux, state, _ = trainer(
        params, aux, state, _place_batch(trainer, X_bad, y), t=2)
    for k, v in _host_tree(params).items():
        np.testing.assert_array_equal(v, snap_p[k],
                                      err_msg="param %s changed" % k)
    for k, v in _host_tree(state).items():
        if k in (trainer.AMP_SCALE_KEY, trainer.AMP_GOOD_KEY):
            continue
        np.testing.assert_array_equal(v, snap_s[k],
                                      err_msg="state %s changed" % k)
    assert float(np.asarray(state[trainer.AMP_SCALE_KEY])) == scale0 / 2
    assert float(np.asarray(state[trainer.AMP_GOOD_KEY])) == 0.0

    # clean continuation: finite step applies an update again
    params, aux, state, _ = trainer(
        params, aux, state, _place_batch(trainer, X, y), t=3)
    changed = any(
        not np.array_equal(np.asarray(v), snap_p[k])
        for k, v in params.items())
    assert changed, "finite step after overflow did not update"
    assert float(np.asarray(state[trainer.AMP_GOOD_KEY])) == 1.0
    assert float(np.asarray(state[trainer.AMP_SCALE_KEY])) == scale0 / 2
    for v in _host_tree(params).values():
        assert np.isfinite(v.astype(np.float32)).all()


def test_amp_scale_growth(monkeypatch):
    """MXTPU_LOSS_SCALE_WINDOW consecutive finite steps double the
    scale and reset the counter."""
    monkeypatch.setenv("MXTPU_AMP", "bf16")
    monkeypatch.setenv("MXTPU_LOSS_SCALE", "8")
    monkeypatch.setenv("MXTPU_LOSS_SCALE_WINDOW", "3")
    trainer, params, aux, state = _direct_trainer(2)
    assert trainer.amp
    assert float(np.asarray(state[trainer.AMP_SCALE_KEY])) == 8.0
    rng = np.random.RandomState(5)
    X = rng.randn(16, 8).astype(np.float32)
    y = rng.randint(0, 4, 16).astype(np.float32)
    batch = _place_batch(trainer, X, y)
    for t in (1, 2):
        params, aux, state, _ = trainer(params, aux, state, batch, t=t)
        assert float(np.asarray(state[trainer.AMP_SCALE_KEY])) == 8.0
        assert float(np.asarray(state[trainer.AMP_GOOD_KEY])) == t
    params, aux, state, _ = trainer(params, aux, state, batch, t=3)
    assert float(np.asarray(state[trainer.AMP_SCALE_KEY])) == 16.0
    assert float(np.asarray(state[trainer.AMP_GOOD_KEY])) == 0.0


# ---------------------------------------------------------------------------
# the flat update as the step applies it, on the plan's whole-tile shards,
# against the slab rule on the plain arrays
# ---------------------------------------------------------------------------

_SLAB_OPTS = {
    "sgd": ("sgd", {}),
    "sgd_mom": ("sgd", {"momentum": 0.9}),
    "adam": ("adam", {"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8}),
}


def _one_key_trainer(kind, size, ndev):
    """A fused trainer over one key of ``size`` parameters at dp=ndev
    (MXTPU_AMP set by the caller), its float32 weight, a bf16 gradient
    and state slabs that are not zeros."""
    import jax
    import jax.numpy as jnp

    from jax.sharding import Mesh
    from mxnet_tpu import optimizer as opt
    from mxnet_tpu.ops.optimizer_ops import SLAB_STATE_SLOTS
    from mxnet_tpu.parallel import ShardedTrainStep

    data = mx.sym.Variable("data")
    net = mx.sym.LinearRegressionOutput(
        mx.sym.FullyConnected(data, num_hidden=1, no_bias=True, name="fc"),
        name="softmax")
    mesh = Mesh(np.asarray(jax.devices()[:ndev]), ("dp",))
    name, extra = _SLAB_OPTS[kind]
    o = opt.create(name, learning_rate=0.05, wd=0.0001,
                   rescale_grad=1.0 / 32, **extra)
    trainer = ShardedTrainStep(net, mesh, optimizer=o)
    assert trainer.amp and trainer.flat_mode == "shard"
    rng = np.random.RandomState(size + len(kind) + ndev)
    w = rng.randn(1, size).astype(np.float32)
    g = jnp.asarray((rng.randn(1, size) * 4).astype(np.float32),
                    jnp.bfloat16)
    states = tuple(np.abs(rng.randn(1, size)).astype(np.float32) * 0.1
                   for _ in range(SLAB_STATE_SLOTS[kind]))
    placed = {"fc_weight": jax.device_put(
        w, trainer._sharding_for("fc_weight"))}
    trainer._ensure_flat_plan(placed)
    named = {"fc_weight": (None if not states else
                           states[0] if len(states) == 1 else states)}
    opt_state = {k: v for k, v in
                 trainer.named_state_to_flat(named).items()
                 if v is not None}
    opt_state.update(trainer.build_amp_master_state(
        {"fc_weight": w}, scale=128.0))
    params = trainer.amp_cast_params(placed)
    return trainer, params, opt_state, w, g, states


def _slabs(trainer, opt_state):
    """Every master and state slab of ``opt_state``, on the host."""
    import jax

    return {k: jax.tree_util.tree_map(np.asarray, v)
            for k, v in opt_state.items()
            if k not in (trainer.AMP_SCALE_KEY, trainer.AMP_GOOD_KEY)}


# (kind, dp, size): the parent's nine sizes (a shard under a tile, odd,
# several tiles with a pad), then a shard of exactly one (16, 128) tile,
# of many, and of the plan's own padded sizes, at dp 2 / 4 / 8
_SLAB_CASES = [(kind, 2, size) for size in (131, 1024, 5000)
               for kind in ("sgd", "sgd_mom", "adam")] + [
    ("sgd_mom", 2, 2 * 2048), ("adam", 4, 4 * 2048), ("sgd", 8, 8 * 2048),
    ("sgd_mom", 2, 24 * 2048), ("sgd_mom", 4, 3 * 4 * 2048 - 5),
    ("adam", 8, 2 * 8 * 2048 + 1), ("sgd", 4, 40000),
]


@pytest.mark.parametrize("kind,ndev,size", _SLAB_CASES)
def test_slab_kernel_matches_reference(monkeypatch, kind, ndev, size):
    """The flat AMP update of ``_apply_optimizer_flat_amp`` on the plan's
    whole-tile shards against ``slab_update`` on the plain arrays: new
    master, new state and bf16 copy; the pad still zero after a step; a
    non-finite step leaves every slab bit for bit what it was. (The name
    is from when a Pallas kernel ran here, PR 60.)"""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.optimizer_ops import slab_update
    from mxnet_tpu.parallel.train_step import _AMP_SHARD_ALIGN

    monkeypatch.setenv("MXTPU_AMP", "bf16")
    monkeypatch.setenv("MXTPU_SHARD_UPDATE", "1")
    trainer, params, opt_state, w, g, states = _one_key_trainer(
        kind, size, ndev)
    (bucket,) = trainer._flat_plan.buckets
    assert bucket.size == size
    assert bucket.padded % (ndev * _AMP_SHARD_ALIGN) == 0
    assert bucket.padded - size < ndev * _AMP_SHARD_ALIGN
    before = _slabs(trainer, opt_state)
    lr, t = jnp.float32(0.05), jnp.float32(3.0)
    apply = jax.jit(lambda p, gr, st: trainer._apply_optimizer_flat_amp(
        p, gr, st, lr, t))
    o = trainer.optimizer

    def rule(w, g, states, finite):
        lr_eff = lr
        if kind == "adam":
            lr_eff = lr * ((1.0 - o.beta2 ** t) ** 0.5
                           / (1.0 - o.beta1 ** t))
        return slab_update(
            kind, w, g, states, lr_eff, jnp.float32(1.0) / 128.0, finite,
            wd=o.wd, rescale_grad=o.rescale_grad, clip_gradient=-1.0,
            momentum=getattr(o, "momentum", 0.0))

    for poison in (False, True):
        grad = g.at[0, size // 2].set(jnp.inf) if poison else g
        new_params, new_state = apply(params, {"fc_weight": grad},
                                      opt_state)
        want_w, want_st, want_w16 = jax.jit(rule)(
            w.reshape(-1), grad.reshape(-1),
            tuple(s.reshape(-1) for s in states),
            0.0 if poison else 1.0)
        got = _slabs(trainer, new_state)
        if poison:
            for key, old in before.items():
                for a, b in zip(jax.tree_util.tree_leaves(got[key]),
                                jax.tree_util.tree_leaves(old)):
                    np.testing.assert_array_equal(a, b, err_msg=key)
            np.testing.assert_array_equal(
                np.asarray(new_params["fc_weight"].astype(jnp.float32)),
                np.asarray(params["fc_weight"].astype(jnp.float32)))
            assert float(new_state[trainer.AMP_SCALE_KEY]) == 64.0
            continue
        master = got[trainer._master_key(0)]
        np.testing.assert_array_equal(master[:size], np.asarray(want_w))
        got_st = got.get(trainer._flat_key(0), ())
        got_st = got_st if isinstance(got_st, tuple) else (got_st,)
        assert len(got_st) == len(want_st)
        for a, b in zip(got_st, want_st):
            np.testing.assert_array_equal(a[:size], np.asarray(b))
        np.testing.assert_array_equal(
            np.asarray(new_params["fc_weight"].astype(jnp.float32))
            .reshape(-1), np.asarray(want_w16.astype(jnp.float32)))
        for slab in (master,) + tuple(got_st):   # the pad is still zeros
            assert slab.shape == (bucket.padded,)
            np.testing.assert_array_equal(slab[size:], 0)


@pytest.mark.parametrize("ndev,net", [(2, "lenet"), (4, "lenet"),
                                      (4, "mlp_adam"), (8, "mlp_sgd")])
def test_amp_replicated_is_bit_equal_to_shard(monkeypatch, ndev, net):
    """MXTPU_SHARD_UPDATE=0 scans the same body over the same whole-tile
    shard width on every replica: masters bit-equal to the sharded mode,
    a 3x3 filter's slab order included."""
    monkeypatch.setenv("MXTPU_AMP", "bf16")
    got = {}
    for mode, want in (("1", "shard"), ("0", "replicated")):
        monkeypatch.setenv("MXTPU_SHARD_UPDATE", mode)
        if net == "lenet":
            mod, _ = _fit_lenet(ndev, num_epoch=2)
        else:
            mod, _ = _fit_mlp(ndev, net.split("_")[1], num_epoch=1)
        assert mod._fused_owner._fused_trainer.flat_mode == want
        got[mode] = {k: np.asarray(v) for k, v in _masters(mod).items()}
    assert sorted(got["1"]) == sorted(got["0"])
    for k, v in got["1"].items():
        np.testing.assert_array_equal(v, got["0"][k], err_msg=k)


def _equations(jaxpr):
    """Every equation of a jaxpr, nested ones included."""
    import jax

    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


@pytest.mark.parametrize("kind", ["sgd", "sgd_mom", "adam"])
def test_traced_flat_update_pads_and_slices_nothing(monkeypatch, kind):
    """The mechanism's gauge, on four simulated devices: the traced
    ``_apply_optimizer_flat_amp`` holds no ``pad``, no slice as large as a
    shard (the views out are a key each) and one update a bucket, and it
    counts itself once a trace and never a step."""
    import jax
    import jax.numpy as jnp

    from jax.sharding import Mesh
    from mxnet_tpu import optimizer as opt
    from mxnet_tpu import telemetry
    from mxnet_tpu.parallel import ShardedTrainStep
    from mxnet_tpu.parallel.train_step import _AMP_SHARD_ALIGN

    monkeypatch.setenv("MXTPU_AMP", "bf16")
    monkeypatch.setenv("MXTPU_SHARD_UPDATE", "1")
    monkeypatch.setenv("MXTPU_BUCKET_BYTES", "128")   # several buckets
    telemetry.enable()
    try:
        ndev, batch = 4, 16
        name, extra = _SLAB_OPTS[kind]
        net = _mlp_net()
        mesh = Mesh(np.asarray(jax.devices()[:ndev]), ("dp",))
        trainer = ShardedTrainStep(net, mesh, optimizer=opt.create(
            name, learning_rate=0.1, rescale_grad=1.0 / batch,
            **extra)).compile()
        arg_shapes, _, _ = net.infer_shape(data=(batch, 8),
                                           softmax_label=(batch,))
        params, aux, state = trainer.init(
            dict(zip(net.list_arguments(), arg_shapes)),
            mx.initializer.Uniform(0.1))
        plan = trainer._flat_plan
        assert len(plan.buckets) > 1
        shard = min(b.padded for b in plan.buckets) // ndev
        assert shard % _AMP_SHARD_ALIGN == 0
        labels = dict(form="slab", calls=len(plan.buckets),
                      tile_rows=_AMP_SHARD_ALIGN // 128)
        counter = telemetry.REGISTRY.get("train_step.flat_update_lowerings")
        traces = counter.value(**labels)

        grads = {k: jnp.ones_like(v) for k, v in params.items()}
        jaxpr = jax.make_jaxpr(
            lambda p, g, s, lr, t: trainer._apply_optimizer_flat_amp(
                p, g, s, lr, t))(params, grads, state, jnp.float32(0.1),
                                 jnp.float32(1.0))
        assert counter.value(**labels) == traces + 1
        eqns = list(_equations(jaxpr.jaxpr))
        names = [e.primitive.name for e in eqns]
        assert "pad" not in names
        assert "pallas_call" not in names and "platform_index" not in names
        assert names.count("shard_map") == len(plan.buckets)
        for e in eqns:
            if e.primitive.name in ("slice", "dynamic_slice", "gather"):
                assert all(v.aval.size < shard for v in e.outvars), e

        rng = np.random.RandomState(3)
        X = rng.randn(batch, 8).astype(np.float32)
        y = rng.randint(0, 4, batch).astype(np.float32)
        traces = counter.value(**labels)
        for t in (1, 2, 3):
            params, aux, state, _ = trainer(
                params, aux, state, _place_batch(trainer, X, y), t=t)
        assert counter.value(**labels) == traces + 1   # the step's one trace
    finally:
        telemetry.disable()


# ---------------------------------------------------------------------------
# the order a key lies in its slab, and per-key checkpoints through it
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape,axes", [
    ((7,), None), ((1000, 2048), None), ((2048, 512, 1, 1), None),
    ((512, 512, 3, 3), (2, 3, 0, 1)), ((64, 3, 7, 7), (2, 3, 0, 1)),
    ((8, 1, 3, 3), (1, 2, 3, 0)), ((16, 8), (1, 0)),
    ((4, 3, 5, 200), None), ((6, 4, 3), (1, 2, 0)), ((512, 64, 3), (2, 0, 1)),
])
def test_slab_order_round_trips(shape, axes):
    """Trailing axes narrower together than a row of lanes lie first in
    the slab; whatever the order, a key comes back as it went in."""
    from mxnet_tpu.parallel.train_step import (
        _from_slab, _slab_axes, _to_slab)

    assert _slab_axes(shape) == axes
    small = tuple(min(d, 6) for d in shape)
    if _slab_axes(small) != axes:   # the rule reads sizes: keep them
        small = shape
    x = np.arange(int(np.prod(small)), dtype=np.float32).reshape(small)
    run = _to_slab(x)
    assert run.shape == (x.size,)
    if axes is None:
        np.testing.assert_array_equal(run, x.reshape(-1))
    else:
        np.testing.assert_array_equal(run, x.transpose(axes).reshape(-1))
    np.testing.assert_array_equal(_from_slab(run, small), x)


def test_amp_per_key_checkpoint_from_another_layout(monkeypatch):
    """A checkpoint is per key and row-major whatever wrote it (the
    parent of PR 60 laid a filter into its slab as [O, I, kh, kw], this
    tree as [kh, kw, O, I]): a blob built by hand from plain per-key
    arrays restores at dp=4 into masters, momentum and bf16 copies that
    read back bit for bit, and captures as the blob it came from."""
    import jax.numpy as jnp

    monkeypatch.setenv("MXTPU_AMP", "bf16")
    mod, _ = _fit_lenet(4, num_epoch=1)
    tr = mod._fused_owner._fused_trainer
    assert tr.amp and tr.flat_mode == "shard"
    plan = tr._flat_plan
    assert any(len(shape) == 4 for b in plan.buckets
               for (_i, _n, _o, _s, shape) in b.views)
    rng = np.random.RandomState(11)
    arg, aux = mod.get_params()
    blob = {
        "arg": {k: rng.randn(*v.shape).astype(np.float32)
                for k, v in arg.items()},
        "aux": {k: v.asnumpy() for k, v in aux.items()},
        "opt": {"kind": "fused", "t": 7,
                "state": {k: rng.randn(*v.shape).astype(np.float32)
                          for k, v in arg.items()},
                "amp": {"scale": 64.0, "good": 3.0}},
    }
    mod._restore_train_state(blob)
    opt_state = mod._fused_owner._fused_opt
    for name, m in tr.master_params_named(opt_state).items():
        np.testing.assert_array_equal(np.asarray(m), blob["arg"][name])
    for name, st in tr.flat_state_to_named(opt_state).items():
        np.testing.assert_array_equal(np.asarray(st),
                                      blob["opt"]["state"][name])
    for name, p in mod._fused_owner._fused_params.items():
        assert p.dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(p.astype(jnp.float32)),
            np.asarray(jnp.asarray(blob["arg"][name], jnp.bfloat16)
                       .astype(jnp.float32)))
    for bi, b in enumerate(plan.buckets):   # the pad is zeros
        for key in (tr._master_key(bi), tr._flat_key(bi)):
            np.testing.assert_array_equal(
                np.asarray(opt_state[key])[b.size:], 0)
    again = mod._capture_train_state()
    for name in blob["arg"]:
        np.testing.assert_array_equal(np.asarray(again["arg"][name]),
                                      blob["arg"][name])
        np.testing.assert_array_equal(
            np.asarray(again["opt"]["state"][name]),
            blob["opt"]["state"][name])
    assert float(np.asarray(again["opt"]["amp"]["scale"])) == 64.0


# ---------------------------------------------------------------------------
# kvstore gradient buckets: dtype grouping + reduce-dtype upcast
# ---------------------------------------------------------------------------

def test_bucketer_groups_by_dtype_and_counts_itemsize():
    """Same-dtype buckets; the byte cap counts actual dtype bytes, so a
    half-precision model packs 2x the elements per bucket."""
    from mxnet_tpu.kvstore import GradBucketer

    def entry(b, prio, key, arr):
        b.add(prio, key, key, {}, arr, lambda *a: None)

    # cap 64 bytes: 16 f32 fill a bucket; 16 f16 leave room for 16 more
    b = GradBucketer(64)
    entry(b, 0, 0, np.zeros(16, np.float32))
    entry(b, 0, 1, np.zeros(16, np.float16))
    entry(b, 0, 2, np.zeros(16, np.float16))
    buckets = b.drain()
    assert len(buckets) == 2
    by_dtype = {bk[0].dtype: bk for bk in buckets}
    assert len(by_dtype[np.dtype(np.float32)]) == 1
    assert len(by_dtype[np.dtype(np.float16)]) == 2  # 2x16x2B == 64B cap
    for bk in buckets:
        assert len({e.dtype for e in bk}) == 1
    # nbytes reflects the real itemsize
    assert by_dtype[np.dtype(np.float16)][0].nbytes == 32
    assert by_dtype[np.dtype(np.float32)][0].nbytes == 64


def test_bucket_reduce_dtype_round_trip(monkeypatch):
    """MXTPU_BUCKET_REDUCE_DTYPE=float32 upcasts the bucket sum only;
    the carve-back recasts, so pulled values keep the push dtype and
    round-trip exactly at P=1."""
    monkeypatch.setenv("MXTPU_BUCKET_REDUCE_DTYPE", "float32")
    monkeypatch.setenv("MXNET_KVSTORE_ASYNC", "0")
    kv = mx.kv.create("local")
    kv.type = "dist_sync"  # fake dist: collectives pass through at P=1
    kv._size = 2
    vals = np.arange(5, dtype=np.float16)
    kv.init(0, mx.nd.zeros((5,), dtype=np.float16))
    kv.push(0, mx.nd.array(vals, dtype=np.float16))
    kv._flush_buckets()
    out = mx.nd.zeros((5,), dtype=np.float16)
    kv.pull(0, out=out)
    assert out.asnumpy().dtype == np.float16
    np.testing.assert_array_equal(out.asnumpy(), vals)


# ---------------------------------------------------------------------------
# checkpoint dtype portability (in-process capture/restore)
# ---------------------------------------------------------------------------

def test_amp_checkpoint_cross_dtype_both_directions(monkeypatch):
    """An AMP snapshot's "arg" is the fp32 masters, so it restores into
    an fp32 run unchanged; an fp32 snapshot restores into an AMP run
    (masters = snapshot params, working = their bf16 cast, fresh
    scaler)."""
    import jax.numpy as jnp

    monkeypatch.setenv("MXTPU_AMP", "bf16")
    mod_amp, _ = _fit_mlp(2, "sgd", num_epoch=1)
    blob_amp = mod_amp._capture_train_state()
    amp_arg = {k: np.asarray(v) for k, v in blob_amp["arg"].items()}
    assert all(v.dtype == np.float32 for v in amp_arg.values())
    assert "amp" in blob_amp["opt"]

    # AMP checkpoint -> fp32 run
    monkeypatch.delenv("MXTPU_AMP", raising=False)
    mod_f32, _ = _fit_mlp(2, "sgd", num_epoch=1)
    assert not mod_f32._fused_owner._fused_trainer.amp
    mod_f32._restore_train_state(
        {k: ({kk: np.asarray(vv) for kk, vv in v.items()}
             if k in ("arg", "aux") else v)
         for k, v in blob_amp.items()})
    for name, p in mod_f32._fused_owner._fused_params.items():
        assert p.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(p), amp_arg[name])

    blob_f32 = mod_f32._capture_train_state()
    f32_arg = {k: np.asarray(v) for k, v in blob_f32["arg"].items()}

    # fp32 checkpoint -> AMP run
    monkeypatch.setenv("MXTPU_AMP", "bf16")
    mod_amp2, _ = _fit_mlp(2, "sgd", num_epoch=1)
    tr2 = mod_amp2._fused_owner._fused_trainer
    assert tr2.amp
    mod_amp2._restore_train_state(
        {k: ({kk: np.asarray(vv) for kk, vv in v.items()}
             if k in ("arg", "aux") else v)
         for k, v in blob_f32.items()})
    masters = _masters(mod_amp2)
    for name, m in masters.items():
        np.testing.assert_array_equal(np.asarray(m), f32_arg[name])
        np.testing.assert_array_equal(
            np.asarray(mod_amp2._fused_owner._fused_params[name]),
            np.asarray(jnp.asarray(m, jnp.bfloat16)))
    # fp32 snapshots carry no scaler: AMP restore starts a fresh one
    scale = float(np.asarray(
        mod_amp2._fused_owner._fused_opt[tr2.AMP_SCALE_KEY]))
    assert scale == tr2.amp_scale_init
    # and the restored module keeps training
    rng = np.random.RandomState(9)
    X = rng.randn(32, 8).astype(np.float32)
    y = rng.randint(0, 4, 32).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=16)
    metric = mx.metric.create("acc")
    mod_amp2.fit(it, eval_metric=metric, num_epoch=1,
                 arg_params=mod_amp2._arg_params,
                 aux_params=mod_amp2._aux_params, force_init=False,
                 kvstore="device", optimizer="sgd",
                 optimizer_params={"learning_rate": 0.1,
                                   "momentum": 0.9,
                                   "rescale_grad": 1.0 / 16})
    assert np.isfinite(metric.get()[1])


# ---------------------------------------------------------------------------
# SIGKILL crash-resume (subprocess, as in the sharded-update tests)
# ---------------------------------------------------------------------------

TRAIN_SCRIPT = textwrap.dedent("""\
    import os, sys
    sys.path.insert(0, %(repo)r)
    ndev = int(os.environ.get("T_NDEV", "4"))
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=" + str(ndev))
    import logging
    logging.basicConfig(level=logging.INFO)
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import mxnet_tpu as mx

    ckpt_dir, out = sys.argv[1], sys.argv[2]
    np.random.seed(0)
    mx.random.seed(0)
    rng = np.random.RandomState(42)
    X = rng.randn(128, 8).astype(np.float32)
    y = rng.randint(0, 4, 128).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=16)  # 8 batches/epoch

    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")

    mod = mx.mod.Module(net, context=[mx.cpu(i) for i in range(ndev)])
    metric = mx.metric.create("acc")
    kw = {}
    if ckpt_dir != "-":
        kw = dict(checkpoint_dir=ckpt_dir, resume="auto")
    mod.fit(it, eval_metric=metric, kvstore="device", optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9,
                              "rescale_grad": 1.0 / 16},
            initializer=mx.init.Uniform(0.1), num_epoch=2, **kw)
    assert mod._fused_trainer is not None
    tr = mod._fused_owner._fused_trainer
    want_amp = os.environ.get("T_WANT_AMP")
    if want_amp is not None:
        assert tr.amp == (want_amp == "1"), (tr.amp, want_amp)

    arg, aux = mod.get_params()
    blob = {"arg:" + k: v.asnumpy() for k, v in arg.items()}
    blob.update({"aux:" + k: v.asnumpy() for k, v in aux.items()})
    blob["__metric__"] = np.asarray([metric.get()[1]])
    host = mod._fused_opt_host_state()
    blob["__t__"] = np.asarray([host["t"]])
    if host.get("amp"):
        blob["__amp_scale__"] = np.asarray([host["amp"]["scale"]])
        blob["__amp_good__"] = np.asarray([host["amp"]["good"]])
    def _flatten(prefix, s):
        if s is None:
            return
        if isinstance(s, tuple):
            for j, x in enumerate(s):
                _flatten(prefix + "." + str(j), x)
        else:
            blob["opt:" + prefix] = np.asarray(s)
    for name, s in host["state"].items():
        _flatten(name, s)
    np.savez(out, **blob)
    print("TRAIN-DONE", flush=True)
""") % {"repo": REPO}


def _run_train(script_dir, ckpt_dir, out, extra_env, timeout=300):
    script = os.path.join(script_dir, "train_amp.py")
    if not os.path.exists(script):
        with open(script, "w") as f:
            f.write(TRAIN_SCRIPT)
    env = os.environ.copy()
    env.pop("XLA_FLAGS", None)
    env.pop(fault.ENV, None)
    for k in ("MXTPU_AMP", "MXTPU_SHARD_UPDATE", "MXTPU_BUCKET_BYTES",
              "MXTPU_DEVICE_FEED", "MXTPU_LOSS_SCALE",
              "MXTPU_LOSS_SCALE_WINDOW"):
        env.pop(k, None)
    env.update(extra_env)
    return subprocess.run(
        [sys.executable, script, ckpt_dir, out],
        capture_output=True, text=True, timeout=timeout, env=env)


def _load_blob(path):
    with np.load(path) as z:
        return {k: z[k] for k in z.files}


def _assert_bitwise(got, want):
    assert sorted(got) == sorted(want), (sorted(got), sorted(want))
    for k in want:
        np.testing.assert_array_equal(got[k], want[k],
                                      err_msg="%s differs" % k)


def test_amp_kill_resume_and_cross_dtype(tmp_path):
    """SIGKILL mid-epoch under AMP, auto-resume under AMP: bitwise
    parity with the uninterrupted AMP run (masters, optimizer state,
    scaler, metric). Then resume the SAME crash checkpoints WITHOUT
    AMP — the snapshot params are the fp32 masters, so the fp32 run
    restores and finishes cleanly (cross-dtype portability under
    crash-resume, not just clean save/load)."""
    base_env = {"T_NDEV": "4", "MXTPU_AMP": "bf16", "T_WANT_AMP": "1",
                ck.ENV_INTERVAL: "3"}
    ref_out = str(tmp_path / "ref.npz")
    proc = _run_train(str(tmp_path), str(tmp_path / "ref_ck"), ref_out,
                      base_env)
    assert proc.returncode == 0, proc.stderr
    assert "TRAIN-DONE" in proc.stdout

    crash_dir = str(tmp_path / "crash_ck")
    crash_env = dict(base_env, **{fault.ENV: "kill_at_step=13"})
    proc = _run_train(str(tmp_path), crash_dir,
                      str(tmp_path / "unused.npz"), crash_env)
    assert proc.returncode == -signal.SIGKILL
    assert ck.list_checkpoints(crash_dir), "no checkpoint survived"
    crash_copy = str(tmp_path / "crash_ck_copy")
    shutil.copytree(crash_dir, crash_copy)

    res_out = str(tmp_path / "res.npz")
    proc = _run_train(str(tmp_path), crash_dir, res_out, base_env)
    assert proc.returncode == 0, proc.stderr
    assert "resume: restored step" in proc.stderr
    ref_blob = _load_blob(ref_out)
    assert "__amp_scale__" in ref_blob
    _assert_bitwise(_load_blob(res_out), ref_blob)

    # cross-dtype: the same AMP crash checkpoints, fp32 resume
    swap_out = str(tmp_path / "swap.npz")
    proc = _run_train(str(tmp_path), crash_copy, swap_out,
                      {"T_NDEV": "4", "T_WANT_AMP": "0",
                       ck.ENV_INTERVAL: "3"})
    assert proc.returncode == 0, proc.stderr
    assert "resume: restored step" in proc.stderr
    swap = _load_blob(swap_out)
    assert "__amp_scale__" not in swap  # genuinely ran fp32
    assert np.isfinite(swap["__metric__"][0])
    # both runs saw identical steps 0..12 (masters are the truth), so
    # the fc weights must be close even though post-crash arithmetic
    # ran in different precisions
    for k in swap:
        if k.startswith("arg:"):
            np.testing.assert_allclose(swap[k], ref_blob[k], atol=0.05,
                                       err_msg=k)


def test_fp32_crash_resumes_under_amp(tmp_path):
    """The reverse direction: crash an fp32 run, resume with
    MXTPU_AMP=bf16 — params seed the masters, training completes."""
    crash_dir = str(tmp_path / "crash_ck")
    proc = _run_train(str(tmp_path), crash_dir,
                      str(tmp_path / "unused.npz"),
                      {"T_NDEV": "4", "T_WANT_AMP": "0",
                       ck.ENV_INTERVAL: "3",
                       fault.ENV: "kill_at_step=13"})
    assert proc.returncode == -signal.SIGKILL
    assert ck.list_checkpoints(crash_dir)

    res_out = str(tmp_path / "res.npz")
    proc = _run_train(str(tmp_path), crash_dir, res_out,
                      {"T_NDEV": "4", "MXTPU_AMP": "bf16",
                       "T_WANT_AMP": "1", ck.ENV_INTERVAL: "3"})
    assert proc.returncode == 0, proc.stderr
    assert "resume: restored step" in proc.stderr
    blob = _load_blob(res_out)
    assert "__amp_scale__" in blob
    assert np.isfinite(blob["__metric__"][0])
