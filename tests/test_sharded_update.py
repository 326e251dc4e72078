"""Sharded weight update parity (ISSUE 5 tentpole acceptance).

The flat bucketed update ships in two modes sharing ONE chunk-width
update body (parallel/train_step.py): "shard" (MXTPU_SHARD_UPDATE=1,
the dp>1 default — each replica updates its 1/N shard inside shard_map,
optimizer state materialized at 1/N, weights all-gathered in-step) and
"replicated" (=0 — the same dp-chunk body scanned on every replica).
Matching chunk widths is what makes the two bitwise-equal: XLA contracts
mul+add into FMA per fusion width, so a monolithic full-width update
would round differently from the sharded one.

These tests pin the acceptance criteria: bitwise-equal params, optimizer
state, and metrics between the sharded and replicated paths — for
SGD-momentum and Adam, with MXTPU_DEVICE_FEED on and off, across
1/2/4 simulated devices — including SIGKILL crash-resume through
resilience checkpoints and checkpoint portability across modes.
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


# ---------------------------------------------------------------------------
# in-process end-to-end parity (the suite runs on an 8-device CPU mesh)
# ---------------------------------------------------------------------------

def _small_net():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _opt_params(optname):
    p = {"learning_rate": 0.1, "rescale_grad": 1.0 / 16}
    if optname == "sgd":
        p["momentum"] = 0.9
    return p


def _fit_once(ndev, optname, num_epoch=2):
    np.random.seed(0)
    mx.random.seed(0)
    rng = np.random.RandomState(42)
    X = rng.randn(128, 8).astype(np.float32)
    y = rng.randint(0, 4, 128).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=16)
    mod = mx.mod.Module(_small_net(),
                        context=[mx.cpu(i) for i in range(ndev)])
    metric = mx.metric.create("acc")
    mod.fit(it, eval_metric=metric, kvstore="device", optimizer=optname,
            optimizer_params=_opt_params(optname),
            initializer=mx.init.Uniform(0.1), num_epoch=num_epoch)
    assert mod._fused_trainer is not None, "fused path did not engage"
    return mod, metric


def _snapshot(mod, metric):
    arg, aux = mod.get_params()
    blob = {"arg:" + k: v.asnumpy() for k, v in arg.items()}
    blob.update({"aux:" + k: v.asnumpy() for k, v in aux.items()})
    blob["__metric__"] = np.asarray([metric.get()[1]])
    host = mod._fused_opt_host_state()
    blob["__t__"] = np.asarray([host["t"]])

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
    return blob


def _assert_bitwise(got, want):
    assert sorted(got) == sorted(want), (sorted(got), sorted(want))
    for k in want:
        np.testing.assert_array_equal(got[k], want[k],
                                      err_msg="%s differs" % k)


@pytest.mark.parametrize("ndev,optname,feed,bucket", [
    (2, "sgd", "1", None),
    (2, "adam", "0", "256"),   # tiny cap: multiple buckets + padding
    (4, "sgd", "1", None),
    (4, "adam", "0", None),
    (8, "sgd", "1", "256"),
])
def test_sharded_bitwise_parity(monkeypatch, ndev, optname, feed, bucket):
    """MXTPU_SHARD_UPDATE=1 vs =0: params, optimizer state, and metric
    bitwise-equal across device counts, optimizers and device-resident
    feeds; sharded state genuinely at 1/N."""
    from jax.sharding import PartitionSpec as P

    monkeypatch.setenv("MXTPU_DEVICE_FEED", feed)
    if bucket is not None:
        monkeypatch.setenv("MXTPU_BUCKET_BYTES", bucket)

    monkeypatch.setenv("MXTPU_SHARD_UPDATE", "1")
    mod_s, met_s = _fit_once(ndev, optname)
    tr = mod_s._fused_owner._fused_trainer
    assert tr.flat_mode == "shard", tr.flat_mode
    for st in mod_s._fused_owner._fused_opt.values():
        leaf = st[0] if isinstance(st, tuple) else st
        assert leaf.sharding.spec == P("dp"), leaf.sharding.spec
        shard0 = leaf.addressable_shards[0].data
        assert shard0.shape[0] * ndev == leaf.shape[0], \
            "state not materialized at 1/N"
    blob_s = _snapshot(mod_s, met_s)

    monkeypatch.setenv("MXTPU_SHARD_UPDATE", "0")
    mod_r, met_r = _fit_once(ndev, optname)
    assert mod_r._fused_owner._fused_trainer.flat_mode == "replicated"
    _assert_bitwise(blob_s, _snapshot(mod_r, met_r))


def test_single_device_uses_legacy_path(monkeypatch):
    """dp=1: nothing to shard — the flat layer must stay out of the way
    (at one device the fused trainer may not even engage; either way no
    flat mode and training completes)."""
    monkeypatch.setenv("MXTPU_SHARD_UPDATE", "1")
    np.random.seed(0)
    mx.random.seed(0)
    rng = np.random.RandomState(42)
    X = rng.randn(128, 8).astype(np.float32)
    y = rng.randint(0, 4, 128).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=16)
    mod = mx.mod.Module(_small_net(), context=[mx.cpu(0)])
    metric = mx.metric.create("acc")
    mod.fit(it, eval_metric=metric, kvstore="device", optimizer="sgd",
            optimizer_params=_opt_params("sgd"),
            initializer=mx.init.Uniform(0.1), num_epoch=1)
    if mod._fused_trainer is not None:
        assert mod._fused_owner._fused_trainer.flat_mode is None
    assert np.isfinite(metric.get()[1])


def test_bucket_bytes_zero_disables_flat(monkeypatch):
    monkeypatch.setenv("MXTPU_BUCKET_BYTES", "0")
    mod, _ = _fit_once(2, "sgd", num_epoch=1)
    assert mod._fused_owner._fused_trainer.flat_mode is None


def test_flat_update_plan_packing():
    """_FlatUpdatePlan: reverse-key packing, size caps, dp padding, and
    full per-key view coverage."""
    from mxnet_tpu import optimizer as opt
    from mxnet_tpu.parallel.train_step import _FlatUpdatePlan

    names = ["a", "b", "c", "d"]
    shapes = {"a": (8, 4), "b": (8,), "c": (6, 4), "d": (3,)}
    dtypes = {n: "float32" for n in names}
    sgd = opt.create("sgd", learning_rate=0.1, momentum=0.9)
    # cap = 32 floats = 128 bytes; reverse walk packs d,c then b,a
    plan = _FlatUpdatePlan(names, shapes, dtypes, sgd, dp=4,
                           bucket_bytes=128)
    assert len(plan.buckets) >= 2
    seen = {}
    for bi, b in enumerate(plan.buckets):
        assert b.size <= 32 or len(b.views) == 1
        assert b.padded % 4 == 0 and b.padded >= b.size
        off_end = 0
        for (_i, name, off, size, shape) in b.views:
            assert off == off_end  # views are contiguous
            off_end = off + size
            assert size == int(np.prod(shape))
            seen[name] = bi
    assert sorted(seen) == sorted(names)
    # reverse-key issue order: later keys land in earlier buckets
    assert seen["d"] <= seen["a"]


def test_flat_plan_groups_by_mult():
    """Keys with distinct lr_mult cannot share a bucket (one scalar
    fused-kwargs set per slab)."""
    from mxnet_tpu import optimizer as opt
    from mxnet_tpu.parallel.train_step import _FlatUpdatePlan

    names = ["w1", "w2"]
    shapes = {"w1": (4,), "w2": (4,)}
    dtypes = {n: "float32" for n in names}
    sgd = opt.create("sgd", learning_rate=0.1,
                     param_idx2name={0: "w1", 1: "w2"})
    sgd.set_lr_mult({"w2": 0.5})
    plan = _FlatUpdatePlan(names, shapes, dtypes, sgd, dp=2,
                           bucket_bytes=1 << 20)
    assert len(plan.buckets) == 2


def test_elementwise_update_flags():
    """Optimizers whose update math is NOT elementwise over the flat
    space must be excluded from the flat path."""
    from mxnet_tpu import optimizer as opt

    for name in ("sgd", "adam", "rmsprop", "adagrad", "adadelta", "ftrl"):
        assert opt.create(name).elementwise_update, name
    for name in ("sgld", "dcasgd"):
        assert not opt.create(name).elementwise_update, name


def test_borrow_optimizer_demotes_flat(monkeypatch):
    """borrow_optimizer shares a param-name subset the flat slabs cannot
    express: the owner must demote to the per-param update, converting
    state in place, and keep training."""
    monkeypatch.setenv("MXTPU_SHARD_UPDATE", "1")
    mod, metric = _fit_once(2, "sgd", num_epoch=1)
    owner_tr = mod._fused_owner._fused_trainer
    assert owner_tr.flat_mode == "shard"
    borrower = mx.mod.Module(_small_net(),
                             context=[mx.cpu(i) for i in range(2)])
    rng = np.random.RandomState(1)
    X = rng.randn(32, 8).astype(np.float32)
    y = rng.randint(0, 4, 32).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=16)
    borrower.bind(data_shapes=it.provide_data,
                  label_shapes=it.provide_label,
                  shared_module=mod)
    borrower.init_params(mx.init.Uniform(0.1))
    borrower.borrow_optimizer(mod)
    assert owner_tr.flat_mode is None  # demoted
    # state keys converted back to per-name layout
    assert all(not str(k).startswith("__flat__")
               for k in mod._fused_owner._fused_opt)
    batch = next(iter(it))
    borrower.forward(batch)
    borrower.backward()
    borrower.update()  # must not raise


# ---------------------------------------------------------------------------
# mesh collective primitives
# ---------------------------------------------------------------------------

def test_reduce_scatter_all_gather_single_process():
    """Single-process passthrough (the multi-process path is covered by
    the dist worker tests): reduce_scatter returns the full sum, gather
    returns its input, and the divisibility contract is enforced."""
    from mxnet_tpu.parallel import all_gather, reduce_scatter_sum

    v = np.arange(12, dtype=np.float32).reshape(6, 2)
    np.testing.assert_array_equal(reduce_scatter_sum(v), v)
    np.testing.assert_array_equal(all_gather(v), v)


def test_bucket_round_trip_two_phase(monkeypatch):
    """MXTPU_BUCKET_TWO_PHASE routes kvstore bucket collectives through
    reduce_scatter_sum + all_gather (with padding); values must round-
    trip exactly."""
    monkeypatch.setenv("MXTPU_BUCKET_TWO_PHASE", "1")
    monkeypatch.setenv("MXNET_KVSTORE_ASYNC", "0")
    kv = mx.kv.create("local")
    kv.type = "dist_sync"  # fake dist: collectives pass through at P=1
    kv._size = 2
    kv.init(0, mx.nd.zeros((5,)))
    kv.init(1, mx.nd.zeros((3,)))
    kv.push(0, mx.nd.array(np.arange(5, dtype=np.float32)))
    kv.push(1, mx.nd.array(np.arange(3, dtype=np.float32) + 10))
    kv._flush_buckets()
    out0, out1 = mx.nd.zeros((5,)), mx.nd.zeros((3,))
    kv.pull(0, out=out0)
    kv.pull(1, out=out1)
    np.testing.assert_array_equal(out0.asnumpy(),
                                  np.arange(5, dtype=np.float32))
    np.testing.assert_array_equal(out1.asnumpy(),
                                  np.arange(3, dtype=np.float32) + 10)


# ---------------------------------------------------------------------------
# crash-resume + checkpoint portability (subprocess: own device count,
# SIGKILL fault injection — the pattern of test_resilience.py)
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

    optname = os.environ.get("T_OPT", "sgd")
    opt_params = {"learning_rate": 0.1, "rescale_grad": 1.0 / 16}
    if optname == "sgd":
        opt_params["momentum"] = 0.9
    mod = mx.mod.Module(net, context=[mx.cpu(i) for i in range(ndev)])
    metric = mx.metric.create("acc")
    kw = {}
    if ckpt_dir != "-":
        kw = dict(checkpoint_dir=ckpt_dir, resume="auto")
    mod.fit(it, eval_metric=metric, kvstore="device", optimizer=optname,
            optimizer_params=opt_params,
            initializer=mx.init.Uniform(0.1), num_epoch=2, **kw)
    assert mod._fused_trainer is not None
    tr = mod._fused_owner._fused_trainer
    want = os.environ.get("T_WANT_MODE")
    if want:
        got = tr.flat_mode or "none"
        assert got == want, (got, want)

    arg, aux = mod.get_params()
    blob = {"arg:" + k: v.asnumpy() for k, v in arg.items()}
    blob.update({"aux:" + k: v.asnumpy() for k, v in aux.items()})
    blob["__metric__"] = np.asarray([metric.get()[1]])
    host = mod._fused_opt_host_state()
    blob["__t__"] = np.asarray([host["t"]])
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
    script = os.path.join(script_dir, "train_sharded.py")
    if not os.path.exists(script):
        with open(script, "w") as f:
            f.write(TRAIN_SCRIPT)
    env = os.environ.copy()
    env.pop("XLA_FLAGS", None)
    env.pop(fault.ENV, None)
    for k in ("MXTPU_SHARD_UPDATE", "MXTPU_BUCKET_BYTES",
              "MXTPU_DEVICE_FEED"):
        env.pop(k, None)
    env.update(extra_env)
    return subprocess.run(
        [sys.executable, script, ckpt_dir, out],
        capture_output=True, text=True, timeout=timeout, env=env)


def _load_blob(path):
    with np.load(path) as z:
        return {k: z[k] for k in z.files}


def test_sharded_kill_resume_and_cross_mode(tmp_path):
    """SIGKILL mid-epoch under the sharded update, auto-resume: bitwise
    parity with the uninterrupted run. Then resume the SAME crash
    checkpoints with MXTPU_SHARD_UPDATE=0 — the snapshot layout is
    per-param, so checkpoints are portable across modes and the result
    is STILL bitwise-identical (both modes share the chunk-width
    body)."""
    base_env = {"T_NDEV": "4", "T_OPT": "sgd",
                "MXTPU_SHARD_UPDATE": "1", ck.ENV_INTERVAL: "3"}
    ref_out = str(tmp_path / "ref.npz")
    proc = _run_train(str(tmp_path), str(tmp_path / "ref_ck"), ref_out,
                      dict(base_env, T_WANT_MODE="shard"))
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
    proc = _run_train(str(tmp_path), crash_dir, res_out,
                      dict(base_env, T_WANT_MODE="shard"))
    assert proc.returncode == 0, proc.stderr
    assert "resume: restored step" in proc.stderr
    _assert_bitwise(_load_blob(res_out), _load_blob(ref_out))

    # cross-mode: same crash checkpoints, replicated-mode resume
    swap_out = str(tmp_path / "swap.npz")
    proc = _run_train(str(tmp_path), crash_copy, swap_out,
                      dict(base_env, MXTPU_SHARD_UPDATE="0",
                           T_WANT_MODE="replicated"))
    assert proc.returncode == 0, proc.stderr
    assert "resume: restored step" in proc.stderr
    _assert_bitwise(_load_blob(swap_out), _load_blob(ref_out))
