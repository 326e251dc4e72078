"""Resilience subsystem tests: atomic checkpoints, fault injection,
retry policy, graceful preemption, and crash-resume parity.

The parity tests are the contract at the heart of docs/robustness.md:
a run that is SIGKILLed mid-epoch and auto-resumed from its last
checkpoint must produce bitwise-identical final params and metrics to
an uninterrupted run — including when the newest checkpoint is torn and
resume has to fall back to the previous valid one.
"""
import errno
import os
import pickle
import signal
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import resilience
from mxnet_tpu.base import MXNetError
from mxnet_tpu.resilience import checkpoint as ck
from mxnet_tpu.resilience import fault, retry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FOUR_DEV = [mx.cpu(i) for i in range(4)]


# ---------------------------------------------------------------------------
# checkpoint primitives
# ---------------------------------------------------------------------------

def _state(step=10, w=None):
    return {
        "module": {
            "arg": {"w": np.arange(12, dtype=np.float32).reshape(3, 4)
                    if w is None else w},
            "aux": {"m": np.ones(3, dtype=np.float64)},
            "opt": {"kind": "none"},
        },
        "epoch": 1, "nbatch": 2, "global_step": step,
        "metric": None,
        "rng": {"numpy": np.random.get_state(),
                "mx": mx.random.get_state()},
    }


def test_atomic_file_success(tmp_path):
    target = tmp_path / "out.bin"
    with ck.atomic_file(str(target)) as f:
        f.write(b"payload")
    assert target.read_bytes() == b"payload"
    assert not [n for n in os.listdir(tmp_path) if n.startswith(".tmp-")]


def test_atomic_file_failure_leaves_previous_intact(tmp_path):
    target = tmp_path / "out.bin"
    target.write_bytes(b"old")
    with pytest.raises(RuntimeError):
        with ck.atomic_file(str(target)) as f:
            f.write(b"half-written new conten")
            raise RuntimeError("boom")
    assert target.read_bytes() == b"old"
    assert not [n for n in os.listdir(tmp_path) if n.startswith(".tmp-")]


def test_checkpoint_roundtrip(tmp_path):
    mgr = ck.CheckpointManager(str(tmp_path), keep=3)
    path = mgr.save(_state(step=7), 7)
    assert os.path.isdir(path)
    ck.verify_checkpoint(path, deep=True)
    state = ck.load_state(path)
    np.testing.assert_array_equal(
        state["module"]["arg"]["w"],
        np.arange(12, dtype=np.float32).reshape(3, 4))
    np.testing.assert_array_equal(state["module"]["aux"]["m"],
                                  np.ones(3, dtype=np.float64))
    assert state["epoch"] == 1 and state["nbatch"] == 2
    assert state["global_step"] == 7
    assert state["module"]["opt"] == {"kind": "none"}


def test_checkpoint_retention_keeps_last_n(tmp_path):
    mgr = ck.CheckpointManager(str(tmp_path), keep=2)
    for step in (1, 2, 3):
        mgr.save(_state(step=step), step)
    assert ck.list_checkpoints(str(tmp_path)) == [2, 3]


def test_checkpoint_duplicate_step_is_noop(tmp_path):
    mgr = ck.CheckpointManager(str(tmp_path), keep=3)
    first = mgr.save(_state(), 5)
    again = mgr.save(_state(), 5)
    assert first == again
    ck.verify_checkpoint(first, deep=True)


def test_latest_valid_skips_truncated_newest(tmp_path):
    mgr = ck.CheckpointManager(str(tmp_path), keep=5)
    mgr.save(_state(step=10), 10)
    mgr.save(_state(step=20), 20)
    torn = os.path.join(ck.step_dir(str(tmp_path), 20), ck.PARAMS_FILE)
    with open(torn, "r+b") as f:
        f.truncate(os.path.getsize(torn) // 2)
    with pytest.raises(ck.CheckpointError):
        ck.verify_checkpoint(ck.step_dir(str(tmp_path), 20))
    assert mgr.latest_valid() == ck.step_dir(str(tmp_path), 10)
    state = mgr.load()
    assert state["global_step"] == 10


def test_latest_valid_none_when_all_torn(tmp_path):
    mgr = ck.CheckpointManager(str(tmp_path), keep=5)
    mgr.save(_state(step=3), 3)
    manifest = os.path.join(ck.step_dir(str(tmp_path), 3), ck.MANIFEST)
    os.unlink(manifest)
    assert mgr.latest_valid() is None
    assert mgr.load() is None


def test_enospc_aborts_without_partial_checkpoint(tmp_path, monkeypatch):
    mgr = ck.CheckpointManager(str(tmp_path), keep=3)
    mgr.save(_state(step=1), 1)
    # second member write (optimizer.state) of the NEXT save hits ENOSPC
    monkeypatch.setenv(fault.ENV, "enospc_at_ckpt_write=2")
    with pytest.raises(OSError) as exc:
        mgr.save(_state(step=2), 2)
    assert exc.value.errno == errno.ENOSPC
    monkeypatch.delenv(fault.ENV)
    # no partial ckpt-2, no leftover build dir, ckpt-1 untouched
    assert ck.list_checkpoints(str(tmp_path)) == [1]
    assert not [n for n in os.listdir(tmp_path) if n.startswith(".tmp-")]
    ck.verify_checkpoint(ck.step_dir(str(tmp_path), 1), deep=True)


def test_transient_ckpt_write_absorbed_by_retry(tmp_path, monkeypatch):
    monkeypatch.setenv(fault.ENV, "fail_ckpt_write=2")
    mgr = ck.CheckpointManager(str(tmp_path), keep=3)
    path = mgr.save(_state(step=4), 4)
    ck.verify_checkpoint(path, deep=True)


def test_save_async_failure_is_contained(tmp_path, monkeypatch):
    monkeypatch.setenv(fault.ENV, "enospc_at_ckpt_write=1")
    mgr = ck.CheckpointManager(str(tmp_path), keep=3)
    mgr.save_async(_state(step=9), 9)
    mgr.wait()  # must not raise; failure is logged + counted
    assert ck.list_checkpoints(str(tmp_path)) == []
    assert isinstance(mgr._last_error, OSError)


# ---------------------------------------------------------------------------
# fault spec + retry policy
# ---------------------------------------------------------------------------

def test_fault_unset_is_noop(monkeypatch):
    monkeypatch.delenv(fault.ENV, raising=False)
    assert not fault.configured()
    fault.fire("step", step=1)  # no spec: must not raise


def test_fault_malformed_directives_ignored(monkeypatch):
    monkeypatch.setenv(fault.ENV, "nonsense,foo=bar,kill_at_step=xyz, ,=3")
    assert fault.configured()
    fault.fire("step", step=1)
    fault.fire("ckpt_write", path="p")


def test_fault_budget_is_consumed_once(monkeypatch):
    monkeypatch.setenv(fault.ENV, "fail_kv_push=1,unit=%d" % os.getpid())
    with pytest.raises(OSError) as exc:
        fault.fire("kv_push", key="3")
    assert exc.value.errno == errno.EIO
    fault.fire("kv_push", key="3")  # budget spent: second fire is a no-op


def test_retry_backoff_then_success():
    sleeps = []
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError(errno.EIO, "transient")
        return "ok"

    assert retry.call(flaky, max_attempts=5, base_delay=0.05, jitter=0.0,
                      sleep=sleeps.append) == "ok"
    assert calls["n"] == 3
    assert sleeps == [0.05, 0.1]  # exponential, jitter disabled


def test_retry_gives_up_after_max_attempts():
    def always():
        raise retry.TransientError("still down")

    with pytest.raises(retry.TransientError):
        retry.call(always, max_attempts=3, sleep=lambda s: None)


def test_retry_does_not_catch_permanent_errors():
    calls = {"n": 0}

    def permanent():
        calls["n"] += 1
        raise ValueError("logic bug")

    with pytest.raises(ValueError):
        retry.call(permanent, max_attempts=5, sleep=lambda s: None)
    assert calls["n"] == 1  # no retry on non-retryable


def test_retry_classification():
    assert retry.is_retryable(OSError(errno.EIO, "io"))
    assert retry.is_retryable(OSError(errno.ETIMEDOUT, "t"))
    assert retry.is_retryable(retry.TransientError("x"))
    assert not retry.is_retryable(OSError(errno.ENOSPC, "full"))
    assert not retry.is_retryable(ValueError("x"))


# ---------------------------------------------------------------------------
# satellite fixes: heartbeat restart, recordio error context, iterator skip
# ---------------------------------------------------------------------------

def test_heartbeat_stop_start_single_beater(tmp_path):
    from mxnet_tpu.parallel.heartbeat import HeartbeatWriter

    w = HeartbeatWriter(str(tmp_path), rank=0, interval=0.05)
    w.start()
    first = w._thread
    assert first.is_alive()
    w.start()  # idempotent: must not spawn a second beater
    assert w._thread is first
    w.stop()
    w.start()  # restartable after stop
    assert w._thread is not None and w._thread.is_alive()
    beaters = [t for t in threading.enumerate()
               if t.name == "mxtpu-heartbeat" and t.is_alive()]
    assert len(beaters) == 1
    w.stop()


def test_heartbeat_stop_timeout_keeps_handle_then_reaps(tmp_path):
    from mxnet_tpu.parallel import heartbeat as hb

    w = hb.HeartbeatWriter(str(tmp_path), rank=1, interval=0.05)

    class _Winding:
        """Thread double stuck past stop()'s join timeout."""

        def __init__(self):
            self.alive = True
            self.joined_blocking = False

        def is_alive(self):
            return self.alive

        def join(self, timeout=None):
            if timeout is None:
                self.joined_blocking = True
                self.alive = False

    stuck = _Winding()
    w._thread = stuck
    w.stop()
    # join timed out: the handle must be KEPT so a later start() can
    # reap it instead of racing a second beater against it
    assert w._thread is stuck
    w.start()
    assert stuck.joined_blocking  # reaped before the new thread spawned
    assert w._thread is not stuck and w._thread.is_alive()
    w.stop()


def _write_rec(path, payloads):
    rec = mx.recordio.MXRecordIO(path, "w")
    for p in payloads:
        rec.write(p)
    rec.close()


def test_recordio_roundtrip_and_clean_eof(tmp_path):
    path = str(tmp_path / "ok.rec")
    _write_rec(path, [b"hello", b"worldworld"])
    rec = mx.recordio.MXRecordIO(path, "r")
    assert rec.read() == b"hello"
    assert rec.read() == b"worldworld"
    assert rec.read() is None  # clean EOF, not an error
    rec.close()


def test_recordio_truncated_payload_has_offset_context(tmp_path):
    path = str(tmp_path / "torn.rec")
    _write_rec(path, [b"hello", b"worldworld"])
    # rec1 occupies [0,16) (8B header + 5B payload + 3B pad); rec2's
    # header ends at 24. Cut inside rec2's payload.
    with open(path, "r+b") as f:
        f.truncate(26)
    rec = mx.recordio.MXRecordIO(path, "r")
    assert rec.read() == b"hello"
    with pytest.raises(MXNetError) as exc:
        rec.read()
    msg = str(exc.value)
    assert "truncated record payload" in msg
    assert "offset 16" in msg and path in msg
    rec.close()


def test_recordio_truncated_header_and_bad_magic(tmp_path):
    path = str(tmp_path / "head.rec")
    _write_rec(path, [b"hello", b"worldworld"])
    with open(path, "r+b") as f:
        f.truncate(20)  # 4 of rec2's 8 header bytes survive
    rec = mx.recordio.MXRecordIO(path, "r")
    assert rec.read() == b"hello"
    with pytest.raises(MXNetError, match="truncated record header"):
        rec.read()
    rec.close()

    bad = str(tmp_path / "magic.rec")
    _write_rec(bad, [b"hello"])
    with open(bad, "r+b") as f:
        f.write(b"\xde\xad\xbe\xef")
    rec = mx.recordio.MXRecordIO(bad, "r")
    with pytest.raises(MXNetError) as exc:
        rec.read()
    assert "invalid record magic" in str(exc.value)
    assert "offset 0" in str(exc.value)
    rec.close()


def test_recordio_transient_read_retried(tmp_path, monkeypatch):
    path = str(tmp_path / "flaky.rec")
    _write_rec(path, [b"hello"])
    monkeypatch.setenv(fault.ENV,
                       "fail_recordio_read=1,uniq=%d" % os.getpid())
    rec = mx.recordio.MXRecordIO(path, "r")
    assert rec.read() == b"hello"  # injected EIO absorbed by retry
    rec.close()


def test_ndarrayiter_skip_is_cursor_math():
    x = np.arange(40, dtype=np.float32).reshape(10, 4)
    it = mx.io.NDArrayIter(x, np.zeros(10, np.float32), batch_size=2)
    it.reset()
    it.skip(3)
    batch = it.next()
    np.testing.assert_array_equal(np.asarray(batch.data[0].asnumpy()),
                                  x[6:8])


@pytest.mark.parametrize("mode", ["pad", "discard", "roll_over"])
def test_ndarrayiter_skip_matches_sequential_all_modes(mode):
    """skip(k) must leave the iterator exactly where k sequential
    next() calls would — cursor, remaining stream, AND the next epoch
    after reset() (roll_over derives its wrap offset from the cursor,
    so an overshooting skip corrupts epoch 2 silently)."""
    x = np.arange(40, dtype=np.float32).reshape(10, 4)

    def make():
        it = mx.io.NDArrayIter(x, np.zeros(10, np.float32), batch_size=3,
                               last_batch_handle=mode)
        it.reset()
        return it

    def drain(it):
        out = []
        while it.iter_next():
            out.append(np.asarray(it.getdata()[0].asnumpy()))
        return out

    for k in range(0, 8):
        skipped, walked = make(), make()
        skipped.skip(k)
        for _ in range(k):
            if not walked.iter_next():
                break
        assert skipped.cursor == walked.cursor, (mode, k)
        rest_s, rest_w = drain(skipped), drain(walked)
        assert len(rest_s) == len(rest_w), (mode, k)
        for a, b in zip(rest_s, rest_w):
            np.testing.assert_array_equal(a, b)
        # epoch 2: reset() must compute the same wrap offset
        skipped.reset()
        walked.reset()
        assert skipped.cursor == walked.cursor, (mode, k)
        for a, b in zip(drain(skipped), drain(walked)):
            np.testing.assert_array_equal(a, b)


def test_devicefeed_iter_skip_matches_sequential(tmp_path):
    import jax

    sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    x = np.arange(64, dtype=np.float32).reshape(16, 4)
    y = np.zeros(16, np.float32)

    ref = mx.io.NDArrayIter(x, y, batch_size=2)
    ref.reset()
    ref.skip(5)
    want = np.asarray(ref.next().data[0].asnumpy())

    feed = mx.io.DeviceFeedIter(
        mx.io.NDArrayIter(x, y, batch_size=2), sharding)
    feed.reset()
    feed.next()  # batches staged in flight before the skip
    feed.skip(4)  # 1 consumed + 4 skipped = positioned at batch 5
    got = np.asarray(feed.next().data[0])
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# graceful preemption (in-process) + crash-resume parity (subprocess)
# ---------------------------------------------------------------------------

def _mlp():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _blob_iter(batch_size=8, n=64):
    rng = np.random.RandomState(42)
    x = rng.randn(n, 8).astype(np.float32)
    y = rng.randint(0, 4, n).astype(np.float32)
    return mx.io.NDArrayIter(x, y, batch_size=batch_size)


def _fused_fit(ckpt_dir, metric, resume=None, num_epoch=1,
               batch_end_callback=None):
    np.random.seed(0)
    mx.random.seed(0)
    mod = mx.mod.Module(_mlp(), context=FOUR_DEV)
    mod.fit(_blob_iter(), eval_metric=metric, kvstore="device",
            optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            initializer=mx.init.Uniform(0.1), num_epoch=num_epoch,
            checkpoint_dir=ckpt_dir, resume=resume,
            batch_end_callback=batch_end_callback)
    assert mod._fused_trainer is not None
    return mod


def _params_of(mod):
    arg, aux = mod.get_params()
    out = {k: np.asarray(v.asnumpy()) for k, v in arg.items()}
    out.update({"aux:" + k: np.asarray(v.asnumpy()) for k, v in aux.items()})
    return out


def test_sigterm_preempts_with_final_checkpoint_and_exact_resume(
        tmp_path, monkeypatch):
    monkeypatch.setenv(ck.ENV_INTERVAL, "2")
    monkeypatch.delenv(fault.ENV, raising=False)

    ref_metric = mx.metric.create("acc")
    ref = _fused_fit(str(tmp_path / "ref"), ref_metric)
    ref_params = _params_of(ref)

    pre_dir = str(tmp_path / "pre")
    monkeypatch.setenv(fault.ENV, "preempt_at_step=5")
    with pytest.raises(SystemExit) as exc:
        _fused_fit(pre_dir, mx.metric.create("acc"))
    assert exc.value.code == resilience.EXIT_PREEMPTED
    monkeypatch.delenv(fault.ENV)
    # the drain wrote a final checkpoint at the preempted step
    assert 5 in ck.list_checkpoints(pre_dir)

    res_metric = mx.metric.create("acc")
    res = _fused_fit(pre_dir, res_metric, resume="auto")
    res_params = _params_of(res)

    assert sorted(res_params) == sorted(ref_params)
    for key in ref_params:
        np.testing.assert_array_equal(res_params[key], ref_params[key],
                                      err_msg="param %s drifted" % key)
    assert res_metric.get() == ref_metric.get()


def test_preempt_with_a_post_step_pending_runs_it_before_the_checkpoint(
        tmp_path, monkeypatch):
    """fit keeps one step in flight: when the preemption is honoured
    after step 3's dispatch, step 3's metric fetch and callback have
    not run yet. The drain before the capture runs them, so the
    checkpoint's metric covers step 3, every batch's callback fires
    exactly once across the two runs with the uninterrupted run's
    metric, and the resumed run ends on its final metric."""
    monkeypatch.delenv(ck.ENV_INTERVAL, raising=False)
    monkeypatch.delenv(fault.ENV, raising=False)

    def recorder(seen):
        return lambda p: seen.append((p.nbatch, p.eval_metric.get()))

    ref_seen, ref_metric = [], mx.metric.create("acc")
    ref = _fused_fit(str(tmp_path / "ref"), ref_metric,
                     batch_end_callback=recorder(ref_seen))
    assert [n for n, _ in ref_seen] == list(range(8))

    pre_dir, pre_seen = str(tmp_path / "pre"), []
    monkeypatch.setenv(fault.ENV, "preempt_at_step=3")
    with pytest.raises(SystemExit) as exc:
        _fused_fit(pre_dir, mx.metric.create("acc"),
                   batch_end_callback=recorder(pre_seen))
    assert exc.value.code == resilience.EXIT_PREEMPTED
    monkeypatch.delenv(fault.ENV)
    assert pre_seen == ref_seen[:3]
    saved = pickle.loads(ck.CheckpointManager(pre_dir).load()["metric"])
    assert saved.get() == ref_seen[2][1]

    res_seen, res_metric = [], mx.metric.create("acc")
    res = _fused_fit(pre_dir, res_metric, resume="auto",
                     batch_end_callback=recorder(res_seen))
    assert pre_seen + res_seen == ref_seen
    assert res_metric.get() == ref_metric.get()
    ref_params, res_params = _params_of(ref), _params_of(res)
    for key in ref_params:
        np.testing.assert_array_equal(res_params[key], ref_params[key],
                                      err_msg="param %s drifted" % key)


def test_async_interval_snapshots_survive_donation(tmp_path, monkeypatch):
    """The fused step donates its param/opt buffers; every async interval
    snapshot must still publish (device-side copy at capture time), not
    race the next dispatch's donation and die with 'Array deleted'."""
    monkeypatch.setenv(ck.ENV_INTERVAL, "1")
    monkeypatch.delenv(fault.ENV, raising=False)
    mgr = ck.CheckpointManager(str(tmp_path), keep=100)
    _fused_fit(mgr, mx.metric.create("acc"))
    assert mgr._last_error is None
    # one checkpoint per optimizer step + no torn stragglers
    steps = ck.list_checkpoints(str(tmp_path))
    assert steps == list(range(1, 9))
    for step in steps:
        ck.verify_checkpoint(ck.step_dir(str(tmp_path), step), deep=True)


TRAIN_SCRIPT = textwrap.dedent("""\
    import os, sys
    sys.path.insert(0, %(repo)r)
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
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

    mod = mx.mod.Module(net, context=[mx.cpu(i) for i in range(4)])
    metric = mx.metric.create("acc")
    mod.fit(it, eval_metric=metric, kvstore="device", optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            initializer=mx.init.Uniform(0.1), num_epoch=2,
            checkpoint_dir=ckpt_dir, resume="auto")
    assert mod._fused_trainer is not None

    arg, aux = mod.get_params()
    blob = {k: v.asnumpy() for k, v in arg.items()}
    blob.update({"aux:" + k: v.asnumpy() for k, v in aux.items()})
    blob["__metric__"] = np.asarray([metric.get()[1]], dtype=np.float64)
    np.savez(out, **blob)
    print("TRAIN-DONE", flush=True)
""") % {"repo": REPO}


def _run_train(script_dir, ckpt_dir, out, extra_env, timeout=300):
    script = os.path.join(script_dir, "train_ckpt.py")
    if not os.path.exists(script):
        with open(script, "w") as f:
            f.write(TRAIN_SCRIPT)
    env = os.environ.copy()
    env.pop("XLA_FLAGS", None)
    env.pop(fault.ENV, None)
    env.update(extra_env)
    return subprocess.run(
        [sys.executable, script, ckpt_dir, out],
        capture_output=True, text=True, timeout=timeout, env=env)


def _load_blob(path):
    with np.load(path) as z:
        return {k: z[k] for k in z.files}


def _assert_blob_equal(got, want):
    assert sorted(got) == sorted(want)
    for key in want:
        np.testing.assert_array_equal(
            got[key], want[key], err_msg="%s differs after resume" % key)


@pytest.mark.slow
@pytest.mark.parametrize("device_feed", ["1", "0"])
def test_sigkill_crash_resume_bitwise_parity(tmp_path, device_feed):
    # the feed-on case also tears the newest checkpoint before resuming
    tear_newest = device_feed == "1"
    base_env = {
        "MXTPU_DEVICE_FEED": device_feed,
        ck.ENV_INTERVAL: "3",
    }
    ref_out = str(tmp_path / "ref.npz")
    proc = _run_train(str(tmp_path), str(tmp_path / "ref_ck"), ref_out,
                      base_env)
    assert proc.returncode == 0, proc.stderr
    assert "TRAIN-DONE" in proc.stdout

    # SIGKILL late in epoch 2 (step 15 of 16): several interval and
    # epoch-end checkpoints have been published by then, so the resume
    # always has something to restore from.
    crash_dir = str(tmp_path / "crash_ck")
    crash_env = dict(base_env, **{fault.ENV: "kill_at_step=15"})
    proc = _run_train(str(tmp_path), crash_dir,
                      str(tmp_path / "unused.npz"), crash_env)
    assert proc.returncode == -signal.SIGKILL
    assert ck.list_checkpoints(crash_dir), "no checkpoint survived the kill"

    if tear_newest:
        # tear the newest checkpoint: resume must fall back to the
        # previous valid one instead of crashing (acceptance criterion)
        mgr = ck.CheckpointManager(crash_dir)
        newest = ck.step_dir(crash_dir, ck.list_checkpoints(crash_dir)[-1])
        params = os.path.join(newest, ck.PARAMS_FILE)
        with open(params, "r+b") as f:
            f.truncate(os.path.getsize(params) // 2)
        fallback = mgr.latest_valid()
        assert fallback is not None and fallback != newest

    res_out = str(tmp_path / "res.npz")
    proc = _run_train(str(tmp_path), crash_dir, res_out, base_env)
    assert proc.returncode == 0, proc.stderr
    assert "resume: restored step" in proc.stderr
    if tear_newest:
        assert "skipping corrupt checkpoint" in proc.stderr

    _assert_blob_equal(_load_blob(res_out), _load_blob(ref_out))
