"""Elastic training tests (docs/robustness.md "Elastic resume").

Covers the four layers of the shrink-and-continue contract:

* fault points — ``replica_lost=<rank>@<step>`` / ``heartbeat_stall``
  parse, tombstone the run dir, and silence the victim's heartbeat;
* topology metadata — checkpoints record the writer's dp/mesh/batch
  geometry, and lost_nodes() votes only for ranks seen alive;
* cross-world resume — a dp=8 run SIGKILLed, resumed at dp=4, killed
  again, and finished back at dp=8 matches the uninterrupted run
  (global batch held constant; optimizer state proven 1/N per world);
* the driver loop — fit's elastic guard exits EXIT_RESHAPE on a lost
  peer and ``tools/watchdog.py`` supervise(elastic=True) restarts at
  the surviving world size, end to end without human intervention.
"""
import os
import re
import signal
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import resilience
from mxnet_tpu.parallel import heartbeat as hb
from mxnet_tpu.resilience import checkpoint as ck
from mxnet_tpu.resilience import fault

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FOUR_DEV = [mx.cpu(i) for i in range(4)]


# ---------------------------------------------------------------------------
# fault points: replica_lost / heartbeat_stall
# ---------------------------------------------------------------------------

def test_fault_spec_parses_rank_at_step(monkeypatch):
    monkeypatch.setenv(
        fault.ENV, "replica_lost=3@5,heartbeat_stall=1@7,kill_at_step=9,"
                   "bogus=x@y,junk=zz,uniq=%d" % os.getpid())
    _, spec = fault._spec()
    assert spec["replica_lost"] == (3, 5)
    assert spec["heartbeat_stall"] == (1, 7)
    assert spec["kill_at_step"] == 9
    assert "bogus" not in spec and "junk" not in spec


def test_replica_lost_tombstones_and_silences_heartbeat(
        tmp_path, monkeypatch):
    d = str(tmp_path)
    monkeypatch.setenv(hb.RUN_DIR_ENV, d)
    monkeypatch.setenv(fault.ENV,
                       "replica_lost=2@4,uniq=%d" % os.getpid())
    victim = hb.HeartbeatWriter(d, 2, interval=0.05).start()
    survivor = hb.HeartbeatWriter(d, 0, interval=0.05).start()
    try:
        for step in range(1, 4):
            fault.fire("step", step=step)
        assert hb.tombstoned(d) == set()  # not until step 4
        fault.fire("step", step=4)
        assert hb.tombstoned(d) == {2}
        # the victim's own writer must NOT resurrect the back-dated file
        time.sleep(0.25)
        assert hb.lost_nodes(d, 4, timeout=60.0) == [2]
        assert 0 not in hb.lost_nodes(d, 4, timeout=60.0)
    finally:
        victim.stop()
        survivor.stop()


def test_heartbeat_stall_freezes_progress_only(tmp_path, monkeypatch):
    d = str(tmp_path)
    monkeypatch.setenv(hb.RUN_DIR_ENV, d)
    monkeypatch.setenv(fault.ENV,
                       "heartbeat_stall=1@2,uniq=%d" % os.getpid())
    w = hb.HeartbeatWriter(d, 1, interval=0.05).start()
    try:
        fault.fire("step", step=1)
        fault.fire("step", step=2)
        time.sleep(0.25)
        w.progress()  # must be swallowed by the stall tombstone
        # alive (beating) but progress frozen: stalled, NOT lost
        assert hb.stalled_nodes(d, 2, timeout=0.2) == [1]
        assert hb.lost_nodes(d, 2, timeout=60.0) == []
    finally:
        w.stop()


def test_lost_nodes_ignores_never_started_ranks(tmp_path):
    d = str(tmp_path)
    # an empty run dir is a startup problem, not 8 lost replicas
    assert hb.lost_nodes(d, 8, timeout=0.0) == []
    hb.mark_lost(d, 5)
    assert hb.lost_nodes(d, 8, timeout=0.0) == [5]
    # a rank seen alive then gone silent DOES vote
    hb.HeartbeatWriter(d, 1, interval=60.0)._beat()
    os.utime(os.path.join(d, "hb_1"), (1.0, 1.0))
    assert hb.lost_nodes(d, 8, timeout=30.0) == [1, 5]


# ---------------------------------------------------------------------------
# topology metadata in the checkpoint manifest
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


def test_manifest_records_writer_topology(tmp_path, monkeypatch):
    monkeypatch.delenv(fault.ENV, raising=False)
    np.random.seed(0)
    mx.random.seed(0)
    mod = mx.mod.Module(_mlp(), context=FOUR_DEV)
    mod.fit(_blob_iter(), eval_metric=mx.metric.create("acc"),
            kvstore="device", optimizer="sgd",
            optimizer_params={"learning_rate": 0.1},
            initializer=mx.init.Uniform(0.1), num_epoch=1,
            checkpoint_dir=str(tmp_path))
    path = ck.CheckpointManager(str(tmp_path)).latest_valid()
    topo = ck.read_manifest(path).get("topology")
    assert topo == {"dp": 4, "mesh": {"dp": 4}, "global_batch": 8,
                    "per_replica_batch": 2}
    # and the inspection tool surfaces + preflights it
    from tools import ckpt_inspect
    lines, bad = ckpt_inspect.list_dir(str(tmp_path))
    assert bad == 0 and any("dp=4" in ln for ln in lines), lines
    warned, bad = ckpt_inspect.list_dir(str(tmp_path), expect_dp=2)
    assert bad == 0 and any("WARNING" in ln for ln in warned), warned


def test_opt_state_shard_info_reports_1_over_n(tmp_path, monkeypatch):
    monkeypatch.delenv(fault.ENV, raising=False)
    np.random.seed(0)
    mx.random.seed(0)
    mod = mx.mod.Module(_mlp(), context=FOUR_DEV)
    mod.fit(_blob_iter(), eval_metric=mx.metric.create("acc"),
            kvstore="device", optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            initializer=mx.init.Uniform(0.1), num_epoch=1)
    trainer = mod._fused_owner._fused_trainer
    assert trainer.flat_mode == "shard"
    total, resident = trainer.opt_state_shard_info(mod._fused_owner._fused_opt)
    assert total > 0
    assert resident * 4 == total  # exact: slabs are padded to dp multiples


# ---------------------------------------------------------------------------
# cross-world resume: dp=8 -> SIGKILL -> dp=4 -> SIGKILL -> dp=8
# ---------------------------------------------------------------------------

ELASTIC_SCRIPT = textwrap.dedent("""\
    import os, sys
    sys.path.insert(0, %(repo)r)
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    import logging
    logging.basicConfig(level=logging.INFO)
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import mesh as pmesh

    ckpt_dir, out = sys.argv[1], sys.argv[2]
    np.random.seed(0)
    mx.random.seed(0)

    BATCH = 16  # GLOBAL batch: constant across every world size
    world = pmesh.world_size(8) or 8
    dp = max(d for d in range(1, min(world, 8) + 1) if BATCH %% d == 0)
    print("ELASTIC-DP dp=%%d world=%%d" %% (dp, world), flush=True)

    rng = np.random.RandomState(42)
    X = rng.randn(128, 8).astype(np.float32)
    y = rng.randint(0, 4, 128).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=BATCH)  # 8 batches/epoch

    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")

    mod = mx.mod.Module(net, context=[mx.cpu(i) for i in range(dp)])
    metric = mx.metric.create("acc")

    printed = []
    def _report_shard(param):
        if printed:
            return
        printed.append(1)
        owner = mod._fused_owner
        total, resident = owner._fused_trainer.opt_state_shard_info(
            owner._fused_opt)
        print("OPT-SHARD total=%%d resident=%%d dp=%%d"
              %% (total, resident, dp), flush=True)

    mod.fit(it, eval_metric=metric, kvstore="device", optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            initializer=mx.init.Uniform(0.1), num_epoch=2,
            batch_end_callback=_report_shard,
            checkpoint_dir=ckpt_dir, resume="auto")
    assert mod._fused_trainer is not None

    arg, aux = mod.get_params()
    blob = {k: v.asnumpy() for k, v in arg.items()}
    blob.update({"aux:" + k: v.asnumpy() for k, v in aux.items()})
    blob["__metric__"] = np.asarray([metric.get()[1]], dtype=np.float64)
    np.savez(out, **blob)
    print("TRAIN-DONE", flush=True)
""") % {"repo": REPO}


def _run_elastic(script_dir, ckpt_dir, out, extra_env, timeout=300):
    script = os.path.join(script_dir, "train_elastic.py")
    if not os.path.exists(script):
        with open(script, "w") as f:
            f.write(ELASTIC_SCRIPT)
    env = os.environ.copy()
    env.pop("XLA_FLAGS", None)
    env.pop(fault.ENV, None)
    env.pop("MXTPU_WORLD_SIZE", None)
    env.pop("MXTPU_ELASTIC", None)
    env.update(extra_env)
    return subprocess.run(
        [sys.executable, script, ckpt_dir, out],
        capture_output=True, text=True, timeout=timeout, env=env)


def _shard_fraction(stdout, want_dp):
    m = re.search(r"OPT-SHARD total=(\d+) resident=(\d+) dp=(\d+)", stdout)
    assert m, stdout
    total, resident, dp = int(m.group(1)), int(m.group(2)), int(m.group(3))
    assert dp == want_dp, stdout
    assert total > 0 and resident * dp == total, (
        "optimizer state not 1/N: total=%d resident=%d dp=%d"
        % (total, resident, dp))


def _load_blob(path):
    with np.load(path) as z:
        return {k: z[k] for k in z.files}


@pytest.mark.slow
def test_cross_world_sigkill_resume_chain(tmp_path):
    """dp=8 SIGKILLed -> resume dp=4 -> SIGKILL -> finish dp=8: the
    chained run's final params/metric match the uninterrupted dp=8 run
    (same GLOBAL batch throughout, so the trajectory is the same modulo
    psum association — cross-dp is allclose, not bitwise), and the
    sharded optimizer state is exactly 1/N at every world size."""
    base = {ck.ENV_INTERVAL: "3"}
    ref_out = str(tmp_path / "ref.npz")
    proc = _run_elastic(str(tmp_path), str(tmp_path / "ref_ck"), ref_out,
                        dict(base, MXTPU_WORLD_SIZE="8"))
    assert proc.returncode == 0, proc.stderr
    _shard_fraction(proc.stdout, 8)

    chain = str(tmp_path / "chain_ck")
    # leg 1: dp=8, killed at step 7 (interval ckpts at 3 and 6 precede it)
    proc = _run_elastic(
        str(tmp_path), chain, str(tmp_path / "unused.npz"),
        dict(base, MXTPU_WORLD_SIZE="8",
             **{fault.ENV: "kill_at_step=7"}))
    assert proc.returncode == -signal.SIGKILL, proc.stderr
    assert ck.list_checkpoints(chain), "no checkpoint survived the kill"

    # leg 2: shrunken world (4 of 8 devices), killed again at step 13
    proc = _run_elastic(
        str(tmp_path), chain, str(tmp_path / "unused.npz"),
        dict(base, MXTPU_WORLD_SIZE="4",
             **{fault.ENV: "kill_at_step=13"}))
    assert proc.returncode == -signal.SIGKILL, proc.stderr
    assert "ELASTIC-DP dp=4 world=4" in proc.stdout
    assert "elastic resume" in proc.stderr, proc.stderr
    assert "resume: restored step" in proc.stderr
    _shard_fraction(proc.stdout, 4)

    # leg 3: grown back to dp=8, runs to completion
    res_out = str(tmp_path / "res.npz")
    proc = _run_elastic(str(tmp_path), chain, res_out,
                        dict(base, MXTPU_WORLD_SIZE="8"))
    assert proc.returncode == 0, proc.stderr
    assert "TRAIN-DONE" in proc.stdout
    assert "elastic resume" in proc.stderr, proc.stderr
    _shard_fraction(proc.stdout, 8)

    got, want = _load_blob(res_out), _load_blob(ref_out)
    assert sorted(got) == sorted(want)
    for key in want:
        np.testing.assert_allclose(
            got[key], want[key], rtol=1e-5, atol=1e-6,
            err_msg="%s diverged across the dp=8->4->8 chain" % key)


# ---------------------------------------------------------------------------
# the driver loop: lost peer -> EXIT_RESHAPE -> watchdog shrink -> done
# ---------------------------------------------------------------------------

def test_fit_elastic_guard_exits_reshape_on_lost_peer(
        tmp_path, monkeypatch):
    run_dir = tmp_path / "run"
    run_dir.mkdir()
    monkeypatch.setenv(hb.RUN_DIR_ENV, str(run_dir))
    monkeypatch.setenv("MXTPU_ELASTIC", "1")
    monkeypatch.setenv("MXTPU_WORLD_SIZE", "4")
    monkeypatch.setenv("MXTPU_ELASTIC_POLL", "0")
    monkeypatch.setenv(fault.ENV,
                       "replica_lost=3@5,uniq=%d" % os.getpid())
    np.random.seed(0)
    mx.random.seed(0)
    mod = mx.mod.Module(_mlp(), context=FOUR_DEV)
    ckpt_dir = str(tmp_path / "ck")
    with pytest.raises(SystemExit) as exc:
        mod.fit(_blob_iter(), eval_metric=mx.metric.create("acc"),
                kvstore="device", optimizer="sgd",
                optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
                initializer=mx.init.Uniform(0.1), num_epoch=1,
                checkpoint_dir=ckpt_dir)
    assert exc.value.code == resilience.EXIT_RESHAPE
    # drained + snapshotted at the boundary where the loss was detected
    assert 5 in ck.list_checkpoints(ckpt_dir)
    assert hb.tombstoned(str(run_dir)) == {3}


@pytest.mark.slow
def test_watchdog_elastic_shrink_and_continue(tmp_path, monkeypatch):
    """The full no-human-in-the-loop flow: fit detects the tombstoned
    peer (replica_lost fault), checkpoints, exits 76; watchdog shrinks
    MXTPU_WORLD_SIZE 8 -> 7 without burning the restart budget; the
    relaunched job picks dp=4 (largest divisor of the global batch
    within the surviving world), resumes cross-dp, and finishes."""
    from tools import watchdog

    script = os.path.join(str(tmp_path), "train_elastic.py")
    with open(script, "w") as f:
        f.write(ELASTIC_SCRIPT)
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    monkeypatch.setenv(fault.ENV, "replica_lost=3@5")
    monkeypatch.setenv(ck.ENV_INTERVAL, "3")
    monkeypatch.setenv("MXTPU_ELASTIC_POLL", "0")
    ckpt_dir = str(tmp_path / "ck")
    out = str(tmp_path / "out.npz")
    msgs = []
    rc = watchdog.supervise(
        [sys.executable, script, ckpt_dir, out],
        max_restarts=0, elastic=True, world=8,
        run_dir=str(tmp_path / "run"), poll_interval=0.2,
        log=msgs.append)
    joined = "\n".join(msgs)
    assert rc == 0, (rc, joined)
    assert "elastic shrink" in joined and "world 7" in joined, joined
    assert os.path.exists(out), joined
    # the relaunch really trained at the shrunken world and converged to
    # the same place an uninterrupted run does
    ref_out = str(tmp_path / "ref.npz")
    monkeypatch.delenv(fault.ENV)
    monkeypatch.delenv("MXTPU_ELASTIC_POLL")
    proc = _run_elastic(str(tmp_path), str(tmp_path / "ref_ck"), ref_out,
                        {ck.ENV_INTERVAL: "3", "MXTPU_WORLD_SIZE": "8"})
    assert proc.returncode == 0, proc.stderr
    got, want = _load_blob(out), _load_blob(ref_out)
    for key in want:
        np.testing.assert_allclose(
            got[key], want[key], rtol=1e-5, atol=1e-6,
            err_msg="%s diverged across shrink-and-continue" % key)
