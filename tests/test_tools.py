"""Tools parity tests: im2rec packer (reference tools/im2rec.*),
launch.py env contract (tools/launch.py + dmlc tracker), and the
allreduce bandwidth measure (tools/bandwidth/measure.py)."""
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx

TOOLS = os.path.join(os.path.dirname(__file__), "..", "tools")
sys.path.insert(0, TOOLS)


def _write_images(root, n_per_class=3, classes=("cat", "dog")):
    from PIL import Image

    rng = np.random.RandomState(0)
    for cls in classes:
        d = os.path.join(root, cls)
        os.makedirs(d, exist_ok=True)
        for i in range(n_per_class):
            arr = (rng.rand(24, 32, 3) * 255).astype(np.uint8)
            Image.fromarray(arr).save(os.path.join(d, "img%d.jpg" % i))


def test_im2rec_list_pack_and_iterate(tmp_path):
    import im2rec

    root = str(tmp_path / "imgs")
    _write_images(root)
    prefix = str(tmp_path / "data")
    out, classes = im2rec.make_list(prefix, root)
    assert len(classes) == 2
    lines = open(out).read().strip().splitlines()
    assert len(lines) == 6

    n = im2rec.pack(prefix, root, num_workers=1, resize=0)
    assert n == 6
    assert os.path.exists(prefix + ".rec") and os.path.exists(prefix + ".idx")

    # records round-trip through the recordio reader
    reader = mx.recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec",
                                           "r")
    assert len(reader.keys) == 6
    header, img = mx.recordio.unpack_img(reader.read_idx(reader.keys[0]))
    assert img.shape == (24, 32, 3)
    reader.close()

    # and feed training through the ImageRecordIter surface
    it = mx.io.ImageRecordIter(path_imgrec=prefix + ".rec",
                               data_shape=(3, 24, 24), batch_size=2,
                               rand_crop=True, shuffle=False)
    batch = next(iter(it))
    assert batch.data[0].shape == (2, 3, 24, 24)
    assert batch.label[0].shape == (2,)


def test_launch_local_env_contract(tmp_path):
    import launch

    env = launch.worker_env(2, 4, "127.0.0.1:29500")
    assert env["JAX_PROCESS_ID"] == "2"
    assert env["DMLC_RANK"] == "2"
    assert env["DMLC_NUM_WORKER"] == "4"
    assert env["DMLC_ROLE"] == "worker"

    script = tmp_path / "worker.py"
    script.write_text(
        "import os, sys\n"
        "sys.exit(0 if os.environ['DMLC_RANK'] in '0123' and "
        "os.environ['DMLC_NUM_WORKER'] == '2' else 1)\n")
    rc = launch.launch_local(2, [sys.executable, str(script)])
    assert rc == 0


def test_bandwidth_measure_runs():
    sys.path.insert(0, os.path.join(TOOLS, "bandwidth"))
    import measure

    results = measure.measure(sizes_mb=(0.25,), iters=2)
    assert results[0]["devices"] >= 1
    assert results[0]["busbw_GBps"] >= 0.0


def test_bandwidth_kvstore_mode():
    """Reference-parity mode (tools/bandwidth/measure.py --network):
    real per-layer model gradients through the product KVStore, merged
    result must match the numpy oracle exactly (error == 0), both with
    and without the optimizer applied on the store."""
    sys.path.insert(0, os.path.join(TOOLS, "bandwidth"))
    import measure

    rows = measure.measure_kvstore(
        network="mlp", ndev=3, kv_store="local", num_batches=2,
        image_shape="1,28,28", num_classes=10)
    assert len(rows) == 2
    # Tolerance (not exact zero): a pairwise/tree device reduction is a
    # legitimate KVStore implementation and reorders the float sums.
    assert all(r["error"] < 1e-6 for r in rows)
    rows = measure.measure_kvstore(
        network="mlp", ndev=2, kv_store="device", num_batches=2,
        image_shape="1,28,28", num_classes=10, optimizer="sgd")
    assert all(r["error"] < 1e-6 for r in rows)


def test_op_docs_fresh():
    """docs/op_docs.md must match the live registry (tools/gen_op_docs.py
    --check is the CI freshness hook; SURVEY §5.6 docgen surface)."""
    r = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "gen_op_docs.py"),
         "--check"],
        capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, r.stdout + r.stderr


def test_lowered_step_prints_a_cell_by_what_the_compilers_read(tmp_path):
    """tools/lowered_step.py on a cell's rehearsal sizes: the step lowered
    for the TPU here, the one Mosaic body decoded, no location left in the
    text, and the same line from a second run (what two trees are compared
    by)."""
    import gzip
    import json

    def line(*more):
        r = subprocess.run(
            [sys.executable, os.path.join(TOOLS, "lowered_step.py"),
             "olmo_hybrid_fit_stage_4k", "--tiny", *more],
            capture_output=True, text=True, timeout=240)
        assert r.returncode == 0, r.stdout + r.stderr
        return json.loads(r.stdout.splitlines()[-1])

    first = line("--out", str(tmp_path))
    assert first["cell"] == "olmo_hybrid_fit_stage_4k.tiny"
    assert first["mosaic_bodies"] == 1 and len(first["kernels"]) == 1
    assert first["blocks"] == ["_gated_delta_block"]
    with gzip.open(tmp_path / "olmo_hybrid_fit_stage_4k.mlir.gz", "rt") as f:
        text = f.read()
    assert "mosaic:" in text and "loc(" not in text and "TUzvUg" not in text
    assert line() == first


def test_launch_tracker_modes_dry_run(tmp_path, capsys, monkeypatch):
    """mpi/sge/yarn trackers (reference dmlc tracker parity): --dry-run
    emits a submission command wrapping the rank shim; the shim itself
    must map every scheduler's rank variable onto the JAX/DMLC env
    contract and exec the command."""
    import launch

    # mpi/sge/yarn all write the shim into cwd: remote tasks see the
    # submit dir via the shared filesystem, never this node's /tmp
    # (ADVICE r5 — the mpi shim used to land in /tmp and broke
    # multi-node runs with file-not-found)
    monkeypatch.chdir(tmp_path)

    for mode, fn, kw in (
            ("mpi", launch.launch_mpi, {}),
            ("sge", launch.launch_sge, {"queue": "batch.q"}),
            ("yarn", launch.launch_yarn, {})):
        rc = fn(3, ["python", "train.py"], dry_run=True, **kw)
        assert rc == 0, mode
        out = capsys.readouterr().out
        shim = next(tok for tok in out.split()
                    if "mxtpu_launch_" in tok).rstrip("'\"")
        shim = shim.split("=")[-1]
        assert os.path.dirname(os.path.abspath(shim)) == str(tmp_path), mode
        body = open(shim).read()
        assert "JAX_NUM_PROCESSES=\"3\"" in body, mode
        assert "DMLC_NUM_WORKER=\"3\"" in body, mode
        assert "exec python train.py" in body, mode
        if mode == "sge":
            assert "-t 1-3" in out
            assert "-q batch.q" in out
        if mode == "yarn":
            assert "-num_containers 3" in out

    # the shim's rank mapping, executed for real under each scheduler's
    # env convention (mpi OMPI var; sge task id is 1-based)
    echo = tmp_path / "echo_rank.sh"
    echo.write_text("#!/bin/sh\necho rank=$DMLC_RANK\n")
    echo.chmod(0o755)
    shim = launch._write_rank_shim(4, "127.0.0.1:29500",
                                   ["sh", str(echo)])
    for envvar, value, want in (("OMPI_COMM_WORLD_RANK", "2", "rank=2"),
                                ("SGE_TASK_ID", "3", "rank=2")):
        env = {k: v for k, v in os.environ.items()
               if k not in ("OMPI_COMM_WORLD_RANK", "SGE_TASK_ID")}
        env[envvar] = value
        r = subprocess.run(["sh", shim], capture_output=True, text=True,
                           env=env, timeout=30)
        assert r.stdout.strip() == want, (envvar, r.stdout, r.stderr)


def test_ckpt_inspect_cli_self_test():
    repo = os.path.join(os.path.dirname(__file__), "..")
    res = subprocess.run(
        [sys.executable, "-m", "tools.ckpt_inspect", "--self-test"],
        cwd=repo, capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr
    assert "self-test passed" in res.stdout


def test_watchdog_cli_self_test():
    """Elastic restart decision table + stub-job supervision end to end
    (dead rank -> shrink, exit-75 -> same-size retry, exhausted budget
    -> fail)."""
    repo = os.path.join(os.path.dirname(__file__), "..")
    res = subprocess.run(
        [sys.executable, "-m", "tools.watchdog", "--self-test"],
        cwd=repo, capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr
    assert "self-test passed" in res.stdout


def test_watchdog_decision_table_rows():
    from tools import watchdog

    # the three ISSUE rows, pinned here as well as in --self-test
    assert watchdog.decide(
        watchdog.EXIT_RESHAPE, [3], 0, 2, 8, True) == ("shrink", 7)
    assert watchdog.decide(
        watchdog.EXIT_PREEMPTED, [], 0, 2, 8, True) == ("retry", 8)
    assert watchdog.decide(1, [], 2, 2, 8, True) == ("fail", 8)
    # shrink is budget-free; elastic off never shrinks
    assert watchdog.decide(
        watchdog.EXIT_RESHAPE, [3], 2, 2, 8, True) == ("shrink", 7)
    assert watchdog.decide(
        watchdog.EXIT_RESHAPE, [3], 2, 2, 8, False) == ("fail", 8)


def test_fleet_top_cli_self_test():
    """Synthetic 3-rank run dir -> straggler table + Prometheus format
    checker (accepts merged registry output, rejects malformed text)."""
    repo = os.path.join(os.path.dirname(__file__), "..")
    res = subprocess.run(
        [sys.executable, "-m", "tools.fleet_top", "--self-test"],
        cwd=repo, capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr
    assert "self-test passed" in res.stdout


def test_fleet_top_prometheus_checker():
    from tools import fleet_top

    good = ("# HELP h help text\n"
            "# TYPE h histogram\n"
            'h_bucket{le="1"} 2\nh_bucket{le="+Inf"} 5\n'
            "h_sum 7.5\nh_count 5\n"
            "# TYPE g gauge\n"
            'g{rank="0"} 1.25e-3\n')
    assert fleet_top.check_prometheus_text(good) == []
    # malformed sample line
    assert fleet_top.check_prometheus_text('metric{le="x} 1\n')
    # non-cumulative buckets
    bad = ("# TYPE h histogram\n"
           'h_bucket{le="1"} 5\nh_bucket{le="+Inf"} 3\nh_count 3\n')
    assert fleet_top.check_prometheus_text(bad)
    # +Inf bucket must be present and equal _count
    bad = ("# TYPE h histogram\n"
           'h_bucket{le="1"} 3\nh_count 3\n')
    assert fleet_top.check_prometheus_text(bad)


def test_perf_doctor_cli_self_test():
    repo = os.path.join(os.path.dirname(__file__), "..")
    res = subprocess.run(
        [sys.executable, "-m", "tools.perf_doctor", "--self-test"],
        cwd=repo, capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr
    assert "self-test passed" in res.stdout


def test_ckpt_inspect_cli_on_real_checkpoints(tmp_path, capsys):
    from mxnet_tpu.resilience import checkpoint as ck
    from tools import ckpt_inspect

    mgr = ck.CheckpointManager(str(tmp_path), keep=5)
    state = {
        "module": {"arg": {"w": np.eye(3, dtype=np.float32)},
                   "aux": {}, "opt": {"kind": "none"}},
        "epoch": 0, "nbatch": 4, "global_step": 4,
        "metric": None, "rng": {},
    }
    mgr.save(state, 4)

    assert ckpt_inspect.main([str(tmp_path), "--verify"]) == 0
    assert "OK (deep)" in capsys.readouterr().out

    assert ckpt_inspect.main([str(tmp_path), "--state", "latest"]) == 0
    out = capsys.readouterr().out
    assert "global_step: 4" in out
    assert "arg:w" in out

    # a torn member must flip both the listing and the exit code
    params = os.path.join(ck.step_dir(str(tmp_path), 4), ck.PARAMS_FILE)
    with open(params, "r+b") as f:
        f.truncate(8)
    assert ckpt_inspect.main([str(tmp_path)]) == 1
    assert "CORRUPT" in capsys.readouterr().out
