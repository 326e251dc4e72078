"""The data-driven claim, proved: a configuration, a cell, a traffic mix
and a per-layer metric added as FILES ONLY (and their manifest entries)
are found and run by the harness. The tree is a copy of bench/ in a
temporary directory, so no file that exists is edited. The two cells
parked for a later benchmark PR (tests/serve_cell/, tests/hostfeed_cell/;
PERF.md, Open questions) are added the same way, which is also the
rehearsal of the ``serve_open`` traffic kind and of the ``host`` feed."""
import json
import os
import shutil

import pytest

import lib
from helpers import check_rehearsal, run_bench

HERE = os.path.dirname(os.path.abspath(__file__))


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        if isinstance(obj, str):
            f.write(obj)
        else:
            json.dump(obj, f)


@pytest.fixture()
def tree(tmp_path):
    """A checkout-shaped copy: BENCHMARK.json beside bench/."""
    bench = tmp_path / "bench"
    shutil.copytree(lib.BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "fixtures"))
    return tmp_path, str(bench), lib.load_json(lib.MANIFEST)


def test_throwaway_config_cell_mix_and_metric_as_files_only(tree):
    root, bench, manifest = tree
    cfg = lib.load_json(lib.find("configs", "resnet50", ".json"))
    cfg["kwargs"].update(num_layers=18, num_classes=10,
                         image_shape="3,32,32")
    cfg.update(input_shape=[3, 32, 32], num_classes=10)
    _write(bench + "/configs/throwaway_net.json", cfg)
    mix = lib.load_json(lib.find("traffic", "fit_resident_b256", ".json"))
    mix.update(batch=4, warmup_steps=2, trace_steps=2)
    _write(bench + "/traffic/fit_resident_b4.json", mix)
    _write(bench + "/cells/throwaway_fit.json", {
        "config": "throwaway_net", "chips": 1, "traffic": "fit_resident_b4",
        "why": "proof", "who": "a test",
        "expect": {"first_loss_tol": 2.0, "loss_fall_min": -100.0}})
    _write(bench + "/tests/rehearsal/throwaway_fit.json", {})
    _write(bench + "/layer_metrics/throwaway_steps.py",
           '"""Steps the window counted."""\n\n\n'
           'def compute(trace, counters, run):\n    return run["steps"]\n')
    manifest["configs"].append({
        "name": "throwaway_net", "source": cfg["source"],
        "file": "bench/configs/throwaway_net.json", "reduced": [],
        "why": "proof"})
    manifest["workloads"].append({
        "name": "throwaway_fit", "config": "throwaway_net",
        "traffic": "fit_resident_b4", "chips": 1, "why": "proof"})
    manifest["per_layer"].append({
        "name": "throwaway_steps", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "fit loop",
        "moves": "train_samples_s", "workloads": ["throwaway_fit"]})
    _write(str(root / "BENCHMARK.json"), manifest)
    proc = run_bench(["--workload", "throwaway_fit", "--seed", "5",
                      "--seconds", "1", "--trace", "1", "--rehearse-cpu"],
                     run_py=bench + "/run.py")
    result = check_rehearsal(proc, ["throwaway_steps", "fused_step_share"])
    assert result["metrics"]["throwaway_steps"] == {"unit": "steps"}


def _unpark(parked, bench, manifest):
    """Lay a parked cell's files into the tree and its entries into the
    manifest."""
    shutil.copytree(os.path.join(HERE, parked), bench, dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("manifest_fragment.json",
                                                  "rehearsal"))
    shutil.copytree(os.path.join(HERE, parked, "rehearsal"),
                    bench + "/tests/rehearsal", dirs_exist_ok=True)
    fragment = lib.load_json(os.path.join(
        HERE, parked, "manifest_fragment.json"))
    for section, entries in fragment.items():
        manifest[section] += entries


def test_hostfeed_cell_added_as_files_only(tree):
    root, bench, manifest = tree
    _unpark("hostfeed_cell", bench, manifest)
    _write(str(root / "BENCHMARK.json"), manifest)
    proc = run_bench(["--workload", "resnet50_fit_hostfeed", "--seed", "7",
                      "--seconds", "1", "--trace", "1", "--rehearse-cpu"],
                     run_py=bench + "/run.py")
    check_rehearsal(proc, ["input_wait_share", "fit_lookahead_share",
                           "fused_step_share"])


@pytest.mark.parametrize("trace", ["0", "1"])
def test_serve_cell_added_as_files_only(tree, trace):
    root, bench, manifest = tree
    _unpark("serve_cell", bench, manifest)
    fit_cells = [w["name"] for w in manifest["workloads"]
                 if w["name"] != "inception_v3_serve_open"]
    for m in manifest["end_to_end"]:
        if m["name"] == "train_samples_s":  # no longer in every cell
            m["workloads"] = fit_cells
    # the ``setup_*`` entries carry no list (PR 68) because every cell is a
    # ``Module.fit`` program, whose spans they read; the ``benchmark`` PR
    # that admits a cell of another kind gives them the fit cells' list,
    # as it gives ``train_samples_s`` one
    for m in manifest["per_layer"]:
        if m["name"].startswith("setup_"):
            assert "workloads" not in m
            m["workloads"] = fit_cells
    _write(str(root / "BENCHMARK.json"), manifest)
    proc = run_bench(["--workload", "inception_v3_serve_open", "--seed", "6",
                      "--seconds", "1", "--trace", trace, "--rehearse-cpu"],
                     run_py=bench + "/run.py")
    want = (["serve_p50_ms", "serve_p99_ms", "setup_s"] if trace == "0" else
            ["serve_queue_wait_ms", "serve_rows_per_batch",
             "gen_late_ms_p99", "compile_s"])
    result = check_rehearsal(proc, want)
    assert "train_samples_s" not in result["metrics"]
    assert "engine_rows_equal_solo ok=True" in proc.stdout
    assert "solo_equals_f32_highest ok=True" in proc.stdout
    assert "gen_late_ms_p99=" in proc.stdout
