"""A ``--rehearse-cpu`` run of every cell of the manifest ends with a
result object of the contract's shape and without a metric value, and
the command has no CPU branch outside the rehearsal."""
import pytest

import lib
from helpers import check_rehearsal, run_bench

MANIFEST = lib.load_json(lib.MANIFEST)
CELLS = [w["name"] for w in MANIFEST["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_rehearse_end_to_end(workload):
    proc = run_bench(["--workload", workload, "--seed", "3", "--seconds",
                      "1", "--trace", "0", "--rehearse-cpu"])
    check_rehearsal(proc, ["train_samples_s", "setup_s"])


@pytest.mark.parametrize("workload", ["resnet50_fit_resident",
                                      "resnet50_fit_dp4"])
def test_rehearse_traced(workload):
    proc = run_bench(["--workload", workload, "--seed", "4", "--seconds",
                      "1", "--trace", "1", "--rehearse-cpu"])
    result = check_rehearsal(proc, ["fused_step_share", "compile_s",
                                    "fit_lookahead_share"])
    # the CPU has no device plane: nothing read from a trace is reported
    assert "device_idle_share" not in result["metrics"]
    assert "train_samples_s" not in result["metrics"]
    assert "fused_step_share ok=True" in proc.stdout
    assert "window_compiles=0" in proc.stdout


def test_no_chip_no_result():
    proc = run_bench(["--workload", CELLS[0], "--seed", "0", "--seconds",
                      "1", "--trace", "0"])
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""
    assert "no CPU branch" in proc.stderr


def test_unknown_workload_is_refused():
    proc = run_bench(["--workload", "no_such_cell", "--rehearse-cpu"])
    assert proc.returncode == 2 and proc.stdout.strip() == ""
