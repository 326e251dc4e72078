"""Shared by the tests that start ``bench/run.py`` as a subprocess."""
import json
import os
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_bench(args, run_py=None, timeout=900):
    env = dict(os.environ, PYTHONPATH=ROOT, TF_CPP_MIN_LOG_LEVEL="3")
    return subprocess.run(
        [sys.executable, run_py or os.path.join(BENCH, "run.py")] + args,
        env=env, capture_output=True, text=True, timeout=timeout)


def check_rehearsal(proc, metric_names):
    """A rehearsal ends with a result object of the contract's shape and
    without a single metric value, and every line says platform=cpu."""
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    for line in lines[:-1]:
        assert line.startswith("platform=cpu "), line
    result = json.loads(lines[-1])
    assert RESULT_KEYS <= set(result), result
    assert result["rehearsal"] is True
    assert result["correct"] is True, proc.stdout[-3000:]
    assert result["device"]["platform"] == "cpu"
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(metric_names) <= set(result["metrics"]), result["metrics"]
    for name, m in result["metrics"].items():
        assert set(m) == {"unit"}, (name, m)
    return result


PARENT_READERS = os.path.join("tests", "parent_readers")


def parent_reader(name):
    """A reader as the tree before PR 68 had it (``parent_readers/``);
    its setup readers import that tree's ``setup_phases`` as
    ``parent_setup_phases``, found beside them."""
    import lib
    beside = os.path.join(BENCH, PARENT_READERS)
    if beside not in sys.path:
        sys.path.append(beside)
    return lib.load_module(PARENT_READERS, name)


def folded():
    """old reader -> {kept, how, cells} (``parent_readers/folded.json``)."""
    import lib
    return lib.load_json(lib.find(PARENT_READERS, "folded", ".json"))[
        "readers"]
