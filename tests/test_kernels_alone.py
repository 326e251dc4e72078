"""benchmarks/: each script that times a kernel alone on the chip keeps
the platform rule of its harness (benchmarks/alone.py). Here, where there
is no TPU, ``--rehearse-cpu`` runs a script's whole flow at its toy size:
it exits 0, every row it prints is JSON and says ``platform`` ``cpu``,
holds no share of a bound (there is no peak without the device) and no
file is written; without the flag a script exits 2 and has no CPU branch.
"""
import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# a key under which a row holds a bound or a share of one
_OF_A_PEAK = re.compile(
    r"share_of|roofline|bound_|_bound|mxu|required_ms|write_ms|gbs")
SCRIPTS = ("channel_delta_rule", "embedding_grad", "flash_attention",
           "flash_select", "flash_window_tiles", "gated_delta_rule",
           "grouped_matmul", "hyper_mix", "keep_top_k", "latent_flash",
           "moe_permute", "rope", "ssd_scan")


@pytest.mark.timeout(60)
@pytest.mark.parametrize(
    "script,rehearse", [(s, True) for s in SCRIPTS] + [(SCRIPTS[4], False)],
    ids=list(SCRIPTS) + ["off_the_chip_exits_2"])
def test_script_keeps_the_platform_rule(script, rehearse, tmp_path):
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", script + ".py")]
        + ["--rehearse-cpu"] * rehearse,
        capture_output=True, text=True, timeout=55, cwd=tmp_path,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert not os.listdir(tmp_path)
    if not rehearse:
        assert r.returncode == 2, r.stdout + r.stderr[-2000:]
        assert not r.stdout and "no CPU branch" in r.stderr
        return
    assert r.returncode == 0, r.stdout + r.stderr[-2000:]
    rows = [json.loads(line) for line in r.stdout.splitlines()]
    assert rows
    for row in rows:
        assert row["platform"] == "cpu", row
        assert not [k for k, v in row.items()
                    if _OF_A_PEAK.search(k) and v is not None], row


def test_the_scripts_are_the_directory():
    found = sorted(f[:-3] for f in os.listdir(os.path.join(REPO, "benchmarks"))
                   if f.endswith(".py"))
    assert found == sorted(SCRIPTS + ("alone",))
