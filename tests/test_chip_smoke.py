"""chip_smoke.py off the chip: it must refuse, quickly and by name.

The smoke itself only means something on a TPU (it is run there through
the chip tool); what the CPU suite pins is the other half of its
contract — without an accelerator it exits non-zero, prints no result
object, names the platform it found, and never falls back to the CPU.
"""
import os
import shutil
import subprocess
import sys

import pytest

import mxnet_tpu as mx

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(script, cwd):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, script], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120)


@pytest.mark.timeout(150)
def test_refuses_without_a_tpu_and_names_the_platform():
    r = _run(SMOKE, REPO)
    assert r.returncode != 0
    assert "platform=cpu" in r.stderr and "not a TPU" in r.stderr
    assert r.stdout.strip() == ""  # no result object, nothing to misread


@pytest.mark.timeout(150)
def test_refuses_alone_in_an_empty_directory(tmp_path):
    alone = shutil.copy(SMOKE, str(tmp_path))
    r = _run(alone, str(tmp_path))
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_no_cpu_branch_outside_the_rehearsal_flag():
    src = open(SMOKE).read()
    # the only place the script may name a host context is the
    # rehearsal's ctx() helper; it never pins a platform itself
    assert src.count("mx.cpu(") == 1
    assert "jax_platforms" not in src and "JAX_PLATFORMS" not in src


def test_tpu_context_raises_without_the_chip():
    """mx.tpu()/mx.gpu() used to hand back CPU devices on a host with no
    accelerator, which is how a CPU run got filed as a v5e result."""
    for ctx in (mx.tpu(0), mx.gpu(0)):
        with pytest.raises(mx.MXNetError, match="platforms present.*cpu"):
            ctx.jax_device
    assert mx.cpu(0).jax_device.platform == "cpu"
