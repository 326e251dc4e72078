"""base.compile_cache_dir: where JAX's persistent compilation cache lives.

The helper runs at import. ``JAX_COMPILATION_CACHE_DIR`` places the
cache from outside and is left untouched; without it the cache sits at
``<checkout>/.jax_cache``, resolved from the package's own path. The
admission thresholds are 0 either way (our programs are many small jit
bodies). Verified in subprocesses because jax reads the variable at
import and the cache must be placed before any compilation.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = r"""
import json, os
import mxnet_tpu  # places the cache at import
import jax, jax.numpy as jnp
from mxnet_tpu import base

helper_dir = base.compile_cache_dir()
out = {"helper_dir": helper_dir,
       "cfg_dir": jax.config.jax_compilation_cache_dir,
       "min_secs": jax.config.jax_persistent_cache_min_compile_time_secs,
       "min_bytes": jax.config.jax_persistent_cache_min_entry_size_bytes}
if os.environ.get("PROBE_COMPILE") == "1":
    jax.jit(lambda x: x * 2.0 + 1.0)(
        jnp.arange(8, dtype=jnp.float32)).block_until_ready()
    out["entries"] = sorted(
        f for _, _, files in os.walk(helper_dir) for f in files)
print(json.dumps(out))
"""


def _run_probe(env_updates, cwd):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # single device is fine here
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    for k, v in env_updates.items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    proc = subprocess.run(
        [sys.executable, "-c", _PROBE], env=env, cwd=cwd,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _checkout_cache_entries():
    default = os.path.join(REPO, ".jax_cache")
    return sorted(os.listdir(default)) if os.path.isdir(default) else None


def test_env_var_places_the_cache_untouched(tmp_path):
    cache = tmp_path / "xla_cache"
    cache.mkdir()
    before = _checkout_cache_entries()
    res = _run_probe({"JAX_COMPILATION_CACHE_DIR": str(cache),
                      "PROBE_COMPILE": "1"}, cwd=str(tmp_path))
    assert res["helper_dir"] == str(cache)
    assert res["cfg_dir"] == str(cache)
    assert res["min_secs"] == 0 and res["min_bytes"] == 0
    # with the variable set nothing is written under the checkout default
    assert _checkout_cache_entries() == before
    if not res["entries"]:  # some jax builds can't cache CPU executables
        pytest.skip("jax persistent cache wrote no CPU entries here")


def test_default_is_checkout_jax_cache(tmp_path):
    # resolved from the package path, not the working directory; no
    # compile here, so the checkout's directory is never written
    res = _run_probe({"JAX_COMPILATION_CACHE_DIR": None},
                     cwd=str(tmp_path))
    want = os.path.join(REPO, ".jax_cache")
    assert res["helper_dir"] == want
    assert res["cfg_dir"] == want
    assert res["min_secs"] == 0 and res["min_bytes"] == 0
