"""bench/tests run on the CPU: ``JAX_PLATFORMS=cpu python -m pytest
bench/tests -q``. No test here describes a TPU topology."""
import atexit
import os
import shutil
import sys
import tempfile

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# a compile cache of the session's own, inherited by the rehearsal
# subprocesses (tests/conftest.py says why XLA:CPU entries must not
# outlive the session)
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _cache = tempfile.mkdtemp(prefix="bench-test-jax-cache-")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache
    atexit.register(shutil.rmtree, _cache, ignore_errors=True)
