"""Core shared definitions for the TPU-native framework.

Capability parity target: pre-Gluon MXNet 0.9.5 (`/root/reference`). The
reference routes every frontend through a C ABI (`include/mxnet/c_api.h`);
here the "ABI" is this Python package itself — JAX is the device runtime, so
the ctypes/handle layer of the reference (`python/mxnet/base.py`) collapses
into plain Python objects.
"""
from __future__ import annotations

import ast
import os
import numpy as np

__version__ = "0.9.5"


class MXNetError(RuntimeError):
    """Error raised by the framework (parity: reference ``base.py:MXNetError``)."""


# ---------------------------------------------------------------------------
# dtype registry
#
# Parity with mshadow's TypeFlag enum (reference include/mxnet/base.h +
# mshadow dtype switch macros); the integer codes match the reference so
# serialized params / graph JSON agree.
# ---------------------------------------------------------------------------
_DTYPE_NP_TO_MX = {
    np.float32: 0,
    np.float64: 1,
    np.float16: 2,
    np.uint8: 3,
    np.int32: 4,
    np.int8: 5,
    np.int64: 6,
}
# TPU-native extension: bfloat16 is the MXU's preferred dtype.
try:  # ml_dtypes ships with jax
    import ml_dtypes

    _DTYPE_NP_TO_MX[ml_dtypes.bfloat16] = 12
    bfloat16 = ml_dtypes.bfloat16
except ImportError:  # pragma: no cover
    bfloat16 = None

_DTYPE_MX_TO_NP = {v: k for k, v in _DTYPE_NP_TO_MX.items()}

_DTYPE_NAMES = {
    "float32": np.float32,
    "float64": np.float64,
    "float16": np.float16,
    "uint8": np.uint8,
    "int32": np.int32,
    "int8": np.int8,
    "int64": np.int64,
}
if bfloat16 is not None:
    _DTYPE_NAMES["bfloat16"] = bfloat16


def np_dtype(dtype):
    """Normalize any dtype spec (np dtype, type, string, mx code) to a numpy type."""
    if dtype is None:
        return np.float32
    if isinstance(dtype, (int, np.integer)) and not isinstance(dtype, bool):
        return _DTYPE_MX_TO_NP[int(dtype)]
    if isinstance(dtype, str):
        if dtype not in _DTYPE_NAMES:
            raise MXNetError("unknown dtype name %s" % dtype)
        return _DTYPE_NAMES[dtype]
    d = np.dtype(dtype)
    for k in _DTYPE_NP_TO_MX:
        if np.dtype(k) == d:
            return k
    raise MXNetError("unsupported dtype %s" % dtype)


def dtype_name(dtype) -> str:
    return np.dtype(np_dtype(dtype)).name


def mx_dtype_code(dtype) -> int:
    return _DTYPE_NP_TO_MX[np_dtype(dtype)]


# ---------------------------------------------------------------------------
# attribute-string parsing
#
# The reference parses operator params from strings via dmlc::Parameter
# (every ``*-inl.h`` has DMLC_DECLARE_PARAMETER). We keep the
# everything-is-a-string wire format for Symbol attrs / graph JSON parity and
# normalize here.
# ---------------------------------------------------------------------------
def parse_attr_value(value):
    """Parse a string attr ('(2,2)', 'True', '0.9', 'relu') into a Python value."""
    if not isinstance(value, str):
        return value
    s = value.strip()
    if s in ("True", "true"):
        return True
    if s in ("False", "false"):
        return False
    if s in ("None", "null"):
        return None
    try:
        return ast.literal_eval(s)
    except (ValueError, SyntaxError):
        return s


def attr_repr(value) -> str:
    """Inverse of :func:`parse_attr_value` — stringify for graph JSON."""
    if isinstance(value, bool):
        return "True" if value else "False"
    if value is None:
        return "None"
    if isinstance(value, (list, tuple)):
        if len(value) == 1:  # "(100,)" — "(100)" would parse back as int
            return "(" + attr_repr(value[0]) + ",)"
        return "(" + ", ".join(attr_repr(v) for v in value) + ")"
    return str(value)


def get_env(name, default, typ=None):
    """Runtime knob lookup (parity: dmlc::GetEnv; knobs documented in
    reference docs/how_to/env_var.md). Same env-var names are honored where
    the knob still makes sense on TPU."""
    v = os.environ.get(name)
    if v is None:
        return default
    if typ is bool or isinstance(default, bool):
        return v not in ("0", "false", "False", "")
    if typ is int or isinstance(default, int):
        return int(v)
    if typ is float or isinstance(default, float):
        return float(v)
    return v


_DEFAULT_BUCKET_BYTES = 4 * 1024 * 1024


def bucket_bytes_env():
    """MXTPU_BUCKET_BYTES: size cap for coalesced gradient buckets,
    shared by the kvstore GradBucketer and the fused flat-update plan
    (docs/env_vars.md). Missing/empty/garbage → 4 MiB default; negative
    clamps to 0 (0 disables coalescing: one collective per key and the
    legacy per-param fused update)."""
    raw = os.environ.get("MXTPU_BUCKET_BYTES")
    if raw is None or raw == "":
        return _DEFAULT_BUCKET_BYTES
    try:
        return max(0, int(raw))
    except ValueError:
        return _DEFAULT_BUCKET_BYTES


def compile_cache_dir():
    """Turn on JAX's persistent compilation cache and return where it
    lives. ``JAX_COMPILATION_CACHE_DIR`` places it from outside: when
    that is set JAX has already read it and no directory is set in
    code. Otherwise the cache sits at ``<checkout>/.jax_cache``,
    resolved from this package's own path — the path is part of the
    cache key, so it must not move between runs. The admission
    thresholds drop to 0 because our programs are many small jit bodies
    (per-key ops, fused steps) that the default 1s/too-small gates
    would mostly skip."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


compile_cache_dir()
