"""Standalone inference — ``c_predict_api`` parity + the serving AOT pool.

Parity: reference ``src/c_api/c_predict_api.cc`` /
``include/mxnet/c_predict_api.h:59-140`` (SURVEY.md §3.6): a
self-contained predictor ABI — ``MXPredCreate(symbol_json, param_bytes,
dev, input_shapes)`` → ``MXPredSetInput`` → ``MXPredForward`` →
``MXPredGetOutput`` — that the amalgamation ships to mobile/JS.

TPU-native: ``Predictor`` AOT-compiles the whole inference graph to one
XLA executable per input-shape bucket. ``reshape()`` keeps every
previously-bound executor in an LRU pool keyed on the input-shape
signature (the reference re-creates; here a bucket flip is a dict
lookup), and all executors share one set of parameter buffers via
``shared_exec`` binding. ``compile()`` lowers and compiles the serving
fast path per bucket up front — warm-started through the persistent
compile cache (``base.compile_cache_dir``) — with the streaming input
buffers donated, so the steady-state request loop never traces (proven
by the
telemetry.anatomy recompile detector: every dispatch routes through
``_GraphProgram.dispatch_plan``).

The amalgamation analog is ``export_bundle``/``load_bundle``: one file
that contains symbol JSON + params. Bundles now carry per-section and
per-tensor CRC32s (same integrity discipline as the resilience
MANIFEST), so a corrupt bundle fails loudly naming the file and the
tensor; ``params_from_checkpoint`` loads a resilience checkpoint
directory through its MANIFEST/CRC verification for the
fp32-master/AMP training→serving path.

Env knobs: ``MXTPU_SERVE_EXEC_CACHE`` (LRU capacity, default 8),
``MXTPU_SERVE_QUANT=int8`` (experimental weight quantization,
serving/quant.py).
"""
from __future__ import annotations

import collections
import json
import os
import struct
import zlib

import numpy as np

from . import ndarray as nd
from . import symbol as sym_mod
from .base import MXNetError
from .context import Context, cpu

def _exec_cache_cap():
    try:
        return max(1, int(os.environ.get("MXTPU_SERVE_EXEC_CACHE", "8")))
    except ValueError:
        return 8


def _shape_key(input_shapes):
    return tuple(sorted(
        (name, tuple(int(d) for d in shape))
        for name, shape in input_shapes.items()))


class Predictor(object):
    """``MXPredCreate`` equivalent.

    Parameters
    ----------
    symbol_json : str — symbol graph JSON (``Symbol.tojson()``)
    param_raw : bytes | dict — serialized params (``nd.save`` format with
        ``arg:``/``aux:`` prefixed names, as ``save_checkpoint`` writes)
        or an already-loaded {name: NDArray} dict
    input_shapes : dict of name → shape
    ctx : Context (default cpu())
    quant : None | "int8" — weight quantization mode (default: the
        MXTPU_SERVE_QUANT env var). "int8" stores dense/conv weights as
        int8 + per-output-channel scales and dequantizes at bind
        (serving/quant.py, experimental).
    """

    def __init__(self, symbol_json, param_raw, input_shapes, ctx=None,
                 quant=None):
        self.symbol = sym_mod.load_json(symbol_json)
        ctx = ctx if ctx is not None else cpu()
        if isinstance(param_raw, (bytes, bytearray)):
            loaded = nd.load_buffer(bytes(param_raw))
        else:
            loaded = param_raw
        if not isinstance(loaded, dict):
            raise MXNetError(
                "Predictor needs NAMED params (a dict serialized by "
                "nd.save / save_checkpoint); got an unnamed list")
        arg_params, aux_params = {}, {}
        for k, v in loaded.items():
            if k.startswith("arg:"):
                arg_params[k[4:]] = v
            elif k.startswith("aux:"):
                aux_params[k[4:]] = v
            else:
                arg_params[k] = v
        self._ctx = ctx
        self._input_shapes = dict(input_shapes)
        self._arg_params = arg_params
        self._aux_params = aux_params
        self.quant = quant if quant is not None else os.environ.get(
            "MXTPU_SERVE_QUANT", "")
        if self.quant not in ("", "int8"):
            raise MXNetError(
                "unsupported MXTPU_SERVE_QUANT mode %r (only int8)"
                % self.quant)
        if self.quant == "int8":
            from .serving import quant as _quant

            self._arg_params = _quant.quantize_arg_params(self._arg_params)
        # LRU pool: shape signature -> bound Executor; all entries share
        # parameter buffers with the first-ever bind (_shared_exec)
        self._exec_cache = collections.OrderedDict()
        self._serve_cache = {}  # shape signature -> _ServeFn
        self._shared_exec = None
        self._exec = None
        self._bind()

    # -- executor pool -------------------------------------------------
    def _bind(self):
        self._exec = self._executor_for(_shape_key(self._input_shapes),
                                        self._input_shapes)

    def _executor_for(self, key, input_shapes):
        exec_ = self._exec_cache.get(key)
        if exec_ is not None:
            self._exec_cache.move_to_end(key)
            return exec_
        exec_ = self.symbol.simple_bind(
            ctx=self._ctx, grad_req="null", shared_exec=self._shared_exec,
            **input_shapes)
        self._load_params_into(exec_)
        if self._shared_exec is None:
            self._shared_exec = exec_
        self._exec_cache[key] = exec_
        cap = _exec_cache_cap()
        while len(self._exec_cache) > cap:
            old_key, _ = self._exec_cache.popitem(last=False)
            self._serve_cache.pop(old_key, None)
        return exec_

    def _dequant(self, name, arr):
        if self.quant == "int8":
            from .serving import quant as _quant

            return _quant.maybe_dequantize(arr)
        return arr.asnumpy() if hasattr(arr, "asnumpy") else np.asarray(arr)

    def _load_params_into(self, exec_):
        for name, arr in self._arg_params.items():
            if name in exec_.arg_dict:
                data = self._dequant(name, arr)
                if tuple(exec_.arg_dict[name].shape) != tuple(data.shape):
                    raise MXNetError(
                        "param %s shape mismatch %s vs %s"
                        % (name, tuple(data.shape),
                           tuple(exec_.arg_dict[name].shape)))
                exec_.arg_dict[name][:] = data
        for name, arr in self._aux_params.items():
            if name in exec_.aux_dict:
                exec_.aux_dict[name][:] = (
                    arr.asnumpy() if hasattr(arr, "asnumpy")
                    else np.asarray(arr))

    # -- c_predict_api surface ----------------------------------------
    def set_input(self, name, data):
        """``MXPredSetInput``."""
        if name not in self._input_shapes:
            raise MXNetError("unknown input %s" % name)
        data = np.asarray(data)
        want = tuple(self._exec.arg_dict[name].shape)
        if tuple(data.shape) != want:
            raise MXNetError(
                "input %s shape %s does not match bound shape %s"
                % (name, tuple(data.shape), want))
        self._exec.arg_dict[name][:] = data

    def forward(self):
        """``MXPredForward``."""
        self._exec.forward(is_train=False)

    def get_output(self, index=0):
        """``MXPredGetOutput`` → numpy."""
        return self._exec.outputs[index].asnumpy()

    def reshape(self, new_input_shapes):
        """``MXPredReshape``: switch to new input shapes, keeping the
        weights. Previously-seen shape signatures reuse their compiled
        executor from the LRU pool (the reference rebinds every time)."""
        self._input_shapes.update(new_input_shapes)
        self._bind()

    def predict(self, **inputs):
        """Convenience: set all inputs, forward, return all outputs."""
        for name, data in inputs.items():
            self.set_input(name, data)
        self.forward()
        return [o.asnumpy() for o in self._exec.outputs]

    # -- serving AOT fast path -----------------------------------------
    def compile(self, input_shapes_list=None):
        """AOT-lower and compile the serving fast path for each shape
        bucket up front (default: the currently-bound shapes). After
        this, ``predict_batch`` for any compiled bucket is a single
        donated-buffer device call with zero tracing; the XLA
        executables warm-start from the persistent compile cache
        across process restarts."""
        if input_shapes_list is None:
            input_shapes_list = [dict(self._input_shapes)]
        for shapes in input_shapes_list:
            merged = dict(self._input_shapes)
            merged.update(shapes)
            key = _shape_key(merged)
            if key in self._serve_cache:
                continue
            exec_ = self._executor_for(key, merged)
            self._serve_cache[key] = _ServeFn(exec_, merged)
        return self

    def predict_batch(self, **inputs):
        """Serving dispatch: route the named input arrays through the
        AOT-compiled executable for their exact shape signature,
        compiling it on first sight (warmup). Returns a list of numpy
        outputs. Every call runs the program's ``dispatch_plan`` so the
        PR 5 recompile detector audits the steady state."""
        merged = dict(self._input_shapes)
        for name, data in inputs.items():
            if name not in self._input_shapes:
                raise MXNetError("unknown input %s" % name)
            merged[name] = tuple(np.asarray(data).shape)
        key = _shape_key(merged)
        fn = self._serve_cache.get(key)
        if fn is None:
            self.compile([merged])
            fn = self._serve_cache[key]
        return fn(inputs)

    @property
    def cached_shape_keys(self):
        """Shape signatures currently resident in the executor pool."""
        return list(self._exec_cache)


class _ServeFn(object):
    """One AOT-compiled forward for one input-shape bucket, pinned to
    the executor's context device: parameters closed over as executable
    constants, streaming inputs donated."""

    def __init__(self, exec_, input_shapes):
        import jax

        self._exec = exec_
        # the avals name the context's device, so the executable lands
        # there whatever the process default backend is
        self._device = exec_._ctx.jax_device
        sharding = jax.sharding.SingleDeviceSharding(self._device)
        self._program = exec_._program
        self._data_names = tuple(sorted(input_shapes))
        self._output_names = list(exec_._output_names)
        arg_names = tuple(exec_._arg_names)
        aux_names = tuple(exec_._aux_names)
        program = exec_._program
        data_names = self._data_names
        const_args = {
            name: arr._data
            for name, arr in zip(arg_names, exec_.arg_arrays)
            if name not in input_shapes
        }
        aux_vals = {n: a._data for n, a in zip(aux_names, exec_.aux_arrays)}
        rng = jax.random.PRNGKey(0) if exec_._needs_rng else None

        def serve(*data_vals):
            args = dict(const_args)
            args.update(zip(data_names, data_vals))
            outs, _ = program(args, aux_vals, rng, False)
            return tuple(outs)

        jitted = jax.jit(
            serve, donate_argnums=tuple(range(len(data_names))))
        self._avals = [
            jax.ShapeDtypeStruct(
                tuple(input_shapes[n]),
                exec_.arg_dict[n].dtype, sharding=sharding)
            for n in data_names
        ]
        # AOT: lower + compile now (the persistent compile cache
        # warm-starts this), so the first request pays zero
        # trace/compile time.
        # CPU XLA cannot honor donation — silence that warning, the
        # request stays meaningful on TPU.
        import warnings

        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            self._compiled = jitted.lower(*self._avals).compile()
        # dispatch-plan signature: lets the anatomy recompile detector
        # fingerprint every serving dispatch exactly like a training
        # step dispatch (first sight per program = warmup-exempt)
        self._sig = tuple(
            (n, tuple(a.shape), str(a.dtype), "serve")
            for n, a in zip(data_names, self._avals))
        overrides = program.shape_overrides
        program.dispatch_plan(self._sig, lambda: overrides)

    def __call__(self, inputs):
        import jax

        overrides = self._program.shape_overrides
        self._program.dispatch_plan(self._sig, lambda: overrides)
        data_vals = []
        for name, aval in zip(self._data_names, self._avals):
            data = np.asarray(inputs[name])
            if tuple(data.shape) != tuple(aval.shape):
                raise MXNetError(
                    "input %s shape %s does not match compiled bucket %s"
                    % (name, tuple(data.shape), tuple(aval.shape)))
            # fresh device array per call: its buffer is donated to the
            # executable, so the output can alias it in place
            data_vals.append(jax.device_put(
                data.astype(aval.dtype, copy=False), self._device))
        outs = self._compiled(*data_vals)
        return [np.asarray(o) for o in outs]


# --------------------------------------------------------------------------
# amalgamation analog: single-file inference bundle
# --------------------------------------------------------------------------

_BUNDLE_MAGIC_V1 = b"MXTPUPRED1"
_BUNDLE_MAGIC = b"MXTPUPRED2"


def _tensor_crcs(save_dict):
    return {
        name: zlib.crc32(np.ascontiguousarray(arr.asnumpy()).tobytes())
        for name, arr in save_dict.items()
    }


def export_bundle(fname, symbol, arg_params, aux_params=None):
    """Write symbol JSON + params as ONE file (the role the reference's
    amalgamation plays: a self-contained deployable predict artifact).
    The v2 header carries a manifest with per-section and per-tensor
    CRC32s — the same integrity discipline as the resilience
    checkpoint MANIFEST — so corruption is caught at load, not at
    first NaN."""
    js = symbol.tojson().encode()
    save_dict = {("arg:%s" % k): v for k, v in arg_params.items()}
    if aux_params:
        save_dict.update({("aux:%s" % k): v for k, v in aux_params.items()})
    param_bytes = nd.save_buffer(save_dict)
    manifest = json.dumps({
        "version": 2,
        "symbol": {"bytes": len(js), "crc32": zlib.crc32(js)},
        "params": {"bytes": len(param_bytes),
                   "crc32": zlib.crc32(param_bytes)},
        "tensors": _tensor_crcs(save_dict),
    }).encode()
    with open(fname, "wb") as f:
        f.write(_BUNDLE_MAGIC)
        f.write(struct.pack("<qqq", len(manifest), len(js),
                            len(param_bytes)))
        f.write(manifest)
        f.write(js)
        f.write(param_bytes)


def _verify_bundle_params(fname, manifest, param_bytes):
    """Per-tensor CRC verification: decode the param dict and check each
    tensor against the manifest so a corrupt bundle names the exact
    tensor, mirroring resilience.checkpoint.verify_checkpoint(deep=True)."""
    loaded = nd.load_buffer(param_bytes)
    want = manifest.get("tensors", {})
    for name, arr in loaded.items():
        if name not in want:
            raise MXNetError(
                "bundle %s: tensor %s missing from manifest (corrupt or "
                "tampered)" % (fname, name))
        got = zlib.crc32(np.ascontiguousarray(arr.asnumpy()).tobytes())
        if got != want[name]:
            raise MXNetError(
                "bundle %s: tensor %s fails CRC32 (corrupt)"
                % (fname, name))
    missing = set(want) - set(loaded)
    if missing:
        raise MXNetError(
            "bundle %s: tensors %s listed in manifest but absent"
            % (fname, sorted(missing)))
    return loaded


def load_bundle(fname, input_shapes, ctx=None, quant=None):
    """Load an ``export_bundle`` file into a ready Predictor. v2
    bundles are CRC-verified section by section and tensor by tensor;
    any mismatch raises naming the file and the tensor. v1 bundles
    (no manifest) still load."""
    with open(fname, "rb") as f:
        magic = f.read(len(_BUNDLE_MAGIC))
        if magic == _BUNDLE_MAGIC_V1:
            js_len, p_len = struct.unpack("<qq", f.read(16))
            js = f.read(js_len).decode()
            param_bytes = f.read(p_len)
            return Predictor(js, param_bytes, input_shapes, ctx=ctx,
                             quant=quant)
        if magic != _BUNDLE_MAGIC:
            raise MXNetError("%s is not a predictor bundle" % fname)
        m_len, js_len, p_len = struct.unpack("<qqq", f.read(24))
        manifest_raw = f.read(m_len)
        js_raw = f.read(js_len)
        param_bytes = f.read(p_len)
    try:
        manifest = json.loads(manifest_raw.decode())
    except (UnicodeDecodeError, json.JSONDecodeError):
        raise MXNetError(
            "bundle %s: manifest section unreadable (corrupt header)"
            % fname)
    if len(js_raw) != manifest["symbol"]["bytes"] or \
            zlib.crc32(js_raw) != manifest["symbol"]["crc32"]:
        raise MXNetError(
            "bundle %s: symbol section fails CRC32 (corrupt)" % fname)
    if len(param_bytes) != manifest["params"]["bytes"] or \
            zlib.crc32(param_bytes) != manifest["params"]["crc32"]:
        # locate the guilty tensor for the error message before failing
        try:
            _verify_bundle_params(fname, manifest, param_bytes)
        except MXNetError:
            raise
        except Exception:
            pass  # params not even decodable — use the section error
        raise MXNetError(
            "bundle %s: params section fails CRC32 (corrupt)" % fname)
    loaded = _verify_bundle_params(fname, manifest, param_bytes)
    return Predictor(js_raw.decode(), loaded, input_shapes, ctx=ctx,
                     quant=quant)


def params_from_checkpoint(ckpt_dir):
    """Load ``{arg:.../aux:...}`` params from a resilience checkpoint
    directory through its MANIFEST/CRC verification (deep per-tensor
    check) — the fp32-master / AMP training→serving path. Corruption
    raises CheckpointError naming the file and tensor."""
    from .resilience import checkpoint as ckpt

    ckpt.verify_checkpoint(ckpt_dir, deep=True)
    state = ckpt.load_state(ckpt_dir, verify=False)
    params = {}
    for name, arr in state["module"]["arg"].items():
        params["arg:%s" % name] = nd.array(np.asarray(arr, np.float32))
    for name, arr in state["module"]["aux"].items():
        params["aux:%s" % name] = nd.array(np.asarray(arr, np.float32))
    return params
