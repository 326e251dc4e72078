"""NDArray: the imperative tensor API.

Parity: reference ``python/mxnet/ndarray.py`` + ``src/ndarray/ndarray.cc``
+ ``include/mxnet/ndarray.h``. Design mapping (SURVEY.md §7 table):

- The reference NDArray is a Chunk (storage handle + engine var); every op
  is an engine push and ``WaitToRead`` is the sync point. Here an NDArray
  wraps a ``jax.Array`` — XLA's async dispatch IS the dependency engine
  (data dependencies are tracked by value), ``wait_to_read`` ≈
  ``block_until_ready``.
- ``MXImperativeInvoke`` (reference src/c_api/c_api_ndarray.cc:322 →
  PushFCompute) becomes :func:`imperative_invoke`: one jit-compiled,
  cache-keyed-by-(op, attrs, shapes, dtypes) callable per op instance, so
  steady-state imperative dispatch is a cache hit + async XLA launch.
- In-place mutation (``+=``, ``a[:]=``, out=) rebinds the handle's
  underlying value — the buffer-versioning layer SURVEY.md §7 calls for.
"""
from __future__ import annotations

import functools
import struct
import sys

import numpy as np

from . import autograd as _autograd
from . import random as _random
from . import telemetry as _tm
from .base import MXNetError, mx_dtype_code, np_dtype, dtype_name
from .context import Context, current_context
from .ops import registry as _registry

__all__ = ["NDArray", "zeros", "ones", "array", "empty", "full", "arange",
           "concatenate", "load", "save", "imperative_invoke", "waitall"]

# op-namespace generation below shadows some builtins at module scope
# (slice, sum, abs, ...); capture the ones methods need.
_py_slice = slice


def _jax():
    import jax

    return jax


def _ctx_of_jax_device(dev):
    """Context for a jax.Device — by LOCAL index, not global id.

    Context.jax_device indexes jax.local_devices(), so the round-trip
    must too: in a multi-controller job rank 1's first device has a
    global id >= num_local, and Context('cpu', global_id) would be out
    of range (or, worse, some peer's device)."""
    plat = dev.platform
    jax = _jax()
    try:
        idx = jax.local_devices(backend=plat).index(dev)
    except (RuntimeError, ValueError):
        idx = dev.id  # non-addressable peer device: keep the global id
    if plat == "cpu":
        return Context("cpu", idx)
    if plat == "tpu":
        return Context("tpu", idx)
    return Context("gpu", idx)


def _hashable(v):
    if isinstance(v, (list, tuple)):
        return tuple(_hashable(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _hashable(x)) for k, x in v.items()))
    return v


@functools.lru_cache(maxsize=None)
def _compiled_op(op_name, attr_key, is_train, with_rng):
    """One jitted callable per (op, static attrs, mode). The returned fn
    takes (rng_or_None, *arrays) and returns a tuple of arrays."""
    jax = _jax()
    opdef = _registry.get(op_name)

    def run(rng, *arrays):
        attrs = dict(attr_key)
        if with_rng:
            attrs["__rng__"] = rng
        out = opdef.fcompute(attrs, list(arrays), is_train)
        return tuple(out)

    return jax.jit(run)


def imperative_invoke(opdef, inputs, attrs, out=None):
    """Invoke an operator imperatively on NDArrays.

    Parity: MXImperativeInvoke (c_api_ndarray.cc:322): shape/type inference
    is implicit (abstract-eval inside jit tracing), the engine push is jax's
    async dispatch, and autograd recording hooks in exactly where
    RecordImperativeFCompute does (c_api_ndarray.cc:375).
    """
    if isinstance(opdef, str):
        opdef = _registry.get(opdef)
    if attrs:
        opdef.check_call_attrs(attrs)  # typo net (dmlc::Parameter analog)
    attrs = opdef.canon_attrs(attrs)
    is_train = _autograd.is_training()
    rng = _random.next_key() if opdef.needs_rng else None
    arrays = []
    for x in inputs:
        if isinstance(x, NDArray):
            if x._engine_dep is not None:  # kvstore-managed array
                x._drain_engine()
            arrays.append(x._data)
        else:
            arrays.append(np.asarray(x))
    from jax.core import Tracer

    if any(isinstance(a, Tracer) for a in arrays) or any(
        isinstance(v, Tracer) for v in attrs.values()
    ):
        # Already inside an outer jit trace (e.g. ShardedTrainStep tracing
        # through Optimizer.update): call fcompute inline — no per-op jit
        # cache (tracers are unhashable) and attrs may be traced scalars
        # (lr/wd enter the fused step as per-call inputs).
        run_attrs = dict(attrs)
        if opdef.needs_rng:
            run_attrs["__rng__"] = rng
        results = tuple(opdef.fcompute(run_attrs, list(arrays), is_train))
    else:
        attr_key = tuple(sorted((k, _hashable(v)) for k, v in attrs.items()))
        fn = _compiled_op(opdef.name, attr_key, is_train, opdef.needs_rng)
        results = fn(rng, *arrays)
    # Trailing results map to reference-mutated inputs: explicit
    # mutate_inputs (sgd_mom_update's momentum) or aux states (BatchNorm's
    # moving_mean/var, which the reference mutates via FMutateInputs).
    n_aux = len(opdef.list_auxiliary_states(attrs))
    n_args = opdef.num_inputs(attrs)
    n_writeback = len(opdef.mutate_inputs) + n_aux
    n_out = len(results) - n_writeback
    outs = results[:n_out]
    writeback_idx = list(opdef.mutate_inputs) + list(
        range(n_args, n_args + n_aux)
    )
    for idx, val in zip(writeback_idx, results[n_out:]):
        if idx < len(inputs) and isinstance(inputs[idx], NDArray):
            inputs[idx]._data = val

    if out is not None:
        out_list = [out] if isinstance(out, NDArray) else list(out)
        for o, v in zip(out_list, outs):
            o._data = v
        ret = out_list[0] if len(out_list) == 1 else out_list
    else:
        out_list = [NDArray(v) for v in outs]
        ret = out_list[0] if len(out_list) == 1 else out_list

    if _autograd.is_recording():
        # record ALL inputs positionally; non-NDArray inputs keep their
        # converted array value so backward replay sees the same arity
        recorded = [
            x if isinstance(x, NDArray) else a
            for x, a in zip(inputs, arrays)
        ]
        _autograd.record_op(
            opdef,
            dict(attr_key) | ({"__rng__": rng} if rng is not None else {}),
            recorded,
            out_list,
        )
    return ret


def _platform(sharding):
    return next(iter(sharding.device_set)).platform


class _Draw:
    """A ``_Deferred``'s value when it is not a constant but a draw:
    ``sigma * normal(key)`` in float32 (``mx.random``'s threefry
    stream), cast to the array's type."""

    __slots__ = ("key", "sigma")

    def __init__(self, key, sigma):
        self.key = key
        self.sigma = sigma


class _Deferred:
    """What an NDArray holds in place of a buffer nobody has read or
    written yet: where a constant, or a draw (``_Draw``), of which shape
    and type belongs. The buffer comes into being on the first read of
    ``NDArray._data``, made on its own device by a program (no host
    array, no crossing), or on the mesh when the fused step takes the
    array first (``place``); a whole write replaces it without the value
    ever being made. Also what an array keeps once the fused step owns
    its value (``NDArray._drop_buffer``): then ``value`` is None, there
    is nothing to read, and a read says so."""

    __slots__ = ("shape", "dtype", "value", "device")

    def __init__(self, shape, dtype, value, device):
        self.shape = tuple(int(d) for d in shape)
        self.dtype = np.dtype(dtype)
        self.value = value.item() if isinstance(value, np.generic) else value
        self.device = device

    @property
    def ndim(self):
        return len(self.shape)

    @property
    def nbytes(self):
        return int(np.prod(self.shape, dtype=np.int64)) * self.dtype.itemsize

    @property
    def sharding(self):
        """``device`` is a Device or, as ``jax.Array.device`` gives for an
        array over several, a Sharding; a program's ``out_shardings``
        wants the latter."""
        from jax.sharding import Sharding, SingleDeviceSharding

        if isinstance(self.device, Sharding):
            return self.device
        return SingleDeviceSharding(self.device)

    @property
    def platform(self):
        return _platform(self.sharding)


@functools.lru_cache(maxsize=1024)
def _constants_program(specs, shardings):
    """One jitted program, kept for the process, that makes every
    constant of ``specs`` ((shape, dtype, value), ...) where
    ``shardings`` says."""
    import jax.numpy as jnp

    def make():
        return tuple(jnp.full(shape, value, dtype)
                     for shape, dtype, value in specs)

    return _jax().jit(make, out_shardings=shardings)


@functools.lru_cache(maxsize=1024)
def _draw_program(shape, dtype, sharding):
    """One jitted program a signature, kept for the process, that makes
    ``sigma * normal(key)`` where ``sharding`` says: drawn and scaled in
    float32 and cast inside, so no float32 copy outlives it. Key and
    sigma are its arguments: every array of a signature, whatever its
    seed, runs the one executable. Drawn as rows of the last axis and
    reshaped (a value of the stream depends on its row-major position
    alone): the TPU's compiler takes 8 s over a three-axis draw and
    1-2.6 s over the same rows. On the host's platform a barrier keeps
    XLA from folding sigma into the normal's own ``sqrt(2)``, so the
    value is bit for bit what the eager draw, scale and cast gave; an
    accelerator has no eager value to keep, and the barrier would cost
    it a float32 pass over HBM and 6-9 s of compile a signature."""
    jax = _jax()
    rows = (int(np.prod(shape[:-1], dtype=np.int64)),) + tuple(shape[-1:])
    on_host = _platform(sharding) == "cpu"

    def draw(key, sigma):
        x = jax.random.normal(key, rows, np.float32)
        if on_host:
            x = jax.lax.optimization_barrier(x)
        return (sigma * x).astype(dtype).reshape(shape)

    return jax.jit(draw, out_shardings=sharding)


def make_deferred(deferred):
    """The buffers of a sequence of ``_Deferred``, made where each says
    and without a host array: the constants by ONE program (a set-up
    that needs a state tree pays one cache load, not one per key), the
    draws by one program a (shape, type, sharding), a call a key."""
    deferred = tuple(deferred)
    if not deferred:
        return ()
    if any(d.value is None for d in deferred):
        raise MXNetError(
            "this array gave its buffer up to the fused step: read "
            "Module.get_params(), or run a forward first")
    draws = [i for i, d in enumerate(deferred) if type(d.value) is _Draw]
    consts = [i for i, d in enumerate(deferred) if type(d.value) is not _Draw]
    if _tm.enabled():
        for i in consts:
            _tm.note_const(deferred[i].nbytes)
        for i in draws:
            _tm.note_drawn(deferred[i].nbytes, deferred[i].platform)
    made = [None] * len(deferred)
    if consts:
        bufs = _constants_program(
            tuple((deferred[i].shape, deferred[i].dtype, deferred[i].value)
                  for i in consts),
            tuple(deferred[i].sharding for i in consts))()
        for i, buf in zip(consts, bufs):
            made[i] = buf
    for i in draws:
        d = deferred[i]
        made[i] = _draw_program(d.shape, d.dtype, d.sharding)(
            d.value.key, np.float32(d.value.sigma))
    return tuple(made)


def place(values, shardings):
    """Each of ``values`` (NDArrays, jax or numpy arrays) as a jax.Array
    under its entry of ``shardings``, a fresh buffer each. What nobody
    has read yet is made there (``make_deferred``); an NDArray that held
    a draw keeps no record of it, because the placed copy is the only
    one: drawn again on another device it would differ in the last
    place. What holds a buffer is put from where it is: from a device
    of the sharding's directly, else by way of the host. The host's
    bytes are counted once a call, a call that sent none included
    (``device.h2d_bytes`` then reads 0, not nothing)."""
    jax = _jax()
    out = [None] * len(values)
    unmade, crossed = [], 0
    for i, (v, sharding) in enumerate(zip(values, shardings)):
        buf = getattr(v, "_buf", v)
        if type(buf) is _Deferred and buf.value is not None:
            unmade.append(i)
            continue
        if isinstance(v, NDArray):
            v._drain_engine()
            buf = v._data
        if (isinstance(buf, jax.Array) and sharding.is_fully_addressable
                and buf.sharding.device_set <= sharding.device_set):
            if _platform(buf.sharding) == "cpu":
                crossed += buf.nbytes  # host memory, as _note_crossing has it
            # of a copy: a put may alias its source where the devices
            # meet, and the step donates what it is given
            out[i] = jax.device_put(jax.numpy.copy(buf), sharding)
        else:
            host = np.asarray(buf)
            crossed += host.nbytes
            out[i] = jax.device_put(host, sharding)
    if _tm.enabled() and values:
        _tm.note_h2d(crossed, next(iter(shardings[0].device_set)))
    records = [values[i]._buf for i in unmade]
    made = make_deferred(
        _Deferred(r.shape, r.dtype, r.value, shardings[i])
        for i, r in zip(unmade, records))
    for i, r, buf in zip(unmade, records, made):
        out[i] = buf
        if type(r.value) is _Draw:
            values[i]._drop_buffer()
    # a fit places once: its programs are not kept for the process,
    # because a loaded executable holds up to 1 MB of the chip's memory
    _draw_program.cache_clear()
    _constants_program.cache_clear()
    return out


class NDArray:
    """An n-dimensional array on a device, with async-op semantics."""

    __slots__ = ("_buf", "_engine_dep")
    # prefer our operators over numpy's in mixed expressions
    __array_priority__ = 1000.0

    def __init__(self, data):
        # a jax.Array, or a _Deferred until something reads or writes it
        self._buf = data
        # (engine, Var) when a host-side engine op (KVStore push/pull)
        # has claimed this array; None for the overwhelmingly common
        # case where jax's value tracking is the only discipline needed
        self._engine_dep = None

    # -- the buffer ---------------------------------------------------------
    @property
    def _data(self):
        """The jax.Array; a deferred constant or draw is made here,
        once, on the array's own device."""
        buf = self._buf
        if type(buf) is _Deferred:
            buf = self._buf = make_deferred((buf,))[0]
        return buf

    @_data.setter
    def _data(self, value):
        self._buf = value

    @property
    def _placement(self):
        """Where the buffer lives (a Device; a Sharding for an array
        over several), whether or not it has been made."""
        return self._buf.device

    def _drop_buffer(self):
        """Give the buffer up: the array keeps shape, type and device,
        and has nothing to read until something writes it whole."""
        buf = self._buf
        self._buf = _Deferred(buf.shape, buf.dtype, None, buf.device)

    def _set_normal(self, key, sigma):
        """A whole write of ``sigma * normal(key)``: recorded while the
        array holds no buffer (whoever reads first decides where it is
        made), made now on the array's own device otherwise."""
        if self._engine_dep is not None:
            self._drain_engine()  # as __setitem__: an in-flight pull lands first
        unread = type(self._buf) is _Deferred
        # the key as host words: a record (the kvstore keeps a copy of
        # each) then holds nothing on the stream's device
        self._buf = _Deferred(self.shape, self._buf.dtype,
                              _Draw(np.asarray(key), float(sigma)),
                              self._placement)
        if not unread:
            self._data  # it held a buffer: it holds the new one at once

    # -- basic properties ---------------------------------------------------
    @property
    def shape(self):
        return tuple(self._buf.shape)

    @property
    def size(self):
        return int(np.prod(self.shape)) if self.shape else 1

    @property
    def ndim(self):
        return self._buf.ndim

    @property
    def dtype(self):
        return np_dtype(self._buf.dtype)

    @property
    def context(self):
        dev = self._buf.device
        if hasattr(dev, "platform"):
            return _ctx_of_jax_device(dev)
        return _ctx_of_jax_device(sorted(dev.device_set, key=lambda d: d.id)[0])

    ctx = context

    @property
    def T(self):
        return imperative_invoke("transpose", [self], {})

    # -- sync ---------------------------------------------------------------
    def _engine_var(self, eng):
        """Attach (or return) this array's dependency Var on engine
        ``eng``. Engine-scheduled host ops (KVStore push/pull) declare
        reads/writes through it; readers drain via _drain_engine."""
        dep = self._engine_dep
        if dep is None or dep[0] is not eng:
            dep = (eng, eng.new_variable())
            self._engine_dep = dep
        return dep[1]

    def _drain_engine(self):
        """Wait for any outstanding engine-scheduled op on this array
        (no-op in the common case: one attribute check)."""
        dep = self._engine_dep
        if dep is not None:
            eng, var = dep
            wait_last = getattr(eng, "wait_last", None)
            if wait_last is not None:
                wait_last(var)
            else:
                eng.wait_for_var(var)

    def wait_to_read(self):
        self._drain_engine()
        if type(self._buf) is not _Deferred:  # nothing in flight otherwise
            self._buf.block_until_ready()

    wait_to_write = wait_to_read

    def asnumpy(self):
        self._drain_engine()
        return np.asarray(self._data)

    def __array__(self, dtype=None, copy=None):
        """numpy protocol: without this, np.asarray(nd) falls back to the
        sequence protocol and builds the array ELEMENT-WISE through
        __getitem__ — ~20k traced gathers for a (300, 64) input (found
        via the C++ Predictor, which fed an NDArray to set_input's
        np.asarray and appeared to hang). The numpy-2 ``copy`` contract
        is honored: copy=True always copies; copy=False always raises,
        because materializing device-backed data can never be guaranteed
        zero-copy."""
        if copy is False:
            raise ValueError(
                "NDArray.__array__: cannot guarantee zero-copy for "
                "device-backed data (np.asarray(nd, copy=False))")
        self._drain_engine()
        a = np.asarray(self._data)
        if dtype is not None and a.dtype != np.dtype(dtype):
            return a.astype(dtype, copy=True)
        if copy:
            return a.copy()
        return a

    def asscalar(self):
        if self.size != 1:
            raise MXNetError("The current array is not a scalar")
        return self.asnumpy().reshape(-1)[0]

    # -- conversion / movement ---------------------------------------------
    def astype(self, dtype):
        return NDArray(self._data.astype(np_dtype(dtype)))

    def copyto(self, other):
        jax = _jax()
        if isinstance(other, NDArray):
            if other is self:
                return other
            if other._engine_dep is not None:
                # order this write after any in-flight engine op on the
                # target. The kvstore pull body writes its target via
                # _data assignment (not copyto) precisely so this drain
                # can't self-deadlock the op that holds the var.
                other._drain_engine()
            if self._unread_as_on(other):
                # neither holds a buffer: the record moves, nothing is made
                buf = self._buf
                other._buf = _Deferred(buf.shape, buf.dtype, buf.value,
                                       other._placement)
                return other
            if _tm.enabled():
                _note_crossing(self._data, other._placement)
            other._data = jax.device_put(self._data, other._placement)
            return other
        if isinstance(other, Context):
            if _tm.enabled():
                _note_crossing(self._data, other.jax_device)
            return NDArray(jax.device_put(self._data, other.jax_device))
        raise MXNetError("copyto: unsupported target %r" % (other,))

    def copy(self):
        if self._unread_as_on(self):
            return NDArray(self._buf)  # the same record, not a buffer
        return NDArray(self._data + 0)

    def _unread_as_on(self, other):
        """Whether this array's value is still a record (``_Deferred``)
        that reads on ``other``'s device, itself holding no buffer, as
        it reads here: a constant anywhere, a draw on the same platform
        (another back end rounds ``erf_inv`` differently)."""
        buf, there = self._buf, other._buf
        return (type(buf) is _Deferred and buf.value is not None
                and type(there) is _Deferred
                and (type(buf.value) is not _Draw
                     or buf.platform == there.platform))

    def as_in_context(self, context):
        if self.context == context:
            return self
        return self.copyto(context)

    # -- shape manipulation -------------------------------------------------
    def reshape(self, shape):
        if isinstance(shape, int):
            shape = (shape,)
        return imperative_invoke("Reshape", [self], {"shape": tuple(shape)})

    def broadcast_to(self, shape):
        return imperative_invoke("broadcast_to", [self], {"shape": tuple(shape)})

    # -- indexing -----------------------------------------------------------
    def __getitem__(self, key):
        if isinstance(key, int):
            return NDArray(self._data[key])
        if isinstance(key, _py_slice):
            if key.step is not None and key.step != 1:
                raise MXNetError("NDArray only supports step=1 slicing")
            return NDArray(self._data[key])
        if isinstance(key, tuple):
            return NDArray(self._data[key])
        if isinstance(key, NDArray):
            return NDArray(self._data[key._data.astype("int32")])
        raise MXNetError("unsupported index %r" % (key,))

    def __setitem__(self, key, value):
        import jax.numpy as jnp

        if self._engine_dep is not None:
            # an in-flight engine op (kvstore pull) targeting this array
            # must land BEFORE this write, or it would clobber it later
            self._drain_engine()
        if isinstance(value, NDArray):
            v = value._data
        else:
            v = value
        if isinstance(key, _py_slice) and key.start is None and key.stop is None:
            # a whole write: what the array held is not read
            if np.isscalar(v):
                if type(self._buf) is _Deferred:
                    self._buf = _Deferred(self.shape, self._buf.dtype, v,
                                          self._buf.device)
                else:
                    self._data = jnp.full_like(self._buf, v)
            elif isinstance(v, _jax().Array):
                v = jnp.broadcast_to(
                    jnp.asarray(v, dtype=self.dtype), self.shape)
                if not isinstance(self._buf, _jax().core.Tracer):
                    v = _jax().device_put(v, self._buf.sharding)
                self._data = v
            else:
                # host values go straight to where the array lives, not
                # by way of the default device
                self._data = _jax().device_put(
                    np.broadcast_to(np.asarray(v, dtype=self.dtype),
                                    self.shape), self._buf.sharding)
            return
        self._data = self._data.at[key].set(v)

    def slice(self, start, stop):
        return NDArray(self._data[start:stop])

    def at(self, idx):
        return NDArray(self._data[idx])

    # -- arithmetic ---------------------------------------------------------
    def _binop(self, other, op, scalar_op, reverse=False):
        if isinstance(other, NDArray):
            a, b = (other, self) if reverse else (self, other)
            if a.shape == b.shape:
                return imperative_invoke(op, [a, b], {})
            return imperative_invoke("broadcast_" + _BCAST_NAME[op], [a, b], {})
        if np.isscalar(other):
            name = ("_r" + scalar_op[1:]) if reverse and op in _NONCOMMUTATIVE else scalar_op
            return imperative_invoke(name, [self], {"scalar": float(other)})
        if isinstance(other, np.ndarray):
            return self._binop(array(other, ctx=self.context, dtype=self.dtype), op, scalar_op, reverse)
        jax = _jax()
        if isinstance(other, (jax.Array, jax.core.Tracer)):
            # jax values (incl. traced scalars like the fused step's lr)
            # participate directly as NDArray operands
            return self._binop(NDArray(other), op, scalar_op, reverse)
        return NotImplemented

    def __add__(self, o):
        return self._binop(o, "elemwise_add", "_plus_scalar")

    __radd__ = __add__

    def __sub__(self, o):
        return self._binop(o, "elemwise_sub", "_minus_scalar")

    def __rsub__(self, o):
        return self._binop(o, "elemwise_sub", "_minus_scalar", reverse=True)

    def __mul__(self, o):
        return self._binop(o, "elemwise_mul", "_mul_scalar")

    __rmul__ = __mul__

    def __div__(self, o):
        return self._binop(o, "elemwise_div", "_div_scalar")

    __truediv__ = __div__

    def __rdiv__(self, o):
        return self._binop(o, "elemwise_div", "_div_scalar", reverse=True)

    __rtruediv__ = __rdiv__

    def __pow__(self, o):
        return self._binop(o, "_power", "_power_scalar")

    def __rpow__(self, o):
        return self._binop(o, "_power", "_power_scalar", reverse=True)

    def __mod__(self, o):
        return self._binop(o, "_mod", "_mod_scalar")

    def __rmod__(self, o):
        return self._binop(o, "_mod", "_mod_scalar", reverse=True)

    def __neg__(self):
        return imperative_invoke("negative", [self], {})

    def __eq__(self, o):
        if isinstance(o, (NDArray, int, float, np.ndarray)):
            return self._binop(o, "_equal", "_equal_scalar")
        return NotImplemented

    def __ne__(self, o):
        if isinstance(o, (NDArray, int, float, np.ndarray)):
            return self._binop(o, "_not_equal", "_not_equal_scalar")
        return NotImplemented

    def __gt__(self, o):
        return self._binop(o, "_greater", "_greater_scalar")

    def __ge__(self, o):
        return self._binop(o, "_greater_equal", "_greater_equal_scalar")

    def __lt__(self, o):
        return self._binop(o, "_lesser", "_lesser_scalar")

    def __le__(self, o):
        return self._binop(o, "_lesser_equal", "_lesser_equal_scalar")

    __hash__ = object.__hash__

    def __iadd__(self, o):
        r = self.__add__(o)
        self._data = r._data
        return self

    def __isub__(self, o):
        r = self.__sub__(o)
        self._data = r._data
        return self

    def __imul__(self, o):
        r = self.__mul__(o)
        self._data = r._data
        return self

    def __idiv__(self, o):
        r = self.__div__(o)
        self._data = r._data
        return self

    __itruediv__ = __idiv__

    def __len__(self):
        return self.shape[0]

    def __bool__(self):
        if self.size == 1:
            return bool(self.asscalar())
        raise MXNetError("ambiguous truth value of multi-element NDArray")

    def __repr__(self):
        return "<NDArray %s @%s>" % ("x".join(map(str, self.shape)), self.context)

    def __getstate__(self):
        return {"data": self.asnumpy()}

    def __setstate__(self, state):
        import jax.numpy as jnp

        self._buf = jnp.asarray(state["data"])
        self._engine_dep = None


_BCAST_NAME = {
    "elemwise_add": "add",
    "elemwise_sub": "sub",
    "elemwise_mul": "mul",
    "elemwise_div": "div",
    "_power": "power",
    "_mod": "mod",
    "_equal": "equal",
    "_not_equal": "not_equal",
    "_greater": "greater",
    "_greater_equal": "greater_equal",
    "_lesser": "lesser",
    "_lesser_equal": "lesser_equal",
}
_NONCOMMUTATIVE = {"elemwise_sub", "elemwise_div", "_power", "_mod"}


# --------------------------------------------------------------------------
# creation API
# --------------------------------------------------------------------------
def _note_crossing(data, device):
    """A buffer held by a host (cpu) device on its way to ``device`` is
    host traffic like ``_put``'s; one between two chips is not."""
    if getattr(getattr(data, "device", None), "platform", None) == "cpu":
        _tm.note_h2d(data.nbytes, device)


def _put(arr, ctx):
    jax = _jax()
    ctx = ctx or current_context()
    if _tm.enabled():
        _tm.note_h2d(arr.nbytes, ctx.jax_device)
    return jax.device_put(arr, ctx.jax_device)


def deferred_full(shape, val, ctx=None, dtype=np.float32):
    """An array that reads as ``full(shape, val)`` on ``ctx`` and holds
    no buffer until something reads it (``_Deferred``): what bind's
    arguments, gradients and auxiliary states and an optimizer's fresh
    state are born as, on every context."""
    if isinstance(shape, int):
        shape = (shape,)
    ctx = ctx or current_context()
    return NDArray(_Deferred(shape, np_dtype(dtype), val, ctx.jax_device))


def _created(host, shape, val, ctx, dtype):
    """On the cpu context ``host``'s numpy array handed to the host
    device; on an accelerator no host array and no crossing
    (``deferred_full``)."""
    if isinstance(shape, int):
        shape = (shape,)
    ctx = ctx or current_context()
    if ctx.device_type != "cpu":
        return deferred_full(shape, val, ctx, dtype)
    return NDArray(_put(host(shape, np_dtype(dtype)), ctx))


def empty(shape, ctx=None, dtype=np.float32):
    return zeros(shape, ctx, dtype)


def zeros(shape, ctx=None, dtype=np.float32):
    return _created(np.zeros, shape, 0, ctx, dtype)


def ones(shape, ctx=None, dtype=np.float32):
    return _created(np.ones, shape, 1, ctx, dtype)


def full(shape, val, ctx=None, dtype=np.float32):
    return _created(lambda s, d: np.full(s, val, d), shape, val, ctx, dtype)


def array(source_array, ctx=None, dtype=None):
    if isinstance(source_array, NDArray):
        arr = source_array.asnumpy()
    else:
        arr = np.asarray(source_array)
    if dtype is None:
        dtype = arr.dtype if arr.dtype != np.float64 else np.float32
    return NDArray(_put(arr.astype(np_dtype(dtype)), ctx))


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype=np.float32):
    if stop is None:
        start, stop = 0, start
    out = np.arange(start, stop, step)
    if repeat > 1:
        out = np.repeat(out, repeat)
    return NDArray(_put(out.astype(np_dtype(dtype)), ctx))


def concatenate(arrays, axis=0, always_copy=True):
    import jax.numpy as jnp

    return NDArray(jnp.concatenate([a._data for a in arrays], axis=axis))


def onehot_encode(indices, out):
    depth = out.shape[1]
    return imperative_invoke("one_hot", [indices], {"depth": depth}, out=out)


def waitall():
    """Parity: MXNDArrayWaitAll — barrier on all async work."""
    _jax().effects_barrier()


# --------------------------------------------------------------------------
# serialization — BINARY-COMPATIBLE with NDArray::Save/Load (reference
# src/ndarray/ndarray.cc:604-689 + python/mxnet/ndarray.py:2063-2097):
# published .params files load here and files written here load in the
# reference. Container layout (all little-endian):
#   uint64 magic=0x112, uint64 reserved=0
#   uint64 n_arrays, then per array (NDArray::Save):
#     uint32 ndim, ndim x uint32 dims          (mshadow TShape::Save)
#     int32 dev_type, int32 dev_id             (Context::Save; written 1,0)
#     int32 type_flag                          (mshadow dtype code)
#     raw contiguous data
#   uint64 n_names, then per name: uint64 len + bytes
# The round-1/2 private MXTPU001 container is still READ for backward
# compatibility with checkpoints written by those rounds.
# --------------------------------------------------------------------------
_DMLC_MAGIC = 0x112
_LEGACY_MAGIC = b"MXTPU001"


def save(fname, data):
    with open(fname, "wb") as f:
        _save_fileobj(f, data)


def save_buffer(data):
    """Serialize NDArrays to bytes (the c_predict param-bytes format)."""
    import io

    f = io.BytesIO()
    _save_fileobj(f, data)
    return f.getvalue()


def _save_fileobj(f, data):
    if isinstance(data, NDArray):
        data = [data]
    if isinstance(data, dict):
        names = list(data.keys())
        arrays = [data[k] for k in names]
    else:
        names = []
        arrays = list(data)
    f.write(struct.pack("<QQ", _DMLC_MAGIC, 0))
    f.write(struct.pack("<Q", len(arrays)))
    for a in arrays:
        arr = np.ascontiguousarray(a.asnumpy())
        if arr.ndim == 0:
            # reference TShape cannot express 0-d (ndim 0 means "none")
            raise MXNetError(
                "cannot save 0-d NDArray in the .params format; "
                "reshape to (1,) first")
        code = mx_dtype_code(arr.dtype)
        if code > 6:
            # bfloat16 (code 12) is a TPU-era extension: the file still
            # round-trips HERE, but reference MXNet's mshadow dtype
            # switch only knows codes 0-6 and would abort loading it
            import warnings

            warnings.warn(
                "saving dtype %s with extension code %d: this .params "
                "file will not load in reference MXNet (cast to float32 "
                "first for cross-compatibility)" % (arr.dtype, code),
                stacklevel=3)
        f.write(struct.pack("<I", arr.ndim))
        f.write(struct.pack("<%dI" % arr.ndim, *arr.shape))
        f.write(struct.pack("<ii", 1, 0))  # Context: cpu(0)
        f.write(struct.pack("<i", code))
        f.write(arr.tobytes())
    f.write(struct.pack("<Q", len(names)))
    for n in names:
        b = n.encode()
        f.write(struct.pack("<Q", len(b)))
        f.write(b)


def load(fname):
    with open(fname, "rb") as f:
        return _load_fileobj(f, fname)


def load_buffer(buf):
    """Deserialize NDArrays from an in-memory bytes buffer (parity: the
    c_predict_api path, MXNDListCreate over param bytes)."""
    import io

    return _load_fileobj(io.BytesIO(buf), "<buffer>")


def _load_fileobj(f, fname):
    head = f.read(8)
    if head == _LEGACY_MAGIC:
        return _load_legacy(f, fname)
    if len(head) < 8 or struct.unpack("<Q", head)[0] != _DMLC_MAGIC:
        raise MXNetError("invalid NDArray file %s" % fname)
    f.read(8)  # reserved
    return _load_dmlc(f, fname)


def _load_dmlc(f, fname):
    from .base import _DTYPE_MX_TO_NP

    (n_arr,) = struct.unpack("<Q", f.read(8))
    arrays = []
    for _ in range(n_arr):
        (ndim,) = struct.unpack("<I", f.read(4))
        if ndim == 0:
            raise MXNetError("%s: empty (none) NDArray entry" % fname)
        shape = struct.unpack("<%dI" % ndim, f.read(4 * ndim))
        f.read(8)  # Context (dev_type, dev_id): arrays land on default ctx
        (code,) = struct.unpack("<i", f.read(4))
        if code not in _DTYPE_MX_TO_NP:
            raise MXNetError("%s: unknown dtype code %d" % (fname, code))
        dt = np.dtype(_DTYPE_MX_TO_NP[code])
        count = int(np.prod(shape))
        arr = np.frombuffer(
            f.read(count * dt.itemsize), dtype=dt).reshape(shape)
        arrays.append(array(arr, dtype=dt))
    (n_names,) = struct.unpack("<Q", f.read(8))
    names = []
    for _ in range(n_names):
        (ln,) = struct.unpack("<Q", f.read(8))
        names.append(f.read(ln).decode())
    if names:
        return dict(zip(names, arrays))
    return arrays


def _load_legacy(f, fname):
    """Round-1/2 MXTPU001 container (magic already consumed)."""
    from .base import _DTYPE_MX_TO_NP

    n_arr, n_names = struct.unpack("<qq", f.read(16))
    names = []
    for _ in range(n_names):
        (ln,) = struct.unpack("<q", f.read(8))
        names.append(f.read(ln).decode())
    arrays = []
    for _ in range(n_arr):
        (code,) = struct.unpack("<q", f.read(8))
        (ndim,) = struct.unpack("<q", f.read(8))
        shape = struct.unpack("<%dq" % ndim, f.read(8 * ndim)) if ndim else ()
        dt = np.dtype(_DTYPE_MX_TO_NP[code])
        count = int(np.prod(shape)) if shape else 1
        arr = np.frombuffer(f.read(count * dt.itemsize), dtype=dt).reshape(shape)
        arrays.append(array(arr, dtype=dt))
    if names:
        return dict(zip(names, arrays))
    return arrays


# --------------------------------------------------------------------------
# op namespace generation — parity with _init_ndarray_module
# (reference ndarray.py:917): every registered op becomes a module function.
# --------------------------------------------------------------------------
def _make_ndarray_function(opdef):
    def fn(*args, **kwargs):
        out = kwargs.pop("out", None)
        kwargs.pop("name", None)
        ctx = kwargs.pop("ctx", None)
        inputs = []
        for a in args:
            if isinstance(a, NDArray):
                inputs.append(a)
            elif isinstance(a, (list, tuple)) and all(
                isinstance(x, NDArray) for x in a
            ):
                inputs.extend(a)
            else:
                inputs.append(a)
        result = imperative_invoke(opdef, inputs, kwargs, out=out)
        if ctx is not None and out is None:
            if isinstance(result, NDArray):
                result = result.copyto(ctx) if result.context != ctx else result
        return result

    fn.__name__ = opdef.name
    fn.__doc__ = opdef.docstring()
    return fn


def _init_ndarray_module():
    module = sys.modules[__name__]
    for name, opdef in list(_registry._REGISTRY.items()):
        if not hasattr(module, name):
            setattr(module, name, _make_ndarray_function(opdef))


def _init_random_module():
    """Expose samplers as mx.random.uniform/normal/... (reference random.py)."""
    rnd = sys.modules[_random.__name__]

    def make(op):
        def fn(*args, **kwargs):
            # reference signature: uniform(low, high, shape, ctx, dtype)
            names = {
                "_sample_uniform": ("low", "high"),
                "_sample_normal": ("loc", "scale"),
                "_sample_gamma": ("alpha", "beta"),
                "_sample_exponential": ("lam",),
                "_sample_poisson": ("lam",),
                "_sample_negbinomial": ("k", "p"),
                "_sample_gennegbinomial": ("mu", "alpha"),
            }[op]
            for n, v in zip(names, args):
                kwargs.setdefault(n, v)
            rest = args[len(names):]
            if rest:
                kwargs.setdefault("shape", rest[0])
            if len(rest) > 1:
                kwargs.setdefault("ctx", rest[1])
            ctx = kwargs.pop("ctx", None)
            out = kwargs.pop("out", None)
            if out is not None:
                kwargs.setdefault("shape", out.shape)
            kwargs.setdefault("shape", (1,))
            r = imperative_invoke(_registry.get(op), [], kwargs, out=out)
            if ctx is not None:
                r = r.copyto(ctx)
            return r

        return fn

    rnd.uniform = make("_sample_uniform")
    rnd.normal = make("_sample_normal")
    rnd.gamma = make("_sample_gamma")
    rnd.exponential = make("_sample_exponential")
    rnd.poisson = make("_sample_poisson")
    rnd.negative_binomial = make("_sample_negbinomial")
    rnd.generalized_negative_binomial = make("_sample_gennegbinomial")


_init_ndarray_module()
_init_random_module()


def imdecode(buf, index=0, flag=1, mean=None, clip_rect=None, out=None,
             **kwargs):
    """Decode an encoded image buffer to an HWC NDArray (parity: the
    reference registers imdecode as an NDArray function,
    src/io/image_io.cc — flag, mean subtraction, clip_rect crop, out).
    Unknown options raise rather than silently change the result."""
    if kwargs:
        raise MXNetError("imdecode: unsupported option(s) %s"
                         % sorted(kwargs))
    from . import image as _image

    img = _image.imdecode(buf, flag=flag)
    if clip_rect is not None:
        x0, y0, x1, y1 = (int(v) for v in clip_rect)
        img = NDArray(img._data[y0:y1, x0:x1])
    if mean is not None:
        mean_arr = mean._data if isinstance(mean, NDArray) else np.asarray(
            mean, np.float32)
        img = NDArray(img._data.astype(np.float32) - mean_arr)
    if out is not None:
        out[:] = img
        return out
    return img
