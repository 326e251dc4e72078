"""Device contexts.

Parity: reference ``python/mxnet/context.py`` (thread-local default-context
stack, ``mx.cpu()/mx.gpu()``). TPU-native: contexts resolve to JAX devices;
``tpu`` is the accelerator device type (the BASELINE.json north star is
"swap ctx=mx.gpu() for ctx=mx.tpu()"), and ``gpu`` is accepted as an alias
for the accelerator so reference scripts run unmodified.
"""
from __future__ import annotations

import threading

from .base import MXNetError

_DEVTYPE2ID = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "tpu": 4}
_DEVID2TYPE = {v: k for k, v in _DEVTYPE2ID.items()}


class Context:
    """A device context (device_type, device_id).

    Unlike the reference's opaque (dev_type, dev_id) pair consumed by mshadow
    streams, a Context here resolves to a concrete ``jax.Device`` and is used
    as the placement target for ``jax.device_put`` / jit compilation.
    """

    _default_ctx = threading.local()
    devtype2id = _DEVTYPE2ID
    devid2type = _DEVID2TYPE

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_type = device_type.device_type
            self.device_id = device_type.device_id
        else:
            if device_type not in _DEVTYPE2ID:
                raise MXNetError("unknown device type %s" % device_type)
            self.device_type = device_type
            self.device_id = device_id

    @property
    def device_typeid(self):
        return _DEVTYPE2ID[self.device_type]

    @property
    def jax_device(self):
        """Resolve to a concrete PROCESS-LOCAL jax.Device (lazily, so
        CPU-only envs work). Local, not global: the reference's cpu(i)/
        gpu(i) numbers devices on this host, and in a multi-controller
        job a global index would hand rank 1 a peer's non-addressable
        device."""
        import jax

        if self.device_type in ("cpu", "cpu_pinned"):
            devs = jax.local_devices(backend="cpu")
        else:  # 'gpu' is an accelerator alias: prefer tpu, fall back to gpu
            devs = None
            for plat in ("tpu", "gpu"):
                try:
                    devs = jax.local_devices(backend=plat)
                    break
                except RuntimeError:
                    continue
            if devs is None:
                # asking for the chip must never hand back the host CPU:
                # a run that silently lands there looks like a chip run
                raise MXNetError(
                    "context %s: JAX has no tpu or gpu backend here "
                    "(platforms present: %s); use mx.cpu() to run on "
                    "the host" % (
                        self,
                        sorted({d.platform for d in jax.devices()})))
        if self.device_id >= len(devs):
            raise MXNetError(
                "context %s: device_id %d out of range (%d %s devices visible)"
                % (self, self.device_id, len(devs), self.device_type)
            )
        return devs[self.device_id]

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    def __enter__(self):
        if not hasattr(Context._default_ctx, "stack"):
            Context._default_ctx.stack = []
        Context._default_ctx.stack.append(Context.current_context())
        Context._default_ctx.value = self
        return self

    def __exit__(self, *args):
        Context._default_ctx.value = Context._default_ctx.stack.pop()

    @staticmethod
    def current_context():
        ctx = getattr(Context._default_ctx, "value", None)
        return ctx if ctx is not None else Context("cpu", 0)

    @staticmethod
    def default_ctx():  # reference-compat alias
        return Context.current_context()


def cpu(device_id=0):
    return Context("cpu", device_id)


def gpu(device_id=0):
    """Accelerator alias — resolves to the TPU on TPU hosts (see Context)."""
    return Context("gpu", device_id)


def tpu(device_id=0):
    return Context("tpu", device_id)


def current_context():
    return Context.current_context()


def num_devices(device_type="tpu"):
    """Count of THIS process's devices (reference num_gpus is per-host)."""
    import jax

    try:
        return len(jax.local_devices(backend=device_type))
    except RuntimeError:
        return 0
