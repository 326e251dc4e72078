"""What the kernel families share: the one function that picks the
platform's branch, the one value by which a test sends kernels through
the Pallas interpreter from inside a model, the two VMEM figures, and the
helpers more than one family uses. Nothing here imports a family."""
from __future__ import annotations

import contextlib
import contextvars
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

NEG_INF = -1e30
LANES = 128

# The scoped VMEM a Mosaic call gets on the v5e when it sets no limit:
# what a kernel that asks for no more budgets its tiles under (flash
# forward, dq and dkv; the grouped matmul), and the least a kernel that
# states its own count asks for.
VMEM_SCOPED_DEFAULT = 16 * 1024 * 1024
# The most a kernel may ask Mosaic for (``vmem_limit_bytes``): three
# eighths of the v5e's 128 MiB of VMEM. A limit is scoped to its call, so
# what XLA places there round the call is not displaced (the one-pass flash
# backward, the scan, the delta rule, the latent pair).
VMEM_RAISED_LIMIT = 48 * 1024 * 1024

# The one seam for tests: what an op-level caller (``attention``,
# ``parallel/moe.py``, ``ops/transformer.py``, ``ops/nn.py``) passes as
# a kernel entry's ``interpret`` when it traces. A test reaches a kernel
# through a model with
# ``monkeypatch.setattr(common, "INTERPRET", True)``; nothing else sets it.
INTERPRET = False


# Whether the program being traced is partitioned over more than one
# device. The kernels have no partitioning rule (``grouped_matmul``'s
# docstring), and a trace under ``jax.jit`` does not see the mesh its
# operands are placed on: ``ShardedTrainStep`` says it round the trace of
# its step, the one program of ops that the partitioner splits.
_PARTITIONED = contextvars.ContextVar("mxtpu_partitioned", default=False)


@contextlib.contextmanager
def partitioned_trace(devices):
    """Round the trace of a program laid over ``devices`` devices."""
    token = _PARTITIONED.set(devices > 1)
    try:
        yield
    finally:
        _PARTITIONED.reset(token)


def trace_is_partitioned():
    """What an op asks that has a form the partitioner can split beside
    its kernels (``Embedding``'s backward rule)."""
    return _PARTITIONED.get()


def on_tpu(kernels, plain, interpret, *args):
    """``kernels(*args, interpret=False)`` where the computation is lowered
    for the TPU and ``plain(*args)``, the ``jax.lax`` / ``jax.numpy`` form
    of the same signature, on every other platform; under ``interpret``
    the kernels through the Pallas interpreter, whatever the platform.
    The one platform switch of the kernel layer: a step lowered for the
    TPU traces no interpreter copy of a body. Inside a ``custom_vjp`` it
    sits in the forward and in the backward rule, never round the
    differentiated function (which would trace every kernel twice)."""
    if interpret:
        return kernels(*args, interpret=True)
    return lax.platform_dependent(
        *args, tpu=functools.partial(kernels, interpret=False),
        default=plain)


def no_x64():
    """Context manager forcing 32-bit tracing: the framework enables
    jax_enable_x64 globally (reference float64 NDArray parity) but
    Mosaic kernels must stay 32-bit."""
    return jax.enable_x64(False)


def pad_to(x, axis, mult):
    size = x.shape[axis]
    rem = size % mult
    if rem == 0:
        return x, size
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, mult - rem)
    return jnp.pad(x, pad), size


def whole_lanes(width):
    return -(-width // LANES) * LANES


def rope_inv_freq(theta, r):
    """Pair i's turn a position of an R-wide rotation, float64 [R / 2]:
    ``theta^(-2i/R)`` where ``theta`` is a number. Where it is a tuple
    ``(theta, factor, beta_fast, beta_slow, original_max_position)``, YaRN's
    blend (arXiv:2309.00071, as ``deepseek_v3`` applies it): that frequency
    where the pair turns more than ``beta_fast`` times over the original
    context, the frequency over ``factor`` where it turns fewer than
    ``beta_slow`` times, the linear ramp between the two pairs in between; a
    static table at any length."""
    if not isinstance(theta, tuple):
        return 1.0 / (theta ** (np.arange(0, r, 2, dtype=np.float64) / r))
    theta, factor, beta_fast, beta_slow, original = theta
    plain = rope_inv_freq(theta, r)

    def pair_turning(rotations):  # the pair that turns so often, a real
        return (r * np.log(original / (rotations * 2 * np.pi))
                / (2 * np.log(theta)))

    low = max(np.floor(pair_turning(beta_fast)), 0)
    high = min(np.ceil(pair_turning(beta_slow)), r - 1)
    if low == high:
        high += 0.001
    slowed = np.clip((np.arange(r // 2, dtype=np.float64) - low)
                     / (high - low), 0, 1)
    return plain / factor * slowed + plain * (1 - slowed)


def operand_label(dtype):
    return {"bfloat16": "bf16", "float16": "f16",
            "float32": "f32"}.get(jnp.dtype(dtype).name,
                                  jnp.dtype(dtype).name)


# Index arithmetic in a kernel body or an index map goes through
# ``jax.lax`` directly: every jnp call or operator on a tracer is a nested
# jit to trace, a millisecond or two apiece inside a deep training step,
# and these run once per index map per kernel per layer.

def affine(i, mult, plus=0):
    """i * mult + plus on an int32 index."""
    out = lax.mul(i, np.int32(mult))
    return lax.add(out, np.int32(plus)) if plus else out


def dot_highest(lhs, rhs, contract):
    """``lhs`` and ``rhs`` contracted over one dimension each into
    float32, float32 operands at the highest precision: the MXU's default
    is one bf16 pass, whose roundings a cotangent that is a difference of
    sums of the same products (the scan's and the delta rule's log decay)
    cannot see."""
    return lax.dot_general(
        lhs, rhs, ((contract[:1], contract[1:]), ((), ())),
        precision=(lax.Precision.HIGHEST if lhs.dtype == jnp.float32
                   else None),
        preferred_element_type=jnp.float32)


def sum_keepdims(v, axis):
    """``jnp.sum(v, axis, keepdims=True)`` of a [rows, lanes] table."""
    shape = tuple(1 if i == axis else s for i, s in enumerate(v.shape))
    return lax.broadcast_in_dim(lax.reduce_sum(v, (axis,)), shape,
                                (1 - axis,))


def first_chunk():
    """Whether this grid step is the first of a (batch, group, chunk)
    walk: where the scan and the delta rule zero what they carry."""
    return lax.eq(pl.program_id(2), np.int32(0))
