"""The rotary position embedding: ``rope`` (the op ``RoPE``, and the
rotations inside ``LatentAttention`` and ``KeyIndexer``), half-rotation or
interleaved pairs, a head's whole width or a slice of it, plain or YaRN's
blended frequencies. Whole heads of whole lane rows under ``rotate_half``
take the kernel family ``ops/kernels/rope.py`` (one pass each way); every
other call is the two halves in ``jax.numpy``."""
from __future__ import annotations

import functools

import jax.numpy as jnp
import numpy as np

from ... import telemetry as _tm
from ..registry import OpDef, register
from ..utils import check_rotation, head_width, required_shape


_M_ROPE_LOWERINGS = _tm.counter(
    "rope.lowerings", "Traces of a rope call site (one per lowering, "
    "nothing per step); labels: heads, head_dim, form (one_pass: a whole "
    "head of whole lane rows rotated in one pass each way, "
    "ops/kernels/rope.py; halves: the two halves computed apart and "
    "concatenated)")


def _rope_tables(t, r, theta, interleave=False):
    """cos and sin of positions 0..t-1 times pair i's frequency
    ``theta^(-2i/r)`` (a tuple ``theta``: YaRN's blended frequencies,
    ``kernels.common.rope_inv_freq``), float64 [t, r/2]; under
    ``interleave`` [t, r], a pair's two lanes sharing its angle."""
    from ..kernels.common import rope_inv_freq

    angles = (np.arange(t, dtype=np.float64)[:, None]
              * rope_inv_freq(theta, r)[None, :])
    if interleave:
        angles = np.repeat(angles, 2, axis=-1)
    return np.cos(angles), np.sin(angles)


def _takes_one_pass(x, num_heads, r, offset, interleave):
    """Whether a ``rope`` call is a whole head's rotation over whole lane
    rows that ``kernels.rope_rows`` has a tile for, read off the call's
    own arguments (and, as ``Embedding``'s rule, not in a program the
    partitioner splits: the kernels have no partitioning rule); counts
    the call site."""
    from .. import kernels

    d = x.shape[2] // num_heads
    one_pass = (not interleave and offset == 0 and r == d
                and kernels.rope_rows(num_heads, d, x.shape[1],
                                      x.dtype) is not None
                and not kernels.common.trace_is_partitioned())
    _M_ROPE_LOWERINGS.inc(heads=num_heads, head_dim=d,
                          form="one_pass" if one_pass else "halves")
    return one_pass


@functools.lru_cache(maxsize=None)
def _whole_head_tables(t, d, theta):
    """``[cos | cos]`` and ``[-sin | sin]``, float32 [t, d]: one pair of
    arrays a shape, so that a program's call sites share two constants."""
    cos, sin = _rope_tables(t, d, theta)
    return (np.concatenate([cos, cos], axis=-1).astype(np.float32),
            np.concatenate([-sin, sin], axis=-1).astype(np.float32))


def _rotate_whole_heads(x, num_heads, theta):
    """``rope``'s one-pass form: ``kernels.rotate_heads`` on the tables."""
    from .. import kernels

    c, s = _whole_head_tables(x.shape[1], x.shape[2] // num_heads, theta)
    return kernels.rotate_heads(x, c, s, num_heads,
                                interpret=kernels.common.INTERPRET)


def rope(x, num_heads, theta, rotary_dim=0, offset=0, interleave=False):
    """Rotate ``x`` [B, T, H*D] by its positions 0..T-1, in place: the
    R = ``rotary_dim`` dimensions of a head from ``offset`` on (0: the
    whole head); the dimensions before and past them pass through.
    The pairs are (i, i + R/2) — the ``rotate_half`` convention — or,
    with ``interleave``, (2i, 2i + 1); pair i turns by ``pos *
    theta^(-2i/R)`` either way. Angles, sines and the rotation itself
    are float32; the result is ``x``'s dtype. Whole heads of whole lane
    rows under ``rotate_half`` take ONE pass each way and no half is an
    array (``_takes_one_pass``): results are EQUAL."""
    b, t, hd = x.shape
    d = hd // num_heads
    r = rotary_dim or d - offset
    if _takes_one_pass(x, num_heads, r, offset, interleave):
        return _rotate_whole_heads(x, num_heads, theta)
    cos, sin = (jnp.asarray(table, jnp.float32)[None, :, None, :]
                for table in _rope_tables(t, r, theta, interleave))
    x4 = x.astype(jnp.float32).reshape(b, t, num_heads, d)
    rot = x4[..., offset: offset + r]
    if interleave:
        # the pair's other lane by two lane rotations and a select on
        # the lane's parity (no [.., R/2, 2] reshape: a minor dimension
        # of 2 is a padded layout on the chip): -x[2i+1] at 2i, x[2i]
        # at 2i+1
        even = (np.arange(r) % 2 == 0)[None, None, None, :]
        other = jnp.where(even, -jnp.roll(rot, -1, axis=-1),
                          jnp.roll(rot, 1, axis=-1))
        rotated = [rot * cos + other * sin]
    else:
        x1, x2 = rot[..., : r // 2], rot[..., r // 2:]
        rotated = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
    before = [x4[..., :offset]] if offset else []
    out = jnp.concatenate(before + rotated + [x4[..., offset + r:]],
                          axis=-1)
    return out.reshape(b, t, hd).astype(x.dtype)


def _rope(attrs, ins, is_train):
    return [rope(ins[0], int(attrs["num_heads"]),
                 float(attrs.get("theta", 10000.0)),
                 int(attrs.get("rotary_dim", 0)),
                 int(attrs.get("rotary_offset", 0)),
                 bool(attrs.get("interleave", False)))]


def _rope_infer(attrs, in_shapes):
    data = required_shape(in_shapes[0], "RoPE")
    d = head_width("RoPE", "data", data, int(attrs["num_heads"]))
    offset = int(attrs.get("rotary_offset", 0))
    check_rotation("RoPE", d, int(attrs.get("rotary_dim", 0)) or d - offset,
                   offset)
    return [data], [data], []


register(
    OpDef(
        "_contrib_RoPE",
        _rope,
        arguments=("data",),
        defaults={"num_heads": 1, "theta": 10000.0, "rotary_dim": 0,
                  "rotary_offset": 0, "interleave": False},
        infer_shape=_rope_infer,
        aliases=("RoPE",),
        op_class="attn",
    )
)
