"""Matrix / layout / slicing ops.

Parity: reference ``src/operator/tensor/matrix_op.cc`` (dot, batch_dot,
transpose, Reshape incl. the 0/-1/-2/-3/-4 special codes, Flatten,
expand_dims, slice, slice_axis, clip, repeat, tile, reverse),
``concat.cc``/``slice_channel.cc`` (layer-op generation in the reference),
``swapaxis.cc``, ``pad.cc``, and ``control_flow_op.cc`` (where).

dot/batch_dot lower to ``jax.lax.dot_general`` → the MXU systolic array;
`preferred_element_type=float32` keeps bf16 inputs accumulating in fp32,
matching TPU best practice rather than the reference's SGEMM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..base import MXNetError
from .registry import OpDef, register
from .utils import as_tuple


# --------------------------------------------------------------------------
# Reshape with MXNet special codes (reference matrix_op-inl.h ReshapeParam)
# --------------------------------------------------------------------------
def _infer_reshape_target(ishape, target):
    ishape = tuple(ishape)
    if not target:
        raise MXNetError("Reshape: shape attr required")
    out = []
    src = list(ishape)
    i = 0  # index into src
    t = 0
    target = list(target)
    while t < len(target):
        d = target[t]
        if d == 0:
            out.append(src[i])
            i += 1
        elif d == -1:
            out.append(-1)
            i += 1  # placeholder; fixed below
        elif d == -2:
            out.extend(src[i:])
            i = len(src)
        elif d == -3:
            out.append(src[i] * src[i + 1])
            i += 2
        elif d == -4:
            d1, d2 = target[t + 1], target[t + 2]
            if d1 == -1:
                d1 = src[i] // d2
            if d2 == -1:
                d2 = src[i] // d1
            out.extend([d1, d2])
            i += 1
            t += 2
        else:
            out.append(int(d))
            i += 1
        t += 1
    if out.count(-1) > 1:
        raise MXNetError("Reshape: more than one -1")
    if -1 in out:
        knownprod = int(np.prod([d for d in out if d != -1])) or 1
        total = int(np.prod(ishape)) if ishape else 1
        out[out.index(-1)] = total // knownprod
    if int(np.prod(out) if out else 1) != int(np.prod(ishape) if ishape else 1):
        raise MXNetError("Reshape: size mismatch %s -> %s" % (ishape, out))
    return tuple(out)


def _reshape_fcompute(attrs, ins, is_train):
    tgt = attrs.get("shape") or attrs.get("target_shape")
    if isinstance(tgt, (int, np.integer)):
        tgt = (int(tgt),)
    return [ins[0].reshape(_infer_reshape_target(ins[0].shape, tgt))]


def _reshape_infer(attrs, in_shapes):
    ishape = in_shapes[0]
    if ishape is None:
        raise MXNetError("Reshape: input shape required")
    tgt = attrs.get("shape") or attrs.get("target_shape")
    if isinstance(tgt, (int, np.integer)):
        tgt = (int(tgt),)
    return [tuple(ishape)], [_infer_reshape_target(ishape, tgt)], []


register(
    OpDef(
        "Reshape",
        _reshape_fcompute,
        arguments=("data",),
        defaults={"shape": None},
        infer_shape=_reshape_infer,
        aliases=("reshape",),
    )
)

register(
    OpDef(
        "Flatten",
        lambda attrs, ins, is_train: [
            ins[0].reshape(ins[0].shape[0], -1)
        ],
        arguments=("data",),
        infer_shape=lambda attrs, in_shapes: (
            [tuple(in_shapes[0])],
            [(in_shapes[0][0], int(np.prod(in_shapes[0][1:])))],
            [],
        ),
        aliases=("flatten",),
    )
)


# --------------------------------------------------------------------------
# transpose / expand_dims / SwapAxis
# --------------------------------------------------------------------------
def _transpose(attrs, ins, is_train):
    axes = attrs.get("axes") or None
    return [jnp.transpose(ins[0], axes)]


def _transpose_infer(attrs, in_shapes):
    ishape = in_shapes[0]
    axes = attrs.get("axes") or tuple(reversed(range(len(ishape))))
    return [tuple(ishape)], [tuple(ishape[a] for a in axes)], []


register(
    OpDef(
        "transpose",
        _transpose,
        arguments=("data",),
        defaults={"axes": ()},
        infer_shape=_transpose_infer,
    )
)

register(
    OpDef(
        "expand_dims",
        lambda attrs, ins, is_train: [jnp.expand_dims(ins[0], int(attrs["axis"]))],
        arguments=("data",),
        defaults={"axis": 0},
        infer_shape=lambda attrs, in_shapes: (
            [tuple(in_shapes[0])],
            [
                tuple(
                    list(in_shapes[0])[: int(attrs["axis"]) % (len(in_shapes[0]) + 1)]
                    + [1]
                    + list(in_shapes[0])[int(attrs["axis"]) % (len(in_shapes[0]) + 1):]
                )
            ],
            [],
        ),
    )
)


def _swapaxis_infer(attrs, in_shapes):
    s = list(in_shapes[0])
    a, b = int(attrs.get("dim1", 0)), int(attrs.get("dim2", 0))
    s[a], s[b] = s[b], s[a]
    return [tuple(in_shapes[0])], [tuple(s)], []


register(
    OpDef(
        "SwapAxis",
        lambda attrs, ins, is_train: [
            jnp.swapaxes(ins[0], int(attrs.get("dim1", 0)), int(attrs.get("dim2", 0)))
        ],
        arguments=("data",),
        defaults={"dim1": 0, "dim2": 0},
        infer_shape=_swapaxis_infer,
        aliases=("swapaxes",),
    )
)


# --------------------------------------------------------------------------
# dot / batch_dot — the MXU path
# --------------------------------------------------------------------------
def _dot(attrs, ins, is_train):
    a, b = ins
    if attrs.get("transpose_a"):
        a = a.T if a.ndim == 2 else jnp.transpose(a)
    if attrs.get("transpose_b"):
        b = b.T if b.ndim == 2 else jnp.transpose(b)
    if a.ndim == 1 and b.ndim == 1:
        return [jnp.dot(a, b).reshape(1)]
    out = jnp.dot(a, b, preferred_element_type=jnp.float32)
    return [out.astype(jnp.result_type(ins[0], ins[1]))]


def _dot_infer(attrs, in_shapes):
    a, b = in_shapes
    if a is None or b is None:
        raise MXNetError("dot: both input shapes required")
    a = tuple(reversed(a)) if attrs.get("transpose_a") else tuple(a)
    b = tuple(reversed(b)) if attrs.get("transpose_b") else tuple(b)
    if len(a) == 1 and len(b) == 1:
        out = (1,)
    else:
        if a[-1] != b[0]:
            raise MXNetError("dot: shape mismatch %s %s" % (in_shapes[0], in_shapes[1]))
        out = a[:-1] + b[1:]
    return [tuple(in_shapes[0]), tuple(in_shapes[1])], [out], []


register(
    OpDef(
        "dot",
        _dot,
        arguments=("lhs", "rhs"),
        defaults={"transpose_a": False, "transpose_b": False},
        infer_shape=_dot_infer,
    )
)


def _batch_dot(attrs, ins, is_train):
    a, b = ins
    if attrs.get("transpose_a"):
        a = jnp.swapaxes(a, -1, -2)
    if attrs.get("transpose_b"):
        b = jnp.swapaxes(b, -1, -2)
    out = jnp.matmul(a, b, preferred_element_type=jnp.float32)
    return [out.astype(jnp.result_type(ins[0], ins[1]))]


def _batch_dot_infer(attrs, in_shapes):
    a, b = [list(s) for s in in_shapes]
    if attrs.get("transpose_a"):
        a[-1], a[-2] = a[-2], a[-1]
    if attrs.get("transpose_b"):
        b[-1], b[-2] = b[-2], b[-1]
    if a[-1] != b[-2] or a[:-2] != b[:-2]:
        raise MXNetError("batch_dot: shape mismatch %s %s" % tuple(in_shapes))
    return (
        [tuple(in_shapes[0]), tuple(in_shapes[1])],
        [tuple(a[:-1] + [b[-1]])],
        [],
    )


register(
    OpDef(
        "batch_dot",
        _batch_dot,
        arguments=("lhs", "rhs"),
        defaults={"transpose_a": False, "transpose_b": False},
        infer_shape=_batch_dot_infer,
    )
)


# --------------------------------------------------------------------------
# slice / slice_axis / clip / repeat / tile / reverse
# --------------------------------------------------------------------------
def _norm_begin_end(shape, begin, end):
    begin = list(begin)
    end = list(end)
    out_b, out_e = [], []
    for i, dim in enumerate(shape):
        b = begin[i] if i < len(begin) and begin[i] is not None else 0
        e = end[i] if i < len(end) and end[i] is not None else dim
        if b < 0:
            b += dim
        if e < 0:
            e += dim
        out_b.append(int(b))
        out_e.append(int(min(e, dim)))
    return out_b, out_e


def _slice(attrs, ins, is_train):
    b, e = _norm_begin_end(ins[0].shape, attrs["begin"], attrs["end"])
    idx = tuple(slice(bb, ee) for bb, ee in zip(b, e))
    return [ins[0][idx]]


def _slice_infer(attrs, in_shapes):
    b, e = _norm_begin_end(in_shapes[0], attrs["begin"], attrs["end"])
    return (
        [tuple(in_shapes[0])],
        [tuple(ee - bb for bb, ee in zip(b, e))],
        [],
    )


register(
    OpDef(
        "slice",
        _slice,
        arguments=("data",),
        defaults={"begin": (), "end": ()},
        infer_shape=_slice_infer,
        aliases=("crop",),
    )
)


def _slice_axis(attrs, ins, is_train):
    ax = int(attrs["axis"])
    dim = ins[0].shape[ax]
    b = int(attrs.get("begin", 0))
    e = attrs.get("end")
    e = dim if e is None else int(e)
    if b < 0:
        b += dim
    if e < 0:
        e += dim
    idx = [slice(None)] * ins[0].ndim
    idx[ax] = slice(b, e)
    return [ins[0][tuple(idx)]]


def _slice_axis_infer(attrs, in_shapes):
    s = list(in_shapes[0])
    ax = int(attrs["axis"])
    dim = s[ax]
    b = int(attrs.get("begin", 0))
    e = attrs.get("end")
    e = dim if e is None else int(e)
    if b < 0:
        b += dim
    if e < 0:
        e += dim
    s[ax] = e - b
    return [tuple(in_shapes[0])], [tuple(s)], []


register(
    OpDef(
        "slice_axis",
        _slice_axis,
        arguments=("data",),
        defaults={"axis": 0, "begin": 0, "end": None},
        infer_shape=_slice_axis_infer,
    )
)

register(
    OpDef(
        "clip",
        lambda attrs, ins, is_train: [
            jnp.clip(ins[0], float(attrs["a_min"]), float(attrs["a_max"]))
        ],
        arguments=("data",),
        defaults={"a_min": 0.0, "a_max": 1.0},
        op_class="act",
    )
)


def _repeat(attrs, ins, is_train):
    ax = attrs.get("axis")
    reps = int(attrs["repeats"])
    if ax is None:
        return [jnp.repeat(ins[0].reshape(-1), reps)]
    return [jnp.repeat(ins[0], reps, axis=int(ax))]


def _repeat_infer(attrs, in_shapes):
    ax = attrs.get("axis")
    reps = int(attrs["repeats"])
    if ax is None:
        out = (int(np.prod(in_shapes[0])) * reps,)
    else:
        s = list(in_shapes[0])
        s[int(ax)] *= reps
        out = tuple(s)
    return [tuple(in_shapes[0])], [out], []


register(
    OpDef(
        "repeat",
        _repeat,
        arguments=("data",),
        defaults={"repeats": 1, "axis": None},
        infer_shape=_repeat_infer,
    )
)


def _tile_infer(attrs, in_shapes):
    reps = as_tuple(attrs["reps"])
    s = list(in_shapes[0])
    if len(reps) < len(s):
        reps = (1,) * (len(s) - len(reps)) + reps
    if len(s) < len(reps):
        s = [1] * (len(reps) - len(s)) + s
    return [tuple(in_shapes[0])], [tuple(a * b for a, b in zip(s, reps))], []


register(
    OpDef(
        "tile",
        lambda attrs, ins, is_train: [jnp.tile(ins[0], as_tuple(attrs["reps"]))],
        arguments=("data",),
        defaults={"reps": (1,)},
        infer_shape=_tile_infer,
    )
)

register(
    OpDef(
        "reverse",
        lambda attrs, ins, is_train: [jnp.flip(ins[0], as_tuple(attrs["axis"]))],
        arguments=("data",),
        defaults={"axis": (0,)},
        aliases=("flip",),
    )
)


# --------------------------------------------------------------------------
# Concat / SliceChannel (multi-in / multi-out layer ops)
# --------------------------------------------------------------------------
def _concat_infer(attrs, in_shapes):
    dim = int(attrs.get("dim", 1))
    known = [s for s in in_shapes if s is not None]
    if not known:
        raise MXNetError("Concat: need at least one known shape")
    base = list(known[0])
    total = 0
    completed = []
    for s in in_shapes:
        if s is None:
            raise MXNetError("Concat: all input shapes required")
        total += s[dim]
        completed.append(tuple(s))
    out = list(base)
    out[dim] = total
    return completed, [tuple(out)], []


register(
    OpDef(
        "Concat",
        lambda attrs, ins, is_train: [
            jnp.concatenate(ins, axis=int(attrs.get("dim", 1)))
        ],
        arguments=("data",),
        key_var_num_args="num_args",
        defaults={"dim": 1, "num_args": 1},
        infer_shape=_concat_infer,
        aliases=("concat",),
    )
)


def _slice_channel(attrs, ins, is_train):
    n = int(attrs["num_outputs"])
    ax = int(attrs.get("axis", 1))
    parts = jnp.split(ins[0], n, axis=ax)
    if attrs.get("squeeze_axis"):
        parts = [jnp.squeeze(p, axis=ax) for p in parts]
    return parts


def _slice_channel_infer(attrs, in_shapes):
    n = int(attrs["num_outputs"])
    ax = int(attrs.get("axis", 1))
    s = list(in_shapes[0])
    if s[ax] % n != 0:
        raise MXNetError("SliceChannel: axis %d (%d) not divisible by %d" % (ax, s[ax], n))
    s[ax] //= n
    if attrs.get("squeeze_axis"):
        if s[ax] != 1:
            raise MXNetError("SliceChannel: squeeze_axis needs size-1 result")
        s = s[:ax] + s[ax + 1:]
    return [tuple(in_shapes[0])], [tuple(s)] * n, []


register(
    OpDef(
        "SliceChannel",
        _slice_channel,
        arguments=("data",),
        outputs=("output",),  # dynamic count via list_outputs override below
        defaults={"num_outputs": 1, "axis": 1, "squeeze_axis": False},
        infer_shape=_slice_channel_infer,
        aliases=("split",),
    )
)
def _slice_channel_outputs(attrs=None):
    n = int((attrs or {}).get("num_outputs", 1))
    return ["output%d" % i for i in range(n)]


from .registry import get as _get_op

_get_op("SliceChannel").list_outputs = _slice_channel_outputs


# --------------------------------------------------------------------------
# Pad (reference pad.cc) — NCHW/NCDHW edge/constant/reflect padding
# --------------------------------------------------------------------------
def _pad(attrs, ins, is_train):
    pw = as_tuple(attrs["pad_width"])
    mode = attrs.get("mode", "constant")
    pad_pairs = [(pw[2 * i], pw[2 * i + 1]) for i in range(len(pw) // 2)]
    if mode == "constant":
        return [
            jnp.pad(
                ins[0],
                pad_pairs,
                mode="constant",
                constant_values=float(attrs.get("constant_value", 0.0)),
            )
        ]
    jmode = {"edge": "edge", "reflect": "reflect"}[mode]
    return [jnp.pad(ins[0], pad_pairs, mode=jmode)]


def _pad_infer(attrs, in_shapes):
    pw = as_tuple(attrs["pad_width"])
    s = list(in_shapes[0])
    out = [d + pw[2 * i] + pw[2 * i + 1] for i, d in enumerate(s)]
    return [tuple(in_shapes[0])], [tuple(out)], []


register(
    OpDef(
        "Pad",
        _pad,
        arguments=("data",),
        defaults={"mode": "constant", "pad_width": (), "constant_value": 0.0},
        infer_shape=_pad_infer,
        aliases=("pad",),
    )
)


# --------------------------------------------------------------------------
# where (reference control_flow_op.cc)
# --------------------------------------------------------------------------
def _where_infer(attrs, in_shapes):
    cond, x, y = in_shapes
    shp = tuple(x if x is not None else y)
    return [tuple(cond) if cond else shp, shp, shp], [shp], []


register(
    OpDef(
        "where",
        lambda attrs, ins, is_train: [
            jnp.where(
                (ins[0] != 0)
                if ins[0].ndim == ins[1].ndim
                else (ins[0] != 0).reshape(
                    ins[0].shape + (1,) * (ins[1].ndim - ins[0].ndim)
                ),
                ins[1],
                ins[2],
            )
        ],
        arguments=("condition", "x", "y"),
        infer_shape=_where_infer,
    )
)
