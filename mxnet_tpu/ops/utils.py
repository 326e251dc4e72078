"""Shared shape-inference and param helpers for operator definitions.

Replaces the reference's ``elemwise_op_common.h`` shape-attr machinery and
the per-op dmlc::Parameter structs' normalization logic.
"""
from __future__ import annotations

import numpy as np

from ..base import MXNetError


def as_tuple(v, n=None, name="param"):
    """Normalize an int-or-tuple param to a tuple (kernel=(2,2) style)."""
    if v is None:
        return None
    if isinstance(v, (int, np.integer)):
        v = (int(v),) * (n or 1)
    v = tuple(int(x) for x in v)
    if n is not None and len(v) != n:
        raise MXNetError("%s must have %d elements, got %s" % (name, n, (v,)))
    return v


def broadcast_shape(lhs, rhs, name="broadcast"):
    """Numpy-style broadcast of two shapes."""
    l, r = list(lhs), list(rhs)
    if len(l) < len(r):
        l = [1] * (len(r) - len(l)) + l
    if len(r) < len(l):
        r = [1] * (len(l) - len(r)) + r
    out = []
    for a, b in zip(l, r):
        if a == b or b == 1:
            out.append(a)
        elif a == 1:
            out.append(b)
        else:
            raise MXNetError("%s: incompatible shapes %s %s" % (name, lhs, rhs))
    return tuple(out)


def merge_shapes(a, b, name="shape"):
    """Dim-wise merge with MXNet's 0-means-unknown convention."""
    if a is None:
        return tuple(b) if b is not None else None
    if b is None:
        return tuple(a)
    if len(a) != len(b):
        raise MXNetError("%s: rank mismatch %s vs %s" % (name, a, b))
    out = []
    for x, y in zip(a, b):
        if x == 0:
            out.append(y)
        elif y == 0 or x == y:
            out.append(x)
        else:
            raise MXNetError("%s: incompatible %s vs %s" % (name, a, b))
    return tuple(out)


def shape_known(s):
    return s is not None and all(d > 0 for d in s)


def same_shape_infer(n_in, n_out=1):
    """All inputs and outputs share one shape (elemwise). Handles partial
    shapes (0 = unknown) by dim-wise merging — the lightweight version of
    nnvm's bidirectional elemwise shape attr."""

    def infer(attrs, in_shapes):
        merged = None
        for s in in_shapes:
            merged = merge_shapes(merged, s, "elemwise")
        if merged is None:
            raise MXNetError("cannot infer shape: all inputs unknown")
        return [merged] * len(in_shapes), [merged] * n_out, []

    return infer


def binary_broadcast_infer(attrs, in_shapes):
    lhs, rhs = in_shapes
    if lhs is None or rhs is None:
        raise MXNetError("broadcast op: both input shapes required")
    return [tuple(lhs), tuple(rhs)], [broadcast_shape(lhs, rhs)], []


def reduce_out_shape(ishape, axis, keepdims, exclude=False):
    ishape = tuple(ishape)
    ndim = len(ishape)
    if axis is None or axis == () or axis == []:
        axes = tuple(range(ndim))
    else:
        if isinstance(axis, (int, np.integer)):
            axis = (int(axis),)
        axes = tuple(sorted(a % ndim for a in axis))
        if exclude:
            axes = tuple(a for a in range(ndim) if a not in axes)
    if keepdims:
        return tuple(1 if i in axes else d for i, d in enumerate(ishape)), axes
    out = tuple(d for i, d in enumerate(ishape) if i not in axes)
    return out, axes


def known(shape):
    return shape is not None and all(d is not None and d > 0 for d in shape)


# -- what the transformer families' inference shares -------------------------
def required_shape(shape, what):
    if shape is None:
        raise MXNetError("%s: data shape required" % what)  # resolvable later
    return tuple(shape)


def first_type(what, types):
    """The first of ``types`` that is known: what an op's others follow."""
    known = [t for t in types if t is not None]
    if not known:
        raise MXNetError("%s: cannot infer type" % what)
    return known[0]


def types_beside_keep(what, names, in_types):
    """(input types, the others' type) of an op whose input ``keep`` has a
    type of its own (int8, ``KeyIndexer``'s keep-mask) while every other
    input, ``names`` in order, shares the first known one."""
    t = first_type(what, [
        t for name, t in zip(names, in_types) if name != "keep"])
    return [np.int8 if name == "keep" and x is None
            else t if x is None else x
            for name, x in zip(names, in_types)], t


def head_width(what, name, shape, heads):
    """``shape`` [batch, time, heads * head_dim] -> head_dim. A
    ValueError: a known-but-wrong shape must survive the infer fixpoint
    loop (see SwitchMoE)."""
    if len(shape) != 3 or heads <= 0 or shape[2] % heads:
        raise ValueError(
            "%s: %s must be [batch, time, %d heads * head_dim], got %s"
            % (what, name, heads, shape))
    return shape[2] // heads


def check_rotation(what, d, r, offset):
    if r % 2 or r <= 0 or offset < 0 or offset + r > d:
        raise ValueError(
            "%s: the rotated dimensions must be an even count inside the "
            "head_dim %d, got %d from %d on" % (what, d, r, offset))


def optional_inputs(attrs, names):
    """The inputs an op takes only under ``with_<name>=True``, in order."""
    return [name for name in names
            if bool((attrs or {}).get("with_" + name, False))]
