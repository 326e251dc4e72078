"""Index / gather / ordering ops.

Parity: reference ``src/operator/tensor/indexing_op.cc`` (take, batch_take,
one_hot, Embedding, pick, argsort family in ``ordering_op.cc``). The
reference's GPU path uses cub/thrust device sorts (``sort_op-inl.cuh``);
XLA's variadic sort replaces that here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry as _tm
from ..base import MXNetError
from .registry import OpDef, register


# --------------------------------------------------------------------------
# take / batch_take / Embedding
# --------------------------------------------------------------------------
def _take(attrs, ins, is_train):
    a, idx = ins
    axis = int(attrs.get("axis", 0))
    mode = attrs.get("mode", "clip")
    return [jnp.take(a, idx.astype(jnp.int32), axis=axis, mode=mode)]


def _take_infer(attrs, in_shapes):
    a, idx = in_shapes
    if a is None or idx is None:
        raise MXNetError("take: both shapes required")
    axis = int(attrs.get("axis", 0))
    out = tuple(a[:axis]) + tuple(idx) + tuple(a[axis + 1:])
    return [tuple(a), tuple(idx)], [out], []


register(
    OpDef(
        "take",
        _take,
        arguments=("a", "indices"),
        defaults={"axis": 0, "mode": "clip"},
        infer_shape=_take_infer,
    )
)


def _batch_take(attrs, ins, is_train):
    a, idx = ins
    return [jnp.take_along_axis(a, idx.astype(jnp.int32)[:, None], axis=1)[:, 0]]


register(
    OpDef(
        "batch_take",
        _batch_take,
        arguments=("a", "indices"),
        infer_shape=lambda attrs, in_shapes: (
            [tuple(in_shapes[0]), tuple(in_shapes[1])],
            [tuple(in_shapes[1])],
            [],
        ),
    )
)


_M_EMBED_GRAD_LOWERINGS = _tm.counter(
    "embedding.grad_lowerings", "Traces of Embedding's backward rule (one "
    "per lowering, nothing per step); labels: rows (ids looked up), vocab, "
    "width, dtype (the table's), impl (segment_product / segment_sum / "
    "scatter)")


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _lookup(weight, idx, vocab):
    """``weight[idx]`` along the first axis as ``jnp.take`` reads it
    (a negative id counts from the end, one out of range reads NaN).
    Autodiff would transpose the gather to a scatter-add of the
    cotangent's rows into the table, which on the TPU costs up to 14 us
    a row (PERF.md section 5); the rule below sorts the ids and sums each
    id's rows as a sorted segment sum
    (``ops.kernels.sorted_segment_sum``: a product where the step is
    lowered for the TPU): float32 accumulation, one rounding to the
    table's type, every row of the table written, exact zeros where no
    id points."""
    return jnp.take(weight, idx, axis=0)


def _lookup_fwd(weight, idx, vocab):
    return jnp.take(weight, idx, axis=0), idx


def _lookup_bwd(vocab, idx, g):
    from . import kernels

    width = g.shape[-1]
    rows = g.reshape(-1, width)
    m = rows.shape[0]
    if kernels.common.trace_is_partitioned():
        # the kernels have no partitioning rule: a program split over
        # devices keeps the scatter-add, which the partitioner knows
        impl = "scatter"
    elif kernels.gmm_runs_kernel(m, g.dtype):
        impl = "segment_product"
    else:
        impl = "segment_sum"
    _M_EMBED_GRAD_LOWERINGS.inc(rows=m, vocab=vocab, width=width,
                                dtype=jnp.dtype(g.dtype).name, impl=impl)
    if impl == "scatter":
        take = functools.partial(jnp.take, indices=idx, axis=0)
        return jax.linear_transpose(
            take, jax.ShapeDtypeStruct((vocab, width), g.dtype))(g) + (None,)
    # what ``jnp.take`` does with an id: below zero it counts from the
    # end, and one still outside the table reads no row, so its
    # cotangent belongs to no segment (and sorts last)
    ids = idx.reshape(m)
    ids = jnp.where(ids < 0, ids + vocab, ids)
    ids = jnp.where((ids < 0) | (ids >= vocab), vocab, ids)
    ids, order = jax.lax.sort((ids, jax.lax.iota(jnp.int32, m)), num_keys=1)
    rows = rows.at[order].get(mode="promise_in_bounds")
    return (kernels.sorted_segment_sum(
        rows, ids, vocab, interpret=kernels.common.INTERPRET), None)


_lookup.defvjp(_lookup_fwd, _lookup_bwd)


def _embedding(attrs, ins, is_train):
    data, weight = ins
    idx = data.astype(jnp.int32)
    return [_lookup(weight, idx, weight.shape[0])]


def _embedding_infer(attrs, in_shapes):
    dshape, wshape = in_shapes
    if dshape is None:
        raise MXNetError("Embedding: data shape required")
    inp = int(attrs["input_dim"])
    out = int(attrs["output_dim"])
    wshape = (inp, out)
    return [tuple(dshape), wshape], [tuple(dshape) + (out,)], []


def _embedding_infer_type(attrs, in_types):
    """The output has the TABLE's dtype, never the indices': ``dtype``
    (later MXNet's parameter, default float32) unless the weight's type
    is already known. Without this a bf16 model fed integer or float32
    token ids would infer every downstream weight in the ids' type."""
    from ..base import np_dtype

    data_t, weight_t = in_types
    if weight_t is None:
        weight_t = np_dtype(attrs.get("dtype", "float32"))
    return ([data_t if data_t is not None else np.float32, weight_t],
            [weight_t], [])


register(
    OpDef(
        "Embedding",
        _embedding,
        arguments=("data", "weight"),
        defaults={"input_dim": 0, "output_dim": 0, "dtype": "float32"},
        infer_shape=_embedding_infer,
        infer_type=_embedding_infer_type,
        op_class="embed",
        doc="""weight[data] along the first axis: data (any shape, cast to int32)
-> data.shape + (output_dim,) in the table's type. An id below zero counts
from the table's end; one outside the table reads NaN and has no gradient.
The table's gradient is dense, in the table's type: each id's cotangent
rows summed in float32 and rounded once, exact zeros where no id points.
It is built without a scatter: the ids are sorted and the rows summed as a
sorted segment sum (ops/kernels/gmm.py sorted_segment_sum: a product over
a 0 / 1 table through the grouped matmul's wgrad kernel where the step is
lowered for the TPU, jax.ops.segment_sum elsewhere); a step partitioned
over more than one device keeps the scatter-add that autodiff gives, which
accumulates in the table's type.""",
    )
)


# --------------------------------------------------------------------------
# one_hot / pick
# --------------------------------------------------------------------------
def _one_hot(attrs, ins, is_train):
    depth = int(attrs["depth"])
    on = float(attrs.get("on_value", 1.0))
    off = float(attrs.get("off_value", 0.0))
    from ..base import np_dtype

    dt = np_dtype(attrs.get("dtype", "float32"))
    oh = jax.nn.one_hot(ins[0].astype(jnp.int32), depth)
    return [(oh * (on - off) + off).astype(dt)]


register(
    OpDef(
        "one_hot",
        _one_hot,
        arguments=("indices",),
        defaults={"depth": 1, "on_value": 1.0, "off_value": 0.0, "dtype": "float32"},
        infer_shape=lambda attrs, in_shapes: (
            [tuple(in_shapes[0])],
            [tuple(in_shapes[0]) + (int(attrs["depth"]),)],
            [],
        ),
    )
)


def _pick(attrs, ins, is_train):
    data, index = ins
    axis = attrs.get("axis", -1)
    axis = int(axis) if axis is not None else -1
    keepdims = bool(attrs.get("keepdims", False))
    idx = jnp.expand_dims(index.astype(jnp.int32), axis=axis)
    out = jnp.take_along_axis(data, idx, axis=axis)
    if not keepdims:
        out = jnp.squeeze(out, axis=axis)
    return [out]


def _pick_infer(attrs, in_shapes):
    dshape = list(in_shapes[0])
    axis = attrs.get("axis", -1)
    axis = int(axis) if axis is not None else -1
    axis = axis % len(dshape)
    keepdims = bool(attrs.get("keepdims", False))
    ishape = dshape[:axis] + dshape[axis + 1:]
    out = list(dshape)
    if keepdims:
        out[axis] = 1
    else:
        out = ishape
    return [tuple(in_shapes[0]), tuple(ishape)], [tuple(out)], []


register(
    OpDef(
        "pick",
        _pick,
        arguments=("data", "index"),
        defaults={"axis": -1, "keepdims": False},
        infer_shape=_pick_infer,
        aliases=("choose_element_0index",),
    )
)


# --------------------------------------------------------------------------
# sort / argsort / topk (reference ordering_op.cc)
# --------------------------------------------------------------------------
def _resolve_axis(attrs, ndim):
    axis = attrs.get("axis", -1)
    if axis is None:
        return None
    return int(axis) % ndim


def _sort(attrs, ins, is_train):
    axis = _resolve_axis(attrs, ins[0].ndim)
    x = ins[0].reshape(-1) if axis is None else ins[0]
    axis = 0 if _resolve_axis(attrs, ins[0].ndim) is None else axis
    out = jnp.sort(x, axis=axis)
    if not bool(attrs.get("is_ascend", True)):
        out = jnp.flip(out, axis=axis)
    return [out]


register(
    OpDef(
        "sort",
        _sort,
        arguments=("data",),
        defaults={"axis": -1, "is_ascend": True},
    )
)


def _argsort(attrs, ins, is_train):
    axis = _resolve_axis(attrs, ins[0].ndim)
    x = ins[0].reshape(-1) if axis is None else ins[0]
    ax = 0 if axis is None else axis
    out = jnp.argsort(x, axis=ax)
    if not bool(attrs.get("is_ascend", True)):
        out = jnp.flip(out, axis=ax)
    return [out.astype(ins[0].dtype)]


register(
    OpDef(
        "argsort",
        _argsort,
        arguments=("data",),
        defaults={"axis": -1, "is_ascend": True},
    )
)


def _topk_out_shapes(attrs, ishape):
    axis = attrs.get("axis", -1)
    axis = len(ishape) - 1 if axis is None else int(axis) % len(ishape)
    k = int(attrs.get("k", 1))
    ret_typ = attrs.get("ret_typ", "indices")
    s = list(ishape)
    if ret_typ != "mask":
        s[axis] = k
    n_out = 2 if ret_typ == "both" else 1
    return [tuple(s)] * n_out, axis, k, ret_typ


def _topk(attrs, ins, is_train):
    out_shapes, axis, k, ret_typ = _topk_out_shapes(attrs, ins[0].shape)
    x = ins[0]
    is_ascend = bool(attrs.get("is_ascend", False))
    key = -x if not is_ascend else x
    idx = jnp.argsort(key, axis=axis)
    idx = jax.lax.slice_in_dim(idx, 0, k, axis=axis)
    vals = jnp.take_along_axis(x, idx, axis=axis)
    if ret_typ == "value":
        return [vals]
    if ret_typ == "indices":
        return [idx.astype(x.dtype)]
    if ret_typ == "mask":
        m = jnp.zeros(x.shape, x.dtype)
        m = jnp.put_along_axis(m, idx, jnp.ones_like(vals), axis=axis, inplace=False)
        return [m]
    return [vals, idx.astype(x.dtype)]


def _topk_infer(attrs, in_shapes):
    out_shapes, _, _, _ = _topk_out_shapes(attrs, in_shapes[0])
    return [tuple(in_shapes[0])], out_shapes, []


_topk_def = OpDef(
    "topk",
    _topk,
    arguments=("data",),
    defaults={"axis": -1, "k": 1, "ret_typ": "indices", "is_ascend": False},
    infer_shape=_topk_infer,
)
_topk_def.list_outputs = lambda attrs=None: (
    ["value", "indices"]
    if (attrs or {}).get("ret_typ") == "both"
    else ["output"]
)
register(_topk_def)


# --------------------------------------------------------------------------
# pick_log_softmax — a row's log-probability of its label, with a backward
# rule of its own (down here so that no line above moves: ``_lookup_bwd``
# is on a kernel's call path)
# --------------------------------------------------------------------------
_M_PICKED_LOGP_TRACES = _tm.counter(
    "lm.picked_logp_traces", "Traces of a pick_log_softmax node (one per "
    "node and lowering, nothing per step); labels: rows, vocab")
_M_MTP_MODULES = _tm.counter(
    "lm.mtp_modules", "Traces of a pick_log_softmax node that reads a "
    "multi-token-prediction module's stream (ahead > 1: the label it picks "
    "lies that many positions ahead; one per node and lowering, nothing "
    "per step); labels: ahead")


def _is_label(logits, labels):
    """[..., V] bool, true at each row's label: an iota compared with the
    label, so neither the pick nor its transpose is a gather or a
    scatter."""
    return jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, logits.ndim - 1) == labels[..., None]


@jax.custom_vjp
def picked_log_prob(logits, labels):
    """``log_softmax(logits)[..., labels]`` along the last axis: logits
    [..., V], labels [...] int32 in ``[0, V)`` -> [...] in the logits'
    type, ``logits[label] - logsumexp(logits)``. Autodiff of
    ``log_softmax`` + ``pick`` gathers from a float32 [..., V] table of
    log-probabilities (which the chip writes to HBM for the gather's
    sake: 1.9 ms a step at [4096, 50304], PERF.md section 7) and
    transposes the gather to a scatter-add; this rule keeps the logits it
    was given, the labels and the rows' ``lse``, sums the picked logit in
    the pass that sums the exponentials, and its backward is ONE
    elementwise expression, ``(onehot(label) - exp(logits - lse)) * g``:
    the exact derivative, softmax less one-hot. Where the logits are a
    cast of a narrower product inside one program, XLA fuses the cast into
    each pass and only the product itself lives from the forward to the
    backward."""
    return _picked_fwd(logits, labels)[0]


def _picked_fwd(logits, labels):
    top = jnp.max(logits, axis=-1)
    lse = top + jnp.log(jnp.sum(jnp.exp(logits - top[..., None]), axis=-1))
    picked = jnp.sum(jnp.where(_is_label(logits, labels), logits, 0), axis=-1)
    return picked - lse, (logits, labels, lse)


def _picked_bwd(res, g):
    logits, labels, lse = res
    onehot = _is_label(logits, labels).astype(logits.dtype)
    return (onehot - jnp.exp(logits - lse[..., None])) * g[..., None], None


picked_log_prob.defvjp(_picked_fwd, _picked_bwd)


def _pick_log_softmax(attrs, ins, is_train):
    data, index = ins
    vocab = data.shape[-1]
    _M_PICKED_LOGP_TRACES.inc(rows=data.size // vocab, vocab=vocab)
    ahead = int(attrs.get("ahead", 1))
    if ahead > 1:
        _M_MTP_MODULES.inc(ahead=ahead)
    # a label below zero counts from the end, as ``pick`` reads it
    labels = index.astype(jnp.int32)
    return [picked_log_prob(data, jnp.where(labels < 0, labels + vocab,
                                            labels))]


register(
    OpDef(
        "pick_log_softmax",
        _pick_log_softmax,
        arguments=("data", "index"),
        defaults={"ahead": 1},
        infer_shape=lambda attrs, in_shapes: (
            [tuple(in_shapes[0]), tuple(in_shapes[0][:-1])],
            [tuple(in_shapes[0][:-1])],
            [],
        ),
        doc="""pick(log_softmax(data, axis=-1), index, axis=-1) as one node: data
[..., V], index [...] (cast to int32; below zero counts from the end) ->
[...] in data's type, data[index] - logsumexp(data). A language model's
head reads its float32 logits through it (models/lm_blocks.py
head_and_loss, node lm_head_pick). The value and the gradient are those
of the two ops under autodiff (the gradient is softmax less one-hot, times
the cotangent), but the rule is the op's own: the forward writes nothing
of [..., V]; kept for the backward are data as it was given, index and one
number a row (the log of the row's sum of exponentials); the backward is
one elementwise expression over [..., V], where autodiff of the two ops
gathers from a [..., V] table of log-probabilities written for the purpose
and transposes the gather to a scatter-add.
An index outside [-V, V) picks nothing: the row reads -logsumexp(data).
ahead (default 1) changes no number: it says how many positions ahead of
its row the label lies, and a node with ahead > 1 (a multi-token-prediction
module's head) counts in lm.mtp_modules where it is traced.""",
    )
)
