"""``Attention``: multi-head scaled-dot-product attention, grouped
key/value heads, causal or under a window, a learned sink a head, an
output gate and a keep-mask that names each query's keys its optional
inputs. A thin op over the one attention dispatch
``kernels.attention`` (``ops/kernels/flash.py``: the flash kernels where
lowered for the TPU at T >= 128, the materialised reference elsewhere)
and, under a keep-mask, over ``kernels.flash_select`` (the selected pair
where it has tiles for the shapes, ``kernels.kept_attention`` elsewhere).
``gate_output`` is the gate ``LatentAttention`` shares.
``DiffAttention`` (differential attention, Ye et al., arXiv:2410.05258):
two softmax maps a head pair read against the pair's two value heads side
by side, two calls of the same dispatch, their difference under a learned
scalar and a norm over the pair's value columns; its keys and values may
be another node's (the cross form)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ... import telemetry as _tm
from ..registry import OpDef, register
from ..utils import (
    head_width, optional_inputs, required_shape, types_beside_keep)


_OPTIONAL = ("sink", "gate", "keep")


def _kv_heads(attrs):
    return int(attrs.get("num_kv_heads", 0)) or int(attrs["num_heads"])


_M_GATED_LOWERINGS = _tm.counter(
    "attention.gated_lowerings", "Traces of an Attention call site whose "
    "output is gated (with_gate: one per lowering, nothing per step); "
    "labels: heads, dv (the value width a head)")


_M_SELECT_LOWERINGS = _tm.counter(
    "attention.select_lowerings", "Traces of an Attention call site whose "
    "keys a keep-mask names (with_keep: one per lowering, nothing per "
    "step); labels: select=1, heads, group (query heads a key/value "
    "head), impl (kernel: the selected flash pair where the step is "
    "lowered for the TPU; composed: the materialised kept_attention)")


def gate_output(out, gate):
    """``out * sigmoid(gate)``, an element each (a gate per head and
    channel) or broadcast (``LatentAttention``'s one gate a head over the
    head's columns): the sigmoid and the product float32, one rounding
    to ``out``'s dtype."""
    return (out.astype(jnp.float32)
            * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(out.dtype)


def _attention(attrs, ins, is_train):
    from ..kernels import attention

    q, k, v = ins[:3]
    optional = dict(zip(optional_inputs(attrs, _OPTIONAL), ins[3:]))
    heads, kv_heads = int(attrs["num_heads"]), _kv_heads(attrs)
    window = int(attrs.get("window", 0))
    b, t, _ = q.shape

    def split(x, n):
        return x.reshape(b, t, n, x.shape[2] // n)

    if "keep" in optional:
        out = _selected(split(q, heads), split(k, kv_heads),
                        split(v, kv_heads), optional["keep"])
    else:
        with jax.named_scope("window" if window else "full"):
            out = attention(split(q, heads), split(k, kv_heads),
                            split(v, kv_heads),
                            causal=bool(attrs.get("causal", True)),
                            window=window, sink=optional.get("sink"))
    out = out.reshape(b, t, -1)
    if "gate" in optional:
        _M_GATED_LOWERINGS.inc(heads=heads, dv=out.shape[2] // heads)
        with jax.named_scope("gate"):
            out = gate_output(out, optional["gate"])
    return [out]


def _selected(q, k, v, keep):
    """Causal attention of q [B, T, H, D] on k, v [B, T, G, .] over the
    keys ``keep`` [B, T, T] names (0 drops the pair; no gradient), under
    the scope ``select``: ``kernels.flash_select`` where it has tiles for
    the shapes, the materialised ``kernels.kept_attention`` elsewhere;
    the shapes alone choose."""
    from .. import kernels

    heads, kv_heads, d = q.shape[2], k.shape[2], q.shape[3]
    kernel = kernels.flash_select_takes(q.shape[1], heads, kv_heads, d,
                                        v.shape[3], q.dtype)
    _M_SELECT_LOWERINGS.inc(select=1, heads=heads, group=heads // kv_heads,
                            impl="kernel" if kernel else "composed")
    keep = jax.lax.stop_gradient(keep)
    with jax.named_scope("select"):
        if kernel:
            return kernels.flash_select(q, k, v, keep,
                                        interpret=kernels.common.INTERPRET)
        return kernels.kept_attention(q, k, v, keep, d ** -0.5)


def _attention_infer(attrs, in_shapes):
    heads, kv_heads = int(attrs["num_heads"]), _kv_heads(attrs)
    if kv_heads <= 0 or heads % kv_heads:
        raise ValueError("Attention: num_kv_heads=%d must divide "
                         "num_heads=%d" % (kv_heads, heads))
    if int(attrs.get("window", 0)) and not bool(attrs.get("causal", True)):
        raise ValueError("Attention: a window needs causal=True")
    if bool(attrs.get("with_keep", False)) and (
            int(attrs.get("window", 0)) or bool(attrs.get("with_sink", False))
            or not bool(attrs.get("causal", True))):
        raise ValueError(
            "Attention: a keep-mask beside a window, a sink or causal=False "
            "is not implemented")
    q, k, v = (required_shape(shape, "Attention") for shape in in_shapes[:3])
    d = head_width("Attention", "query", q, heads)
    dk = head_width("Attention", "key", k, kv_heads)
    dv = head_width("Attention", "value", v, kv_heads)
    for name, shape in (("key", k), ("value", v)):
        if shape[:2] != q[:2]:
            raise ValueError(
                "Attention: %s %s does not share query's batch and time "
                "%s" % (name, shape, q[:2]))
    if dk != d:
        raise ValueError(
            "Attention: key %s has head_dim %d over %d heads, query %s "
            "has %d over %d" % (k, dk, kv_heads, q, d, heads))
    out = q[:2] + (heads * dv,)
    optional = {"sink": (heads,), "gate": out, "keep": q[:2] + (q[1],)}
    ins = [q, k, v] + [optional[name] for name in optional_inputs(attrs, _OPTIONAL)]
    return ins, [out], []


def _attention_infer_type(attrs, in_types):
    """The keep-mask has a type of its own (int8, ``KeyIndexer``'s);
    every other input and the output share the query's."""
    types, t = types_beside_keep(
        "Attention",
        ["query", "key", "value"] + optional_inputs(attrs, _OPTIONAL),
        in_types)
    return types, [t], []


_attn = OpDef(
    "_contrib_Attention",
    _attention,
    arguments=("query", "key", "value", "sink", "gate", "keep"),
    defaults={"num_heads": 1, "num_kv_heads": 0, "causal": True,
              "window": 0, "with_sink": False, "with_gate": False,
              "with_keep": False},
    infer_shape=_attention_infer,
    infer_type=_attention_infer_type,
    aliases=("Attention",),
    op_class="attn",
)
_attn.list_arguments = lambda attrs=None: (
    ["query", "key", "value"] + optional_inputs(attrs, _OPTIONAL))
register(_attn)


_M_DIFF_LOWERINGS = _tm.counter(
    "attention.diff_lowerings", "Traces of a DiffAttention call site (one "
    "per lowering, nothing per step); labels: heads (query heads: two a "
    "pair), window, cross (1 where the keys and values are another "
    "node's)")
_M_SHARED_KV_READERS = _tm.counter(
    "attention.shared_kv_readers", "Traces of a DiffAttention call site "
    "that reads another node's keys and values (cross=True), one per "
    "reader and lowering, nothing per step: its value is how many nodes "
    "read the shared pair besides the node that made it; labels: source "
    "(the attribute kv_from, the node whose keys and values they are)")


def diff_lambda_init(depth):
    """``0.8 - 0.6 exp(-0.3 depth)``: the published schedule of the
    subtracted map's starting weight, by the layer's number."""
    return 0.8 - 0.6 * math.exp(-0.3 * depth)


def diff_attention(query, key, value, lambda_q1, lambda_k1, lambda_q2,
                   lambda_k2, subln_gamma, num_heads, num_kv_heads,
                   lambda_init, window=0, eps=1e-5, cross=False):
    """query [B, T, H D], key and value [B, T, G D] (H query heads on G
    key/value heads of one width D; query heads 2p, 2p + 1 are pair p's
    ``q1``, ``q2``, key heads 2g, 2g + 1 group g's ``k1``, ``k2``, value
    heads 2g, 2g + 1 side by side group g's one value of width 2 D; pair
    p reads group ``p // (H / G)``), four vectors [D], subln_gamma [2 D]
    -> [B, T, H D]:

        O1 = softmax(q1 k1^T / sqrt(D) + mask) V
        O2 = softmax(q2 k2^T / sqrt(D) + mask) V
        lambda = exp(lambda_q1 . lambda_k1) - exp(lambda_q2 . lambda_k2)
                 + lambda_init
        out = (1 - lambda_init) RMSNorm_2D(O1 - lambda O2) subln_gamma

    causal, under ``window`` keys where it is not 0. The two maps are two
    calls of ``kernels.attention`` with a value twice the query's width
    (scope ``diff/window``, ``diff/full`` or, under ``cross``,
    ``diff/cross``); ``lambda``, the difference, the norm's statistics and
    the factor are float32 (scope ``diff/combine``), one rounding to the
    query's type before ``subln_gamma``."""
    from ..kernels import attention

    f32 = jnp.float32
    b, t, _ = query.shape
    pairs, groups = num_heads // 2, num_kv_heads // 2
    d = query.shape[2] // num_heads
    _M_DIFF_LOWERINGS.inc(heads=num_heads, window=int(window),
                          cross=int(bool(cross)))
    q = query.reshape(b, t, pairs, 2, d)
    k = key.reshape(b, t, groups, 2, d)
    v = value.reshape(b, t, groups, 2 * d)
    with jax.named_scope(
            "diff/" + ("cross" if cross else "window" if window else "full")):
        o1, o2 = (attention(q[:, :, :, i], k[:, :, :, i], v, causal=True,
                            window=int(window)) for i in (0, 1))
    with jax.named_scope("diff/combine"):
        lam = (jnp.exp(jnp.sum(lambda_q1.astype(f32) * lambda_k1.astype(f32)))
               - jnp.exp(jnp.sum(lambda_q2.astype(f32)
                                 * lambda_k2.astype(f32)))
               + lambda_init)
        diff = o1.astype(f32) - lam * o2.astype(f32)
        var = jnp.mean(jnp.square(diff), axis=-1, keepdims=True)
        normed = (diff * (jax.lax.rsqrt(var + eps) * (1.0 - lambda_init))
                  ).astype(query.dtype)
        return (subln_gamma.astype(query.dtype) * normed).reshape(b, t, -1)


def _diff_attention(attrs, ins, is_train):
    heads, kv_heads = int(attrs["num_heads"]), _kv_heads(attrs)
    cross = bool(attrs.get("cross", False))
    if cross:
        _M_SHARED_KV_READERS.inc(source=str(attrs.get("kv_from", "")))
    return [diff_attention(
        *ins, num_heads=heads, num_kv_heads=kv_heads,
        lambda_init=diff_lambda_init(float(attrs["depth"])),
        window=int(attrs.get("window", 0)),
        eps=float(attrs.get("eps", 1e-5)), cross=cross)]


def _diff_attention_infer(attrs, in_shapes):
    heads, kv_heads = int(attrs["num_heads"]), _kv_heads(attrs)
    if heads <= 0 or heads % 2 or kv_heads <= 0 or kv_heads % 2 \
            or heads % kv_heads:
        raise ValueError(
            "DiffAttention: num_heads=%d and num_kv_heads=%d must be even "
            "(two heads a pair) and the key/value heads divide the query "
            "heads" % (heads, kv_heads))
    if float(attrs.get("depth", -1)) < 0:
        raise ValueError(
            "DiffAttention: depth=%r must be the layer's number, 0 or "
            "more (lambda_init reads it)" % (attrs.get("depth"),))
    if int(attrs.get("window", 0)) < 0:
        raise ValueError("DiffAttention: window=%r must be 0 (full) or a "
                         "number of keys" % (attrs.get("window"),))
    q, k, v = (required_shape(shape, "DiffAttention")
               for shape in in_shapes[:3])
    d = head_width("DiffAttention", "query", q, heads)
    for name, shape in (("key", k), ("value", v)):
        if shape[:2] != q[:2]:
            raise ValueError(
                "DiffAttention: %s %s does not share query's batch and "
                "time %s" % (name, shape, q[:2]))
        if head_width("DiffAttention", name, shape, kv_heads) != d:
            raise ValueError(
                "DiffAttention: %s %s has head_dim %d over %d heads, query "
                "%s has %d over %d" % (name, shape, shape[2] // kv_heads,
                                       kv_heads, q, d, heads))
    return [q, k, v] + [(d,)] * 4 + [(2 * d,)], [q], []


register(
    OpDef(
        "_contrib_DiffAttention",
        _diff_attention,
        arguments=("query", "key", "value", "lambda_q1", "lambda_k1",
                   "lambda_q2", "lambda_k2", "subln_gamma"),
        defaults={"num_heads": 2, "num_kv_heads": 0, "depth": -1,
                  "window": 0, "eps": 1e-5, "cross": False, "kv_from": ""},
        infer_shape=_diff_attention_infer,
        aliases=("DiffAttention",),
        op_class="attn",
    )
)
