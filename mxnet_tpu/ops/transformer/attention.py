"""``Attention``: multi-head scaled-dot-product attention, grouped
key/value heads, causal or under a window, a learned sink a head and an
output gate its optional inputs. A thin op over the one attention dispatch
``kernels.attention`` (``ops/kernels/flash.py``: the flash kernels where
lowered for the TPU at T >= 128, the materialised reference elsewhere).
``gate_output`` is the gate ``LatentAttention`` shares."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ... import telemetry as _tm
from ..registry import OpDef, register
from ..utils import head_width, optional_inputs, required_shape


_OPTIONAL = ("sink", "gate")


def _kv_heads(attrs):
    return int(attrs.get("num_kv_heads", 0)) or int(attrs["num_heads"])


_M_GATED_LOWERINGS = _tm.counter(
    "attention.gated_lowerings", "Traces of an Attention call site whose "
    "output is gated (with_gate: one per lowering, nothing per step); "
    "labels: heads, dv (the value width a head)")


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


def _attention_infer(attrs, in_shapes):
    heads, kv_heads = int(attrs["num_heads"]), _kv_heads(attrs)
    if kv_heads <= 0 or heads % kv_heads:
        raise ValueError("Attention: num_kv_heads=%d must divide "
                         "num_heads=%d" % (kv_heads, heads))
    if int(attrs.get("window", 0)) and not bool(attrs.get("causal", True)):
        raise ValueError("Attention: a window needs causal=True")
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
    optional = {"sink": (heads,), "gate": out}
    ins = [q, k, v] + [optional[name] for name in optional_inputs(attrs, _OPTIONAL)]
    return ins, [out], []


_attn = OpDef(
    "_contrib_Attention",
    _attention,
    arguments=("query", "key", "value", "sink", "gate"),
    defaults={"num_heads": 1, "num_kv_heads": 0, "causal": True,
              "window": 0, "with_sink": False, "with_gate": False},
    infer_shape=_attention_infer,
    aliases=("Attention",),
    op_class="attn",
)
_attn.list_arguments = lambda attrs=None: (
    ["query", "key", "value"] + optional_inputs(attrs, _OPTIONAL))
register(_attn)
