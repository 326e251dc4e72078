"""``TopKMoE``: the dropless top-k sparse-expert FFN over ``[tokens, d_model]``,
a thin op over ``parallel/moe.py::topk_moe`` (this package's one import from
above ``ops/``), whose grouped matmuls are ``ops/kernels/gmm.py``."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..registry import OpDef, register
from ..utils import first_type, required_shape


def _topk_moe(attrs, ins, is_train):
    """``parallel/moe.topk_moe`` as a Symbol op. Two outputs: the routed
    FFN result and how many (token, expert) rows each of the
    ``num_experts`` experts received — float32 so that it can ride out
    of a training step beside the loss (behind ``BlockGrad``; it has no
    gradient)."""
    from ...parallel.moe import topk_moe

    data, gate_w, w_gate_up, w_down = ins[:4]
    params = {"gate_w": gate_w, "w_gate_up": w_gate_up, "w_down": w_down}
    if bool(attrs.get("with_select_bias", False)):
        params["select_bias"] = ins[4]
    y, counts = topk_moe(
        params, data, top_k=int(attrs["top_k"]),
        norm_topk_prob=bool(attrs.get("norm_topk_prob", False)),
        scoring=str(attrs.get("scoring", "softmax")),
        routed_scale=float(attrs.get("routed_scale", 1.0)),
        activation=str(attrs.get("activation", "swiglu")),
        expert_offset=int(attrs.get("expert_offset", 0)),
        share_rows_bound=int(attrs.get("share_rows_bound", 0)),
        renorm_eps=float(attrs.get("renorm_eps", 0.0)))
    return [y, counts.astype(jnp.float32)]


def _topk_moe_infer(attrs, in_shapes):
    data = required_shape(in_shapes[0], "TopKMoE")
    if len(data) != 2:
        raise ValueError("TopKMoE: data must be [tokens, d_model] "
                         "(Reshape (B,T,D) inputs to (B*T, D))")
    d_model = data[1]
    num_experts = int(attrs["num_experts"])
    held = int(attrs.get("experts_held", 0)) or num_experts
    offset = int(attrs.get("expert_offset", 0))
    hidden = int(attrs["num_hidden"])
    top_k = int(attrs["top_k"])
    if hidden <= 0:
        raise ValueError("TopKMoE: num_hidden must be set (> 0)")
    if not 1 <= top_k <= num_experts:
        raise ValueError("TopKMoE: top_k must lie in 1..num_experts, got "
                         "%d of %d" % (top_k, num_experts))
    if str(attrs.get("scoring", "softmax")) not in ("softmax", "sigmoid"):
        raise ValueError("TopKMoE: scoring must be softmax or sigmoid, "
                         "got %r" % (attrs["scoring"],))
    activation = str(attrs.get("activation", "swiglu"))
    if activation not in ("swiglu", "relu2"):
        raise ValueError("TopKMoE: activation must be swiglu or relu2, "
                         "got %r" % (activation,))
    if not (0 < held <= num_experts and 0 <= offset <= num_experts - held):
        raise ValueError(
            "TopKMoE: experts_held=%d from expert_offset=%d are not among "
            "num_experts=%d" % (held, offset, num_experts))
    if held < num_experts and not (
            0 < int(attrs.get("share_rows_bound", 0)) <= data[0] * top_k):
        raise ValueError(
            "TopKMoE: a share (experts_held=%d of %d) needs "
            "share_rows_bound in 1..tokens * top_k (%d), got %s"
            % (held, num_experts, data[0] * top_k,
               attrs.get("share_rows_bound", 0)))
    # un-gated, ``gate_up_weight`` is the up projection alone
    up = hidden if activation == "relu2" else 2 * hidden
    ins = [data, (d_model, num_experts), (held, d_model, up),
           (held, hidden, d_model)]
    return (ins + [(num_experts,)] * (len(in_shapes) - 4),
            [data, (num_experts,)], [])


def _topk_moe_infer_type(attrs, in_types):
    t = first_type("TopKMoE", in_types)
    return ([t if x is None else x for x in in_types],
            [t, np.float32], [])


_moe = OpDef(
    "_contrib_TopKMoE",
    _topk_moe,
    arguments=("data", "gate_weight", "gate_up_weight", "down_weight",
               "select_bias"),
    outputs=("output", "expert_count"),
    defaults={"num_experts": 8, "num_hidden": 0, "top_k": 2,
              "norm_topk_prob": False, "scoring": "softmax",
              "routed_scale": 1.0, "activation": "swiglu",
              "renorm_eps": 0.0,
              "with_select_bias": False,
              "experts_held": 0,
              "expert_offset": 0,
              "share_rows_bound": 0},
    infer_shape=_topk_moe_infer,
    infer_type=_topk_moe_infer_type,
    aliases=("TopKMoE",),
    op_class="moe",
)
_moe.list_arguments = lambda attrs=None: (
    ["data", "gate_weight", "gate_up_weight", "down_weight"]
    + (["select_bias"] if (attrs or {}).get("with_select_bias") else []))
register(_moe)
