"""Elementwise unary/binary/scalar/logic ops and their broadcast variants.

Parity: reference ``src/operator/tensor/elemwise_unary_op.cc`` (~40 unary
ops), ``elemwise_binary_op.cc`` + ``_scalar`` + ``_logic`` variants, and
``elemwise_binary_broadcast_op*.cc``. The reference implements each as an
mshadow expression-template kernel; here each is a jnp one-liner that XLA
fuses on the VPU — the entire mshadow layer (SURVEY.md §2 N18) collapses
into these definitions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .registry import OpDef, register
from .utils import binary_broadcast_infer, merge_shapes, same_shape_infer

_f32 = np.float32


def elemwise_backward_infer(attrs, in_shapes, out_shapes):
    """Reverse inference for same-shape ops: outputs refine inputs."""
    merged = None
    for s in list(out_shapes) + list(in_shapes):
        merged = merge_shapes(merged, s, "elemwise")
    return [merged] * len(in_shapes)


def _unary(name, fn, aliases=(), op_class=None):
    register(
        OpDef(
            name,
            lambda attrs, ins, is_train, _fn=fn: [_fn(ins[0])],
            arguments=("data",),
            infer_shape=same_shape_infer(1),
            backward_infer_shape=elemwise_backward_infer,
            aliases=aliases,
            op_class=op_class,
        )
    )


def _binary(name, fn, aliases=(), logic=False):
    def fcompute(attrs, ins, is_train, _fn=fn):
        out = _fn(ins[0], ins[1])
        if logic:  # reference logic ops return same dtype as inputs
            out = out.astype(ins[0].dtype)
        return [out]

    register(
        OpDef(
            name,
            fcompute,
            arguments=("lhs", "rhs"),
            infer_shape=same_shape_infer(2),
            backward_infer_shape=elemwise_backward_infer,
            aliases=aliases,
        )
    )


def _binary_scalar(name, fn, aliases=()):
    def fcompute(attrs, ins, is_train, _fn=fn):
        scalar = jnp.asarray(attrs["scalar"], dtype=ins[0].dtype)
        return [_fn(ins[0], scalar)]

    register(
        OpDef(
            name,
            fcompute,
            arguments=("data",),
            defaults={"scalar": 0.0},
            infer_shape=same_shape_infer(1),
            aliases=aliases,
        )
    )


def _broadcast(name, fn, aliases=(), logic=False):
    def fcompute(attrs, ins, is_train, _fn=fn):
        out = _fn(ins[0], ins[1])
        if logic:
            out = out.astype(ins[0].dtype)
        return [out]

    register(
        OpDef(
            name,
            fcompute,
            arguments=("lhs", "rhs"),
            infer_shape=binary_broadcast_infer,
            aliases=aliases,
        )
    )


# --------------------------------------------------------------------------
# unary (reference elemwise_unary_op.cc)
# --------------------------------------------------------------------------
def _relu(x):
    return jnp.where(x > 0, x, jnp.zeros_like(x))  # exact subgradient parity


_unary("relu", _relu, op_class="act")
_unary("sigmoid", jax.nn.sigmoid, op_class="act")
_unary("_copy", lambda x: x, aliases=("identity",))
_unary("BlockGrad", jax.lax.stop_gradient, aliases=("stop_gradient",))
_unary("make_loss", lambda x: x)
_unary("negative", jnp.negative)
_unary("abs", jnp.abs)
_unary("sign", jnp.sign)
_unary("round", jnp.round)
_unary("rint", jnp.rint)
_unary("ceil", jnp.ceil)
_unary("floor", jnp.floor)
_unary("trunc", jnp.trunc)
_unary("fix", jnp.trunc)  # round-toward-zero (jnp.fix deprecated alias)
_unary("square", jnp.square)
_unary("sqrt", jnp.sqrt)
_unary("rsqrt", jax.lax.rsqrt)
_unary("cbrt", jnp.cbrt)
_unary("rcbrt", lambda x: 1.0 / jnp.cbrt(x))
_unary("exp", jnp.exp)
_unary("log", jnp.log)
_unary("log10", jnp.log10)
_unary("log2", jnp.log2)
_unary("log1p", jnp.log1p)
_unary("expm1", jnp.expm1)
_unary("reciprocal", jnp.reciprocal)
_unary("sin", jnp.sin)
_unary("cos", jnp.cos)
_unary("tan", jnp.tan)
_unary("arcsin", jnp.arcsin)
_unary("arccos", jnp.arccos)
_unary("arctan", jnp.arctan)
_unary("sinh", jnp.sinh)
_unary("cosh", jnp.cosh)
_unary("tanh", jnp.tanh, op_class="act")
_unary("arcsinh", jnp.arcsinh)
_unary("arccosh", jnp.arccosh)
_unary("arctanh", jnp.arctanh)
_unary("degrees", jnp.degrees)
_unary("radians", jnp.radians)
_unary("gamma", lambda x: jnp.exp(jax.scipy.special.gammaln(x)))
_unary("gammaln", jax.scipy.special.gammaln)
_unary("erf", jax.scipy.special.erf)
_unary("softsign", jax.nn.soft_sign)


# Cast — dtype change (reference elemwise_unary_op.cc Cast)
def _cast_fcompute(attrs, ins, is_train):
    from ..base import np_dtype

    return [ins[0].astype(np_dtype(attrs["dtype"]))]


def _cast_infer_type(attrs, in_types):
    from ..base import np_dtype

    t = np_dtype(attrs["dtype"])
    inferred = [in_types[0] if in_types[0] is not None else _f32]
    return inferred, [t], []


register(
    OpDef(
        "Cast",
        _cast_fcompute,
        arguments=("data",),
        defaults={"dtype": "float32"},
        infer_shape=same_shape_infer(1),
        infer_type=_cast_infer_type,
        aliases=("cast",),
    )
)


# smooth_l1 (reference smooth_l1_unary-inl.h): scalar sigma; f(x) =
# 0.5 (sigma x)^2 if |x| < 1/sigma^2 else |x| - 0.5/sigma^2
def _smooth_l1(attrs, ins, is_train):
    sigma = float(attrs.get("scalar", 1.0))
    x = ins[0]
    s2 = sigma * sigma
    return [
        jnp.where(
            jnp.abs(x) < 1.0 / s2,
            0.5 * s2 * jnp.square(x),
            jnp.abs(x) - 0.5 / s2,
        )
    ]


register(
    OpDef(
        "smooth_l1",
        _smooth_l1,
        arguments=("data",),
        defaults={"scalar": 1.0},
        infer_shape=same_shape_infer(1),
    )
)

# --------------------------------------------------------------------------
# binary elemwise (same-shape) — reference elemwise_binary_op.cc
# --------------------------------------------------------------------------
_binary("elemwise_add", jnp.add, aliases=("_plus", "_add", "_Plus"))
_binary("elemwise_sub", jnp.subtract, aliases=("_minus", "_sub", "_Minus"))
_binary("elemwise_mul", jnp.multiply, aliases=("_mul", "_Mul"))
_binary("elemwise_div", jnp.divide, aliases=("_div", "_Div"))
_binary("_mod", jnp.mod, aliases=("_Mod",))
_binary("_power", jnp.power, aliases=("_Power", "_pow"))
_binary("_maximum", jnp.maximum, aliases=("_Maximum",))
_binary("_minimum", jnp.minimum, aliases=("_Minimum",))
_binary("_hypot", jnp.hypot)
_binary("_equal", jnp.equal, logic=True, aliases=("_Equal",))
_binary("_not_equal", jnp.not_equal, logic=True, aliases=("_Not_Equal",))
_binary("_greater", jnp.greater, logic=True, aliases=("_Greater",))
_binary("_greater_equal", jnp.greater_equal, logic=True, aliases=("_Greater_Equal",))
_binary("_lesser", jnp.less, logic=True, aliases=("_Lesser",))
_binary("_lesser_equal", jnp.less_equal, logic=True, aliases=("_Lesser_Equal",))

# --------------------------------------------------------------------------
# binary scalar — reference elemwise_binary_scalar_op.cc
# --------------------------------------------------------------------------
_binary_scalar("_plus_scalar", jnp.add, aliases=("_PlusScalar",))
_binary_scalar("_minus_scalar", jnp.subtract, aliases=("_MinusScalar",))
_binary_scalar("_rminus_scalar", lambda x, s: s - x, aliases=("_RMinusScalar",))
_binary_scalar("_mul_scalar", jnp.multiply, aliases=("_MulScalar",))
_binary_scalar("_div_scalar", jnp.divide, aliases=("_DivScalar",))
_binary_scalar("_rdiv_scalar", lambda x, s: s / x, aliases=("_RDivScalar",))
_binary_scalar("_mod_scalar", jnp.mod, aliases=("_ModScalar",))
_binary_scalar("_rmod_scalar", lambda x, s: jnp.mod(s, x), aliases=("_RModScalar",))
_binary_scalar("_power_scalar", jnp.power, aliases=("_PowerScalar",))
_binary_scalar("_rpower_scalar", lambda x, s: jnp.power(s, x), aliases=("_RPowerScalar",))
_binary_scalar("_maximum_scalar", jnp.maximum, aliases=("_MaximumScalar",))
_binary_scalar("_minimum_scalar", jnp.minimum, aliases=("_MinimumScalar",))
_binary_scalar("_hypot_scalar", jnp.hypot, aliases=("_HypotScalar",))
_binary_scalar("_equal_scalar", lambda x, s: (x == s).astype(x.dtype), aliases=("_EqualScalar",))
_binary_scalar("_not_equal_scalar", lambda x, s: (x != s).astype(x.dtype), aliases=("_NotEqualScalar",))
_binary_scalar("_greater_scalar", lambda x, s: (x > s).astype(x.dtype), aliases=("_GreaterScalar",))
_binary_scalar("_greater_equal_scalar", lambda x, s: (x >= s).astype(x.dtype), aliases=("_GreaterEqualScalar",))
_binary_scalar("_lesser_scalar", lambda x, s: (x < s).astype(x.dtype), aliases=("_LesserScalar",))
_binary_scalar("_lesser_equal_scalar", lambda x, s: (x <= s).astype(x.dtype), aliases=("_LesserEqualScalar",))

# --------------------------------------------------------------------------
# broadcast binary — reference elemwise_binary_broadcast_op_*.cc
# --------------------------------------------------------------------------
_broadcast("broadcast_add", jnp.add, aliases=("broadcast_plus",))
_broadcast("broadcast_sub", jnp.subtract, aliases=("broadcast_minus",))
_broadcast("broadcast_mul", jnp.multiply)
_broadcast("broadcast_div", jnp.divide)
_broadcast("broadcast_mod", jnp.mod)
_broadcast("broadcast_power", jnp.power)
_broadcast("broadcast_maximum", jnp.maximum)
_broadcast("broadcast_minimum", jnp.minimum)
_broadcast("broadcast_hypot", jnp.hypot)
_broadcast("broadcast_equal", jnp.equal, logic=True)
_broadcast("broadcast_not_equal", jnp.not_equal, logic=True)
_broadcast("broadcast_greater", jnp.greater, logic=True)
_broadcast("broadcast_greater_equal", jnp.greater_equal, logic=True)
_broadcast("broadcast_lesser", jnp.less, logic=True)
_broadcast("broadcast_lesser_equal", jnp.less_equal, logic=True)


# add_n / ElementwiseSum — variable input count (reference elemwise_sum.cc)
def _add_n(attrs, ins, is_train):
    out = ins[0]
    for x in ins[1:]:
        out = out + x
    return [out]


register(
    OpDef(
        "add_n",
        _add_n,
        arguments=("args",),
        key_var_num_args="num_args",
        infer_shape=lambda attrs, in_shapes: same_shape_infer(len(in_shapes))(
            attrs, in_shapes
        ),
        aliases=("ElementWiseSum", "_sum"),
        op_class="act",
    )
)
