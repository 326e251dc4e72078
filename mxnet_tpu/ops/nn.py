"""Neural-network layer operators.

Parity: the reference's legacy stateful layer ops (SURVEY.md §2 N6,
``src/operator/*-inl.h`` registered via MXNET_REGISTER_OP_PROPERTY):
Activation, FullyConnected, Convolution, Deconvolution, Pooling, BatchNorm,
Dropout, LRN, LeakyReLU, SoftmaxActivation/Output, regression outputs,
MakeLoss, InstanceNorm, L2Normalization, UpSampling, SequenceLast/Mask/
Reverse, softmax/log_softmax (``src/operator/nn/softmax.cc``),
softmax_cross_entropy (``loss_binary_op.cc``).

TPU-native notes:
- Convolution/FullyConnected lower to ``lax.conv_general_dilated`` /
  ``lax.dot_general`` → the MXU. FullyConnected forces fp32 accumulation
  via ``preferred_element_type``; convolutions rely on the MXU's native
  fp32 accumulation of bf16 matmuls (an explicit f32 output + cast breaks
  lax's conv transpose rules under bf16).
- The stateless/stateful split of the reference (OperatorProperty holding
  cuDNN descriptors) disappears: XLA owns algorithm choice, so every layer
  here is a pure function; BatchNorm's moving stats are threaded as aux
  inputs/outputs (the reference mutates them via FMutateInputs).
- Loss ops (``*Output``, MakeLoss) use jax.custom_vjp to reproduce the
  reference contract that Executor.backward() needs no head gradient — the
  op's backward ignores the incoming cotangent exactly as
  ``SoftmaxOutput::Backward`` ignores out_grad.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from ..base import MXNetError
from .registry import OpDef, register
from .utils import as_tuple, same_shape_infer

_ACT = {
    "relu": lambda x: jnp.where(x > 0, x, jnp.zeros_like(x)),
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "softrelu": jax.nn.softplus,
    "softsign": jax.nn.soft_sign,
    "silu": jax.nn.silu,
}


from .elemwise import elemwise_backward_infer

register(
    OpDef(
        "Activation",
        lambda attrs, ins, is_train: [_ACT[attrs.get("act_type", "relu")](ins[0])],
        arguments=("data",),
        defaults={"act_type": "relu"},
        infer_shape=same_shape_infer(1),
        backward_infer_shape=elemwise_backward_infer,
        op_class="act",
    )
)


def _leaky_relu(attrs, ins, is_train):
    act = attrs.get("act_type", "leaky")
    slope = float(attrs.get("slope", 0.25))
    x = ins[0]
    if act == "leaky":
        return [jnp.where(x > 0, x, slope * x)]
    if act == "elu":
        return [jnp.where(x > 0, x, slope * (jnp.exp(x) - 1.0))]
    if act == "prelu":
        gamma = ins[1].reshape((1, -1) + (1,) * (x.ndim - 2))
        return [jnp.where(x > 0, x, gamma * x)]
    if act == "rrelu":
        lo = float(attrs.get("lower_bound", 0.125))
        up = float(attrs.get("upper_bound", 0.334))
        if is_train:
            key = attrs["__rng__"]
            slope_r = jax.random.uniform(key, x.shape, minval=lo, maxval=up)
            return [jnp.where(x > 0, x, slope_r * x)]
        return [jnp.where(x > 0, x, ((lo + up) / 2.0) * x)]
    raise MXNetError("LeakyReLU: unknown act_type %s" % act)


def _leaky_relu_infer(attrs, in_shapes):
    d = tuple(in_shapes[0])
    if attrs.get("act_type", "leaky") == "prelu":
        return [d, (d[1],)], [d], []
    return [d], [d], []


_lrelu = OpDef(
    "LeakyReLU",
    _leaky_relu,
    arguments=("data",),
    defaults={
        "act_type": "leaky",
        "slope": 0.25,
        "lower_bound": 0.125,
        "upper_bound": 0.334,
    },
    infer_shape=_leaky_relu_infer,
    needs_rng=True,
    op_class="act",
)
_lrelu.list_arguments = lambda attrs=None: (
    ["data", "gamma"] if (attrs or {}).get("act_type") == "prelu" else ["data"]
)
register(_lrelu)


# --------------------------------------------------------------------------
# FullyConnected — reference fully_connected-inl.h:47-135
# --------------------------------------------------------------------------
def _fully_connected(attrs, ins, is_train):
    no_bias = bool(attrs.get("no_bias", False))
    data = ins[0]
    weight = ins[1]
    x2d = data.reshape(data.shape[0], -1)
    out = jax.lax.dot_general(
        x2d,
        weight,
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(data.dtype)
    if not no_bias:
        out = out + ins[2]
    return [out]


def _fc_infer(attrs, in_shapes):
    nh = int(attrs["num_hidden"])
    no_bias = bool(attrs.get("no_bias", False))
    dshape = in_shapes[0]
    if dshape is None:
        raise MXNetError("FullyConnected: data shape required")
    if 0 in tuple(dshape)[1:]:
        # feature dims unknown (partial shape): only batch/out inferable
        return (
            [tuple(dshape)] + [None] * (len(in_shapes) - 1),
            [(dshape[0], nh)],
            [],
        )
    in_dim = int(np.prod(dshape[1:]))
    shapes = [tuple(dshape), (nh, in_dim)]
    if not no_bias:
        shapes.append((nh,))
    return shapes, [(dshape[0], nh)], []


def _fc_backward_infer(attrs, in_shapes, out_shapes):
    """Refine data batch dim (and, with known weight, the feature dim) from
    the output — resolves RNN begin_state zeros with unknown batch."""
    out = out_shapes[0]
    refined = list(in_shapes)
    dshape = in_shapes[0]
    if out is not None and out[0] > 0:
        wshape = in_shapes[1] if len(in_shapes) > 1 else None
        if dshape is not None:
            d = list(dshape)
            if d[0] == 0:
                d[0] = out[0]
            if (
                len(d) == 2
                and d[1] == 0
                and wshape is not None
                and wshape[1] > 0
            ):
                d[1] = wshape[1]
            refined[0] = tuple(d)
        elif wshape is not None and all(x > 0 for x in wshape):
            refined[0] = (out[0], wshape[1])
    return refined


_fc = OpDef(
    "FullyConnected",
    _fully_connected,
    arguments=("data", "weight", "bias"),
    defaults={"num_hidden": 0, "no_bias": False},
    infer_shape=_fc_infer,
    backward_infer_shape=_fc_backward_infer,
    op_class="fc",
)
_fc.list_arguments = lambda attrs=None: (
    ["data", "weight"]
    if (attrs or {}).get("no_bias")
    else ["data", "weight", "bias"]
)
register(_fc)


# --------------------------------------------------------------------------
# Convolution / Deconvolution — reference convolution-inl.h; lowered to
# lax.conv_general_dilated (XLA chooses the MXU tiling; no im2col needed)
# --------------------------------------------------------------------------
def _conv_dims(attrs):
    kernel = as_tuple(attrs["kernel"])
    nd = len(kernel)
    stride = as_tuple(attrs.get("stride") or (1,) * nd, nd, "stride")
    dilate = as_tuple(attrs.get("dilate") or (1,) * nd, nd, "dilate")
    pad = as_tuple(attrs.get("pad") or (0,) * nd, nd, "pad")
    return kernel, stride, dilate, pad


def _conv_dn(nd):
    # NCHW / OIHW layout (reference layout); XLA relayouts internally for TPU
    spatial = "DHW"[-nd:] if nd <= 3 else None
    lhs = "NC" + spatial
    rhs = "OI" + spatial
    return jax.lax.conv_dimension_numbers(
        (1, 1) + (1,) * nd, (1, 1) + (1,) * nd, (lhs, rhs, lhs)
    )


def _conv_nhwc_dn():
    return jax.lax.conv_dimension_numbers(
        (1, 1, 1, 1), (1, 1, 1, 1), ("NHWC", "HWIO", "NHWC"))


def _conv_named_grads(plain, data, weight, dgrad=None, wgrad=None):
    """``plain(data, weight)`` with its two gradient convolutions traced
    under ``jax.named_scope("dgrad")`` and ``("wgrad")``: the one
    custom_vjp scaffold of the default path and of every lever, so that
    in a device trace a backward op says which of the two it is
    (``.../jvp(conv/<node>)/dgrad/...`` inside jax's ``transpose(``) and
    a fix to either construction lands everywhere at once.

    ``dgrad(d, w, g)`` / ``wgrad(d, w, g)`` replace a gradient; without
    one it is jax's own transpose of ``plain``: one vjp per gradient
    conv, so that each carries its own scope (the unused primal convs
    are dead code). A scope is metadata: the default path compiles to
    the program the bare differentiated call gives, and a data gradient
    nobody reads (the first convolution's, of the batch) is dead code
    that jax drops before it lowers, as its own rule would not have
    made it."""

    @jax.custom_vjp
    def conv(d, w):
        return plain(d, w)

    def fwd(d, w):
        return plain(d, w), (d, w)

    def bwd(res, g):
        d, w = res
        with jax.named_scope("dgrad"):
            gd = (dgrad(d, w, g) if dgrad is not None
                  else jax.vjp(lambda dd: plain(dd, w), d)[1](g)[0])
        with jax.named_scope("wgrad"):
            gw = (wgrad(d, w, g) if wgrad is not None
                  else jax.vjp(lambda ww: plain(d, ww), w)[1](g)[0])
        return gd, gw

    conv.defvjp(fwd, bwd)
    return conv(data, weight)


def _carry_shift_grad(out, plain, image, weight, shift):
    """``out = conv(data, weight)``, by whichever path, handed on with the
    gradient of ``shift`` ``[C]`` hung on it: ``data``, of shape ``image``,
    is something plus a per-channel shift that is already inside it (an
    input BatchNorm's beta: ``executor._shift_grad_plan`` finds the pair and
    cuts BatchNorm's own path to it). The forward reads nothing.

    The shift's gradient is the data gradient summed over batch and
    positions, and ``plain`` is linear in the data and the same map for
    every sample, so channel c's number is ``<sum_n g, plain(e_c, w)>``,
    ``e_c`` the image that is one in channel c: ONE forward convolution at
    batch ``C`` (float32 operands at full precision) against the batch's
    summed cotangent (float32), traced under ``dgrad``. The data gradient of
    the batch, from which BatchNorm would have summed the same ``C`` numbers
    (a transposed convolution into 3 of the MXU's 128 lanes behind
    ResNet-50's ``bn_data``: 3.88 ms of a 95.9 ms step, and 1.6 ms still at
    batch 1), is then read only where a caller differentiates the data."""
    channels = image[1]

    @jax.custom_vjp
    def carry(out, b, w):
        return out

    def bwd(w, g):
        with jax.named_scope("dgrad"), \
                jax.default_matmul_precision("float32"):
            each = jnp.eye(channels, dtype=jnp.float32).reshape(
                (channels, channels) + (1,) * len(image[2:]))
            response = plain(
                jnp.broadcast_to(each, each.shape[:2] + image[2:]),
                w.astype(jnp.float32))
            gb = jnp.sum(response * jnp.sum(g.astype(jnp.float32), axis=0),
                         axis=tuple(range(1, len(image))))
        return g, gb.astype(shift.dtype), None

    carry.defvjp(lambda out, b, w: (out, w), bwd)
    return carry(out, shift, jax.lax.stop_gradient(weight))


def _conv_plain(nd, stride, pad, dilate, groups=1):
    """The NCHW / OIHW convolution every path's forward is.

    NOTE: no preferred_element_type here — the MXU accumulates bf16
    matmuls in fp32 natively, and an explicit f32 output + cast breaks
    lax's conv transpose rules under bf16 (mixed-dtype cotangent)."""

    def plain(d, w):
        return jax.lax.conv_general_dilated(
            d, w, window_strides=stride,
            padding=[(p, p) for p in pad], rhs_dilation=dilate,
            dimension_numbers=_conv_dn(nd), feature_group_count=groups)

    return plain


def _conv2d_bwd_nhwc(data, weight, stride, pad, dilate, groups):
    """2-D conv, NCHW interface, with the BACKWARD convs computed in
    explicit NHWC layout (custom_vjp; forward stays the plain NCHW conv
    XLA already lays out well).

    Rationale: the r3 device trace puts 51.4 ms of the 96.4 ms ResNet-50
    bf16 step in conv backward, and the r3 layout probe falsified the
    whole-op NHWC wrap (fwd+bwd) as the lever — this targets ONLY the
    gradient convs, whose dgrad (lhs-dilated) and wgrad (batch-
    contracting) shapes are the ones layout assignment most often gets
    wrong. The backward derives the gradient convs by differentiating
    an NHWC-wrapped conv at transposed primals, so the grad math is
    jax's own (no hand-derived transposed-conv formulas to get wrong)
    and the only additions are the boundary transposes, which XLA can
    fuse or cancel. Gated by MXNET_CONV_BWD_LAYOUT=NHWC; numerics
    pinned against the default path in tests/test_conv_bwd_layout.py."""

    def f_nhwc(dt, wt):
        return jax.lax.conv_general_dilated(
            dt, wt, window_strides=stride,
            padding=[(p, p) for p in pad], rhs_dilation=dilate,
            dimension_numbers=_conv_nhwc_dn(),
            feature_group_count=groups)

    def nhwc(a):
        return jnp.transpose(a, (0, 2, 3, 1))          # NCHW -> NHWC

    def hwio(w):
        return jnp.transpose(w, (2, 3, 1, 0))          # OIHW -> HWIO

    def dgrad(d, w, g):
        gd_t, = jax.vjp(lambda dt: f_nhwc(dt, hwio(w)), nhwc(d))[1](nhwc(g))
        return jnp.transpose(gd_t, (0, 3, 1, 2))

    def wgrad(d, w, g):
        gw_t, = jax.vjp(lambda wt: f_nhwc(nhwc(d), wt), hwio(w))[1](nhwc(g))
        return jnp.transpose(gw_t, (3, 2, 0, 1))

    return _conv_named_grads(_conv_plain(2, stride, pad, dilate, groups),
                             data, weight, dgrad, wgrad)


def _conv2d_wgrad_custom(data, weight, stride, pad, dilate, wgrad_fn):
    """The wgrad levers: forward and the DATA gradient stay jax's own
    lowerings (vjp of the plain conv); only the filter gradient is
    replaced by wgrad_fn(d, g, w) -> f32 array reshapeable to w.shape."""

    def wgrad(d, w, g):
        return wgrad_fn(d, g, w).astype(w.dtype).reshape(w.shape)

    return _conv_named_grads(_conv_plain(2, stride, pad, dilate),
                             data, weight, wgrad=wgrad)


def _conv2d_wgrad_patches(data, weight, stride, pad, dilate):
    """2-D conv (NCHW, groups=1) whose FILTER gradient is computed as an
    explicit patches x grad matmul instead of XLA's native
    conv-backprop-filter (custom_vjp; forward and the data gradient stay
    jax's own lowerings).

    Rationale: the r3 device trace puts 51.4 ms of the 96.4 ms ResNet-50
    bf16 step in conv backward; wgrad contracts over (N, OH, OW), a
    shape XLA's layout assignment can tile badly on the MXU. Extracting
    the receptive-field patches (conv_general_dilated_patches) and
    contracting with one dot_general hands the MXU a single large
    matmul — and accumulates in f32 via preferred_element_type, which
    the native bf16 wgrad conv does not guarantee. Exact same math;
    gated by MXNET_CONV_WGRAD=patches; numerics pinned in
    tests/test_conv_bwd_layout.py.

    Memory: the patches tensor is (N, C*kh*kw, OH, OW) — ~kh*kw x the
    activation footprint (9x for 3x3), which can exceed HBM at large
    batch. MXNET_CONV_WGRAD_CHUNK=<k> splits the batch into k chunks
    and lax.scan-accumulates the f32 partial wgrads, bounding the live
    patches slab to N/k images at the cost of k smaller matmuls (same
    math — the contraction over N is a sum and accumulation stays f32;
    only f32 summation order differs)."""

    def partial_wgrad(dd, gg, w):
        """f32 (O, C*kh*kw) wgrad contribution of one batch chunk."""
        if (w.shape[2:] == (1, 1) and tuple(stride) == (1, 1)
                and tuple(pad) == (0, 0)):
            patches = dd  # 1x1/s1: the receptive field IS the input
        else:
            patches = jax.lax.conv_general_dilated_patches(
                dd, filter_shape=w.shape[2:], window_strides=stride,
                padding=[(p, p) for p in pad], rhs_dilation=dilate,
                dimension_numbers=_conv_dn(2))
        # patches: (n, C*kh*kw, OH, OW) with feature order (c, kh, kw);
        # gg: (n, O, OH, OW). Contract over (n, OH, OW) in ONE matmul.
        ckk = patches.shape[1]
        o = gg.shape[1]
        p2 = jnp.transpose(patches, (1, 0, 2, 3)).reshape(ckk, -1)
        g2 = jnp.transpose(gg, (1, 0, 2, 3)).reshape(o, -1)
        return jax.lax.dot_general(
            g2, p2, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    def wgrad(d, g, w):
        n = d.shape[0]
        try:
            chunks = int(os.environ.get("MXNET_CONV_WGRAD_CHUNK", "1"))
        except ValueError:
            chunks = 1
        if chunks > 1 and n % chunks == 0 and n // chunks >= 1:
            ds = d.reshape((chunks, n // chunks) + d.shape[1:])
            gs = g.reshape((chunks, n // chunks) + g.shape[1:])

            def body(acc, dg):
                dd, gg = dg
                return acc + partial_wgrad(dd, gg, w), None

            # C*kh*kw; equals C on the 1x1 fast path since kh=kw=1
            ckk = w.shape[1] * w.shape[2] * w.shape[3]
            gw, _ = jax.lax.scan(
                body, jnp.zeros((w.shape[0], ckk), jnp.float32),
                (ds, gs))
            return gw
        return partial_wgrad(d, g, w)

    return _conv2d_wgrad_custom(data, weight, stride, pad, dilate, wgrad)


def _conv2d_wgrad_taps(data, weight, stride, pad, dilate):
    """2-D conv (NCHW, groups=1) whose FILTER gradient is computed as
    kh*kw per-tap matmuls over shifted input views instead of XLA's
    native conv-backprop-filter or the patches lever's one big matmul.

    Rationale: the patches lever (_conv2d_wgrad_patches) hands the MXU
    one large contraction but materializes a (N, C*kh*kw, OH, OW) slab
    — kh*kw x the activation footprint, an HBM-bandwidth/capacity tax
    the r4 advisor flagged at large batch. The same contraction
    decomposes exactly by kernel tap:

        gw[o,c,kh,kw] = sum_{n,oh,ow} g[n,o,oh,ow] *
                        xpad[n,c, oh*s+kh*dh, ow*s+kw*dw]

    i.e. kh*kw independent (O x C) dot_generals, each contracting the
    SAME g against a strided view of the padded input — total FLOPs
    identical to the single matmul, peak memory 1x the activation (the
    strided slice is fusable), f32 accumulation via
    preferred_element_type. Data gradient stays jax's own lowering.
    Gated by MXNET_CONV_WGRAD=taps; numerics pinned in
    tests/test_conv_bwd_layout.py."""

    def wgrad(d, g, w):
        o, c, kh, kw = w.shape
        sh, sw = stride
        dh, dw = dilate
        oh, ow = g.shape[2], g.shape[3]
        xpad = jnp.pad(d, ((0, 0), (0, 0),
                           (pad[0], pad[0]), (pad[1], pad[1])))
        taps = []
        for ih in range(kh):
            for iw in range(kw):
                xs = jax.lax.slice(
                    xpad,
                    (0, 0, ih * dh, iw * dw),
                    (d.shape[0], c,
                     ih * dh + sh * (oh - 1) + 1,
                     iw * dw + sw * (ow - 1) + 1),
                    (1, 1, sh, sw))  # (N, C, OH, OW) view of this tap
                taps.append(jax.lax.dot_general(
                    g, xs,
                    (((0, 2, 3), (0, 2, 3)), ((), ())),
                    preferred_element_type=jnp.float32))  # (O, C)
        return jnp.stack(taps, axis=-1)  # (O, C, kh*kw)

    return _conv2d_wgrad_custom(data, weight, stride, pad, dilate, wgrad)


def _pallas_conv_plan(data, weight, stride, pad, dilate, groups):
    """Dispatch-table lookup for the Pallas conv-backward pair.

    Cheap env check first; the per-shape envelope decision (memoized in
    ops/kernels/conv.py) only runs when MXTPU_CONV_KERNEL=pallas is set.
    Returns the plan dict or None — None falls through to the taps lever /
    XLA default below."""
    if groups != 1:
        return None
    try:
        from . import kernels
    except Exception:  # noqa: BLE001 — pallas unavailable: fall back
        return None
    if not kernels.conv_kernel_enabled():
        return None
    return kernels.conv_bwd_plan(tuple(data.shape), tuple(weight.shape),
                                 tuple(stride), tuple(pad), tuple(dilate),
                                 data.dtype)


def _conv2d_pallas_bwd(data, weight, pad):
    """Stride-1 2-D conv whose BOTH gradient convs are the Pallas
    conv-backward pair (ops/kernels.conv_bwd_input/_filter):
    im2col-free in-register tap accumulation, f32 accumulators, no
    lhs-dilated dgrad conv. Forward stays XLA's own lowering (it is
    already MXU-shaped). Only called for shapes inside the tuned
    envelope (_pallas_conv_plan); numerics pinned in
    tests/test_conv_kernels.py."""
    from . import kernels

    interpret = kernels.common.INTERPRET

    def dgrad(d, w, g):
        return kernels.conv_bwd_input(
            g, w, d.shape, pad, interpret=interpret).astype(d.dtype)

    def wgrad(d, w, g):
        return kernels.conv_bwd_filter(
            d, g, w.shape, pad, interpret=interpret).astype(w.dtype)

    return _conv_named_grads(_conv_plain(2, (1, 1), pad, (1, 1)),
                             data, weight, dgrad, wgrad)


def _conv2d_s2d_strided(data, weight, kernel, pad, groups):
    """Stride-2 2-D conv computed in 2x2 space-to-depth space — exact,
    and the gradient convs become STRIDE-1 (no lhs-dilated dgrad, which
    wastes 3/4 of its MACs multiplying stuffed zeros; the generalization
    of the MLPerf stem trick to every stride-2 conv, same tap algebra as
    models/resnet.convert_stem_to_s2d).

    Per spatial dim (stride 2, kernel k, pad p): input index
    m = 2i + q - p maps tap q to (u, dm) with q = 2(u) + dm + p shifted
    so u ranges [u_min, u_max]; the s2d conv has kernel
    K = u_max - u_min + 1, asymmetric pad (-u_min, u_max), and weight
    w_s2d[o, (c,dh,dw), U, V] = w[o, c, 2(U+u_min_h)+dh+p_h, ...]
    (zero outside [0, k)). Autodiff differentiates straight through the
    reshapes + stride-1 conv, so no custom_vjp is needed.

    Gated by MXNET_CONV_S2D=1 (only stride (2,2), dilate 1, even
    spatial, and kernel in {2*pad+1, 2*pad+2} per dim — the s2d form
    always emits H/2 outputs, which equals the strided conv's count
    only for those 'same'-family shapes; the _convolution gate
    enforces this); numerics pinned in
    tests/test_conv_bwd_layout.py."""
    n, c, h, w = data.shape
    o, cg, kh, kw = weight.shape
    assert all(k in (2 * p + 1, 2 * p + 2)
               for k, p in zip(kernel, pad)), (kernel, pad)

    def dim_map(k, p):
        u_min = (0 - p - ((0 - p) % 2)) // 2
        u_max = (k - 1 - p - ((k - 1 - p) % 2)) // 2
        return u_min, u_max

    uh0, uh1 = dim_map(kh, pad[0])
    uw0, uw1 = dim_map(kw, pad[1])
    K_h, K_w = uh1 - uh0 + 1, uw1 - uw0 + 1

    # s2d input: (N, C, H, W) -> (N, C*4, H/2, W/2), channels (c, dh, dw)
    xs = data.reshape(n, c, h // 2, 2, w // 2, 2)
    xs = jnp.transpose(xs, (0, 1, 3, 5, 2, 4)).reshape(
        n, c * 4, h // 2, w // 2)

    # s2d weight, built by gathering taps (zero outside the kernel):
    # embed w into a zero canvas indexed by q = 2(U+u_min)+dm+p
    qh = 2 * (jnp.arange(K_h)[:, None] + uh0) + jnp.arange(2)[None, :] \
        + pad[0]  # (K_h, dh)
    qw = 2 * (jnp.arange(K_w)[:, None] + uw0) + jnp.arange(2)[None, :] \
        + pad[1]  # (K_w, dw)
    # gather with clamping + mask (jnp.take clamps; mask zeroes OOB taps)
    wh_idx = jnp.clip(qh, 0, kh - 1)
    ww_idx = jnp.clip(qw, 0, kw - 1)
    mask_h = ((qh >= 0) & (qh < kh)).astype(weight.dtype)
    mask_w = ((qw >= 0) & (qw < kw)).astype(weight.dtype)
    # w: (O, C/g, kh, kw) -> (O, C/g, K_h, dh, K_w, dw)
    wg = jnp.take(weight, wh_idx.reshape(-1), axis=2).reshape(
        o, cg, K_h, 2, kw)
    wg = jnp.take(wg, ww_idx.reshape(-1), axis=4).reshape(
        o, cg, K_h, 2, K_w, 2)
    wg = wg * mask_h[None, None, :, :, None, None] \
            * mask_w[None, None, None, None, :, :]
    # -> (O, (c, dh, dw), K_h, K_w) matching the input channel order
    ws = jnp.transpose(wg, (0, 1, 3, 5, 2, 4)).reshape(
        o, cg * 4, K_h, K_w)

    return jax.lax.conv_general_dilated(
        xs, ws, window_strides=(1, 1),
        padding=[(-uh0, uh1), (-uw0, uw1)],
        dimension_numbers=_conv_dn(2), feature_group_count=groups)


def _convolution(attrs, ins, is_train):
    kernel, stride, dilate, pad = _conv_dims(attrs)
    nd = len(kernel)
    groups = int(attrs.get("num_group", 1))
    data, weight = ins[0], ins[1]
    if (nd == 2 and os.environ.get("MXNET_CONV_S2D") == "1"
            and tuple(stride) == (2, 2) and tuple(dilate) == (1, 1)
            and data.shape[2] % 2 == 0 and data.shape[3] % 2 == 0
            and tuple(kernel) == (1, 1) and tuple(pad) == (0, 0)):
        # 1x1/s2: strided SLICE + dense 1x1 conv. The s2d canvas form
        # would 4x the dense MACs (masked zero channels are traced
        # values XLA can't prune); slicing keeps fwd/wgrad dense-sized
        # and the dgrad becomes slice-transpose (a cheap zero-pad
        # scatter) instead of an lhs-dilated conv.
        out = jax.lax.conv_general_dilated(
            data[:, :, ::2, ::2], weight, window_strides=(1, 1),
            padding=[(0, 0), (0, 0)], dimension_numbers=_conv_dn(2),
            feature_group_count=groups)
    elif (nd == 2 and os.environ.get("MXNET_CONV_S2D") == "1"
            and tuple(stride) == (2, 2) and tuple(dilate) == (1, 1)
            and data.shape[2] % 2 == 0 and data.shape[3] % 2 == 0
            and max(kernel) > 1
            # the s2d form emits exactly H/2 outputs per dim, which
            # matches the strided conv only for 'same'-family shapes
            # (k == 2p+1 or 2p+2); others (e.g. 3x3/s2/p0 inception
            # reductions) fall back to the default lowering
            and all(k in (2 * p + 1, 2 * p + 2)
                    for k, p in zip(kernel, pad))):
        out = _conv2d_s2d_strided(data, weight, kernel, pad, groups)
    elif (nd == 2
            and _pallas_conv_plan(data, weight, stride, pad, dilate,
                                  groups) is not None):
        # MXTPU_CONV_KERNEL=pallas and this shape is inside the tuned
        # envelope: gradient convs go through the Pallas pair.
        # Out-of-envelope shapes fall through — to the taps/patches
        # levers if also set, else XLA's default gradient lowering.
        out = _conv2d_pallas_bwd(data, weight, pad)
    elif nd == 2 and os.environ.get("MXNET_CONV_BWD_LAYOUT") == "NHWC":
        out = _conv2d_bwd_nhwc(data, weight, stride, pad, dilate, groups)
    elif (nd == 2 and os.environ.get("MXNET_CONV_WGRAD") == "patches"
            and groups == 1):
        out = _conv2d_wgrad_patches(data, weight, stride, pad, dilate)
    elif (nd == 2 and os.environ.get("MXNET_CONV_WGRAD") == "taps"
            and groups == 1):
        out = _conv2d_wgrad_taps(data, weight, stride, pad, dilate)
    else:
        # XLA's own lowering of all three convs, the two gradient
        # ones under their names
        out = _conv_named_grads(
            _conv_plain(nd, stride, pad, dilate, groups), data, weight)
    if attrs.get("__shift__") is not None:
        # the node carries the gradient of the per-channel shift inside its
        # data (executor._shift_grad_plan), whichever path it took
        out = _carry_shift_grad(
            out, _conv_plain(nd, stride, pad, dilate, groups),
            tuple(data.shape), weight, attrs["__shift__"])
    if not bool(attrs.get("no_bias", False)):
        bias = ins[2].reshape((1, -1) + (1,) * nd)
        out = out + bias
    return [out]


def _conv_infer(attrs, in_shapes):
    kernel, stride, dilate, pad = _conv_dims(attrs)
    nd = len(kernel)
    nf = int(attrs["num_filter"])
    groups = int(attrs.get("num_group", 1))
    no_bias = bool(attrs.get("no_bias", False))
    dshape = in_shapes[0]
    if dshape is None:
        raise MXNetError("Convolution: data shape required")
    if len(dshape) != nd + 2:
        raise MXNetError("Convolution: data must be %dD, got %s" % (nd + 2, (dshape,)))
    c = dshape[1]
    wshape = (nf, c // groups) + kernel
    out_sp = tuple(
        (dshape[2 + i] + 2 * pad[i] - (dilate[i] * (kernel[i] - 1) + 1)) // stride[i]
        + 1
        for i in range(nd)
    )
    oshape = (dshape[0], nf) + out_sp
    shapes = [tuple(dshape), wshape] + ([] if no_bias else [(nf,)])
    return shapes, [oshape], []


_conv = OpDef(
    "Convolution",
    _convolution,
    arguments=("data", "weight", "bias"),
    defaults={
        "kernel": (1, 1),
        "stride": None,
        "dilate": None,
        "pad": None,
        "num_filter": 1,
        "num_group": 1,
        "no_bias": False,
        "workspace": 1024,
        "cudnn_tune": None,
        "cudnn_off": False,
        "layout": None,
    },
    infer_shape=_conv_infer,
    op_class="conv",
)
_conv.list_arguments = lambda attrs=None: (
    ["data", "weight"]
    if (attrs or {}).get("no_bias")
    else ["data", "weight", "bias"]
)
register(_conv)
from .registry import _REGISTRY as _R

_R["Convolution_v1"] = _conv  # reference keeps the pre-NNVM name alive


def _deconvolution(attrs, ins, is_train):
    kernel, stride, dilate, pad = _conv_dims(attrs)
    nd = len(kernel)
    groups = int(attrs.get("num_group", 1))
    adj = as_tuple(attrs.get("adj") or (0,) * nd, nd, "adj")
    data, weight = ins[0], ins[1]
    # Transposed conv = gradient of conv wrt its input: lhs-dilated conv with
    # flipped kernel (weight layout (C_in, C_out/g, *K) as in the reference).
    # Expressed directly as the transpose of a strided conv: an
    # lhs-dilated conv_general_dilated with the spatially-flipped,
    # in/out-swapped kernel. (lax.conv_transpose lacks group support and
    # its transpose_kernel path fails to differentiate in current jax.)
    c_in = weight.shape[0]
    c_out_g = weight.shape[1]
    # (C_in, C_out/g, *K) -> (C_out, C_in/g, *K)
    w = weight.reshape((groups, c_in // groups, c_out_g) + kernel)
    w = jnp.swapaxes(w, 1, 2).reshape(
        (groups * c_out_g, c_in // groups) + kernel)
    w = jnp.flip(w, axis=tuple(range(2, 2 + nd)))
    k_eff = tuple((k - 1) * d + 1 for k, d in zip(kernel, dilate))
    out = jax.lax.conv_general_dilated(
        data,
        w,
        window_strides=(1,) * nd,
        padding=[(ke - 1 - p, ke - 1 - p + a)
                 for ke, p, a in zip(k_eff, pad, adj)],
        lhs_dilation=stride,
        rhs_dilation=dilate,
        dimension_numbers=_conv_dn(nd),
        feature_group_count=groups,
    )
    if not bool(attrs.get("no_bias", True)):
        out = out + ins[2].reshape((1, -1) + (1,) * nd)
    return [out]


def _deconv_infer(attrs, in_shapes):
    kernel, stride, dilate, pad = _conv_dims(attrs)
    nd = len(kernel)
    nf = int(attrs["num_filter"])
    groups = int(attrs.get("num_group", 1))
    no_bias = bool(attrs.get("no_bias", True))
    adj = as_tuple(attrs.get("adj") or (0,) * nd, nd, "adj")
    dshape = in_shapes[0]
    c = dshape[1]
    wshape = (c, nf // groups) + kernel
    out_sp = tuple(
        stride[i] * (dshape[2 + i] - 1)
        + (dilate[i] * (kernel[i] - 1) + 1)
        - 2 * pad[i]
        + adj[i]
        for i in range(nd)
    )
    oshape = (dshape[0], nf) + out_sp
    shapes = [tuple(dshape), wshape] + ([] if no_bias else [(nf,)])
    return shapes, [oshape], []


_deconv = OpDef(
    "Deconvolution",
    _deconvolution,
    arguments=("data", "weight", "bias"),
    defaults={
        "kernel": (1, 1),
        "stride": None,
        "dilate": None,
        "pad": None,
        "adj": None,
        "target_shape": None,
        "num_filter": 1,
        "num_group": 1,
        "no_bias": True,
        "workspace": 512,
    },
    infer_shape=_deconv_infer,
    op_class="conv",
)
_deconv.list_arguments = lambda attrs=None: (
    ["data", "weight"]
    if (attrs or {}).get("no_bias", True)
    else ["data", "weight", "bias"]
)
register(_deconv)


# --------------------------------------------------------------------------
# Pooling — reference pooling-inl.h; lax.reduce_window
# --------------------------------------------------------------------------
def _pool_out_dim(x, k, s, p, convention):
    if convention == "full":
        return int(np.ceil(float(x + 2 * p - k) / s)) + 1
    return (x + 2 * p - k) // s + 1


def _pooling(attrs, ins, is_train):
    data = ins[0]
    nd = data.ndim - 2
    global_pool = bool(attrs.get("global_pool", False))
    if global_pool:
        kernel = data.shape[2:]
        stride = (1,) * nd
        pad = (0,) * nd
    else:
        kernel = as_tuple(attrs["kernel"])
        stride = as_tuple(attrs.get("stride") or (1,) * nd, nd, "stride")
        pad = as_tuple(attrs.get("pad") or (0,) * nd, nd, "pad")
    ptype = attrs.get("pool_type", "max")
    window = (1, 1) + kernel
    strides = (1, 1) + stride
    # "full" convention (ceil output size): extend the high-side pad so
    # reduce_window emits ceil((x+2p-k)/s)+1 windows; the avg divisor
    # below only counts in-bounds elements so border windows stay exact.
    hi_extra = (0,) * nd
    if not global_pool and attrs.get("pooling_convention", "valid") == "full":
        hi_extra = tuple(
            max(0, (_pool_out_dim(data.shape[2 + i], kernel[i], stride[i],
                                  pad[i], "full") - 1) * stride[i]
                + kernel[i] - (data.shape[2 + i] + 2 * pad[i]))
            for i in range(nd)
        )
    padding = ((0, 0), (0, 0)) + tuple(
        (p, p + e) for p, e in zip(pad, hi_extra))
    # init values MUST be python scalars: a traced init keeps XLA from
    # recognizing the differentiable reduce_window_max/add patterns and
    # vjp-under-jit fails to linearize.
    if ptype == "max":
        if jnp.issubdtype(data.dtype, jnp.floating):
            init = -float(np.inf)
        else:
            init = int(np.iinfo(np.dtype(data.dtype)).min)
        out = jax.lax.reduce_window(
            data, init, jax.lax.max, window, strides, padding
        )
    elif ptype in ("avg", "sum"):
        zero = 0.0 if jnp.issubdtype(data.dtype, jnp.floating) else 0
        out = jax.lax.reduce_window(
            data, zero, jax.lax.add, window, strides, padding
        )
        if ptype == "avg":
            # divisor = window area clipped to the PADDED extent
            # (reference pool.h pool_sum_2d_cpu: pool_size uses
            # hend=min(hstart+k, H+pad) before clipping to real bounds,
            # i.e. padding counts toward the average, but the "full"
            # convention's extra high-side extension does not)
            cdt = data.dtype if jnp.issubdtype(data.dtype, jnp.floating) \
                else jnp.float32
            ones = jnp.ones(
                tuple(data.shape[2 + i] + 2 * pad[i] for i in range(nd)), cdt)
            counts = jax.lax.reduce_window(
                ones, 0.0, jax.lax.add, kernel, stride,
                tuple((0, e) for e in hi_extra)
            )
            out = (out / counts).astype(data.dtype)
    else:
        raise MXNetError("Pooling: unknown pool_type %s" % ptype)
    return [out]


def _pooling_infer(attrs, in_shapes):
    dshape = in_shapes[0]
    nd = len(dshape) - 2
    if bool(attrs.get("global_pool", False)):
        return [tuple(dshape)], [tuple(dshape[:2]) + (1,) * nd], []
    kernel = as_tuple(attrs["kernel"])
    stride = as_tuple(attrs.get("stride") or (1,) * nd, nd, "stride")
    pad = as_tuple(attrs.get("pad") or (0,) * nd, nd, "pad")
    conv = attrs.get("pooling_convention", "valid")
    out_sp = tuple(
        _pool_out_dim(dshape[2 + i], kernel[i], stride[i], pad[i], conv)
        for i in range(nd)
    )
    return [tuple(dshape)], [tuple(dshape[:2]) + out_sp], []


register(
    OpDef(
        "Pooling",
        _pooling,
        arguments=("data",),
        defaults={
            "kernel": (1, 1),
            "stride": None,
            "pad": None,
            "pool_type": "max",
            "global_pool": False,
            "pooling_convention": "valid",
            "cudnn_off": False,
        },
        infer_shape=_pooling_infer,
        aliases=("Pooling_v1",),
        op_class="pool",
    )
)


# --------------------------------------------------------------------------
# BatchNorm — reference batch_norm-inl.h. aux: moving_mean/moving_var;
# outputs (output, save_mean, save_var) with 1 visible. Per-replica stats
# (no cross-replica sync) to match reference convergence (SURVEY.md §7).
#
# The training path is a custom_vjp core tuned from a v5e device trace:
# autodiff through the two-pass stats formulation cost 27.5 ms of a
# 110 ms ResNet-50 b256 step (25%). The core does one-pass stats
# (sum / sum-of-squares in a single multi-output reduce over the bf16
# input with f32 accumulation) and a closed-form backward (one fused
# (sum(dy), sum(dy*xhat)) reduce + one dx pass), which is the minimum
# HBM traffic without a persistent kernel.
# --------------------------------------------------------------------------
def _bn_reduce_axes(ndim):
    return tuple(i for i in range(ndim) if i != 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _bn_train_core(x, gamma, beta, eps):
    y, mean, var, _rstd = _bn_train_fwd_math(x, gamma, beta, eps)
    return y, mean, var


def _bn_train_fwd_math(x, gamma, beta, eps):
    ax = _bn_reduce_axes(x.ndim)
    bshape = (1, -1) + (1,) * (x.ndim - 2)
    n = x.size // x.shape[1]
    x32 = x.astype(jnp.float32)
    # two reduces over one operand: XLA fuses into a single pass
    s1 = jnp.sum(x32, axis=ax)
    s2 = jnp.sum(x32 * x32, axis=ax)
    mean = s1 / n
    # E[x^2] - mean^2; clamp tiny negative cancellation residue
    var = jnp.maximum(s2 / n - mean * mean, 0.0)
    rstd = jax.lax.rsqrt(var + eps)
    scale = (gamma.astype(jnp.float32) * rstd).reshape(bshape)
    shift = (beta.astype(jnp.float32)
             - gamma.astype(jnp.float32) * rstd * mean).reshape(bshape)
    y = (x32 * scale + shift).astype(x.dtype)
    return y, mean, var, rstd


def _bn_core_fwd(x, gamma, beta, eps):
    # symbolic_zeros=True wraps primals in CustomVJPPrimal(.value,
    # .perturbed); unwrap before doing math
    x, gamma, beta = x.value, gamma.value, beta.value
    y, mean, var, rstd = _bn_train_fwd_math(x, gamma, beta, eps)
    return (y, mean, var), (x, gamma, mean, rstd)


def _bn_core_bwd(eps, res, cts):
    from jax.custom_derivatives import SymbolicZero

    dy, dmean, dvar = cts
    x, gamma, mean, rstd = res
    ax = _bn_reduce_axes(x.ndim)
    bshape = (1, -1) + (1,) * (x.ndim - 2)
    n = x.size // x.shape[1]
    g32 = gamma.astype(jnp.float32)
    x32 = x.astype(jnp.float32)
    if isinstance(dy, SymbolicZero):
        dx32 = jnp.zeros(x.shape, jnp.float32)
        dgamma = jnp.zeros(gamma.shape, jnp.float32)
        dbeta = jnp.zeros(gamma.shape, jnp.float32)
    else:
        dy32 = dy.astype(jnp.float32)
        xhat = (x32 - mean.reshape(bshape)) * rstd.reshape(bshape)
        # one fused two-output reduce over (dy, x)
        dbeta = jnp.sum(dy32, axis=ax)
        dgamma = jnp.sum(dy32 * xhat, axis=ax)
        dx32 = (g32 * rstd).reshape(bshape) * (
            dy32 - (dbeta / n).reshape(bshape)
            - xhat * (dgamma / n).reshape(bshape)
        )
    # mean/var cotangent terms: mean/var ARE graph outputs, but in the
    # training step they feed only the (non-differentiated) moving-stat
    # aux updates, so their cotangents are SYMBOLIC zeros — skipping the
    # terms at trace time removes a whole extra pass over the
    # activations (~16ms of a 96ms ResNet-50 b256 step on v5e: the
    # add_any accumulations and the dvar*x re-read do real HBM traffic
    # even when the incoming cotangent arrays are all-zero at runtime).
    if not isinstance(dmean, SymbolicZero):
        dx32 = dx32 + (dmean / n).reshape(bshape).astype(jnp.float32)
    if not isinstance(dvar, SymbolicZero):
        dx32 = dx32 + (
            dvar.reshape(bshape).astype(jnp.float32)
            * 2.0 / n * (x32 - mean.reshape(bshape))
        )
    return (dx32.astype(x.dtype), dgamma.astype(gamma.dtype),
            dbeta.astype(gamma.dtype))


_bn_train_core.defvjp(_bn_core_fwd, _bn_core_bwd, symbolic_zeros=True)


def _batch_norm(attrs, ins, is_train):
    data, gamma, beta, moving_mean, moving_var = ins
    eps = float(attrs.get("eps", 1e-3))
    momentum = float(attrs.get("momentum", 0.9))
    fix_gamma = bool(attrs.get("fix_gamma", True))
    use_global = bool(attrs.get("use_global_stats", False)) or not is_train
    ax = tuple(i for i in range(data.ndim) if i != 1)
    bshape = (1, -1) + (1,) * (data.ndim - 2)
    if fix_gamma:
        gamma = jnp.ones_like(gamma) + jax.lax.stop_gradient(gamma * 0)
    if use_global:
        mean, var = moving_mean, moving_var
        new_mean, new_var = moving_mean, moving_var
        out = (data - mean.reshape(bshape)) * jax.lax.rsqrt(
            var.reshape(bshape) + eps
        ) * gamma.reshape(bshape) + beta.reshape(bshape)
        # normalize in the STATS dtype (f32 moving stats) but return the
        # input's dtype: a bf16 graph's inference BN must not upcast the
        # activation stream — the next conv would see (f32, bf16) and
        # type inference already promised it data.dtype
        out = out.astype(data.dtype)
    else:
        out, mean, var = _bn_train_core(data, gamma, beta, eps)
        new_mean = momentum * moving_mean + (1.0 - momentum) * mean.astype(
            moving_mean.dtype
        )
        new_var = momentum * moving_var + (1.0 - momentum) * var.astype(
            moving_var.dtype
        )
    return [out, mean.astype(jnp.float32), var.astype(jnp.float32), new_mean, new_var]


def _bn_infer(attrs, in_shapes):
    dshape = in_shapes[0]
    if dshape is None:
        raise MXNetError("BatchNorm: data shape required")
    c = (dshape[1],)
    return (
        [tuple(dshape), c, c],
        [tuple(dshape), c, c],
        [c, c],
    )


_bn = OpDef(
    "BatchNorm",
    _batch_norm,
    arguments=("data", "gamma", "beta"),
    outputs=("output", "mean", "var"),
    aux=("moving_mean", "moving_var"),
    defaults={
        "eps": 1e-3,
        "momentum": 0.9,
        "fix_gamma": True,
        "use_global_stats": False,
        "output_mean_var": False,
    },
    infer_shape=_bn_infer,
    aliases=("CuDNNBatchNorm",),
    op_class="bn",
)
_bn._num_visible_outputs = 1
register(_bn)


# --------------------------------------------------------------------------
# InstanceNorm / L2Normalization / LRN
# --------------------------------------------------------------------------
def _instance_norm(attrs, ins, is_train):
    data, gamma, beta = ins
    eps = float(attrs.get("eps", 1e-3))
    ax = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=ax, keepdims=True)
    var = jnp.mean(jnp.square(data - mean), axis=ax, keepdims=True)
    bshape = (1, -1) + (1,) * (data.ndim - 2)
    return [
        (data - mean) * jax.lax.rsqrt(var + eps) * gamma.reshape(bshape)
        + beta.reshape(bshape)
    ]


register(
    OpDef(
        "InstanceNorm",
        _instance_norm,
        arguments=("data", "gamma", "beta"),
        defaults={"eps": 1e-3},
        infer_shape=lambda attrs, in_shapes: (
            [tuple(in_shapes[0]), (in_shapes[0][1],), (in_shapes[0][1],)],
            [tuple(in_shapes[0])],
            [],
        ),
    )
)


def _l2_normalization(attrs, ins, is_train):
    data = ins[0]
    eps = float(attrs.get("eps", 1e-10))
    mode = attrs.get("mode", "instance")
    if mode == "instance":
        ax = tuple(range(1, data.ndim))
    elif mode == "channel":
        ax = (1,)
    elif mode == "spatial":
        ax = tuple(range(2, data.ndim))
    else:
        raise MXNetError("L2Normalization: unknown mode %s" % mode)
    norm = jnp.sqrt(jnp.sum(jnp.square(data), axis=ax, keepdims=True) + eps)
    return [data / norm]


register(
    OpDef(
        "L2Normalization",
        _l2_normalization,
        arguments=("data",),
        defaults={"eps": 1e-10, "mode": "instance"},
        infer_shape=same_shape_infer(1),
    )
)


def _lrn(attrs, ins, is_train):
    x = ins[0]
    nsize = int(attrs.get("nsize", 5))
    alpha = float(attrs.get("alpha", 1e-4))
    beta = float(attrs.get("beta", 0.75))
    knorm = float(attrs.get("knorm", 2.0))
    sq = jnp.square(x)
    half = nsize // 2
    pad = [(0, 0), (half, half)] + [(0, 0)] * (x.ndim - 2)
    sq_pad = jnp.pad(sq, pad)
    window = jnp.stack(
        [sq_pad[:, i : i + x.shape[1]] for i in range(nsize)], axis=0
    ).sum(axis=0)
    return [x * jnp.power(knorm + (alpha / nsize) * window, -beta)]


register(
    OpDef(
        "LRN",
        _lrn,
        arguments=("data",),
        defaults={"nsize": 5, "alpha": 1e-4, "beta": 0.75, "knorm": 2.0},
        infer_shape=same_shape_infer(1),
    )
)


# --------------------------------------------------------------------------
# Dropout — reference dropout-inl.h (scale-at-train, identity at eval)
# --------------------------------------------------------------------------
def _dropout(attrs, ins, is_train):
    p = float(attrs.get("p", 0.5))
    if not is_train or p <= 0.0:
        return [ins[0]]
    key = attrs["__rng__"]
    keep = 1.0 - p
    mask = jax.random.bernoulli(key, keep, ins[0].shape)
    return [jnp.where(mask, ins[0] / keep, jnp.zeros_like(ins[0]))]


register(
    OpDef(
        "Dropout",
        _dropout,
        arguments=("data",),
        defaults={"p": 0.5, "mode": "training"},
        infer_shape=same_shape_infer(1),
        needs_rng=True,
    )
)


# --------------------------------------------------------------------------
# softmax / log_softmax / SoftmaxActivation
# --------------------------------------------------------------------------
register(
    OpDef(
        "softmax",
        lambda attrs, ins, is_train: [
            jax.nn.softmax(ins[0], axis=int(attrs.get("axis", -1)))
        ],
        arguments=("data",),
        defaults={"axis": -1, "temperature": None},
        infer_shape=same_shape_infer(1),
    )
)
register(
    OpDef(
        "log_softmax",
        lambda attrs, ins, is_train: [
            jax.nn.log_softmax(ins[0], axis=int(attrs.get("axis", -1)))
        ],
        arguments=("data",),
        defaults={"axis": -1, "temperature": None},
        infer_shape=same_shape_infer(1),
    )
)
register(
    OpDef(
        "SoftmaxActivation",
        lambda attrs, ins, is_train: [
            jax.nn.softmax(ins[0], axis=1)
            if attrs.get("mode", "instance") == "channel"
            else jax.nn.softmax(
                ins[0].reshape(ins[0].shape[0], -1), axis=-1
            ).reshape(ins[0].shape)
        ],
        arguments=("data",),
        defaults={"mode": "instance"},
        infer_shape=same_shape_infer(1),
    )
)


# --------------------------------------------------------------------------
# SoftmaxOutput and friends — loss heads with reference backward semantics
# --------------------------------------------------------------------------
def _normalize_grad(grad, label, attrs, valid_mask=None):
    normalization = attrs.get("normalization", "null")
    if normalization == "batch":
        grad = grad / label.shape[0]
    elif normalization == "valid" and valid_mask is not None:
        grad = grad / jnp.maximum(valid_mask.sum(), 1.0)
    elif normalization == "valid":
        grad = grad / float(np.prod(label.shape))
    return grad


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _softmax_output_core(data, label, attr_key):
    attrs = dict(attr_key)
    if attrs.get("multi_output") and data.ndim > 2:
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data, axis=-1)


def _softmax_output_fwd(data, label, attr_key):
    out = _softmax_output_core(data, label, attr_key)
    return out, (out, label)


def _softmax_output_bwd(attr_key, res, g):
    # Reference contract: backward ignores the head gradient entirely
    # (softmax_output-inl.h Backward). g is unused by design.
    out, label = res
    attrs = dict(attr_key)
    grad_scale = float(attrs.get("grad_scale", 1.0))
    use_ignore = bool(attrs.get("use_ignore", False))
    ignore_label = float(attrs.get("ignore_label", -1.0))
    multi = bool(attrs.get("multi_output", False)) and out.ndim > 2
    axis = 1 if multi else -1
    depth = out.shape[axis]
    lbl = label.astype(jnp.int32)
    onehot = jax.nn.one_hot(lbl, depth, dtype=out.dtype)
    if multi:
        # label (n, d1...) → put class axis at 1
        onehot = jnp.moveaxis(onehot, -1, 1)
    grad = out - onehot
    valid = None
    if use_ignore:
        mask = (label != ignore_label).astype(out.dtype)
        valid = mask
        grad = grad * jnp.expand_dims(mask, axis=axis)
    grad = _normalize_grad(grad * grad_scale, label, attrs, valid)
    return grad.astype(out.dtype), jnp.zeros_like(label)


_softmax_output_core.defvjp(_softmax_output_fwd, _softmax_output_bwd)


def _softmax_output(attrs, ins, is_train):
    attr_key = tuple(
        sorted((k, v) for k, v in attrs.items() if not k.startswith("__") and not isinstance(v, jax.Array))
    )
    return [_softmax_output_core(ins[0], ins[1], attr_key)]


def _softmax_output_infer(attrs, in_shapes):
    dshape = in_shapes[0]
    if dshape is None:
        raise MXNetError("SoftmaxOutput: data shape required")
    if attrs.get("multi_output") and len(dshape) > 2:
        lshape = (dshape[0],) + tuple(dshape[2:])
    else:
        lshape = tuple(dshape[:-1]) if len(dshape) > 1 else (dshape[0],)
    return [tuple(dshape), lshape], [tuple(dshape)], []


register(
    OpDef(
        "SoftmaxOutput",
        _softmax_output,
        arguments=("data", "label"),
        defaults={
            "grad_scale": 1.0,
            "ignore_label": -1.0,
            "use_ignore": False,
            "multi_output": False,
            "normalization": "null",
            "preserve_shape": False,
            "out_grad": False,
        },
        infer_shape=_softmax_output_infer,
        need_top_grad=False,
        aliases=("Softmax",),
    )
)


def _make_output_op(name, bwd_fn, act=lambda x: x):
    """Regression output heads (linear/logistic/MAE) — backward ignores the
    head gradient, grad = bwd_fn(out, label) * grad_scale / batch."""

    @functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
    def core(data, label, grad_scale):
        return act(data)

    def fwd(data, label, grad_scale):
        out = core(data, label, grad_scale)
        return out, (out, label)

    def bwd(grad_scale, res, g):
        out, label = res
        n = float(np.prod(out.shape[1:])) if out.ndim > 1 else 1.0
        grad = bwd_fn(out, label.reshape(out.shape)) * (grad_scale / n)
        return grad.astype(out.dtype), jnp.zeros_like(label)

    core.defvjp(fwd, bwd)

    def fcompute(attrs, ins, is_train):
        return [core(ins[0], ins[1], float(attrs.get("grad_scale", 1.0)))]

    register(
        OpDef(
            name,
            fcompute,
            arguments=("data", "label"),
            defaults={"grad_scale": 1.0},
            infer_shape=lambda attrs, in_shapes: (
                [tuple(in_shapes[0]), tuple(in_shapes[0])],
                [tuple(in_shapes[0])],
                [],
            ),
            need_top_grad=False,
        )
    )


_make_output_op("LinearRegressionOutput", lambda o, l: o - l)
_make_output_op(
    "LogisticRegressionOutput", lambda o, l: o - l, act=jax.nn.sigmoid
)
_make_output_op("MAERegressionOutput", lambda o, l: jnp.sign(o - l))


# SVMOutput — reference svm_output-inl.h: hinge loss gradients
def _svm_output(attrs, ins, is_train):
    margin = float(attrs.get("margin", 1.0))
    reg = float(attrs.get("regularization_coefficient", 1.0))
    use_linear = bool(attrs.get("use_linear", False))

    @functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
    def core(data, label, margin, reg, use_linear):
        return data

    def fwd(data, label, margin, reg, use_linear):
        return data, (data, label)

    def bwd(margin, reg, use_linear, res, g):
        data, label = res
        lbl = label.astype(jnp.int32)
        onehot = jax.nn.one_hot(lbl, data.shape[-1], dtype=data.dtype)
        sign = 2.0 * onehot - 1.0  # +1 at true class, -1 elsewhere
        viol = (margin - sign * data) > 0
        if use_linear:
            grad = jnp.where(viol, -sign * reg, 0.0)
        else:
            grad = jnp.where(viol, -2.0 * (margin - sign * data) * sign * reg, 0.0)
        return grad.astype(data.dtype), jnp.zeros_like(label)

    core.defvjp(fwd, bwd)
    return [core(ins[0], ins[1], margin, reg, use_linear)]


register(
    OpDef(
        "SVMOutput",
        _svm_output,
        arguments=("data", "label"),
        defaults={
            "margin": 1.0,
            "regularization_coefficient": 1.0,
            "use_linear": False,
        },
        infer_shape=lambda attrs, in_shapes: (
            [tuple(in_shapes[0]), (in_shapes[0][0],)],
            [tuple(in_shapes[0])],
            [],
        ),
        need_top_grad=False,
    )
)


# MakeLoss layer — reference make_loss-inl.h
def _make_loss(attrs, ins, is_train):
    grad_scale = float(attrs.get("grad_scale", 1.0))
    normalization = attrs.get("normalization", "null")

    @functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
    def core(data, gs, norm):
        return data

    def fwd(data, gs, norm):
        return data, data

    def bwd(gs, norm, res, g):
        data = res
        scale = gs
        if norm == "batch":
            scale = gs / data.shape[0]
        return (jnp.full(data.shape, scale, data.dtype),)

    core.defvjp(fwd, bwd)
    return [core(ins[0], grad_scale, normalization)]


register(
    OpDef(
        "MakeLoss",
        _make_loss,
        arguments=("data",),
        defaults={"grad_scale": 1.0, "valid_thresh": 0.0, "normalization": "null"},
        infer_shape=same_shape_infer(1),
        need_top_grad=False,
        op_class="loss",
    )
)


# softmax_cross_entropy — reference loss_binary_op.cc
def _softmax_cross_entropy(attrs, ins, is_train):
    data, label = ins
    logp = jax.nn.log_softmax(data, axis=-1)
    lbl = label.astype(jnp.int32)
    picked = jnp.take_along_axis(logp, lbl[:, None], axis=-1)
    return [-jnp.sum(picked).reshape(1)]


register(
    OpDef(
        "softmax_cross_entropy",
        _softmax_cross_entropy,
        arguments=("data", "label"),
        infer_shape=lambda attrs, in_shapes: (
            [tuple(in_shapes[0]), (in_shapes[0][0],)],
            [(1,)],
            [],
        ),
        op_class="loss",
    )
)


# --------------------------------------------------------------------------
# UpSampling — reference upsampling-inl.h (nearest; bilinear via Deconvolution)
# --------------------------------------------------------------------------
def _upsampling(attrs, ins, is_train):
    scale = int(attrs["scale"])
    sample_type = attrs.get("sample_type", "nearest")
    if sample_type == "nearest":
        outs = []
        target = None
        for x in ins:
            h, w = x.shape[2], x.shape[3]
            if target is None:
                target = (h * scale, w * scale)
            s = target[0] // h
            up = jnp.repeat(jnp.repeat(x, s, axis=2), s, axis=3)
            outs.append(up)
        return [jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]]
    # bilinear: single input + weight, implemented via resize
    x = ins[0]
    out = jax.image.resize(
        x,
        (x.shape[0], x.shape[1], x.shape[2] * scale, x.shape[3] * scale),
        method="bilinear",
    )
    return [out]


def _upsampling_infer(attrs, in_shapes):
    scale = int(attrs["scale"])
    sample_type = attrs.get("sample_type", "nearest")
    d0 = in_shapes[0]
    if sample_type == "bilinear":
        nf = int(attrs.get("num_filter", d0[1]))
        kernel = 2 * scale - scale % 2
        wshape = (d0[1], 1, kernel, kernel)
        return (
            [tuple(d0), wshape],
            [(d0[0], d0[1], d0[2] * scale, d0[3] * scale)],
            [],
        )
    c = sum(s[1] for s in in_shapes)
    return (
        [tuple(s) for s in in_shapes],
        [(d0[0], c, d0[2] * scale, d0[3] * scale)],
        [],
    )


_ups = OpDef(
    "UpSampling",
    _upsampling,
    arguments=("data",),
    key_var_num_args="num_args",
    defaults={
        "scale": 1,
        "num_filter": 0,
        "sample_type": "nearest",
        "multi_input_mode": "concat",
        "num_args": 1,
        "workspace": 512,
    },
    infer_shape=_upsampling_infer,
)
register(_ups)


# --------------------------------------------------------------------------
# Sequence ops — reference sequence_last/mask/reverse-inl.h
# (TDNC layout: (seq_len, batch, ...))
# --------------------------------------------------------------------------
def _seq_lengths(attrs, ins, maxlen, batch):
    if bool(attrs.get("use_sequence_length", False)) and len(ins) > 1:
        return ins[1].astype(jnp.int32)
    return jnp.full((batch,), maxlen, jnp.int32)


def _sequence_last(attrs, ins, is_train):
    data = ins[0]
    lengths = _seq_lengths(attrs, ins, data.shape[0], data.shape[1])
    idx = jnp.maximum(lengths - 1, 0)
    return [jnp.take_along_axis(
        data, idx.reshape((1, -1) + (1,) * (data.ndim - 2)), axis=0
    )[0]]


_seq_last = OpDef(
    "SequenceLast",
    _sequence_last,
    arguments=("data", "sequence_length"),
    defaults={"use_sequence_length": False},
    infer_shape=lambda attrs, in_shapes: (
        [tuple(s) for s in in_shapes if s is not None],
        [tuple(in_shapes[0][1:])],
        [],
    ),
)
_seq_last.list_arguments = lambda attrs=None: (
    ["data", "sequence_length"]
    if (attrs or {}).get("use_sequence_length")
    else ["data"]
)
register(_seq_last)


def _sequence_mask(attrs, ins, is_train):
    data = ins[0]
    value = float(attrs.get("value", 0.0))
    lengths = _seq_lengths(attrs, ins, data.shape[0], data.shape[1])
    t = jnp.arange(data.shape[0])[:, None]
    mask = t < lengths[None, :]
    mask = mask.reshape(mask.shape + (1,) * (data.ndim - 2))
    return [jnp.where(mask, data, jnp.asarray(value, data.dtype))]


_seq_mask = OpDef(
    "SequenceMask",
    _sequence_mask,
    arguments=("data", "sequence_length"),
    defaults={"use_sequence_length": False, "value": 0.0},
    infer_shape=lambda attrs, in_shapes: (
        [tuple(s) for s in in_shapes if s is not None],
        [tuple(in_shapes[0])],
        [],
    ),
)
_seq_mask.list_arguments = _seq_last.list_arguments
register(_seq_mask)


def _sequence_reverse(attrs, ins, is_train):
    data = ins[0]
    lengths = _seq_lengths(attrs, ins, data.shape[0], data.shape[1])
    maxlen = data.shape[0]
    t = jnp.arange(maxlen)[:, None]
    src = jnp.where(t < lengths[None, :], lengths[None, :] - 1 - t, t)
    return [jnp.take_along_axis(
        data, src.reshape(src.shape + (1,) * (data.ndim - 2)), axis=0
    )]


_seq_rev = OpDef(
    "SequenceReverse",
    _sequence_reverse,
    arguments=("data", "sequence_length"),
    defaults={"use_sequence_length": False},
    infer_shape=lambda attrs, in_shapes: (
        [tuple(s) for s in in_shapes if s is not None],
        [tuple(in_shapes[0])],
        [],
    ),
)
_seq_rev.list_arguments = _seq_last.list_arguments
register(_seq_rev)


# --------------------------------------------------------------------------
# Crop layer (reference crop-inl.h) — crop first input to match second (or
# h_w attr), offset-based
# --------------------------------------------------------------------------
def _crop(attrs, ins, is_train):
    x = ins[0]
    if len(ins) > 1:
        th, tw = ins[1].shape[2], ins[1].shape[3]
    else:
        th, tw = as_tuple(attrs["h_w"], 2, "h_w")
    if bool(attrs.get("center_crop", False)):
        oy = (x.shape[2] - th) // 2
        ox = (x.shape[3] - tw) // 2
    else:
        oy, ox = as_tuple(attrs.get("offset", (0, 0)), 2, "offset")
    return [x[:, :, oy : oy + th, ox : ox + tw]]


def _crop_infer(attrs, in_shapes):
    d0 = in_shapes[0]
    if int(attrs.get("num_args", 1)) > 1 and len(in_shapes) > 1 and in_shapes[1]:
        th, tw = in_shapes[1][2], in_shapes[1][3]
    else:
        th, tw = as_tuple(attrs["h_w"], 2, "h_w")
    return (
        [tuple(s) for s in in_shapes],
        [(d0[0], d0[1], th, tw)],
        [],
    )


register(
    OpDef(
        "Crop",
        _crop,
        arguments=("data",),
        key_var_num_args="num_args",
        defaults={"num_args": 1, "offset": (0, 0), "h_w": (0, 0), "center_crop": False},
        infer_shape=_crop_infer,
    )
)
