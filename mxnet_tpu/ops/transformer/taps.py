"""The causal taps, a short depthwise convolution over time: ``causal_taps``
the plain form, ``ShortConv`` (the double-gated ``conv`` mixer of the LFM2
family) the op. ``Mamba2`` (``ssm.py``) and ``GatedDeltaNet`` (``delta.py``)
run the same taps and share what sits here: ``_taps_site`` (whether
``ops/kernels/taps.py`` takes a convolution), ``_gate_norm_site`` (whether
``ops/kernels/gate_norm.py`` takes the gate and grouped norm behind a scan),
each counting its call site, and ``again``."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ... import telemetry as _tm
from ..registry import OpDef, register
from ..utils import required_shape


def causal_taps(x, weight, bias=None):
    """A causal depthwise convolution over time as shifted multiply-adds:
    x [B, T, C], weight [taps, C] (tap ``taps - 1`` meets the current
    token, ``x`` is zero before the sequence), bias [C] or None -> float32
    [B, T, C], ``bias + sum_j weight[j] * x[t - (taps - 1) + j]`` summed
    in float32 in that order. The plain form: what ``mamba2``,
    ``gated_delta_net`` and ``short_conv`` run on every platform but the
    TPU and for the shapes ``kernels.taps_takes`` refuses, and the oracle
    of ``kernels.causal_conv``, which sums the same terms in the same
    order in VMEM."""
    taps, t = weight.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0))).astype(jnp.float32)
    w = weight.astype(jnp.float32)
    acc = None if bias is None else bias.astype(jnp.float32)
    for j in range(taps):
        term = padded[:, j:j + t] * w[j]
        acc = term if acc is None else acc + term
    return acc


def again(f, remat, **policy):
    """``f``, computed again in the backward pass under ``remat``."""
    return jax.checkpoint(f, **policy) if remat else f


_M_TAPS_LOWERINGS = _tm.counter(
    "causal_taps.lowerings", "Traces of a causal depthwise convolution's "
    "call site (one per convolved array, node and lowering, nothing per "
    "step); labels: site (mamba2 / gated_delta_net / short_conv), "
    "channels, taps, impl (kernel: the Pallas pair of ops/kernels/taps.py "
    "where the step is lowered for the TPU, the jax.numpy form of the "
    "same signature elsewhere; jnp: causal_taps' shifted multiply-adds "
    "everywhere)")


def _taps_site(site, src, conv_weight, form, offset=0, channels=None):
    """Whether ``kernels.causal_conv`` takes ``channels`` columns of src
    from ``offset`` (``conv_weight``'s by default); the call site counts
    itself here, outside its block's ``jax.jit``."""
    from .. import kernels

    taps = conv_weight.shape[0]
    channels = conv_weight.shape[1] if channels is None else channels
    kernel = kernels.taps_takes(channels, src.shape[1], taps, src.dtype,
                                form, offset, src.shape[2])
    _M_TAPS_LOWERINGS.inc(site=site, channels=channels, taps=taps,
                          impl="kernel" if kernel else "jnp")
    return kernel


_M_GATE_NORM_LOWERINGS = _tm.counter(
    "gate_norm.lowerings", "Traces of the gate and grouped RMSNorm of a "
    "Mamba2 or GatedDeltaNet call site (one per node and lowering, nothing "
    "per step); labels: site, groups, width, gate (where not silu), impl "
    "(kernel: the pair of ops/kernels/gate_norm.py where lowered for the TPU, "
    "jax.numpy form of the same signature elsewhere; jnp: the block's "
    "gate_norm closure everywhere)")


def _gate_norm_site(site, form, groups, width, src, core=True, **gated):
    """Whether ``kernels.gated_rms_norm`` takes ``groups`` groups of
    ``width`` columns gated by the first of src's (``core``: whether what
    feeds it is laid out as the kernels read it); the call site counts
    itself here (``gated``: more labels), outside its block's ``jax.jit``."""
    from .. import kernels

    kernel = bool(core) and kernels.gate_norm_takes(
        form, groups, width, src.shape[1], src.dtype, 0, src.shape[2])
    _M_GATE_NORM_LOWERINGS.inc(site=site, groups=groups, width=width,
                               impl="kernel" if kernel else "jnp", **gated)
    return kernel


_M_SCONV_LOWERINGS = _tm.counter(
    "sconv.lowerings", "Traces of a ShortConv call site (one per lowering, "
    "nothing per step); labels: channels, taps, impl (kernel: the Pallas "
    "pair of ops/kernels/taps.py where the step is lowered for the TPU; "
    "jnp: shifted multiply-adds that XLA fuses)")


def gated_taps(proj, conv_weight):
    """``C * causal_taps(B * x)`` of proj [B, T, 3 H] = ``B | C | x``,
    conv_weight [taps, H] -> [B, T, H] in proj's dtype, the gates and the
    sum float32: ``short_conv`` in ``jax.numpy``."""
    h = conv_weight.shape[1]
    f32 = jnp.float32
    with jax.named_scope("gate_in"):
        z = proj[..., :h].astype(f32) * proj[..., 2 * h:].astype(f32)
    with jax.named_scope("conv1d"):
        c = causal_taps(z, conv_weight)
    with jax.named_scope("gate_out"):
        return (proj[..., h:2 * h].astype(f32) * c).astype(proj.dtype)


def short_conv(proj, conv_weight, remat=False):
    """proj [B, T, 3 H] (``in_proj``'s output, ``B | C | x`` in that
    order), conv_weight [taps, H] -> [B, T, H] (``out_proj``'s input):
    ``C * causal_taps(B * x)``, no bias and no activation anywhere. The
    two gates and the taps' sum are float32 whatever ``proj``'s dtype,
    the result ``proj``'s. One Pallas kernel each way where the family
    has tiles for the shapes and the step is lowered for the TPU
    (``kernels.taps_takes`` / ``causal_conv``, scope ``conv1d``: the
    three thirds read where ``proj`` holds them, all of ``dproj`` written
    by the backward), ``gated_taps`` elsewhere (scopes ``gate_in``,
    ``conv1d``, ``gate_out``). ``remat`` (training): the backward pass
    keeps the op's two inputs and computes the float32 tables again, the
    kernel in VMEM, ``gated_taps`` under one ``jax.checkpoint``."""
    from .. import kernels

    kernel = _taps_site("short_conv", proj, conv_weight, "gates")
    _M_SCONV_LOWERINGS.inc(channels=conv_weight.shape[1],
                           taps=conv_weight.shape[0],
                           impl="kernel" if kernel else "jnp")
    if kernel:
        with jax.named_scope("conv1d"):
            return kernels.causal_conv(proj, conv_weight, form="gates",
                                       interpret=kernels.common.INTERPRET)
    return (jax.checkpoint(gated_taps) if remat else gated_taps)(
        proj, conv_weight)


def _short_conv(attrs, ins, is_train):
    return [short_conv(*ins, remat=is_train)]


def _short_conv_infer(attrs, in_shapes):
    taps = int(attrs.get("conv_kernel", 3))
    data = required_shape(in_shapes[0], "ShortConv")
    if taps <= 0 or len(data) != 3 or data[2] % 3:
        raise ValueError(
            "ShortConv: conv_kernel=%d must be positive and data [batch, "
            "time, 3 * channels] (B | C | x), got %s" % (taps, data))
    h = data[2] // 3
    return [data, (taps, h)], [data[:2] + (h,)], []


register(
    OpDef(
        "_contrib_ShortConv",
        _short_conv,
        arguments=("data", "conv_weight"),
        defaults={"conv_kernel": 3},
        infer_shape=_short_conv_infer,
        aliases=("ShortConv",),
        op_class="sconv",
    )
)
