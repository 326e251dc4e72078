"""Conv-backward pair (ROADMAP item 3: the MFU climb).

ResNet's dominant FLOP sink is conv backward, and an explicit tap
decomposition is a candidate against XLA's native
conv-backprop-filter (neither side measured on the chip; PERF.md).
The kernels below productize that decomposition WITHOUT the im2col
patches slab. OPT-IN and staying so: on the v5e Mosaic refuses the
pair at most ResNet-50 shapes (scoped-VMEM stack overflow — the
12 MiB plan budget undercounts what the 4-D blocks take once tiled);
see PERF.md, PR 13.

  wgrad:  gw[o,c,kh,kw] = sum_{n,oh,ow} g[n,o,oh,ow]
                          * xpad[n,c,oh+kh,ow+kw]
  dgrad:  dx = stride-1 conv of the (kh-1-p)-padded grad with the
          180°-rotated, O<->C-swapped filter

Both are tiled over (N, H-out, W-out, C) blocks — a grid over N-blocks
whose per-step VMEM working set is one halo'd NHWC activation block,
one grad block, and the f32 accumulator; the kh*kw filter-tap
accumulation happens in-register per block (one MXU dot_general per
tap), never materializing a kh*kw-sized patches tensor. bf16 inputs
accumulate in f32 via preferred_element_type; the accumulation order
(grid-sequential over N blocks, then taps) is fixed, so bf16 results
are bitwise stable across runs.

Tuned envelope (conv_bwd_plan): stride (1,1), dilation (1,1),
groups 1, f32/bf16, kernel covering its padding (k > p), channel
counts in MXU-friendly multiples, and a VMEM bound on the block
working set. Everything else returns None and the caller falls back
to XLA or the MXNET_CONV_WGRAD=taps lever — the dispatch table is
per-shape and memoized, so the decision costs nothing on the trace
hot path.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .common import no_x64, on_tpu, pad_to

_CONV_VMEM_BUDGET = int(os.environ.get(
    "MXTPU_CONV_KERNEL_VMEM", str(12 * 1024 * 1024)))
conv_plan_cache = {}


def conv_kernel_enabled():
    """Whether the Pallas conv-backward pair replaces XLA's gradient
    convs for in-envelope shapes. ``MXTPU_CONV_KERNEL``: "pallas" takes
    the pair (Mosaic where the step is lowered for the TPU; off the TPU
    the pair's plain form is XLA's gradient convs again); anything else
    keeps XLA's lowering."""
    return os.environ.get("MXTPU_CONV_KERNEL", "") == "pallas"


def conv_bwd_plan(dshape, wshape, stride, pad, dilate, dtype):
    """Per-shape dispatch decision for the conv-backward kernels.

    Returns ``{"block_n": int}`` when BOTH kernels can run this shape
    inside the tuned envelope, else None (caller falls back to XLA /
    the taps lever). Memoized per shape signature so the elif chain in
    ops/nn.py pays one dict lookup per trace."""
    key = (tuple(dshape), tuple(wshape), tuple(stride), tuple(pad),
           tuple(dilate), str(dtype))
    hit = conv_plan_cache.get(key, "miss")
    if hit != "miss":
        return hit
    plan = _conv_bwd_plan_uncached(*key)
    conv_plan_cache[key] = plan
    return plan


def _conv_bwd_plan_uncached(dshape, wshape, stride, pad, dilate, dtype):
    n, c, h, w = dshape
    o, cg, kh, kw = wshape
    if str(dtype) not in ("float32", "bfloat16"):
        return None
    if tuple(stride) != (1, 1) or tuple(dilate) != (1, 1) or cg != c:
        return None
    # dgrad-as-flipped-conv needs the kernel to cover its padding
    if kh - 1 - pad[0] < 0 or kw - 1 - pad[1] < 0:
        return None
    oh = h + 2 * pad[0] - kh + 1
    ow = w + 2 * pad[1] - kw + 1
    if oh < 1 or ow < 1:
        return None
    # MXU-friendly channel counts (lane dim); every ResNet body conv
    # (64..512) qualifies, toy C=3 stems do not
    if c % 8 or o % 8:
        return None
    esz = 2 if str(dtype) == "bfloat16" else 4
    # per-grid-step VMEM at block_n images: halo'd x block + g block +
    # the larger of the two f32 accumulators (wgrad taps / dgrad out)
    def vmem(bn):
        x_blk = bn * (h + 2 * pad[0]) * (w + 2 * pad[1]) * c * esz
        g_blk = bn * max(oh * ow * o,
                         (h + kh - 1) * (w + kw - 1) * o) * esz
        acc = max(kh * kw * o * c * 4, bn * h * w * c * 4)
        return x_blk + g_blk + acc
    if vmem(1) > _CONV_VMEM_BUDGET:
        return None
    block_n = 1
    while (block_n * 2 <= min(n, 8) and n % (block_n * 2) == 0
           and vmem(block_n * 2) <= _CONV_VMEM_BUDGET):
        block_n *= 2
    return {"block_n": block_n}


def _conv_wgrad_kernel(x_ref, g_ref, out_ref, *, bn, oh, ow, kh, kw):
    ni = pl.program_id(0)

    @pl.when(ni == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    g = g_ref[...].astype(jnp.float32).reshape(bn * oh * ow, -1)  # (M, O)
    x = x_ref[...]
    for ih in range(kh):
        for iw in range(kw):
            xs = x[:, ih:ih + oh, iw:iw + ow, :].astype(
                jnp.float32).reshape(bn * oh * ow, -1)  # (M, C)
            out_ref[ih * kw + iw] = out_ref[ih * kw + iw] + \
                jax.lax.dot_general(
                    g, xs, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)  # (O, C)


def _xla_conv(pad):
    """The stride-1 NCHW / OIHW conv both kernels are gradients of: the
    plain forms are its two transposes, XLA's own gradient convs."""
    def conv(d, w):
        return lax.conv_general_dilated(
            d, w, (1, 1), [(p, p) for p in pad],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
    return conv


def conv_bwd_filter(data, grad, wshape, pad, block_n=None, interpret=False):
    """Pallas filter gradient of a stride-1/dilation-1/groups-1 2-D conv.

    data: (N, C, H, W); grad: (N, O, OH, OW) cotangent; wshape:
    (O, C, kh, kw). Returns the f32 filter gradient (O, C, kh, kw).
    The tap accumulation runs in-register per (block_n, OH, OW, C)
    block; f32 accumulation regardless of input dtype. The kernel where
    the computation is lowered for the TPU, XLA's filter-gradient conv on
    every other platform; ``interpret=True`` (the kernels' tests) runs
    the kernel through the Pallas interpreter."""
    n, c, h, w = data.shape
    o, _, kh, kw = wshape
    oh, ow = grad.shape[2], grad.shape[3]
    if block_n is None:
        plan = conv_bwd_plan(data.shape, wshape, (1, 1), pad, (1, 1),
                             data.dtype)
        block_n = plan["block_n"] if plan else 1

    def kernel(data, grad, interpret):
        # layout + halo pad happen OUTSIDE no_x64: under the global x64 a
        # lowered subfunction's cache key would meet i64 and i32 operands
        x_t = jnp.pad(jnp.transpose(data, (0, 2, 3, 1)),
                      ((0, 0), (pad[0], pad[0]), (pad[1], pad[1]), (0, 0)))
        g_t = jnp.transpose(grad, (0, 2, 3, 1))
        x_t, _ = pad_to(x_t, 0, block_n)  # zero images contribute zero
        g_t, _ = pad_to(g_t, 0, block_n)
        hp, wp = x_t.shape[1], x_t.shape[2]
        with no_x64():
            gw = pl.pallas_call(
                functools.partial(_conv_wgrad_kernel, bn=block_n, oh=oh,
                                  ow=ow, kh=kh, kw=kw),
                grid=(x_t.shape[0] // block_n,),
                in_specs=[
                    pl.BlockSpec((block_n, hp, wp, c),
                                 lambda i: (i, 0, 0, 0)),
                    pl.BlockSpec((block_n, oh, ow, o),
                                 lambda i: (i, 0, 0, 0)),
                ],
                # constant index map: the accumulator block stays
                # VMEM-resident across the whole N-block grid
                out_specs=pl.BlockSpec((kh * kw, o, c),
                                       lambda i: (0, 0, 0)),
                out_shape=jax.ShapeDtypeStruct((kh * kw, o, c),
                                               jnp.float32),
                interpret=interpret,
            )(x_t, g_t)
        return jnp.transpose(gw, (1, 2, 0)).reshape(o, c, kh, kw)

    def plain(data, grad):
        gw, = jax.linear_transpose(
            functools.partial(_xla_conv(pad), data),
            jax.ShapeDtypeStruct(tuple(wshape), data.dtype))(grad)
        return gw.astype(jnp.float32)

    return on_tpu(kernel, plain, interpret, data, grad)


def _conv_dgrad_kernel(g_ref, w_ref, out_ref, *, bn, h, w, kh, kw):
    g = g_ref[...]
    acc = jnp.zeros((bn * h * w, out_ref.shape[-1]), jnp.float32)
    for ih in range(kh):
        for iw in range(kw):
            gs = g[:, ih:ih + h, iw:iw + w, :].astype(
                jnp.float32).reshape(bn * h * w, -1)  # (M, O)
            acc = acc + jax.lax.dot_general(
                gs, w_ref[ih, iw].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)  # (M, C)
    out_ref[...] = acc.reshape(out_ref.shape).astype(out_ref.dtype)


def conv_bwd_input(grad, weight, dshape, pad, block_n=None, interpret=False):
    """Pallas data gradient of a stride-1/dilation-1/groups-1 2-D conv.

    grad: (N, O, OH, OW) cotangent; weight: (O, C, kh, kw); dshape:
    the (N, C, H, W) input shape to reconstruct. dgrad is the stride-1
    conv of the (k-1-p)-padded grad with the rotated/transposed filter;
    each grid step computes one (block_n, H, W, C) output block with
    in-register f32 tap accumulation. Returns f32 (N, C, H, W). The
    kernel where the computation is lowered for the TPU, XLA's
    input-gradient conv on every other platform; ``interpret=True`` (the
    kernels' tests) runs the kernel through the Pallas interpreter."""
    n, c, h, w = dshape
    o, _, kh, kw = weight.shape
    if block_n is None:
        plan = conv_bwd_plan(dshape, weight.shape, (1, 1), pad, (1, 1),
                             grad.dtype)
        block_n = plan["block_n"] if plan else 1

    def kernel(grad, weight, interpret):
        ph, pw = kh - 1 - pad[0], kw - 1 - pad[1]
        g_t = jnp.pad(jnp.transpose(grad, (0, 2, 3, 1)),
                      ((0, 0), (ph, ph), (pw, pw), (0, 0)))
        g_t, _ = pad_to(g_t, 0, block_n)
        # w[o, c, ::-1, ::-1] transposed to (kh, kw, O, C): the
        # correlation taps of the full (lhs-dilation-free, stride already
        # 1) dgrad conv
        w_rot = jnp.transpose(weight[:, :, ::-1, ::-1], (2, 3, 0, 1))
        hgp, wgp = g_t.shape[1], g_t.shape[2]
        with no_x64():
            gd = pl.pallas_call(
                functools.partial(_conv_dgrad_kernel, bn=block_n, h=h, w=w,
                                  kh=kh, kw=kw),
                grid=(g_t.shape[0] // block_n,),
                in_specs=[
                    pl.BlockSpec((block_n, hgp, wgp, o),
                                 lambda i: (i, 0, 0, 0)),
                    pl.BlockSpec((kh, kw, o, c), lambda i: (0, 0, 0, 0)),
                ],
                out_specs=pl.BlockSpec((block_n, h, w, c),
                                       lambda i: (i, 0, 0, 0)),
                out_shape=jax.ShapeDtypeStruct(
                    (g_t.shape[0], h, w, c), jnp.float32),
                interpret=interpret,
            )(g_t, w_rot)
        return jnp.transpose(gd[:n], (0, 3, 1, 2))

    def plain(grad, weight):
        gd, = jax.linear_transpose(
            lambda d: _xla_conv(pad)(d, weight),
            jax.ShapeDtypeStruct(tuple(dshape), grad.dtype))(grad)
        return gd.astype(jnp.float32)

    return on_tpu(kernel, plain, interpret, grad, weight)
