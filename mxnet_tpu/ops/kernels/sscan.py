"""The selective scan (ops/transformer/ssm.py::mamba1; Mamba-1, Gu & Dao,
arXiv:2312.00752).

``S_t[c, n] = exp(dt_t[c] A[c, n]) S_{t-1}[c, n] + dt_t[c] x_t[c] B_t[n]``,
``y_t[c] = sum_n C_t[n] S_t[c, n] + D[c] x_t[c]``: a decay a channel AND a
state index, so there is no matmul form (``ssd.py`` needs a scalar decay a
head) and the work is elementwise. One grid step is one chunk of ``chunk``
tokens of one tile of ``width`` channels: nothing of it but ``x``, ``dt``,
``B``, ``C`` in and ``y`` out reaches HBM, and the state a chunk entered
with (the backward's residual, [T / chunk, N, D] float32).

  grid      (batch, channel tile, chunk), the chunks one after another;
            the tile's state is carried in float32 VMEM scratch TRANSPOSED,
            [N, width]: the state index on sublanes, channels on lanes, so
            that a token's ``dt`` and ``x`` rows broadcast over sublanes
            and the sum over ``n`` is a sublane reduction.
  B and C   are handed in transposed, [N, T] float32 (XLA's, 0.25 MB each):
            token t's column is picked by a lane compare and a lane
            reduction, and broadcasts over the channels' lanes as a
            [N, 1] column does.
  time      a ``fori_loop`` over blocks of 8 tokens (one sublane tile of
            ``x``, ``dt`` and ``y``), the 8 unrolled.
  backward  the same walk from the last chunk to the first carrying the
            state's cotangent: a chunk's states are computed again from
            the state it entered with and kept in VMEM ([chunk + 1, N,
            width] float32), then the tokens are walked downwards. dB and
            dC are sums over channels: a lane reduction a token into a
            [N, chunk] tile, one partial a channel tile that XLA sums.
            dA and dD are accumulated in their output blocks, resident
            over the chunks.

``dt``, the decays, the state, the sums and every cotangent float32; ``x``
and ``y`` in the model's type.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import telemetry as _tm
from .common import (
    VMEM_RAISED_LIMIT, VMEM_SCOPED_DEFAULT, first_chunk, no_x64, on_tpu,
    operand_label)

_M_SSCAN_TRACES = _tm.counter(
    "ssm.selective_kernel_traces", "Traces of the selective scan's two "
    "pallas_call wrappers (one per signature, not one per layer: each is "
    "behind a jax.jit); labels: mode (fwd / bwd)")

CHUNK = 128            # tokens a grid step: one lane row of B^T and C^T
_WIDTHS = (512, 256, 128)
_ROWS = 8              # tokens a loop step: one float32 sublane tile


def sscan_tiles(channels, state, dtype):
    """(chunk, width) of the kernel pair for ``channels`` channels of
    ``state`` state indices, or None where it has none: whole lane rows of
    channels, whole sublane tiles of state, bf16 or float32."""
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16),
                                jnp.dtype(jnp.float32)):
        return None
    if state <= 0 or state % _ROWS or state > 64:
        return None
    for width in _WIDTHS:
        if channels % width == 0:
            return CHUNK, width
    return None


def sscan_takes(channels, state, dtype):
    """Whether ``selective_scan`` runs the kernel pair where the step is
    lowered for the TPU (shapes and operand type alone decide)."""
    return sscan_tiles(channels, state, dtype) is not None


def sscan_vmem_bytes(chunk, width, state, itemsize):
    """What the backward holds in a grid step: a chunk's states, the
    float32 copies of x, dy and dx, the carried cotangent, the
    double-buffered blocks in and out."""
    hist = (chunk + 1) * state * width * 4
    rows = 3 * chunk * width * 4
    blocks = 2 * chunk * width * (3 * itemsize + 2 * 4)
    small = 6 * state * max(width, chunk) * 4 * 2
    return hist + rows + blocks + small


def _column(tile, at):
    """The [N, 1] column of the [N, chunk] tile that ``at`` marks."""
    return jnp.sum(jnp.where(at, tile, 0.0), axis=1, keepdims=True)


def _advance(s, dt_t, dtx_t, a, b_t):
    """``S_t`` from ``S_{t-1}``: dt_t and dtx_t [1, width] rows, a
    [N, width], b_t the token's [N, 1] column of B."""
    return jnp.exp(dt_t * a) * s + dtx_t * b_t


def _sscan_fwd_kernel(x_ref, dt_ref, bt_ref, ct_ref, a_ref, skip_ref,
                      y_ref, enter_ref, state, xs, ys):
    f32 = jnp.float32

    @pl.when(first_chunk())
    def _():
        state[...] = jnp.zeros(state.shape, f32)

    enter_ref[...] = state[...]
    xs[...] = x_ref[...].astype(f32)
    a, skip = a_ref[...], skip_ref[...]
    bt, ct = bt_ref[...], ct_ref[...]
    lane = lax.broadcasted_iota(jnp.int32, bt.shape, 1)
    row = lax.broadcasted_iota(jnp.int32, (_ROWS, xs.shape[1]), 0)

    def block(i, s):
        r = pl.multiple_of(i * _ROWS, _ROWS)
        x8, dt8 = xs[pl.ds(r, _ROWS), :], dt_ref[pl.ds(r, _ROWS), :]
        dtx8 = dt8 * x8
        y8 = skip * x8
        for j in range(_ROWS):
            at = lane == r + j
            s = _advance(s, dt8[j:j + 1], dtx8[j:j + 1], a, _column(bt, at))
            read = jnp.sum(_column(ct, at) * s, axis=0, keepdims=True)
            y8 = jnp.where(row == j, y8 + read, y8)
        ys[pl.ds(r, _ROWS), :] = y8
        return s

    state[...] = lax.fori_loop(0, xs.shape[0] // _ROWS, block, state[...])
    y_ref[...] = ys[...].astype(y_ref.dtype)


def _sscan_bwd_kernel(x_ref, dt_ref, bt_ref, ct_ref, a_ref, skip_ref,
                      enter_ref, dy_ref, dx_ref, ddt_ref, dbt_ref, dct_ref,
                      da_ref, dskip_ref, hist, carry, xs, dys, dxs):
    f32 = jnp.float32
    chunk, width = xs.shape

    @pl.when(first_chunk())
    def _():
        carry[...] = jnp.zeros(carry.shape, f32)
        da_ref[...] = jnp.zeros(da_ref.shape, f32)
        dskip_ref[...] = jnp.zeros(dskip_ref.shape, f32)

    xs[...] = x_ref[...].astype(f32)
    dys[...] = dy_ref[...].astype(f32)
    a, skip = a_ref[...], skip_ref[...]
    bt, ct = bt_ref[...], ct_ref[...]
    lane = lax.broadcasted_iota(jnp.int32, bt.shape, 1)
    row = lax.broadcasted_iota(jnp.int32, (_ROWS, width), 0)
    blocks = chunk // _ROWS

    # the chunk's states again, every one kept: hist[t + 1] is S_t
    hist[0] = enter_ref[...]

    def again(i, s):
        r = pl.multiple_of(i * _ROWS, _ROWS)
        x8, dt8 = xs[pl.ds(r, _ROWS), :], dt_ref[pl.ds(r, _ROWS), :]
        dtx8 = dt8 * x8
        for j in range(_ROWS):
            s = _advance(s, dt8[j:j + 1], dtx8[j:j + 1], a,
                         _column(bt, lane == r + j))
            hist[r + j + 1] = s
        return s

    lax.fori_loop(0, blocks, again, hist[0])

    def block(k, val):
        h, dbt, dct, da, dskip = val
        r = pl.multiple_of((blocks - 1 - k) * _ROWS, _ROWS)
        x8, dt8 = xs[pl.ds(r, _ROWS), :], dt_ref[pl.ds(r, _ROWS), :]
        dy8 = dys[pl.ds(r, _ROWS), :]
        dtx8 = dt8 * x8
        dx8 = skip * dy8
        ddt8 = jnp.zeros((_ROWS, width), f32)
        for j in reversed(range(_ROWS)):
            at = lane == r + j
            dy_t, dt_t = dy8[j:j + 1], dt8[j:j + 1]
            g = _column(ct, at) * dy_t + h           # dL/dS_t, whole
            dct = jnp.where(
                at, jnp.sum(hist[r + j + 1] * dy_t, axis=1, keepdims=True),
                dct)
            dbt = jnp.where(
                at, jnp.sum(g * dtx8[j:j + 1], axis=1, keepdims=True), dbt)
            ddtx = jnp.sum(g * _column(bt, at), axis=0, keepdims=True)
            decay = jnp.exp(dt_t * a)
            dlog = g * hist[r + j] * decay           # d / d(dt_t A)
            da = da + dlog * dt_t
            ddt_t = (jnp.sum(dlog * a, axis=0, keepdims=True)
                     + ddtx * x8[j:j + 1])
            dx8 = jnp.where(row == j, dx8 + ddtx * dt_t, dx8)
            ddt8 = jnp.where(row == j, ddt_t, ddt8)
            h = decay * g
        dxs[pl.ds(r, _ROWS), :] = dx8
        ddt_ref[pl.ds(r, _ROWS), :] = ddt8
        return h, dbt, dct, da, dskip + dy8 * x8

    zero = jnp.zeros(bt.shape, f32)
    h, dbt, dct, da, dskip = lax.fori_loop(
        0, blocks, block,
        (carry[...], zero, zero, da_ref[...], dskip_ref[...]))
    carry[...] = h
    da_ref[...] = da
    dskip_ref[...] = dskip
    dbt_ref[...] = dbt
    dct_ref[...] = dct
    dx_ref[...] = dxs[...].astype(dx_ref.dtype)


def _sscan_name(which, dtype, chunk, width, state):
    return "sscan_%s_%s_q%d_w%d_n%d" % (which, operand_label(dtype), chunk,
                                        width, state)


def _sscan_specs(chunk, width, state, nc, reverse):
    """Block specs of (a tile's columns of a chunk's tokens, B^T / C^T's
    chunk, a tile's rows of A^T, the skip's row, the entering state) at
    grid step (batch, tile, chunk), the chunks walked downwards under
    ``reverse``."""
    def at(c):
        return lax.sub(np.int32(nc - 1), c) if reverse else c

    return (pl.BlockSpec((None, chunk, width),
                         lambda b, w, c: (b, at(c), w)),
            pl.BlockSpec((None, state, chunk),
                         lambda b, w, c: (b, 0, at(c))),
            pl.BlockSpec((state, width), lambda b, w, c: (0, w)),
            pl.BlockSpec((1, width), lambda b, w, c: (0, w)),
            pl.BlockSpec((None, None, state, width),
                         lambda b, w, c: (b, at(c), 0, w)))


def _sscan_params(chunk, width, state, dtype):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=min(VMEM_RAISED_LIMIT, max(
            VMEM_SCOPED_DEFAULT, sscan_vmem_bytes(
                chunk, width, state, jnp.dtype(dtype).itemsize))))


_STATIC = ("chunk", "width", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def sscan_fwd_call(x, dt, bt, ct, a_t, skip, *, chunk, width, interpret):
    """x [B, T, D], dt [B, T, D] float32, bt and ct [B, N, T] float32, a_t
    [N, D] float32, skip [1, D] float32 -> y [B, T, D] in x's type and the
    entering states [B, T / chunk, N, D] float32."""
    _M_SSCAN_TRACES.inc(mode="fwd")
    b, t, d = x.shape
    n = a_t.shape[0]
    nc = t // chunk
    wide, narrow, a_spec, skip_spec, state_spec = _sscan_specs(
        chunk, width, n, nc, False)
    with no_x64():
        return pl.pallas_call(
            _sscan_fwd_kernel,
            grid=(b, d // width, nc),
            in_specs=[wide, wide, narrow, narrow, a_spec, skip_spec],
            out_specs=[wide, state_spec],
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                       jax.ShapeDtypeStruct((b, nc, n, d), jnp.float32)],
            scratch_shapes=[pltpu.VMEM((n, width), jnp.float32),
                            pltpu.VMEM((chunk, width), jnp.float32),
                            pltpu.VMEM((chunk, width), jnp.float32)],
            compiler_params=_sscan_params(chunk, width, n, x.dtype),
            name=_sscan_name("fwd", x.dtype, chunk, width, n),
            interpret=interpret,
        )(x, dt, bt, ct, a_t, skip)


@functools.partial(jax.jit, static_argnames=_STATIC)
def sscan_bwd_call(x, dt, bt, ct, a_t, skip, entering, dy, *, chunk, width,
                   interpret):
    """-> dx [B, T, D] in x's type, ddt [B, T, D], the partial dB^T and
    dC^T a channel tile [B, D / width, N, T], dA^T a sequence [B, N, D] and
    the skip's cotangent by sublane [B, 8, D], float32."""
    _M_SSCAN_TRACES.inc(mode="bwd")
    b, t, d = x.shape
    n = a_t.shape[0]
    nc = t // chunk
    wide, narrow, a_spec, skip_spec, state_spec = _sscan_specs(
        chunk, width, n, nc, True)
    f32 = jnp.float32
    partial = pl.BlockSpec(          # dB^T / dC^T: a tile's, a chunk's
        (None, None, n, chunk),
        lambda b_, w, c: (b_, w, 0, lax.sub(np.int32(nc - 1), c)))
    with no_x64():
        return pl.pallas_call(
            _sscan_bwd_kernel,
            grid=(b, d // width, nc),
            in_specs=[wide, wide, narrow, narrow, a_spec, skip_spec,
                      state_spec, wide],
            out_specs=[
                wide, wide, partial, partial,
                pl.BlockSpec((None, n, width), lambda b_, w, c: (b_, 0, w)),
                pl.BlockSpec((None, _ROWS, width),
                             lambda b_, w, c: (b_, 0, w))],
            out_shape=[
                jax.ShapeDtypeStruct(x.shape, x.dtype),
                jax.ShapeDtypeStruct(x.shape, f32),
                jax.ShapeDtypeStruct((b, d // width, n, t), f32),
                jax.ShapeDtypeStruct((b, d // width, n, t), f32),
                jax.ShapeDtypeStruct((b, n, d), f32),
                jax.ShapeDtypeStruct((b, _ROWS, d), f32)],
            scratch_shapes=[pltpu.VMEM((chunk + 1, n, width), f32),
                            pltpu.VMEM((n, width), f32),
                            pltpu.VMEM((chunk, width), f32),
                            pltpu.VMEM((chunk, width), f32),
                            pltpu.VMEM((chunk, width), f32)],
            compiler_params=_sscan_params(chunk, width, n, x.dtype),
            name=_sscan_name("bwd", x.dtype, chunk, width, n),
            interpret=interpret,
        )(x, dt, bt, ct, a_t, skip, entering, dy)


def _sscan_plain(x, dt, bmat, cmat, a, skip):
    """The scan in its ``jax.numpy`` form on the entry's operands, y as
    the kernels give it (x's type): the branch for every platform but the
    TPU."""
    from ..transformer import selective_scan as plain_form

    return plain_form(x, dt, bmat, cmat, a, skip).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _sscan(x, dt, bmat, cmat, a, skip, tiles, interpret):
    return _sscan_fwd(x, dt, bmat, cmat, a, skip, tiles, interpret)[0]


def _sscan_fwd(x, dt, bmat, cmat, a, skip, tiles, interpret):
    # one trace whichever abstract mesh is in context (ssd.py::_ssd_fwd)
    with jax.sharding.use_abstract_mesh(jax.sharding.get_abstract_mesh()):
        y, entering = _sscan_forward(x, dt, bmat, cmat, a, skip, tiles=tiles,
                                     interpret=interpret)
    return y, (x, dt, bmat, cmat, a, skip, entering)


def _transposed(bmat, cmat, a, skip):
    """The small operands as the kernels hold them: B^T and C^T [B, N, T],
    A^T [N, D], the skip's row [1, D], float32."""
    f32 = jnp.float32
    return (jnp.swapaxes(bmat.astype(f32), 1, 2),
            jnp.swapaxes(cmat.astype(f32), 1, 2), a.T, skip.reshape(1, -1))


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def _sscan_forward(x, dt, bmat, cmat, a, skip, *, tiles, interpret):
    """y and the state each chunk entered with (zeros off the TPU, where
    the ``jax.numpy`` form's own transpose is the backward)."""
    chunk, width = tiles
    b, t, d = x.shape

    def kernels(x, dt, bmat, cmat, a, skip, interpret):
        return sscan_fwd_call(x, dt, *_transposed(bmat, cmat, a, skip),
                              chunk=chunk, width=width, interpret=interpret)

    def plain(x, dt, bmat, cmat, a, skip):
        return (_sscan_plain(x, dt, bmat, cmat, a, skip),
                jnp.zeros((b, t // chunk, a.shape[1], d), jnp.float32))

    return on_tpu(kernels, plain, interpret, x, dt, bmat, cmat, a, skip)


def _sscan_bwd(tiles, interpret, res, dy):
    chunk, width = tiles
    x, dt, bmat, cmat, a, skip, entering = res

    def kernels(x, dt, bmat, cmat, a, skip, entering, dy, interpret):
        dx, ddt, dbt, dct, da, dskip = sscan_bwd_call(
            x, dt, *_transposed(bmat, cmat, a, skip), entering, dy,
            chunk=chunk, width=width, interpret=interpret)
        return (dx, ddt,
                jnp.swapaxes(jnp.sum(dbt, axis=1), 1, 2).astype(bmat.dtype),
                jnp.swapaxes(jnp.sum(dct, axis=1), 1, 2).astype(cmat.dtype),
                jnp.sum(da, axis=0).T, jnp.sum(dskip, axis=(0, 1)))

    def plain(x, dt, bmat, cmat, a, skip, entering, dy):
        return jax.vjp(_sscan_plain, x, dt, bmat, cmat, a, skip)[1](dy)

    return tuple(on_tpu(kernels, plain, interpret, *res, dy))


_sscan.defvjp(_sscan_fwd, _sscan_bwd)


def selective_scan(x, dt, bmat, cmat, a, skip, interpret=False):
    """``ops/transformer/ssm.py::selective_scan`` (x [B, T, D], dt [B, T, D]
    float32 and positive, bmat and cmat [B, T, N], a [D, N] float32 and
    negative, skip [D] -> y [B, T, D] in x's type) as a Pallas kernel
    pair, differentiable in all six, for the shapes ``sscan_takes``
    admits. T is padded to whole chunks with ``dt`` 0 (no decay, no
    input). Mosaic where the computation is lowered for the TPU and the
    ``jax.numpy`` form itself on every other platform, the choice made
    inside the ``custom_vjp`` as ``ssd_scan`` makes it; ``interpret=True``
    (the kernels' tests) runs the kernels through the Pallas interpreter
    wherever the computation is lowered. No partitioning rule: inside a
    sharded ``jit``, call under ``shard_map``."""
    tiles = sscan_tiles(x.shape[2], a.shape[1], x.dtype)
    if tiles is None:
        raise ValueError(
            "selective_scan: no tiles for %d channels of %d state indices "
            "in %s (sscan_takes)" % (x.shape[2], a.shape[1], x.dtype))
    t = x.shape[1]
    pad = -t % tiles[0]
    if pad:
        x, dt, bmat, cmat = (jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
                             for v in (x, dt, bmat, cmat))
    f32 = jnp.float32
    return _sscan(x, dt.astype(f32), bmat, cmat, a.astype(f32),
                  skip.astype(f32), tiles, bool(interpret))[:, :t]
