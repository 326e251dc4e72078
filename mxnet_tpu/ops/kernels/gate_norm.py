"""The gate and the grouped RMSNorm between a state-space layer's core and
its output projection (scope ``gate_norm`` of ``ops/transformer.py``'s
``Mamba2`` and ``GatedDeltaNet`` blocks), one pass over the operands each
way. Three forms, a static flag of the one body pair, since the callers
differ in order and in where the core left its output:

  gate_first   ``u = y * act(m z)``, ``r = rsqrt(mean_group(u^2) + eps)``,
               ``gamma.astype(dtype) * (u r).astype(dtype)`` (``Mamba2``):
               ``y`` [B, T, C] float32 as the scan wrote it, ``z`` a window
               of columns of ``in_proj``'s output, ``m`` a fixed scalar
               (Falcon-H1's multiplier) or none
  norm_first   ``(o * rsqrt(mean_head(o^2) + eps) * gamma) * act(gate)``
               (``GatedDeltaNet``): ``o`` [B, H, T, V] float32, head-major
               as the scalar delta rule's kernel wrote it; gate and result
               token-major [B, T, H V]. The pass is the transpose too.
  token_major  ``norm_first``'s order on ``gate_first``'s operands: ``o``
               [B, T, H V] float32 as the channel delta rule's pair wrote
               it (Kimi Delta Attention), a head whole lane rows, gamma
               [V] shared by the heads. Nothing is moved: a head is a
               128-lane range of a token's row on both sides.

``act`` is the gate's activation, a second static flag of the bodies:
``silu`` (``Mamba2``, the scalar ``GatedDeltaNet``) or ``sigmoid`` (Kimi's
gate). A kernel's name carries the form and, where it is not ``silu``, the
gate (``gate_norm_fwd_bf16_r256_g128_token_major_sigmoid``).

  grid      (batch, column tile, row tile), the rows innermost. A column
            tile holds whole groups (gate_first, token_major: as many
            groups as fit ``_COLUMN_TILE`` columns; norm_first: one head
            where V is whole lane rows, else the two that make them: 2 x
            192 = 384). The window's index map adds its offset, a multiple
            of the column tile: no slice is made in front of the call.
  body      a ``fori_loop`` over ``_ROWS`` rows a group (``token_major``:
            ``_ROWS_TOKEN_MAJOR``, its groups one lane row): load, cast to
            float32, the gate, the squares' sum over the group's lanes,
            the scaling, one cast, one store. A step's float32 values
            stay in VMEM between the statistics and the scaling: nothing
            of them is written to HBM. A pair of heads is read side by
            side (one lane shift) and its two statistics are masked sums
            over the block's lanes, so nothing else is cut at lane 192.
  backward  one kernel on the same grid: the op's INPUTS and the result's
            cotangent in, the statistics computed again, ``dy`` (``do``,
            laid out as ``o``) float32 and ``dz`` (``dgate``) in the gate's
            type out. ``dgamma`` a column accumulates in float32 in the
            loop's carry and, over the row tiles, in its [8, column tile]
            output block, folded (over the heads too, where they share
            gamma) and summed outside.
  set-up    as the taps': ``jax.lax`` primitives in the bodies, each
            ``pallas_call`` behind a ``jax.jit``, the ``jax.numpy`` form
            on every platform but the TPU, inside the ``custom_vjp``
            (whose residuals are the op's inputs: what the backward kernel
            computes again in VMEM is what a ``jax.checkpoint`` round the
            op ran as passes of its own).

The gate, the statistics and every sum float32; the results and the
roundings are the ``jax.numpy`` forms' (``plain_form``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import (
    LANES, VMEM_RAISED_LIMIT, VMEM_SCOPED_DEFAULT, no_x64, on_tpu,
    operand_label, sum_keepdims, whole_lanes)

FORMS = ("gate_first", "norm_first", "token_major")
ACTS = ("silu", "sigmoid")
# a loop step of a body: four bf16 sublane tiles of rows. A step's chain
# (load, gate, sum over lanes, rsqrt, scale, store) is latency the next
# step cannot hide, so a step holds enough independent rows to fill it:
# at 16 rows the pair took 1.58 ms a Nemotron block and 2.10 an
# Olmo-Hybrid layer, at 64 rows 1.01 and 0.81 (PERF.md section 7, PR 52)
_ROWS = 64
# ``token_major``'s groups are ONE lane row wide, so 64 rows are 8 vregs an
# array and the loop's own steps show: the pair at the Kimi Linear cell's
# shape read 1.39 ms a layer at 64 rows a step, 1.13 at 128, 1.10 at 256
# and 1.09 at 512 (PERF.md section 7, PR 56). 256 rows of 128 lanes are what
# 64 rows of Nemotron's groups of 512 are.
_ROWS_TOKEN_MAJOR = 256
_ROW_TILES = (1024, 512, 256, 128)
# the widest column tile of ``gate_first`` and ``token_major``, and what a
# backward step's blocks may take of VMEM before a shorter row tile is tried
_COLUMN_TILE = 2048
_BLOCK_BUDGET = 24 * 1024 * 1024


def _rows_a_step(form, row_tile):
    """The rows a loop step of a body takes."""
    return min(row_tile, _ROWS_TOKEN_MAJOR) if form == "token_major" else _ROWS


def gate_norm_vmem_bytes(rows, columns, width, itemsize, form):
    """What a backward step holds: the double-buffered blocks (the core's
    output and its cotangent float32, a head's V columns padded to lane
    rows; the gate, its cotangent and the result's in the gate's type),
    gamma and its gradient's block, and a loop step's float32 values (a
    dozen arrays of a step's rows of one group, or of a pair of heads),
    which Mosaic keeps in VMEM."""
    if form == "norm_first":
        core, step = columns // width * whole_lanes(width), columns
    else:
        core, step = columns, width
    blocks = rows * (2 * 4 * core + 3 * itemsize * columns)
    return (2 * blocks + 4 * 8 * 4 * columns
            + 12 * 4 * _rows_a_step(form, rows) * step + 2 * 1024 * 1024)


def gate_norm_tiles(form, groups, width, time, dtype, offset=0,
                    src_width=None):
    """(row tile, column tile) for ``groups`` groups of ``width`` columns
    whose gate sits at ``offset`` of an array ``src_width`` wide (the
    columns alone by default), or None where the family has none: a group
    that is neither whole lane rows nor (``norm_first``) half of a pair
    that is, a window no column tile of whole groups reaches, a time
    length no tile of at least 128 rows divides, an operand type Mosaic
    does not take, or blocks over the raised VMEM limit."""
    columns = groups * width
    src_width = columns if src_width is None else src_width
    if (form not in FORMS or groups <= 0 or width <= 0
            or jnp.dtype(dtype).name not in ("bfloat16", "float32")
            or offset < 0 or offset + columns > src_width):
        return None
    if form != "norm_first":
        if width % LANES:
            return None
        fit = [n for n in range(1, groups + 1)
               if groups % n == 0 and n * width <= max(_COLUMN_TILE, width)
               and offset % (n * width) == 0]
        if not fit:
            return None
        tile = fit[-1] * width
    else:
        pair = 1 if width % LANES == 0 else 2
        tile = pair * width
        if tile % LANES or groups % pair or offset or src_width != columns:
            return None
    itemsize = jnp.dtype(dtype).itemsize
    held = {r: gate_norm_vmem_bytes(r, tile, width, itemsize, form)
            for r in _ROW_TILES if time % r == 0}
    fits = [r for r in held if held[r] <= VMEM_RAISED_LIMIT]
    if not fits:
        return None
    inside = [r for r in fits if held[r] <= _BLOCK_BUDGET]
    return (inside[0] if inside else fits[-1]), tile


def gate_norm_takes(form, groups, width, time, dtype, offset=0,
                    src_width=None):
    """Whether ``gated_rms_norm`` has tiles for these shapes
    (``gate_norm_tiles``). Everything else is the ``jax.numpy`` form's
    (the ``gate_norm`` closures of ``ops/transformer.py``)."""
    return gate_norm_tiles(form, groups, width, time, dtype, offset,
                           src_width) is not None


def _row_step(r, rows):
    return pl.ds(pl.multiple_of(lax.mul(r, np.int32(rows)), rows), rows)


def _spread(column, like):
    """A [rows, 1] column over ``like``'s lanes."""
    return lax.broadcast_in_dim(column, like.shape, (0, 1))


def _gate(z, scale, act):
    """``m z``, its logistic and ``act(m z)``, float32."""
    g = z if scale is None else lax.mul(z, np.float32(scale))
    sig = lax.logistic(g)
    return g, sig, lax.mul(g, sig) if act == "silu" else sig


def _gate_slope(g, sig, act):
    """``silu'(g) = sig (1 + g (1 - sig))``, ``sigmoid'(g) = sig (1 -
    sig)``."""
    one = np.float32(1)
    if act == "silu":
        return lax.mul(sig, lax.add(one, lax.mul(g, lax.sub(one, sig))))
    return lax.mul(sig, lax.sub(one, sig))


def _by_range(v, ranges, then):
    """``then(mean of v over each of ``ranges`` equal lane ranges)`` (a
    [rows, 1] column a range) spread back over the range's lanes. Two
    ranges (a pair of heads side by side) are two masked sums: no slice
    at a lane that is no multiple of 128."""
    width = np.float32(v.shape[1] // ranges)
    if ranges == 1:
        return _spread(then(lax.div(sum_keepdims(v, 1), width)), v)
    first = lax.lt(lax.broadcasted_iota(jnp.int32, v.shape, 1),
                   np.int32(v.shape[1] // ranges))
    zero = lax.full(v.shape, 0, jnp.float32)
    halves = (lax.select(first, v, zero), lax.select(first, zero, v))
    low, high = (_spread(then(lax.div(sum_keepdims(h, 1), width)), v)
                 for h in halves)
    return lax.select(first, low, high)


def _scale_of(u, eps, ranges=1):
    """``rsqrt(mean(u^2) + eps)`` a row of each range, over its lanes."""
    return _by_range(lax.mul(u, u), ranges, lambda mean: lax.rsqrt(
        lax.add(mean, np.float32(eps))))


def _norm_back(dn, n, r, ranges=1):
    """The cotangent of ``u`` through ``n = u rsqrt(mean(u^2) + eps)``:
    ``r (dn - n mean(dn n))``."""
    mean = _by_range(lax.mul(dn, n), ranges, lambda mean: mean)
    return lax.mul(r, lax.sub(dn, lax.mul(n, mean)))


def _fold(v):
    """[rows, lanes] -> [8, lanes]: the sublane tiles summed."""
    out = lax.slice_in_dim(v, 0, 8, axis=0)
    for at in range(8, v.shape[0], 8):
        out = lax.add(out, lax.slice_in_dim(v, at, at + 8, axis=0))
    return out


def _through(v, dtype):
    """float32 values rounded to ``dtype``, as float32."""
    if jnp.dtype(dtype) == jnp.float32:
        return v
    return lax.convert_element_type(
        lax.convert_element_type(v, dtype), jnp.float32)


def _rows_of(ref, lo, hi, rows):
    return lax.broadcast_in_dim(ref[:, lo:hi], (rows, hi - lo), (0, 1))


def _heads(y_ref, rows):
    """``norm_first``: the block's heads of ``o`` side by side, as the
    gate holds them."""
    heads = [y_ref[j, rows, :] for j in range(y_ref.shape[0])]
    return heads[0] if len(heads) == 1 else lax.concatenate(heads, 1)


def _gate_norm_fwd_kernel(y_ref, z_ref, gamma_ref, o_ref, *, form, width,
                          eps, scale, act):
    """One [row tile, column tile] block. y ([row tile, columns] float32;
    ``norm_first``: [heads, row tile, V]), the gate's block, gamma [1,
    columns] float32 -> the result in the gate's type."""
    tr, tc = o_ref.shape
    cast, f32 = lax.convert_element_type, jnp.float32
    dtype = o_ref.dtype
    per = _rows_a_step(form, tr)

    if form == "norm_first":
        gamma = _rows_of(gamma_ref, 0, tc, per)

        def step(r, carry):
            rows = _row_step(r, per)
            o = _heads(y_ref, rows)
            normed = lax.mul(lax.mul(o, _scale_of(o, eps, tc // width)),
                             gamma)
            gate = _gate(cast(z_ref[rows, :], f32), scale, act)[2]
            o_ref[rows, :] = cast(lax.mul(normed, gate), dtype)
            return carry

        lax.fori_loop(0, tr // per, step, np.int32(0))
        return

    for lo in range(0, tc, width):
        hi = lo + width
        gamma = _rows_of(gamma_ref, lo, hi, per)

        def step(r, carry, lo=lo, hi=hi, gamma=gamma):
            rows = _row_step(r, per)
            gate = _gate(cast(z_ref[rows, lo:hi], f32), scale, act)[2]
            y = y_ref[rows, lo:hi]
            if form == "token_major":
                normed = lax.mul(lax.mul(y, _scale_of(y, eps)), gamma)
                o_ref[rows, lo:hi] = cast(lax.mul(normed, gate), dtype)
                return carry
            u = lax.mul(y, gate)
            n = lax.mul(u, _scale_of(u, eps))
            o_ref[rows, lo:hi] = cast(lax.mul(gamma, _through(n, dtype)),
                                      dtype)
            return carry

        lax.fori_loop(0, tr // per, step, np.int32(0))


def _gate_norm_bwd_kernel(y_ref, z_ref, gamma_ref, dout_ref, dy_ref, dz_ref,
                          dgamma_ref, *, form, width, eps, scale, act):
    """The same block with the result's cotangent -> the cotangents of
    ``y`` (float32, laid out as ``y``) and of the gate (its type), and the
    block's rows of gamma's, summed a sublane into [8, column tile]
    float32 over the row tiles."""
    tr, tc = dout_ref.shape
    cast, f32 = lax.convert_element_type, jnp.float32
    mul = lax.mul
    dtype = dz_ref.dtype
    per = _rows_a_step(form, tr)

    @pl.when(lax.eq(pl.program_id(2), np.int32(0)))
    def _():
        dgamma_ref[...] = lax.full(dgamma_ref.shape, 0, f32)

    if form == "norm_first":
        gamma = _rows_of(gamma_ref, 0, tc, per)
        heads = tc // width

        def step(r, acc):
            rows = _row_step(r, per)
            o = _heads(y_ref, rows)
            r_o = _scale_of(o, eps, heads)
            n = mul(o, r_o)
            g, sig, gate = _gate(cast(z_ref[rows, :], f32), scale, act)
            dout = cast(dout_ref[rows, :], f32)
            dz_ref[rows, :] = cast(
                mul(mul(dout, mul(n, gamma)), _gate_slope(g, sig, act)),
                dtype)
            dnormed = mul(dout, gate)
            do = _norm_back(mul(dnormed, gamma), n, r_o, heads)
            for j in range(heads):
                dy_ref[j, rows, :] = do if heads == 1 else lax.slice_in_dim(
                    do, j * width, (j + 1) * width, axis=1)
            return lax.add(acc, _fold(mul(dnormed, n)))

        dgamma_ref[...] = lax.add(dgamma_ref[...], lax.fori_loop(
            0, tr // per, step, lax.full((8, tc), 0, f32)))
        return

    for lo in range(0, tc, width):
        hi = lo + width
        gamma = _rows_of(gamma_ref, lo, hi, per)

        def step(r, acc, lo=lo, hi=hi, gamma=gamma):
            rows = _row_step(r, per)
            y = y_ref[rows, lo:hi]
            g, sig, gate = _gate(cast(z_ref[rows, lo:hi], f32), scale, act)
            if form == "token_major":
                r_y = _scale_of(y, eps)
                n = mul(y, r_y)
                dout = cast(dout_ref[rows, lo:hi], f32)
                dz_ref[rows, lo:hi] = cast(
                    mul(mul(dout, mul(n, gamma)), _gate_slope(g, sig, act)),
                    dtype)
                dnormed = mul(dout, gate)
                dy_ref[rows, lo:hi] = _norm_back(mul(dnormed, gamma), n, r_y)
                return lax.add(acc, _fold(mul(dnormed, n)))
            u = mul(y, gate)
            r_u = _scale_of(u, eps)
            n = mul(u, r_u)
            dout = cast(dout_ref[rows, lo:hi], f32)
            acc = lax.add(acc, _fold(mul(dout, _through(n, dtype))))
            du = _norm_back(mul(dout, gamma), n, r_u)
            dy_ref[rows, lo:hi] = mul(du, gate)
            dg = mul(mul(du, y), _gate_slope(g, sig, act))
            dz_ref[rows, lo:hi] = cast(
                dg if scale is None else mul(dg, np.float32(scale)), dtype)
            return acc

        dgamma_ref[:, lo:hi] = lax.add(dgamma_ref[:, lo:hi], lax.fori_loop(
            0, tr // per, step, lax.full((8, width), 0, f32)))


def _name(which, dtype, tiles, width, form, act):
    """What a device trace shows: the form and, where it is not ``silu``,
    the gate."""
    return "gate_norm_%s_%s_r%d_g%d_%s%s" % (
        which, operand_label(dtype), tiles[0], width, form,
        "" if act == "silu" else "_" + act)


def _params(tiles, width, dtype, form):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=max(
            VMEM_SCOPED_DEFAULT,
            gate_norm_vmem_bytes(tiles[0], tiles[1], width,
                                 jnp.dtype(dtype).itemsize, form)))


def _specs(tiles, form, width, offset):
    """Block specs at grid step (batch, column tile, row tile) of (``y``
    and its cotangent, the gate's window, gamma, an array as wide as the
    columns, gamma's gradient)."""
    tr, tc = tiles
    shift = np.int32(offset // tc)

    def column(j):
        return lax.add(j, shift) if offset else j

    across = pl.BlockSpec((None, tr, tc), lambda b, j, i: (b, i, j))
    core = across
    if form == "norm_first":
        core = pl.BlockSpec((None, tc // width, tr, width),
                            lambda b, j, i: (b, j, i, 0))
    if form == "gate_first":
        gamma = pl.BlockSpec((1, tc), lambda b, j, i: (0, j))
    else:  # a head's, side by side over one column tile
        gamma = pl.BlockSpec((1, tc), lambda b, j, i: (0, 0))
    return (core,
            pl.BlockSpec((None, tr, tc), lambda b, j, i: (b, i, column(j))),
            gamma, across,
            pl.BlockSpec((None, 8, tc), lambda b, j, i: (b, 0, j)))


_STATIC = ("form", "width", "eps", "scale", "act", "offset", "tiles",
           "interpret")


def _extent(y, form):
    """(batch, time, columns) of the result."""
    if form == "norm_first":
        b, h, t, v = y.shape
        return b, t, h * v
    return y.shape


@functools.partial(jax.jit, static_argnames=_STATIC)
def gate_norm_fwd_call(y, src, gamma, *, form, width, eps, scale, offset,
                       tiles, interpret, act="silu"):
    """y ([B, T, C], ``norm_first``: [B, H, T, V]) float32, src [B, T, W],
    gamma [1, C] (a head's forms: [1, column tile]) float32 -> [B, T, C]
    in src's type."""
    b, t, columns = _extent(y, form)
    core, gate, gamma_spec, across, _ = _specs(tiles, form, width, offset)
    with no_x64():
        return pl.pallas_call(
            functools.partial(_gate_norm_fwd_kernel, form=form, width=width,
                              eps=eps, scale=scale, act=act),
            grid=(b, columns // tiles[1], t // tiles[0]),
            in_specs=[core, gate, gamma_spec],
            out_specs=across,
            out_shape=jax.ShapeDtypeStruct((b, t, columns), src.dtype),
            compiler_params=_params(tiles, width, src.dtype, form),
            name=_name("fwd", src.dtype, tiles, width, form, act),
            interpret=interpret,
        )(y, src, gamma)


@functools.partial(jax.jit, static_argnames=_STATIC)
def gate_norm_bwd_call(y, src, gamma, dout, *, form, width, eps, scale,
                       offset, tiles, interpret, act="silu"):
    """-> ``y``'s cotangent float32 (laid out as ``y``), the gate's [B, T,
    C] in src's type, and gamma's a batch row and sublane, [B, 8, C]
    float32."""
    b, t, columns = _extent(y, form)
    core, gate, gamma_spec, across, small = _specs(tiles, form, width,
                                                   offset)
    with no_x64():
        return pl.pallas_call(
            functools.partial(_gate_norm_bwd_kernel, form=form, width=width,
                              eps=eps, scale=scale, act=act),
            grid=(b, columns // tiles[1], t // tiles[0]),
            in_specs=[core, gate, gamma_spec, across],
            out_specs=[core, across, small],
            out_shape=[
                jax.ShapeDtypeStruct(y.shape, jnp.float32),
                jax.ShapeDtypeStruct((b, t, columns), src.dtype),
                jax.ShapeDtypeStruct((b, 8, columns), jnp.float32)],
            compiler_params=_params(tiles, width, src.dtype, form),
            name=_name("bwd", src.dtype, tiles, width, form, act),
            interpret=interpret,
        )(y, src, gamma, dout)


def plain_form(y, src, gamma, *, form, width, eps, scale, offset,
               act="silu"):
    """The op in ``jax.numpy`` on the kernels' operands, as the
    ``gate_norm`` closures of ``_mamba2_block``, ``_gated_delta_block``
    and ``_channel_delta_block`` write it: the branch for every platform
    but the TPU, and the oracle of the kernels' tests."""
    f32 = jnp.float32
    b, t, columns = _extent(y, form)
    gate = getattr(jax.nn, act)
    if form == "norm_first":
        o = jnp.moveaxis(y, 1, 2)
        var = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
        normed = o * jax.lax.rsqrt(var + eps) * gamma.astype(f32)
        gated = normed.reshape(b, t, columns) * gate(src.astype(f32))
        return gated.astype(src.dtype)
    z = src[..., offset:offset + columns].astype(f32)
    if form == "token_major":
        o = y.reshape(b, t, columns // width, width)
        var = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
        normed = o * jax.lax.rsqrt(var + eps) * gamma.astype(f32)
        return (normed.reshape(b, t, columns) * gate(z)).astype(src.dtype)
    gated = y * gate(z if scale is None else z * scale)
    groups = gated.reshape(b, t, columns // width, width)
    var = jnp.mean(jnp.square(groups), axis=-1, keepdims=True)
    normed = (groups * jax.lax.rsqrt(var + eps)).reshape(b, t, columns)
    return gamma.astype(src.dtype) * normed.astype(src.dtype)


def _gamma_row(gamma, dtype, form, tiles):
    """gamma as the kernels hold it, float32 [1, columns]: rounded to the
    result's type first where the form multiplies in it (``gate_first``),
    a head's side by side over a column tile (the two other forms)."""
    if form == "gate_first":
        gamma = gamma.astype(dtype)
    else:
        gamma = jnp.tile(gamma, tiles[1] // gamma.shape[0])
    return gamma.astype(jnp.float32).reshape(1, -1)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _gate_norm(y, src, gamma, form, width, eps, scale, act, offset, tiles,
               interpret):
    return _gate_norm_fwd(y, src, gamma, form, width, eps, scale, act,
                          offset, tiles, interpret)[0]


def _gate_norm_fwd(y, src, gamma, form, width, eps, scale, act, offset,
                   tiles, interpret):
    # one trace for the primal and the rule: see ``ssd._ssd_fwd``
    with jax.sharding.use_abstract_mesh(jax.sharding.get_abstract_mesh()):
        out = _gate_norm_forward(y, src, gamma, form=form, width=width,
                                 eps=eps, scale=scale, act=act,
                                 offset=offset, tiles=tiles,
                                 interpret=interpret)
    return out, (y, src, gamma)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _gate_norm_forward(y, src, gamma, *, form, width, eps, scale, act,
                       offset, tiles, interpret):
    static = dict(form=form, width=width, eps=eps, scale=scale, act=act,
                  offset=offset)

    def kernels(y, src, gamma, interpret):
        return gate_norm_fwd_call(
            y, src, _gamma_row(gamma, src.dtype, form, tiles), tiles=tiles,
            interpret=interpret, **static)

    return on_tpu(kernels, functools.partial(plain_form, **static),
                  interpret, y, src, gamma)


def _gate_norm_bwd(form, width, eps, scale, act, offset, tiles, interpret,
                   res, dout):
    static = dict(form=form, width=width, eps=eps, scale=scale, act=act,
                  offset=offset)

    def kernels(y, src, gamma, dout, interpret):
        dy, dz, dgamma = gate_norm_bwd_call(
            y, src, _gamma_row(gamma, src.dtype, form, tiles), dout,
            tiles=tiles, interpret=interpret, **static)
        rest = src.shape[2] - dz.shape[2] - offset
        if offset or rest:
            dz = jnp.pad(dz, ((0, 0), (0, 0), (offset, rest)))
        dgamma = jnp.sum(dgamma.reshape(-1, gamma.shape[0]), axis=0)
        return dy, dz, dgamma.astype(gamma.dtype)

    def plain(y, src, gamma, dout):
        return jax.vjp(functools.partial(plain_form, **static), y, src,
                       gamma)[1](dout)

    return on_tpu(kernels, plain, interpret, *res, dout)


_gate_norm.defvjp(_gate_norm_fwd, _gate_norm_bwd)


def gated_rms_norm(y, src, gamma, *, form, eps, groups=None, scale=None,
                   offset=0, act="silu", interpret=False):
    """The gate and the grouped RMSNorm of a state-space block
    (``FORMS``), as a Pallas kernel pair differentiable in all three, for
    the shapes ``gate_norm_takes`` admits. ``gate_first``: y [B, T, C]
    float32 in ``groups`` groups, the gate the ``C`` columns of src [B, T,
    W] from ``offset``, ``scale`` a fixed scalar inside the gate's
    activation, gamma [C]. ``norm_first``: y [B, H, T, V] float32
    (head-major), src the gate [B, T, H V], gamma [V]. ``token_major``:
    y [B, T, H V] float32 in ``groups`` heads, the gate as
    ``gate_first``'s, gamma [V]. ``act``: the gate's activation
    (``ACTS``). -> [B, T, C] in src's type. Mosaic where the computation
    is lowered for the TPU and ``plain_form`` on every other platform, the
    choice made inside the ``custom_vjp``; ``interpret=True`` (the
    kernels' tests) runs the kernels through the Pallas interpreter. No
    partitioning rule: inside a sharded ``jit``, call under ``shard_map``."""
    if form == "norm_first":
        groups, width = y.shape[1], y.shape[3]
    else:
        width = y.shape[2] // groups
    time = _extent(y, form)[1]
    tiles = gate_norm_tiles(form, groups, width, time, src.dtype, offset,
                            src.shape[2])
    if (tiles is None or y.dtype != jnp.float32 or act not in ACTS
            or (form != "gate_first" and scale is not None)):
        raise ValueError(
            "gated_rms_norm: no tiles for %d groups of %d columns over %d "
            "rows (%s) with the gate (%s) at %d of %s, form %r "
            "(gate_norm_takes decides)" % (groups, width, time, y.dtype, act,
                                           offset, src.shape, form))
    return _gate_norm(y, src, gamma, form, int(width), float(eps),
                      None if scale is None else float(scale), act,
                      int(offset), tiles, bool(interpret))
