"""The causal taps: the short depthwise convolution over time that
``Mamba2``, ``GatedDeltaNet`` and ``ShortConv`` share
(ops/transformer.py::causal_taps), with what is elementwise round it.

``acc_t = bias + sum_j weight[j] * z[t - (taps - 1) + j]`` a channel, ``z``
zero before the sequence, summed in float32 in that order; then one of
three forms, a static flag of the one body:

  bias_silu  ``silu(acc)``, ``z`` a window of columns of the projection's
             output (``Mamba2``: ``x | B | C`` of ``in_proj``)
  silu       the same without a bias (``GatedDeltaNet``'s query, key and
             value)
  gates      ``C * acc`` with ``z = B * x``, the three thirds of one
             array (``ShortConv``); no bias, no activation

  grid      (batch, column tile, time tile), time walked in order. A
            block is [time tile, column tile] of the array where the
            projection left it: the index map adds the window's offset
            (a multiple of the column tile), so no slice is made in
            front of the call. A width that is no multiple of 128 lanes
            is taken whole (the delta rule's 2,880); the gates' three
            thirds are one block [time tile, 3 H] each way, so that the
            backward writes ``dproj`` itself and nothing concatenates
            behind it.
  body      a ``fori_loop`` over 16 rows (one bf16 tile) of at most 512
            lanes: load, cast to float32, the taps from the 8 rows
            before and the 16 in hand (shifted in registers, never as a
            pass over HBM), the epilogue, one cast, one store. A step's
            values are a few vregs, whatever the tile. The last 8 rows
            of ``z`` are the loop's carry, and between two time tiles a
            [8, width] float32 scratch, zeroed at the first tile.
  backward  one kernel walking time in reverse: it loads the input and
            the output's cotangent, computes ``acc`` again (and
            ``silu``'s derivative, or the gates' products), and writes
            the input's cotangent in the input's type. What it carries
            is the first 8 rows of the NEXT tile's ``d acc`` (the
            anti-causal halo); the 8 rows of ``z`` before a tile come
            with a second, 16-row block of the same array (zeros at the
            first tile). The taps' and the bias's gradients accumulate
            in float32 [8 taps, width] and [8, width] scratch and are
            folded and written at the walk's last step.
  set-up    as the scan's: ``jax.lax`` primitives in the bodies, each
            ``pallas_call`` behind a ``jax.jit``, the ``jax.numpy`` form
            on every platform but the TPU, inside the ``custom_vjp``
            (whose residuals are the op's inputs: the backward kernel's
            recomputation is what a ``jax.checkpoint`` round the op
            would have run as a pass of its own).

The sum, the bias, ``silu``, both gates and the taps' gradients float32;
input, output and cotangents in the input's type.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import (
    LANES, VMEM_RAISED_LIMIT, VMEM_SCOPED_DEFAULT, affine, first_chunk,
    no_x64, on_tpu, operand_label, sum_keepdims, whole_lanes)

FORMS = ("bias_silu", "silu", "gates")
# rows of history a step reads: one float32 sublane tile, so ``taps - 1``
# of them at most
HALO = 8
# a loop step of a body: one bf16 sublane tile of rows, four lane rows
_ROWS, _LANES_A_STEP = 16, 512
_TIME_TILES = (1024, 512, 256, 128)
# the widest column tile (a block's row in HBM is 2 to 4 KB then) and what
# a step's blocks may take of VMEM before a shorter time tile is tried
_COLUMN_TILE = 2048
_BLOCK_BUDGET = 24 * 1024 * 1024


def taps_vmem_bytes(time_tile, width, taps, itemsize, form):
    """What a backward step holds: the double-buffered blocks (input,
    cotangent in, cotangent out, the 16 rows before; the gates' input and
    its cotangent are three widths), weights and their gradients padded
    to sublane tiles, the carried scratches, and a loop step's values
    when they spill."""
    lanes = whole_lanes(width)
    wide = 3 if form == "gates" else 1
    blocks = (2 * wide + 1) * time_tile * lanes * itemsize
    before = wide * _ROWS * lanes * itemsize
    small = (4 + 2 * HALO * (taps + 2)) * lanes * 4
    return 2 * (blocks + before) + small * 4 + 2 * 1024 * 1024


def taps_tiles(channels, time, taps, dtype, form, offset=0, width=None):
    """(time tile, column tile) for ``channels`` columns at ``offset`` of
    an array ``width`` wide (the columns alone by default), or None where
    the family has none: a time length no tile of at least 128 rows
    divides (under that a grid step's fixed cost is the step), taps that
    outreach the carried rows, a width that is neither whole lane rows
    reached by a column tile nor the whole array, an operand type Mosaic
    does not take, or blocks over the raised VMEM limit. An array whose
    only column tiles are under four lane rows is taken whole too (its
    rows are contiguous then, and a grid step is not a sliver)."""
    width = channels if width is None else width
    if (form not in FORMS or not 1 <= taps <= HALO + 1 or channels <= 0
            or jnp.dtype(dtype).name not in ("bfloat16", "float32")):
        return None
    if form == "gates":
        if offset or width != 3 * channels or channels % LANES:
            return None
        column = channels
    else:
        column = 0
        if channels % LANES == 0:
            reach = math.gcd(channels, offset) if offset else channels
            column = max([c for c in range(LANES, min(reach, _COLUMN_TILE)
                                           + 1, LANES) if reach % c == 0],
                         default=0)
        if column < _LANES_A_STEP and offset == 0 and width == channels:
            column = channels
        if not column:
            return None
    itemsize = jnp.dtype(dtype).itemsize
    held = {t: taps_vmem_bytes(t, column, taps, itemsize, form)
            for t in _TIME_TILES if time % t == 0}
    fits = [t for t in held if held[t] <= VMEM_RAISED_LIMIT]
    if not fits:
        return None
    inside = [t for t in fits if held[t] <= _BLOCK_BUDGET]
    return (inside[0] if inside else fits[-1]), column


def taps_takes(channels, time, taps, dtype, form, offset=0, width=None):
    """Whether ``causal_conv`` has tiles for these shapes
    (``taps_tiles``). Everything else is the ``jax.numpy`` form's
    (``ops/transformer.py::causal_taps``)."""
    return taps_tiles(channels, time, taps, dtype, form, offset,
                      width) is not None


def _lane_steps(width):
    """The loop's column ranges of a block ``width`` wide."""
    return [(lo, min(lo + _LANES_A_STEP, width))
            for lo in range(0, width, _LANES_A_STEP)]


def _rows_from(window, start):
    """``_ROWS`` rows of ``window`` [_ROWS + HALO, lanes] from row
    ``start``: the shift, on values in registers."""
    if start % HALO == 0:
        return lax.slice_in_dim(window, start, start + _ROWS, axis=0)
    rolled = pltpu.roll(window, window.shape[0] - start, 0)
    return lax.slice_in_dim(rolled, 0, _ROWS, axis=0)


def _tap_rows(w_ref, b_ref, taps, lo, hi):
    """Each tap's weights, and the bias, over a loop step's rows."""
    shape = (_ROWS, hi - lo)
    spread = [lax.broadcast_in_dim(w_ref[j:j + 1, lo:hi], shape, (0, 1))
              for j in range(taps)]
    return spread, (None if b_ref is None else
                    lax.broadcast_in_dim(b_ref[:, lo:hi], shape, (0, 1)))


def _operands(src_ref, rows, lo, hi, form):
    """float32 ``z`` of these rows and columns, and for the gates the
    three thirds it is made of (B, C in the array's type, x)."""
    f32 = jnp.float32
    if form != "gates":
        return lax.convert_element_type(src_ref[rows, lo:hi], f32), None
    h = src_ref.shape[1] // 3
    gate_b, x = (lax.convert_element_type(src_ref[rows, k + lo:k + hi], f32)
                 for k in (0, 2 * h))
    return lax.mul(gate_b, x), (gate_b, src_ref[rows, h + lo:h + hi], x)


def _taps_sum(shifted, spread, bias):
    """``bias + sum_j w_j z_j`` in ``causal_taps``' order."""
    acc = bias
    for z_j, w_j in zip(shifted, spread):
        term = lax.mul(z_j, w_j)
        acc = term if acc is None else lax.add(acc, term)
    return acc


def _shifted(before, z, taps):
    """Tap j's rows of ``z``: ``taps - 1 - j`` rows back, the first of
    them out of the 8 rows ``before``."""
    window = lax.concatenate([before, z], 0)
    return [_rows_from(window, HALO - (taps - 1 - j)) for j in range(taps)]


def _row_step(r):
    return pl.ds(pl.multiple_of(lax.mul(r, np.int32(_ROWS)), _ROWS), _ROWS)


def _taps_fwd_kernel(*refs, form, taps, bias):
    """One [time tile, width] block. src (the gates': [time tile, 3 H]),
    weight [taps, width] float32 and with ``bias`` a [1, width] float32
    -> out in src's type; scratch: the last 8 rows of ``z``."""
    if bias:
        src_ref, w_ref, b_ref, o_ref, last = refs
    else:
        (src_ref, w_ref, o_ref, last), b_ref = refs, None
    tt, width = o_ref.shape
    cast, f32 = lax.convert_element_type, jnp.float32

    @pl.when(first_chunk())
    def _():
        last[...] = lax.full(last.shape, 0, f32)

    for lo, hi in _lane_steps(width):
        spread, bias_rows = _tap_rows(w_ref, b_ref, taps, lo, hi)

        def step(r, before, lo=lo, hi=hi, spread=spread,
                 bias_rows=bias_rows):
            rows = _row_step(r)
            z, thirds = _operands(src_ref, rows, lo, hi, form)
            acc = _taps_sum(_shifted(before, z, taps), spread, bias_rows)
            if form == "gates":
                y = lax.mul(cast(thirds[1], f32), acc)
            else:
                y = lax.mul(acc, lax.logistic(acc))
            o_ref[rows, lo:hi] = cast(y, o_ref.dtype)
            return lax.slice_in_dim(z, _ROWS - HALO, _ROWS, axis=0)

        last[:, lo:hi] = lax.fori_loop(0, tt // _ROWS, step, last[:, lo:hi])


def _taps_bwd_kernel(*refs, form, taps, bias, tiles):
    """The same block at the reverse walk's step, with the 16 rows of src
    before it and the output's cotangent -> the input's cotangent in
    src's type (the gates': all three thirds) and, at the walk's last
    step, the taps' and the bias's gradients float32. Scratch: the first
    8 rows of the next tile's ``d acc``, the 8 rows of ``z`` before this
    tile, the gradients' partial sums a sublane."""
    if bias:
        (src_ref, head_ref, w_ref, b_ref, dy_ref, dsrc_ref, dw_ref, db_ref,
         later, first, dw_s, db_s) = refs
    else:
        (src_ref, head_ref, w_ref, dy_ref, dsrc_ref, dw_ref, later, first,
         dw_s) = refs
        b_ref = db_s = None
    tt, width = dy_ref.shape
    steps = tt // _ROWS
    cast, f32 = lax.convert_element_type, jnp.float32
    mul, add, sub = lax.mul, lax.add, lax.sub
    at_start = lax.eq(pl.program_id(2), np.int32(tiles - 1))
    h = width if form == "gates" else 0

    def fold(v):
        out = lax.slice_in_dim(v, 0, HALO, axis=0)
        for at in range(HALO, _ROWS, HALO):
            out = add(out, lax.slice_in_dim(v, at, at + HALO, axis=0))
        return out

    @pl.when(first_chunk())
    def _():
        for ref in (later, dw_s) + (() if db_s is None else (db_s,)):
            ref[...] = lax.full(ref.shape, 0, f32)

    @pl.when(at_start)
    def _():
        first[...] = lax.full(first.shape, 0, f32)

    @pl.when(lax.ne(pl.program_id(2), np.int32(tiles - 1)))
    def _():
        for lo, hi in _lane_steps(width):
            z = _operands(head_ref, slice(None), lo, hi, form)[0]
            first[:, lo:hi] = lax.slice_in_dim(z, _ROWS - HALO, _ROWS,
                                               axis=0)

    for lo, hi in _lane_steps(width):
        spread, bias_rows = _tap_rows(w_ref, b_ref, taps, lo, hi)
        one = lax.full((_ROWS, hi - lo), 1, f32)

        def rows_of(rows, before, after, lo=lo, hi=hi, spread=spread,
                    bias_rows=bias_rows, one=one):
            z, thirds = _operands(src_ref, rows, lo, hi, form)
            shifted = _shifted(before, z, taps)
            acc = _taps_sum(shifted, spread, bias_rows)
            dy = cast(dy_ref[rows, lo:hi], f32)
            if form == "gates":
                gate_b, gate_c, x = thirds
                dsrc_ref[rows, h + lo:h + hi] = cast(mul(dy, acc),
                                                     dsrc_ref.dtype)
                dacc = mul(dy, cast(gate_c, f32))
            else:
                s = lax.logistic(acc)
                dacc = mul(dy, mul(s, add(one, mul(acc, sub(one, s)))))
            window = lax.concatenate([dacc, after], 0)
            dz = _taps_sum([_rows_from(window, taps - 1 - j)
                            for j in range(taps)], spread, None)
            if form == "gates":
                dsrc_ref[rows, lo:hi] = cast(mul(dz, x), dsrc_ref.dtype)
                dsrc_ref[rows, 2 * h + lo:2 * h + hi] = cast(
                    mul(dz, gate_b), dsrc_ref.dtype)
            else:
                dsrc_ref[rows, lo:hi] = cast(dz, dsrc_ref.dtype)
            for j, z_j in enumerate(shifted):
                at = slice(j * HALO, (j + 1) * HALO)
                dw_s[at, lo:hi] = add(dw_s[at, lo:hi], fold(mul(dacc, z_j)))
            if db_s is not None:
                db_s[:, lo:hi] = add(db_s[:, lo:hi], fold(dacc))
            return lax.slice_in_dim(dacc, 0, HALO, axis=0)

        def step(k, after, rows_of=rows_of, lo=lo, hi=hi):
            r = sub(np.int32(steps - 1), k)
            z = _operands(src_ref, _row_step(sub(r, np.int32(1))), lo, hi,
                          form)[0]
            return rows_of(_row_step(r),
                           lax.slice_in_dim(z, _ROWS - HALO, _ROWS, axis=0),
                           after)

        after = lax.fori_loop(0, steps - 1, step, later[:, lo:hi])
        later[:, lo:hi] = rows_of(pl.ds(0, _ROWS), first[:, lo:hi], after)

    @pl.when(at_start)
    def _():
        for j in range(taps):
            dw_ref[j:j + 1, :] = sum_keepdims(
                dw_s[j * HALO:(j + 1) * HALO, :], 0)
        if db_s is not None:
            db_ref[...] = sum_keepdims(db_s[...], 0)


def _taps_name(which, dtype, tiles, taps, form):
    return "taps_%s_%s_t%d_c%d_k%d_%s" % (
        which, operand_label(dtype), tiles[0], tiles[1], taps, form)


def _taps_params(tiles, taps, dtype, form):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=max(
            VMEM_SCOPED_DEFAULT,
            taps_vmem_bytes(tiles[0], tiles[1], taps,
                            jnp.dtype(dtype).itemsize, form)))


def _taps_specs(tiles, form, offset, nt, reverse):
    """Block specs at grid step (batch, column tile, time tile) of (the
    input's window, the 16 rows before it, a [rows, column tile] row of
    weights, an array as wide as the window), time walked downwards under
    ``reverse``."""
    tt, tc = tiles
    wide = 3 * tc if form == "gates" else tc
    shift = np.int32(offset // tc)

    def at(i):
        return lax.sub(np.int32(nt - 1), i) if reverse else i

    def column(j):
        return lax.add(j, shift) if offset else j

    def before(i):
        return lax.max(affine(at(i), tt // _ROWS, -1), np.int32(0))

    return (pl.BlockSpec((None, tt, wide),
                         lambda b, j, i: (b, at(i), column(j))),
            pl.BlockSpec((None, _ROWS, wide),
                         lambda b, j, i: (b, before(i), column(j))),
            lambda rows: pl.BlockSpec((rows, tc), lambda b, j, i: (0, j)),
            pl.BlockSpec((None, tt, tc), lambda b, j, i: (b, at(i), j)))


_STATIC = ("form", "offset", "channels", "tiles", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def taps_fwd_call(src, weight, bias=None, *, form, offset, channels, tiles,
                  interpret):
    """src [B, T, W], weight [taps, channels] and bias [1, channels] (or
    None) float32 -> [B, T, channels] in src's type."""
    b, t, _ = src.shape
    taps = weight.shape[0]
    nt = t // tiles[0]
    window, _, row, out = _taps_specs(tiles, form, offset, nt, False)
    operands = (src, weight) + (() if bias is None else (bias,))
    with no_x64():
        return pl.pallas_call(
            functools.partial(_taps_fwd_kernel, form=form, taps=taps,
                              bias=bias is not None),
            grid=(b, channels // tiles[1], nt),
            in_specs=[window, row(taps)] + [row(1)] * (bias is not None),
            out_specs=out,
            out_shape=jax.ShapeDtypeStruct((b, t, channels), src.dtype),
            scratch_shapes=[pltpu.VMEM((HALO, tiles[1]), jnp.float32)],
            compiler_params=_taps_params(tiles, taps, src.dtype, form),
            name=_taps_name("fwd", src.dtype, tiles, taps, form),
            interpret=interpret,
        )(*operands)


@functools.partial(jax.jit, static_argnames=_STATIC)
def taps_bwd_call(src, weight, bias, dy, *, form, offset, channels, tiles,
                  interpret):
    """-> the window's cotangent [B, T, channels] in src's type (the
    gates': the whole of dproj, [B, T, 3 channels]), the taps' gradient
    [B, taps, channels] and with a bias its gradient [B, 1, channels],
    float32."""
    b, t, _ = src.shape
    taps = weight.shape[0]
    tc = tiles[1]
    nt = t // tiles[0]
    window, before, row, out = _taps_specs(tiles, form, offset, nt, True)
    f32 = jnp.float32

    def small(rows):
        return (pl.BlockSpec((None, rows, tc), lambda b_, j, i: (b_, 0, j)),
                jax.ShapeDtypeStruct((b, rows, channels), f32))

    gates = form == "gates"
    outs = [(window if gates else out,
             jax.ShapeDtypeStruct(
                 (b, t, 3 * channels if gates else channels), src.dtype)),
            small(taps)] + [small(1)] * (bias is not None)
    operands = (src, src, weight) + (() if bias is None else (bias,)) + (dy,)
    with no_x64():
        return pl.pallas_call(
            functools.partial(_taps_bwd_kernel, form=form, taps=taps,
                              bias=bias is not None, tiles=nt),
            grid=(b, channels // tc, nt),
            in_specs=([window, before, row(taps)]
                      + [row(1)] * (bias is not None) + [out]),
            out_specs=[spec for spec, _ in outs],
            out_shape=[shape for _, shape in outs],
            scratch_shapes=(
                [pltpu.VMEM((HALO, tc), f32), pltpu.VMEM((HALO, tc), f32),
                 pltpu.VMEM((taps * HALO, tc), f32)]
                + [pltpu.VMEM((HALO, tc), f32)] * (bias is not None)),
            compiler_params=_taps_params(tiles, taps, src.dtype, form),
            name=_taps_name("bwd", src.dtype, tiles, taps, form),
            interpret=interpret,
        )(*operands)


def plain_form(src, weight, bias=None, *, form, offset, channels):
    """The op in ``jax.numpy`` on the kernels' operands: the branch for
    every platform but the TPU, and the oracle of the kernels' tests."""
    from ..transformer import causal_taps, gated_taps

    if form == "gates":
        return gated_taps(src, weight)
    acc = causal_taps(src[..., offset:offset + channels], weight,
                      None if bias is None else bias[0])
    return jax.nn.silu(acc).astype(src.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _taps(src, weight, bias, form, offset, channels, tiles, interpret):
    return _taps_fwd(src, weight, bias, form, offset, channels, tiles,
                     interpret)[0]


def _taps_fwd(src, weight, bias, form, offset, channels, tiles, interpret):
    # one trace for the primal and the rule: see ``ssd._ssd_fwd``
    with jax.sharding.use_abstract_mesh(jax.sharding.get_abstract_mesh()):
        out = _taps_forward(src, weight, bias, form=form, offset=offset,
                            channels=channels, tiles=tiles,
                            interpret=interpret)
    return out, (src, weight, bias)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _taps_forward(src, weight, bias, *, form, offset, channels, tiles,
                  interpret):
    static = dict(form=form, offset=offset, channels=channels)
    operands = (src, weight) + (() if bias is None else (bias,))
    return on_tpu(
        functools.partial(taps_fwd_call, tiles=tiles, **static),
        functools.partial(plain_form, **static), interpret, *operands)


def _taps_bwd(form, offset, channels, tiles, interpret, res, dy):
    src, weight, bias = res
    static = dict(form=form, offset=offset, channels=channels)
    operands = (src, weight) + (() if bias is None else (bias,))

    def kernels(*ins, interpret):
        *ins, dy = ins
        dsrc, *small = taps_bwd_call(
            ins[0], ins[1], ins[2] if len(ins) > 2 else None, dy,
            tiles=tiles, interpret=interpret, **static)
        rest = src.shape[2] - dsrc.shape[2] - offset
        if offset or rest:
            dsrc = jnp.pad(dsrc, ((0, 0), (0, 0), (offset, rest)))
        return (dsrc,) + tuple(jnp.sum(v, axis=0) for v in small)

    def plain(*ins):
        *ins, dy = ins
        return jax.vjp(functools.partial(plain_form, **static), *ins)[1](dy)

    grads = on_tpu(kernels, plain, interpret, *operands, dy)
    return tuple(grads) + (None,) * (bias is None)


_taps.defvjp(_taps_fwd, _taps_bwd)


def causal_conv(src, weight, bias=None, *, form, offset=0, channels=None,
                interpret=False):
    """``causal_taps`` over ``channels`` columns of src [B, T, W] from
    ``offset`` (all of them by default; the gates': src is ``B | C | x``
    and ``channels`` a third of it) with its epilogue (``FORMS``), as a
    Pallas kernel pair differentiable in src, weight [taps, channels] and
    bias [channels], for the shapes ``taps_takes`` admits -> [B, T,
    channels] in src's type. Mosaic where the computation is lowered for
    the TPU and ``plain_form`` on every other platform, the choice made
    inside the ``custom_vjp``; ``interpret=True`` (the kernels' tests)
    runs the kernels through the Pallas interpreter. No partitioning
    rule: inside a sharded ``jit``, call under ``shard_map``."""
    channels = weight.shape[1] if channels is None else channels
    tiles = taps_tiles(channels, src.shape[1], weight.shape[0], src.dtype,
                       form, offset, src.shape[2])
    if tiles is None or (bias is None) != (form != "bias_silu"):
        raise ValueError(
            "causal_conv: no tiles for %d columns at %d of %s, %d taps, "
            "form %r (taps_takes decides)"
            % (channels, offset, src.shape, weight.shape[0], form))
    f32 = jnp.float32
    return _taps(src, weight.astype(f32),
                 None if bias is None else bias.astype(f32).reshape(1, -1),
                 form, int(offset), int(channels), tiles, bool(interpret))
