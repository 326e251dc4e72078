"""The chunked state-space scan (ops/transformer.py::mamba2; Mamba-2 / SSD).

``S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t`` a head,
G groups of heads sharing B and C. One grid step is one chunk of Q tokens
of one group: nothing of it but its inputs, its output and the state it
entered with reaches HBM.

  grid      (batch, group, chunk), the chunks one after another; the
            group's state is carried in float32 VMEM scratch TRANSPOSED,
            [N, heads * P], so that reading it through C, its update
            from B and their transposes are one product each over all
            of the group's heads (a result 512 lanes wide in the
            Nemotron cell).
  heads     a head's [Q, Q] table ``exp(cum_i - cum_j)`` (masked before
            the exp) times ``C B^T`` (made once a group) is its own left
            operand, so the products inside a chunk are one a head. A
            head narrower than a lane row shares its 128 lanes with its
            neighbours: the product runs over the whole lane tile (the
            MXU is 128 wide whatever the operand) and a select keeps the
            head's own lanes, so no slice leaves the (8, 128) tiling.
  tables    what is per (token, head) is made by XLA from ``dt`` and
            ``a`` (``_ssd_tables``: 2 MB at the cell's shape, float32):
            the running log decay of a chunk as a product with a
            triangular matrix (a ``cumsum`` lowers to a
            ``reduce_window``), its exponentials, token-major (a head a
            column of one lane row: a lane gather spreads a tile's
            heads over their lanes, one pass where two broadcasts and a
            select took three), head-major (a row along lanes, for the
            [Q, Q] table) and a chunk's ``exp(total)`` by lane.
  skip      ``d x`` is added to y here (and its transposes made here):
            as XLA's it was three more passes over [T, H P] float32.
  backward  the same walk from the last chunk to the first carrying the
            state's cotangent, the decay tables rebuilt. The log decay's
            cotangent needs no [Q, Q] reduction: ``cum_i`` multiplies
            everything of ``y_i`` (``dy_i . y_i``), ``-cum_j`` everything
            token j's input reaches (``-(dt x)_j . d(dt x)_j``), and the
            chunk's last one the state it leaves. dB and dC are summed
            over the group's heads by the products' contraction.
  set-up    both bodies are ``jax.lax`` primitives only (a ``jnp`` call
            or an operator on a tracer is a nested ``jit`` to trace,
            2 ms apiece on the chip's host), their iotas and masks made
            once a body; each ``pallas_call`` sits behind a ``jax.jit``
            (one trace a signature however many layers call it,
            ``ssm.scan_kernel_traces``); and the branch for every
            platform but the TPU is the ``jnp.einsum`` form
            (``ops/transformer.py::ssd_scan``), not the Pallas
            interpreter, whose trace of both bodies a step lowered for
            the TPU would pay for nothing. The interpreter runs them
            only where a caller says ``interpret=True`` (their tests).

Log decays, their sums and exponentials, the carried state and every
accumulator float32; the MXU's operands in x's type.
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
    LANES, NEG_INF, VMEM_RAISED_LIMIT, VMEM_SCOPED_DEFAULT, dot_highest,
    first_chunk, no_x64, on_tpu, operand_label, sum_keepdims)

_M_SSD_TRACES = _tm.counter(
    "ssm.scan_kernel_traces", "Traces of a state-space scan kernel's "
    "pallas_call (one a signature and process, however many Mamba2 nodes "
    "call it; nothing per step); labels: mode (fwd / bwd)")

# ``jnp.take_along_axis(table, at, axis=1)`` as the one primitive it ends in
_SSD_LANE_GATHER = lax.GatherDimensionNumbers(
    offset_dims=(), collapsed_slice_dims=(1,), start_index_map=(1,),
    operand_batching_dims=(0,), start_indices_batching_dims=(0,))


def _ssd_tile(per, p):
    """(lanes of a tile, heads in it, tiles a group) for ``per`` heads of
    width ``p``."""
    width = max(p, LANES)
    return width, width // p, per * p // width


def _ssd_vmem_bytes(chunk, per, p, n, itemsize):
    """What a backward step holds, counted generously: the double-buffered
    blocks (x, dx; B, C, dB, dC; y, dy, the entering state; the tables
    lane-padded), the carried cotangent, three operand-typed scratches and
    a dozen float32 temporaries a group wide."""
    wide = chunk * per * p
    state = n * per * p * 4
    return (2 * (2 * wide * itemsize + 4 * chunk * n * itemsize
                 + 2 * wide * 4 + state + 3 * chunk * LANES * 4)
            + state + 3 * wide * itemsize + 12 * max(wide * 4, state)
            + 8 * chunk * chunk * 4)


def ssd_takes(heads, head_dim, state, groups, chunk, dtype):
    """Whether ``ssd_scan`` has tiles for these shapes: whole lane rows
    of chunk and state, heads that tile a lane row (or are whole lane
    rows), a group that is whole tiles, an operand type Mosaic takes and
    a step that fits VMEM. Everything else is the ``jnp.einsum`` form's
    (``ops/transformer.py::ssd_scan``)."""
    if min(heads, head_dim, state, groups, chunk) <= 0 or heads % groups:
        return False
    per = heads // groups
    width = _ssd_tile(per, head_dim)[0]
    return (chunk % LANES == 0 and state % LANES == 0
            and head_dim % 8 == 0 and width % head_dim == 0
            and (per * head_dim) % width == 0 and 4 * per <= LANES
            and jnp.dtype(dtype).name in ("bfloat16", "float32")
            and _ssd_vmem_bytes(chunk, per, head_dim, state,
                                jnp.dtype(dtype).itemsize)
            <= VMEM_RAISED_LIMIT)


def _ssd_tables(dt, a, chunk, groups, p):
    """dt [B, T, H] float32 (T whole chunks), a [H], heads of ``p``
    lanes -> ``cols`` [B, G, T, 128] (dt | cum | exp(cum) | exp(total -
    cum), a head a column, padded to a lane row), ``rows`` [B, G, E, T]
    (cum) and ``ends`` [B, G, T / Q, E P] (exp(total), a head's over its
    lanes), ``cum`` the running sum of ``dt a`` inside each chunk and
    ``total`` its last."""
    b, t, h = dt.shape
    nc, e = t // chunk, h // groups
    dt = dt.reshape(b, nc, chunk, h)
    cum = jnp.einsum("ij,bcjh->bcih",
                     np.tril(np.ones((chunk, chunk), np.float32)), dt * a,
                     precision=lax.Precision.HIGHEST)
    total = cum[:, :, -1:]
    cols = jnp.stack([dt, cum, jnp.exp(cum), jnp.exp(total - cum)],
                     axis=3).reshape(b, nc, chunk, 4, groups, e)
    ends = jnp.repeat(jnp.exp(total).reshape(b, nc, groups, e), p, axis=-1)
    cols = cols.transpose(0, 4, 1, 2, 3, 5).reshape(b, groups, t, 4 * e)
    return (jnp.pad(cols, ((0, 0),) * 3 + ((0, LANES - 4 * e),)),
            cum.reshape(b, t, groups, e).transpose(0, 2, 3, 1),
            ends.transpose(0, 2, 1, 3))


def _ssd_masks(q, width, heads, p):
    """Made once a body: ``causal`` [Q, Q] (j <= i) and the table of
    ``NEG_INF`` its select falls to, and for a tile of several heads the
    head of each lane [Q, W] and each head's own lanes (``None`` and
    ``()`` where the head is the tile)."""
    iota = lax.broadcasted_iota
    causal = lax.ge(iota(jnp.int32, (q, q), 0), iota(jnp.int32, (q, q), 1))
    masked = lax.full((q, q), NEG_INF, jnp.float32)
    if heads == 1:
        return causal, masked, None, ()
    head_of = lax.div(iota(jnp.int32, (q, width), 1), np.int32(p))
    return causal, masked, head_of, tuple(
        lax.eq(head_of, np.int32(h)) for h in range(heads))


def _ssd_by_head(cols_ref, column, head_of, shape):
    """A [Q, W] table whose lanes of the tile's head h hold column
    ``column + h`` of ``cols_ref``: a broadcast of the one column where
    the head is the tile, else a gather along the tables' one lane row."""
    if head_of is None:
        return lax.broadcast_in_dim(cols_ref[:, column:column + 1], shape,
                                    (0, 1))
    at = lax.add(head_of, np.int32(column))
    return lax.gather(cols_ref[...], lax.reshape(at, shape + (1,)),
                      _SSD_LANE_GATHER, (1, 1),
                      mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)


def _ssd_own_lanes(parts, mine):
    """[Q, W] taking head h's lanes from ``parts[h]``."""
    out = parts[0]
    for h in range(1, len(parts)):
        out = lax.select(mine[h], parts[h], out)
    return out


def _ssd_decay(cols_ref, rows_ref, per, e, causal, masked):
    """Head e's [Q, Q] table ``exp(cum_i - cum_j)`` for j <= i, else 0."""
    return lax.exp(lax.select(
        causal, lax.sub(cols_ref[:, per + e:per + e + 1],
                        rows_ref[e:e + 1, :]), masked))


def _ssd_end(ends_ref, reverse):
    """[1, E P]: exp(total) of this step's chunk over each head's lanes
    (the group's ``ends`` block holds every chunk's)."""
    c = pl.program_id(2)
    if reverse:
        c = lax.sub(lax.sub(pl.num_programs(2), np.int32(1)), c)
    return ends_ref[pl.ds(c, 1), :]


def _ssd_fwd_kernel(x_ref, b_ref, c_ref, cols_ref, rows_ref, ends_ref,
                    skip_ref, y_ref, ent_ref, state, xw_s, *, per, p):
    """One chunk of one group. x [Q, E P]; B, C [Q, N]; cols [Q, 128];
    rows [E, Q]; ends [T / Q, E P]; skip [1, E P] -> y [Q, E P] float32
    (the skip's ``d x`` added) and the state the chunk entered with,
    [N, E P] float32."""
    q = x_ref.shape[0]
    op, f32 = x_ref.dtype, jnp.float32
    cast, mul, add = lax.convert_element_type, lax.mul, lax.add
    width, heads, tiles = _ssd_tile(per, p)
    causal, masked, head_of, mine = _ssd_masks(q, width, heads, p)

    def by_head(column):
        return _ssd_by_head(cols_ref, column, head_of, (q, width))

    @pl.when(first_chunk())
    def _():
        state[...] = lax.full(state.shape, 0, f32)

    entered = state[...]
    ent_ref[...] = entered
    bm, cm = b_ref[...], c_ref[...]
    cb = dot_highest(cm, bm, (1, 1))                      # C_i . B_j
    through_c = dot_highest(cm, cast(entered, op), (1, 0))
    for k in range(tiles):
        at = slice(k * width, (k + 1) * width)
        first = k * heads
        x32 = cast(x_ref[:, at], f32)
        dt = by_head(first)
        xdt = cast(mul(x32, dt), op)
        parts = [
            dot_highest(cast(mul(cb, _ssd_decay(cols_ref, rows_ref, per,
                                             first + h, causal, masked)),
                          op), xdt, (1, 0))
            for h in range(heads)]
        y_ref[:, at] = add(
            add(_ssd_own_lanes(parts, mine), mul(skip_ref[:, at], x32)),
            mul(lax.slice_in_dim(through_c, at.start, at.stop, axis=1),
                by_head(2 * per + first)))
        xw_s[:, at] = cast(mul(x32, mul(dt, by_head(3 * per + first))), op)
    state[...] = add(dot_highest(bm, xw_s[...], (0, 0)),     # [N, E P]
                     mul(entered, _ssd_end(ends_ref, False)))


def _ssd_bwd_kernel(x_ref, b_ref, c_ref, cols_ref, rows_ref, ends_ref,
                    skip_ref, y_ref, ent_ref, dy_ref, dx_ref, db_ref, dc_ref,
                    small_ref, dskip_ref, dstate, xw_s, dr_s, *, per, p):
    """The same chunk with dy [Q, E P] float32 and the cotangent of the
    state it leaves (carried, [N, E P]) -> dx, dB, dC, ``small``
    [Q, 2 E] (a head's ``x_j . d(dt x)_j``, dt's own cotangent, and the
    cotangent of its running log decay) and the skip's cotangent by
    lane, ``sum_j dy_j x_j`` ([1, E P], summed over the group's
    chunks)."""
    q = x_ref.shape[0]
    op, f32 = x_ref.dtype, jnp.float32
    cast, mul, add, sub = (lax.convert_element_type, lax.mul, lax.add,
                           lax.sub)
    width, heads, tiles = _ssd_tile(per, p)
    causal, masked, head_of, mine = _ssd_masks(q, width, heads, p)
    last_row = lax.eq(lax.broadcasted_iota(jnp.int32, (q, width), 0),
                      np.int32(q - 1))
    zero = lax.full((q, width), 0, f32)

    def by_head(column):
        return _ssd_by_head(cols_ref, column, head_of, (q, width))

    def cut(v, at):
        return lax.slice_in_dim(v, at.start, at.stop, axis=1)

    @pl.when(first_chunk())
    def _():
        dstate[...] = lax.full(dstate.shape, 0, f32)
        dskip_ref[...] = lax.full(dskip_ref.shape, 0, f32)

    entered, dleft = ent_ref[...], dstate[...]
    entered_op, dleft_op = cast(entered, op), cast(dleft, op)
    bm, cm = b_ref[...], c_ref[...]
    cb = dot_highest(cm, bm, (1, 1))
    dxw = dot_highest(bm, dleft_op, (1, 0))               # B_j . dS', [Q, E P]
    end = _ssd_end(ends_ref, True)
    carried = mul(end, sum_keepdims(mul(dleft, entered), 0))
    dcb = lax.full((q, q), 0, f32)
    for k in range(tiles):
        at = slice(k * width, (k + 1) * width)
        first = k * heads
        x32 = cast(x_ref[:, at], f32)
        dy = dy_ref[:, at]
        dy_op = cast(dy, op)
        dt = by_head(first)
        to_end = by_head(3 * per + first)
        u = mul(x32, dt)
        xdt = cast(u, op)
        xw = mul(u, to_end)
        xw_s[:, at] = cast(xw, op)
        dr_s[:, at] = cast(mul(dy, by_head(2 * per + first)), op)
        parts = []
        for h in range(heads):
            decay = _ssd_decay(cols_ref, rows_ref, per, first + h, causal,
                               masked)
            own = xdt if heads == 1 else cast(
                lax.select(mine[h], u, zero), op)
            dcb = add(dcb, mul(decay, dot_highest(dy_op, own, (1, 1))))
            parts.append(dot_highest(cast(mul(cb, decay), op), dy_op, (0, 0)))
        inside = _ssd_own_lanes(parts, mine)
        dxw_k = cut(dxw, at)
        through = mul(xw, dxw_k)
        du = add(inside, mul(to_end, dxw_k))
        skip = skip_ref[:, at]
        dx_ref[:, at] = cast(add(mul(dt, du), mul(skip, dy)), dx_ref.dtype)
        dskip_ref[:, at] = add(dskip_ref[:, at], sum_keepdims(mul(dy, x32), 0))
        # d total: the state the chunk leaves is exp(total) (entered +
        # what the chunk's tokens add)
        leaves = add(sum_keepdims(through, 0), cut(carried, at))
        # a pair (i, j) of one chunk enters cum_i and leaves cum_j with
        # the SAME value only if both sides see the products' rounded
        # operands: their difference is what a running sum keeps
        dcum = add(
            sub(sub(mul(cast(dy_op, f32),
                        sub(y_ref[:, at], mul(skip, x32))),
                    mul(cast(xdt, f32), inside)), through),
            lax.select(last_row,
                       lax.broadcast_in_dim(leaves, (q, width), (0, 1)),
                       zero))
        for column, value in ((first, mul(x32, du)), (per + first, dcum)):
            for h in range(heads):
                own = value if heads == 1 else lax.select(mine[h], value,
                                                          zero)
                small_ref[:, column + h:column + h + 1] = sum_keepdims(own, 1)
    dcb_op = cast(dcb, op)
    dc_ref[...] = cast(add(dot_highest(dr_s[...], entered_op, (1, 1)),
                           dot_highest(dcb_op, bm, (1, 0))), dc_ref.dtype)
    db_ref[...] = cast(add(dot_highest(xw_s[...], dleft_op, (1, 1)),
                           dot_highest(dcb_op, cm, (0, 0))), db_ref.dtype)
    dstate[...] = add(dot_highest(cm, dr_s[...], (0, 0)), mul(dleft, end))


def _ssd_name(which, dtype, chunk, p, n):
    return "ssd_%s_%s_q%d_p%d_n%d" % (which, operand_label(dtype), chunk,
                                      p, n)


def _ssd_specs(chunk, per, p, n, nc, reverse):
    """Block specs of (a group's columns of x, of B and C, the token-major
    tables and the backward's ``small``, the head-major table, every
    chunk's ``ends``, the skip's row, the entering state) at grid step
    (batch, group, chunk), the chunks walked downwards under
    ``reverse``."""
    def at(c):
        return lax.sub(np.int32(nc - 1), c) if reverse else c

    def by_token(width):
        return pl.BlockSpec((None, None, chunk, width),
                            lambda b, g, c: (b, g, at(c), 0))

    return (pl.BlockSpec((None, chunk, per * p),
                         lambda b, g, c: (b, at(c), g)),
            pl.BlockSpec((None, chunk, n), lambda b, g, c: (b, at(c), g)),
            by_token(LANES), by_token(2 * per),
            pl.BlockSpec((None, None, per, chunk),
                         lambda b, g, c: (b, g, 0, at(c))),
            pl.BlockSpec((None, None, nc, per * p),
                         lambda b, g, c: (b, g, 0, 0)),
            pl.BlockSpec((1, per * p), lambda b, g, c: (0, g)),
            pl.BlockSpec((None, None, None, n, per * p),
                         lambda b, g, c: (b, at(c), g, 0, 0)))


def _ssd_params(chunk, per, p, n, dtype):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=max(
            VMEM_SCOPED_DEFAULT,
            _ssd_vmem_bytes(chunk, per, p, n, jnp.dtype(dtype).itemsize)))


@functools.partial(jax.jit,
                   static_argnames=("chunk", "per", "p", "interpret"))
def ssd_fwd_call(x, bm, cm, cols, rows, ends, skip, *, chunk, per, p,
                 interpret):
    """x [B, T, H P], bm and cm [B, T, G N], the tables, skip [1, H P]
    -> y [B, T, H P] float32 and the entering states [B, T / Q, G, N,
    E P] float32."""
    _M_SSD_TRACES.inc(mode="fwd")
    b, t, _ = x.shape
    groups = cols.shape[1]
    n = bm.shape[2] // groups
    nc = t // chunk
    (wide, narrow, cols_spec, _, rows_spec, ends_spec, skip_spec,
     state_spec) = _ssd_specs(chunk, per, p, n, nc, False)
    with no_x64():
        return pl.pallas_call(
            functools.partial(_ssd_fwd_kernel, per=per, p=p),
            grid=(b, groups, nc),
            in_specs=[wide, narrow, narrow, cols_spec, rows_spec, ends_spec,
                      skip_spec],
            out_specs=[wide, state_spec],
            out_shape=[
                jax.ShapeDtypeStruct(x.shape, jnp.float32),
                jax.ShapeDtypeStruct((b, nc, groups, n, per * p),
                                     jnp.float32)],
            scratch_shapes=[pltpu.VMEM((n, per * p), jnp.float32),
                            pltpu.VMEM((chunk, per * p), x.dtype)],
            compiler_params=_ssd_params(chunk, per, p, n, x.dtype),
            name=_ssd_name("fwd", x.dtype, chunk, p, n),
            interpret=interpret,
        )(x, bm, cm, cols, rows, ends, skip)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "per", "p", "interpret"))
def ssd_bwd_call(x, bm, cm, cols, rows, ends, skip, y, entering, dy, *,
                 chunk, per, p, interpret):
    """-> dx [B, T, H P], dB and dC [B, T, G N] in the operands' type,
    ``small`` [B, G, T, 2 E] and the skip's cotangent by lane
    [B, G, 1, E P], float32 (``_ssd_bwd_kernel``)."""
    _M_SSD_TRACES.inc(mode="bwd")
    b, t, _ = x.shape
    groups = cols.shape[1]
    n = bm.shape[2] // groups
    nc = t // chunk
    (wide, narrow, cols_spec, small_spec, rows_spec, ends_spec, skip_spec,
     state_spec) = _ssd_specs(chunk, per, p, n, nc, True)
    with no_x64():
        return pl.pallas_call(
            functools.partial(_ssd_bwd_kernel, per=per, p=p),
            grid=(b, groups, nc),
            in_specs=[wide, narrow, narrow, cols_spec, rows_spec, ends_spec,
                      skip_spec, wide, state_spec, wide],
            out_specs=[wide, narrow, narrow, small_spec,
                       pl.BlockSpec((None, None, 1, per * p),
                                    lambda b_, g, c: (b_, g, 0, 0))],
            out_shape=[
                jax.ShapeDtypeStruct(x.shape, x.dtype),
                jax.ShapeDtypeStruct(bm.shape, bm.dtype),
                jax.ShapeDtypeStruct(cm.shape, cm.dtype),
                jax.ShapeDtypeStruct((b, groups, t, 2 * per), jnp.float32),
                jax.ShapeDtypeStruct((b, groups, 1, per * p), jnp.float32)],
            scratch_shapes=[pltpu.VMEM((n, per * p), jnp.float32),
                            pltpu.VMEM((chunk, per * p), x.dtype),
                            pltpu.VMEM((chunk, per * p), x.dtype)],
            compiler_params=_ssd_params(chunk, per, p, n, x.dtype),
            name=_ssd_name("bwd", x.dtype, chunk, p, n),
            interpret=interpret,
        )(x, bm, cm, cols, rows, ends, skip, y, entering, dy)


def _ssd_flat(x, bmat, cmat, skip):
    """The operands as the kernels hold them: heads and groups side by
    side in the last dimension, the skip spread over its head's lanes."""
    b, t, h, p = x.shape
    width = bmat.shape[2] * bmat.shape[3]
    return (x.reshape(b, t, h * p), bmat.reshape(b, t, width),
            cmat.reshape(b, t, width), jnp.repeat(skip, p).reshape(1, h * p))


def _ssd_einsum(flat, dt, a, skip, chunk, groups):
    """The scan with its skip in the ``jnp.einsum`` form on the kernels'
    operands, y as they give it ([B, T, H P] float32): the branch for
    every platform but the TPU."""
    from ..transformer import ssd_scan as einsum_form

    b, t, h = dt.shape
    x, bmat, cmat = (v.reshape(b, t, heads, -1)
                     for v, heads in zip(flat, (h, groups, groups)))
    y = (einsum_form(x, bmat, cmat, dt, a, chunk)
         + skip[:, None] * x.astype(jnp.float32))
    return y.reshape(b, t, -1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _ssd(x, bmat, cmat, dt, a, skip, chunk, interpret):
    return _ssd_fwd(x, bmat, cmat, dt, a, skip, chunk, interpret)[0]


def _ssd_fwd(x, bmat, cmat, dt, a, skip, chunk, interpret):
    # inside an enclosing jit this runs twice, for the custom_vjp's
    # primal and, when that jit is linearized, for this rule, and jax
    # keys a trace on the abstract mesh in context: none the first time,
    # the empty one the second. Naming the current one makes them one
    # key, and the forward (tables, both branches, the body) one trace.
    with jax.sharding.use_abstract_mesh(jax.sharding.get_abstract_mesh()):
        res = _ssd_forward(x, bmat, cmat, dt, a, skip, chunk=chunk,
                           interpret=interpret)
    return res[-2].reshape(x.shape), res


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _ssd_forward(x, bmat, cmat, dt, a, skip, *, chunk, interpret):
    """The backward's residuals: the operands as the kernels hold them,
    the tables, dt, a, the skip, y [B, T, H P] float32 and the state each
    chunk entered with (zeros off the TPU, where the einsum form's own
    transpose is the backward)."""
    b, t, h, p = x.shape
    groups, n = bmat.shape[2:]
    res = (_ssd_flat(x, bmat, cmat, skip),
           _ssd_tables(dt, a, chunk, groups, p), dt, a, skip)

    def kernels(flat, tables, dt, a, skip, interpret):
        return ssd_fwd_call(*flat[:3], *tables, flat[3], chunk=chunk,
                            per=h // groups, p=p, interpret=interpret)

    def einsum(flat, tables, dt, a, skip):
        return (_ssd_einsum(flat[:3], dt, a, skip, chunk, groups),
                jnp.zeros((b, t // chunk, groups, n, h // groups * p),
                          jnp.float32))

    return res + tuple(on_tpu(kernels, einsum, interpret, *res))


def _ssd_bwd(chunk, interpret, res, dy):
    flat, tables, dt, a, skip, y, entering = res
    b, t, h = dt.shape
    groups = tables[0].shape[1]
    per, nc = h // groups, t // chunk
    p, n = flat[0].shape[2] // h, flat[1].shape[2] // groups
    dy = dy.reshape(y.shape).astype(jnp.float32)

    def kernels(flat, tables, dt, a, skip, y, entering, dy, interpret):
        dx, db, dc, small, dskip = ssd_bwd_call(
            *flat[:3], *tables, flat[3], y, entering, dy, chunk=chunk,
            per=per, p=p, interpret=interpret)
        x_du, dcum = (
            v.reshape(b, groups, nc, chunk, per).transpose(0, 2, 3, 1, 4)
            .reshape(b, nc, chunk, h)
            for v in (small[..., :per], small[..., per:]))
        # a token's log decay reaches every running sum from its own
        # onwards
        dlog = jnp.einsum("ji,bcjh->bcih",
                          np.tril(np.ones((chunk, chunk), np.float32)),
                          dcum, precision=lax.Precision.HIGHEST)
        return (dx, db, dc, (x_du + dlog * a).reshape(b, t, h),
                jnp.sum(dlog * dt.reshape(b, nc, chunk, h), axis=(0, 1, 2)),
                jnp.sum(dskip.reshape(b, h, p), axis=(0, 2)))

    def einsum(flat, tables, dt, a, skip, y, entering, dy):
        return jax.vjp(
            lambda *ins: _ssd_einsum(ins[:3], *ins[3:], chunk, groups),
            *flat[:3], dt, a, skip)[1](dy)

    grads = on_tpu(kernels, einsum, interpret, *res, dy)
    dx, db, dc = grads[:3]
    return (dx.reshape(b, t, h, p), db.reshape(b, t, groups, n),
            dc.reshape(b, t, groups, n)) + tuple(grads[3:])


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_scan(x, bmat, cmat, dt, a, skip, chunk, interpret=False):
    """``ops/transformer.py::ssd_scan`` with the skip, ``ssd_scan(x, bmat,
    cmat, dt, a, chunk) + skip[:, None] * x`` (x [B, T, H, P], bmat and
    cmat [B, T, G, N] in x's type, dt [B, T, H] float32 and positive, a
    [H] float32 and negative, skip [H] -> y [B, T, H, P] float32), as a
    Pallas kernel pair, differentiable in all six, for the shapes
    ``ssd_takes`` admits. T is padded to whole chunks with ``dt`` 0 (no
    decay, no input). Mosaic where the computation is lowered for the
    TPU and the ``jnp.einsum`` form itself on every other platform, the
    choice made inside the ``custom_vjp`` as ``grouped_matmul`` makes it;
    ``interpret=True`` (the kernels' tests) runs the kernels through the
    Pallas interpreter wherever the computation is lowered. Like
    ``flash_attention``, no partitioning rule: inside a sharded ``jit``,
    call under ``shard_map``."""
    t = x.shape[1]
    pad = -t % chunk
    if pad:
        x, bmat, cmat, dt = (
            jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
            for v in (x, bmat, cmat, dt))
    f32 = jnp.float32
    return _ssd(x, bmat, cmat, dt.astype(f32), a.astype(f32),
                skip.astype(f32), int(chunk), bool(interpret))[:, :t]
