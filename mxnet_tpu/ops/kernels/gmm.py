"""Grouped matmul: the expert layer's products (parallel/moe.py::topk_moe).

Rows sorted by group (expert), ``group_sizes[g]`` of them for group g,
each group multiplied by its own [k, n] weight. What a row tile costs:

  visits    a row tile of ``tm`` rows is visited once for every group
            that owns rows in it, whole products each time and a masked
            store, so a group boundary inside a tile costs a second
            visit: ``tm`` is bounded by the mean rows a group
            (``gmm_tiles``), or either end of the load distribution
            (a few fat groups and many of a handful of rows; uniform,
            no boundary aligned) loses up to half the MXU time to it.
  traffic   the grid walks n tiles outermost, then visits, then k: with
            ``tk`` the whole k a group's [k, tn] weight block stays in
            VMEM over the consecutive row tiles of that group and a row
            block is fetched once per n tile.
  metadata  offsets, visit -> group and visit -> row tile, made once
            (``gmm_metadata``) and shared by every call over the same
            rows; it reaches the kernels by scalar prefetch and the
            number of visits is the grid's (dynamic) extent.

forward and dgrad are one kernel (dgrad indexes the transposed weight
block, nothing is copied); wgrad contracts the ragged row dimension
into a float32 [tk, tn] accumulator that is stored when the visit's
group changes. Empty groups are VISITED by wgrad (and store zeros), not
by the other two. Operands reach the MXU in the type they arrive in,
every product accumulates in float32 and is rounded once on the way out.
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
    LANES, VMEM_SCOPED_DEFAULT, affine, no_x64, on_tpu, operand_label,
    pad_to, whole_lanes)

_M_GMM_LOWERINGS = _tm.counter(
    "moe.gmm_lowerings", "Traces of a grouped_matmul kernel call site "
    "(one per lowering, nothing per step); labels: mode (fwd / dgrad / "
    "wgrad), operands (the type the MXU is fed), tm, tk, tn and, on a "
    "product over a weight, rhs (declared / held_transposed: the order "
    "the weight reached the kernels in)")

# Row tiles by measurement on the v5e (PERF.md section 7): 128 rows feed
# the MXU at 61% of its peak, 256 at 67%, 512 at 74%, but a tile of the
# mean rows a group is visited twice as often as it is filled; half the
# mean (256 in the OLMoE cell) is the fastest or within 2% of it on the
# cell's skewed load and on a uniform one.
GMM_MIN_ROW_TILE = 128
GMM_MAX_ROW_TILE = 512
GMM_MAX_COL_TILE = 2048


def gmm_vmem_bytes(tm, tk, tn, itemsize, wgrad=False, split=False):
    """Upper bound on the VMEM one grid step holds: double-buffered
    operand and result blocks, the float32 accumulator and a float32
    product-shaped temporary; wgrad also the masked copies of its two
    row blocks (it contracts the rows and reads no ``split``); ``split``
    (forward and dgrad walking the contraction in more than one step,
    ``tk`` under ``k``) a third float32 table of the result's shape. Against Mosaic on the v5e under its default limit,
    bf16: forward 13 MiB counted at (256, 2048, 1024), compiles; 18 at
    (512, 2048, 1024), refused; wgrad 15 at (256, 1024, 1024), compiles;
    28 at (256, 2048, 1024), refused; dgrad with the contraction in two
    steps at (512, 1536, 1024), 15 counted without the third table,
    refused at 16.22 MiB used (the same tiles with the contraction whole
    use 13.08)."""
    if wgrad:
        return (3 * (tm * tk + tm * tn) * itemsize
                + 2 * tk * tn * itemsize + 2 * tk * tn * 4)
    return (2 * (tm * tk + tk * tn + tm * tn) * itemsize
            + (3 if split else 2) * tm * tn * 4)


def gmm_row_tile(m, groups):
    """Rows a tile, from the rows and the groups alone (so that every
    product over the same sorted rows shares one set of metadata): half
    the mean rows a group, as a power of two within the measured
    bounds."""
    mean = max(m // max(groups, 1), 1)
    half = (1 << (mean.bit_length() - 1)) // 2
    return min(GMM_MAX_ROW_TILE, max(GMM_MIN_ROW_TILE, half))


def _col_tile(size, cap):
    """The whole dimension where it is within ``cap``, else the largest
    multiple of 128 within it that divides ``size`` (the whole again
    where there is none: the contraction admits no partial tile)."""
    if size <= cap:
        return size
    for tile in range(cap - cap % 128, 127, -128):
        if size % tile == 0:
            return tile
    return size


def gmm_tiles(m, k, n, groups, dtype, wgrad=False):
    """(tm, tk, tn) for ``[m, k] x [groups, k, n]`` with operands of
    ``dtype``, the largest whose working set ``gmm_vmem_bytes`` counts
    within ``VMEM_SCOPED_DEFAULT`` (Mosaic's default scoped limit; the
    calls ask for no more). ``tm`` is ``gmm_row_tile``'s. Forward keeps
    ``tk`` the whole k while an n tile of 512 still fits beside it (a row
    block is then fetched once per n tile, a group's weight block once
    per n tile whatever its rows) and gives n the rest; dgrad runs the
    transposed problem's, ``gmm_tiles(m, n, k, ...)``. ``wgrad``: the
    [tk, tn] result block as near square as fits (each row block is read
    once per tile of the other dimension)."""
    itemsize = jnp.dtype(dtype).itemsize
    tm = gmm_row_tile(m, groups)
    tk = _col_tile(k, GMM_MAX_COL_TILE)
    tn = _col_tile(n, GMM_MAX_COL_TILE)

    def over():
        return (gmm_vmem_bytes(tm, tk, tn, itemsize, wgrad, split=tk < k)
                > VMEM_SCOPED_DEFAULT)

    def halved(size, tile):
        return _col_tile(size, max(tile // 2, 128)) if tile > 128 else tile

    while over():
        shrink_n = tn >= tk if wgrad else tn > 512 or halved(k, tk) == tk
        if shrink_n and halved(n, tn) < tn:
            tn = halved(n, tn)
        elif halved(k, tk) < tk:
            tk = halved(k, tk)
        elif halved(n, tn) < tn:
            tn = halved(n, tn)
        else:
            break
    return tm, tk, tn


def gmm_metadata(group_sizes, m, tm):
    """What the kernels need to know of ``group_sizes`` over ``m`` sorted
    rows in tiles of ``tm``: ``offsets`` [g + 1] (row at which a group
    starts), and for forward / dgrad and for wgrad each (visit -> group,
    visit -> row tile, number of visits). A group is visited once for
    every row tile it owns rows in; wgrad visits an empty group once as
    well, to store its zeros. The arrays are sized for the worst case
    (``tiles + g - 1`` visits); the grid runs the counted ones. Written
    over ``jax.lax`` (see ``affine``): made once a layer, inside a deep
    step."""
    groups = group_sizes.shape[0]
    tiles_m = -(-m // tm)
    n_visits = tiles_m + groups - 1
    i32 = np.int32
    sizes = lax.convert_element_type(group_sizes, jnp.int32)
    ends = lax.cumsum(sizes)
    starts = lax.sub(ends, sizes)
    offsets = lax.concatenate([jnp.zeros(1, jnp.int32), ends], 0)
    top = i32(tiles_m - 1)
    first = lax.min(lax.div(starts, i32(tm)), top)
    last = lax.min(lax.div(lax.max(lax.sub(ends, i32(1)), i32(0)), i32(tm)),
                   top)
    nonempty = lax.gt(sizes, i32(0))
    owned = lax.select(nonempty, lax.add(lax.sub(last, first), i32(1)),
                       jnp.zeros_like(sizes))
    visit = lax.iota(jnp.int32, n_visits)

    def visits(per_group):
        # visit v belongs to the group whose visits end after v: the
        # number of groups whose visits end at or before it
        done = lax.cumsum(per_group)
        ended = lax.le(lax.broadcast_in_dim(done, (n_visits, groups), (1,)),
                       lax.broadcast_in_dim(visit, (n_visits, groups), (0,)))
        gids = lax.min(
            lax.reduce(lax.convert_element_type(ended, jnp.int32), i32(0),
                       lax.add, (1,)), i32(groups - 1))
        begin = lax.sub(done, per_group)
        take = functools.partial(jnp.take, indices=gids, axis=0)
        tids = lax.add(take(first), lax.sub(visit, take(begin)))
        return (gids, lax.clamp(i32(0), tids, top),
                lax.index_in_dim(done, groups - 1, keepdims=False))

    return ((offsets,) + visits(owned)
            + visits(lax.max(owned, jnp.ones_like(owned))))


def _visit_rows(offs_ref, gids_ref, tids_ref, v, tm):
    """(first row of the visit's group, one past its last, first row of
    the visit's tile)."""
    gid = gids_ref[v]
    return (offs_ref[gid], offs_ref[jax.lax.add(gid, np.int32(1))],
            affine(tids_ref[v], tm))


def _row_mask(shape, row0, start, end):
    """Rows of a [tm, ...] block that belong to [start, end)."""
    rows = jax.lax.add(jax.lax.broadcasted_iota(jnp.int32, shape, 0), row0)
    return jax.lax.bitwise_and(jax.lax.ge(rows, start),
                               jax.lax.lt(rows, end))


def _gmm_kernel(offs_ref, gids_ref, tids_ref, lhs_ref, rhs_ref, out_ref,
                *acc, tm, k_steps, rhs_contract):
    """One (n tile, visit, k tile) step of forward (``rhs_contract`` 0:
    weight block [tk, tn]) or dgrad (1: the block is [tn, tk] of the
    untransposed weight and is contracted over its second dimension).
    The store keeps the rows of the tile's other groups: one select over
    the result block on every visit (no slower on the chip than a second,
    unmasked body for tiles wholly inside a group, and one body fewer to
    trace)."""
    v = pl.program_id(1)
    prod = jax.lax.dot_general(
        lhs_ref[...], rhs_ref[...], (((1,), (rhs_contract,)), ((), ())),
        preferred_element_type=jnp.float32)

    def store(total):
        start, end, row0 = _visit_rows(offs_ref, gids_ref, tids_ref, v, tm)
        out_ref[...] = jax.lax.select(
            _row_mask(out_ref.shape, row0, start, end),
            total.astype(out_ref.dtype), out_ref[...])

    if k_steps == 1:
        store(prod)
        return
    acc_ref, = acc
    ki = pl.program_id(2)

    @pl.when(jax.lax.eq(ki, np.int32(0)))
    def _():
        acc_ref[...] = prod

    @pl.when(jax.lax.gt(ki, np.int32(0)))
    def _():
        acc_ref[...] = acc_ref[...] + prod

    pl.when(jax.lax.eq(ki, np.int32(k_steps - 1)))(
        lambda: store(acc_ref[...]))


def _gmm_wgrad_kernel(offs_ref, gids_ref, tids_ref, lhs_ref, dout_ref,
                      out_ref, acc_ref, *, tm):
    """One (n tile, k tile, visit) step of wgrad: this visit's rows of
    lhs^T x dout into the group's float32 [tk, tn] block."""
    v = pl.program_id(2)
    last_v = jax.lax.sub(pl.num_programs(2), np.int32(1))
    gid = gids_ref[v]
    before = gids_ref[jax.lax.max(jax.lax.sub(v, np.int32(1)), np.int32(0))]
    after = gids_ref[jax.lax.min(jax.lax.add(v, np.int32(1)), last_v)]
    start, end, row0 = _visit_rows(offs_ref, gids_ref, tids_ref, v, tm)
    # the tile lies wholly inside the group: nothing to mask
    whole = jax.lax.bitwise_and(
        jax.lax.le(start, row0),
        jax.lax.le(jax.lax.add(row0, np.int32(tm)), end))

    @pl.when(jax.lax.bitwise_or(jax.lax.eq(v, np.int32(0)),
                                jax.lax.ne(before, gid)))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def accumulate(lhs, dout):
        acc_ref[...] = acc_ref[...] + jax.lax.dot_general(
            lhs, dout, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(whole)
    def _():
        accumulate(lhs_ref[...], dout_ref[...])

    # a tile the group shares (or the last one, past m): rows of other
    # groups, and whatever lies past the last row, are zeroed on BOTH
    # sides; an empty group's visit multiplies nothing
    @pl.when(jax.lax.bitwise_and(jax.lax.bitwise_not(whole),
                                 jax.lax.lt(start, end)))
    def _():
        lhs = lhs_ref[...]
        dout = dout_ref[...]
        accumulate(
            jax.lax.select(_row_mask(lhs.shape, row0, start, end), lhs,
                           jnp.zeros_like(lhs)),
            jax.lax.select(_row_mask(dout.shape, row0, start, end), dout,
                           jnp.zeros_like(dout)))

    @pl.when(jax.lax.bitwise_or(jax.lax.eq(v, last_v),
                                jax.lax.ne(after, gid)))
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _gmm_name(mode, dtype, tm, tk, tn):
    return "gmm_%s_%s_m%d_k%d_n%d" % (
        mode, operand_label(dtype), tm, tk, tn)


_GMM_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"))


@functools.partial(jax.jit,
                   static_argnames=("tiles", "transposed", "interpret"))
def gmm_call(offsets, gids, tids, visits, lhs, rhs, *, tiles, transposed,
             interpret):
    """forward ``[m, k] x [g, k, n] -> [m, n]`` with (tm, tk, tn), or
    with ``transposed`` dgrad ``[m, n] x [g, k, n]^T -> [m, k]`` with
    the same meaning of the three tiles (tn walks the contraction)."""
    tm, tk, tn = tiles
    m = lhs.shape[0]
    k, n = rhs.shape[1], rhs.shape[2]
    if transposed:
        contract, t_contract, out_cols, t_out = n, tn, k, tk
        rhs_spec = pl.BlockSpec(
            (None, tk, tn), lambda j, v, c, offs, gid, tid: (gid[v], j, c))
    else:
        contract, t_contract, out_cols, t_out = k, tk, n, tn
        rhs_spec = pl.BlockSpec(
            (None, tk, tn), lambda j, v, c, offs, gid, tid: (gid[v], c, j))
    k_steps = contract // t_contract
    kern = functools.partial(_gmm_kernel, tm=tm, k_steps=k_steps,
                             rhs_contract=1 if transposed else 0)
    with no_x64():
        return pl.pallas_call(
            kern,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(pl.cdiv(out_cols, t_out), visits, k_steps),
                in_specs=[
                    pl.BlockSpec(
                        (tm, t_contract),
                        lambda j, v, c, offs, gid, tid: (tid[v], c)),
                    rhs_spec,
                ],
                out_specs=pl.BlockSpec(
                    (tm, t_out), lambda j, v, c, offs, gid, tid: (tid[v], j)),
                scratch_shapes=([] if k_steps == 1 else
                                [pltpu.VMEM((tm, t_out), jnp.float32)]),
            ),
            out_shape=jax.ShapeDtypeStruct((m, out_cols), lhs.dtype),
            compiler_params=_GMM_PARAMS,
            name=_gmm_name("dgrad" if transposed else "fwd", lhs.dtype,
                           tm, tk, tn),
            interpret=interpret,
        )(offsets, gids, tids, lhs, rhs)


@functools.partial(jax.jit,
                   static_argnames=("groups", "tiles", "interpret"))
def gmm_wgrad_call(offsets, gids, tids, visits, lhs, dout, *, groups,
                   tiles, interpret):
    """wgrad ``lhs[m, k]^T x dout[m, n]`` per group ``-> [g, k, n]``."""
    tm, tk, tn = tiles
    k, n = lhs.shape[1], dout.shape[1]
    with no_x64():
        return pl.pallas_call(
            functools.partial(_gmm_wgrad_kernel, tm=tm),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(pl.cdiv(n, tn), pl.cdiv(k, tk), visits),
                in_specs=[
                    pl.BlockSpec(
                        (tm, tk), lambda j, i, v, offs, gid, tid: (tid[v], i)),
                    pl.BlockSpec(
                        (tm, tn), lambda j, i, v, offs, gid, tid: (tid[v], j)),
                ],
                out_specs=pl.BlockSpec(
                    (None, tk, tn),
                    lambda j, i, v, offs, gid, tid: (gid[v], i, j)),
                scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
            ),
            out_shape=jax.ShapeDtypeStruct((groups, k, n), lhs.dtype),
            compiler_params=_GMM_PARAMS,
            name=_gmm_name("wgrad", lhs.dtype, tm, tk, tn),
            interpret=interpret,
        )(offsets, gids, tids, lhs, dout)


def _gmm_count(mode, dtype, tiles, rhs_t=None):
    """``rhs_t``: whether the product's weight came held transposed; None
    where there is no weight (a segment sum)."""
    rhs = {} if rhs_t is None else {
        "rhs": "held_transposed" if rhs_t else "declared"}
    _M_GMM_LOWERINGS.inc(mode=mode, operands=operand_label(dtype),
                         tm=tiles[0], tk=tiles[1], tn=tiles[2], **rhs)


def held_transposed(shape):
    """Whether the chip holds a ``[g, k, n]`` weight with ``k`` minor, as
    ``[g, n, k]`` row-major: the TPU's compiler lays an entry parameter
    whose last dimension is no multiple of a lane row and whose middle
    one is with the middle one minor (so do the optimizer's states of its
    shape), and a Mosaic operand, which is row-major, then costs a copy
    of the whole array each way, every step, unless the kernels take the
    swap of its last two axes (``grouped_matmul(rhs_transposed=True)``),
    which is a bitcast of what is held."""
    _, k, n = shape
    return n % LANES != 0 and k % LANES == 0


def _ragged(lhs, rhs, group_sizes, rhs_t):
    return jax.lax.ragged_dot(
        lhs, jnp.swapaxes(rhs, 1, 2) if rhs_t else rhs, group_sizes)


# ``plan`` holds the tiles of the three kernels over the weight AS IT IS
# HELD, ``[g, a, b]``: (rows of ``a`` columns times it, rows of ``b``
# columns times its transposed blocks, the ragged contraction into its
# shape). ``rhs_t`` says which of the first two is the forward.

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _gmm(lhs, rhs, group_sizes, meta, plan, rhs_t, interpret):
    return _gmm_fwd(lhs, rhs, group_sizes, meta, plan, rhs_t, interpret)[0]


def _gmm_fwd(lhs, rhs, group_sizes, meta, plan, rhs_t, interpret):
    tiles = plan[1 if rhs_t else 0]
    _gmm_count("fwd", lhs.dtype, tiles, rhs_t)

    def kernels(lhs, rhs, group_sizes, meta, interpret):
        return gmm_call(*meta[:4], lhs, rhs, tiles=tiles, transposed=rhs_t,
                        interpret=interpret)

    def ragged(lhs, rhs, group_sizes, meta):
        return _ragged(lhs, rhs, group_sizes, rhs_t)

    out = on_tpu(kernels, ragged, interpret, lhs, rhs, group_sizes, meta)
    return out, (lhs, rhs, group_sizes, meta)


def _gmm_bwd(plan, rhs_t, interpret, res, dout):
    lhs, rhs, group_sizes, meta = res
    dgrad_tiles, wgrad_tiles = plan[0 if rhs_t else 1], plan[2]
    _gmm_count("dgrad", lhs.dtype, dgrad_tiles, rhs_t)
    _gmm_count("wgrad", lhs.dtype, wgrad_tiles, rhs_t)

    def kernels(lhs, rhs, dout, group_sizes, meta, interpret):
        offsets, gids, tids, visits, w_gids, w_tids, w_visits = meta
        # the ragged contraction writes the weight's held shape: rows of
        # its middle dimension's columns first
        rows = (dout, lhs) if rhs_t else (lhs, dout)
        return (
            gmm_call(offsets, gids, tids, visits, dout, rhs,
                     tiles=dgrad_tiles, transposed=not rhs_t,
                     interpret=interpret),
            gmm_wgrad_call(offsets, w_gids, w_tids, w_visits, *rows,
                           groups=rhs.shape[0], tiles=wgrad_tiles,
                           interpret=interpret))

    def ragged(lhs, rhs, dout, group_sizes, meta):
        return jax.vjp(lambda l, r: _ragged(l, r, group_sizes, rhs_t),
                       lhs, rhs)[1](dout)

    dlhs, drhs = on_tpu(kernels, ragged, interpret, lhs, rhs,
                        dout.astype(lhs.dtype), group_sizes, meta)
    return dlhs, drhs, None, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def gmm_runs_kernel(m, dtype):
    """Whether ``grouped_matmul`` runs its kernels at these shapes or
    hands the call to ``jax.lax.ragged_dot``: fewer rows than one row
    tile, or an operand type Mosaic does not take."""
    return (m >= GMM_MIN_ROW_TILE
            and jnp.dtype(dtype).name in ("bfloat16", "float32"))


def grouped_matmul(lhs, rhs, group_sizes, metadata=None, interpret=False,
                   rhs_transposed=False):
    """``lhs[m, k]`` sorted by group, ``rhs[g, k, n]``, ``group_sizes[g]``
    (int32, summing to m) -> ``[m, n]``: row r of group i times
    ``rhs[i]``, what ``jax.lax.ragged_dot`` computes, differentiable in
    ``lhs`` and ``rhs``. With ``rhs_transposed`` the weight comes held
    transposed, ``rhs[g, n, k]``, and row r is multiplied by
    ``rhs[i]^T``: the same three kernels over the array as it is held
    (the forward indexes the transposed weight block, as dgrad does of a
    declared one; dgrad the straight one; wgrad writes ``[g, n, k]``),
    at the tiles a declared ``[g, n, k]`` weight has. For a weight that
    ``held_transposed`` is true of.

    Three Pallas kernels (forward, dgrad over the transposed weight
    block, wgrad with the ragged contraction) with tiles from
    ``gmm_tiles``; float32 accumulation, one rounding to the operands'
    type. Mosaic where the computation is lowered for the TPU and XLA's
    own grouped matmul and its transposes on every other platform, the
    choice made inside the ``custom_vjp`` (``common.on_tpu``);
    ``interpret=True`` (the kernels' tests) runs the kernels through the
    Pallas interpreter wherever the computation is lowered. Shapes the
    kernels do not take (``gmm_runs_kernel``) go to ``ragged_dot``
    everywhere.
    ``metadata`` is ``gmm_metadata(group_sizes, m, tm)`` where several
    products walk the same rows (an expert layer's six); made here
    otherwise. The weight gradient of a group with no rows is exactly
    zero. Like ``flash_attention``, the kernels have no partitioning
    rule: inside a sharded ``jit``, call under ``shard_map``."""
    m = lhs.shape[0]
    groups, a, b = rhs.shape
    rhs_t = bool(rhs_transposed)
    dtype = jnp.result_type(lhs.dtype, rhs.dtype)
    if not gmm_runs_kernel(m, dtype):
        return _ragged(lhs, rhs, group_sizes, rhs_t)
    tiles = gmm_tiles(m, a, b, groups, dtype)
    tn_d, tk_d = gmm_tiles(m, b, a, groups, dtype)[1:]
    if metadata is None:
        metadata = gmm_metadata(group_sizes, m, tiles[0])
    plan = (tiles, (tiles[0], tk_d, tn_d),
            gmm_tiles(m, a, b, groups, dtype, wgrad=True))
    return _gmm(lhs.astype(dtype), rhs.astype(dtype), group_sizes,
                tuple(metadata), plan, rhs_t, bool(interpret))


# Tokens a group of ``sorted_segment_sum``: the contraction's ``k``, one
# MXU pass wide twice over; 12,288 rows of 2,048 into 8,192 tokens are
# 12.9 GFLOP at it and 8 MiB of VMEM at ``gmm_tiles``' (128, 256, 2048).
SEGMENT_TILE = 256


def _bf16_pieces(rows):
    """float32 ``rows`` as three float32 tables of bf16-exact values that
    sum to it, side by side: whatever precision the MXU multiplies float32
    operands at (its default is one bf16 pass), each piece times the 0 / 1
    table is exact. ``reduce_precision`` and not a cast there and back,
    which XLA may drop."""
    pieces, rest = [], rows
    for _ in range(3):
        piece = lax.reduce_precision(rest, exponent_bits=8, mantissa_bits=7)
        pieces.append(piece)
        rest = lax.sub(rest, piece)
    return lax.concatenate(pieces, 1)


@functools.partial(jax.jit,
                   static_argnames=("num_segments", "tiles", "interpret"))
def segment_product_call(rows, segment, *, num_segments, tiles, interpret):
    """``sorted_segment_sum``'s kernel branch: the 0 / 1 table, the
    groups' sizes and ``gmm_wgrad_call`` over them. One ``jax.jit`` a
    signature, as the two wrappers above, so that a model's layers trace
    the table and the metadata once."""
    m, n = rows.shape
    groups = -(-num_segments // SEGMENT_TILE)
    # a row of no segment is a row of no group: the kernel's masks keep
    # it out, so a NaN there never meets a 0 of the table
    group = lax.select(lax.lt(segment, np.int32(num_segments)),
                       lax.div(segment, np.int32(SEGMENT_TILE)),
                       lax.full_like(segment, groups))
    sizes = lax.reduce(
        lax.convert_element_type(lax.eq(
            lax.broadcast_in_dim(group, (m, groups), (0,)),
            lax.broadcasted_iota(jnp.int32, (m, groups), 1)), jnp.int32),
        np.int32(0), lax.add, (0,))
    table = lax.convert_element_type(lax.eq(
        lax.broadcast_in_dim(lax.rem(segment, np.int32(SEGMENT_TILE)),
                             (m, SEGMENT_TILE), (0,)),
        lax.broadcasted_iota(jnp.int32, (m, SEGMENT_TILE), 1)), rows.dtype)
    wide = rows.dtype == jnp.float32
    operand, _ = pad_to(_bf16_pieces(rows) if wide else rows, 1, LANES)
    offsets, _, _, _, gids, tids, visits = gmm_metadata(sizes, m, tiles[0])
    out = gmm_wgrad_call(offsets, gids, tids, visits, table, operand,
                         groups=groups, tiles=tiles, interpret=interpret)
    out = lax.slice_in_dim(
        lax.reshape(out, (groups * SEGMENT_TILE, operand.shape[1])),
        0, num_segments)
    part = functools.partial(lax.slice_in_dim, out, axis=1)
    if wide:
        return lax.add(lax.add(part(0, n), part(n, 2 * n)),
                       part(2 * n, 3 * n))
    return part(0, n)


@functools.partial(jax.jit, static_argnames=("num_segments",))
def segment_sum_call(rows, segment, *, num_segments):
    """``sorted_segment_sum``'s branch off the TPU."""
    return jax.ops.segment_sum(
        rows.astype(jnp.float32), segment, num_segments=num_segments,
        indices_are_sorted=True).astype(rows.dtype)


def sorted_segment_sum(rows, segment, num_segments, interpret=False):
    """``rows[m, n]`` whose ``segment`` ids [m] (int32) never fall, summed
    by id -> ``[num_segments, n]`` in ``rows.dtype``: float32 accumulation,
    one rounding. Rows whose id is ``num_segments`` or more (they stand
    last) belong to no segment and contribute nothing, whatever they
    hold.

    Where the computation is lowered for the TPU the sum is a product and
    no scatter: ``SEGMENT_TILE`` consecutive segments are one group, the
    rows of a group are a slab of ``rows`` (they are sorted), and the
    group's sums are ``table[slab]^T x rows[slab]`` with ``table[r, c]``
    1 where row r's id is the group's c-th: the ragged contraction over
    rows that ``gmm_wgrad_call`` computes, called here at its shapes
    (lhs the exact 0 / 1 table, the group sizes a count of ids, tiles
    from ``gmm_tiles``; counted in ``moe.gmm_lowerings`` as a ``wgrad``).
    bf16 rows are its operand as they are; float32 rows go as three
    bf16-exact pieces side by side, summed after (``_bf16_pieces``), so
    the result is float32's whatever the MXU's pass. On every other
    platform, and where ``gmm_runs_kernel`` refuses the shapes,
    ``jax.ops.segment_sum(indices_are_sorted=True)``. Not
    differentiable: its callers are ``custom_vjp`` rules (a share's
    moves in ``parallel/moe.py``, ``Embedding`` in ``ops/indexing.py``)."""
    m, n = rows.shape
    segment = lax.convert_element_type(segment, jnp.int32)
    plain = functools.partial(segment_sum_call, num_segments=num_segments)
    if not gmm_runs_kernel(m, rows.dtype):
        return plain(rows, segment)
    columns = n * (3 if rows.dtype == jnp.float32 else 1)
    tiles = gmm_tiles(m, SEGMENT_TILE, whole_lanes(columns),
                      -(-num_segments // SEGMENT_TILE), rows.dtype,
                      wgrad=True)
    _gmm_count("wgrad", rows.dtype, tiles)
    return on_tpu(
        functools.partial(segment_product_call, num_segments=num_segments,
                          tiles=tiles),
        plain, interpret, rows, segment)
