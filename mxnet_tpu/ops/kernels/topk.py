"""The choice of a row's ``k`` largest scores (``KeyIndexer``'s keep-mask;
``ops/transformer/latent.py::keep_top_k``), made on a block of rows that
stays in VMEM from the first counting pass to the mask: ONE read of the
float32 scores and one write of the int8 mask, where the ``jax.numpy`` form
reads the scores' bits from HBM once a pass.

  the choice  ``jax.lax.top_k``'s without its sort. A float32's bits are
              turned into an int32 of the same order (``_ordered_bits``:
              -0.0 under +0.0). The k-th largest value of a row is found
              bit by bit from the top: 32 candidates, each kept where at
              least ``k`` entries of the row reach it. Entries over the
              k-th value are kept; of the entries AT it the lowest
              indices, as many as the row has room for.
  the kernel  grid (batch, row block). A step reads [rows, S] scores,
              writes their ordered bits to a VMEM scratch laid out
              [S / 128, rows, 128] (a lane column a leading index; only
              the block's read and the mask's write slice lanes by a loop
              index, whole lane rows), and runs the 32 passes there:
              a compare, a select and an add per vreg into [rows, 128]
              partial counts, one cross-lane sum a pass. Where no row of
              the block holds more entries at its k-th value than it has
              room for, the mask is ONE compare against a threshold a
              row; else (``pl.when``) the entries at the k-th value are
              counted along the row, a lane column at a time: a product
              with a [128, 128] triangle of ones (0 / 1 whatever the
              MXU's passes, sums to 128 in float32: exact) plus the
              count carried from the columns before.
  ``live``    the caller says an -inf is never kept (``KeyIndexer`` drops
              them): they get the order's lowest value, which no candidate
              reaches, so a row with fewer than ``k`` live entries keeps
              those and is no tie row.
  ``causal``  the caller says row t holds -inf past column t (and
              ``live``): a block of rows [lo, hi) then stops every pass at
              column hi, a bound from the grid index; the mask past
              it is zeros either way.
  set-up      ``jax.lax`` primitives in the body, the ``pallas_call``
              behind a ``jax.jit``; ``plain_form`` is the branch of every
              platform but the TPU and the oracle of the kernel's tests.
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

from .common import LANES, VMEM_SCOPED_DEFAULT, affine, no_x64, on_tpu

# Query rows a grid step, the first that divides the rows and fits: the
# int8 mask's tile is 32 rows; at 128 a pass's partial counts and the
# candidates are 32 vregs and a step of 8,192 columns holds 14 MiB (alone
# at Keye-VL-2's shape, causal: 1.91 / 1.41 / 1.22 / 1.62 ms at 32 / 64 /
# 128 / 256 rows, PERF.md section 7, PR 76).
_ROW_BLOCKS = (128, 64, 32)
# Lane columns an iteration of a pass's loop (fewer where the row's columns
# are no multiple): a causal block's trips are ceil(hi / (_GROUP * 128)).
_GROUP = 4
# Lane columns an iteration of the loops over every column (the ordered
# bits, the mask).
_UNROLL = 8
_INT_MIN = np.int32(-2 ** 31)


def top_k_vmem_bytes(rows, width):
    """What a grid step holds in VMEM: the scores and the mask double
    buffered, the ordered bits once, 2 MiB of Mosaic's own. ``top_k_rows``
    picks a block that leaves the scoped default alone; a caller that
    names a larger one (``benchmarks/keep_top_k.py``'s sweep) gets its
    count."""
    return rows * width * (2 * 4 + 2 * 1 + 4) + 2 * 1024 * 1024


def top_k_rows(shape, k, dtype=jnp.float32):
    """The row block for float32 scores [..., T, S] and ``k``, or None
    where the kernel has none: S whole lane rows and over ``k``, a block
    that divides T and fits the scoped default."""
    if len(shape) < 2 or jnp.dtype(dtype) != jnp.float32:
        return None
    t, s = shape[-2:]
    if s % LANES or s <= k or k < 1:
        return None
    for rows in _ROW_BLOCKS:
        if t % rows == 0 and top_k_vmem_bytes(rows, s) <= VMEM_SCOPED_DEFAULT:
            return rows
    return None


def _ordered_bits(x):
    """float32 -> int32 of the same order (negative values reversed under
    the others, -0.0 under +0.0)."""
    bits = lax.bitcast_convert_type(x, jnp.int32)
    return lax.select(lax.lt(bits, np.int32(0)),
                      lax.bitwise_xor(bits, np.int32(2 ** 31 - 1)), bits)


def _row_sum(v):
    """[rows, 128] -> each row's sum in every lane of it."""
    return lax.broadcast_in_dim(lax.reduce_sum(v, (1,)), v.shape, (0,))


def _top_k_kernel(s_ref, keep_ref, kept_ref, u_ref, *, k, rows, width, live,
                  causal):
    columns = width // LANES
    group = math.gcd(columns, _GROUP)
    if causal:
        # rows [lo, hi) hold nothing live past column hi
        hi = affine(pl.program_id(1), rows, rows)
        trips = lax.div(lax.add(hi, np.int32(group * LANES - 1)),
                        np.int32(group * LANES))
    else:
        trips = columns // group

    def full(value, dtype=jnp.int32):
        return lax.full((rows, LANES), value, dtype)

    zeros, ones = full(0), full(1)

    def lanes(c):
        """Lane column ``c`` of a [rows, S] block."""
        return (slice(None),
                pl.ds(pl.multiple_of(affine(c, LANES), LANES), LANES))

    def walk(trips, per, body, carry=None):
        """``body(column, carry)`` over ``trips`` runs of ``per`` lane
        columns from the first."""
        def run(g, carry):
            for j in range(per):
                carry = body(affine(g, per, j), carry)
            return carry
        return lax.fori_loop(0, trips, run, carry)

    def every_column(body, carry=None):
        """Over all the columns, live or not."""
        per = math.gcd(columns, _UNROLL)
        return walk(columns // per, per, body, carry)

    # the ordered bits, once
    def ordered(c, carry):
        x = s_ref[lanes(c)]
        u = _ordered_bits(x)
        if live:
            u = lax.select(lax.eq(x, np.float32(-np.inf)), full(_INT_MIN), u)
        u_ref[c] = u

    every_column(ordered)

    def count(hit):
        """Entries a row of the live columns where ``hit(bits)``, in
        every lane of the row."""
        return _row_sum(walk(trips, group, lambda c, acc: lax.add(
            acc, lax.select(hit(u_ref[c]), ones, zeros)), zeros))

    # the k-th largest value's bits, from the top: ``cur`` holds them as
    # the unsigned order reads them, the compare is signed (the top bit
    # flipped)
    def bit(i, cur):
        cand = lax.bitwise_or(cur, lax.shift_right_logical(
            full(_INT_MIN), lax.broadcast_in_dim(i, (rows, LANES), ())))
        at = lax.bitwise_xor(cand, _INT_MIN)
        reached = count(lambda u: lax.ge(u, at))
        return lax.select(lax.ge(reached, np.int32(k)), cand, cur)

    kth = lax.bitwise_xor(lax.fori_loop(0, 32, bit, zeros), _INT_MIN)
    above = count(lambda u: lax.gt(u, kth))
    tied = count(lambda u: lax.eq(u, kth))
    if live:
        # the order's lowest value is an -inf's: a row that ends there
        # has fewer than k live entries, and none of them at it
        floor = lax.eq(kth, _INT_MIN)
        tied = lax.select(floor, zeros, tied)
    room = lax.sub(full(k), above)
    kept_ref[...] = lax.slice(lax.add(above, lax.min(tied, room)), (0, 0),
                              (rows, 1))
    over = lax.gt(lax.reduce_max(lax.sub(tied, room), (0, 1)), np.int32(0))

    def write(c, mask):
        keep_ref[lanes(c)] = lax.select(mask, ones, zeros).astype(jnp.int8)

    # both forms of the mask walk every column: a dead one holds the
    # order's lowest value, which neither keeps

    @pl.when(lax.bitwise_not(over))
    def _():
        # every entry at the k-th value has room: one threshold a row
        at = lax.max(kth, full(_INT_MIN + 1)) if live else kth

        def column(c, carry):
            write(c, lax.ge(u_ref[c], at))

        every_column(column)

    @pl.when(over)
    def _():
        # the entries at the k-th value, lowest index first, while a row
        # has room: a running count along the row
        triangle = lax.select(
            lax.le(lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0),
                   lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)),
            lax.full((LANES, LANES), 1, jnp.float32),
            lax.full((LANES, LANES), 0, jnp.float32))
        room_f = room.astype(jnp.float32)

        def column(c, before):
            u = u_ref[c]
            at = lax.eq(u, kth)
            if live:
                at = lax.bitwise_and(at, lax.bitwise_not(floor))
            so_far = lax.add(before, lax.dot_general(
                lax.select(at, full(1, jnp.float32), full(0, jnp.float32)),
                triangle, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
            write(c, lax.bitwise_or(
                lax.gt(u, kth), lax.bitwise_and(at, lax.le(so_far, room_f))))
            return lax.broadcast_in_dim(
                lax.slice(so_far, (0, LANES - 1), (rows, LANES)),
                (rows, LANES), (0, 1))

        every_column(column, full(0, jnp.float32))


@functools.partial(jax.jit, static_argnames=("k", "rows", "live", "causal",
                                              "interpret"))
def top_k_call(scores, *, k, rows, live, causal, interpret):
    """scores [B, T, S] float32 -> (keep [B, T, S] int8, kept [B, T, 1]
    int32), a block of ``rows`` rows a grid step."""
    b, t, s = scores.shape
    block = pl.BlockSpec((None, rows, s), lambda b, i: (b, i, 0))
    with no_x64():
        return pl.pallas_call(
            functools.partial(_top_k_kernel, k=k, rows=rows, width=s,
                              live=live, causal=causal),
            grid=(b, t // rows),
            in_specs=[block],
            out_specs=[block, pl.BlockSpec((None, rows, 1),
                                           lambda b, i: (b, i, 0))],
            out_shape=[jax.ShapeDtypeStruct((b, t, s), jnp.int8),
                       jax.ShapeDtypeStruct((b, t, 1), jnp.int32)],
            scratch_shapes=[pltpu.VMEM((s // LANES, rows, LANES),
                                       jnp.int32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel"),
                vmem_limit_bytes=max(VMEM_SCOPED_DEFAULT,
                                     top_k_vmem_bytes(rows, s))),
            name="topk_mask_f32_r%d_s%d_k%d%s" % (
                rows, s, k, "_causal" if causal else "_live" if live else ""),
            interpret=interpret,
        )(scores)


def _sortable_bits(x):
    """float32 -> uint32 of the same order (negative values reversed
    under the others, -0.0 under +0.0)."""
    bits = lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >> np.uint32(31) == 0, bits | np.uint32(1 << 31),
                     ~bits)


@functools.partial(jax.jit, static_argnames=("k", "live"))
def plain_form(scores, *, k, live=False):
    """``top_k_call`` in ``jax.numpy`` on scores [..., T, S]: the branch
    of every platform but the TPU, the form of the shapes ``top_k_rows``
    refuses and the oracle of the kernel's tests; one ``jax.jit`` a
    signature, because ``on_tpu`` traces both branches a call site. The
    k-th largest value of a row is found bit by bit (32 counting passes
    over the scores' order-preserving bits); only where a row holds its
    k-th value more often than it has room for is the running count taken
    that breaks the tie."""
    def count(mask):
        return jnp.sum(mask, axis=-1, keepdims=True, dtype=jnp.int32)

    if scores.shape[-1] <= k:
        keep = (scores > -jnp.inf if live
                else jnp.ones(scores.shape, bool))
        return keep.astype(jnp.int8), count(keep)
    u = _sortable_bits(scores)
    if live:
        u = jnp.where(scores == -jnp.inf, np.uint32(0), u)

    def step(i, cur):
        cand = cur | (np.uint32(1 << 31) >> i.astype(jnp.uint32))
        return jnp.where(count(u >= cand) >= k, cand, cur)

    kth = lax.fori_loop(0, 32, step,
                        jnp.zeros(u.shape[:-1] + (1,), jnp.uint32))
    above = u > kth
    # entries of the k-th value a row may still take, lowest index first
    room = k - count(above)
    tied = u == kth
    if live:
        tied &= kth != 0
    held = count(tied)

    def by_index():
        return above | (tied & (jnp.cumsum(tied, axis=-1, dtype=jnp.int32)
                                <= room))

    keep = lax.cond(jnp.any(held > room), by_index, lambda: above | tied)
    return keep.astype(jnp.int8), k - room + jnp.minimum(held, room)


def top_k_mask(scores, k, live=False, causal=False, interpret=False):
    """scores [..., T, S] float32 -> (keep int8 of the same shape, kept
    [..., T, 1] int32): in each row its ``k`` largest entries (all of them
    where S <= k), ties to the lower index (``jax.lax.top_k``'s choice),
    and how many that is. Under ``live`` an -inf is never kept: a row
    keeps ``min(its entries over -inf, k)``. ``causal`` is the caller's
    word that row t holds -inf past column t, for square scores under
    ``live``: the kernel then stops a row block's passes at its last row's
    column; the result is the same. The kernel of this module where
    ``top_k_rows`` has a block for the shape and the computation is
    lowered for the TPU, ``plain_form`` on every other platform and for
    every other shape; ``interpret=True`` (the kernel's tests) runs the
    kernel through the Pallas interpreter. No gradient, no partitioning
    rule."""
    k, live = int(k), bool(live)
    if causal and not (live and scores.shape[-1] == scores.shape[-2]):
        raise ValueError(
            "top_k_mask: causal is for square scores under live, got %s "
            "live=%s" % (scores.shape, live))
    rows = top_k_rows(scores.shape, k, scores.dtype)
    if rows is None:
        return plain_form(scores, k=k, live=live)

    def kernels(x, interpret):
        keep, kept = top_k_call(
            x.reshape((-1,) + x.shape[-2:]), k=k, rows=rows, live=live,
            causal=bool(causal), interpret=interpret)
        return keep.reshape(x.shape), kept.reshape(x.shape[:-1] + (1,))

    return on_tpu(kernels, functools.partial(plain_form, k=k, live=live),
                  interpret, scores)
