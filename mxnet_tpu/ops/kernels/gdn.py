"""The gated delta rule's chunk core (ops/transformer/delta.py::
gated_delta_net; Gated DeltaNet, Yang, Kautz & Hatamizadeh,
arXiv:2412.06464) and, second, the rule with a decay a channel (Kimi
Delta Attention).

``S_t = a_t S_{t-1} + k_t u_t^T``, ``u_t = beta_t (v_t - a_t S_{t-1}^T
k_t)``, ``o_t = S_t^T q_t`` a head. One grid step is one chunk of C
tokens of a few heads: nothing of it but its inputs, its output, the
state it entered with and the inverse of its triangular system reaches
HBM.

  grid      (batch, head group, chunk), the chunks one after another; a
            head's state [K, V] is carried in float32 VMEM scratch. The
            operands are head-major ([B, H, T, K]: XLA's unit-norm pass
            writes them so), so a head's block is [C, K] whole whatever
            K is of a lane row, and its padding to one happens in VMEM.
  heads     ``gdn_group`` heads a step, TWO a trip of a ``fori_loop``
            (``_pair``): one traced body however many there are. What is
            as wide as a chunk is laid out for the two side by side, a
            [C, C] table of each one [C, 2 C] table (a whole lane row at
            C 64: a select, an exponential, a row or column sum over it
            is one pass for the pair); what has a row a token is the two
            heads' stacked, [2 C, .]. A product whose result is a chunk
            wide takes the stacked rows on both sides and keeps the two
            diagonal blocks, one that contracts over a chunk takes the
            pair's table as a block diagonal [2 C, 2 C]; the products
            with the state have nothing to pair and stay a head's. A
            group is even where the head count has an even divisor; an
            odd group's last trip pairs its head with zeros whose outputs
            are not written (``_held``, ``_each``). A head's values do
            not depend on the head beside it.
  system    with ``b`` the running log decay, ``D_ij = exp(b_i - b_j)``
            and ``L = beta_i D_ij (k_i . k_j)`` strictly below the
            diagonal, ``(I + L)^-1`` is built ONCE a chunk and pair by
            forward substitution (``_gdn_inverse``: column j's
            multipliers eliminate row j from the rows below it, 16-row
            tiles the column has passed left alone, the two heads' tiles
            one [16, 2 C] tile, the multipliers chosen by lane), never
            by a series in powers of ``L``, whose terms cancel once keys
            repeat. It is applied as a float32 product: ``u = (I + L)^-1
            beta (v - c k S)``, which is ``W - Y S`` of the chunk form
            with one system solved, not two. The forward keeps the
            inverse (a pair's side by side, [.., H / 2, C, 2 C]: 16 KB a
            chunk and head at C 64) and the backward applies its
            transpose: no substitution runs backwards.
  tables    ``b`` and ``beta`` token-major (a head a column of one lane
            row) and ``b`` pair-major (a pair's two rows along the lanes
            of one, for ``D``) are XLA's (``_gdn_tables``); every
            exponential is taken here.
  backward  the same walk from the last chunk to the first carrying the
            state's cotangent; a chunk's tables, ``u`` and the products
            with the entering state rebuilt. ``dL = -(T^T du) u^T``
            (``T`` the inverse), so the system's cotangent is two
            products. The log decay's cotangent is the row sums less
            the column sums of ``dM * M + dL * L`` (token-major and
            pair-major outputs, joined by XLA) plus what ``c``, the
            decay to the chunk's end and the carried state add.
  set-up    as the scan's: ``jax.lax`` primitives only, each
            ``pallas_call`` behind a ``jax.jit``
            (``linear_attn.kernel_traces``, which says how many heads a
            trip the body it traced takes), the ``jax.numpy`` chunk
            form on every platform but the TPU.

Decays, their sums and exponentials, ``L``, the substitution, the state
and every accumulator float32; the MXU's other operands in v's type. The
rule with a decay a CHANNEL (``kda_``) is the second pair, behind the
scalar one; both share the pair's layout and the substitution.
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
    first_chunk, no_x64, on_tpu, operand_label, sum_keepdims, whole_lanes)

_M_GDN_TRACES = _tm.counter(
    "linear_attn.kernel_traces", "Traces of a gated delta rule kernel's "
    "pallas_call (one a signature and process, however many GatedDeltaNet "
    "nodes call it; nothing per step); labels: mode (fwd / bwd), "
    "heads_a_trip (the heads one trip of the body's loop takes: 2)")

GDN_HEADS_A_STEP = 16
HEADS_A_TRIP = 2


def _group(heads, most):
    """Heads a grid step: the largest EVEN divisor of ``heads`` up to
    ``most`` (whole pairs a step), where there is none the largest divisor
    (its last trip pairs a head with zeros)."""
    fit = [g for g in range(1, most + 1) if heads % g == 0]
    return max([g for g in fit if g % 2 == 0] or fit)


def gdn_group(heads):
    """Heads a grid step of the scalar pair, their two scalars a token
    columns of one lane row of tables with room to spare. The body is one
    PAIR's whatever the group (a ``fori_loop``); a larger group is fewer,
    longer steps and less padding in the tables."""
    return _group(heads, GDN_HEADS_A_STEP)


def _trips(per):
    """Loop trips a grid step of ``per`` heads."""
    return -(-per // HEADS_A_TRIP)


def gdn_vmem_bytes(chunk, per, key_dim, value_dim, itemsize):
    """What a backward step holds, counted generously: the double-buffered
    blocks (q, k, dq, dk; v, dv; do; the entering state; the inverse; the
    four tables), the carried cotangents in scratch, and three dozen
    float32 temporaries of the PAIR in hand, each as wide as the two heads'
    widest table together."""
    k, v, c = (whole_lanes(w) for w in (key_dim, value_dim, chunk))
    state = key_dim * v * 4
    head = (4 * chunk * k * itemsize + 2 * chunk * v * itemsize
            + chunk * v * 4 + state + chunk * c * 4)
    tables = (2 * chunk * LANES * 4
              + 2 * -(-_trips(per) // 8) * 8 * whole_lanes(2 * chunk) * 4)
    return (2 * (per * head + tables) + per * state + chunk * c * 4
            + 36 * 2 * max(chunk * max(k, v) * 4, state))


def gdn_takes(heads, key_dim, value_dim, chunk, dtype, decay="scalar"):
    """Whether the delta rule has tiles: chunks of whole bf16 sublane tiles
    up to a lane row, keys and values in multiples of 32, Mosaic's operand
    types, a step within VMEM; ``decay="channel"``: ``kda_takes`` below."""
    if decay != "scalar":
        return decay == "channel" and kda_takes(heads, key_dim, value_dim,
                                                chunk, dtype)
    if min(heads, key_dim, value_dim, chunk) <= 0:
        return False
    return (chunk % 16 == 0 and chunk <= LANES
            and key_dim % 32 == 0 and value_dim % 32 == 0
            and jnp.dtype(dtype).name in ("bfloat16", "float32")
            and gdn_vmem_bytes(chunk, gdn_group(heads), key_dim,
                               value_dim, jnp.dtype(dtype).itemsize)
            <= VMEM_RAISED_LIMIT)


def _gdn_tables(g, beta, chunk, per):
    """g and beta [B, T, H] float32 (T whole chunks), ``per`` heads a
    group -> ``cols`` [B, H / per, T, 128] (b | beta, a head a column,
    padded to a lane row) and ``rows`` [B, H / per, T / C, 8n, 2 C] (b, a
    PAIR of heads a row, the second behind the first, zeros behind an odd
    group's last head; padded to whole sublane tiles), ``b`` the running
    sum of ``g`` inside each chunk."""
    b, t, h = g.shape
    nc, groups, trips = t // chunk, h // per, _trips(per)
    cum = jnp.einsum("ij,bcjh->bcih",
                     np.tril(np.ones((chunk, chunk), np.float32)),
                     g.reshape(b, nc, chunk, h),
                     precision=lax.Precision.HIGHEST)
    cols = jnp.stack([cum, beta.reshape(b, nc, chunk, h)], axis=3)
    cols = cols.reshape(b, nc, chunk, 2, groups, per).transpose(
        0, 4, 1, 2, 3, 5).reshape(b, groups, t, 2 * per)
    rows = jnp.pad(cum.reshape(b, nc, chunk, groups, per),
                   ((0, 0),) * 4 + ((0, per % 2),))
    rows = rows.reshape(b, nc, chunk, groups, trips, 2).transpose(
        0, 3, 1, 4, 5, 2).reshape(b, groups, nc, trips, 2 * chunk)
    return (jnp.pad(cols, ((0, 0),) * 3 + ((0, LANES - 2 * per),)),
            jnp.pad(rows, ((0, 0),) * 3 + ((0, -trips % 8), (0, 0))))


def _by_head_rows(rows, per, chunk):
    """``_gdn_tables``'s ``rows`` layout back to [B, T / C, C, H]."""
    b, groups, nc = rows.shape[:3]
    trips = _trips(per)
    rows = rows[:, :, :, :trips].reshape(b, groups, nc, 2 * trips, chunk)
    return rows[:, :, :, :per].transpose(0, 2, 4, 1, 3).reshape(
        b, nc, chunk, groups * per)


def _rows(x, lo, hi):
    return lax.slice(x, (lo, 0), (hi, x.shape[1]))


def _along(row, rows):
    """[1, W] -> [rows, W]."""
    return lax.broadcast_in_dim(row, (rows, row.shape[1]), (0, 1))


def _across(col, width):
    """[R, 1] -> [R, width]."""
    return lax.broadcast_in_dim(col, (col.shape[0], width), (0, 1))


# -- a pair of heads a trip --------------------------------------------------
# Everything as wide as a chunk is laid out for TWO heads side by side: a
# [C, C] table of each is one [C, 2 C] table (a whole lane row at C 64), the
# first head's in lanes under C; what has a row a token ([C, K], [C, V], a
# column [C, 1]) is the two heads' stacked, [2 C, .]. A product whose RESULT
# is a chunk wide takes the stacked rows on both sides and keeps the two
# diagonal blocks (``_side_by_side``); one that CONTRACTS over a chunk takes
# the pair's table as a block diagonal [2 C, 2 C] (``_blocks``) against the
# stacked rows: one pass of 128 for two of 64.

def _pair(trip, heads):
    """The two heads of loop trip ``trip`` of a step of ``heads``: ``(h0,
    h1, live)``. An odd step's last trip has no second head: ``h1`` is then
    ``h0`` again and ``live`` (a float32 scalar, None in an even step) is 0,
    what ``_held`` multiplies its operands by, and nothing of it is written
    (``_each``)."""
    h0 = lax.mul(trip, np.int32(HEADS_A_TRIP))
    h1 = lax.add(h0, np.int32(1))
    if heads % HEADS_A_TRIP == 0:
        return h0, h1, None
    there = lax.lt(h1, np.int32(heads))
    return (h0, lax.min(h1, np.int32(heads - 1)),
            lax.select(there, np.float32(1), np.float32(0)))


def _held(x, live):
    """``x`` of a trip's second head: zeros where there is none."""
    if live is None:
        return x
    return lax.convert_element_type(
        lax.mul(lax.convert_element_type(x, jnp.float32), live), x.dtype)


def _each(pair, write):
    """``write(i, h)`` for the pair's heads, the second only if it is
    there."""
    h0, h1, live = pair
    write(0, h0)
    if live is None:
        write(1, h1)
    else:
        pl.when(lax.gt(live, np.float32(0)))(lambda: write(1, h1))


def _stack(x0, x1):
    return lax.concatenate([x0, x1], 0)


def _half(x, i):
    """Head i's rows of a stacked [2 C, W]."""
    c = x.shape[0] // 2
    return _rows(x, i * c, i * c + c)


def _side_by_side(full, left):
    """The two diagonal blocks of [2 C, 2 C] as one table [C, 2 C]."""
    return lax.select(left, _half(full, 0), _half(full, 1))


def _blocks(table, left):
    """[R, 2 C] -> the block diagonal [2 R, 2 C]."""
    zero = lax.full(table.shape, 0, table.dtype)
    return lax.concatenate([lax.select(left, table, zero),
                            lax.select(left, zero, table)], 0)


def _wide(col, left):
    """Stacked columns [2 C, 1] -> [C, 2 C], a head's along its lanes."""
    width = left.shape[1]
    return lax.select(left, _across(_half(col, 0), width),
                      _across(_half(col, 1), width))


def _half_sums(table, left):
    """Each head's row sums of [C, 2 C], stacked [2 C, 1]."""
    return sum_keepdims(_blocks(table, left), 1)


def _pair_lanes(rows, c):
    """``left`` (the first head's lanes of a pair's table) and a lane's
    column inside its own head's table, [rows, 2 C]."""
    lane = lax.broadcasted_iota(jnp.int32, (rows, 2 * c), 1)
    left = lax.lt(lane, np.int32(c))
    return left, lax.select(left, lane, lax.sub(lane, np.int32(c)))


def _gdn_masks(c):
    """Made once a body, a PAIR of heads' tables wide ([C, 2 C], a lane's
    column that inside its own head's table): ``causal`` (j <= i),
    ``strict`` (j < i), ``left`` (the first head's lanes; ``left16`` the
    same 16 rows high: a mask is made at its size, never sliced), the table
    of ``NEG_INF`` the decay's select falls to and the identity in 16-row
    tiles."""
    left, col = _pair_lanes(c, c)
    left16, lane = _pair_lanes(16, c)
    row = lax.broadcasted_iota(jnp.int32, (c, 2 * c), 0)
    at = lax.broadcasted_iota(jnp.int32, (16, 2 * c), 0)
    one, zero = (lax.full((16, 2 * c), v, jnp.float32) for v in (1, 0))
    eye = tuple(
        lax.select(lax.eq(lax.add(at, np.int32(16 * p)), lane), one, zero)
        for p in range(c // 16))
    return dict(causal=lax.ge(row, col), strict=lax.gt(row, col), left=left,
                left16=left16,
                masked=lax.full((c, 2 * c), NEG_INF, jnp.float32), eye=eye)


def _gdn_inverse(low_ref, masks):
    """``(I + L)^-1`` of a pair of heads, the strictly lower triangular
    ``L`` of each [C, C] float32 side by side in ``low_ref`` [C, 2 C], by
    forward substitution: ``I + L`` is the product over j of ``I + l_j
    e_j^T`` (``l_j`` column j of ``L``), so its inverse is ``I - l_j
    e_j^T`` applied to the identity for j = 0, 1, ...: row j, final once
    the columns before it are through, times column j's multipliers leaves
    the rows below it. Rows in 16-row tiles, both heads' in one; the
    multipliers are column j of the first head's table along its lanes and
    of the second's along the others; a tile wholly above row j + 1 is not
    touched. Each head's half sees the operations its own table alone
    would give it, in their order."""
    c = low_ref.shape[0]
    tiles = list(masks["eye"])
    for j in range(c - 1):
        row = lax.slice(tiles[j // 16], (j % 16, 0), (j % 16 + 1, 2 * c))
        for p in range((j + 1) // 16, c // 16):
            by = lax.select(
                masks["left16"],
                _across(low_ref[16 * p:16 * p + 16, j:j + 1], 2 * c),
                _across(low_ref[16 * p:16 * p + 16, c + j:c + j + 1], 2 * c))
            tiles[p] = lax.sub(tiles[p], lax.mul(by, row))
    return lax.concatenate(tiles, 0)


def _gdn_column(cols, lane, at):
    """Column ``at`` (a traced index) of the token-major tables [C, 128]
    as [C, 1]: a select and a sum along lanes, which is exact."""
    return sum_keepdims(lax.select(lax.eq(lane, at), cols,
                               lax.full(cols.shape, 0, cols.dtype)), 1)


def _gdn_chunk(q_ref, k_ref, v_ref, cols_ref, rows_ref, states, trip, pair,
               heads, masks):
    """What both kernels make of a pair's chunk (``trip`` a traced index:
    the pairs of a step are a ``fori_loop``, one traced body) before the
    system: the operands stacked, ``b`` and ``beta`` by token ([2 C, 1];
    ``beta`` also along its head's lanes, ``betaw``), the decay table ``D``
    (0 above the diagonal), ``c``, the decay to the chunk's end and over
    the whole chunk ([1, V] a head), ``D * (k k^T)``, ``D * (q k^T)``
    ([C, 2 C], one product of the stacked rows of k over q with those of
    k) and the two products with each head's entering state [K, V] (one
    product a head, of its rows of k over q)."""
    c = q_ref.shape[1]
    op, f32 = v_ref.dtype, jnp.float32
    cast = lax.convert_element_type
    h0, h1, live = pair
    left = masks["left"]
    q, k, v = (_stack(x[h0], _held(x[h1], live))
               for x in (q_ref, k_ref, v_ref))
    cols = cols_ref[...]
    lane = lax.broadcasted_iota(jnp.int32, cols.shape, 1)
    b_col, beta = (
        _stack(_gdn_column(cols, lane, lax.add(h0, np.int32(at))),
               _held(_gdn_column(cols, lane, lax.add(h1, np.int32(at))),
                     live))
        for at in (0, heads))
    b_row = rows_ref[pl.ds(trip, 1), :]
    decay = lax.exp(lax.select(
        masks["causal"], lax.sub(_wide(b_col, left), b_row),
        masks["masked"]))
    totals = [_rows(b_col, i * c + c - 1, i * c + c) for i in range(2)]
    # [1, 1] over a table in two steps, lanes first and the exponential
    # between them: Mosaic has no broadcast along both at once
    whole = [lax.exp(lax.broadcast_in_dim(x, (1, states[0].shape[1]), (0, 1)))
             for x in totals]
    to_end = lax.exp(_stack(*(
        lax.sub(x, _half(b_col, i)) for i, x in enumerate(totals))))
    products = dot_highest(_stack(k, q), k, (1, 1))          # [4 C, 2 C]
    by_state = [
        dot_highest(_stack(_half(k, i), _half(q, i)), cast(s, op), (1, 0))
        for i, s in enumerate(states)]                       # [2 C, V] each
    return dict(
        q=q, k=k, v=cast(v, f32), beta=beta, betaw=_wide(beta, left),
        decay=decay, c=lax.exp(b_col), to_end=to_end, whole=whole, lane=lane,
        kk=lax.mul(decay, _side_by_side(_rows(products, 0, 2 * c), left)),
        qk=lax.mul(decay, _side_by_side(_rows(products, 2 * c, 4 * c),
                                        left)),
        ks=_stack(*(_half(x, 0) for x in by_state)),
        qs=_stack(*(_half(x, 1) for x in by_state)))


def _gdn_fwd_kernel(q_ref, k_ref, v_ref, cols_ref, rows_ref, o_ref, ent_ref,
                    inv_ref, state, low_s, *, heads):
    """One chunk of ``heads`` heads, two a trip. q, k [G, C, K]; v [G, C,
    V]; cols [C, 128]; rows [8n, 2 C] -> o [G, C, V] float32, the state
    each head's chunk entered with [G, K, V] and a pair's ``(I + L)^-1``
    side by side [G / 2, C, 2 C], float32."""
    op, f32 = v_ref.dtype, jnp.float32
    cast, mul, add, sub = (lax.convert_element_type, lax.mul, lax.add,
                           lax.sub)
    masks = _gdn_masks(q_ref.shape[1])
    left = masks["left"]
    zero = lax.full(left.shape, 0, f32)

    @pl.when(first_chunk())
    def _():
        state[...] = lax.full(state.shape, 0, f32)

    def trip(p, carry):
        pair = _pair(p, heads)
        entered = [state[pair[0]], _held(state[pair[1]], pair[2])]
        t = _gdn_chunk(q_ref, k_ref, v_ref, cols_ref, rows_ref, entered, p,
                       pair, heads, masks)
        low_s[...] = lax.select(masks["strict"], mul(t["betaw"], t["kk"]),
                                zero)
        inv = _gdn_inverse(low_s, masks)
        inv_ref[p] = inv
        u = cast(dot_highest(
            _blocks(inv, left),
            mul(t["beta"], sub(t["v"], mul(t["c"], t["ks"]))), (1, 0)), op)
        o = add(dot_highest(cast(_blocks(t["qk"], left), op), u, (1, 0)),
                mul(t["c"], t["qs"]))
        k_out = cast(mul(t["to_end"], cast(t["k"], f32)), op)

        def write(i, h):
            ent_ref[h] = entered[i]
            o_ref[h] = _half(o, i)
            state[h] = add(mul(t["whole"][i], entered[i]),
                           dot_highest(_half(k_out, i), _half(u, i), (0, 0)))

        _each(pair, write)
        return carry

    lax.fori_loop(0, _trips(heads), trip, np.int32(0))


def _gdn_bwd_kernel(q_ref, k_ref, v_ref, cols_ref, rows_ref, ent_ref,
                    inv_ref, do_ref, dq_ref, dk_ref, dv_ref, small_ref,
                    across_ref, dstate, *, heads):
    """The same chunk with do [G, C, V] float32 and the cotangent of the
    state each head leaves (carried, [G, K, V]) -> dq, dk, dv in the
    operands' type, ``small`` [C, 128] (a head's cotangent of ``b`` by
    row sums | of ``beta``, a head a column) and ``across`` [8n, 2 C]
    (what the column sums of ``dM * M + dL * L`` take from ``b``'s, a pair
    a row)."""
    c = q_ref.shape[1]
    op, f32 = v_ref.dtype, jnp.float32
    cast, mul, add, sub = (lax.convert_element_type, lax.mul, lax.add,
                           lax.sub)
    masks = _gdn_masks(c)
    strict, left = masks["strict"], masks["left"]
    zero = lax.full(strict.shape, 0, f32)
    row = lax.broadcasted_iota(jnp.int32, (2 * c, 1), 0)
    last = [lax.eq(row, np.int32(i * c + c - 1)) for i in range(2)]
    none = lax.full((2 * c, 1), 0, f32)

    def total(v):
        return sum_keepdims(sum_keepdims(v, 1), 0)

    def column(lane, at, v):
        """[C, 1] in column ``at`` of a [C, 128] table of zeros."""
        wide = lax.broadcast_in_dim(v, lane.shape, (0, 1))
        return lax.select(lax.eq(lane, at), wide,
                          lax.full(lane.shape, 0, f32))

    @pl.when(first_chunk())
    def _():
        dstate[...] = lax.full(dstate.shape, 0, f32)

    def trip(p, small):
        pair = _pair(p, heads)
        h0, h1, live = pair
        entered = [ent_ref[h0], _held(ent_ref[h1], live)]
        dleft = [dstate[h0], _held(dstate[h1], live)]
        entered_op, dleft_op = ([cast(x, op) for x in xs]
                                for xs in (entered, dleft))
        t = _gdn_chunk(q_ref, k_ref, v_ref, cols_ref, rows_ref, entered, p,
                       pair, heads, masks)
        q, k, beta, betaw, decay = (t["q"], t["k"], t["beta"], t["betaw"],
                                    t["decay"])
        k32 = cast(k, f32)
        inv = _blocks(inv_ref[p], left)
        low = lax.select(strict, mul(betaw, t["kk"]), zero)
        kept = sub(t["v"], mul(t["c"], t["ks"]))        # v - c k S
        u = dot_highest(inv, mul(beta, kept), (1, 0))
        u_op = cast(u, op)
        do = _stack(do_ref[h0], _held(do_ref[h1], live))
        do_op = cast(do, op)
        k_out = cast(mul(t["to_end"], k32), op)
        du = add(dot_highest(cast(_blocks(t["qk"], left), op), do_op, (0, 0)),
                 _stack(*(dot_highest(_half(k_out, i), dleft_op[i], (1, 0))
                          for i in range(2))))
        dr = dot_highest(inv, du, (0, 0))                  # T^T du
        dm = _side_by_side(dot_highest(do_op, u_op, (1, 1)), left)  # do u^T
        dqk = mul(decay, dm)
        dlow = lax.select(strict, lax.neg(_side_by_side(
            dot_highest(dr, u, (1, 1)), left)), zero)
        dkk = mul(dlow, mul(betaw, decay))
        dqk_op, dkk_op = (cast(_blocks(x, left), op) for x in (dqk, dkk))
        c_do = cast(mul(t["c"], do), op)
        dks = cast(lax.neg(mul(mul(beta, t["c"]), dr)), op)
        # u dS'^T, [C, K] a head; and a head's rows of c do over those of
        # dks against its entering state, one product
        left_k = _stack(*(dot_highest(_half(u_op, i), dleft_op[i], (1, 1))
                          for i in range(2)))
        both = [_stack(_half(c_do, i), _half(dks, i)) for i in range(2)]
        on_state = [dot_highest(both[i], entered_op[i], (1, 1))
                    for i in range(2)]                     # [2 C, K] each
        dq = cast(add(_stack(*(_half(x, 0) for x in on_state)),
                      dot_highest(dqk_op, k, (1, 0))), dq_ref.dtype)
        dk = cast(
            add(add(_stack(*(_half(x, 1) for x in on_state)),
                    dot_highest(dqk_op, q, (0, 0))),
                add(add(dot_highest(dkk_op, k, (1, 0)),
                        dot_highest(dkk_op, k, (0, 0))),
                    mul(t["to_end"], left_k))), dk_ref.dtype)
        dv = cast(mul(beta, dr), dv_ref.dtype)
        # b_i multiplies row i of D and divides column i; c_i = exp(b_i);
        # the decay to the end divides by it; the last one carries the
        # whole chunk's decay of the entering state and of every key
        pairs = add(mul(dm, t["qk"]), mul(dlow, low))
        to_end = mul(t["to_end"], sum_keepdims(mul(k32, left_k), 1))
        decayed = [mul(t["whole"][i], dleft[i]) for i in range(2)]
        leaves = none
        for i in range(2):
            leaves = lax.select(
                last[i], lax.broadcast_in_dim(
                    add(total(_half(to_end, i)),
                        total(mul(decayed[i], entered[i]))),
                    (2 * c, 1), (0, 1)), leaves)
        db = add(
            sub(add(_half_sums(pairs, left),
                    mul(t["c"], sum_keepdims(
                        sub(mul(do, t["qs"]),
                            mul(mul(beta, dr), t["ks"])), 1))), to_end),
            leaves)
        dbeta = add(sum_keepdims(mul(dr, kept), 1),
                    _half_sums(mul(dlow, t["kk"]), left))
        across_ref[pl.ds(p, 1), :] = sum_keepdims(pairs, 0)

        def write(i, h):
            dq_ref[h], dk_ref[h], dv_ref[h] = (_half(x, i)
                                               for x in (dq, dk, dv))
            # a head's rows of q over those of k against c do over dks
            dstate[h] = add(decayed[i], dot_highest(
                _stack(_half(q, i), _half(k, i)), both[i], (0, 0)))

        _each(pair, write)
        # an absent second head's columns are zeros added to the first's
        for i, h in enumerate((h0, h1)):
            small = add(small, add(
                column(t["lane"], h, _half(db, i)),
                column(t["lane"], lax.add(h, np.int32(heads)),
                       _half(dbeta, i))))
        return small

    small_ref[...] = lax.fori_loop(
        0, _trips(heads), trip, lax.full(small_ref.shape, 0, f32))


def _gdn_name(which, dtype, chunk, key_dim, value_dim):
    return "gdn_%s_%s_c%d_k%d_v%d" % (which, operand_label(dtype), chunk,
                                      key_dim, value_dim)


def _gdn_specs(chunk, per, key_dim, value_dim, nc, reverse):
    """Block specs of (a group's heads of q and k, of v, the token-major
    tables, the pair-major ones, the entering states, the pairs' inverses)
    at grid step (batch, group, chunk), the chunks walked downwards under
    ``reverse``."""
    trips = _trips(per)

    def at(c):
        return lax.sub(np.int32(nc - 1), c) if reverse else c

    def by_head(width):
        return pl.BlockSpec((None, per, chunk, width),
                            lambda b, g, c: (b, g, at(c), 0))

    def by_chunk(heads, rows, width):
        return pl.BlockSpec((None, None, heads, rows, width),
                            lambda b, g, c: (b, at(c), g, 0, 0))

    return (by_head(key_dim), by_head(value_dim),
            pl.BlockSpec((None, None, chunk, LANES),
                         lambda b, g, c: (b, g, at(c), 0)),
            pl.BlockSpec((None, None, None, -(-trips // 8) * 8, 2 * chunk),
                         lambda b, g, c: (b, g, at(c), 0, 0)),
            by_chunk(per, key_dim, value_dim),
            by_chunk(trips, chunk, 2 * chunk))


def _pairs(heads, per):
    """The pairs of heads (an odd group's last head one of them) a chunk's
    residual tables hold."""
    return heads // per * _trips(per)


def _gdn_params(chunk, per, key_dim, value_dim, dtype):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=max(
            VMEM_SCOPED_DEFAULT,
            gdn_vmem_bytes(chunk, per, key_dim, value_dim,
                           jnp.dtype(dtype).itemsize)))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def gdn_fwd_call(q, k, v, cols, rows, *, chunk, interpret):
    """q, k [B, H, T, K], v [B, H, T, V], the tables -> o [B, H, T, V],
    the entering states [B, T / C, H, K, V] and the systems' inverses, a
    pair of heads side by side, [B, T / C, H / 2, C, 2 C], float32."""
    _M_GDN_TRACES.inc(mode="fwd", heads_a_trip=HEADS_A_TRIP)
    b, h, t, key_dim = q.shape
    value_dim = v.shape[3]
    per, nc = h // cols.shape[1], t // chunk
    narrow, wide, cols_spec, rows_spec, state_spec, inv_spec = _gdn_specs(
        chunk, per, key_dim, value_dim, nc, False)
    with no_x64():
        return pl.pallas_call(
            functools.partial(_gdn_fwd_kernel, heads=per),
            grid=(b, h // per, nc),
            in_specs=[narrow, narrow, wide, cols_spec, rows_spec],
            out_specs=[wide, state_spec, inv_spec],
            out_shape=[
                jax.ShapeDtypeStruct(v.shape, jnp.float32),
                jax.ShapeDtypeStruct((b, nc, h, key_dim, value_dim),
                                     jnp.float32),
                jax.ShapeDtypeStruct((b, nc, _pairs(h, per), chunk,
                                      2 * chunk), jnp.float32)],
            scratch_shapes=[
                pltpu.VMEM((per, key_dim, value_dim), jnp.float32),
                pltpu.VMEM((chunk, 2 * chunk), jnp.float32)],
            compiler_params=_gdn_params(chunk, per, key_dim, value_dim,
                                        v.dtype),
            name=_gdn_name("fwd", v.dtype, chunk, key_dim, value_dim),
            interpret=interpret,
        )(q, k, v, cols, rows)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def gdn_bwd_call(q, k, v, cols, rows, entering, inverse, do, *, chunk,
                 interpret):
    """-> dq, dk [B, H, T, K] and dv [B, H, T, V] in the operands' type,
    ``small`` [B, H / G, T, 128] and ``across`` [B, H / G, T / C, 8n, 2 C],
    float32 (``_gdn_bwd_kernel``)."""
    _M_GDN_TRACES.inc(mode="bwd", heads_a_trip=HEADS_A_TRIP)
    b, h, t, key_dim = q.shape
    value_dim = v.shape[3]
    per, nc = h // cols.shape[1], t // chunk
    narrow, wide, cols_spec, rows_spec, state_spec, inv_spec = _gdn_specs(
        chunk, per, key_dim, value_dim, nc, True)
    with no_x64():
        return pl.pallas_call(
            functools.partial(_gdn_bwd_kernel, heads=per),
            grid=(b, h // per, nc),
            in_specs=[narrow, narrow, wide, cols_spec, rows_spec, state_spec,
                      inv_spec, wide],
            out_specs=[narrow, narrow, wide, cols_spec, rows_spec],
            out_shape=[
                jax.ShapeDtypeStruct(q.shape, q.dtype),
                jax.ShapeDtypeStruct(k.shape, k.dtype),
                jax.ShapeDtypeStruct(v.shape, v.dtype),
                jax.ShapeDtypeStruct(cols.shape, jnp.float32),
                jax.ShapeDtypeStruct(rows.shape, jnp.float32)],
            scratch_shapes=[
                pltpu.VMEM((per, key_dim, value_dim), jnp.float32)],
            compiler_params=_gdn_params(chunk, per, key_dim, value_dim,
                                        v.dtype),
            name=_gdn_name("bwd", v.dtype, chunk, key_dim, value_dim),
            interpret=interpret,
        )(q, k, v, cols, rows, entering, inverse, do)


def _gdn_chunked(q, k, v, g, beta, chunk):
    """The rule in the ``jax.numpy`` chunk form on the kernels' head-major
    operands, o as they give it ([B, H, T, V] float32): the branch for
    every platform but the TPU."""
    from ..transformer import gated_delta_rule as chunk_form

    q, k, v = (jnp.moveaxis(x, 1, 2) for x in (q, k, v))
    return jnp.moveaxis(chunk_form(q, k, v, g, beta, chunk), 2, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _gdn(q, k, v, g, beta, chunk, interpret):
    return _gdn_fwd(q, k, v, g, beta, chunk, interpret)[0]


def _gdn_fwd(q, k, v, g, beta, chunk, interpret):
    # one trace of the forward for the primal and the rule: see _ssd_fwd
    with jax.sharding.use_abstract_mesh(jax.sharding.get_abstract_mesh()):
        res = gdn_forward(q, k, v, g, beta, chunk=chunk,
                          interpret=interpret)
    return res[-3], res[:5] + res[-2:]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def gdn_forward(q, k, v, g, beta, *, chunk, interpret):
    """The inputs, o [B, H, T, V] float32 and the backward's other
    residuals: the state each chunk entered with and its system's inverse
    (zeros off the TPU, where the chunk form's own transpose is the
    backward)."""
    b, h, t, key_dim = q.shape
    res = (q, k, v, g, beta)

    def kernels(q, k, v, g, beta, interpret):
        return gdn_fwd_call(
            q, k, v, *_gdn_tables(g, beta, chunk, gdn_group(h)),
            chunk=chunk, interpret=interpret)

    def chunked(q, k, v, g, beta):
        return (_gdn_chunked(q, k, v, g, beta, chunk),
                jnp.zeros((b, t // chunk, h, key_dim, v.shape[3]),
                          jnp.float32),
                jnp.zeros((b, t // chunk, _pairs(h, gdn_group(h)), chunk,
                           2 * chunk), jnp.float32))

    return res + tuple(on_tpu(kernels, chunked, interpret, *res))


def _gdn_bwd(chunk, interpret, res, do):
    g = res[3]
    b, t, h = g.shape
    per, nc = gdn_group(h), t // chunk

    def kernels(q, k, v, g, beta, entering, inverse, do, interpret):
        dq, dk, dv, small, across = gdn_bwd_call(
            q, k, v, *_gdn_tables(g, beta, chunk, per), entering, inverse,
            do, chunk=chunk, interpret=interpret)
        small = small.reshape(b, h // per, nc, chunk, LANES)
        db, dbeta = (
            small[..., at:at + per].transpose(0, 2, 3, 1, 4)
            .reshape(b, nc, chunk, h) for at in (0, per))
        db = db - _by_head_rows(across, per, chunk)
        # a token's log decay reaches every running sum from its own
        # onwards
        dg = jnp.einsum("ji,bcjh->bcih",
                        np.tril(np.ones((chunk, chunk), np.float32)), db,
                        precision=lax.Precision.HIGHEST)
        return dq, dk, dv, dg.reshape(b, t, h), dbeta.reshape(b, t, h)

    def chunked(q, k, v, g, beta, entering, inverse, do):
        return jax.vjp(lambda *ins: _gdn_chunked(*ins, chunk),
                       q, k, v, g, beta)[1](do)

    return on_tpu(kernels, chunked, interpret, *res,
                  do.astype(jnp.float32))


_gdn.defvjp(_gdn_fwd, _gdn_bwd)


def gated_delta_rule(q, k, v, g, beta, chunk, interpret=False,
                     head_major=False):
    """``ops/transformer.py::gated_delta_rule`` (q and k [B, T, H, K], v
    [B, T, H, V] in one type, g and beta [B, T, H] -> o [B, T, H, V]
    float32) as a Pallas kernel pair, differentiable in all five, for the
    shapes ``gdn_takes`` admits. T is padded to whole chunks with ``k``
    0, ``beta`` 0 and ``g`` 0 (no write, no decay); the kernels read and
    write head-major ([B, H, T, .]), the moves XLA's to fuse into what
    makes q, k and v and reads o. Mosaic where the computation is lowered
    for the TPU and the ``jax.numpy`` chunk form itself on every other
    platform, the choice made inside the ``custom_vjp`` as ``ssd_scan``
    makes it; ``interpret=True`` (the kernels' tests) runs the kernels
    through the Pallas interpreter wherever the computation is lowered.
    No partitioning rule: inside a sharded ``jit``, call under
    ``shard_map``. ``head_major`` (T whole chunks): o as the kernel wrote
    it, [B, H, T, V], for a reader that takes it so (``gated_rms_norm``):
    no move behind the forward and none in front of the backward."""
    t = q.shape[1]
    pad = -t % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    f32 = jnp.float32
    o = _gdn(*(jnp.moveaxis(x, 1, 2) for x in (q, k, v)), g.astype(f32),
             beta.astype(f32), int(chunk), bool(interpret))
    if head_major:
        if pad:
            raise ValueError("gated_delta_rule: head_major with %d tokens "
                             "in chunks of %d" % (t, chunk))
        return o
    return jnp.moveaxis(o, 1, 2)[:, :t]


# --------------------------------------------------------------------------
# The rule with a decay a CHANNEL (Kimi Delta Attention, arXiv:2510.26692):
# ``ops/transformer/delta.py::channel_delta_rule`` as a second pair of
# bodies on the same plan: two heads a trip, their tables side by side, the
# scalar pair's substitution
#
#   operands  token-major, as the projections and the taps' pair leave them:
#             a head is whole lane rows of [B, T, H K] (K and V multiples of
#             128), so a step's block is [C, G K] and a head a lane slice of
#             it at a traced offset; nothing is moved round the pair.
#   heads     a trip's two heads are read side by side, [C, 2 K]
#             (``_kda_columns``): every elementwise pass of the prologue,
#             the decays and the diagonal tiles is the two heads' at once on
#             whole vregs (a head's lane sums taken over its own lanes),
#             ``_stacked`` / ``_unstacked`` turn that into the stacked rows
#             [2 C, K] of the products and back (a head whole lane rows: no
#             data moves), and what is a chunk wide (``A(k)``, ``A(q)``,
#             ``L``, the inverse, ``P``, ``Q``) is the pair's [C, 2 C].
#   prologue  what the op computes between the taps and the rule (the unit
#             norms of q and k a head, ``g = -exp(a_log) softplus(a +
#             dt_bias)``) is made HERE where the op calls
#             (``channel_delta_net``, kernels ``kda_*_pre``) and transposed
#             in the backward kernel: as XLA's passes over [B, T, H, K] (a
#             tile eight heads of a token, where [B, T, H K]'s is eight
#             tokens of a head) they cost 10 ms a layer in moves at the
#             cell's shape. ``channel_delta_rule`` is the pair on q, k, g
#             as given (``kda_*``), what the tests hold to the chunk form.
#   decay     ``b`` [C, K], the running sum of ``g`` inside the chunk, is a
#             float32 product with a triangle of ones here; it sits INSIDE
#             the contraction, ``A(x)_ij = sum_d x_id k_jd exp(b_id - b_jd)``.
#             Sub-blocks of 16 tokens: rows of a later sub-block against the
#             columns before it factor through the sub-block's first token
#             ``n`` (``x_i exp(b_i - b_n)`` times ``k_j exp(b_n - b_j)``, both
#             factors at most 1, one product on the MXU for q and k's rows
#             together); inside a sub-block column j is the lane sum of
#             ``x_i * k_j exp(b_i - b_j)`` over an 8-row tile, the difference
#             masked to j <= i BEFORE the exponential. No exponential of a
#             positive number.
#   state     carried transposed, [V, K]: the chunk's decay ``exp(b_C)`` is
#             a row along lanes there, and the three products with it are
#             the MXU's plain, NT and TN forms.
#   residuals the state each chunk entered with, a pair's ``(I + L)^-1``
#             [.., H / 2, C, 2 C] and its two tables ``A(k)`` over ``A(q)``
#             [.., H / 2, 2 C, 2 C] (the bytes a head at a time took, whole
#             lane rows): the backward rebuilds neither the substitution nor
#             a lane sum.
#   backward  the tables' cotangents ``P`` (of ``A(k)``, strictly lower) and
#             ``Q`` (of ``A(q)``) reach k's and q's rows as ``R_i = sum_j P_ij
#             k_j exp(b_i - b_j)`` and k's columns as ``C_j = sum_i (P_ij k_i +
#             Q_ij q_i) exp(b_i - b_j)``, the same sub-blocks: products through
#             ``n`` across them, an 8-row tile a column inside one. The log
#             decay's cotangent is a [C, K] table: ``q dq + k (dk_rows -
#             dk_columns)``, the last token taking what the chunk's whole
#             decay carries, summed backwards by the triangle's transpose.
# --------------------------------------------------------------------------
KDA_HEADS_A_STEP = 8
KDA_SUB_BLOCK = 16   # ops/transformer.py::KDA_SUB_BLOCK (fla's chunk_kda)


def kda_group(heads):
    """Heads a grid step of the channel pair: ``_group`` up to
    ``KDA_HEADS_A_STEP`` (a block's rows are then that many lane rows long;
    the body is one PAIR's, a ``fori_loop``)."""
    return _group(heads, KDA_HEADS_A_STEP)


def kda_vmem_bytes(chunk, per, heads, key_dim, value_dim, itemsize):
    """What a backward step of the channel pair holds, counted generously:
    the double-buffered blocks (q, k, dq, dk; v, dv; g, dg; do; the
    entering state; the inverse; the two tables; beta and its cotangent),
    the carried cotangents and the three tables in scratch, and eight dozen
    float32 temporaries as wide as ONE head's of the pair in hand."""
    c = whole_lanes(chunk)
    head = (4 * chunk * key_dim * itemsize + 2 * chunk * value_dim * itemsize
            + 2 * chunk * key_dim * 4 + chunk * value_dim * 4
            + key_dim * value_dim * 4 + 3 * chunk * c * 4)
    small = chunk * (whole_lanes(heads) + whole_lanes(per)) * 4
    return (2 * (per * head + small) + per * key_dim * value_dim * 4
            + 3 * chunk * whole_lanes(2 * chunk) * 4
            + 2 * chunk * key_dim * 4
            + 96 * max(chunk * max(key_dim, value_dim) * 4,
                       key_dim * value_dim * 4))


def kda_takes(heads, key_dim, value_dim, chunk, dtype):
    """Whether the channel pair has tiles: a head whole lane rows of keys
    and of values, chunks of whole sub-blocks up to a lane row, an operand
    type Mosaic takes, a step that fits VMEM."""
    return (min(heads, key_dim, value_dim, chunk) > 0
            and chunk % KDA_SUB_BLOCK == 0 and chunk <= LANES
            and key_dim % LANES == 0 and value_dim % LANES == 0
            and jnp.dtype(dtype).name in ("bfloat16", "float32")
            and kda_vmem_bytes(chunk, kda_group(heads), heads, key_dim,
                               value_dim, jnp.dtype(dtype).itemsize)
            <= VMEM_RAISED_LIMIT)


def _lanes(x, i, width):
    """Head i's lanes of a pair's [R, 2 W]."""
    return lax.slice(x, (0, i * width), (x.shape[0], i * width + width))


def _stacked(x):
    """A pair's [R, 2 W] (a head whole lane rows) -> [2 R, W]."""
    width = x.shape[1] // 2
    return lax.concatenate([_lanes(x, 0, width), _lanes(x, 1, width)], 0)


def _unstacked(x):
    """[2 R, W] -> [R, 2 W]."""
    return lax.concatenate([_half(x, 0), _half(x, 1)], 1)


def _head_sums(x):
    """Each head's sum along its own lanes of [R, 2 W], along them."""
    width = x.shape[1] // 2
    return lax.concatenate(
        [_across(sum_keepdims(_lanes(x, i, width), 1), width)
         for i in range(2)], 1)


def _kda_masks(c):
    """``_gdn_masks`` and the triangle of ones [C, C] float32 that sums a
    chunk's log decays forwards and, transposed, their cotangents back."""
    row, col = (lax.broadcasted_iota(jnp.int32, (c, c), i) for i in (0, 1))
    (left8, col8), (left32, _) = (_pair_lanes(rows, c)
                                  for rows in (8, 2 * KDA_SUB_BLOCK))
    return dict(_gdn_masks(c), left8=left8, col8=col8, left32=left32,
                ones=lax.select(
                    lax.ge(row, col), lax.full((c, c), 1, jnp.float32),
                    lax.full((c, c), 0, jnp.float32)))


def _kda_columns(ref, pair, width):
    """The pair's columns of a token-major block [C, G W], [C, 2 W]."""
    h0, h1, live = pair
    return lax.concatenate(
        [ref[:, pl.ds(pl.multiple_of(lax.mul(h0, np.int32(width)), LANES),
                      width)],
         _held(ref[:, pl.ds(pl.multiple_of(lax.mul(h1, np.int32(width)),
                                           LANES), width)], live)], 1)


def _kda_chunk(q_ref, k_ref, g_ref, beta_ref, pre, pair, first, key_dim,
               masks):
    """What both kernels make of a pair's chunk (the pair's heads traced
    indices) before the tables, the two heads side by side along lanes
    ([C, 2 K]: an elementwise pass is the two heads' at once): q and k in
    float32, ``beta`` stacked [2 C, 1] and along its head's lanes of a
    table (``betaw``),
    ``b`` and its exponentials from the chunk's start (``c``), to its end
    (``to_end``) and over the whole of it (``whole`` [1, 2 K]), q and k
    decayed from the start and k to the end in the operands' type, and a
    sub-block's two factors: ``from_n`` [C, 2 K] (a row's decay from its
    sub-block's first token) and, a later sub-block I, ``to_n[I]`` (the
    decay from a column before it to that token, [16 I, 2 K]) with k times
    it in the operands' type, the heads stacked [2 C, K], zero rows below
    each.

    ``pre`` (the op's call: ``(rate_ref, bias_ref)``, rows [1, G K]): q_ref
    and k_ref hold the convolution's output and g_ref the decay's
    pre-activation ``a``, and what ``_gated_delta_block`` computes before
    the rule is computed here, a chunk at a time: ``q = x / |x| / sqrt(K)``
    and ``k = x / |x|`` a head, rounded to the operands' type as XLA's pass
    rounded them, ``g = rate * softplus(a + bias)`` (``rate`` is ``-exp(
    a_log)`` along a head's lanes). Kept for the backward: the unit vectors
    and their scales (along a head's lanes), the pre-activation's sigmoid,
    ``g``."""
    c = q_ref.shape[0]
    op, f32 = q_ref.dtype, jnp.float32
    cast, mul, sub = lax.convert_element_type, lax.mul, lax.sub
    h0, h1, live = pair
    q, k = (cast(_kda_columns(x, pair, key_dim), f32) for x in (q_ref, k_ref))
    g = _kda_columns(g_ref, pair, key_dim)
    kept = {}
    if pre is not None:
        for name, x, scale in (("q", q, key_dim ** -0.5), ("k", k, 1.0)):
            inv = lax.rsqrt(lax.add(_head_sums(mul(x, x)), np.float32(1e-6)))
            kept["unit_" + name] = mul(x, inv)
            kept["scale_" + name] = mul(inv, np.float32(scale))
        q = cast(cast(mul(kept["unit_q"], np.float32(key_dim ** -0.5)), op),
                 f32)
        k = cast(cast(kept["unit_k"], op), f32)
        rate, bias = (_along(_kda_columns(x, (h0, h1, None), key_dim), c)
                      for x in pre)
        x = lax.add(cast(g, f32), bias)
        # jax.nn.softplus: max(x, 0) + log1p(exp(-|x|))
        g = mul(rate, lax.add(
            lax.max(x, np.float32(0)),
            lax.log1p(lax.exp(lax.neg(lax.abs(x))))))
        kept.update(g=g, slope=mul(rate, lax.logistic(x)))
    cols = beta_ref[...]
    lane = lax.broadcasted_iota(jnp.int32, cols.shape, 1)
    beta = _stack(_gdn_column(cols, lane, lax.add(first, h0)),
                  _held(_gdn_column(cols, lane, lax.add(first, h1)), live))
    b = dot_highest(masks["ones"], g, (1, 0))
    total = _rows(b, c - 1, c)
    decay, to_end = lax.exp(b), lax.exp(sub(_along(total, c), b))
    sb = KDA_SUB_BLOCK
    firsts = [_rows(b, n, n + 1) for n in range(0, c, sb)]
    from_n = lax.exp(sub(b, lax.concatenate(
        [_along(n, sb) for n in firsts], 0)))
    to_n, k_to = {}, {}
    for i in range(1, c // sb):
        to_n[i] = lax.exp(sub(_along(firsts[i], sb * i), _rows(b, 0, sb * i)))
        k_n = cast(mul(_rows(k, 0, sb * i), to_n[i]), op)
        rest = lax.full((c - sb * i, key_dim), 0, op)
        k_to[i] = lax.concatenate(
            [_lanes(k_n, 0, key_dim), rest, _lanes(k_n, 1, key_dim), rest],
            0)
    return dict(
        kept, q=q, k=k, beta=beta, betaw=_wide(beta, masks["left"]), b=b,
        c=decay, to_end=to_end, whole=lax.exp(total), from_n=from_n,
        to_n=to_n, k_to=k_to,
        qc=_stacked(cast(mul(decay, q), op)),
        kc=_stacked(cast(mul(decay, k), op)),
        k_out=_stacked(cast(mul(to_end, k), op)),
        k_n=cast(mul(from_n, k), op), q_n=cast(mul(from_n, q), op))


def _kda_pair(t, i):
    """Sub-block i's rows of k over those of q, each decayed from the
    sub-block's first token, the first head's over the second's: [64, K] in
    the operands' type."""
    sb = KDA_SUB_BLOCK
    width = t["b"].shape[1] // 2
    return lax.concatenate(
        [_lanes(_rows(t[x], sb * i, sb * i + sb), h, width)
         for h in range(2) for x in ("k_n", "q_n")], 0)


def _kda_tiles(t):
    """The 8-row tiles of a chunk's diagonal sub-blocks, one (tile, column)
    after another, both heads' side by side: yields ``(i, half, j, e,
    k_j)`` with ``e`` [8, 2 K] the decay ``exp(b_r - b_j)`` of rows ``16 i
    + 8 half ...`` from column j of the same sub-block (0 where r < j:
    masked before the exponential) and ``k_j`` that column's key along the
    tile's rows. A column in a sub-block's lower half meets the lower tile
    only."""
    b, k = t["b"], t["k"]
    c, width = b.shape
    sb = KDA_SUB_BLOCK
    row = lax.broadcasted_iota(jnp.int32, (8, width), 0)
    masked = lax.full((8, width), NEG_INF, jnp.float32)
    for i in range(c // sb):
        for jj in range(sb):
            j = sb * i + jj
            b_j, k_j = (_along(_rows(x, j, j + 1), 8) for x in (b, k))
            for half in range(jj // 8, 2):
                at = sb * i + 8 * half
                d = lax.sub(_rows(b, at, at + 8), b_j)
                if jj > 8 * half:
                    d = lax.select(lax.ge(row, np.int32(jj - 8 * half)), d,
                                   masked)
                yield i, half, j, lax.exp(d), k_j


def _kda_products(t, masks):
    """``A(k)`` and ``A(q)``, a pair's side by side [C, 2 C] float32, zero
    above the diagonal: across sub-blocks ONE product a later sub-block of
    the pair's stacked rows through its first token, inside one a lane sum
    a head, tile and column."""
    c = t["b"].shape[0]
    key_dim = t["b"].shape[1] // 2
    sb = KDA_SUB_BLOCK
    left, col = masks["left8"], masks["col8"]
    tiles = {}
    for i in range(c // sb):
        both = (_side_by_side(
            dot_highest(_kda_pair(t, i), t["k_to"][i], (1, 1)),
            masks["left32"]) if i
            else lax.full((2 * sb, 2 * c), 0, jnp.float32))
        for half in range(2):
            tiles[i, half] = [_rows(both, x + 8 * half, x + 8 * half + 8)
                              for x in (0, sb)]
    for i, half, j, e, k_j in _kda_tiles(t):
        at = sb * i + 8 * half
        z = lax.mul(e, k_j)
        here = lax.eq(col, np.int32(j))
        tiles[i, half] = [
            lax.select(here, lax.select(left, *(
                _across(sum_keepdims(_lanes(y, h, key_dim), 1), 2 * c)
                for h in range(2))), acc)
            for y, acc in zip(
                (lax.mul(_rows(t[x], at, at + 8), z) for x in "kq"),
                tiles[i, half])]
    return tuple(
        lax.concatenate([tiles[i, half][x] for i in range(c // sb)
                         for half in range(2)], 0) for x in range(2))


def _kda_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, *rest, heads,
                    key_dim, value_dim, fused):
    """One chunk of ``heads`` heads, two a trip. q, k [C, G K]; v [C, G
    V]; g [C, G K] float32; beta [C, H] (``fused``: q, k and ``a`` before
    the rule's prologue, then ``rate`` and ``bias`` [1, G K]:
    ``_kda_chunk``) -> o [C, G V] float32, the state each head's chunk
    entered with [G, V, K], a pair's ``(I + L)^-1`` side by side [G / 2, C,
    2 C] and its ``A(k)`` over ``A(q)`` [G / 2, 2 C, 2 C], float32."""
    pre, rest = (rest[:2], rest[2:]) if fused else (None, rest)
    o_ref, ent_ref, inv_ref, tab_ref, state, low_s = rest
    c = q_ref.shape[0]
    op, f32 = v_ref.dtype, jnp.float32
    cast, mul, add, sub = (lax.convert_element_type, lax.mul, lax.add,
                           lax.sub)
    masks = _kda_masks(c)
    left = masks["left"]
    zero = lax.full(left.shape, 0, f32)
    first = lax.mul(pl.program_id(1), np.int32(heads))

    @pl.when(first_chunk())
    def _():
        state[...] = lax.full(state.shape, 0, f32)

    def trip(p, carry):
        pair = _pair(p, heads)
        entered = [state[pair[0]], _held(state[pair[1]], pair[2])]
        entered_op = [cast(x, op) for x in entered]
        t = _kda_chunk(q_ref, k_ref, g_ref, beta_ref, pre, pair, first,
                       key_dim, masks)
        kk, qk = _kda_products(t, masks)
        tab_ref[p, :c] = kk
        tab_ref[p, c:] = qk
        low_s[...] = lax.select(masks["strict"], mul(t["betaw"], kk), zero)
        inv = _gdn_inverse(low_s, masks)
        inv_ref[p] = inv
        v = cast(_stacked(_kda_columns(v_ref, pair, value_dim)), f32)
        # a head's rows of k over those of q, decayed from the chunk's
        # start, against its entering state: one product
        by_state = [
            dot_highest(_stack(_half(t["kc"], i), _half(t["qc"], i)),
                        entered_op[i], (1, 1)) for i in range(2)]
        kept = sub(v, _stack(*(_half(x, 0) for x in by_state)))
        u = cast(dot_highest(_blocks(inv, left), mul(t["beta"], kept),
                             (1, 0)), op)
        o = add(dot_highest(cast(_blocks(qk, left), op), u, (1, 0)),
                _stack(*(_half(x, 1) for x in by_state)))

        def write(i, h):
            at = pl.multiple_of(lax.mul(h, np.int32(value_dim)), LANES)
            ent_ref[h] = entered[i]
            o_ref[:, pl.ds(at, value_dim)] = _half(o, i)
            state[h] = add(
                mul(_along(_lanes(t["whole"], i, key_dim), value_dim),
                    entered[i]),
                dot_highest(_half(u, i), _half(t["k_out"], i), (0, 0)))

        _each(pair, write)
        return carry

    lax.fori_loop(0, _trips(heads), trip, np.int32(0))


def _kda_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, *rest, heads,
                    key_dim, value_dim, fused):
    """The same chunk with do [C, G V] float32 and the cotangent of the
    state each head leaves (carried, [G, V, K]) -> dq, dk, dv in the
    operands' type, dg [C, G K] float32 and beta's cotangent [C, G] (a
    head a column). ``fused``: the cotangents of q, k and ``a`` BEFORE
    the prologue (through the unit norms and the softplus), and ``sums``
    [2, G K]: the chunk's sums over its tokens of ``dg * g`` (a head's
    lanes summed are its ``a_log``'s cotangent) and of ``a``'s cotangent
    in float32 (the bias's)."""
    pre, rest = (rest[:2], rest[2:]) if fused else (None, rest)
    (ent_ref, inv_ref, tab_ref, do_ref, dq_ref, dk_ref, dv_ref, dg_ref,
     dbeta_ref, *sums_ref, dstate, p_s, q_s, col_s) = rest
    c = q_ref.shape[0]
    sb = KDA_SUB_BLOCK
    op, f32 = v_ref.dtype, jnp.float32
    cast, mul, add, sub = (lax.convert_element_type, lax.mul, lax.add,
                           lax.sub)
    masks = _kda_masks(c)
    left = masks["left"]
    zero = lax.full(left.shape, 0, f32)
    first = lax.mul(pl.program_id(1), np.int32(heads))
    last = lax.eq(lax.broadcasted_iota(jnp.int32, (c, 2 * key_dim), 0),
                  np.int32(c - 1))
    lane = lax.broadcasted_iota(jnp.int32, (c, heads), 1)

    @pl.when(first_chunk())
    def _():
        dstate[...] = lax.full(dstate.shape, 0, f32)

    def trip(p, small):
        pair = _pair(p, heads)
        h0, h1, live = pair
        entered = [ent_ref[h0], _held(ent_ref[h1], live)]
        dleft = [dstate[h0], _held(dstate[h1], live)]
        entered_op, dleft_op = ([cast(x, op) for x in xs]
                                for xs in (entered, dleft))
        t = _kda_chunk(q_ref, k_ref, g_ref, beta_ref, pre, pair, first,
                       key_dim, masks)
        q, k, beta = t["q"], t["k"], t["beta"]
        kk, qk = tab_ref[p, :c], tab_ref[p, c:]
        inv = _blocks(inv_ref[p], left)
        v = cast(_stacked(_kda_columns(v_ref, pair, value_dim)), f32)
        kept = sub(v, _stack(*(
            dot_highest(_half(t["kc"], i), entered_op[i], (1, 1))
            for i in range(2))))
        u = dot_highest(inv, mul(beta, kept), (1, 0))
        u_op = cast(u, op)
        do_op = cast(_stacked(_kda_columns(do_ref, pair, value_dim)), op)
        du = add(dot_highest(cast(_blocks(qk, left), op), do_op, (0, 0)),
                 _stack(*(dot_highest(_half(t["k_out"], i), dleft_op[i],
                                      (1, 1)) for i in range(2))))
        dr = dot_highest(inv, du, (0, 0))                  # T^T du
        dqk = lax.select(masks["causal"], _side_by_side(
            dot_highest(do_op, u_op, (1, 1)), left), zero)
        dlow = lax.select(masks["strict"], lax.neg(_side_by_side(
            dot_highest(dr, u, (1, 1)), left)), zero)
        dkk = mul(t["betaw"], dlow)
        p_s[...] = dkk
        q_s[...] = dqk
        beta_dr = mul(beta, dr)
        dks = cast(lax.neg(beta_dr), op)
        # a head's rows of do over those of dks against its entering
        # state, one product: [2 C, K]
        both = [_stack(_half(do_op, i), _half(dks, i)) for i in range(2)]
        on_state = [dot_highest(both[i], entered_op[i], (1, 0))
                    for i in range(2)]
        dqc, dkc = (lax.concatenate([_half(x, n) for x in on_state], 1)
                    for n in range(2))                     # [C, 2 K]
        dk_out = lax.concatenate(
            [dot_highest(_half(u_op, i), dleft_op[i], (1, 0))
             for i in range(2)], 1)
        # the tables' cotangents: across sub-blocks through the first token
        # of the later one, inside one an 8-row tile a column
        rows, below = {}, lax.full((c, 2 * key_dim), 0, f32)
        for i in range(c // sb):
            if i:
                tables = cast(_blocks(lax.concatenate(
                    [_rows(x, sb * i, sb * i + sb) for x in (dkk, dqk)], 0),
                    masks["left32"]), op)                  # [64, 2 C]
                from_n = _rows(t["from_n"], sb * i, sb * i + sb)
                onto = _unstacked(
                    dot_highest(tables, t["k_to"][i], (1, 0)))  # [32, 2 K]
                under = _unstacked(
                    dot_highest(tables, _kda_pair(t, i), (0, 0)))
                below = add(below, lax.concatenate(
                    [mul(t["to_n"][i], _rows(under, 0, sb * i)),
                     lax.full((c - sb * i, 2 * key_dim), 0, f32)], 0))
            for half in range(2):
                rows[i, half] = [
                    mul(_rows(from_n, 8 * half, 8 * half + 8),
                        _rows(onto, x + 8 * half, x + 8 * half + 8))
                    if i else lax.full((8, 2 * key_dim), 0, f32)
                    for x in (0, sb)]
        column = None
        for i, half, j, e, k_j in _kda_tiles(t):
            lo = sb * i + 8 * half
            z = mul(e, k_j)
            p_j, q_j = (
                lax.concatenate(
                    [_across(ref[lo:lo + 8, at:at + 1], key_dim)
                     for at in (j, c + j)], 1) for ref in (p_s, q_s))
            rows[i, half] = [add(acc, mul(w, z))
                             for acc, w in zip(rows[i, half], (p_j, q_j))]
            y = mul(add(mul(p_j, _rows(k, lo, lo + 8)),
                        mul(q_j, _rows(q, lo, lo + 8))), e)
            column = y if column is None else add(column, y)
            if half == 1:
                col_s[j:j + 1, :] = sum_keepdims(column, 0)
                column = None
        dk_rows, dq_rows = (
            lax.concatenate([rows[i, half][x] for i in range(c // sb)
                             for half in range(2)], 0) for x in range(2))
        dk_columns = add(add(col_s[...], below), mul(t["to_end"], dk_out))
        dq = add(mul(t["c"], dqc), dq_rows)
        dk_rows = add(mul(t["c"], dkc), dk_rows)
        dk = add(dk_rows, dk_columns)
        # b_i multiplies row i of the tables and divides column i, and so
        # with the decays from the start and to the end; the last token's
        # carries the whole chunk's decay of the entering state and of
        # every key
        leaves = add(
            sum_keepdims(mul(mul(t["to_end"], k), dk_out), 0),
            mul(t["whole"], lax.concatenate(
                [sum_keepdims(mul(dleft[i], entered[i]), 0)
                 for i in range(2)], 1)))
        db = add(add(mul(q, dq), mul(k, sub(dk_rows, dk_columns))),
                 lax.select(last, _along(leaves, c),
                            lax.full((c, 2 * key_dim), 0, f32)))
        dg = dot_highest(masks["ones"], db, (0, 0))
        sums = None
        if fused:  # through the unit norms and the softplus
            dq, dk = (
                mul(t["scale_" + x], sub(d, mul(
                    t["unit_" + x], _head_sums(mul(t["unit_" + x], d)))))
                for x, d in (("q", dq), ("k", dk)))
            da = mul(dg, t["slope"])
            sums = lax.concatenate(
                [sum_keepdims(mul(dg, t["g"]), 0), sum_keepdims(da, 0)], 0)
            dg = da
        dbeta = add(sum_keepdims(mul(dr, kept), 1),
                    _half_sums(mul(dlow, kk), left))

        def write(i, h):
            at = pl.multiple_of(lax.mul(h, np.int32(value_dim)), LANES)
            at_k = pl.multiple_of(lax.mul(h, np.int32(key_dim)), LANES)
            dv_ref[:, pl.ds(at, value_dim)] = cast(_half(beta_dr, i),
                                                   dv_ref.dtype)
            for ref, x in ((dq_ref, dq), (dk_ref, dk), (dg_ref, dg)):
                ref[:, pl.ds(at_k, key_dim)] = cast(_lanes(x, i, key_dim),
                                                    ref.dtype)
            if fused:
                sums_ref[0][:, pl.ds(at_k, key_dim)] = _lanes(sums, i,
                                                              key_dim)
            # a head's rows of do over those of dks against q's over k's,
            # decayed from the chunk's start
            dstate[h] = add(
                mul(_along(_lanes(t["whole"], i, key_dim), value_dim),
                    dleft[i]),
                dot_highest(both[i], _stack(_half(t["qc"], i),
                                            _half(t["kc"], i)), (0, 0)))

        _each(pair, write)
        # an absent second head's column is zeros added to the first's
        for i, h in enumerate((h0, h1)):
            small = add(small, lax.select(
                lax.eq(lane, h), _across(_half(dbeta, i), heads),
                lax.full(lane.shape, 0, f32)))
        return small

    dbeta_ref[...] = lax.fori_loop(
        0, _trips(heads), trip, lax.full(dbeta_ref.shape, 0, f32))


def _kda_name(which, dtype, chunk, key_dim, value_dim, fused):
    return "kda_%s_%s_c%d_k%d_v%d%s" % (
        which, operand_label(dtype), chunk, key_dim, value_dim,
        "_pre" if fused else "")


def _kda_specs(chunk, per, heads, key_dim, value_dim, nc, reverse):
    """Block specs of (a group's columns of q, k and g; of v; beta; its
    cotangent; the entering states; the inverses; the tables; a row of a
    group's columns; two rows a chunk of them) at grid step (batch, group,
    chunk), the chunks walked downwards under ``reverse``."""
    def at(c):
        return lax.sub(np.int32(nc - 1), c) if reverse else c

    def columns(width):
        return pl.BlockSpec((None, chunk, per * width),
                            lambda b, g, c: (b, at(c), g))

    def by_chunk(heads, rows, width):
        return pl.BlockSpec((None, None, heads, rows, width),
                            lambda b, g, c: (b, at(c), g, 0, 0))

    return (columns(key_dim), columns(value_dim),
            pl.BlockSpec((None, chunk, heads), lambda b, g, c: (b, at(c), 0)),
            pl.BlockSpec((None, None, chunk, per),
                         lambda b, g, c: (b, g, at(c), 0)),
            by_chunk(per, value_dim, key_dim),
            by_chunk(_trips(per), chunk, 2 * chunk),
            by_chunk(_trips(per), 2 * chunk, 2 * chunk),
            pl.BlockSpec((1, per * key_dim), lambda b, g, c: (0, g)),
            pl.BlockSpec((None, None, 2, per * key_dim),
                         lambda b, g, c: (b, at(c), 0, g)))


def _kda_params(chunk, per, heads, key_dim, value_dim, dtype):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=max(
            VMEM_SCOPED_DEFAULT,
            kda_vmem_bytes(chunk, per, heads, key_dim, value_dim,
                           jnp.dtype(dtype).itemsize)))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def kda_fwd_call(q, k, v, g, beta, *pre, chunk, interpret):
    """q, k [B, T, H K], v [B, T, H V], g [B, T, H K] and beta [B, T, H]
    float32 (with ``pre``, rate and bias [1, H K] float32: q, k and ``a``
    before the rule's prologue, ``_kda_chunk``) -> o [B, T, H V], the
    entering states [B, T / C, H, V, K], the systems' inverses [B, T / C,
    H, C, C] and the tables [B, T / C, H, 2 C, C], float32."""
    _M_GDN_TRACES.inc(mode="fwd", heads_a_trip=HEADS_A_TRIP)
    b, t, h = beta.shape
    key_dim, value_dim = q.shape[2] // h, v.shape[2] // h
    per, nc = kda_group(h), t // chunk
    (narrow, wide, beta_spec, _, state_spec, inv_spec, tab_spec, row_spec,
     _) = _kda_specs(chunk, per, h, key_dim, value_dim, nc, False)
    with no_x64():
        return pl.pallas_call(
            functools.partial(_kda_fwd_kernel, heads=per, key_dim=key_dim,
                              value_dim=value_dim, fused=bool(pre)),
            grid=(b, h // per, nc),
            in_specs=[narrow, narrow, wide, narrow, beta_spec]
            + [row_spec] * len(pre),
            out_specs=[wide, state_spec, inv_spec, tab_spec],
            out_shape=[
                jax.ShapeDtypeStruct(v.shape, jnp.float32),
                jax.ShapeDtypeStruct((b, nc, h, value_dim, key_dim),
                                     jnp.float32),
                jax.ShapeDtypeStruct((b, nc, _pairs(h, per), chunk,
                                      2 * chunk), jnp.float32),
                jax.ShapeDtypeStruct((b, nc, _pairs(h, per), 2 * chunk,
                                      2 * chunk), jnp.float32)],
            scratch_shapes=[
                pltpu.VMEM((per, value_dim, key_dim), jnp.float32),
                pltpu.VMEM((chunk, 2 * chunk), jnp.float32)],
            compiler_params=_kda_params(chunk, per, h, key_dim, value_dim,
                                        v.dtype),
            name=_kda_name("fwd", v.dtype, chunk, key_dim, value_dim,
                           bool(pre)),
            interpret=interpret,
        )(q, k, v, g, beta, *pre)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def kda_bwd_call(q, k, v, g, beta, *rest, chunk, interpret):
    """``rest``: (rate, bias,) the entering states, the inverses, the
    tables, do -> dq, dk [B, T, H K] and dv [B, T, H V] in the operands'
    type, dg [B, T, H K] float32 (with rate and bias: ``a``'s cotangent in
    its type) and beta's cotangent [B, H / G, T, G], float32; with rate
    and bias also ``sums`` [B, T / C, 2, H K] (``_kda_bwd_kernel``)."""
    _M_GDN_TRACES.inc(mode="bwd", heads_a_trip=HEADS_A_TRIP)
    b, t, h = beta.shape
    key_dim, value_dim = q.shape[2] // h, v.shape[2] // h
    per, nc = kda_group(h), t // chunk
    fused = len(rest) == 6
    (narrow, wide, beta_spec, dbeta_spec, state_spec, inv_spec, tab_spec,
     row_spec, sums_spec) = _kda_specs(chunk, per, h, key_dim, value_dim,
                                       nc, True)
    with no_x64():
        return pl.pallas_call(
            functools.partial(_kda_bwd_kernel, heads=per, key_dim=key_dim,
                              value_dim=value_dim, fused=fused),
            grid=(b, h // per, nc),
            in_specs=[narrow, narrow, wide, narrow, beta_spec]
            + [row_spec] * (2 * fused)
            + [state_spec, inv_spec, tab_spec, wide],
            out_specs=[narrow, narrow, wide, narrow, dbeta_spec]
            + [sums_spec] * fused,
            out_shape=[
                jax.ShapeDtypeStruct(q.shape, q.dtype),
                jax.ShapeDtypeStruct(k.shape, k.dtype),
                jax.ShapeDtypeStruct(v.shape, v.dtype),
                jax.ShapeDtypeStruct(g.shape, g.dtype),
                jax.ShapeDtypeStruct((b, h // per, t, per), jnp.float32)]
            + [jax.ShapeDtypeStruct((b, nc, 2, h * key_dim), jnp.float32)]
            * fused,
            scratch_shapes=[
                pltpu.VMEM((per, value_dim, key_dim), jnp.float32),
                pltpu.VMEM((chunk, 2 * chunk), jnp.float32),
                pltpu.VMEM((chunk, 2 * chunk), jnp.float32),
                pltpu.VMEM((chunk, 2 * key_dim), jnp.float32)],
            compiler_params=_kda_params(chunk, per, h, key_dim, value_dim,
                                        v.dtype),
            name=_kda_name("bwd", v.dtype, chunk, key_dim, value_dim, fused),
            interpret=interpret,
        )(q, k, v, g, beta, *rest)


def _kda_chunked(q, k, v, g, beta, chunk):
    """The rule in the ``jax.numpy`` chunk form on the kernels' operands
    (a head's columns side by side), o as they give it ([B, T, H V]
    float32): the branch for every platform but the TPU."""
    from ..transformer import channel_delta_rule as chunk_form

    b, t, h = beta.shape
    q, k, v, g = (x.reshape(b, t, h, -1) for x in (q, k, v, g))
    return chunk_form(q, k, v, g, beta, chunk).reshape(b, t, -1)


def _kda_net_chunked(q, k, v, a, beta, a_log, dt_bias, chunk):
    """``_gated_delta_block``'s ``delta_rule`` stage in its channel form as
    it stands there (the unit norms, the decays a channel, the ``jax.numpy``
    chunk form), on the fused pair's operands."""
    f32 = jnp.float32
    b, t, h = beta.shape
    key_dim = q.shape[2] // h

    def unit(x):  # each head's vector over its length, float32
        x = x.astype(f32).reshape(b, t, h, -1)
        return x * lax.rsqrt(
            jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)

    g = -jnp.exp(a_log[:, None].astype(f32)) * jax.nn.softplus(
        a.reshape(b, t, h, key_dim).astype(f32)
        + dt_bias.reshape(h, key_dim).astype(f32))
    return _kda_chunked(
        (unit(q) * key_dim ** -0.5).astype(v.dtype).reshape(q.shape),
        unit(k).astype(v.dtype).reshape(k.shape), v,
        g.reshape(b, t, -1), beta, chunk)


def _kda_rule(fused):
    """The ``custom_vjp`` over (q, k, v, g, beta) and, ``fused``, over (q,
    k, v, a, beta, a_log, dt_bias): one plan, the platform's branch chosen
    inside the forward and inside the rule."""
    plain = _kda_net_chunked if fused else _kda_chunked
    n = 7 if fused else 5

    def rows(*pre):
        """``-exp(a_log)`` along each head's lanes and the bias, [1, H K]
        float32."""
        if not fused:
            return ()
        a_log, dt_bias = pre
        key_dim = dt_bias.shape[0] // a_log.shape[0]
        return (jnp.repeat(-jnp.exp(a_log.astype(jnp.float32)),
                           key_dim)[None],
                dt_bias.astype(jnp.float32)[None])

    def whole_chunks(chunk, *xs):
        """[B, T, ...] padded with zeros to whole chunks: no write (k 0,
        beta 0), and nothing reads the state behind the last token."""
        pad = -xs[0].shape[1] % chunk
        return tuple(jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
                     for x in xs) if pad else xs

    @functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
    def forward(*ins, chunk, interpret):
        """The inputs, o [B, T, H V] float32 and the backward's other
        residuals: the state each chunk entered with, its system's
        inverse and its two tables (zeros off the TPU, where the chunk
        form's own transpose is the backward)."""
        b, t, h = ins[4].shape
        key_dim, value_dim = ins[0].shape[2] // h, ins[2].shape[2] // h

        def kernels(*ins, interpret):
            o, *kept = kda_fwd_call(
                *whole_chunks(chunk, *ins[:5]), *rows(*ins[5:]), chunk=chunk,
                interpret=interpret)
            return (o[:, :t],) + tuple(kept)

        def chunked(*ins):
            pairs = _pairs(h, kda_group(h))
            return (plain(*ins, chunk),) + tuple(
                jnp.zeros((b, -(-t // chunk)) + tail, jnp.float32)
                for tail in ((h, value_dim, key_dim),
                             (pairs, chunk, 2 * chunk),
                             (pairs, 2 * chunk, 2 * chunk)))

        return ins + tuple(on_tpu(kernels, chunked, interpret, *ins))

    @functools.partial(jax.custom_vjp, nondiff_argnums=(n, n + 1))
    def rule(*args):
        return rule_fwd(*args)[0]

    def rule_fwd(*args):
        # one trace of the forward for the primal and the rule: see _ssd_fwd
        with jax.sharding.use_abstract_mesh(jax.sharding.get_abstract_mesh()):
            res = forward(*args[:n], chunk=args[n], interpret=args[n + 1])
        return res[n], res[:n] + res[n + 1:]

    def rule_bwd(chunk, interpret, res, do):
        b, t, h = res[4].shape

        def kernels(*ins, interpret):
            dq, dk, dv, dg, dbeta, *sums = kda_bwd_call(
                *whole_chunks(chunk, *ins[:5]), *rows(*ins[5:n]), *ins[n:-1],
                *whole_chunks(chunk, ins[-1]), chunk=chunk,
                interpret=interpret)
            grads = tuple(x[:, :t] for x in (
                dq, dk, dv, dg, jnp.moveaxis(dbeta, 1, 2).reshape(b, -1, h)))
            if not fused:
                return grads
            # a head's lanes of the first row are its a_log's cotangent
            by_lane = jnp.sum(sums[0], axis=(0, 1))
            return grads + (
                jnp.sum(by_lane[0].reshape(h, -1), 1).astype(ins[5].dtype),
                by_lane[1].astype(ins[6].dtype))

        def chunked(*ins):
            return jax.vjp(lambda *x: plain(*x, chunk), *ins[:n])[1](ins[-1])

        return on_tpu(kernels, chunked, interpret, *res,
                      do.astype(jnp.float32))

    rule.defvjp(rule_fwd, rule_bwd)
    return rule, forward


_kda, kda_forward = _kda_rule(False)
_kda_net, kda_net_forward = _kda_rule(True)


def channel_delta_rule(q, k, v, g, beta, chunk, interpret=False):
    """``ops/transformer.py::channel_delta_rule`` (q and k [B, T, H, K], v
    [B, T, H, V] in one type, g [B, T, H, K] and beta [B, T, H] -> o [B, T,
    H, V] float32) as a Pallas kernel pair, differentiable in all five, for
    the shapes ``gdn_takes(..., "channel")`` admits. T is padded to whole
    chunks as ``gated_delta_rule`` pads it (on the kernels' branch: the
    chunk form pads itself); the kernels read and write token-major, a
    head's lane rows a column block of [B, T, H K]. Mosaic where the
    computation is lowered for the TPU, the ``jax.numpy`` chunk form itself
    on every other platform, ``interpret=True`` the kernels through the
    Pallas interpreter wherever; no partitioning rule
    (``gated_delta_rule``'s notes). What the op calls is
    ``channel_delta_net`` below: these arrays [B, T, H, K] are not laid out
    as the kernels' [B, T, H K] are (a tile is eight heads of one token,
    not eight tokens of one head), so round this entry XLA moves them."""
    b, t, h, _ = q.shape
    f32 = jnp.float32
    o = _kda(*(x.reshape(b, t, -1) for x in (q, k, v, g.astype(f32))),
             beta.astype(f32), int(chunk), bool(interpret))
    return o.reshape(b, t, h, -1)


def channel_delta_net(q, k, v, a, beta, a_log, dt_bias, chunk,
                      interpret=False):
    """``GatedDeltaNet``'s ``delta_rule`` stage in its channel form, from
    the convolution's outputs to the rule's: q, k and ``a`` [B, T, H K], v
    [B, T, H V] as the projections and the taps' pair leave them, beta [B,
    T, H] float32, a_log [H], dt_bias [H K] -> o [B, T, H V] float32,
    differentiable in all seven. The unit norms, ``g = -exp(a_log)
    softplus(a + dt_bias)`` and its running sums are made in VMEM a chunk
    at a time (``_kda_chunk``) and their transposes in the backward
    kernel, so no array [B, T, H, K] exists and XLA moves nothing round
    the pair; what is kept for the backward is the inputs and the pair's
    three residuals. Branches and padding as ``channel_delta_rule``'s (a
    padded token: q, k, v and ``a`` 0, beta 0; its decay is not 1, and
    nothing reads the state behind the last token)."""
    return _kda_net(q, k, v, a, beta.astype(jnp.float32), a_log, dt_bias,
                    int(chunk), bool(interpret))
