"""The gated delta rule's chunk core (ops/transformer.py::gated_delta_net;
Gated DeltaNet, Yang, Kautz & Hatamizadeh, arXiv:2412.06464).

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
  heads     ``gdn_group`` heads a step, one after another in a
            ``fori_loop``: one traced body however many there are (a
            head's tables are a lane and a sublane of the step's two
            blocks, picked by a select and a dynamic slice). A step of
            several heads spreads the grid's cost a step.
  system    with ``b`` the running log decay, ``D_ij = exp(b_i - b_j)``
            and ``L = beta_i D_ij (k_i . k_j)`` strictly below the
            diagonal, ``(I + L)^-1`` is built ONCE a chunk by forward
            substitution (``_gdn_inverse``: column j's multipliers
            eliminate row j from the rows below it, 16-row tiles the
            column has passed left alone), never by a series in powers
            of ``L``, whose terms cancel once keys repeat. It is applied
            as a float32 product: ``u = (I + L)^-1 beta (v - c k S)``,
            which is ``W - Y S`` of the chunk form with one system
            solved, not two. The forward keeps the inverse (16 KB a
            chunk and head at C 64) and the backward applies its
            transpose: no substitution runs backwards.
  tables    ``b`` and ``beta`` token-major (a head a column of one lane
            row) and ``b`` head-major (a row along lanes, for ``D``) are
            XLA's (``_gdn_tables``); every exponential is taken here.
  backward  the same walk from the last chunk to the first carrying the
            state's cotangent; a chunk's tables, ``u`` and the products
            with the entering state rebuilt. ``dL = -(T^T du) u^T``
            (``T`` the inverse), so the system's cotangent is two
            products. The log decay's cotangent is the row sums less
            the column sums of ``dM * M + dL * L`` (token-major and
            head-major outputs, joined by XLA) plus what ``c``, the
            decay to the chunk's end and the carried state add.
  set-up    as the scan's: ``jax.lax`` primitives only, each
            ``pallas_call`` behind a ``jax.jit``
            (``linear_attn.kernel_traces``), the ``jax.numpy`` chunk
            form on every platform but the TPU.

Log decays, their sums and exponentials, ``L``, the substitution, the
inverse's products, the state and every accumulator float32; the MXU's
other operands in v's type.
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
    "nodes call it; nothing per step); labels: mode (fwd / bwd)")

GDN_HEADS_A_STEP = 16


def gdn_group(heads):
    """Heads a grid step: the largest divisor of ``heads`` whose two
    scalars a token are columns of one lane row of tables with room to
    spare. The body is one head's whatever the group (a ``fori_loop``);
    a larger group is fewer, longer steps and less padding in the
    tables."""
    return max(g for g in range(1, GDN_HEADS_A_STEP + 1) if heads % g == 0)


def gdn_vmem_bytes(chunk, per, key_dim, value_dim, itemsize):
    """What a backward step holds, counted generously: the double-buffered
    blocks (q, k, dq, dk; v, dv; do; the entering state; the inverse; the
    four tables), the carried cotangents in scratch, and two dozen float32
    temporaries of the one head in hand as wide as the widest table."""
    k, v, c = (whole_lanes(w) for w in (key_dim, value_dim, chunk))
    state = key_dim * v * 4
    head = (4 * chunk * k * itemsize + 2 * chunk * v * itemsize
            + chunk * v * 4 + state + chunk * c * 4)
    tables = 2 * chunk * LANES * 4 + 2 * -(-per // 8) * 8 * c * 4
    return (2 * (per * head + tables) + per * state + chunk * c * 4
            + 24 * max(chunk * max(k, v) * 4, state))


def gdn_takes(heads, key_dim, value_dim, chunk, dtype, decay="scalar"):
    """Whether ``gated_delta_rule`` has tiles for these shapes: chunks of
    whole bf16 sublane tiles up to a lane row, keys and values of a head in
    multiples of 32 (a quarter of a lane row, padded in VMEM), an operand
    type Mosaic takes, a step that fits VMEM, and ONE decay a head and token
    (``decay="channel"``: refused). Everything else is the ``jax.numpy``
    chunk forms' (``ops/transformer.py::gated_delta_rule``)."""
    if min(heads, key_dim, value_dim, chunk) <= 0 or decay != "scalar":
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
    padded to a lane row) and ``rows`` [B, H / per, T / C, 8n, C] (b, a
    head a row, padded to whole sublane tiles), ``b`` the running sum of
    ``g`` inside each chunk."""
    b, t, h = g.shape
    nc, groups = t // chunk, h // per
    cum = jnp.einsum("ij,bcjh->bcih",
                     np.tril(np.ones((chunk, chunk), np.float32)),
                     g.reshape(b, nc, chunk, h),
                     precision=lax.Precision.HIGHEST)
    cols = jnp.stack([cum, beta.reshape(b, nc, chunk, h)], axis=3)
    cols = cols.reshape(b, nc, chunk, 2, groups, per).transpose(
        0, 4, 1, 2, 3, 5).reshape(b, groups, t, 2 * per)
    rows = cum.reshape(b, nc, chunk, groups, per).transpose(0, 3, 1, 4, 2)
    return (jnp.pad(cols, ((0, 0),) * 3 + ((0, LANES - 2 * per),)),
            jnp.pad(rows, ((0, 0),) * 3 + ((0, -per % 8), (0, 0))))


def _gdn_masks(c):
    """Made once a body: ``causal`` [C, C] (j <= i), ``strict`` (j < i),
    the table of ``NEG_INF`` the decay's select falls to and the identity
    in 16-row tiles."""
    iota = lax.broadcasted_iota
    row, col = iota(jnp.int32, (c, c), 0), iota(jnp.int32, (c, c), 1)
    one, zero = (lax.full((16, c), v, jnp.float32) for v in (1, 0))
    at, lane = iota(jnp.int32, (16, c), 0), iota(jnp.int32, (16, c), 1)
    eye = tuple(
        lax.select(lax.eq(lax.add(at, np.int32(16 * p)), lane), one, zero)
        for p in range(c // 16))
    return dict(causal=lax.ge(row, col), strict=lax.gt(row, col),
                masked=lax.full((c, c), NEG_INF, jnp.float32), eye=eye)


def _gdn_inverse(low_ref, eye):
    """``(I + L)^-1`` for the strictly lower triangular ``L`` [C, C]
    float32 in ``low_ref``, by forward substitution: ``I + L`` is the
    product over j of ``I + l_j e_j^T`` (``l_j`` column j of ``L``), so
    its inverse is ``I - l_j e_j^T`` applied to the identity for j = 0,
    1, ...: row j, final once the columns before it are through, times
    column j's multipliers leaves the rows below it. Rows in 16-row
    tiles; a tile wholly above row j + 1 is not touched."""
    c = low_ref.shape[0]
    tiles = list(eye)
    for j in range(c - 1):
        row = lax.slice(tiles[j // 16], (j % 16, 0), (j % 16 + 1, c))
        for p in range((j + 1) // 16, c // 16):
            tiles[p] = lax.sub(tiles[p], lax.mul(
                low_ref[16 * p:16 * p + 16, j:j + 1], row))
    return lax.concatenate(tiles, 0)


def _gdn_column(cols, lane, at):
    """Column ``at`` (a traced index) of the token-major tables [C, 128]
    as [C, 1]: a select and a sum along lanes, which is exact."""
    return sum_keepdims(lax.select(lax.eq(lane, at), cols,
                               lax.full(cols.shape, 0, cols.dtype)), 1)


def _gdn_chunk(q_ref, k_ref, v_ref, cols_ref, rows_ref, state, h, heads,
               masks):
    """What both kernels make of head h's chunk (h a traced index: the
    heads of a step are a ``fori_loop``, one traced body) before the
    system: the operands, ``b`` and ``beta`` by token, the decay table
    ``D`` (0 above the diagonal), ``c``, the decay to the chunk's end and
    over the whole chunk ([1, V]), ``D * (k k^T)``, ``D * (q k^T)`` and
    the two products with the entering ``state`` [K, V]."""
    c = q_ref.shape[1]
    op, f32 = v_ref.dtype, jnp.float32
    cast = lax.convert_element_type
    q, k, v = q_ref[h], k_ref[h], cast(v_ref[h], f32)
    cols = cols_ref[...]
    lane = lax.broadcasted_iota(jnp.int32, cols.shape, 1)
    b_col = _gdn_column(cols, lane, h)
    beta = _gdn_column(cols, lane, lax.add(h, np.int32(heads)))
    b_row = rows_ref[pl.ds(h, 1), :]
    decay = lax.exp(lax.select(
        masks["causal"], lax.sub(b_col, b_row), masks["masked"]))
    total = lax.slice(b_row, (0, c - 1), (1, c))
    # [1, 1] over a table in two steps, lanes first and the exponential
    # between them: Mosaic has no broadcast along both at once
    whole = lax.exp(lax.broadcast_in_dim(total, (1, state.shape[1]), (0, 1)))
    state_op = cast(state, op)
    return dict(
        q=q, k=k, v=v, beta=beta, decay=decay, c=lax.exp(b_col),
        to_end=lax.exp(lax.sub(total, b_col)), whole=whole, lane=lane,
        kk=lax.mul(decay, dot_highest(k, k, (1, 1))),
        qk=lax.mul(decay, dot_highest(q, k, (1, 1))),
        ks=dot_highest(k, state_op, (1, 0)),
        qs=dot_highest(q, state_op, (1, 0)))


def _gdn_fwd_kernel(q_ref, k_ref, v_ref, cols_ref, rows_ref, o_ref, ent_ref,
                    inv_ref, state, low_s, *, heads):
    """One chunk of ``heads`` heads. q, k [G, C, K]; v [G, C, V]; cols
    [C, 128]; rows [8n, C] -> o [G, C, V] float32, the state each head's
    chunk entered with [G, K, V] and ``(I + L)^-1`` [G, C, C], float32."""
    op, f32 = v_ref.dtype, jnp.float32
    cast, mul, add, sub = (lax.convert_element_type, lax.mul, lax.add,
                           lax.sub)
    masks = _gdn_masks(q_ref.shape[1])
    zero = lax.full(masks["strict"].shape, 0, f32)

    @pl.when(first_chunk())
    def _():
        state[...] = lax.full(state.shape, 0, f32)

    def head(h, carry):
        entered = state[h]
        ent_ref[h] = entered
        t = _gdn_chunk(q_ref, k_ref, v_ref, cols_ref, rows_ref, entered, h,
                       heads, masks)
        low_s[...] = lax.select(masks["strict"], mul(t["beta"], t["kk"]),
                                zero)
        inv = _gdn_inverse(low_s, masks["eye"])
        inv_ref[h] = inv
        u = cast(dot_highest(inv, mul(t["beta"],
                                   sub(t["v"], mul(t["c"], t["ks"]))),
                          (1, 0)), op)
        o_ref[h] = add(dot_highest(cast(t["qk"], op), u, (1, 0)),
                       mul(t["c"], t["qs"]))
        k_out = cast(mul(t["to_end"], cast(t["k"], f32)), op)
        state[h] = add(mul(t["whole"], entered), dot_highest(k_out, u, (0, 0)))
        return carry

    lax.fori_loop(0, heads, head, np.int32(0))


def _gdn_bwd_kernel(q_ref, k_ref, v_ref, cols_ref, rows_ref, ent_ref,
                    inv_ref, do_ref, dq_ref, dk_ref, dv_ref, small_ref,
                    across_ref, dstate, *, heads):
    """The same chunk with do [G, C, V] float32 and the cotangent of the
    state each head leaves (carried, [G, K, V]) -> dq, dk, dv in the
    operands' type, ``small`` [C, 128] (a head's cotangent of ``b`` by
    row sums | of ``beta``, a head a column) and ``across`` [8n, C] (what
    the column sums of ``dM * M + dL * L`` take from ``b``'s, a head a
    row)."""
    c = q_ref.shape[1]
    op, f32 = v_ref.dtype, jnp.float32
    cast, mul, add, sub = (lax.convert_element_type, lax.mul, lax.add,
                           lax.sub)
    masks = _gdn_masks(c)
    strict = masks["strict"]
    zero = lax.full(strict.shape, 0, f32)
    last = lax.eq(lax.broadcasted_iota(jnp.int32, (c, 1), 0),
                  np.int32(c - 1))
    none = lax.full((c, 1), 0, f32)

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

    def head(h, small):
        entered, dleft = ent_ref[h], dstate[h]
        entered_op, dleft_op = cast(entered, op), cast(dleft, op)
        t = _gdn_chunk(q_ref, k_ref, v_ref, cols_ref, rows_ref, entered, h,
                       heads, masks)
        q, k, beta, decay = t["q"], t["k"], t["beta"], t["decay"]
        k32 = cast(k, f32)
        inv = inv_ref[h]
        low = lax.select(strict, mul(beta, t["kk"]), zero)
        kept = sub(t["v"], mul(t["c"], t["ks"]))        # v - c k S
        u = dot_highest(inv, mul(beta, kept), (1, 0))
        u_op = cast(u, op)
        do = do_ref[h]
        do_op = cast(do, op)
        k_out = cast(mul(t["to_end"], k32), op)
        du = add(dot_highest(cast(t["qk"], op), do_op, (0, 0)),
                 dot_highest(k_out, dleft_op, (1, 0)))
        dr = dot_highest(inv, du, (0, 0))                  # T^T du
        dm = dot_highest(do_op, u_op, (1, 1))              # do u^T
        dqk = mul(decay, dm)
        dlow = lax.select(strict, lax.neg(dot_highest(dr, u, (1, 1))), zero)
        dkk = mul(dlow, mul(beta, decay))
        dqk_op, dkk_op = cast(dqk, op), cast(dkk, op)
        c_do = cast(mul(t["c"], do), op)
        dks = cast(lax.neg(mul(mul(beta, t["c"]), dr)), op)
        left = dot_highest(u_op, dleft_op, (1, 1))         # u dS'^T, [C, K]
        dq_ref[h] = cast(add(dot_highest(c_do, entered_op, (1, 1)),
                             dot_highest(dqk_op, k, (1, 0))), dq_ref.dtype)
        dk_ref[h] = cast(
            add(add(dot_highest(dks, entered_op, (1, 1)),
                    dot_highest(dqk_op, q, (0, 0))),
                add(add(dot_highest(dkk_op, k, (1, 0)),
                        dot_highest(dkk_op, k, (0, 0))),
                    mul(t["to_end"], left))), dk_ref.dtype)
        dv_ref[h] = cast(mul(beta, dr), dv_ref.dtype)
        # b_i multiplies row i of D and divides column i; c_i = exp(b_i);
        # the decay to the end divides by it; the last one carries the
        # whole chunk's decay of the entering state and of every key
        pairs = add(mul(dm, t["qk"]), mul(dlow, low))
        to_end = mul(t["to_end"], sum_keepdims(mul(k32, left), 1))
        decayed = mul(t["whole"], dleft)
        leaves = add(total(to_end), total(mul(decayed, entered)))
        db = add(
            sub(add(sum_keepdims(pairs, 1),
                    mul(t["c"], sum_keepdims(
                        sub(mul(do, t["qs"]),
                            mul(mul(beta, dr), t["ks"])), 1))), to_end),
            lax.select(last, lax.broadcast_in_dim(leaves, (c, 1), (0, 1)),
                       none))
        dbeta = add(sum_keepdims(mul(dr, kept), 1),
                    sum_keepdims(mul(dlow, t["kk"]), 1))
        across_ref[pl.ds(h, 1), :] = sum_keepdims(pairs, 0)
        dstate[h] = add(decayed,
                        add(dot_highest(q, c_do, (0, 0)),
                            dot_highest(k, dks, (0, 0))))
        return add(small, add(
            column(t["lane"], h, db),
            column(t["lane"], lax.add(h, np.int32(heads)), dbeta)))

    small_ref[...] = lax.fori_loop(
        0, heads, head, lax.full(small_ref.shape, 0, f32))


def _gdn_name(which, dtype, chunk, key_dim, value_dim):
    return "gdn_%s_%s_c%d_k%d_v%d" % (which, operand_label(dtype), chunk,
                                      key_dim, value_dim)


def _gdn_specs(chunk, per, key_dim, value_dim, nc, reverse):
    """Block specs of (a group's heads of q and k, of v, the token-major
    tables, the head-major ones, the entering states, the inverses) at
    grid step (batch, group, chunk), the chunks walked downwards under
    ``reverse``."""
    def at(c):
        return lax.sub(np.int32(nc - 1), c) if reverse else c

    def by_head(width):
        return pl.BlockSpec((None, per, chunk, width),
                            lambda b, g, c: (b, g, at(c), 0))

    def by_chunk(rows, width):
        return pl.BlockSpec((None, None, per, rows, width),
                            lambda b, g, c: (b, at(c), g, 0, 0))

    return (by_head(key_dim), by_head(value_dim),
            pl.BlockSpec((None, None, chunk, LANES),
                         lambda b, g, c: (b, g, at(c), 0)),
            pl.BlockSpec((None, None, None, -(-per // 8) * 8, chunk),
                         lambda b, g, c: (b, g, at(c), 0, 0)),
            by_chunk(key_dim, value_dim), by_chunk(chunk, chunk))


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
    the entering states [B, T / C, H, K, V] and the systems' inverses
    [B, T / C, H, C, C], float32."""
    _M_GDN_TRACES.inc(mode="fwd")
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
                jax.ShapeDtypeStruct((b, nc, h, chunk, chunk),
                                     jnp.float32)],
            scratch_shapes=[
                pltpu.VMEM((per, key_dim, value_dim), jnp.float32),
                pltpu.VMEM((chunk, chunk), jnp.float32)],
            compiler_params=_gdn_params(chunk, per, key_dim, value_dim,
                                        v.dtype),
            name=_gdn_name("fwd", v.dtype, chunk, key_dim, value_dim),
            interpret=interpret,
        )(q, k, v, cols, rows)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def gdn_bwd_call(q, k, v, cols, rows, entering, inverse, do, *, chunk,
                 interpret):
    """-> dq, dk [B, H, T, K] and dv [B, H, T, V] in the operands' type,
    ``small`` [B, H / G, T, 128] and ``across`` [B, H / G, T / C, 8n, C],
    float32 (``_gdn_bwd_kernel``)."""
    _M_GDN_TRACES.inc(mode="bwd")
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
                jnp.zeros((b, t // chunk, h, chunk, chunk), jnp.float32))

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
        db = db - across[:, :, :, :per].transpose(0, 2, 4, 1, 3).reshape(
            b, nc, chunk, h)
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
