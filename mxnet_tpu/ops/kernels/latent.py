"""Latent attention's flash pair (ops/transformer.py::latent_attention; MLA,
DeepSeek-V2/V3) and the pass over its query. The same algorithm as the
single-key flash kernels (tiles from ``flash.flash_tiles``, the causal
live-tile walk, ``flash.tile_cases``' unmasked fast path and its diagonal
tiles by quarters, the one-pass backward with the diagonal first), on the
operands where the
neighbouring matmuls leave and take them, every one token-major and a
head a block of whole lane rows of its columns:

  score     ``s = scale (q_nope k_nope^T + q_rope k_rope^T)``: a head's
            own key and the ONE rotary key a token, two key operands
            whose tiles stand side by side in VMEM for the one product
            (``_latent_key``); the concatenated contraction, over zero
            lanes besides. No key of [T, H, N + R] exists in HBM.
  kv        [B, T, H (N + Dv)], the up-projection's output as its
            matmul writes it: head h's block is column block h, its
            lanes 0:N the keys and N: the values (N and Dv whole lane
            rows, so both are free views of the VMEM tile). The
            backward writes dK_nope and dV into the two halves of the
            same layout: the up-projection's output gradient, no pad.
  k_rope    [B, T, Rp] (the R rotated lanes, then zeros to a whole lane
            row): its block index ignores the head. The backward sums
            dK_rope over the heads in float32 VMEM scratch and rounds
            it once a batch row (the head is an ``arbitrary`` grid
            dimension there).
  q, dq     [B, T, H (N + Rp)]: a head's N un-rotated lanes, its R
            rotated ones and zeros to a whole lane row, which the one
            pass over the query (the rotation: ``latent_query`` below)
            writes and its transpose reads. A head of 192 lanes is not
            a block of the query as the projection leaves it; padded in
            VMEM it took 256 before, and the product over the zero lanes
            is the half MXU pass the 64 rotary lanes always left empty.
  o, dO     [B, T, H Dv], column block h: what the output projection
            reads and its transpose writes. ``lse`` [B, H, T, 1], the
            kernels' own; ``delta`` never leaves VMEM.
  set-up    as the scan's: each ``pallas_call`` and the forward and the
            backward round it behind a ``jax.jit`` (one trace a
            signature however many layers, ``attention.
            latent_kernel_traces``); on every platform but the TPU the
            branch is ``flash.reference_attention`` over the concatenated key
            on the same operands, not the Pallas interpreter, which
            runs only where a caller says ``interpret=True`` (tests).

grid (batch, head, q tile, k step), k innermost. Only the one-pass
backward exists: ``latent_flash_takes`` admits the shapes whose dK / dV
of a head stay in VMEM (``flash.bwd_fuses``) and whose N and Dv are whole
lane rows; every other shape keeps ``latent_attention``'s composition
over ``attention``. Float32 scores, mask, softmax, lse, delta and
accumulators; operands in the type they arrive in.
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
from . import flash
from .common import (
    LANES, NEG_INF, affine, no_x64, on_tpu, operand_label, pad_to,
    rope_inv_freq, whole_lanes)

_M_LATENT_TRACES = _tm.counter(
    "attention.latent_kernel_traces", "Traces of a latent flash kernel's "
    "pallas_call (one a signature and process, however many "
    "LatentAttention nodes call it; nothing per step); labels: pass "
    "(fwd / bwd) and, where the kernel runs its cut tiles by quarters, "
    "edge (a quarter's rows)")

_NT = (((1,), (1,)), ((), ()))      # a @ b^T
_TN = (((0,), (0,)), ((), ()))      # a^T @ b
_NN = (((1,), (0,)), ((), ()))      # a @ b


def _f32_dot(lhs, rhs, dims):
    return lax.dot_general(lhs, rhs, dims,
                           preferred_element_type=jnp.float32)


def latent_flash_takes(t, nope, rope, dv, dtype):
    """Whether the kernel pair runs latent attention over ``t`` positions
    with a head's own key ``nope`` wide, the shared rotary key ``rope``
    and values ``dv``: decided by the shapes and the operand type alone.
    ``nope`` and ``dv`` whole lane rows (a head's keys and values are
    lane-aligned halves of one block), a sequence of a tile at least, an
    operand type Mosaic takes, and a head's dK / dV resident for the
    one-pass backward."""
    if (t < flash.FLASH_MIN_BLOCK or nope <= 0 or nope % LANES
            or dv <= 0 or dv % LANES or rope <= 0
            or jnp.dtype(dtype).name not in ("bfloat16", "float32")):
        return False
    width = nope + whole_lanes(rope)
    block_q, block_k = flash.flash_tiles(t, max(width, dv), dtype)
    mult = int(np.lcm(block_q, block_k))
    return flash.bwd_fuses(-(-t // mult) * mult, block_q, block_k, width, dv,
                      dtype)


def _latent_key(kv_ref, kr_ref, nope, cols):
    """Rows ``cols`` of a head's key tile [k_nope | k_rope], put together
    in VMEM: two lane-aligned column groups of one value, so ONE product
    over both (the MXU sums the two inside it; two products summed by the
    vector unit cost a pass over the scores more: PERF.md section 7,
    PR 44)."""
    return lax.concatenate([kv_ref[cols, :nope], kr_ref[cols]], 1)


def _latent_scores(q_ref, k_blk, qi, ki, masked, part, *, block_q, block_k,
                   t_real, scale):
    """``scale (q_nope k_nope^T + q_rope k_rope^T)`` of one tile pair (of
    its ``part``) and, under ``masked``, ``_masked_scores``' keep-mask of
    a causal call."""
    s = lax.mul(_f32_dot(q_ref[part.rows], k_blk, _NT), np.float32(scale))
    if not masked:
        return s, None
    if part.delta is not None:
        return s, flash.part_mask(*s.shape, part.delta, 0)
    shape = (block_q, block_k)
    q_pos = lax.add(lax.broadcasted_iota(jnp.int32, shape, 0),
                    affine(qi, block_q))
    k_pos = lax.add(lax.broadcasted_iota(jnp.int32, shape, 1),
                    affine(ki, block_k))
    return s, lax.bitwise_and(lax.lt(k_pos, np.int32(t_real)),
                              lax.ge(q_pos, k_pos))


def _kept(keep_ref, mask):
    """The keep-mask's tile (int8, 0 drops the pair) and the positions'
    own ``mask`` (None off the diagonal): a pair is live under both."""
    kept = lax.ne(keep_ref[...].astype(jnp.int32), np.int32(0))
    return kept if mask is None else lax.bitwise_and(mask, kept)


def _latent_fwd_kernel(q_ref, kv_ref, kr_ref, o_ref, l_ref, acc, m_s, l_s,
                       *, nope, block_q, block_k, t_real, t_pad, scale,
                       edge, keep_ref=None):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _():
        acc[...] = jnp.zeros(acc.shape, jnp.float32)
        m_s[...] = jnp.full(m_s.shape, NEG_INF, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)

    def body(masked, part=flash.WHOLE):
        rows, cols = part.rows, part.cols
        v_blk = kv_ref[cols, nope:]
        s, mask = _latent_scores(
            q_ref, _latent_key(kv_ref, kr_ref, nope, cols), qi, ki, masked,
            part, block_q=block_q, block_k=block_k, t_real=t_real,
            scale=scale)
        if keep_ref is not None:
            mask = _kept(keep_ref, mask)
        if mask is not None:
            s = jnp.where(mask, s, jnp.float32(NEG_INF))
        m_prev = m_s[rows]
        m_cur = lax.max(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = lax.exp(lax.sub(m_prev, m_cur))
        p = lax.exp(lax.sub(s, m_cur))
        if keep_ref is not None:
            # a row may keep no key of a tile before its first kept one:
            # its maximum is still NEG_INF there and exp(0) is no weight
            p = jnp.where(mask, p, jnp.float32(0.0))
        l_s[rows] = lax.add(lax.mul(l_s[rows], alpha),
                            jnp.sum(p, axis=1, keepdims=True))
        m_s[rows] = m_cur
        acc[rows] = lax.add(lax.mul(acc[rows], alpha),
                            _f32_dot(p.astype(v_blk.dtype), v_blk, _NN))

    flash.tile_cases(body, qi, ki, block_q=block_q, block_k=block_k,
                     t_real=t_real, t_pad=t_pad, causal=True, edge=edge,
                     by_rows=True)

    @pl.when(ki == pl.num_programs(3) - 1)
    def _():
        l_fin = l_s[...]
        safe_l = jnp.where(l_fin > 0, l_fin, jnp.float32(1.0))
        o_ref[...] = lax.div(acc[...], safe_l).astype(o_ref.dtype)
        l_ref[...] = lax.add(m_s[...], lax.log(safe_l))


def _latent_bwd_kernel(q_ref, kv_ref, kr_ref, o_ref, do_ref, l_ref, dq_ref,
                       dkv_ref, dkr_ref, delta, dq_acc, dk_acc, dv_acc,
                       dkr_acc, *, nope, block_q, block_k, t_real, t_pad,
                       scale, edge, keep_ref=None):
    """dq, dK_nope | dV and dK_rope in one pass, as ``_bwd_fused_kernel``
    makes dq, dk and dv: a row's k tiles from the diagonal down, dq in
    tile-sized scratch over the inner steps, a head's dK_nope and dV in
    float32 scratch over its q tiles, written at the head's last step
    into the two lane halves of its block; dK_rope in scratch over every
    head of the batch row, written at the last head's last step.
    ``delta_i = sum_d dO_id O_id`` is made here at a row's first step (o
    and dO are column blocks of token-major arrays: XLA's reduction over
    them wrote the products out in float32 and transposed them)."""
    head = pl.program_id(1)
    qi = pl.program_id(2)
    j = pl.program_id(3)
    ki = lax.sub(pl.num_programs(3) - 1, j)
    first = lax.bitwise_and(lax.eq(qi, np.int32(0)),
                            lax.eq(j, np.int32(0)))
    last = lax.bitwise_and(lax.eq(qi, pl.num_programs(2) - 1),
                           lax.eq(j, pl.num_programs(3) - 1))
    scale32 = np.float32(scale)

    def each_k_tile(fn):
        def step(i, carry):
            fn(i)
            return carry
        lax.fori_loop(0, dk_acc.shape[0], step, 0)

    @pl.when(first)
    def _():
        def zero(i):
            dk_acc[i] = jnp.zeros(dk_acc.shape[1:], jnp.float32)
            dv_acc[i] = jnp.zeros(dv_acc.shape[1:], jnp.float32)
        each_k_tile(zero)

    @pl.when(lax.bitwise_and(first, lax.eq(head, np.int32(0))))
    def _():
        def zero(i):
            dkr_acc[i] = jnp.zeros(dkr_acc.shape[1:], jnp.float32)
        each_k_tile(zero)

    @pl.when(j == 0)
    def _():
        dq_acc[...] = jnp.zeros(dq_acc.shape, jnp.float32)
        delta[...] = jnp.sum(
            lax.mul(do_ref[...].astype(jnp.float32),
                    o_ref[...].astype(jnp.float32)), axis=1, keepdims=True)

    def body(masked, part=flash.WHOLE):
        rows, cols = part.rows, part.cols
        do = do_ref[rows]
        k_blk = _latent_key(kv_ref, kr_ref, nope, cols)
        s, mask = _latent_scores(
            q_ref, k_blk, qi, ki, masked, part, block_q=block_q,
            block_k=block_k, t_real=t_real, scale=scale)
        p = lax.exp(lax.sub(s, l_ref[rows]))
        if keep_ref is not None:
            mask = _kept(keep_ref, mask)
        if mask is not None:
            p = jnp.where(mask, p, jnp.float32(0.0))
        dp = _f32_dot(do, kv_ref[cols, nope:], _NT)
        ds = lax.mul(p, lax.sub(dp, delta[rows])).astype(q_ref.dtype)
        dv_acc[ki, cols] = lax.add(
            dv_acc[ki, cols], _f32_dot(p.astype(do.dtype), do, _TN))
        dk = _f32_dot(ds, q_ref[rows], _TN)     # [dK_nope | dK_rope]
        dk_acc[ki, cols] = lax.add(dk_acc[ki, cols], dk[:, :nope])
        dkr_acc[ki, cols] = lax.add(dkr_acc[ki, cols], dk[:, nope:])
        dq_acc[rows] = lax.add(dq_acc[rows], _f32_dot(ds, k_blk, _NN))

    flash.tile_cases(body, qi, ki, block_q=block_q, block_k=block_k,
                     t_real=t_real, t_pad=t_pad, causal=True, edge=edge)

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        dq_ref[...] = lax.mul(dq_acc[...], scale32).astype(dq_ref.dtype)

    @pl.when(last)
    def _():
        def write(i):
            dkv_ref[i, :, :nope] = lax.mul(dk_acc[i], scale32).astype(
                dkv_ref.dtype)
            dkv_ref[i, :, nope:] = dv_acc[i].astype(dkv_ref.dtype)
        each_k_tile(write)

    @pl.when(lax.bitwise_and(
        last, lax.eq(head, pl.num_programs(1) - 1)))
    def _():
        def write(i):
            dkr_ref[i] = lax.mul(dkr_acc[i], scale32).astype(dkr_ref.dtype)
        each_k_tile(write)


def _select_fwd_kernel(q_ref, kv_ref, kr_ref, keep_ref, *refs, **static):
    """``_latent_fwd_kernel`` whose every live tile is also masked by the
    keep-mask's tile: the selected variant's operand order."""
    _latent_fwd_kernel(q_ref, kv_ref, kr_ref, *refs, keep_ref=keep_ref,
                       **static)


def _select_bwd_kernel(q_ref, kv_ref, kr_ref, keep_ref, *refs, **static):
    _latent_bwd_kernel(q_ref, kv_ref, kr_ref, *refs, keep_ref=keep_ref,
                       **static)


def _latent_name(which, dtype, block_q, block_k, edge, select=False):
    return "flash2%s_%s_%s_q%d_k%d%s" % (
        "sel" if select else "", which, operand_label(dtype), block_q,
        block_k, "_e%d" % edge if edge else "")


def _count_trace(which, edge, select=False):
    _M_LATENT_TRACES.inc(**{"pass": which},
                         **({"edge": edge} if edge else {}),
                         **({"select": 1} if select else {}))


def _k_tile(block_q, block_k, steps=0):
    """(q tile, inner step) -> the k tile that step reads; with ``steps``
    the inner steps walk a row's k tiles downwards. A dead step names
    the row's last live tile, already resident."""
    def k_tile(i, j):
        if steps:
            j = lax.sub(np.int32(steps - 1), j)
        return lax.min(j, flash.last_live_k(i, block_q, block_k))
    return k_tile


def _latent_specs(block_q, block_k, width, kv_width, rope, dv, steps=0):
    """Block specs of (q and dq, kv, k_rope, o and dO, lse) at
    grid step (batch, head, q tile, k step); ``steps`` as ``_k_tile``
    takes it."""
    k_tile = _k_tile(block_q, block_k, steps)

    def by_row(width):
        return pl.BlockSpec((None, block_q, width),
                            lambda b, h, i, j: (b, i, h))

    return (by_row(width),
            pl.BlockSpec((None, block_k, kv_width),
                         lambda b, h, i, j: (b, k_tile(i, j), h)),
            pl.BlockSpec((None, block_k, rope),
                         lambda b, h, i, j: (b, k_tile(i, j), 0)),
            by_row(dv),
            pl.BlockSpec((None, None, block_q, 1),
                         lambda b, h, i, j: (b, h, i, 0)))


def _keep_specs(keep, block_q, block_k, steps=0):
    """[the block spec of the keep-mask's tile of a pair, every head's]
    for a selected call, [] for one that selects nothing."""
    if keep is None:
        return []
    k_tile = _k_tile(block_q, block_k, steps)
    return [pl.BlockSpec((None, block_q, block_k),
                         lambda b, h, i, j: (b, i, k_tile(i, j)))]


_LATENT_STATIC = ("heads", "nope", "t_real", "scale", "block_q", "block_k",
                  "edge", "interpret")


def _keep_vmem_bytes(block_q, block_k):
    """What a selected call holds in VMEM on top of
    ``flash.flash_vmem_bytes``: the keep-mask's two int8 tile buffers and
    the tile widened to 32 bits."""
    return 6 * block_q * block_k


@functools.partial(jax.jit, static_argnames=_LATENT_STATIC)
def latent_fwd_call(q, kv, kr, keep=None, *, heads, nope, t_real, scale,
                    block_q, block_k, edge, interpret):
    """q [B, T, H (N + Rp)], kv [B, T, H (N + Dv)], kr [B, T, Rp] and,
    for the selected variant, keep [B, T, T] int8 -> o [B, T, H Dv] and
    lse [B, H, T, 1] float32."""
    select = keep is not None
    _count_trace("fwd", edge, select)
    b, t_pad, _ = q.shape
    width, kv_width, rope = (x.shape[2] // n for x, n in (
        (q, heads), (kv, heads), (kr, 1)))
    dv = kv_width - nope
    q_spec, kv_spec, kr_spec, o_spec, row_spec = _latent_specs(
        block_q, block_k, width, kv_width, rope, dv)
    with no_x64():
        return pl.pallas_call(
            functools.partial(
                _select_fwd_kernel if select else _latent_fwd_kernel,
                nope=nope, block_q=block_q,
                block_k=block_k, t_real=t_real, t_pad=t_pad, scale=scale,
                edge=edge),
            grid=(b, heads, t_pad // block_q, t_pad // block_k),
            in_specs=[q_spec, kv_spec, kr_spec] + _keep_specs(
                keep, block_q, block_k),
            out_specs=[o_spec, row_spec],
            out_shape=[
                jax.ShapeDtypeStruct((b, t_pad, heads * dv), q.dtype),
                jax.ShapeDtypeStruct((b, heads, t_pad, 1), jnp.float32)],
            scratch_shapes=[
                pltpu.VMEM((block_q, dv), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32)],
            compiler_params=pltpu.CompilerParams(dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary"), **(
                    {"vmem_limit_bytes": flash.flash_vmem_bytes(
                        block_q, block_k, max(width, dv), q.dtype.itemsize)
                        + _keep_vmem_bytes(block_q, block_k)}
                    if select else {})),
            name=_latent_name("fwd", q.dtype, block_q, block_k, edge,
                              select),
            interpret=interpret,
        )(q, kv, kr, *([keep] if select else []))


@functools.partial(jax.jit, static_argnames=_LATENT_STATIC)
def latent_bwd_call(q, kv, kr, out, do, lse, keep=None, *, heads, nope,
                    t_real, scale, block_q, block_k, edge, interpret):
    """-> dq, dkv and dkr, shaped and typed as q, kv and kr."""
    select = keep is not None
    _count_trace("bwd", edge, select)
    b, t_pad, _ = q.shape
    width, kv_width, rope = (x.shape[2] // n for x, n in (
        (q, heads), (kv, heads), (kr, 1)))
    dv = kv_width - nope
    nk = t_pad // block_k
    q_spec, kv_spec, kr_spec, o_spec, row_spec = _latent_specs(
        block_q, block_k, width, kv_width, rope, dv, steps=nk)
    with no_x64():
        dq, dkv, dkr = pl.pallas_call(
            functools.partial(
                _select_bwd_kernel if select else _latent_bwd_kernel,
                nope=nope, block_q=block_q,
                block_k=block_k, t_real=t_real, t_pad=t_pad, scale=scale,
                edge=edge),
            grid=(b, heads, t_pad // block_q, nk),
            in_specs=[q_spec, kv_spec, kr_spec] + _keep_specs(
                keep, block_q, block_k, steps=nk) + [o_spec, o_spec,
                                                    row_spec],
            out_specs=[
                q_spec,
                pl.BlockSpec((None, nk, block_k, kv_width),
                             lambda b_, h, i, j: (b_, 0, 0, h)),
                pl.BlockSpec((None, nk, block_k, rope),
                             lambda b_, h, i, j: (b_, 0, 0, 0))],
            out_shape=[
                jax.ShapeDtypeStruct(q.shape, q.dtype),
                jax.ShapeDtypeStruct((b, nk, block_k, heads * kv_width),
                                     kv.dtype),
                jax.ShapeDtypeStruct((b, nk, block_k, rope), kr.dtype)],
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, width), jnp.float32),
                pltpu.VMEM((nk, block_k, nope), jnp.float32),
                pltpu.VMEM((nk, block_k, dv), jnp.float32),
                pltpu.VMEM((nk, block_k, rope), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                # dK_rope accumulates over the heads, dK_nope and dV over
                # a head's q tiles
                dimension_semantics=("parallel", "arbitrary", "arbitrary",
                                     "arbitrary"),
                vmem_limit_bytes=flash.flash_vmem_bytes(
                    block_q, block_k, max(width, dv), q.dtype.itemsize,
                    resident=(t_pad, width, dv))
                + (_keep_vmem_bytes(block_q, block_k) if select else 0)),
            name=_latent_name("bwd", q.dtype, block_q, block_k, edge,
                              select),
            interpret=interpret,
        )(q, kv, kr, *([keep] if select else []), out, do, lse)
    return dq, dkv.reshape(kv.shape), dkr.reshape(kr.shape)


def latent_composed(q, kv, kr, heads, nope, scale, keep=None):
    """``flash.reference_attention`` over the concatenated key on the kernels'
    operands (``kept_attention`` under a keep-mask), o as they give it:
    the branch for every platform but the TPU, and what the kernels'
    tests hold them to."""
    b, t, _ = q.shape
    kv = kv.reshape(b, t, heads, -1)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(kr[:, :, None, :], (b, t, heads, kr.shape[2]))],
        axis=-1)
    q = q.reshape(b, t, heads, -1)
    if keep is None:
        out = flash.reference_attention(q, k, kv[..., nope:], causal=True,
                                        scale=scale)
    else:
        out = flash.kept_attention(q, k, kv[..., nope:], keep, scale)
    return out.reshape(b, t, -1)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11))
def _latent(q, kv, kr, keep, heads, nope, t_real, scale, block_q, block_k,
            edge, interpret):
    return _latent_fwd(q, kv, kr, keep, heads, nope, t_real, scale, block_q,
                       block_k, edge, interpret)[0]


def _latent_fwd(q, kv, kr, keep, heads, nope, t_real, scale, block_q,
                block_k, edge, interpret):
    # one trace of the forward for the primal and the rule: see _ssd_fwd
    with jax.sharding.use_abstract_mesh(jax.sharding.get_abstract_mesh()):
        out, lse = latent_forward(
            q, kv, kr, keep, heads=heads, nope=nope, t_real=t_real,
            scale=scale, block_q=block_q, block_k=block_k, edge=edge,
            interpret=interpret)
    return out, (q, kv, kr, out, lse, keep)


@functools.partial(jax.jit, static_argnames=_LATENT_STATIC)
def latent_forward(q, kv, kr, keep=None, *, interpret, **call):
    """o and lse (zeros off the TPU, where the composed form's own
    transpose is the backward); ``keep`` [B, T, T] int8 or None (a pair
    that selects no keys: today's kernels under today's names)."""
    def kernels(q, kv, kr, keep, interpret):
        return latent_fwd_call(q, kv, kr, keep, interpret=interpret, **call)

    def plain(q, kv, kr, keep):
        return (latent_composed(q, kv, kr, call["heads"], call["nope"],
                                call["scale"], keep),
                jnp.zeros((q.shape[0], call["heads"], q.shape[1], 1),
                          jnp.float32))

    return on_tpu(kernels, plain, interpret, q, kv, kr, keep)


@functools.partial(jax.jit, static_argnames=_LATENT_STATIC)
def latent_backward(q, kv, kr, out, lse, keep, g, *, interpret, **call):
    heads = call["heads"]

    def kernels(q, kv, kr, out, lse, keep, g, interpret):
        return latent_bwd_call(q, kv, kr, out, g.astype(q.dtype), lse, keep,
                               interpret=interpret, **call)

    def plain(q, kv, kr, out, lse, keep, g):
        return jax.vjp(
            lambda *ins: latent_composed(*ins, heads, call["nope"],
                                         call["scale"], keep),
            q, kv, kr)[1](g)

    return on_tpu(kernels, plain, interpret, q, kv, kr, out, lse, keep, g)


def _latent_bwd(heads, nope, t_real, scale, block_q, block_k, edge,
                interpret, res, g):
    # the keep-mask is data, not a weight: no cotangent
    return latent_backward(
        *res, g, heads=heads, nope=nope, t_real=t_real, scale=scale,
        block_q=block_q, block_k=block_k, edge=edge,
        interpret=interpret) + (None,)


_latent.defvjp(_latent_fwd, _latent_bwd)


def latent_flash(q, kv, k_rope, heads, nope, scale, block_q=None,
                 block_k=None, interpret=False, keep=None):
    """Causal latent attention as a Pallas kernel pair, for the shapes
    ``latent_flash_takes`` admits. Every operand token-major, a head a
    block of columns: q [B, T, H (N + Rp)] (a head's N un-rotated
    dimensions, its R rotated ones, zeros to a whole lane row), kv [B, T,
    H (N + Dv)] (the up-projection's output: a head's N key columns, then
    its Dv value columns), k_rope [B, T, Rp] (the one rotated key a
    token, every head's, padded as q's) -> [B, T, H Dv]; differentiable
    in all three. ``scale`` multiplies the scores (``1 / sqrt(N + R)``:
    the padded widths do not say R). ``block_q`` / ``block_k`` default to
    ``flash.flash_tiles(T, max(N + Rp, Dv), dtype)`` (pass them only to pin a
    tiling: tests, benchmarks). T is padded to whole tiles (a copy; a
    sequence of whole tiles is read in place). Mosaic where the
    computation is lowered for the TPU and ``flash.reference_attention`` over
    the concatenated key on every other platform, the choice made inside
    the ``custom_vjp`` as ``ssd_scan`` makes it; ``interpret=True`` (the
    kernels' tests) runs the kernels through the Pallas interpreter
    wherever the computation is lowered. No partitioning rule: inside a
    sharded ``jit``, call under ``shard_map``.

    ``keep`` [B, T, T] (any integer or bool type; read as int8, 0 drops
    the pair): attention that chooses its keys. Row t's softmax runs over
    the keys s <= t with ``keep[b, t, s] != 0`` only; the mask is every
    head's, carries no gradient, and is one more tile operand of a pair
    named ``flash2sel_*`` (every live tile masked, none by quarters: a
    selection of half a row's keys leaves no tile to skip). Without it
    the call lowers to the ``flash2_*`` pair as it always did."""
    t = q.shape[1]
    if block_q is None or block_k is None:
        auto_q, auto_k = flash.flash_tiles(
            t, max(q.shape[2] // heads, kv.shape[2] // heads - nope),
            q.dtype)
        block_q, block_k = block_q or auto_q, block_k or auto_k
    mult = int(np.lcm(block_q, block_k))
    q, kv, k_rope = (pad_to(x, 1, mult)[0] for x in (q, kv, k_rope))
    edge = flash.cut_half(int(block_q), int(block_k), True)
    if keep is not None:
        keep = pad_to(pad_to(keep.astype(jnp.int8), 1, mult)[0], 2, mult)[0]
        edge = 0
    out = _latent(q, kv, k_rope, keep, int(heads), int(nope), t,
                  float(scale), int(block_q), int(block_k), edge,
                  bool(interpret))
    return out[:, :t]


# ---------------------------------------------------------------------------
# the pass over the query round the latent pair: a head's R rotary lanes
# rotated by their positions and padded to a lane row, [B, T, H (N + R)] ->
# [B, T, H (N + Rp)], and its transpose on the cotangent. One read and one
# write of the query each way; the ``jax.numpy`` form (``ops/transformer.py
# ::_query_pass``) made XLA choose a token-minor layout for the 64-lane
# slices and pay four passes and a transposing copy for it (PERF.md
# section 6, PR 44).
#
# A head of N + R lanes does not start on a lane row, so a grid step takes
# the fewest heads whose lanes do (2 of 192: 384 in, 512 out) and moves
# each by whole lane rows, one lane rotation (``pltpu.roll``) and selects
# on the lane index: no slice or store that is not lane-aligned. The
# rotation's partner lane is a rotation by one (interleaved pairs) or by
# R / 2 (``rotate_half``) and a select; cos and sin come as [T, Rp] float32
# tables, zero behind the R lanes, the arithmetic float32.
# ---------------------------------------------------------------------------

def _query_heads_a_step(width):
    """The fewest heads of ``width`` lanes that fill whole lane rows."""
    return LANES // int(np.gcd(width, LANES))


def latent_query_takes(t, heads, nope, rope):
    """Whether ``latent_query`` has blocks for the shapes: N whole lane
    rows, R a divisor of a lane row (so that a head's offset in its lane
    row leaves the zero lanes behind R room to turn into), whole steps of
    heads and of 8 tokens at least."""
    return bool(nope % LANES == 0 and 0 < rope <= LANES
                and LANES % rope == 0 and rope % 2 == 0
                and heads % _query_heads_a_step(nope + rope) == 0
                and t % 8 == 0)


def _latent_query_kernel(x_ref, cos_ref, sin_ref, o_ref, *, per, nope, rope,
                         interleave, inverse):
    f32 = jnp.float32
    lanes = LANES
    rope_p = whole_lanes(rope)
    width, width_p = nope + rope, nope + rope_p
    rows = x_ref.shape[0]
    lane = lax.broadcasted_iota(jnp.int32, (rows, rope_p), 1)
    if interleave:      # (2i, 2i + 1): the partner one lane up or down
        first = lax.eq(lax.rem(lane, np.int32(2)), np.int32(0))
        reach = 1
    else:               # (i, i + R / 2)
        first = lax.lt(lane, np.int32(rope // 2))
        reach = rope // 2
    live = lax.lt(lane, np.int32(rope))
    cos = cos_ref[...]
    sin = lax.neg(sin_ref[...]) if inverse else sin_ref[...]

    def rotated(xr):    # [rows, Rp] float32, zero behind the R lanes
        other = jnp.where(first, lax.neg(pltpu.roll(xr, rope_p - reach, 1)),
                          pltpu.roll(xr, reach, 1))
        return jnp.where(live, lax.add(lax.mul(xr, cos),
                                       lax.mul(other, sin)), f32(0.0))

    x = x_ref[...].astype(f32)
    if not inverse:
        for k in range(per):
            at = k * width
            row0, shift = at // lanes * lanes, at % lanes
            head = x[:, row0:row0 + width_p]
            if shift:   # the head's first lane to the window's
                head = pltpu.roll(head, width_p - shift, 1)
            o_ref[:, k * width_p:k * width_p + nope] = head[:, :nope].astype(
                o_ref.dtype)
            o_ref[:, k * width_p + nope:(k + 1) * width_p] = rotated(
                head[:, nope:]).astype(o_ref.dtype)
        return
    out = [None] * (per * width // lanes)
    for k in range(per):
        g = x[:, k * width_p:(k + 1) * width_p]
        head = lax.concatenate([g[:, :nope], rotated(g[:, nope:])], 1)
        at = k * width
        row0, shift = at // lanes, at % lanes
        if shift:       # zeros behind the R lanes turn into the front
            head = pltpu.roll(head, shift, 1)
        for j in range(width_p // lanes):
            part = head[:, j * lanes:(j + 1) * lanes]
            if row0 + j < len(out):
                out[row0 + j] = (part if out[row0 + j] is None
                                 else lax.add(out[row0 + j], part))
    for j, part in enumerate(out):
        o_ref[:, j * lanes:(j + 1) * lanes] = part.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "heads", "nope", "rope", "theta", "interleave", "inverse", "interpret"))
def latent_query(x, *, heads, nope, rope, theta, interleave, inverse=False,
                 interpret=False):
    """x [B, T, H (N + R)] -> [B, T, H (N + Rp)]: each head's R last lanes
    rotated by their positions (pairs (2i, 2i + 1) under ``interleave``,
    (i, i + R / 2) without; pair i by ``pos theta^(-2i/R)``, float32) and
    zeros behind them to a whole lane row, Rp; under ``inverse`` the
    transpose, [B, T, H (N + Rp)] -> [B, T, H (N + R)]: what the
    cotangent takes back. For the shapes ``latent_query_takes`` admits."""
    b, t, _ = x.shape
    rope_p = whole_lanes(rope)
    per = _query_heads_a_step(nope + rope)
    wide, narrow = per * (nope + rope_p), per * (nope + rope)
    angles = (np.arange(t, dtype=np.float64)[:, None]
              * rope_inv_freq(theta, rope)[None, :])
    angles = (np.repeat(angles, 2, axis=-1) if interleave
              else np.concatenate([angles, angles], axis=-1))
    cos, sin = (jnp.asarray(np.pad(f(angles), ((0, 0), (0, rope_p - rope))),
                            jnp.float32) for f in (np.cos, np.sin))
    block_t = next(blk for blk in (512, 256, 128, 64, 32, 16, 8)
                   if t % blk == 0)
    table = pl.BlockSpec((block_t, rope_p), lambda b_, i, h: (i, 0))
    w_in, w_out = (wide, narrow) if inverse else (narrow, wide)
    with no_x64():
        return pl.pallas_call(
            functools.partial(
                _latent_query_kernel, per=per, nope=nope, rope=rope,
                interleave=interleave, inverse=inverse),
            grid=(b, t // block_t, heads // per),
            in_specs=[pl.BlockSpec((None, block_t, w_in),
                                   lambda b_, i, h: (b_, i, h)),
                      table, table],
            out_specs=pl.BlockSpec((None, block_t, w_out),
                                   lambda b_, i, h: (b_, i, h)),
            out_shape=jax.ShapeDtypeStruct(
                (b, t, heads // per * w_out), x.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel")),
            name="latent_query_%s_%s" % ("bwd" if inverse else "fwd",
                                         operand_label(x.dtype)),
            interpret=interpret,
        )(x, cos, sin)
