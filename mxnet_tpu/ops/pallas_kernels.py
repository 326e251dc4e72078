"""Pallas TPU kernels for the hot ops.

The reference reaches for hand-written CUDA / cuDNN where the stock ops
are too slow (SURVEY.md §2 N6 cudnn_*-inl.h, N18 mshadow). The TPU-native
equivalent is Pallas: kernels that XLA cannot produce from jnp alone
because they need explicit on-chip (VMEM) accumulation patterns. The
flagship here is flash attention — blockwise online-softmax attention
whose VMEM working set is O(block²+block·D) per grid step (the K/V axis
is walked by the innermost grid dimension, not loaded whole), forward and
backward both as MXU-tiled kernels.

Mosaic or interpreter is decided per call site by the platform the
enclosing computation is LOWERED for (``_by_platform``), never by the
process default backend: a step placed on a TPU device compiles the
Mosaic kernels, the same step placed on a host device runs them through
the Pallas interpreter (tests). The pure-jnp references
(``reference_attention``, ``slab_update_reference``) are the oracles.

Layout convention matches ``parallel/ring_attention``: [B, T, H, D].
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import telemetry as _tm

_NEG_INF = -1e30


def _by_platform(call, *args):
    """Run ``call(*args, interpret=...)`` as a Mosaic kernel where the
    enclosing computation is lowered for TPU and through the Pallas
    interpreter on every other platform. The choice is made at lowering
    from the platform of the device the step runs on
    (``lax.platform_dependent``), so only the matching branch reaches
    the compiler."""
    return jax.lax.platform_dependent(
        *args,
        tpu=functools.partial(call, interpret=False),
        default=functools.partial(call, interpret=True))


def _no_x64():
    """Context manager forcing 32-bit tracing: the framework enables
    jax_enable_x64 globally (reference float64 NDArray parity) but
    Mosaic kernels must stay 32-bit."""
    return jax.enable_x64(False)


def fused_update_enabled(platform):
    """Whether the fused optimizer-slab kernel replaces the jnp update
    chain on a mesh of ``platform`` devices. ``MXTPU_FUSED_UPDATE_KERNEL``:
    "1" forces it on everywhere (interpret mode off-TPU — the parity
    tests), "0" forces the jnp reference, unset enables it on TPU only."""
    v = os.environ.get("MXTPU_FUSED_UPDATE_KERNEL", "")
    if v == "0":
        return False
    if v == "1":
        return True
    return platform == "tpu"


def _pad_to(x, axis, mult):
    size = x.shape[axis]
    rem = size % mult
    if rem == 0:
        return x, size
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, mult - rem)
    return jnp.pad(x, pad), size


# ---------------------------------------------------------------------------
# flash attention. What a (q tile, k tile) pair costs is decided in three
# places, all below:
#
#   operands  the blocks go to the MXU in the type they arrive in (bf16
#             stays bf16, one pass; float32 stays float32) and every
#             product accumulates in float32. Scores, mask, running max,
#             exp, row sums, lse, delta and the accumulators are float32;
#             p and dS are rounded to the operand type before the four
#             products that consume them — the rounding the kernel's own
#             output takes anyway.
#   tiles     ``flash_tiles`` picks (block_q, block_k) from (T, D, dtype)
#             and a VMEM budget: a grid step costs ~0.3 us whatever it
#             holds, so a 128 x 128 tile (0.04 us of bf16 work) is all
#             overhead — so much so that the operand type changes
#             nothing there (PERF.md section 6, PR 28).
#   causal    a dead tile (above the diagonal) is skipped by ``pl.when``
#             AND its index map names the tile already resident, so no
#             DMA is issued for it; the iota/compare/select mask runs only
#             on tiles the diagonal crosses or that hold padding.
#   window    a causal window of w keys (query i sees keys i-w+1 .. i)
#             shortens the grid's inner dimension to the tiles a band
#             can touch (``_band_steps``): inner step j visits block
#             ``first live + j``, so tiles below the band are neither
#             fetched nor stepped over; the band's lower edge is one more
#             compare in the mask.
#   heads     G key/value heads serve H = G * group query heads: q head
#             b reads k/v head b // group through the index maps; dkv's
#             inner dimension walks the group's q heads one after
#             another and sums their dK/dV in the one accumulator. The
#             value width may differ from the query/key width.
#   backward  one pass (``_bwd_fused_kernel``: P and dS built once a tile
#             pair, five products) where dK and dV of a whole key/value
#             head fit VMEM beside a step's tiles (``_bwd_fuses``: shapes
#             and operand type alone decide); dq and dkv (seven products,
#             P and dS twice) for the sequences too long for that.
#   sink      a per-head logit that joins the softmax's denominator and
#             no value: applied to the forward kernel's (out, lse) by
#             ``_apply_sink`` outside it; the backward kernels rebuild P
#             from the lse that holds it and need nothing else.
#
# forward / dq / the one-pass backward: grid (B*H, nq, nk), k innermost;
# dkv: grid (B*G, nk, group * nq).
# The output block index map ignores the innermost dimension, so Mosaic
# keeps the output resident in VMEM while the inner loop accumulates into
# scratch; one (block_q, block_k) tile pair is on-chip at a time. The
# one-pass backward's dK / dV blocks ignore the q tile too (and the q
# head within its group): they stay for the whole key/value head.
# dS = P * (dP - delta), P = exp(S - L), dP = dO V^T,
# delta_i = sum_d dO_id * O_id.
# ---------------------------------------------------------------------------

_M_FLASH_LOWERINGS = _tm.counter(
    "attention.flash_lowerings", "Traces of a flash_attention call site "
    "(one per lowering, nothing per step); labels: operands (the type "
    "the MXU is fed), block_q, block_k and, where the call has them, "
    "window, kv_heads (fewer than the query heads), dv (a value width "
    "other than the query's). Where a site's backward is traced it "
    "counts once more under operands, block_q, block_k, window and bwd: "
    "fused (dq, dk and dv in one pass) or split (dq and dkv)")

# What one grid step of forward, dq and dkv may hold in VMEM as
# ``_flash_vmem_bytes`` counts it: Mosaic's default scoped limit on the
# v5e. Those calls ask for no more (no ``vmem_limit_bytes``), and the
# tiles are chosen under it. The one-pass backward keeps dK and dV of a
# whole key/value head beside such a step's tiles, so it states its count
# as its own limit (44 MiB at T 8,192 and widths 192 / 128 in bf16), and
# is taken only where that count stays under ``_FLASH_BWD_VMEM_LIMIT``.
_FLASH_VMEM_BUDGET = 16 * 1024 * 1024
# Three eighths of the v5e's 128 MiB of VMEM (a limit is scoped to its
# call: what XLA places there round the call is not displaced). T 16,384
# at 192 / 128 counts 68 MiB and T 32,768 116: those keep dq and dkv.
_FLASH_BWD_VMEM_LIMIT = 48 * 1024 * 1024
# Largest tiles worth taking, by measurement on the v5e (T 2048-8192,
# D 64-256, bf16 and float32, causal and not: 1024 x 1024 is the fastest
# or within 1% of it everywhere, 2048 is slower again; PERF.md section 7).
_FLASH_MAX_BLOCK_Q = 1024
_FLASH_MAX_BLOCK_K = 1024
_FLASH_MIN_BLOCK = 128


def _lanes(width):
    return -(-width // 128) * 128


def _flash_vmem_bytes(block_q, block_k, d, itemsize, resident=None):
    """Upper bound on the VMEM one grid step of the widest kernel holds:
    double-buffered operand and result tiles, the float32 accumulators
    and two float32 score-shaped temporaries, as dkv has them; with
    ``resident`` = (t_pad, d, dv), plus what the one-pass backward keeps
    for a whole key/value head: dK and dV in float32 scratch and their
    double-buffered output blocks, and a third score-shaped temporary.
    Against the smallest limit Mosaic compiles each shape under (v5e,
    1-2 MiB steps), counted / needed:

    ====================================================  =======  ======
    dkv, 1024 x 1024, D 128, bf16                          12 MiB   10
    dkv, the same in float32                                15       12
    dkv, D 256, float32                                     22       16
    dkv, 2048 x 2048                                        40       40
    one pass, T 8192, 192 / 128, bf16 (Kanana)              44       39
    one pass, T 4096, 128 / 128, bf16 (OLMoE)               24       18
    one pass, T 4096, 192 / 128, bf16, 8 heads on 1 (MiMo)  32       18
    one pass, the same, 256 x 256 under a window of 128     14.75    9
    one pass, T 4096, 128 / 128, float32                    31       27
    ====================================================  =======  ======
    """
    lanes = _lanes(d)
    row_tiles = 2 * 2 * block_q * lanes * itemsize      # q, dO
    col_tiles = 2 * 4 * block_k * lanes * itemsize      # k, v, dk, dv
    acc = 2 * block_k * lanes * 4
    scores = 2 * block_q * block_k * 4
    step = row_tiles + col_tiles + acc + scores
    if resident is None:
        return step
    t_pad, d, dv = resident
    return (step + block_q * block_k * 4    # P and dS both outlive dP
            + t_pad * (_lanes(d) + _lanes(dv)) * (4 + 2 * itemsize))


def _bwd_fuses(t_pad, block_q, block_k, d, dv, dtype):
    """Whether the backward of a call runs as one pass: decided by the
    call's shapes and operand type alone, through what the pass would
    hold in VMEM."""
    return _flash_vmem_bytes(
        block_q, block_k, max(d, dv), jnp.dtype(dtype).itemsize,
        resident=(t_pad, d, dv)) <= _FLASH_BWD_VMEM_LIMIT


def _one_tile(t):
    """The tile that holds a whole short sequence: the next power of
    two, 8 at the least."""
    return max(8, 1 << (t - 1).bit_length())


def flash_tiles(t, d, dtype, window=0):
    """(block_q, block_k) for a sequence of ``t`` positions, head size
    ``d`` (the wider of query and value), operands of ``dtype``: the
    largest powers of two up to the measured caps whose working set fits
    ``_FLASH_VMEM_BUDGET`` and that pad ``t`` by no more than an eighth
    over what 128-wide tiles would. A sequence shorter than the smallest
    tile gets one tile of its own size (the next power of two, 8 at the
    least). Under a causal ``window`` the band of a q tile of B rows
    crosses two k tiles of B >= window keys, B * window of their 2 B^2
    scores live: square tiles of twice the window, where the products
    of a step weigh about what the step itself costs."""
    if t < _FLASH_MIN_BLOCK:
        return _one_tile(t), _one_tile(t)
    if window:
        block = min(max(2 * _one_tile(window), _FLASH_MIN_BLOCK),
                    _FLASH_MAX_BLOCK_Q, _one_tile(t))
        return block, block
    itemsize = jnp.dtype(dtype).itemsize
    t_min = -(-t // _FLASH_MIN_BLOCK) * _FLASH_MIN_BLOCK

    def pads_little(blk):
        return -(-t // blk) * blk * 8 <= t_min * 9

    block_q = block_k = _FLASH_MIN_BLOCK
    # k first: a wider k tile amortises the per-step cost without
    # lengthening the accumulators
    while (block_k * 2 <= _FLASH_MAX_BLOCK_K and pads_little(block_k * 2)
           and _flash_vmem_bytes(block_q, block_k * 2, d, itemsize)
           <= _FLASH_VMEM_BUDGET):
        block_k *= 2
    while (block_q * 2 <= _FLASH_MAX_BLOCK_Q and pads_little(block_q * 2)
           and _flash_vmem_bytes(block_q * 2, block_k, d, itemsize)
           <= _FLASH_VMEM_BUDGET):
        block_q *= 2
    return block_q, block_k


# Tile-index arithmetic goes through ``jax.lax`` directly: every jnp
# call or operator on a tracer is a nested jit to trace, a millisecond
# or two apiece inside a deep training step, and these run once per
# index map per kernel per layer per platform branch.

def _affine(i, mult, plus=0):
    """i * mult + plus on an int32 index."""
    out = jax.lax.mul(i, np.int32(mult))
    return jax.lax.add(out, np.int32(plus)) if plus else out


def _causal_block_live(qi, ki, block_q, block_k):
    """Whether k block ki intersects the causal triangle of q block qi."""
    return jax.lax.le(_affine(ki, block_k),
                      _affine(qi, block_q, block_q - 1))


def _last_live_k(qi, block_q, block_k):
    """The last k block that ``_causal_block_live`` admits for q block
    qi: what the k/v index maps of forward and dq clamp to."""
    return jax.lax.div(_affine(qi, block_q, block_q - 1),
                       np.int32(block_k))


def _first_live_q(ki, block_q, block_k):
    """The first q block that ``_causal_block_live`` admits for k block
    ki: what the q/dO/lse/delta index maps of dkv clamp to."""
    return jax.lax.div(_affine(ki, block_k), np.int32(block_q))


def _first_live_k(qi, block_q, block_k, window):
    """The first k block that holds a key inside the window of q block
    qi's first row."""
    return jax.lax.div(
        jax.lax.max(_affine(qi, block_q, 1 - window), np.int32(0)),
        np.int32(block_k))


def _last_live_q(ki, block_q, block_k, window, nq):
    """The last q block that holds a row whose window reaches k block
    ki's last key."""
    return jax.lax.min(
        jax.lax.div(_affine(ki, block_k, block_k + window - 2),
                    np.int32(block_q)),
        np.int32(nq - 1))


def _band_steps(nq, nk, block_q, block_k, window, inner):
    """Extent of the grid's inner dimension under a window: the most
    inner blocks the band of any one outer block touches."""
    if inner == "k":
        return max((i * block_q + block_q - 1) // block_k
                   - max(i * block_q + 1 - window, 0) // block_k + 1
                   for i in range(nq))
    return max(min((i * block_k + block_k + window - 2) // block_q, nq - 1)
               - (i * block_k) // block_q + 1 for i in range(nk))


def _inner_k(qi, j, *, block_q, block_k, window):
    """The k block that inner step j of q block qi visits (forward,
    dq): j itself, or the j-th of the band."""
    if not window:
        return j
    return jax.lax.add(_first_live_k(qi, block_q, block_k, window), j)


def _inner_q(ki, j, *, block_q, block_k, window, steps, group):
    """(q head within the group, q block) that inner step j of k block
    ki visits (dkv): the group's heads one after another, ``steps``
    blocks each."""
    head = None
    if group > 1:
        head = jax.lax.div(j, np.int32(steps))
        j = jax.lax.rem(j, np.int32(steps))
    if window:
        j = jax.lax.add(_first_live_q(ki, block_q, block_k), j)
    return head, j


def _masked_scores(q, k_blk, qi, ki, *, block_q, block_k, t_real, scale,
                   causal, window=0, masked=True):
    """The shared score/mask invariant of all three kernels:
    s = scale·q@kᵀ on the MXU plus the (padding, causal, window)
    keep-mask for this (qi, ki) block pair — None for a tile
    ``_tile_cases`` found to need none. Kept in ONE place so forward and
    backward can never disagree on masking."""
    s = jnp.float32(scale) * jax.lax.dot_general(
        q, k_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [bq, bk]
    if not masked:
        return s, None
    q_pos = qi * jnp.int32(block_q) + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_pos = ki * jnp.int32(block_k) + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    mask = k_pos < jnp.int32(t_real)
    if causal:
        mask = mask & (q_pos >= k_pos)
    if window:
        mask = mask & (q_pos - k_pos < jnp.int32(window))
    return s, mask


def _tile_cases(body, qi, ki, *, block_q, block_k, t_real, t_pad, causal,
                window=0):
    """Run ``body(masked)`` for this tile pair at what it holds: not at
    all for a dead tile, with the mask where the diagonal or the
    window's edge crosses it or it holds padding keys, without it
    everywhere else."""
    live = needs_mask = None  # None: statically "always" / "never"
    if causal:
        live = _causal_block_live(qi, ki, block_q, block_k)
        needs_mask = jax.lax.gt(_affine(ki, block_k, block_k - 1),
                                _affine(qi, block_q))
    if window:
        # the band's inner steps start at its first block, so a dead
        # tile lies above the diagonal (forward, dq) or below the band
        # or past the last q block (dkv)
        live = jax.lax.bitwise_and(live, jax.lax.bitwise_and(
            jax.lax.ge(_affine(ki, block_k, block_k + window - 2),
                       _affine(qi, block_q)),
            jax.lax.lt(qi, np.int32(t_pad // block_q))))
        needs_mask = jax.lax.bitwise_or(needs_mask, jax.lax.ge(
            _affine(qi, block_q, block_q - 1),
            _affine(ki, block_k, window)))
    if t_real < t_pad:
        pads = jax.lax.gt(_affine(ki, block_k, block_k), np.int32(t_real))
        needs_mask = (pads if needs_mask is None
                      else jax.lax.bitwise_or(needs_mask, pads))
    if needs_mask is None:
        body(False)
        return
    unmasked = jax.lax.bitwise_not(needs_mask)
    if live is not None:
        needs_mask = jax.lax.bitwise_and(live, needs_mask)
        unmasked = jax.lax.bitwise_and(live, unmasked)
    pl.when(needs_mask)(lambda: body(True))
    pl.when(unmasked)(lambda: body(False))


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, l_ref, acc, m_s, l_s,
                *, block_q, block_k, t_real, t_pad, scale, causal, window):
    qi = pl.program_id(1)
    j = pl.program_id(2)
    ki = _inner_k(qi, j, block_q=block_q, block_k=block_k, window=window)

    @pl.when(j == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, jnp.float32(_NEG_INF))
        l_s[...] = jnp.zeros_like(l_s)

    def body(masked):
        v_blk = v_ref[0]  # [bk, Dv]
        s, mask = _masked_scores(
            q_ref[0], k_ref[0], qi, ki, block_q=block_q, block_k=block_k,
            t_real=t_real, scale=scale, causal=causal, window=window,
            masked=masked)
        if masked:
            s = jnp.where(mask, s, jnp.float32(_NEG_INF))
        m_prev = m_s[...]  # [bq, 1]
        m_cur = jax.lax.max(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jax.lax.exp(m_prev - m_cur)
        p = jax.lax.exp(s - m_cur)
        l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_s[...] = m_cur
        acc[...] = acc[...] * alpha + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _tile_cases(body, qi, ki, block_q=block_q, block_k=block_k,
                t_real=t_real, t_pad=t_pad, causal=causal, window=window)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        l_fin = l_s[...]
        safe_l = jnp.where(l_fin > 0, l_fin, jnp.float32(1.0))
        o_ref[0] = (acc[...] / safe_l).astype(o_ref.dtype)
        # logsumexp residual for backward
        l_ref[0] = m_s[...] + jnp.log(safe_l)


def _bwd_p_ds(q, k_blk, v_blk, do, lse, delta, qi, ki, masked, **tile):
    """P and dS of one tile pair, rounded to the operand type: what dq
    and dkv both rebuild from the residuals."""
    s, mask = _masked_scores(q, k_blk, qi, ki, masked=masked, **tile)
    p = jax.lax.exp(s - lse)
    if masked:
        p = jnp.where(mask, p, jnp.float32(0.0))
    dp = jax.lax.dot_general(
        do, v_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ds = p * (dp - delta)
    return p.astype(do.dtype), ds.astype(q.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, l_ref, d_ref, dq_ref,
                   dq_acc, *, block_q, block_k, t_real, t_pad, scale,
                   causal, window):
    qi = pl.program_id(1)
    j = pl.program_id(2)
    ki = _inner_k(qi, j, block_q=block_q, block_k=block_k, window=window)

    @pl.when(j == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def body(masked):
        k_blk = k_ref[0]
        _, ds = _bwd_p_ds(
            q_ref[0], k_blk, v_ref[0], do_ref[0], l_ref[0], d_ref[0],
            qi, ki, masked, block_q=block_q, block_k=block_k,
            t_real=t_real, scale=scale, causal=causal, window=window)
        dq_acc[...] = dq_acc[...] + jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _tile_cases(body, qi, ki, block_q=block_q, block_k=block_k,
                t_real=t_real, t_pad=t_pad, causal=causal, window=window)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        dq_ref[0] = (jnp.float32(scale) * dq_acc[...]).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, l_ref, d_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, block_q, block_k,
                    t_real, t_pad, scale, causal, window, steps, group):
    ki = pl.program_id(1)
    j = pl.program_id(2)
    _, qi = _inner_q(ki, j, block_q=block_q, block_k=block_k,
                     window=window, steps=steps, group=group)

    @pl.when(j == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def body(masked):
        q = q_ref[0]  # [bq, D]
        do = do_ref[0]
        p, ds = _bwd_p_ds(
            q, k_ref[0], v_ref[0], do, l_ref[0], d_ref[0], qi, ki,
            masked, block_q=block_q, block_k=block_k, t_real=t_real,
            scale=scale, causal=causal, window=window)
        dv_acc[...] = dv_acc[...] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bk, Dv]
        dk_acc[...] = dk_acc[...] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _tile_cases(body, qi, ki, block_q=block_q, block_k=block_k,
                t_real=t_real, t_pad=t_pad, causal=causal, window=window)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        dk_ref[0] = (jnp.float32(scale) * dk_acc[...]).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, l_ref, d_ref, dq_ref,
                      dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *, block_q,
                      block_k, t_real, t_pad, scale, causal, window, group):
    """dq, dk and dv in one pass: P and dS are built once a tile pair and
    feed all three products. The grid is dq's, (q head, q tile, k step):
    dq accumulates in tile-sized scratch over the inner steps; dk and dv
    accumulate in float32 scratch that holds the key/value head whole
    ([k tile, row, column]) over every q tile of every q head of its
    group, and are written out at the group's last step. The inner steps
    walk a row's k tiles from the diagonal down to the first, so a row's
    dead steps come first and its last step is a live one: the next
    row's q, dO, lse and delta arrive under a step that computes."""
    b = pl.program_id(0)
    qi = pl.program_id(1)
    j = pl.program_id(2)
    ki = _inner_k(qi, jax.lax.sub(pl.num_programs(2) - 1, j),
                  block_q=block_q, block_k=block_k, window=window)
    first = jax.lax.bitwise_and(jax.lax.eq(qi, np.int32(0)),
                                jax.lax.eq(j, np.int32(0)))
    last = jax.lax.bitwise_and(
        jax.lax.eq(qi, pl.num_programs(1) - 1),
        jax.lax.eq(j, pl.num_programs(2) - 1))
    if group > 1:
        head = jax.lax.rem(b, np.int32(group))
        first = jax.lax.bitwise_and(first, jax.lax.eq(head, np.int32(0)))
        last = jax.lax.bitwise_and(
            last, jax.lax.eq(head, np.int32(group - 1)))

    def each_k_tile(fn):
        def step(i, carry):
            fn(i)
            return carry
        jax.lax.fori_loop(0, dk_acc.shape[0], step, 0)

    @pl.when(first)
    def _():
        def zero(i):
            dk_acc[i] = jnp.zeros(dk_acc.shape[1:], jnp.float32)
            dv_acc[i] = jnp.zeros(dv_acc.shape[1:], jnp.float32)
        each_k_tile(zero)

    @pl.when(j == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def body(masked):
        q = q_ref[0]
        k_blk = k_ref[0]
        do = do_ref[0]
        p, ds = _bwd_p_ds(
            q, k_blk, v_ref[0], do, l_ref[0], d_ref[0], qi, ki, masked,
            block_q=block_q, block_k=block_k, t_real=t_real, scale=scale,
            causal=causal, window=window)
        dv_acc[ki] = dv_acc[ki] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bk, Dv]
        dk_acc[ki] = dk_acc[ki] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dq_acc[...] = dq_acc[...] + jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _tile_cases(body, qi, ki, block_q=block_q, block_k=block_k,
                t_real=t_real, t_pad=t_pad, causal=causal, window=window)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        dq_ref[0] = (jnp.float32(scale) * dq_acc[...]).astype(dq_ref.dtype)

    @pl.when(last)
    def _():
        def write(i):
            dk_ref[0, i] = (jnp.float32(scale) * dk_acc[i]).astype(
                dk_ref.dtype)
            dv_ref[0, i] = dv_acc[i].astype(dv_ref.dtype)
        each_k_tile(write)


# ---------------------------------------------------------------------------
# host-side wrappers
# ---------------------------------------------------------------------------

def _operand_label(dtype):
    return {"bfloat16": "bf16", "float16": "f16",
            "float32": "f32"}.get(jnp.dtype(dtype).name,
                                  jnp.dtype(dtype).name)


def _kernel_name(which, dtype, block_q, block_k, window=0):
    return "flash_%s_%s_q%d_k%d%s" % (
        which, _operand_label(dtype), block_q, block_k,
        "_w%d" % window if window else "")


_FLASH_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _tile_specs(block_q, block_k, d, dv, causal, inner, *, window=0,
                group=1, nq=0, steps=0, reverse=False):
    """Block specs of a q-shaped tile, a k-shaped tile, their
    value-width twins and a per-row statistic for a grid whose innermost
    dimension walks ``inner`` ("k": forward, dq and the one-pass
    backward, grid (bh, nq, nk), the last with ``reverse``: its ``steps``
    inner steps walk downwards; "q": dkv, grid (bg, nk, group * nq)).
    Under ``causal`` the streamed operand's index clamps to the row's
    (column's) live range: a dead step names the tile already resident
    and fetches nothing. The leading index is a q head for q-shaped
    tiles and the key/value head it reads for k-shaped ones."""
    tile = dict(block_q=block_q, block_k=block_k, window=window)
    if inner == "k":
        def q_idx(b, i, j):
            return (b, i, 0)

        def k_idx(b, i, j):
            if reverse:
                j = jax.lax.sub(np.int32(steps - 1), j)
            j = _inner_k(i, j, **tile)
            if causal:
                j = jax.lax.min(j, _last_live_k(i, block_q, block_k))
            if group > 1:
                b = jax.lax.div(b, np.int32(group))
            return (b, j, 0)
    else:
        def q_idx(b, i, j):
            head, j = _inner_q(i, j, steps=steps, group=group, **tile)
            if window:
                j = jax.lax.min(
                    j, _last_live_q(i, block_q, block_k, window, nq))
            elif causal:
                j = jax.lax.max(j, _first_live_q(i, block_q, block_k))
            if head is not None:
                b = jax.lax.add(_affine(b, group), head)
            return (b, j, 0)

        def k_idx(b, i, j):
            return (b, i, 0)
    return (pl.BlockSpec((1, block_q, d), q_idx),
            pl.BlockSpec((1, block_k, d), k_idx),
            pl.BlockSpec((1, block_q, dv), q_idx),
            pl.BlockSpec((1, block_k, dv), k_idx),
            pl.BlockSpec((1, block_q, 1), q_idx))


def _fwd_call(q3, k3, v3, *, t_real, scale, causal, window, block_q,
              block_k, interpret):
    bh, t_pad, d = q3.shape
    dv = v3.shape[2]
    group = bh // k3.shape[0]
    nq = t_pad // block_q
    nk = t_pad // block_k
    kern = functools.partial(
        _fwd_kernel, block_q=block_q, block_k=block_k, t_real=t_real,
        t_pad=t_pad, scale=scale, causal=causal, window=window,
    )
    q_spec, k_spec, o_spec, v_spec, row_spec = _tile_specs(
        block_q, block_k, d, dv, causal, "k", window=window, group=group)
    inner = (_band_steps(nq, nk, block_q, block_k, window, "k")
             if window else nk)
    with _no_x64():
        out, lse = pl.pallas_call(
            kern,
            grid=(bh, nq, inner),
            in_specs=[q_spec, k_spec, v_spec],
            out_specs=[o_spec, row_spec],
            out_shape=[
                jax.ShapeDtypeStruct((bh, t_pad, dv), q3.dtype),
                jax.ShapeDtypeStruct((bh, t_pad, 1), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, dv), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
            ],
            compiler_params=_FLASH_PARAMS,
            name=_kernel_name("fwd", q3.dtype, block_q, block_k, window),
            interpret=interpret,
        )(q3, k3, v3)
    return out, lse


def _bwd_fused_call(q3, k3, v3, do3, lse, delta, *, interpret, **tile):
    bh, t_pad, d = q3.shape
    bg, dv = k3.shape[0], v3.shape[2]
    group = bh // bg
    block_q, block_k = tile["block_q"], tile["block_k"]
    causal, window = tile["causal"], tile["window"]
    nq = t_pad // block_q
    nk = t_pad // block_k
    steps = (_band_steps(nq, nk, block_q, block_k, window, "k")
             if window else nk)
    q_spec, k_spec, o_spec, v_spec, row_spec = _tile_specs(
        block_q, block_k, d, dv, causal, "k", window=window, group=group,
        steps=steps, reverse=True)

    def whole_head(width):
        def idx(b, i, j):
            if group > 1:
                b = jax.lax.div(b, np.int32(group))
            return (b, 0, 0, 0)
        return pl.BlockSpec((1, nk, block_k, width), idx)

    dq, dk, dv_ = pl.pallas_call(
        functools.partial(_bwd_fused_kernel, group=group, **tile),
        grid=(bh, nq, steps),
        in_specs=[q_spec, k_spec, v_spec, o_spec, row_spec, row_spec],
        out_specs=[q_spec, whole_head(d), whole_head(dv)],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_pad, d), q3.dtype),
            jax.ShapeDtypeStruct((bg, nk, block_k, d), q3.dtype),
            jax.ShapeDtypeStruct((bg, nk, block_k, dv), q3.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((nk, block_k, d), jnp.float32),
            pltpu.VMEM((nk, block_k, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            # dk / dv accumulate over the q tiles, and over the q heads
            # of a group
            dimension_semantics=(
                "parallel" if group == 1 else "arbitrary", "arbitrary",
                "arbitrary"),
            vmem_limit_bytes=_flash_vmem_bytes(
                block_q, block_k, max(d, dv), q3.dtype.itemsize,
                resident=(t_pad, d, dv))),
        name=_kernel_name("bwd", q3.dtype, block_q, block_k, window),
        interpret=interpret,
    )(q3, k3, v3, do3, lse, delta)
    return dq, dk.reshape(bg, t_pad, d), dv_.reshape(bg, t_pad, dv)


def _bwd_call(q3, k3, v3, do3, lse, delta, *, t_real, scale, causal,
              window, block_q, block_k, interpret, fused=False):
    bh, t_pad, d = q3.shape
    bg, dv = k3.shape[0], v3.shape[2]
    group = bh // bg
    nq = t_pad // block_q
    nk = t_pad // block_k
    tile = dict(block_q=block_q, block_k=block_k, t_real=t_real,
                t_pad=t_pad, scale=scale, causal=causal, window=window)
    with _no_x64():
        if fused:
            return _bwd_fused_call(q3, k3, v3, do3, lse, delta,
                                   interpret=interpret, **tile)
        q_spec, k_spec, o_spec, v_spec, row_spec = _tile_specs(
            block_q, block_k, d, dv, causal, "k", window=window,
            group=group)
        inner = (_band_steps(nq, nk, block_q, block_k, window, "k")
                 if window else nk)
        dq = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, **tile),
            grid=(bh, nq, inner),
            in_specs=[q_spec, k_spec, v_spec, o_spec, row_spec, row_spec],
            out_specs=q_spec,
            out_shape=jax.ShapeDtypeStruct((bh, t_pad, d), q3.dtype),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
            compiler_params=_FLASH_PARAMS,
            name=_kernel_name("dq", q3.dtype, block_q, block_k, window),
            interpret=interpret,
        )(q3, k3, v3, do3, lse, delta)
        steps = (_band_steps(nq, nk, block_q, block_k, window, "q")
                 if window else nq)
        q_spec, k_spec, o_spec, v_spec, row_spec = _tile_specs(
            block_q, block_k, d, dv, causal, "q", window=window,
            group=group, nq=nq, steps=steps)
        dk, dv_ = pl.pallas_call(
            functools.partial(_bwd_dkv_kernel, steps=steps, group=group,
                              **tile),
            grid=(bg, nk, group * steps),
            in_specs=[q_spec, k_spec, v_spec, o_spec, row_spec, row_spec],
            out_specs=[k_spec, v_spec],
            out_shape=[
                jax.ShapeDtypeStruct((bg, t_pad, d), q3.dtype),
                jax.ShapeDtypeStruct((bg, t_pad, dv), q3.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, d), jnp.float32),
                pltpu.VMEM((block_k, dv), jnp.float32),
            ],
            compiler_params=_FLASH_PARAMS,
            name=_kernel_name("dkv", q3.dtype, block_q, block_k, window),
            interpret=interpret,
        )(q3, k3, v3, do3, lse, delta)
    return dq, dk, dv_


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9)
)
def _flash(q3, k3, v3, sink, t_real, scale, causal, window, block_q,
           block_k):
    out, _ = _flash_fwd(q3, k3, v3, sink, t_real, scale, causal, window,
                        block_q, block_k)
    return out


def _flash_fwd(q3, k3, v3, sink, t_real, scale, causal, window, block_q,
               block_k):
    out, lse = _by_platform(
        functools.partial(
            _fwd_call, t_real=t_real, scale=scale, causal=causal,
            window=window, block_q=block_q, block_k=block_k),
        q3, k3, v3)
    if sink is not None:
        with_sink = jnp.logaddexp(lse, sink[:, None, None])
        out = (out.astype(jnp.float32)
               * jnp.exp(lse - with_sink)).astype(out.dtype)
        lse = with_sink
    return out, (q3, k3, v3, sink, out, lse)


def _flash_bwd(t_real, scale, causal, window, block_q, block_k, res, g):
    q3, k3, v3, sink, out, lse = res
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1,
        keepdims=True,
    )  # [BH, T, 1]
    fused = _bwd_fuses(q3.shape[1], block_q, block_k, q3.shape[2],
                       v3.shape[2], q3.dtype)
    _M_FLASH_LOWERINGS.inc(
        operands=_operand_label(q3.dtype), block_q=block_q,
        block_k=block_k, window=window, bwd="fused" if fused else "split")
    dq, dk, dv = _by_platform(
        functools.partial(
            _bwd_call, t_real=t_real, scale=scale, causal=causal,
            window=window, block_q=block_q, block_k=block_k, fused=fused),
        q3, k3, v3, g.astype(q3.dtype), lse, delta)
    dsink = None
    if sink is not None:
        # the sink's probability exp(sink - lse) meets a zero value:
        # d sink = sum_i p_sink,i * (0 - delta_i)
        dsink = -jnp.sum(jnp.exp(sink[:, None, None] - lse) * delta,
                         axis=(1, 2))
    return dq, dk, dv, dsink


_flash.defvjp(_flash_fwd, _flash_bwd)


def _heads_first(x):
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, window=0, sink=None):
    """Blockwise (flash) attention. q [B, T, H, D], k [B, T, G, D],
    v [B, T, G, Dv] -> [B, T, H, Dv]; H a multiple of G, query head h
    reads key/value head ``h // (H / G)``.

    Pallas MXU kernels on TPU; the same kernels run under the Pallas
    interpreter elsewhere so tests don't need hardware. The TPU-native
    replacement for what the reference delegates to cuDNN fused kernels
    (cudnn_rnn-inl.h being the closest 2017 analog of a fused
    sequence kernel).

    The MXU is fed the type the inputs arrive in, accumulating in
    float32; the softmax arithmetic is float32 whatever the inputs.
    ``block_q`` / ``block_k`` default to ``flash_tiles(T, max(D, Dv),
    dtype, window)``; pass them only to pin a tiling (tests,
    benchmarks). ``window`` w > 0 (with ``causal``): query i sees keys
    i-w+1 .. i, and no tile outside that band is fetched or computed.
    ``sink`` [H]: a learnable logit per query head that joins each
    row's softmax denominator and carries no value (float32 arithmetic;
    differentiable).

    NOTE: pallas_call has no GSPMD partitioning rules — inside pjit over a
    sharded mesh, wrap calls in shard_map (see parallel/ring_attention for
    the sp-sharded composition) or keep attention inputs replicated.
    """
    b, t, h, d = q.shape
    g, dv = k.shape[2], v.shape[3]
    if h % g or v.shape[2] != g or k.shape[3] != d:
        raise ValueError(
            "flash_attention: query %s, key %s, value %s: key and value "
            "need one head count that divides the query's, and the key "
            "the query's width" % (q.shape, k.shape, v.shape))
    if window and not causal:
        raise ValueError("flash_attention: a window needs causal=True")
    if scale is None:
        scale = 1.0 / float(np.sqrt(d))
    if block_q is None or block_k is None:
        auto_q, auto_k = flash_tiles(t, max(d, dv), q.dtype, window)
        block_q = block_q or auto_q
        block_k = block_k or auto_k
    if t < min(block_q, block_k):
        block_q = block_k = _one_tile(t)
    labels = dict(operands=_operand_label(q.dtype), block_q=int(block_q),
                  block_k=int(block_k))
    if window or g != h or dv != d:
        labels.update(window=int(window), kv_heads=int(g), dv=int(dv))
    _M_FLASH_LOWERINGS.inc(**labels)
    mult = int(np.lcm(block_q, block_k))
    q3, k3, v3 = (_pad_to(_heads_first(x), 1, mult)[0] for x in (q, k, v))
    if sink is not None:
        sink = jnp.tile(sink.astype(jnp.float32), b)  # [B*H], as q3's rows
    out = _flash(q3, k3, v3, sink, t, float(scale), bool(causal),
                 int(window), int(block_q), int(block_k))
    out = out[:, :t]
    return out.reshape(b, h, t, dv).transpose(0, 2, 1, 3)


def attention(q, k, v, causal=False, scale=None, mesh=None, window=0,
              sink=None):
    """Shared attention dispatch for every model that wants fused
    attention without hand-picking a kernel: sequence-parallel ring
    attention when the mesh shards the sequence axis, the Pallas flash
    kernel when it pays (lowered for TPU and T >= 128, or forced via
    ``MXNET_TPU_FORCE_FLASH=1``), the materialized reference otherwise.
    q [B, T, H, D], k [B, T, G, D], v [B, T, G, Dv] -> [B, T, H, Dv];
    ``window`` and ``sink`` as ``flash_attention`` takes them."""
    t = q.shape[1]
    if mesh is not None and mesh.shape.get("sp", 1) > 1:
        from ..parallel.ring_attention import sequence_parallel_attention

        if window or sink is not None or k.shape != q.shape:
            raise ValueError("attention: window, sink and grouped heads "
                             "are not implemented over an sp mesh")
        return sequence_parallel_attention(q, k, v, mesh, causal=causal)
    extra = () if sink is None else (sink,)

    def flash(q, k, v, *sink):
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               window=window, sink=sink[0] if sink else None)

    def reference(q, k, v, *sink):
        return reference_attention(
            q, k, v, causal=causal, scale=scale, window=window,
            sink=sink[0] if sink else None)

    if mesh is None and os.environ.get("MXNET_TPU_FORCE_FLASH") == "1":
        return flash(q, k, v, *extra)
    if mesh is None and t >= 128:
        return jax.lax.platform_dependent(
            q, k, v, *extra, tpu=flash, default=reference)
    return reference(q, k, v, *extra)


# ---------------------------------------------------------------------------
# fused optimizer-slab kernel (AMP update path, parallel/train_step.py).
#
# The flat sharded update applies one elementwise optimizer step to a 1/N
# contiguous slab of the flattened parameter space. Under AMP that step
# is a chain of ~10 elementwise HLOs (unscale, clip, wd, state math,
# finite-select, bf16 cast-out) each of which round-trips the slab
# through HBM. The kernel below runs the whole chain in one VMEM pass:
# each grid step streams a (block_rows, 128) tile of every operand in,
# does the full update in registers, and writes new master weight, new
# state, and the bf16 weight copy out.
#
# The jnp path (`slab_update_reference`) and the kernel share
# `_slab_update_math`, so kernel-vs-reference parity reduces to the
# pallas_call plumbing (tiling, padding, SMEM scalars) — which is what
# the interpret-mode tests pin across 1/2/4/8 simulated devices.
# ---------------------------------------------------------------------------

_SLAB_LANES = 128
_SLAB_STATE_SLOTS = {"sgd": 0, "sgd_mom": 1, "adam": 2}


def _slab_update_math(kind, w, g, states, lr, inv_scale, finite, *, wd,
                      rescale_grad, clip_gradient, momentum, beta1, beta2,
                      epsilon):
    """One AMP optimizer step on a slab, mirroring optimizer_ops.py
    (`_prep_grad` + sgd/sgd_mom/adam update) with the AMP extras: grad
    unscale up front, branchless finite-select at the end, bf16 weight
    copy out. All math in f32 regardless of grad dtype."""
    w = w.astype(jnp.float32)
    g = g.astype(jnp.float32) * inv_scale
    if rescale_grad != 1.0:
        g = g * jnp.float32(rescale_grad)
    if clip_gradient is not None and clip_gradient > 0:
        g = jnp.clip(g, -jnp.float32(clip_gradient),
                     jnp.float32(clip_gradient))
    if wd != 0.0:
        g = g + jnp.float32(wd) * w
    if kind == "sgd":
        new_w = w - lr * g
        new_states = ()
    elif kind == "sgd_mom":
        mom = states[0].astype(jnp.float32)
        new_mom = jnp.float32(momentum) * mom - lr * g
        new_w = w + new_mom
        new_states = (new_mom,)
    elif kind == "adam":
        mean = states[0].astype(jnp.float32)
        var = states[1].astype(jnp.float32)
        new_mean = beta1 * mean + (1.0 - beta1) * g
        new_var = beta2 * var + (1.0 - beta2) * jnp.square(g)
        new_w = w - lr * new_mean / (jnp.sqrt(new_var) + epsilon)
        new_states = (new_mean, new_var)
    else:
        raise ValueError("unknown slab kind %r" % (kind,))
    keep = finite > jnp.float32(0.5)
    new_w = jnp.where(keep, new_w, w)
    new_states = tuple(jnp.where(keep, ns, os_.astype(jnp.float32))
                       for ns, os_ in zip(new_states, states))
    return new_w, new_states, new_w.astype(jnp.bfloat16)


def _slab_kernel(kind, n_state, scalar_ref, w_ref, g_ref, *refs, wd,
                 rescale_grad, clip_gradient, momentum, beta1, beta2,
                 epsilon):
    state_refs = refs[:n_state]
    out_w_ref = refs[n_state]
    out_state_refs = refs[n_state + 1:2 * n_state + 1]
    out_w16_ref = refs[2 * n_state + 1]
    lr = scalar_ref[0, 0]
    inv_scale = scalar_ref[0, 1]
    finite = scalar_ref[0, 2]
    new_w, new_states, w16 = _slab_update_math(
        kind, w_ref[...], g_ref[...],
        tuple(r[...] for r in state_refs), lr, inv_scale, finite,
        wd=wd, rescale_grad=rescale_grad, clip_gradient=clip_gradient,
        momentum=momentum, beta1=beta1, beta2=beta2, epsilon=epsilon)
    out_w_ref[...] = new_w
    for r, ns in zip(out_state_refs, new_states):
        r[...] = ns
    out_w16_ref[...] = w16


def _slab_pad_2d(x, rows, block_rows):
    """(S,) -> (rows_padded, 128), zero-filled."""
    x2 = jnp.pad(x, (0, rows * _SLAB_LANES - x.shape[0])).reshape(
        rows, _SLAB_LANES)
    if rows % block_rows:
        x2 = jnp.pad(x2, ((0, block_rows - rows % block_rows), (0, 0)))
    return x2


def slab_update_reference(kind, w, g, states, lr, inv_scale, finite, *,
                          wd, rescale_grad, clip_gradient, momentum=0.0,
                          beta1=0.9, beta2=0.999, epsilon=1e-8):
    """The pure-jnp slab update (the XLA path and the kernel's oracle)."""
    new_w, new_states, w16 = _slab_update_math(
        kind, w, g, states, jnp.asarray(lr, jnp.float32),
        jnp.asarray(inv_scale, jnp.float32),
        jnp.asarray(finite, jnp.float32), wd=wd, rescale_grad=rescale_grad,
        clip_gradient=clip_gradient, momentum=momentum, beta1=beta1,
        beta2=beta2, epsilon=epsilon)
    return new_w, new_states, w16


def fused_slab_update(kind, w, g, states, lr, inv_scale, finite, *, wd,
                      rescale_grad, clip_gradient, momentum=0.0, beta1=0.9,
                      beta2=0.999, epsilon=1e-8):
    """AMP optimizer step over a flat slab in one Pallas VMEM pass.

    w: (S,) f32 master shard; g: (S,) grad shard (bf16 under AMP);
    states: tuple of (S,) f32 state slabs (len per `kind`); lr /
    inv_scale / finite: traced f32 scalars (finite: 1.0 = apply,
    0.0 = skip bitwise-cleanly). Static hyperparameters are baked into
    the kernel. Returns (new_w f32, new_states tuple, w16 bf16), each
    (S,).
    """
    n_state = _SLAB_STATE_SLOTS[kind]
    assert len(states) == n_state, (kind, len(states))
    s = w.shape[0]
    rows = -(-s // _SLAB_LANES)
    block_rows = 256 if rows >= 256 else (-(-rows // 16) * 16)
    kern = functools.partial(
        _slab_kernel, kind, n_state, wd=float(wd),
        rescale_grad=float(rescale_grad),
        clip_gradient=float(clip_gradient) if clip_gradient else -1.0,
        momentum=float(momentum), beta1=float(beta1), beta2=float(beta2),
        epsilon=float(epsilon))
    # pads/stacks stay OUTSIDE the 32-bit context: under the global
    # jax_enable_x64 an outer trace caches their lowered subfunctions
    # with i64 scalar operands, and re-tracing them under _no_x64
    # emits i32 signatures for the same cache key — mixed-width
    # func.call verifier errors. Only the pallas_call itself (whose
    # Mosaic grid indexing must be 32-bit) runs under _no_x64.
    scalars = jnp.stack([
        jnp.asarray(lr, jnp.float32),
        jnp.asarray(inv_scale, jnp.float32),
        jnp.asarray(finite, jnp.float32)]).reshape(1, 3)
    w2 = _slab_pad_2d(w.astype(jnp.float32), rows, block_rows)
    g2 = _slab_pad_2d(g, rows, block_rows)
    st2 = [_slab_pad_2d(st.astype(jnp.float32), rows, block_rows)
           for st in states]
    rp = w2.shape[0]
    grid = (rp // block_rows,)
    blk = pl.BlockSpec((block_rows, _SLAB_LANES), lambda i: (i, 0))

    def call(*operands, interpret):
        with _no_x64():
            return pl.pallas_call(
                kern,
                grid=grid,
                in_specs=[pl.BlockSpec((1, 3), lambda i: (0, 0),
                                       memory_space=pltpu.SMEM),
                          blk, blk] + [blk] * n_state,
                out_specs=[blk] * (n_state + 2),
                out_shape=[jax.ShapeDtypeStruct((rp, _SLAB_LANES),
                                                jnp.float32)]
                * (n_state + 1)
                + [jax.ShapeDtypeStruct((rp, _SLAB_LANES), jnp.bfloat16)],
                interpret=interpret,
            )(*operands)

    outs = _by_platform(call, scalars, w2, g2, *st2)
    new_w = outs[0].reshape(-1)[:s]
    new_states = tuple(o.reshape(-1)[:s] for o in outs[1:n_state + 1])
    w16 = outs[n_state + 1].reshape(-1)[:s]
    return new_w, new_states, w16


# ---------------------------------------------------------------------------
# conv-backward pair (ROADMAP item 3: the MFU climb).
#
# ResNet's dominant FLOP sink is conv backward, and an explicit tap
# decomposition is a candidate against XLA's native
# conv-backprop-filter (neither side measured on the chip; PERF.md).
# The kernels below productize that decomposition WITHOUT the im2col
# patches slab. OPT-IN and staying so: on the v5e Mosaic refuses the
# pair at most ResNet-50 shapes (scoped-VMEM stack overflow — the
# 12 MiB plan budget undercounts what the 4-D blocks take once tiled);
# see PERF.md, PR 13.
#
#   wgrad:  gw[o,c,kh,kw] = sum_{n,oh,ow} g[n,o,oh,ow]
#                           * xpad[n,c,oh+kh,ow+kw]
#   dgrad:  dx = stride-1 conv of the (kh-1-p)-padded grad with the
#           180°-rotated, O<->C-swapped filter
#
# Both are tiled over (N, H-out, W-out, C) blocks — a grid over N-blocks
# whose per-step VMEM working set is one halo'd NHWC activation block,
# one grad block, and the f32 accumulator; the kh*kw filter-tap
# accumulation happens in-register per block (one MXU dot_general per
# tap), never materializing a kh*kw-sized patches tensor. bf16 inputs
# accumulate in f32 via preferred_element_type; the accumulation order
# (grid-sequential over N blocks, then taps) is fixed, so bf16 results
# are bitwise stable across runs.
#
# Tuned envelope (conv_bwd_plan): stride (1,1), dilation (1,1),
# groups 1, f32/bf16, kernel covering its padding (k > p), channel
# counts in MXU-friendly multiples, and a VMEM bound on the block
# working set. Everything else returns None and the caller falls back
# to XLA or the MXNET_CONV_WGRAD=taps lever — the dispatch table is
# per-shape and memoized, so the decision costs nothing on the trace
# hot path.
# ---------------------------------------------------------------------------

_CONV_VMEM_BUDGET = int(os.environ.get(
    "MXTPU_CONV_KERNEL_VMEM", str(12 * 1024 * 1024)))
_conv_plan_cache = {}


def conv_kernel_enabled():
    """Whether the Pallas conv-backward pair replaces XLA's gradient
    convs for in-envelope shapes. ``MXTPU_CONV_KERNEL``: "pallas" (or
    "1") enables it everywhere (interpret mode off-TPU — the parity
    tests); unset/"0"/"xla" keeps XLA's lowering."""
    return os.environ.get("MXTPU_CONV_KERNEL", "") in ("pallas", "1")


def conv_bwd_plan(dshape, wshape, stride, pad, dilate, dtype):
    """Per-shape dispatch decision for the conv-backward kernels.

    Returns ``{"block_n": int}`` when BOTH kernels can run this shape
    inside the tuned envelope, else None (caller falls back to XLA /
    the taps lever). Memoized per shape signature so the elif chain in
    ops/nn.py pays one dict lookup per trace."""
    key = (tuple(dshape), tuple(wshape), tuple(stride), tuple(pad),
           tuple(dilate), str(dtype))
    hit = _conv_plan_cache.get(key, "miss")
    if hit != "miss":
        return hit
    plan = _conv_bwd_plan_uncached(*key)
    _conv_plan_cache[key] = plan
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


def conv_bwd_filter(data, grad, wshape, pad, block_n=None):
    """Pallas filter gradient of a stride-1/dilation-1/groups-1 2-D conv.

    data: (N, C, H, W); grad: (N, O, OH, OW) cotangent; wshape:
    (O, C, kh, kw). Returns the f32 filter gradient (O, C, kh, kw).
    The tap accumulation runs in-register per (block_n, OH, OW, C)
    block; f32 accumulation regardless of input dtype."""
    n, c, h, w = data.shape
    o, _, kh, kw = wshape
    oh, ow = grad.shape[2], grad.shape[3]
    if block_n is None:
        plan = conv_bwd_plan(data.shape, wshape, (1, 1), pad, (1, 1),
                             data.dtype)
        block_n = plan["block_n"] if plan else 1
    # layout + halo pad happen OUTSIDE _no_x64 (see fused_slab_update's
    # note on i64/i32 subfunction cache keys under global x64)
    x_t = jnp.pad(jnp.transpose(data, (0, 2, 3, 1)),
                  ((0, 0), (pad[0], pad[0]), (pad[1], pad[1]), (0, 0)))
    g_t = jnp.transpose(grad, (0, 2, 3, 1))
    x_t, _ = _pad_to(x_t, 0, block_n)  # zero images contribute zero
    g_t, _ = _pad_to(g_t, 0, block_n)
    grid = (x_t.shape[0] // block_n,)
    hp, wp = x_t.shape[1], x_t.shape[2]
    kern = functools.partial(_conv_wgrad_kernel, bn=block_n, oh=oh, ow=ow,
                             kh=kh, kw=kw)

    def call(x_t, g_t, interpret):
        with _no_x64():
            return pl.pallas_call(
                kern,
                grid=grid,
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

    gw = _by_platform(call, x_t, g_t)
    return jnp.transpose(gw, (1, 2, 0)).reshape(o, c, kh, kw)


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


def conv_bwd_input(grad, weight, dshape, pad, block_n=None):
    """Pallas data gradient of a stride-1/dilation-1/groups-1 2-D conv.

    grad: (N, O, OH, OW) cotangent; weight: (O, C, kh, kw); dshape:
    the (N, C, H, W) input shape to reconstruct. dgrad is the stride-1
    conv of the (k-1-p)-padded grad with the rotated/transposed filter;
    each grid step computes one (block_n, H, W, C) output block with
    in-register f32 tap accumulation. Returns f32 (N, C, H, W)."""
    n, c, h, w = dshape
    o, _, kh, kw = weight.shape
    if block_n is None:
        plan = conv_bwd_plan(dshape, weight.shape, (1, 1), pad, (1, 1),
                             grad.dtype)
        block_n = plan["block_n"] if plan else 1
    ph, pw = kh - 1 - pad[0], kw - 1 - pad[1]
    g_t = jnp.pad(jnp.transpose(grad, (0, 2, 3, 1)),
                  ((0, 0), (ph, ph), (pw, pw), (0, 0)))
    g_t, _ = _pad_to(g_t, 0, block_n)
    # w[o, c, ::-1, ::-1] transposed to (kh, kw, O, C): the correlation
    # taps of the full (lhs-dilation-free, stride already 1) dgrad conv
    w_rot = jnp.transpose(weight[:, :, ::-1, ::-1], (2, 3, 0, 1))
    grid = (g_t.shape[0] // block_n,)
    hgp, wgp = g_t.shape[1], g_t.shape[2]
    kern = functools.partial(_conv_dgrad_kernel, bn=block_n, h=h, w=w,
                             kh=kh, kw=kw)

    def call(g_t, w_rot, interpret):
        with _no_x64():
            return pl.pallas_call(
                kern,
                grid=grid,
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

    gd = _by_platform(call, g_t, w_rot)
    return jnp.transpose(gd[:n], (0, 3, 1, 2))


def reference_attention(q, k, v, causal=False, scale=None, window=0,
                        sink=None):
    """Materialized-scores attention, the correctness oracle for the
    kernels (and the XLA path for tiny sequence lengths): shapes,
    ``window`` and ``sink`` as ``flash_attention`` takes them."""
    b, t, h, d = q.shape
    group = h // k.shape[2]
    if group > 1:
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    if scale is None:
        scale = 1.0 / float(np.sqrt(d))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        pos = np.arange(t)
        mask = pos[:, None] >= pos[None, :]
        if window:
            mask &= pos[:, None] - pos[None, :] < window
        s = jnp.where(mask[None, None], s, _NEG_INF)
    if sink is not None:
        s = jnp.concatenate([s, jnp.broadcast_to(
            sink.astype(jnp.float32)[None, :, None, None], (b, h, t, 1))],
            axis=-1)
    p = jax.nn.softmax(s, axis=-1)[..., :t]
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(
        q.dtype
    )


# ---------------------------------------------------------------------------
# grouped matmul: the expert layer's products (parallel/moe.py::topk_moe).
#
# Rows sorted by group (expert), ``group_sizes[g]`` of them for group g,
# each group multiplied by its own [k, n] weight. What a row tile costs:
#
#   visits    a row tile of ``tm`` rows is visited once for every group
#             that owns rows in it, whole products each time and a masked
#             store, so a group boundary inside a tile costs a second
#             visit: ``tm`` is bounded by the mean rows a group
#             (``gmm_tiles``), or either end of the load distribution
#             (a few fat groups and many of a handful of rows; uniform,
#             no boundary aligned) loses up to half the MXU time to it.
#   traffic   the grid walks n tiles outermost, then visits, then k: with
#             ``tk`` the whole k a group's [k, tn] weight block stays in
#             VMEM over the consecutive row tiles of that group and a row
#             block is fetched once per n tile.
#   metadata  offsets, visit -> group and visit -> row tile, made once
#             (``gmm_metadata``) and shared by every call over the same
#             rows; it reaches the kernels by scalar prefetch and the
#             number of visits is the grid's (dynamic) extent.
#
# forward and dgrad are one kernel (dgrad indexes the transposed weight
# block, nothing is copied); wgrad contracts the ragged row dimension
# into a float32 [tk, tn] accumulator that is stored when the visit's
# group changes. Empty groups are VISITED by wgrad (and store zeros), not
# by the other two. Operands reach the MXU in the type they arrive in,
# every product accumulates in float32 and is rounded once on the way out.
# ---------------------------------------------------------------------------

_M_GMM_LOWERINGS = _tm.counter(
    "moe.gmm_lowerings", "Traces of a grouped_matmul kernel call site "
    "(one per lowering, nothing per step); labels: mode (fwd / dgrad / "
    "wgrad), operands (the type the MXU is fed), tm, tk, tn")

# Row tiles by measurement on the v5e (PERF.md section 7): 128 rows feed
# the MXU at 61% of its peak, 256 at 67%, 512 at 74%, but a tile of the
# mean rows a group is visited twice as often as it is filled; half the
# mean (256 in the OLMoE cell) is the fastest or within 2% of it on the
# cell's skewed load and on a uniform one.
_GMM_MIN_ROW_TILE = 128
_GMM_MAX_ROW_TILE = 512
_GMM_MAX_COL_TILE = 2048


def _gmm_vmem_bytes(tm, tk, tn, itemsize, wgrad=False):
    """Upper bound on the VMEM one grid step holds: double-buffered
    operand and result blocks, the float32 accumulator and a float32
    product-shaped temporary; wgrad also the masked copies of its two
    row blocks. Against Mosaic on the v5e under its default limit, bf16:
    forward 13 MiB counted at (256, 2048, 1024), compiles; 18 at
    (512, 2048, 1024), refused; wgrad 15 at (256, 1024, 1024), compiles;
    28 at (256, 2048, 1024), refused."""
    if wgrad:
        return (3 * (tm * tk + tm * tn) * itemsize
                + 2 * tk * tn * itemsize + 2 * tk * tn * 4)
    return 2 * (tm * tk + tk * tn + tm * tn) * itemsize + 2 * tm * tn * 4


def gmm_row_tile(m, groups):
    """Rows a tile, from the rows and the groups alone (so that every
    product over the same sorted rows shares one set of metadata): half
    the mean rows a group, as a power of two within the measured
    bounds."""
    mean = max(m // max(groups, 1), 1)
    half = (1 << (mean.bit_length() - 1)) // 2
    return min(_GMM_MAX_ROW_TILE, max(_GMM_MIN_ROW_TILE, half))


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
    ``dtype``, the largest whose working set ``_gmm_vmem_bytes`` counts
    within ``_FLASH_VMEM_BUDGET`` (Mosaic's default scoped limit; the
    calls ask for no more). ``tm`` is ``gmm_row_tile``'s. Forward keeps
    ``tk`` the whole k while an n tile of 512 still fits beside it (a row
    block is then fetched once per n tile, a group's weight block once
    per n tile whatever its rows) and gives n the rest; dgrad runs the
    transposed problem's, ``gmm_tiles(m, n, k, ...)``. ``wgrad``: the
    [tk, tn] result block as near square as fits (each row block is read
    once per tile of the other dimension)."""
    itemsize = jnp.dtype(dtype).itemsize
    tm = gmm_row_tile(m, groups)
    tk = _col_tile(k, _GMM_MAX_COL_TILE)
    tn = _col_tile(n, _GMM_MAX_COL_TILE)

    def over():
        return (_gmm_vmem_bytes(tm, tk, tn, itemsize, wgrad)
                > _FLASH_VMEM_BUDGET)

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
    over ``jax.lax`` (see ``_affine``): made once a layer, inside a deep
    step."""
    lax = jax.lax
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
            _affine(tids_ref[v], tm))


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
        mode, _operand_label(dtype), tm, tk, tn)


_GMM_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"))


@functools.partial(jax.jit,
                   static_argnames=("tiles", "transposed", "interpret"))
def _gmm_call(offsets, gids, tids, visits, lhs, rhs, *, tiles, transposed,
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
    with _no_x64():
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
def _gmm_wgrad_call(offsets, gids, tids, visits, lhs, dout, *, groups,
                    tiles, interpret):
    """wgrad ``lhs[m, k]^T x dout[m, n]`` per group ``-> [g, k, n]``."""
    tm, tk, tn = tiles
    k, n = lhs.shape[1], dout.shape[1]
    with _no_x64():
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


def _gmm_count(mode, dtype, tiles):
    _M_GMM_LOWERINGS.inc(mode=mode, operands=_operand_label(dtype),
                         tm=tiles[0], tk=tiles[1], tn=tiles[2])


def _gmm_elsewhere(off_tpu, kernels, ragged):
    """The branch for every platform but the TPU: the same kernels
    through the Pallas interpreter, or XLA's own grouped matmul."""
    if off_tpu == "ragged_dot":
        return ragged
    return functools.partial(kernels, interpret=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _gmm(lhs, rhs, group_sizes, meta, plan, off_tpu):
    return _gmm_fwd(lhs, rhs, group_sizes, meta, plan, off_tpu)[0]


def _gmm_fwd(lhs, rhs, group_sizes, meta, plan, off_tpu):
    tiles = plan[0]
    _gmm_count("fwd", lhs.dtype, tiles)

    def kernels(lhs, rhs, group_sizes, meta, interpret):
        return _gmm_call(*meta[:4], lhs, rhs, tiles=tiles, transposed=False,
                         interpret=interpret)

    def ragged(lhs, rhs, group_sizes, meta):
        return jax.lax.ragged_dot(lhs, rhs, group_sizes)

    out = jax.lax.platform_dependent(
        lhs, rhs, group_sizes, meta,
        tpu=functools.partial(kernels, interpret=False),
        default=_gmm_elsewhere(off_tpu, kernels, ragged))
    return out, (lhs, rhs, group_sizes, meta)


def _gmm_bwd(plan, off_tpu, res, dout):
    lhs, rhs, group_sizes, meta = res
    _, dgrad_tiles, wgrad_tiles = plan
    _gmm_count("dgrad", lhs.dtype, dgrad_tiles)
    _gmm_count("wgrad", lhs.dtype, wgrad_tiles)

    def kernels(lhs, rhs, dout, group_sizes, meta, interpret):
        offsets, gids, tids, visits, w_gids, w_tids, w_visits = meta
        return (
            _gmm_call(offsets, gids, tids, visits, dout, rhs,
                      tiles=dgrad_tiles, transposed=True,
                      interpret=interpret),
            _gmm_wgrad_call(offsets, w_gids, w_tids, w_visits, lhs, dout,
                            groups=rhs.shape[0], tiles=wgrad_tiles,
                            interpret=interpret))

    def ragged(lhs, rhs, dout, group_sizes, meta):
        return jax.vjp(lambda l, r: jax.lax.ragged_dot(l, r, group_sizes),
                       lhs, rhs)[1](dout)

    dlhs, drhs = jax.lax.platform_dependent(
        lhs, rhs, dout.astype(lhs.dtype), group_sizes, meta,
        tpu=functools.partial(kernels, interpret=False),
        default=_gmm_elsewhere(off_tpu, kernels, ragged))
    return dlhs, drhs, None, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def gmm_runs_kernel(m, dtype):
    """Whether ``grouped_matmul`` runs its kernels at these shapes or
    hands the call to ``jax.lax.ragged_dot``: fewer rows than one row
    tile, or an operand type Mosaic does not take."""
    return (m >= _GMM_MIN_ROW_TILE
            and jnp.dtype(dtype).name in ("bfloat16", "float32"))


def grouped_matmul(lhs, rhs, group_sizes, metadata=None,
                   off_tpu="interpret"):
    """``lhs[m, k]`` sorted by group, ``rhs[g, k, n]``, ``group_sizes[g]``
    (int32, summing to m) -> ``[m, n]``: row r of group i times
    ``rhs[i]``, what ``jax.lax.ragged_dot`` computes, differentiable in
    ``lhs`` and ``rhs``.

    Three Pallas kernels (forward, dgrad over the transposed weight
    block, wgrad with the ragged contraction) with tiles from
    ``gmm_tiles``; float32 accumulation, one rounding to the operands'
    type. Mosaic where the computation is lowered for the TPU; on every
    other platform ``off_tpu`` decides: ``"interpret"`` the same kernels
    through the Pallas interpreter (tests), ``"ragged_dot"`` XLA's own
    grouped matmul and its transposes. The choice is made inside the
    ``custom_vjp`` (``lax.platform_dependent`` round a differentiated
    function would trace every kernel twice). Shapes the kernels do not
    take (``gmm_runs_kernel``) go to ``ragged_dot`` everywhere.
    ``metadata`` is ``gmm_metadata(group_sizes, m, tm)`` where several
    products walk the same rows (an expert layer's six); made here
    otherwise. The weight gradient of a group with no rows is exactly
    zero. Like ``flash_attention``, the kernels have no partitioning
    rule: inside a sharded ``jit``, call under ``shard_map``."""
    m, k = lhs.shape
    groups, _, n = rhs.shape
    dtype = jnp.result_type(lhs.dtype, rhs.dtype)
    if not gmm_runs_kernel(m, dtype):
        return jax.lax.ragged_dot(lhs, rhs, group_sizes)
    tiles = gmm_tiles(m, k, n, groups, dtype)
    tn_d, tk_d = gmm_tiles(m, n, k, groups, dtype)[1:]
    if metadata is None:
        metadata = gmm_metadata(group_sizes, m, tiles[0])
    plan = (tiles, (tiles[0], tk_d, tn_d),
            gmm_tiles(m, k, n, groups, dtype, wgrad=True))
    return _gmm(lhs.astype(dtype), rhs.astype(dtype), group_sizes,
                tuple(metadata), plan, off_tpu)


# ---------------------------------------------------------------------------
# the chunked state-space scan (ops/transformer.py::mamba2; Mamba-2 / SSD).
#
# ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t`` a head,
# G groups of heads sharing B and C. One grid step is one chunk of Q tokens
# of one group: nothing of it but its inputs, its output and the state it
# entered with reaches HBM.
#
#   grid      (batch, group, chunk), the chunks one after another; the
#             group's state is carried in float32 VMEM scratch TRANSPOSED,
#             [N, heads * P], so that reading it through C, its update
#             from B and their transposes are one product each over all
#             of the group's heads (a result 512 lanes wide in the
#             Nemotron cell).
#   heads     a head's [Q, Q] table ``exp(cum_i - cum_j)`` (masked before
#             the exp) times ``C B^T`` (made once a group) is its own left
#             operand, so the products inside a chunk are one a head. A
#             head narrower than a lane row shares its 128 lanes with its
#             neighbours: the product runs over the whole lane tile (the
#             MXU is 128 wide whatever the operand) and a select keeps the
#             head's own lanes, so no slice leaves the (8, 128) tiling.
#   tables    what is per (token, head) is made by XLA from ``dt`` and
#             ``a`` (``_ssd_tables``: 2 MB at the cell's shape, float32):
#             the running log decay of a chunk as a product with a
#             triangular matrix (a ``cumsum`` lowers to a
#             ``reduce_window``), its exponentials, token-major (a head a
#             column of one lane row: a lane gather spreads a tile's
#             heads over their lanes, one pass where two broadcasts and a
#             select took three), head-major (a row along lanes, for the
#             [Q, Q] table) and a chunk's ``exp(total)`` by lane.
#   skip      ``d x`` is added to y here (and its transposes made here):
#             as XLA's it was three more passes over [T, H P] float32.
#   backward  the same walk from the last chunk to the first carrying the
#             state's cotangent, the decay tables rebuilt. The log decay's
#             cotangent needs no [Q, Q] reduction: ``cum_i`` multiplies
#             everything of ``y_i`` (``dy_i . y_i``), ``-cum_j`` everything
#             token j's input reaches (``-(dt x)_j . d(dt x)_j``), and the
#             chunk's last one the state it leaves. dB and dC are summed
#             over the group's heads by the products' contraction.
#   set-up    both bodies are ``jax.lax`` primitives only (a ``jnp`` call
#             or an operator on a tracer is a nested ``jit`` to trace,
#             2 ms apiece on the chip's host), their iotas and masks made
#             once a body; each ``pallas_call`` sits behind a ``jax.jit``
#             (one trace a signature however many layers call it,
#             ``ssm.scan_kernel_traces``); and the branch for every
#             platform but the TPU is the ``jnp.einsum`` form
#             (``ops/transformer.py::ssd_scan``), not the Pallas
#             interpreter, whose trace of both bodies a step lowered for
#             the TPU would pay for nothing. The interpreter runs them
#             only where a caller says ``interpret=True`` (their tests).
#
# Log decays, their sums and exponentials, the carried state and every
# accumulator float32; the MXU's operands in x's type.
# ---------------------------------------------------------------------------

_M_SSD_TRACES = _tm.counter(
    "ssm.scan_kernel_traces", "Traces of a state-space scan kernel's "
    "pallas_call (one a signature and process, however many Mamba2 nodes "
    "call it; nothing per step); labels: mode (fwd / bwd)")

_SSD_VMEM_LIMIT = 48 * 1024 * 1024

lax = jax.lax
# ``jnp.take_along_axis(table, at, axis=1)`` as the one primitive it ends in
_SSD_LANE_GATHER = lax.GatherDimensionNumbers(
    offset_dims=(), collapsed_slice_dims=(1,), start_index_map=(1,),
    operand_batching_dims=(0,), start_indices_batching_dims=(0,))


def _ssd_tile(per, p):
    """(lanes of a tile, heads in it, tiles a group) for ``per`` heads of
    width ``p``."""
    width = max(p, _SLAB_LANES)
    return width, width // p, per * p // width


def _ssd_vmem_bytes(chunk, per, p, n, itemsize):
    """What a backward step holds, counted generously: the double-buffered
    blocks (x, dx; B, C, dB, dC; y, dy, the entering state; the tables
    lane-padded), the carried cotangent, three operand-typed scratches and
    a dozen float32 temporaries a group wide."""
    wide = chunk * per * p
    state = n * per * p * 4
    return (2 * (2 * wide * itemsize + 4 * chunk * n * itemsize
                 + 2 * wide * 4 + state + 3 * chunk * _SLAB_LANES * 4)
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
    return (chunk % _SLAB_LANES == 0 and state % _SLAB_LANES == 0
            and head_dim % 8 == 0 and width % head_dim == 0
            and (per * head_dim) % width == 0 and 4 * per <= _SLAB_LANES
            and jnp.dtype(dtype).name in ("bfloat16", "float32")
            and _ssd_vmem_bytes(chunk, per, head_dim, state,
                                jnp.dtype(dtype).itemsize)
            <= _SSD_VMEM_LIMIT)


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
    return (jnp.pad(cols, ((0, 0),) * 3 + ((0, _SLAB_LANES - 4 * e),)),
            cum.reshape(b, t, groups, e).transpose(0, 2, 3, 1),
            ends.transpose(0, 2, 1, 3))


def _ssd_masks(q, width, heads, p):
    """Made once a body: ``causal`` [Q, Q] (j <= i) and the table of
    ``_NEG_INF`` its select falls to, and for a tile of several heads the
    head of each lane [Q, W] and each head's own lanes (``None`` and
    ``()`` where the head is the tile)."""
    iota = lax.broadcasted_iota
    causal = lax.ge(iota(jnp.int32, (q, q), 0), iota(jnp.int32, (q, q), 1))
    masked = lax.full((q, q), _NEG_INF, jnp.float32)
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


def _ssd_dot(lhs, rhs, contract):
    """float32 operands at the highest precision: the MXU's default is one
    bf16 pass, whose roundings the log decay's cotangent (a difference of
    sums of the same products, ``_ssd_bwd_kernel``) cannot see."""
    return lax.dot_general(
        lhs, rhs, ((contract[:1], contract[1:]), ((), ())),
        precision=(lax.Precision.HIGHEST if lhs.dtype == jnp.float32
                   else None),
        preferred_element_type=jnp.float32)


def _ssd_sum(v, axis):
    """``jnp.sum(v, axis, keepdims=True)`` of a [rows, lanes] table."""
    shape = tuple(1 if i == axis else s for i, s in enumerate(v.shape))
    return lax.broadcast_in_dim(lax.reduce_sum(v, (axis,)), shape,
                                (1 - axis,))


def _ssd_first_chunk():
    return lax.eq(pl.program_id(2), np.int32(0))


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

    @pl.when(_ssd_first_chunk())
    def _():
        state[...] = lax.full(state.shape, 0, f32)

    entered = state[...]
    ent_ref[...] = entered
    bm, cm = b_ref[...], c_ref[...]
    cb = _ssd_dot(cm, bm, (1, 1))                      # C_i . B_j
    through_c = _ssd_dot(cm, cast(entered, op), (1, 0))
    for k in range(tiles):
        at = slice(k * width, (k + 1) * width)
        first = k * heads
        x32 = cast(x_ref[:, at], f32)
        dt = by_head(first)
        xdt = cast(mul(x32, dt), op)
        parts = [
            _ssd_dot(cast(mul(cb, _ssd_decay(cols_ref, rows_ref, per,
                                             first + h, causal, masked)),
                          op), xdt, (1, 0))
            for h in range(heads)]
        y_ref[:, at] = add(
            add(_ssd_own_lanes(parts, mine), mul(skip_ref[:, at], x32)),
            mul(lax.slice_in_dim(through_c, at.start, at.stop, axis=1),
                by_head(2 * per + first)))
        xw_s[:, at] = cast(mul(x32, mul(dt, by_head(3 * per + first))), op)
    state[...] = add(_ssd_dot(bm, xw_s[...], (0, 0)),     # [N, E P]
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

    @pl.when(_ssd_first_chunk())
    def _():
        dstate[...] = lax.full(dstate.shape, 0, f32)
        dskip_ref[...] = lax.full(dskip_ref.shape, 0, f32)

    entered, dleft = ent_ref[...], dstate[...]
    entered_op, dleft_op = cast(entered, op), cast(dleft, op)
    bm, cm = b_ref[...], c_ref[...]
    cb = _ssd_dot(cm, bm, (1, 1))
    dxw = _ssd_dot(bm, dleft_op, (1, 0))               # B_j . dS', [Q, E P]
    end = _ssd_end(ends_ref, True)
    carried = mul(end, _ssd_sum(mul(dleft, entered), 0))
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
            dcb = add(dcb, mul(decay, _ssd_dot(dy_op, own, (1, 1))))
            parts.append(_ssd_dot(cast(mul(cb, decay), op), dy_op, (0, 0)))
        inside = _ssd_own_lanes(parts, mine)
        dxw_k = cut(dxw, at)
        through = mul(xw, dxw_k)
        du = add(inside, mul(to_end, dxw_k))
        skip = skip_ref[:, at]
        dx_ref[:, at] = cast(add(mul(dt, du), mul(skip, dy)), dx_ref.dtype)
        dskip_ref[:, at] = add(dskip_ref[:, at], _ssd_sum(mul(dy, x32), 0))
        # d total: the state the chunk leaves is exp(total) (entered +
        # what the chunk's tokens add)
        leaves = add(_ssd_sum(through, 0), cut(carried, at))
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
                small_ref[:, column + h:column + h + 1] = _ssd_sum(own, 1)
    dcb_op = cast(dcb, op)
    dc_ref[...] = cast(add(_ssd_dot(dr_s[...], entered_op, (1, 1)),
                           _ssd_dot(dcb_op, bm, (1, 0))), dc_ref.dtype)
    db_ref[...] = cast(add(_ssd_dot(xw_s[...], dleft_op, (1, 1)),
                           _ssd_dot(dcb_op, cm, (0, 0))), db_ref.dtype)
    dstate[...] = add(_ssd_dot(cm, dr_s[...], (0, 0)), mul(dleft, end))


def _ssd_name(which, dtype, chunk, p, n):
    return "ssd_%s_%s_q%d_p%d_n%d" % (which, _operand_label(dtype), chunk,
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
            by_token(_SLAB_LANES), by_token(2 * per),
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
            _FLASH_VMEM_BUDGET,
            _ssd_vmem_bytes(chunk, per, p, n, jnp.dtype(dtype).itemsize)))


@functools.partial(jax.jit,
                   static_argnames=("chunk", "per", "p", "interpret"))
def _ssd_fwd_call(x, bm, cm, cols, rows, ends, skip, *, chunk, per, p,
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
    with _no_x64():
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
def _ssd_bwd_call(x, bm, cm, cols, rows, ends, skip, y, entering, dy, *,
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
    with _no_x64():
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
    from .transformer import ssd_scan as einsum_form

    b, t, h = dt.shape
    x, bmat, cmat = (v.reshape(b, t, heads, -1)
                     for v, heads in zip(flat, (h, groups, groups)))
    y = (einsum_form(x, bmat, cmat, dt, a, chunk)
         + skip[:, None] * x.astype(jnp.float32))
    return y.reshape(b, t, -1)


def _ssd_by_platform(kernels, einsum, interpret, *args):
    """``kernels(*args, interpret=False)`` where the computation is lowered
    for the TPU and ``einsum(*args)`` on every other platform; under
    ``interpret`` the kernels through the Pallas interpreter, whatever
    the platform."""
    if interpret:
        return kernels(*args, interpret=True)
    return lax.platform_dependent(
        *args, tpu=functools.partial(kernels, interpret=False),
        default=einsum)


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
        return _ssd_fwd_call(*flat[:3], *tables, flat[3], chunk=chunk,
                             per=h // groups, p=p, interpret=interpret)

    def einsum(flat, tables, dt, a, skip):
        return (_ssd_einsum(flat[:3], dt, a, skip, chunk, groups),
                jnp.zeros((b, t // chunk, groups, n, h // groups * p),
                          jnp.float32))

    return res + tuple(_ssd_by_platform(kernels, einsum, interpret, *res))


def _ssd_bwd(chunk, interpret, res, dy):
    flat, tables, dt, a, skip, y, entering = res
    b, t, h = dt.shape
    groups = tables[0].shape[1]
    per, nc = h // groups, t // chunk
    p, n = flat[0].shape[2] // h, flat[1].shape[2] // groups
    dy = dy.reshape(y.shape).astype(jnp.float32)

    def kernels(flat, tables, dt, a, skip, y, entering, dy, interpret):
        dx, db, dc, small, dskip = _ssd_bwd_call(
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

    grads = _ssd_by_platform(kernels, einsum, interpret, *res, dy)
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


# ---------------------------------------------------------------------------
# the gated delta rule's chunk core (ops/transformer.py::gated_delta_net;
# Gated DeltaNet, Yang, Kautz & Hatamizadeh, arXiv:2412.06464).
#
# ``S_t = a_t S_{t-1} + k_t u_t^T``, ``u_t = beta_t (v_t - a_t S_{t-1}^T
# k_t)``, ``o_t = S_t^T q_t`` a head. One grid step is one chunk of C
# tokens of a few heads: nothing of it but its inputs, its output, the
# state it entered with and the inverse of its triangular system reaches
# HBM.
#
#   grid      (batch, head group, chunk), the chunks one after another; a
#             head's state [K, V] is carried in float32 VMEM scratch. The
#             operands are head-major ([B, H, T, K]: XLA's unit-norm pass
#             writes them so), so a head's block is [C, K] whole whatever
#             K is of a lane row, and its padding to one happens in VMEM.
#   heads     ``_gdn_group`` heads a step, one after another in a
#             ``fori_loop``: one traced body however many there are (a
#             head's tables are a lane and a sublane of the step's two
#             blocks, picked by a select and a dynamic slice). A step of
#             several heads spreads the grid's cost a step.
#   system    with ``b`` the running log decay, ``D_ij = exp(b_i - b_j)``
#             and ``L = beta_i D_ij (k_i . k_j)`` strictly below the
#             diagonal, ``(I + L)^-1`` is built ONCE a chunk by forward
#             substitution (``_gdn_inverse``: column j's multipliers
#             eliminate row j from the rows below it, 16-row tiles the
#             column has passed left alone), never by a series in powers
#             of ``L``, whose terms cancel once keys repeat. It is applied
#             as a float32 product: ``u = (I + L)^-1 beta (v - c k S)``,
#             which is ``W - Y S`` of the chunk form with one system
#             solved, not two. The forward keeps the inverse (16 KB a
#             chunk and head at C 64) and the backward applies its
#             transpose: no substitution runs backwards.
#   tables    ``b`` and ``beta`` token-major (a head a column of one lane
#             row) and ``b`` head-major (a row along lanes, for ``D``) are
#             XLA's (``_gdn_tables``); every exponential is taken here.
#   backward  the same walk from the last chunk to the first carrying the
#             state's cotangent; a chunk's tables, ``u`` and the products
#             with the entering state rebuilt. ``dL = -(T^T du) u^T``
#             (``T`` the inverse), so the system's cotangent is two
#             products. The log decay's cotangent is the row sums less
#             the column sums of ``dM * M + dL * L`` (token-major and
#             head-major outputs, joined by XLA) plus what ``c``, the
#             decay to the chunk's end and the carried state add.
#   set-up    as the scan's: ``jax.lax`` primitives only, each
#             ``pallas_call`` behind a ``jax.jit``
#             (``linear_attn.kernel_traces``), the ``jax.numpy`` chunk
#             form on every platform but the TPU.
#
# Log decays, their sums and exponentials, ``L``, the substitution, the
# inverse's products, the state and every accumulator float32; the MXU's
# other operands in v's type.
# ---------------------------------------------------------------------------

_M_GDN_TRACES = _tm.counter(
    "linear_attn.kernel_traces", "Traces of a gated delta rule kernel's "
    "pallas_call (one a signature and process, however many GatedDeltaNet "
    "nodes call it; nothing per step); labels: mode (fwd / bwd)")

_GDN_VMEM_LIMIT = 48 * 1024 * 1024
_GDN_HEADS_A_STEP = 16


def _gdn_group(heads):
    """Heads a grid step: the largest divisor of ``heads`` whose two
    scalars a token are columns of one lane row of tables with room to
    spare. The body is one head's whatever the group (a ``fori_loop``);
    a larger group is fewer, longer steps and less padding in the
    tables."""
    return max(g for g in range(1, _GDN_HEADS_A_STEP + 1) if heads % g == 0)


def _gdn_vmem_bytes(chunk, per, key_dim, value_dim, itemsize):
    """What a backward step holds, counted generously: the double-buffered
    blocks (q, k, dq, dk; v, dv; do; the entering state; the inverse; the
    four tables), the carried cotangents in scratch, and two dozen float32
    temporaries of the one head in hand as wide as the widest table."""
    k, v, c = (_lanes(w) for w in (key_dim, value_dim, chunk))
    state = key_dim * v * 4
    head = (4 * chunk * k * itemsize + 2 * chunk * v * itemsize
            + chunk * v * 4 + state + chunk * c * 4)
    tables = 2 * chunk * _SLAB_LANES * 4 + 2 * -(-per // 8) * 8 * c * 4
    return (2 * (per * head + tables) + per * state + chunk * c * 4
            + 24 * max(chunk * max(k, v) * 4, state))


def gdn_takes(heads, key_dim, value_dim, chunk, dtype):
    """Whether ``gated_delta_rule`` has tiles for these shapes: chunks of
    whole bf16 sublane tiles up to a lane row, a head's keys and values in
    multiples of 32 (a quarter of a lane row; the rest of the row is
    padding in VMEM), an operand type Mosaic takes and a step that fits
    VMEM. Everything else is the ``jax.numpy`` chunk form's
    (``ops/transformer.py::gated_delta_rule``)."""
    if min(heads, key_dim, value_dim, chunk) <= 0:
        return False
    return (chunk % 16 == 0 and chunk <= _SLAB_LANES
            and key_dim % 32 == 0 and value_dim % 32 == 0
            and jnp.dtype(dtype).name in ("bfloat16", "float32")
            and _gdn_vmem_bytes(chunk, _gdn_group(heads), key_dim,
                                value_dim, jnp.dtype(dtype).itemsize)
            <= _GDN_VMEM_LIMIT)


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
    return (jnp.pad(cols, ((0, 0),) * 3 + ((0, _SLAB_LANES - 2 * per),)),
            jnp.pad(rows, ((0, 0),) * 3 + ((0, -per % 8), (0, 0))))


def _gdn_masks(c):
    """Made once a body: ``causal`` [C, C] (j <= i), ``strict`` (j < i),
    the table of ``_NEG_INF`` the decay's select falls to and the identity
    in 16-row tiles."""
    iota = lax.broadcasted_iota
    row, col = iota(jnp.int32, (c, c), 0), iota(jnp.int32, (c, c), 1)
    one, zero = (lax.full((16, c), v, jnp.float32) for v in (1, 0))
    at, lane = iota(jnp.int32, (16, c), 0), iota(jnp.int32, (16, c), 1)
    eye = tuple(
        lax.select(lax.eq(lax.add(at, np.int32(16 * p)), lane), one, zero)
        for p in range(c // 16))
    return dict(causal=lax.ge(row, col), strict=lax.gt(row, col),
                masked=lax.full((c, c), _NEG_INF, jnp.float32), eye=eye)


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
    return _ssd_sum(lax.select(lax.eq(lane, at), cols,
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
        kk=lax.mul(decay, _ssd_dot(k, k, (1, 1))),
        qk=lax.mul(decay, _ssd_dot(q, k, (1, 1))),
        ks=_ssd_dot(k, state_op, (1, 0)), qs=_ssd_dot(q, state_op, (1, 0)))


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

    @pl.when(_ssd_first_chunk())
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
        u = cast(_ssd_dot(inv, mul(t["beta"],
                                   sub(t["v"], mul(t["c"], t["ks"]))),
                          (1, 0)), op)
        o_ref[h] = add(_ssd_dot(cast(t["qk"], op), u, (1, 0)),
                       mul(t["c"], t["qs"]))
        k_out = cast(mul(t["to_end"], cast(t["k"], f32)), op)
        state[h] = add(mul(t["whole"], entered), _ssd_dot(k_out, u, (0, 0)))
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
        return _ssd_sum(_ssd_sum(v, 1), 0)

    def column(lane, at, v):
        """[C, 1] in column ``at`` of a [C, 128] table of zeros."""
        wide = lax.broadcast_in_dim(v, lane.shape, (0, 1))
        return lax.select(lax.eq(lane, at), wide,
                          lax.full(lane.shape, 0, f32))

    @pl.when(_ssd_first_chunk())
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
        u = _ssd_dot(inv, mul(beta, kept), (1, 0))
        u_op = cast(u, op)
        do = do_ref[h]
        do_op = cast(do, op)
        k_out = cast(mul(t["to_end"], k32), op)
        du = add(_ssd_dot(cast(t["qk"], op), do_op, (0, 0)),
                 _ssd_dot(k_out, dleft_op, (1, 0)))
        dr = _ssd_dot(inv, du, (0, 0))                  # T^T du
        dm = _ssd_dot(do_op, u_op, (1, 1))              # do u^T
        dqk = mul(decay, dm)
        dlow = lax.select(strict, lax.neg(_ssd_dot(dr, u, (1, 1))), zero)
        dkk = mul(dlow, mul(beta, decay))
        dqk_op, dkk_op = cast(dqk, op), cast(dkk, op)
        c_do = cast(mul(t["c"], do), op)
        dks = cast(lax.neg(mul(mul(beta, t["c"]), dr)), op)
        left = _ssd_dot(u_op, dleft_op, (1, 1))         # u dS'^T, [C, K]
        dq_ref[h] = cast(add(_ssd_dot(c_do, entered_op, (1, 1)),
                             _ssd_dot(dqk_op, k, (1, 0))), dq_ref.dtype)
        dk_ref[h] = cast(
            add(add(_ssd_dot(dks, entered_op, (1, 1)),
                    _ssd_dot(dqk_op, q, (0, 0))),
                add(add(_ssd_dot(dkk_op, k, (1, 0)),
                        _ssd_dot(dkk_op, k, (0, 0))),
                    mul(t["to_end"], left))), dk_ref.dtype)
        dv_ref[h] = cast(mul(beta, dr), dv_ref.dtype)
        # b_i multiplies row i of D and divides column i; c_i = exp(b_i);
        # the decay to the end divides by it; the last one carries the
        # whole chunk's decay of the entering state and of every key
        pairs = add(mul(dm, t["qk"]), mul(dlow, low))
        to_end = mul(t["to_end"], _ssd_sum(mul(k32, left), 1))
        decayed = mul(t["whole"], dleft)
        leaves = add(total(to_end), total(mul(decayed, entered)))
        db = add(
            sub(add(_ssd_sum(pairs, 1),
                    mul(t["c"], _ssd_sum(
                        sub(mul(do, t["qs"]),
                            mul(mul(beta, dr), t["ks"])), 1))), to_end),
            lax.select(last, lax.broadcast_in_dim(leaves, (c, 1), (0, 1)),
                       none))
        dbeta = add(_ssd_sum(mul(dr, kept), 1),
                    _ssd_sum(mul(dlow, t["kk"]), 1))
        across_ref[pl.ds(h, 1), :] = _ssd_sum(pairs, 0)
        dstate[h] = add(decayed,
                        add(_ssd_dot(q, c_do, (0, 0)),
                            _ssd_dot(k, dks, (0, 0))))
        return add(small, add(
            column(t["lane"], h, db),
            column(t["lane"], lax.add(h, np.int32(heads)), dbeta)))

    small_ref[...] = lax.fori_loop(
        0, heads, head, lax.full(small_ref.shape, 0, f32))


def _gdn_name(which, dtype, chunk, key_dim, value_dim):
    return "gdn_%s_%s_c%d_k%d_v%d" % (which, _operand_label(dtype), chunk,
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
            pl.BlockSpec((None, None, chunk, _SLAB_LANES),
                         lambda b, g, c: (b, g, at(c), 0)),
            pl.BlockSpec((None, None, None, -(-per // 8) * 8, chunk),
                         lambda b, g, c: (b, g, at(c), 0, 0)),
            by_chunk(key_dim, value_dim), by_chunk(chunk, chunk))


def _gdn_params(chunk, per, key_dim, value_dim, dtype):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=max(
            _FLASH_VMEM_BUDGET,
            _gdn_vmem_bytes(chunk, per, key_dim, value_dim,
                            jnp.dtype(dtype).itemsize)))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _gdn_fwd_call(q, k, v, cols, rows, *, chunk, interpret):
    """q, k [B, H, T, K], v [B, H, T, V], the tables -> o [B, H, T, V],
    the entering states [B, T / C, H, K, V] and the systems' inverses
    [B, T / C, H, C, C], float32."""
    _M_GDN_TRACES.inc(mode="fwd")
    b, h, t, key_dim = q.shape
    value_dim = v.shape[3]
    per, nc = h // cols.shape[1], t // chunk
    narrow, wide, cols_spec, rows_spec, state_spec, inv_spec = _gdn_specs(
        chunk, per, key_dim, value_dim, nc, False)
    with _no_x64():
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
def _gdn_bwd_call(q, k, v, cols, rows, entering, inverse, do, *, chunk,
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
    with _no_x64():
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
    from .transformer import gated_delta_rule as chunk_form

    q, k, v = (jnp.moveaxis(x, 1, 2) for x in (q, k, v))
    return jnp.moveaxis(chunk_form(q, k, v, g, beta, chunk), 2, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _gdn(q, k, v, g, beta, chunk, interpret):
    return _gdn_fwd(q, k, v, g, beta, chunk, interpret)[0]


def _gdn_fwd(q, k, v, g, beta, chunk, interpret):
    # one trace of the forward for the primal and the rule: see _ssd_fwd
    with jax.sharding.use_abstract_mesh(jax.sharding.get_abstract_mesh()):
        res = _gdn_forward(q, k, v, g, beta, chunk=chunk,
                           interpret=interpret)
    return res[-3], res[:5] + res[-2:]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _gdn_forward(q, k, v, g, beta, *, chunk, interpret):
    """The inputs, o [B, H, T, V] float32 and the backward's other
    residuals: the state each chunk entered with and its system's inverse
    (zeros off the TPU, where the chunk form's own transpose is the
    backward)."""
    b, h, t, key_dim = q.shape
    res = (q, k, v, g, beta)

    def kernels(q, k, v, g, beta, interpret):
        return _gdn_fwd_call(
            q, k, v, *_gdn_tables(g, beta, chunk, _gdn_group(h)),
            chunk=chunk, interpret=interpret)

    def chunked(q, k, v, g, beta):
        return (_gdn_chunked(q, k, v, g, beta, chunk),
                jnp.zeros((b, t // chunk, h, key_dim, v.shape[3]),
                          jnp.float32),
                jnp.zeros((b, t // chunk, h, chunk, chunk), jnp.float32))

    return res + tuple(_ssd_by_platform(kernels, chunked, interpret, *res))


def _gdn_bwd(chunk, interpret, res, do):
    g = res[3]
    b, t, h = g.shape
    per, nc = _gdn_group(h), t // chunk

    def kernels(q, k, v, g, beta, entering, inverse, do, interpret):
        dq, dk, dv, small, across = _gdn_bwd_call(
            q, k, v, *_gdn_tables(g, beta, chunk, per), entering, inverse,
            do, chunk=chunk, interpret=interpret)
        small = small.reshape(b, h // per, nc, chunk, _SLAB_LANES)
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

    return _ssd_by_platform(kernels, chunked, interpret, *res,
                            do.astype(jnp.float32))


_gdn.defvjp(_gdn_fwd, _gdn_bwd)


def gated_delta_rule(q, k, v, g, beta, chunk, interpret=False):
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
    ``shard_map``."""
    t = q.shape[1]
    pad = -t % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    f32 = jnp.float32
    o = _gdn(*(jnp.moveaxis(x, 1, 2) for x in (q, k, v)), g.astype(f32),
             beta.astype(f32), int(chunk), bool(interpret))
    return jnp.moveaxis(o, 1, 2)[:, :t]


# ---------------------------------------------------------------------------
# latent attention's flash pair (ops/transformer.py::latent_attention; MLA,
# DeepSeek-V2/V3). The same algorithm as the flash kernels at the top of
# this file (tiles from ``flash_tiles``, the causal live-tile walk,
# ``_tile_cases``' unmasked fast path, the one-pass backward with the
# diagonal first), on the operands where the neighbouring matmuls leave
# and take them, every one token-major and a head a block of whole lane
# rows of its columns:
#
#   score     ``s = scale (q_nope k_nope^T + q_rope k_rope^T)``: a head's
#             own key and the ONE rotary key a token, two key operands
#             whose tiles stand side by side in VMEM for the one product
#             (``_latent_key``); the concatenated contraction, over zero
#             lanes besides. No key of [T, H, N + R] exists in HBM.
#   kv        [B, T, H (N + Dv)], the up-projection's output as its
#             matmul writes it: head h's block is column block h, its
#             lanes 0:N the keys and N: the values (N and Dv whole lane
#             rows, so both are free views of the VMEM tile). The
#             backward writes dK_nope and dV into the two halves of the
#             same layout: the up-projection's output gradient, no pad.
#   k_rope    [B, T, Rp] (the R rotated lanes, then zeros to a whole lane
#             row): its block index ignores the head. The backward sums
#             dK_rope over the heads in float32 VMEM scratch and rounds
#             it once a batch row (the head is an ``arbitrary`` grid
#             dimension there).
#   q, dq     [B, T, H (N + Rp)]: a head's N un-rotated lanes, its R
#             rotated ones and zeros to a whole lane row, which the one
#             pass over the query (the rotation: ``latent_query`` below)
#             writes and its transpose reads. A head of 192 lanes is not
#             a block of the query as the projection leaves it; padded in
#             VMEM it took 256 before, and the product over the zero lanes
#             is the half MXU pass the 64 rotary lanes always left empty.
#   o, dO     [B, T, H Dv], column block h: what the output projection
#             reads and its transpose writes. ``lse`` [B, H, T, 1], the
#             kernels' own; ``delta`` never leaves VMEM.
#   set-up    as the scan's: each ``pallas_call`` and the forward and the
#             backward round it behind a ``jax.jit`` (one trace a
#             signature however many layers, ``attention.
#             latent_kernel_traces``); on every platform but the TPU the
#             branch is ``reference_attention`` over the concatenated key
#             on the same operands, not the Pallas interpreter, which
#             runs only where a caller says ``interpret=True`` (tests).
#
# grid (batch, head, q tile, k step), k innermost. Only the one-pass
# backward exists: ``latent_flash_takes`` admits the shapes whose dK / dV
# of a head stay in VMEM (``_bwd_fuses``) and whose N and Dv are whole
# lane rows; every other shape keeps ``latent_attention``'s composition
# over ``attention``. Float32 scores, mask, softmax, lse, delta and
# accumulators; operands in the type they arrive in.
# ---------------------------------------------------------------------------

_M_LATENT_TRACES = _tm.counter(
    "attention.latent_kernel_traces", "Traces of a latent flash kernel's "
    "pallas_call (one a signature and process, however many "
    "LatentAttention nodes call it; nothing per step); labels: pass "
    "(fwd / bwd)")

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
    if (t < _FLASH_MIN_BLOCK or nope <= 0 or nope % _SLAB_LANES
            or dv <= 0 or dv % _SLAB_LANES or rope <= 0
            or jnp.dtype(dtype).name not in ("bfloat16", "float32")):
        return False
    width = nope + _lanes(rope)
    block_q, block_k = flash_tiles(t, max(width, dv), dtype)
    mult = int(np.lcm(block_q, block_k))
    return _bwd_fuses(-(-t // mult) * mult, block_q, block_k, width, dv,
                      dtype)


def _latent_key(kv_ref, kr_ref, nope):
    """A head's key tile [k_nope | k_rope], put together in VMEM: two
    lane-aligned column groups of one value, so ONE product over both
    (the MXU sums the two inside it; two products summed by the vector
    unit cost a pass over the scores more: PERF.md section 7, PR 44)."""
    return lax.concatenate([kv_ref[:, :nope], kr_ref[...]], 1)


def _latent_scores(q_ref, k_blk, qi, ki, masked, *, block_q, block_k,
                   t_real, scale):
    """``scale (q_nope k_nope^T + q_rope k_rope^T)`` of one tile pair and,
    under ``masked``, ``_masked_scores``' keep-mask of a causal call."""
    s = lax.mul(_f32_dot(q_ref[...], k_blk, _NT), np.float32(scale))
    if not masked:
        return s, None
    shape = (block_q, block_k)
    q_pos = lax.add(lax.broadcasted_iota(jnp.int32, shape, 0),
                    _affine(qi, block_q))
    k_pos = lax.add(lax.broadcasted_iota(jnp.int32, shape, 1),
                    _affine(ki, block_k))
    return s, lax.bitwise_and(lax.lt(k_pos, np.int32(t_real)),
                              lax.ge(q_pos, k_pos))


def _latent_fwd_kernel(q_ref, kv_ref, kr_ref, o_ref, l_ref, acc, m_s, l_s,
                       *, nope, block_q, block_k, t_real, t_pad, scale):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _():
        acc[...] = jnp.zeros(acc.shape, jnp.float32)
        m_s[...] = jnp.full(m_s.shape, _NEG_INF, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)

    def body(masked):
        v_blk = kv_ref[:, nope:]
        s, mask = _latent_scores(
            q_ref, _latent_key(kv_ref, kr_ref, nope), qi, ki, masked,
            block_q=block_q, block_k=block_k, t_real=t_real, scale=scale)
        if masked:
            s = jnp.where(mask, s, jnp.float32(_NEG_INF))
        m_prev = m_s[...]
        m_cur = lax.max(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = lax.exp(lax.sub(m_prev, m_cur))
        p = lax.exp(lax.sub(s, m_cur))
        l_s[...] = lax.add(lax.mul(l_s[...], alpha),
                           jnp.sum(p, axis=1, keepdims=True))
        m_s[...] = m_cur
        acc[...] = lax.add(lax.mul(acc[...], alpha),
                           _f32_dot(p.astype(v_blk.dtype), v_blk, _NN))

    _tile_cases(body, qi, ki, block_q=block_q, block_k=block_k,
                t_real=t_real, t_pad=t_pad, causal=True)

    @pl.when(ki == pl.num_programs(3) - 1)
    def _():
        l_fin = l_s[...]
        safe_l = jnp.where(l_fin > 0, l_fin, jnp.float32(1.0))
        o_ref[...] = lax.div(acc[...], safe_l).astype(o_ref.dtype)
        l_ref[...] = lax.add(m_s[...], lax.log(safe_l))


def _latent_bwd_kernel(q_ref, kv_ref, kr_ref, o_ref, do_ref, l_ref, dq_ref,
                       dkv_ref, dkr_ref, delta, dq_acc, dk_acc, dv_acc,
                       dkr_acc, *, nope, block_q, block_k, t_real, t_pad,
                       scale):
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

    def body(masked):
        do = do_ref[...]
        k_blk = _latent_key(kv_ref, kr_ref, nope)
        s, mask = _latent_scores(
            q_ref, k_blk, qi, ki, masked, block_q=block_q, block_k=block_k,
            t_real=t_real, scale=scale)
        p = lax.exp(lax.sub(s, l_ref[...]))
        if masked:
            p = jnp.where(mask, p, jnp.float32(0.0))
        dp = _f32_dot(do, kv_ref[:, nope:], _NT)
        ds = lax.mul(p, lax.sub(dp, delta[...])).astype(q_ref.dtype)
        dv_acc[ki] = lax.add(dv_acc[ki], _f32_dot(p.astype(do.dtype), do,
                                                  _TN))
        dk = _f32_dot(ds, q_ref[...], _TN)      # [dK_nope | dK_rope]
        dk_acc[ki] = lax.add(dk_acc[ki], dk[:, :nope])
        dkr_acc[ki] = lax.add(dkr_acc[ki], dk[:, nope:])
        dq_acc[...] = lax.add(dq_acc[...], _f32_dot(ds, k_blk, _NN))

    _tile_cases(body, qi, ki, block_q=block_q, block_k=block_k,
                t_real=t_real, t_pad=t_pad, causal=True)

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


def _latent_name(which, dtype, block_q, block_k):
    return "flash2_%s_%s_q%d_k%d" % (which, _operand_label(dtype), block_q,
                                     block_k)


def _latent_specs(block_q, block_k, width, kv_width, rope, dv, steps=0):
    """Block specs of (q and dq, kv, k_rope, o and dO, lse) at
    grid step (batch, head, q tile, k step); with ``steps`` the inner
    steps walk a row's k tiles downwards. A dead step names the row's
    last live tile, already resident."""
    def k_tile(i, j):
        if steps:
            j = lax.sub(np.int32(steps - 1), j)
        return lax.min(j, _last_live_k(i, block_q, block_k))

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


_LATENT_STATIC = ("heads", "nope", "t_real", "scale", "block_q", "block_k",
                  "interpret")


@functools.partial(jax.jit, static_argnames=_LATENT_STATIC)
def _latent_fwd_call(q, kv, kr, *, heads, nope, t_real, scale, block_q,
                     block_k, interpret):
    """q [B, T, H (N + Rp)], kv [B, T, H (N + Dv)], kr [B, T, Rp] -> o
    [B, T, H Dv] and lse [B, H, T, 1] float32."""
    _M_LATENT_TRACES.inc(**{"pass": "fwd"})
    b, t_pad, _ = q.shape
    width, kv_width, rope = (x.shape[2] // n for x, n in (
        (q, heads), (kv, heads), (kr, 1)))
    dv = kv_width - nope
    q_spec, kv_spec, kr_spec, o_spec, row_spec = _latent_specs(
        block_q, block_k, width, kv_width, rope, dv)
    with _no_x64():
        return pl.pallas_call(
            functools.partial(
                _latent_fwd_kernel, nope=nope, block_q=block_q,
                block_k=block_k, t_real=t_real, t_pad=t_pad, scale=scale),
            grid=(b, heads, t_pad // block_q, t_pad // block_k),
            in_specs=[q_spec, kv_spec, kr_spec],
            out_specs=[o_spec, row_spec],
            out_shape=[
                jax.ShapeDtypeStruct((b, t_pad, heads * dv), q.dtype),
                jax.ShapeDtypeStruct((b, heads, t_pad, 1), jnp.float32)],
            scratch_shapes=[
                pltpu.VMEM((block_q, dv), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32)],
            compiler_params=pltpu.CompilerParams(dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary")),
            name=_latent_name("fwd", q.dtype, block_q, block_k),
            interpret=interpret,
        )(q, kv, kr)


@functools.partial(jax.jit, static_argnames=_LATENT_STATIC)
def _latent_bwd_call(q, kv, kr, out, do, lse, *, heads, nope, t_real,
                     scale, block_q, block_k, interpret):
    """-> dq, dkv and dkr, shaped and typed as q, kv and kr."""
    _M_LATENT_TRACES.inc(**{"pass": "bwd"})
    b, t_pad, _ = q.shape
    width, kv_width, rope = (x.shape[2] // n for x, n in (
        (q, heads), (kv, heads), (kr, 1)))
    dv = kv_width - nope
    nk = t_pad // block_k
    q_spec, kv_spec, kr_spec, o_spec, row_spec = _latent_specs(
        block_q, block_k, width, kv_width, rope, dv, steps=nk)
    with _no_x64():
        dq, dkv, dkr = pl.pallas_call(
            functools.partial(
                _latent_bwd_kernel, nope=nope, block_q=block_q,
                block_k=block_k, t_real=t_real, t_pad=t_pad, scale=scale),
            grid=(b, heads, t_pad // block_q, nk),
            in_specs=[q_spec, kv_spec, kr_spec, o_spec, o_spec, row_spec],
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
                vmem_limit_bytes=_flash_vmem_bytes(
                    block_q, block_k, max(width, dv), q.dtype.itemsize,
                    resident=(t_pad, width, dv))),
            name=_latent_name("bwd", q.dtype, block_q, block_k),
            interpret=interpret,
        )(q, kv, kr, out, do, lse)
    return dq, dkv.reshape(kv.shape), dkr.reshape(kr.shape)


def _latent_composed(q, kv, kr, heads, nope, scale):
    """``reference_attention`` over the concatenated key on the kernels'
    operands, o as they give it: the branch for every platform but the
    TPU, and what the kernels' tests hold them to."""
    b, t, _ = q.shape
    kv = kv.reshape(b, t, heads, -1)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(kr[:, :, None, :], (b, t, heads, kr.shape[2]))],
        axis=-1)
    out = reference_attention(q.reshape(b, t, heads, -1), k, kv[..., nope:],
                              causal=True, scale=scale)
    return out.reshape(b, t, -1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _latent(q, kv, kr, heads, nope, t_real, scale, block_q, block_k,
            interpret):
    return _latent_fwd(q, kv, kr, heads, nope, t_real, scale, block_q,
                       block_k, interpret)[0]


def _latent_fwd(q, kv, kr, heads, nope, t_real, scale, block_q, block_k,
                interpret):
    # one trace of the forward for the primal and the rule: see _ssd_fwd
    with jax.sharding.use_abstract_mesh(jax.sharding.get_abstract_mesh()):
        out, lse = _latent_forward(
            q, kv, kr, heads=heads, nope=nope, t_real=t_real, scale=scale,
            block_q=block_q, block_k=block_k, interpret=interpret)
    return out, (q, kv, kr, out, lse)


@functools.partial(jax.jit, static_argnames=_LATENT_STATIC)
def _latent_forward(q, kv, kr, *, interpret, **call):
    """o and lse (zeros off the TPU, where the composed form's own
    transpose is the backward)."""
    def kernels(q, kv, kr, interpret):
        return _latent_fwd_call(q, kv, kr, interpret=interpret, **call)

    def composed(q, kv, kr):
        return (_latent_composed(q, kv, kr, call["heads"], call["nope"],
                                 call["scale"]),
                jnp.zeros((q.shape[0], call["heads"], q.shape[1], 1),
                          jnp.float32))

    return _ssd_by_platform(kernels, composed, interpret, q, kv, kr)


@functools.partial(jax.jit, static_argnames=_LATENT_STATIC)
def _latent_backward(q, kv, kr, out, lse, g, *, interpret, **call):
    heads = call["heads"]

    def kernels(q, kv, kr, out, lse, g, interpret):
        return _latent_bwd_call(q, kv, kr, out, g.astype(q.dtype), lse,
                                interpret=interpret, **call)

    def composed(q, kv, kr, out, lse, g):
        return jax.vjp(
            lambda *ins: _latent_composed(*ins, heads, call["nope"],
                                          call["scale"]),
            q, kv, kr)[1](g)

    return _ssd_by_platform(kernels, composed, interpret, q, kv, kr, out,
                            lse, g)


def _latent_bwd(heads, nope, t_real, scale, block_q, block_k, interpret,
                res, g):
    return _latent_backward(
        *res, g, heads=heads, nope=nope, t_real=t_real, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret)


_latent.defvjp(_latent_fwd, _latent_bwd)


def latent_flash(q, kv, k_rope, heads, nope, scale, block_q=None,
                 block_k=None, interpret=False):
    """Causal latent attention as a Pallas kernel pair, for the shapes
    ``latent_flash_takes`` admits. Every operand token-major, a head a
    block of columns: q [B, T, H (N + Rp)] (a head's N un-rotated
    dimensions, its R rotated ones, zeros to a whole lane row), kv [B, T,
    H (N + Dv)] (the up-projection's output: a head's N key columns, then
    its Dv value columns), k_rope [B, T, Rp] (the one rotated key a
    token, every head's, padded as q's) -> [B, T, H Dv]; differentiable
    in all three. ``scale`` multiplies the scores (``1 / sqrt(N + R)``:
    the padded widths do not say R). ``block_q`` / ``block_k`` default to
    ``flash_tiles(T, max(N + Rp, Dv), dtype)`` (pass them only to pin a
    tiling: tests, benchmarks). T is padded to whole tiles (a copy; a
    sequence of whole tiles is read in place). Mosaic where the
    computation is lowered for the TPU and ``reference_attention`` over
    the concatenated key on every other platform, the choice made inside
    the ``custom_vjp`` as ``ssd_scan`` makes it; ``interpret=True`` (the
    kernels' tests) runs the kernels through the Pallas interpreter
    wherever the computation is lowered. No partitioning rule: inside a
    sharded ``jit``, call under ``shard_map``."""
    t = q.shape[1]
    if block_q is None or block_k is None:
        auto_q, auto_k = flash_tiles(
            t, max(q.shape[2] // heads, kv.shape[2] // heads - nope),
            q.dtype)
        block_q, block_k = block_q or auto_q, block_k or auto_k
    mult = int(np.lcm(block_q, block_k))
    q, kv, k_rope = (_pad_to(x, 1, mult)[0] for x in (q, kv, k_rope))
    out = _latent(q, kv, k_rope, int(heads), int(nope), t, float(scale),
                  int(block_q), int(block_k), bool(interpret))
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
    return _SLAB_LANES // int(np.gcd(width, _SLAB_LANES))


def latent_query_takes(t, heads, nope, rope):
    """Whether ``latent_query`` has blocks for the shapes: N whole lane
    rows, R a divisor of a lane row (so that a head's offset in its lane
    row leaves the zero lanes behind R room to turn into), whole steps of
    heads and of 8 tokens at least."""
    return bool(nope % _SLAB_LANES == 0 and 0 < rope <= _SLAB_LANES
                and _SLAB_LANES % rope == 0 and rope % 2 == 0
                and heads % _query_heads_a_step(nope + rope) == 0
                and t % 8 == 0)


def _latent_query_kernel(x_ref, cos_ref, sin_ref, o_ref, *, per, nope, rope,
                         interleave, inverse):
    f32 = jnp.float32
    lanes = _SLAB_LANES
    rope_p = _lanes(rope)
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
    rope_p = _lanes(rope)
    per = _query_heads_a_step(nope + rope)
    wide, narrow = per * (nope + rope_p), per * (nope + rope)
    inv_freq = 1.0 / (theta ** (np.arange(0, rope, 2, dtype=np.float64)
                                / rope))
    angles = np.arange(t, dtype=np.float64)[:, None] * inv_freq[None, :]
    angles = (np.repeat(angles, 2, axis=-1) if interleave
              else np.concatenate([angles, angles], axis=-1))
    cos, sin = (jnp.asarray(np.pad(f(angles), ((0, 0), (0, rope_p - rope))),
                            jnp.float32) for f in (np.cos, np.sin))
    block_t = next(blk for blk in (512, 256, 128, 64, 32, 16, 8)
                   if t % blk == 0)
    table = pl.BlockSpec((block_t, rope_p), lambda b_, i, h: (i, 0))
    w_in, w_out = (wide, narrow) if inverse else (narrow, wide)
    with _no_x64():
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
                                         _operand_label(x.dtype)),
            interpret=interpret,
        )(x, cos, sin)
