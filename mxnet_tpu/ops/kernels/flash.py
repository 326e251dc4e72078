"""Flash attention. What a (q tile, k tile) pair costs is decided in three
places, all below:

  operands  the blocks go to the MXU in the type they arrive in (bf16
            stays bf16, one pass; float32 stays float32) and every
            product accumulates in float32. Scores, mask, running max,
            exp, row sums, lse, delta and the accumulators are float32;
            p and dS are rounded to the operand type before the four
            products that consume them — the rounding the kernel's own
            output takes anyway.
  tiles     ``flash_tiles`` picks (block_q, block_k) from (T, D, dtype)
            and a VMEM budget: a grid step costs ~0.3 us whatever it
            holds, so a 128 x 128 tile (0.04 us of bf16 work) is all
            overhead — so much so that the operand type changes
            nothing there (PERF.md section 6, PR 28).
  causal    a dead tile (above the diagonal) is skipped by ``pl.when``
            AND its index map names the tile already resident, so no
            DMA is issued for it; the iota/compare/select mask runs only
            on tiles the diagonal crosses or that hold padding.
  window    a causal window of w keys (query i sees keys i-w+1 .. i)
            shortens the grid's inner dimension to the tiles a band
            can touch (``_band_steps``): inner step j visits block
            ``first live + j``, so tiles below the band are neither
            fetched nor stepped over; the band's lower edge is one more
            compare in the mask.
  cut tiles a square tile of B rows that the diagonal or the window's
            edge cuts corner to corner (the window 0 or a multiple of
            B / 2, B / 2 >= ``FLASH_MIN_EDGE``: tiles of 1,024; no
            padding key in it)
            runs by its quarters of B / 2 (``cut_steps``): a dead
            quarter is no step, a whole one a step without the mask,
            a cut one a step whose mask is the one compare that cuts
            it; the forward takes a row half's live quarters as one
            step. The steps are inside the tile's body, on static
            slices of the tile's blocks and accumulators: the grid,
            the walk and the DMA are the whole tile's. Kernels
            that do are named ``..._e<B / 2>``. Every other masked tile
            (unequal tiles, another window, padding keys) computes the
            mask over all of its scores, as before; a call that has no
            such tile (whole tiles of keys, every masked offset of its
            band a cut one) holds no whole-tile masked body at all.
  heads     G key/value heads serve H = G * group query heads: q head
            b reads k/v head b // group through the index maps; dkv's
            inner dimension walks the group's q heads one after
            another and sums their dK/dV in the one accumulator. The
            value width may differ from the query/key width.
  backward  one pass (``_bwd_fused_kernel``: P and dS built once a tile
            pair, five products) where dK and dV of a whole key/value
            head fit VMEM beside a step's tiles (``bwd_fuses``: shapes
            and operand type alone decide); dq and dkv (seven products,
            P and dS twice) for the sequences too long for that.
  sink      a per-head logit that joins the softmax's denominator and
            no value: applied to the forward kernel's (out, lse) by
            ``_apply_sink`` outside it; the backward kernels rebuild P
            from the lse that holds it and need nothing else.
  select    attention that is told its keys (``flash_select``: an int8
            keep-mask [B, T, T], 0 drops the pair) is a pair of its own,
            ``flashsel_fwd_`` / ``flashsel_bwd_``: a q tile holds the
            SAME ``rows`` positions of every query head of a key/value
            head's group one under another, so a step's one K / V tile
            and one [rows, block_k] mask tile serve the whole group, and
            dK / dV sum over the group inside the products. A scattered
            selection leaves no tile to skip, so every live causal tile
            is computed, and masked at the mask's own size: the tile
            becomes ONE float32 bias of [rows, block_k] (0 kept,
            ``NEG_INF`` dropped) that is added under each head's scores,
            with no select behind it (a row's maximum starts at a finite
            floor). A k tile wholly before the q tile's positions
            compares none; the tile that holds them (``rows`` positions
            against ``block_k`` keys: lopsided) has the causal compare
            in its bias and, in the backward, runs ONE step over its
            first column blocks of ``select_edge`` that a row can see
            (a kernel that does is named ``..._e<width>``). Both
            passes walk a q tile's k tiles from the diagonal down, so
            its dead steps come first. Where a key/value head's dK / dV
            do not fit VMEM whole (``select_range``: T 16,384 at a group
            of 16), the backward keeps them a RANGE of keys at a time
            (``flashsel_bwd_..._r<keys>``): the same five products, the
            q tiles walked once a range, dq a float32 partial sum a
            range that XLA adds up, so its VMEM does not grow with T.

forward / dq / the one-pass backward: grid (B*H, nq, nk), k innermost;
dkv: grid (B*G, nk, group * nq).
The output block index map ignores the innermost dimension, so Mosaic
keeps the output resident in VMEM while the inner loop accumulates into
scratch; one (block_q, block_k) tile pair is on-chip at a time. The
one-pass backward's dK / dV blocks ignore the q tile too (and the q
head within its group): they stay for the whole key/value head.
dS = P * (dP - delta), P = exp(S - L), dP = dO V^T,
delta_i = sum_d dO_id * O_id.
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import telemetry as _tm
from . import common
from .common import (
    NEG_INF, VMEM_RAISED_LIMIT, VMEM_SCOPED_DEFAULT, affine, no_x64, on_tpu,
    operand_label, pad_to, whole_lanes)

_M_FLASH_LOWERINGS = _tm.counter(
    "attention.flash_lowerings", "Traces of a flash_attention call site "
    "(one per lowering, nothing per step); labels: operands (the type "
    "the MXU is fed), block_q, block_k and, where the call has them, "
    "window, kv_heads (fewer than the query heads), dv (a value width "
    "other than the query's), edge (the rows of a quarter, where the "
    "site's cut tiles run by quarters). Where a site's backward is traced "
    "it counts once more under operands, block_q, block_k, window, bwd: "
    "fused (dq, dk and dv in one pass) or split (dq and dkv), and edge "
    "where the site has it")

# Forward, dq and dkv ask Mosaic for no more than its scoped default
# (``VMEM_SCOPED_DEFAULT``), and their tiles are chosen under it as
# ``flash_vmem_bytes`` counts a grid step. The one-pass backward keeps dK
# and dV of a whole key/value head beside such a step's tiles, so it
# states its count as its own limit (44 MiB at T 8,192 and widths 192 /
# 128 in bf16) and is taken only where that count stays under
# ``VMEM_RAISED_LIMIT``: T 16,384 at 192 / 128 counts 68 MiB and T 32,768
# 116, those keep dq and dkv.
# Largest tiles worth taking, by measurement on the v5e (T 2048-8192,
# D 64-256, bf16 and float32, causal and not: 1024 x 1024 is the fastest
# or within 1% of it everywhere, 2048 is slower again; PERF.md section 7).
FLASH_MAX_BLOCK_Q = 1024
FLASH_MAX_BLOCK_K = 1024
FLASH_MIN_BLOCK = 128
# The smallest quarter of a cut tile that is worth a step of its own, by
# measurement on the v5e (PERF.md section 7, PR 58): tiles of 1,024 by
# quarters of 512 take 15.5% off Trinity-Mini's window pair and 5-9% off
# every full-causal pair; tiles of 512 by quarters of 256 under the same
# window lose 1% (a step of 256 x 256 costs what it saves), and a
# 256 x 256 tile under a window of 128 costs 2 us whatever it computes.
FLASH_MIN_EDGE = 512


def flash_vmem_bytes(block_q, block_k, d, itemsize, resident=None,
                     select_rows=0):
    """Upper bound on the VMEM one grid step of the widest kernel holds:
    double-buffered operand and result tiles, the float32 accumulators
    and two float32 score-shaped temporaries, as dkv has them; with
    ``resident`` = (t_pad, d, dv), plus what the one-pass backward keeps
    for a whole key/value head: dK and dV in float32 scratch and their
    double-buffered output blocks, and a third score-shaped temporary;
    with ``select_rows`` (the selected pair: a head's rows of a q tile),
    plus the keep-mask's two int8 tile buffers of [select_rows, block_k]
    and the tile's float32 bias.
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
    selected one pass, T 8192, 128 / 128, bf16, 8 on 1     32.75    27.3 (*)
    selected by ranges of 8192, T 16384, bf16, 16 on 1     33.4     27.7 (*)
    ====================================================  =======  ======

    (*) what Mosaic used of its own count when compiled for a described
    v5e (``tests/test_flash_compile_tpu.py``).
    """
    lanes = whole_lanes(d)
    row_tiles = 2 * 2 * block_q * lanes * itemsize      # q, dO
    col_tiles = 2 * 4 * block_k * lanes * itemsize      # k, v, dk, dv
    acc = 2 * block_k * lanes * 4
    scores = 2 * block_q * block_k * 4
    step = row_tiles + col_tiles + acc + scores
    if select_rows:
        step += (2 + 4) * select_rows * block_k
    if resident is None:
        return step
    t_pad, d, dv = resident
    return (step + block_q * block_k * 4    # P and dS both outlive dP
            + t_pad * (whole_lanes(d) + whole_lanes(dv)) * (4 + 2 * itemsize))


def bwd_fuses(t_pad, block_q, block_k, d, dv, dtype, select_rows=0):
    """Whether the backward of a call runs as one pass: decided by the
    call's shapes and operand type alone, through what the pass would
    hold in VMEM."""
    return flash_vmem_bytes(
        block_q, block_k, max(d, dv), jnp.dtype(dtype).itemsize,
        resident=(t_pad, d, dv),
        select_rows=select_rows) <= VMEM_RAISED_LIMIT


def _one_tile(t):
    """The tile that holds a whole short sequence: the next power of
    two, 8 at the least."""
    return max(8, 1 << (t - 1).bit_length())


def flash_tiles(t, d, dtype, window=0):
    """(block_q, block_k) for a sequence of ``t`` positions, head size
    ``d`` (the wider of query and value), operands of ``dtype``: the
    largest powers of two up to the measured caps whose working set fits
    ``VMEM_SCOPED_DEFAULT`` and that pad ``t`` by no more than an eighth
    over what 128-wide tiles would. A sequence shorter than the smallest
    tile gets one tile of its own size (the next power of two, 8 at the
    least). Under a causal ``window`` the band of a q tile of B rows
    crosses two k tiles of B >= window keys, B * window of their 2 B^2
    scores live: square tiles of twice the window, where the products
    of a step weigh about what the step itself costs."""
    if t < FLASH_MIN_BLOCK:
        return _one_tile(t), _one_tile(t)
    if window:
        block = min(max(2 * _one_tile(window), FLASH_MIN_BLOCK),
                    FLASH_MAX_BLOCK_Q, _one_tile(t))
        return block, block
    itemsize = jnp.dtype(dtype).itemsize
    t_min = -(-t // FLASH_MIN_BLOCK) * FLASH_MIN_BLOCK

    def pads_little(blk):
        return -(-t // blk) * blk * 8 <= t_min * 9

    block_q = block_k = FLASH_MIN_BLOCK
    # k first: a wider k tile amortises the per-step cost without
    # lengthening the accumulators
    while (block_k * 2 <= FLASH_MAX_BLOCK_K and pads_little(block_k * 2)
           and flash_vmem_bytes(block_q, block_k * 2, d, itemsize)
           <= VMEM_SCOPED_DEFAULT):
        block_k *= 2
    while (block_q * 2 <= FLASH_MAX_BLOCK_Q and pads_little(block_q * 2)
           and flash_vmem_bytes(block_q * 2, block_k, d, itemsize)
           <= VMEM_SCOPED_DEFAULT):
        block_q *= 2
    return block_q, block_k


def _causal_block_live(qi, ki, block_q, block_k):
    """Whether k block ki intersects the causal triangle of q block qi."""
    return jax.lax.le(affine(ki, block_k),
                      affine(qi, block_q, block_q - 1))


def last_live_k(qi, block_q, block_k):
    """The last k block that ``_causal_block_live`` admits for q block
    qi: what the k/v index maps of forward and dq clamp to."""
    return jax.lax.div(affine(qi, block_q, block_q - 1),
                       np.int32(block_k))


def _first_live_q(ki, block_q, block_k):
    """The first q block that ``_causal_block_live`` admits for k block
    ki: what the q/dO/lse/delta index maps of dkv clamp to."""
    return jax.lax.div(affine(ki, block_k), np.int32(block_q))


def _first_live_k(qi, block_q, block_k, window):
    """The first k block that holds a key inside the window of q block
    qi's first row."""
    return jax.lax.div(
        jax.lax.max(affine(qi, block_q, 1 - window), np.int32(0)),
        np.int32(block_k))


def _last_live_q(ki, block_q, block_k, window, nq):
    """The last q block that holds a row whose window reaches k block
    ki's last key."""
    return jax.lax.min(
        jax.lax.div(affine(ki, block_k, block_k + window - 2),
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


# What of a tile pair one call of a kernel's body computes: ``rows`` and
# ``cols``, static slices of the q tile's and the k tile's rows, and
# ``delta``, ``q_pos - k_pos`` at its first row and column where the part
# is a step inside a cut tile (the tile offset is static there), None for
# the tile whole.
Part = collections.namedtuple("Part", "rows cols delta")
WHOLE = Part(..., ..., None)


def _cuts(delta, rows, cols, window):
    """Which bounds of ``0 <= q_pos - k_pos < window`` cut a rectangle of
    ``rows`` x ``cols`` scores whose first has ``q_pos - k_pos = delta``:
    (the diagonal does, the window's edge does); None where no score of
    it is live."""
    least, most = delta - (cols - 1), delta + rows - 1
    if most < 0 or (window and least >= window):
        return None
    return least < 0, bool(window) and most >= window


def quarter_states(block, window, offset):
    """The four quarters [row half][column half] of a square tile of
    ``block`` rows, ``offset`` = qi - ki tiles under the diagonal, of a
    causal call under ``window``: "dead", "cut" (the mask decides) or
    "whole". ``_keep``'s predicate on the quarter's corners."""
    h = block // 2

    def state(a, b):
        cuts = _cuts(offset * block + (a - b) * h, h, h, window)
        return "dead" if cuts is None else "cut" if any(cuts) else "whole"
    return [[state(a, b) for b in range(2)] for a in range(2)]


def cut_half(block_q, block_k, causal, window=0):
    """Half a tile's rows where a causal call's cut tiles run by quarters
    (``cut_steps``), 0 where they run whole: shapes alone decide. Square
    tiles whose half is ``FLASH_MIN_EDGE`` at least, and a window whose
    edge runs through the quarters' corners."""
    h = block_q // 2
    if not causal or block_q != block_k or h < FLASH_MIN_EDGE or window % h:
        return 0
    return h


def cut_steps(block, window, by_rows=False):
    """{tile offset qi - ki: [(masked, Part), ...]} of the offsets a
    causal call's band holds at which a tile has a dead quarter, and the
    steps that cover its live scores: each live quarter one, masked
    where it is cut; ``by_rows``, a row half's live quarters together
    (the forward's: a step rescales the rows' softmax state and costs
    each row its two lane reductions whatever its width, so three steps
    a tile lost to the whole tile and two win; the backward keeps no
    state a row and is the same either way: PERF.md section 7, PR 58).
    An offset with no dead quarter keeps ``tile_cases``' whole-tile
    cases."""
    h = block // 2
    steps = {}
    for offset in range(window // block + 2):
        states = quarter_states(block, window, offset)
        dead = sum(states, []).count("dead")
        if not 0 < dead < 4:
            continue
        steps[offset] = []
        for a in range(2):
            live = [b for b in range(2) if states[a][b] != "dead"]
            # (first column half, column halves) of the row half's steps
            for b, n in ([(live[0], len(live))] if by_rows and live
                         else [(b, 1) for b in live]):
                delta = offset * block + (a - b) * h
                steps[offset].append(
                    (any(_cuts(delta, h, n * h, window)),
                     Part(slice(a * h, (a + 1) * h),
                          slice(b * h, (b + n) * h), delta)))
    return steps


def part_mask(rows, cols, delta, window):
    """The keep-mask of a step inside a cut tile, None where nothing cuts
    it: ``0 <= delta + row - col < window`` by the one compare (or two)
    that its rectangle needs. The tile holds no padding key."""
    diagonal, edge = _cuts(delta, rows, cols, window)
    if not (diagonal or edge):
        return None
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)

    def shifted(by):
        return row + jnp.int32(by) if by else row
    mask = None
    if diagonal:
        mask = shifted(delta) >= col
    if edge:
        below = shifted(delta - window) < col
        mask = below if mask is None else mask & below
    return mask


def _masked_scores(q, k_blk, qi, ki, *, block_q, block_k, t_real, scale,
                   causal, window=0, masked=True, part=WHOLE):
    """The shared score/mask invariant of all three kernels:
    s = scale·q@kᵀ on the MXU plus the (padding, causal, window)
    keep-mask for this (qi, ki) block pair — None for a tile (or a step
    inside a cut tile, ``part``) that needs none. Kept in ONE place so
    forward and backward can never disagree on masking."""
    s = jnp.float32(scale) * jax.lax.dot_general(
        q, k_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [bq, bk]
    if not masked:
        return s, None
    if part.delta is not None:
        return s, part_mask(*s.shape, part.delta, window)
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


def tile_cases(body, qi, ki, *, block_q, block_k, t_real, t_pad, causal,
               window=0, edge=0, by_rows=False):
    """Run ``body(masked)`` for this tile pair at what it holds: not at
    all for a dead tile, with the mask where the diagonal or the
    window's edge crosses it or it holds padding keys, without it
    everywhere else. Under ``edge`` (``cut_half``) a tile with a dead
    quarter and no padding key runs ``body(masked, part)`` once a step
    of ``cut_steps(.., by_rows)`` instead."""
    live = needs_mask = pads = None  # None: statically "always" / "never"
    if causal:
        live = _causal_block_live(qi, ki, block_q, block_k)
        needs_mask = jax.lax.gt(affine(ki, block_k, block_k - 1),
                                affine(qi, block_q))
    if window:
        # the band's inner steps start at its first block, so a dead
        # tile lies above the diagonal (forward, dq) or below the band
        # or past the last q block (dkv)
        live = jax.lax.bitwise_and(live, jax.lax.bitwise_and(
            jax.lax.ge(affine(ki, block_k, block_k + window - 2),
                       affine(qi, block_q)),
            jax.lax.lt(qi, np.int32(t_pad // block_q))))
        needs_mask = jax.lax.bitwise_or(needs_mask, jax.lax.ge(
            affine(qi, block_q, block_q - 1),
            affine(ki, block_k, window)))
    if t_real < t_pad:
        pads = jax.lax.gt(affine(ki, block_k, block_k), np.int32(t_real))
        needs_mask = (pads if needs_mask is None
                      else jax.lax.bitwise_or(needs_mask, pads))
    if needs_mask is None:
        body(False)
        return
    unmasked = jax.lax.bitwise_not(needs_mask)
    cut = cut_steps(block_q, window, by_rows) if edge else {}
    for offset, steps in cut.items():
        here = jax.lax.eq(jax.lax.sub(qi, ki), np.int32(offset))
        if pads is not None:
            here = jax.lax.bitwise_and(here, jax.lax.bitwise_not(pads))
        needs_mask = jax.lax.bitwise_and(needs_mask,
                                         jax.lax.bitwise_not(here))

        def by_quarters(steps=steps):
            for masked, part in steps:
                body(masked, part)
        pl.when(jax.lax.bitwise_and(live, here))(by_quarters)
    if live is not None:
        needs_mask = jax.lax.bitwise_and(live, needs_mask)
        unmasked = jax.lax.bitwise_and(live, unmasked)
    # without padding keys, a call whose every masked tile is a cut one
    # (no offset of its band is cut and has no dead quarter) has no tile
    # left for the whole-tile mask: the body is not traced
    if not (cut and pads is None and all(
            "dead" in states or "cut" not in states
            for states in (sum(quarter_states(block_q, window, offset), [])
                           for offset in range(window // block_q + 2)))):
        pl.when(needs_mask)(lambda: body(True))
    pl.when(unmasked)(lambda: body(False))


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, l_ref, acc, m_s, l_s,
                *, block_q, block_k, t_real, t_pad, scale, causal, window,
                edge):
    qi = pl.program_id(1)
    j = pl.program_id(2)
    ki = _inner_k(qi, j, block_q=block_q, block_k=block_k, window=window)

    @pl.when(j == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, jnp.float32(NEG_INF))
        l_s[...] = jnp.zeros_like(l_s)

    def body(masked, part=WHOLE):
        rows, cols = part.rows, part.cols
        v_blk = v_ref[0, cols]  # [bk, Dv]
        s, mask = _masked_scores(
            q_ref[0, rows], k_ref[0, cols], qi, ki, block_q=block_q,
            block_k=block_k, t_real=t_real, scale=scale, causal=causal,
            window=window, masked=masked, part=part)
        if masked:
            s = jnp.where(mask, s, jnp.float32(NEG_INF))
        m_prev = m_s[rows]  # [bq, 1]
        m_cur = jax.lax.max(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jax.lax.exp(m_prev - m_cur)
        p = jax.lax.exp(s - m_cur)
        l_s[rows] = l_s[rows] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_s[rows] = m_cur
        acc[rows] = acc[rows] * alpha + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    tile_cases(body, qi, ki, block_q=block_q, block_k=block_k,
               t_real=t_real, t_pad=t_pad, causal=causal, window=window,
               edge=edge, by_rows=True)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        l_fin = l_s[...]
        safe_l = jnp.where(l_fin > 0, l_fin, jnp.float32(1.0))
        o_ref[0] = (acc[...] / safe_l).astype(o_ref.dtype)
        # logsumexp residual for backward
        l_ref[0] = m_s[...] + jnp.log(safe_l)


def _bwd_p_ds(q, k_blk, v_blk, do, lse, delta, qi, ki, masked, part,
              **tile):
    """P and dS of one tile pair (of its ``part``), rounded to the
    operand type: what dq and dkv both rebuild from the residuals."""
    s, mask = _masked_scores(q, k_blk, qi, ki, masked=masked, part=part,
                             **tile)
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
                   causal, window, edge):
    qi = pl.program_id(1)
    j = pl.program_id(2)
    ki = _inner_k(qi, j, block_q=block_q, block_k=block_k, window=window)

    @pl.when(j == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def body(masked, part=WHOLE):
        rows, cols = part.rows, part.cols
        k_blk = k_ref[0, cols]
        _, ds = _bwd_p_ds(
            q_ref[0, rows], k_blk, v_ref[0, cols], do_ref[0, rows],
            l_ref[0, rows], d_ref[0, rows], qi, ki, masked, part,
            block_q=block_q, block_k=block_k, t_real=t_real, scale=scale,
            causal=causal, window=window)
        dq_acc[rows] = dq_acc[rows] + jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    tile_cases(body, qi, ki, block_q=block_q, block_k=block_k,
               t_real=t_real, t_pad=t_pad, causal=causal, window=window,
               edge=edge)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        dq_ref[0] = (jnp.float32(scale) * dq_acc[...]).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, l_ref, d_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, block_q, block_k,
                    t_real, t_pad, scale, causal, window, edge, steps,
                    group):
    ki = pl.program_id(1)
    j = pl.program_id(2)
    _, qi = _inner_q(ki, j, block_q=block_q, block_k=block_k,
                     window=window, steps=steps, group=group)

    @pl.when(j == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def body(masked, part=WHOLE):
        rows, cols = part.rows, part.cols
        q = q_ref[0, rows]  # [bq, D]
        do = do_ref[0, rows]
        p, ds = _bwd_p_ds(
            q, k_ref[0, cols], v_ref[0, cols], do, l_ref[0, rows],
            d_ref[0, rows], qi, ki, masked, part, block_q=block_q,
            block_k=block_k, t_real=t_real, scale=scale, causal=causal,
            window=window)
        dv_acc[cols] = dv_acc[cols] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bk, Dv]
        dk_acc[cols] = dk_acc[cols] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    tile_cases(body, qi, ki, block_q=block_q, block_k=block_k,
               t_real=t_real, t_pad=t_pad, causal=causal, window=window,
               edge=edge)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        dk_ref[0] = (jnp.float32(scale) * dk_acc[...]).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, l_ref, d_ref, dq_ref,
                      dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *, block_q,
                      block_k, t_real, t_pad, scale, causal, window, edge,
                      group):
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

    def body(masked, part=WHOLE):
        rows, cols = part.rows, part.cols
        q = q_ref[0, rows]
        k_blk = k_ref[0, cols]
        do = do_ref[0, rows]
        p, ds = _bwd_p_ds(
            q, k_blk, v_ref[0, cols], do, l_ref[0, rows], d_ref[0, rows],
            qi, ki, masked, part, block_q=block_q, block_k=block_k,
            t_real=t_real, scale=scale, causal=causal, window=window)
        dv_acc[ki, cols] = dv_acc[ki, cols] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bk, Dv]
        dk_acc[ki, cols] = dk_acc[ki, cols] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dq_acc[rows] = dq_acc[rows] + jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    tile_cases(body, qi, ki, block_q=block_q, block_k=block_k,
               t_real=t_real, t_pad=t_pad, causal=causal, window=window,
               edge=edge)

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

def _kernel_name(which, dtype, block_q, block_k, window=0, edge=0):
    return "flash_%s_%s_q%d_k%d%s%s" % (
        which, operand_label(dtype), block_q, block_k,
        "_w%d" % window if window else "", "_e%d" % edge if edge else "")


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
                j = jax.lax.min(j, last_live_k(i, block_q, block_k))
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
                b = jax.lax.add(affine(b, group), head)
            return (b, j, 0)

        def k_idx(b, i, j):
            return (b, i, 0)
    return (pl.BlockSpec((1, block_q, d), q_idx),
            pl.BlockSpec((1, block_k, d), k_idx),
            pl.BlockSpec((1, block_q, dv), q_idx),
            pl.BlockSpec((1, block_k, dv), k_idx),
            pl.BlockSpec((1, block_q, 1), q_idx))


# One trace and one lowering a signature, however many attention sites
# of a program share it (as the latent pair, the scan and the taps keep
# theirs): a site's kernels are some hundred equations a body, and a cut
# tile's steps are bodies of their own.
_CALL_STATIC = ("t_real", "scale", "causal", "window", "block_q", "block_k",
                "interpret", "edge")


@functools.partial(jax.jit, static_argnames=_CALL_STATIC)
def fwd_call(q3, k3, v3, *, t_real, scale, causal, window, block_q,
             block_k, interpret, edge=0):
    bh, t_pad, d = q3.shape
    dv = v3.shape[2]
    group = bh // k3.shape[0]
    nq = t_pad // block_q
    nk = t_pad // block_k
    kern = functools.partial(
        _fwd_kernel, block_q=block_q, block_k=block_k, t_real=t_real,
        t_pad=t_pad, scale=scale, causal=causal, window=window, edge=edge,
    )
    q_spec, k_spec, o_spec, v_spec, row_spec = _tile_specs(
        block_q, block_k, d, dv, causal, "k", window=window, group=group)
    inner = (_band_steps(nq, nk, block_q, block_k, window, "k")
             if window else nk)
    with no_x64():
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
            name=_kernel_name("fwd", q3.dtype, block_q, block_k, window,
                              edge),
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
            vmem_limit_bytes=flash_vmem_bytes(
                block_q, block_k, max(d, dv), q3.dtype.itemsize,
                resident=(t_pad, d, dv))),
        name=_kernel_name("bwd", q3.dtype, block_q, block_k, window,
                          tile["edge"]),
        interpret=interpret,
    )(q3, k3, v3, do3, lse, delta)
    return dq, dk.reshape(bg, t_pad, d), dv_.reshape(bg, t_pad, dv)


@functools.partial(jax.jit, static_argnames=_CALL_STATIC + ("fused",))
def bwd_call(q3, k3, v3, do3, lse, delta, *, t_real, scale, causal,
             window, block_q, block_k, interpret, fused=False, edge=0):
    bh, t_pad, d = q3.shape
    bg, dv = k3.shape[0], v3.shape[2]
    group = bh // bg
    nq = t_pad // block_q
    nk = t_pad // block_k
    tile = dict(block_q=block_q, block_k=block_k, t_real=t_real,
                t_pad=t_pad, scale=scale, causal=causal, window=window,
                edge=edge)
    with no_x64():
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
            name=_kernel_name("dq", q3.dtype, block_q, block_k, window,
                              edge),
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
            name=_kernel_name("dkv", q3.dtype, block_q, block_k, window,
                              edge),
            interpret=interpret,
        )(q3, k3, v3, do3, lse, delta)
    return dq, dk, dv_


def _keep(t, causal, window):
    """The [T, T] keep-mask of a causal call (a query sees the keys up to
    its own, the last ``window`` of them under a window), None of a call
    that is not: ``reference_attention``'s and the plain pair's."""
    if not causal:
        return None
    pos = np.arange(t)
    mask = pos[:, None] >= pos[None, :]
    if window:
        mask &= pos[:, None] - pos[None, :] < window
    return mask


def _plain_scores(q3, k3, v3, t_real, scale, causal, window):
    """The real rows of the padded [BH, T, d] operands, key and value
    heads repeated over their group, and their masked float32 scores."""
    group = q3.shape[0] // k3.shape[0]
    q, k, v = (x[:, :t_real] for x in (q3, k3, v3))
    if group > 1:
        k, v = (jnp.repeat(x, group, axis=0) for x in (k, v))
    s = jnp.einsum("bqd,bkd->bqk", q, k).astype(jnp.float32) * scale
    mask = _keep(t_real, causal, window)
    if mask is not None:
        s = jnp.where(mask[None], s, NEG_INF)
    return q, k, v, s


def plain_fwd(q3, k3, v3, *, t_real, scale, causal, window, **tiles):
    """``fwd_call`` in ``jax.numpy``: ``reference_attention``'s arithmetic
    on the kernel's operands -> (out, lse), padded as the kernel's. The
    branch for every platform but the TPU."""
    _, _, v, s = _plain_scores(q3, k3, v3, t_real, scale, causal, window)
    out = jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, axis=-1),
                     v.astype(jnp.float32)).astype(q3.dtype)
    lse = jax.nn.logsumexp(s, axis=-1, keepdims=True)
    pad = ((0, 0), (0, q3.shape[1] - t_real), (0, 0))
    return jnp.pad(out, pad), jnp.pad(lse, pad)


def plain_bwd(q3, k3, v3, do3, lse, delta, *, t_real, scale, causal,
               window, **tiles):
    """``bwd_call`` in ``jax.numpy``, float32 throughout: ``p = exp(s -
    lse)``, ``ds = p * (dp - delta)`` -> (dq, dk, dv), the key and value
    heads' summed over their group, padded and typed as the kernel's."""
    f32 = jnp.float32
    bg = k3.shape[0]
    q, k, v, s = _plain_scores(q3, k3, v3, t_real, scale, causal, window)
    q, k, v, do = (x.astype(f32) for x in (q, k, v, do3[:, :t_real]))
    p = jnp.exp(s - lse[:, :t_real])
    ds = p * (jnp.einsum("bqd,bkd->bqk", do, v) - delta[:, :t_real])
    pad = ((0, 0), (0, q3.shape[1] - t_real), (0, 0))

    def shaped(x, heads):
        x = x.reshape(heads, -1, *x.shape[1:]).sum(axis=1)
        return jnp.pad(x, pad).astype(q3.dtype)

    return (shaped(scale * jnp.einsum("bqk,bkd->bqd", ds, k), q3.shape[0]),
            shaped(scale * jnp.einsum("bqk,bqd->bkd", ds, q), bg),
            shaped(jnp.einsum("bqk,bqd->bkd", p, do), bg))


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11)
)
def _flash(q3, k3, v3, sink, t_real, scale, causal, window, block_q,
           block_k, edge, interpret):
    out, _ = _flash_fwd(q3, k3, v3, sink, t_real, scale, causal, window,
                        block_q, block_k, edge, interpret)
    return out


def _static(t_real, scale, causal, window, block_q, block_k, edge):
    """A call's static arguments, as both of a pair's forms take them."""
    return dict(t_real=t_real, scale=scale, causal=causal, window=window,
                block_q=block_q, block_k=block_k, edge=edge)


def _flash_fwd(q3, k3, v3, sink, t_real, scale, causal, window, block_q,
               block_k, edge, interpret):
    call = _static(t_real, scale, causal, window, block_q, block_k, edge)
    out, lse = on_tpu(functools.partial(fwd_call, **call),
                      functools.partial(plain_fwd, **call), interpret,
                      q3, k3, v3)
    if sink is not None:
        with_sink = jnp.logaddexp(lse, sink[:, None, None])
        out = (out.astype(jnp.float32)
               * jnp.exp(lse - with_sink)).astype(out.dtype)
        lse = with_sink
    return out, (q3, k3, v3, sink, out, lse)


def _flash_bwd(t_real, scale, causal, window, block_q, block_k, edge,
               interpret, res, g):
    q3, k3, v3, sink, out, lse = res
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1,
        keepdims=True,
    )  # [BH, T, 1]
    fused = bwd_fuses(q3.shape[1], block_q, block_k, q3.shape[2],
                      v3.shape[2], q3.dtype)
    _M_FLASH_LOWERINGS.inc(
        operands=operand_label(q3.dtype), block_q=block_q,
        block_k=block_k, window=window, bwd="fused" if fused else "split",
        **({"edge": edge} if edge else {}))
    call = _static(t_real, scale, causal, window, block_q, block_k, edge)
    dq, dk, dv = on_tpu(
        functools.partial(bwd_call, fused=fused, **call),
        functools.partial(plain_bwd, **call), interpret,
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
                    block_k=None, window=0, sink=None, interpret=False):
    """Blockwise (flash) attention. q [B, T, H, D], k [B, T, G, D],
    v [B, T, G, Dv] -> [B, T, H, Dv]; H a multiple of G, query head h
    reads key/value head ``h // (H / G)``.

    Pallas MXU kernels where the computation is lowered for the TPU and
    ``reference_attention``'s arithmetic in ``jax.numpy`` on every other
    platform, the choice made inside the ``custom_vjp``
    (``common.on_tpu``); ``interpret=True`` (the kernels' tests) runs the
    kernels through the Pallas interpreter wherever the computation is
    lowered. The TPU-native replacement for what the reference delegates
    to cuDNN fused kernels (cudnn_rnn-inl.h being the closest 2017 analog
    of a fused sequence kernel).

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
    labels = dict(operands=operand_label(q.dtype), block_q=int(block_q),
                  block_k=int(block_k))
    if window or g != h or dv != d:
        labels.update(window=int(window), kv_heads=int(g), dv=int(dv))
    edge = cut_half(int(block_q), int(block_k), bool(causal), int(window))
    if edge:
        labels.update(edge=edge)
    _M_FLASH_LOWERINGS.inc(**labels)
    mult = int(np.lcm(block_q, block_k))
    q3, k3, v3 = (pad_to(_heads_first(x), 1, mult)[0] for x in (q, k, v))
    if sink is not None:
        sink = jnp.tile(sink.astype(jnp.float32), b)  # [B*H], as q3's rows
    out = _flash(q3, k3, v3, sink, t, float(scale), bool(causal),
                 int(window), int(block_q), int(block_k), edge,
                 bool(interpret))
    out = out[:, :t]
    return out.reshape(b, h, t, dv).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# the selected pair: causal attention over the keys a keep-mask names
# ---------------------------------------------------------------------------

_M_SELECT_TRACES = _tm.counter(
    "attention.select_kernel_traces", "Traces of a selected flash kernel's "
    "pallas_call (one a signature and process, however many Attention "
    "nodes call it; nothing per step); labels: pass (fwd / bwd, or "
    "bwd_ranges: the backward that keeps dK / dV a range of keys at a "
    "time, with its label range, the keys of one), group "
    "(query heads a key/value head), rows (a head's rows of a q tile), edge "
    "(the width of the column blocks a q tile's diagonal k tile runs by, 0 "
    "where it runs whole)")

# the fewest rows of an int8 tile (its sublanes pack by 32)
_KEEP_MIN_ROWS = 32
# Where a row's running maximum starts: far above NEG_INF, so that a dropped
# pair's exp(NEG_INF - m) is 0 whatever m the row has, and far below any
# score, so that a row's first kept key takes the maximum over
SELECT_FLOOR = -1e20
# The narrowest column block of a diagonal tile that is worth a case of its
# own, by measurement on the v5e (PERF.md section 7, PR 77)
SELECT_EDGE = 256


def select_tiles(t, group, d, dv, dtype):
    """(rows, block_k, t_pad) of the selected pair for ``t`` positions and
    ``group`` query heads a key/value head, or None where it has no tiles
    for the shapes: ``flash_tiles``' q tile is shared out among the
    group's heads, ``rows`` = block_q / group positions each (whole int8
    tiles of the mask), against ``flash_tiles``' k tile; T a tile at
    least, an operand type Mosaic takes, and a backward whose dK / dV fit
    VMEM: a key/value head's whole (one pass) or a range of its keys at a
    time (``select_range``)."""
    if (t < FLASH_MIN_BLOCK
            or jnp.dtype(dtype).name not in ("bfloat16", "float32")):
        return None
    block_q, block_k = flash_tiles(t, max(d, dv), dtype)
    rows = block_q // group
    if block_q % group or rows % _KEEP_MIN_ROWS:
        return None
    mult = int(np.lcm(rows, block_k))
    t_pad = -(-t // mult) * mult
    if not select_range(t_pad, rows, group, block_k, d, dv, dtype):
        return None
    return rows, block_k, t_pad


def select_range(t_pad, rows, group, block_k, d, dv, dtype):
    """The keys whose dK / dV the selected backward keeps in VMEM at a time:
    ``t_pad`` where a key/value head's fit whole beside a step's tiles (the
    one-pass backward, ``bwd_fuses``: every sequence it admitted before
    there were ranges), else the most k tiles that divide the sequence and
    fit beside a step's tiles and the float32 block of dq's partial sum;
    0 where not even one tile does. Shapes and operand type alone decide."""
    block_q, nk = rows * group, t_pad // block_k
    if bwd_fuses(t_pad, block_q, block_k, d, dv, dtype, select_rows=rows):
        return t_pad
    for tiles in range(nk - 1, 0, -1):
        if nk % tiles == 0 and select_range_vmem_bytes(
                tiles * block_k, rows, group, block_k, d, dv,
                jnp.dtype(dtype).itemsize) <= VMEM_RAISED_LIMIT:
            return tiles * block_k
    return 0


def select_range_vmem_bytes(keys, rows, group, block_k, d, dv, itemsize):
    """What a step of the ranged backward holds: ``flash_vmem_bytes`` with
    ``keys`` keys of dK / dV resident, and the two float32 buffers of dq's
    partial-sum block."""
    block_q = rows * group
    return 2 * block_q * whole_lanes(d) * 4 + flash_vmem_bytes(
        block_q, block_k, max(d, dv), itemsize, resident=(keys, d, dv),
        select_rows=rows)


def flash_select_takes(t, heads, kv_heads, d, dv, dtype):
    """Whether ``flash_select`` has kernels for the shapes (``Attention``
    asks; shapes and operand type alone decide)."""
    return (heads % kv_heads == 0
            and select_tiles(t, heads // kv_heads, d, dv, dtype) is not None)


def select_edge(which, rows, block_k):
    """The width of the column blocks a q tile's diagonal k tile runs by in
    pass ``which`` ("fwd" / "bwd"), 0 where that tile runs whole: the pass
    and the shapes alone decide, as ``cut_half`` does for the plain pair. A
    q tile holds ``rows`` positions and its diagonal tile ``block_k`` keys,
    of which a row of it can see the first ``rows (i % (block_k / rows) +
    1)`` at the most. The backward, whose five products bound it, runs ONE
    step over the first blocks of ``SELECT_EDGE`` columns (``rows`` where
    that is more) that hold them; the forward's step costs every row its
    two lane reductions and its accumulator's rescaling whatever its
    width, and a narrower one saved nothing (PERF.md section 7, PR 77): it
    runs the tile whole. ``rows == block_k`` (no group) leaves nothing to
    cut."""
    edge = max(rows, SELECT_EDGE)
    return edge if which == "bwd" and edge < block_k else 0


def _select_bias(keep, offset=None):
    """What a tile step adds to its scores, float32 at the keep-mask's own
    [rows, width]: 0 where the pair is kept and ``NEG_INF`` where it is
    dropped, so the sum is the score itself or ``NEG_INF`` itself. ``keep``
    is the int8 tile (0 drops the pair); ``offset`` the q tile's first
    position less the k tile's first key where the diagonal runs through
    the tile (the causal compare folded in), None for a tile wholly before
    the q tile."""
    kept = jax.lax.ne(keep.astype(jnp.int32), np.int32(0))
    if offset is not None:
        row = jax.lax.broadcasted_iota(jnp.int32, keep.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, keep.shape, 1)
        kept = jax.lax.bitwise_and(
            kept, jax.lax.ge(jax.lax.add(row, offset), col))
    return jnp.where(kept, jnp.float32(0.0), jnp.float32(NEG_INF))


def _select_scores(q, k_blk, bias, *, group, scale):
    """[group * rows, width] float32 scores of a q tile against a k tile's
    first ``width`` keys, the dropped pairs at ``NEG_INF``: the one bias of
    [rows, width] under each of the group's heads (the split of the leading
    dimension at whole sublane tiles moves nothing)."""
    s = jnp.float32(scale) * jax.lax.dot_general(
        q, k_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if group == 1:
        return s + bias
    return (s.reshape((group,) + bias.shape) + bias).reshape(s.shape)


def _select_steps(step, keep_ref, qi, ki, *, rows, block_k, edge):
    """Run ``step(width, bias)`` for k tile ki of q tile qi at what it
    holds: nothing for a tile past the q tile's positions; the keep-mask
    alone, no position compared, for a tile wholly before them; and the
    tile that holds them (``rows`` divides ``block_k``: there is one) over
    its first column blocks of ``edge`` that a row can see, one ``pl.when``
    case a width (``select_edge``; whole under ``edge`` 0), the causal
    compare in that step's bias."""
    first = affine(qi, rows)
    diagonal = jax.lax.div(first, np.int32(block_k))
    offset = jax.lax.sub(first, affine(ki, block_k))

    @pl.when(jax.lax.lt(ki, diagonal))
    def _():
        step(block_k, _select_bias(keep_ref[0]))

    edge = edge or block_k
    # rows divides edge: a q tile's positions lie in ONE column block
    blocks = jax.lax.add(jax.lax.div(offset, np.int32(edge)), np.int32(1))
    for n in range(1, block_k // edge + 1):
        @pl.when(jax.lax.bitwise_and(jax.lax.eq(ki, diagonal),
                                     jax.lax.eq(blocks, np.int32(n))))
        def _(width=n * edge):
            step(width, _select_bias(keep_ref[0, :, :width], offset))


def _select_fwd_kernel(q_ref, k_ref, v_ref, keep_ref, o_ref, l_ref, acc,
                       m_s, l_s, *, rows, group, block_k, scale, edge):
    """The softmax of a q tile over its k tiles from the diagonal down, as
    the backward walks them: a q tile's dead steps come first and its last
    step computes, so the next q tile's operands arrive under it (a walk
    upwards left that wait bare, 0.36 ms of a 5.3 ms call: PERF.md section
    7, PR 77)."""
    qi = pl.program_id(1)
    j = pl.program_id(2)
    ki = jax.lax.sub(pl.num_programs(2) - 1, j)

    @pl.when(j == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        # a row's maximum starts at, and so stays above, a finite floor:
        # before its first kept key exp(NEG_INF - floor) is 0 and no
        # dropped pair weighs anything, with no select on p
        m_s[...] = jnp.full_like(m_s, jnp.float32(SELECT_FLOOR))
        l_s[...] = jnp.zeros_like(l_s)

    def step(width, bias):
        v_blk = v_ref[0, :width]
        s = _select_scores(q_ref[0], k_ref[0, :width], bias, group=group,
                           scale=scale)
        m_prev = m_s[...]
        m_cur = jax.lax.max(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jax.lax.exp(m_prev - m_cur)
        p = jax.lax.exp(s - m_cur)
        l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_s[...] = m_cur
        acc[...] = acc[...] * alpha + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _select_steps(step, keep_ref, qi, ki, rows=rows, block_k=block_k,
                  edge=edge)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        l_fin = l_s[...]
        safe_l = jnp.where(l_fin > 0, l_fin, jnp.float32(1.0))
        o_ref[0] = (acc[...] / safe_l).astype(o_ref.dtype)
        l_ref[0] = m_s[...] + jnp.log(safe_l)


def _select_bwd_step(q_ref, k_ref, v_ref, do_ref, l_ref, d_ref, dq_acc,
                     dk_acc, dv_acc, at, *, group, scale):
    """``step(width, bias)`` of a backward tile step, both backwards': P and
    dS once, then the three products into dv_acc[at] and dk_acc[at] (the k
    tile's place in the resident scratch) and dq_acc."""
    def step(width, bias):
        q, k_blk, do = q_ref[0], k_ref[0, :width], do_ref[0]
        s = _select_scores(q, k_blk, bias, group=group, scale=scale)
        # a dropped pair's NEG_INF less any row's lse (the floor at the
        # least) is no weight
        p = jax.lax.exp(s - l_ref[0])
        dp = jax.lax.dot_general(
            do, v_ref[0, :width], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - d_ref[0])).astype(q.dtype)
        dv_acc[at, :width] = dv_acc[at, :width] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[at, :width] = dk_acc[at, :width] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dq_acc[...] = dq_acc[...] + jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    return step


def _select_bwd_kernel(q_ref, k_ref, v_ref, keep_ref, do_ref, l_ref, d_ref,
                       dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *,
                       rows, group, block_k, scale, edge):
    """dq, dk and dv in one pass, as ``_bwd_fused_kernel`` makes them: a q
    tile's k tiles from the diagonal down, dq in tile-sized scratch over
    the inner steps, the key/value head's dK and dV whole in float32
    scratch over its q tiles. A q tile's rows are those of every head of
    the group, so the two products into dK and dV sum over the group."""
    qi = pl.program_id(1)
    j = pl.program_id(2)
    ki = jax.lax.sub(pl.num_programs(2) - 1, j)
    first = jax.lax.bitwise_and(jax.lax.eq(qi, np.int32(0)),
                                jax.lax.eq(j, np.int32(0)))
    last = jax.lax.bitwise_and(
        jax.lax.eq(qi, pl.num_programs(1) - 1),
        jax.lax.eq(j, pl.num_programs(2) - 1))

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

    step = _select_bwd_step(q_ref, k_ref, v_ref, do_ref, l_ref, d_ref, dq_acc,
                            dk_acc, dv_acc, ki, group=group, scale=scale)
    _select_steps(step, keep_ref, qi, ki, rows=rows, block_k=block_k,
                  edge=edge)

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


def _select_name(which, dtype, block_q, block_k, group, edge):
    return "flashsel_%s_%s_q%d_k%d_g%d%s" % (
        which, operand_label(dtype), block_q, block_k, group,
        "_e%d" % edge if edge else "")


def _select_specs(rows, group, block_k, d, dv, kv_heads, steps):
    """Block specs of (a q-shaped tile, a k tile, their value-width twins,
    a statistic a row, the keep-mask's tile) at grid step (batch x
    key/value head, q tile, k step): the ``steps`` inner steps walk a q
    tile's k tiles downwards. A dead step names the q tile's last live k
    tile, the one its first live step takes, and fetches nothing more."""
    block_q = rows * group

    def k_tile(i, j):
        return jax.lax.min(jax.lax.sub(np.int32(steps - 1), j),
                           jax.lax.div(affine(i, rows, rows - 1),
                                       np.int32(block_k)))

    def q_idx(b, i, j):
        return (b, i, 0)

    def k_idx(b, i, j):
        return (b, k_tile(i, j), 0)

    def keep_idx(b, i, j):
        return (jax.lax.div(b, np.int32(kv_heads)), i, k_tile(i, j))

    return (pl.BlockSpec((1, block_q, d), q_idx),
            pl.BlockSpec((1, block_k, d), k_idx),
            pl.BlockSpec((1, block_q, dv), q_idx),
            pl.BlockSpec((1, block_k, dv), k_idx),
            pl.BlockSpec((1, block_q, 1), q_idx),
            pl.BlockSpec((1, rows, block_k), keep_idx))


_SELECT_STATIC = ("rows", "group", "block_k", "scale", "edge", "interpret")


@functools.partial(jax.jit, static_argnames=_SELECT_STATIC)
def select_fwd_call(q3, k3, v3, keep, *, rows, group, block_k, scale,
                    edge, interpret):
    """q3 [B G, group T, D] (``_group_rows``), k3 [B G, T, D], v3 [B G, T,
    Dv], keep [B, T, T] int8 -> o [B G, group T, Dv] and lse [B G, group
    T, 1] float32, rows as q3's; ``edge`` as ``select_edge`` gives it a
    pass."""
    _M_SELECT_TRACES.inc(**{"pass": "fwd"}, group=group, rows=rows,
                         edge=edge)
    bg, t_pad, d = k3.shape
    dv = v3.shape[2]
    block_q = rows * group
    q_spec, k_spec, o_spec, v_spec, row_spec, keep_spec = _select_specs(
        rows, group, block_k, d, dv, bg // keep.shape[0], t_pad // block_k)
    with no_x64():
        return pl.pallas_call(
            functools.partial(_select_fwd_kernel, rows=rows, group=group,
                              block_k=block_k, scale=scale, edge=edge),
            grid=(bg, t_pad // rows, t_pad // block_k),
            in_specs=[q_spec, k_spec, v_spec, keep_spec],
            out_specs=[o_spec, row_spec],
            out_shape=[
                jax.ShapeDtypeStruct((bg, group * t_pad, dv), q3.dtype),
                jax.ShapeDtypeStruct((bg, group * t_pad, 1), jnp.float32)],
            scratch_shapes=[
                pltpu.VMEM((block_q, dv), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=max(VMEM_SCOPED_DEFAULT, flash_vmem_bytes(
                    block_q, block_k, max(d, dv), q3.dtype.itemsize,
                    select_rows=rows))),
            name=_select_name("fwd", q3.dtype, block_q, block_k, group,
                              edge),
            interpret=interpret,
        )(q3, k3, v3, keep)


@functools.partial(jax.jit, static_argnames=_SELECT_STATIC)
def select_bwd_call(q3, k3, v3, keep, do3, lse, delta, *, rows, group,
                    block_k, scale, edge, interpret):
    """-> dq, dk and dv, shaped and typed as q3, k3 and v3."""
    _M_SELECT_TRACES.inc(**{"pass": "bwd"}, group=group, rows=rows,
                         edge=edge)
    bg, t_pad, d = k3.shape
    dv = v3.shape[2]
    block_q = rows * group
    nk = t_pad // block_k
    q_spec, k_spec, o_spec, v_spec, row_spec, keep_spec = _select_specs(
        rows, group, block_k, d, dv, bg // keep.shape[0], nk)

    def whole_head(width):
        return pl.BlockSpec((1, nk, block_k, width),
                            lambda b, i, j: (b, 0, 0, 0))

    with no_x64():
        dq, dk, dv_ = pl.pallas_call(
            functools.partial(_select_bwd_kernel, rows=rows, group=group,
                              block_k=block_k, scale=scale, edge=edge),
            grid=(bg, t_pad // rows, nk),
            in_specs=[q_spec, k_spec, v_spec, keep_spec, o_spec, row_spec,
                      row_spec],
            out_specs=[q_spec, whole_head(d), whole_head(dv)],
            out_shape=[
                jax.ShapeDtypeStruct(q3.shape, q3.dtype),
                jax.ShapeDtypeStruct((bg, nk, block_k, d), q3.dtype),
                jax.ShapeDtypeStruct((bg, nk, block_k, dv), q3.dtype)],
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
                pltpu.VMEM((nk, block_k, d), jnp.float32),
                pltpu.VMEM((nk, block_k, dv), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                # dk / dv accumulate over a key/value head's q tiles
                dimension_semantics=("parallel", "arbitrary", "arbitrary"),
                vmem_limit_bytes=flash_vmem_bytes(
                    block_q, block_k, max(d, dv), q3.dtype.itemsize,
                    resident=(t_pad, d, dv), select_rows=rows)),
            name=_select_name("bwd", q3.dtype, block_q, block_k, group,
                              edge),
            interpret=interpret,
        )(q3, k3, v3, keep, do3, lse, delta)
    return dq, dk.reshape(bg, t_pad, d), dv_.reshape(bg, t_pad, dv)


def _select_bwd_range_kernel(q_ref, k_ref, v_ref, keep_ref, do_ref, l_ref,
                             d_ref, dq_ref, dk_ref, dv_ref, dq_acc, dk_acc,
                             dv_acc, *, rows, group, block_k, scale, edge):
    """``_select_bwd_kernel`` for a sequence whose dK / dV do not fit VMEM
    whole: grid (batch x key/value head, key range, q tile, k step). A
    range's dK and dV stay in float32 scratch while EVERY q tile walks the
    range's k tiles from its diagonal (or the range's last tile) down; dq is
    a range's partial sum, written float32 a (range, q tile) and summed
    outside. A q tile wholly before the range computes nothing and writes
    zeros."""
    r = pl.program_id(1)
    qi = pl.program_id(2)
    j = pl.program_id(3)
    tiles = dk_acc.shape[0]
    local = jax.lax.sub(np.int32(tiles - 1), j)
    ki = jax.lax.add(affine(r, tiles), local)
    first = jax.lax.bitwise_and(jax.lax.eq(qi, np.int32(0)),
                                jax.lax.eq(j, np.int32(0)))
    last = jax.lax.bitwise_and(
        jax.lax.eq(qi, pl.num_programs(2) - 1),
        jax.lax.eq(j, np.int32(tiles - 1)))

    def each_k_tile(fn):
        def step(i, carry):
            fn(i)
            return carry
        jax.lax.fori_loop(0, tiles, step, 0)

    @pl.when(first)
    def _():
        def zero(i):
            dk_acc[i] = jnp.zeros(dk_acc.shape[1:], jnp.float32)
            dv_acc[i] = jnp.zeros(dv_acc.shape[1:], jnp.float32)
        each_k_tile(zero)

    @pl.when(j == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    step = _select_bwd_step(q_ref, k_ref, v_ref, do_ref, l_ref, d_ref, dq_acc,
                            dk_acc, dv_acc, local, group=group, scale=scale)
    _select_steps(step, keep_ref, qi, ki, rows=rows, block_k=block_k,
                  edge=edge)

    @pl.when(j == tiles - 1)
    def _():
        dq_ref[0, 0] = jnp.float32(scale) * dq_acc[...]

    @pl.when(last)
    def _():
        def write(i):
            dk_ref[0, i] = (jnp.float32(scale) * dk_acc[i]).astype(
                dk_ref.dtype)
            dv_ref[0, i] = dv_acc[i].astype(dv_ref.dtype)
        each_k_tile(write)


@functools.partial(jax.jit, static_argnames=_SELECT_STATIC + ("keys",))
def select_bwd_range_call(q3, k3, v3, keep, do3, lse, delta, *, rows, group,
                          block_k, scale, edge, keys, interpret):
    """``select_bwd_call`` with dK / dV resident ``keys`` keys at a time
    (``select_range``; whole k tiles that divide the sequence) -> dq, dk and
    dv, shaped and typed as q3, k3 and v3."""
    _M_SELECT_TRACES.inc(**{"pass": "bwd_ranges"}, group=group, rows=rows,
                         edge=edge, range=keys)
    bg, t_pad, d = k3.shape
    dv = v3.shape[2]
    block_q = rows * group
    nk, tiles = t_pad // block_k, keys // block_k
    ranges = nk // tiles
    kv_heads = bg // keep.shape[0]

    def q_tile(r, i):  # a q tile before the range names the range's first
        return jax.lax.max(i, affine(r, keys // rows))

    def k_tile(r, i, j):  # the walk down from the diagonal, inside the range
        top = jax.lax.min(
            jax.lax.add(affine(r, tiles), jax.lax.sub(np.int32(tiles - 1), j)),
            jax.lax.div(affine(i, rows, rows - 1), np.int32(block_k)))
        return jax.lax.max(top, affine(r, tiles))

    def q_idx(b, r, i, j):
        return (b, q_tile(r, i), 0)

    def k_idx(b, r, i, j):
        return (b, k_tile(r, i, j), 0)

    def keep_idx(b, r, i, j):
        return (jax.lax.div(b, np.int32(kv_heads)), q_tile(r, i),
                k_tile(r, i, j))

    def a_range(width):
        return pl.BlockSpec((1, tiles, block_k, width),
                            lambda b, r, i, j: (b, r, 0, 0))

    with no_x64():
        dq, dk, dv_ = pl.pallas_call(
            functools.partial(_select_bwd_range_kernel, rows=rows,
                              group=group, block_k=block_k, scale=scale,
                              edge=edge),
            grid=(bg, ranges, t_pad // rows, tiles),
            in_specs=[pl.BlockSpec((1, block_q, d), q_idx),
                      pl.BlockSpec((1, block_k, d), k_idx),
                      pl.BlockSpec((1, block_k, dv), k_idx),
                      pl.BlockSpec((1, rows, block_k), keep_idx),
                      pl.BlockSpec((1, block_q, dv), q_idx),
                      pl.BlockSpec((1, block_q, 1), q_idx),
                      pl.BlockSpec((1, block_q, 1), q_idx)],
            out_specs=[pl.BlockSpec((1, 1, block_q, d),
                                    lambda b, r, i, j: (r, b, i, 0)),
                       a_range(d), a_range(dv)],
            out_shape=[
                jax.ShapeDtypeStruct((ranges,) + q3.shape, jnp.float32),
                jax.ShapeDtypeStruct((bg, nk, block_k, d), q3.dtype),
                jax.ShapeDtypeStruct((bg, nk, block_k, dv), q3.dtype)],
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
                pltpu.VMEM((tiles, block_k, d), jnp.float32),
                pltpu.VMEM((tiles, block_k, dv), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                # dk / dv accumulate over a range's q tiles
                dimension_semantics=("parallel", "arbitrary", "arbitrary",
                                     "arbitrary"),
                vmem_limit_bytes=select_range_vmem_bytes(
                    keys, rows, group, block_k, d, dv, q3.dtype.itemsize)),
            name=_select_name("bwd", q3.dtype, block_q, block_k, group,
                              edge) + "_r%d" % keys,
            interpret=interpret,
        )(q3, k3, v3, keep, do3, lse, delta)
    return (jnp.sum(dq, axis=0).astype(q3.dtype), dk.reshape(bg, t_pad, d),
            dv_.reshape(bg, t_pad, dv))


def _group_rows(x, kv_heads, rows):
    """x [B, T, H, D] -> [B G, (T / rows) group rows, D]: the ``rows``
    positions of a q tile of each of a group's heads one under another,
    the order the selected pair's q-shaped operands have."""
    b, t, h, d = x.shape
    group = h // kv_heads
    x = x.reshape(b, t // rows, rows, kv_heads, group, d)
    return x.transpose(0, 3, 1, 4, 2, 5).reshape(b * kv_heads, group * t, d)


def _ungroup_rows(x, batch, rows, group):
    """``_group_rows``' inverse: [B G, group T, D] -> [B, T, H, D]."""
    bg, gt, d = x.shape
    kv_heads, t = bg // batch, gt // group
    x = x.reshape(batch, kv_heads, t // rows, group, rows, d)
    return x.transpose(0, 2, 4, 1, 3, 5).reshape(batch, t, kv_heads * group,
                                                 d)


# The most bytes of float32 scores [B, H, T, T] ``kept_attention`` builds
# when it is called by name: 16 heads at T 8,192. Past it a call site that
# has no selected kernels for its shapes is told so, where it used to ask
# the device for the scores (17 GB at 16 heads and T 16,384).
KEPT_SCORES_LIMIT = 1 << 32


def kept_attention(q, k, v, keep, scale):
    """``reference_attention``'s causal arithmetic under a keep-mask
    besides: q [B, T, H, D], k [B, T, G, D], v [B, T, G, Dv] (H a multiple
    of G), keep [B, T, T] (0 drops the pair) -> [B, T, H, Dv].
    Materialised float32 scores; a row that keeps no key gives zeros.
    Raises where the scores would pass ``KEPT_SCORES_LIMIT`` bytes."""
    b, t, h = q.shape[:3]
    if 4 * b * h * t * t > KEPT_SCORES_LIMIT:
        raise ValueError(
            "kept_attention: float32 scores [%d, %d, %d, %d] are %.1f GB, "
            "over KEPT_SCORES_LIMIT (%.1f GB): these shapes need the "
            "selected flash pair (flash_select_takes)"
            % (b, h, t, t, 4e-9 * b * h * t * t, 1e-9 * KEPT_SCORES_LIMIT))
    return _kept_attention(q, k, v, keep, scale)


def _kept_attention(q, k, v, keep, scale):
    """``kept_attention`` at any size: the selected pair's branch off the
    TPU, which a step lowered for the TPU traces and never runs."""
    group = q.shape[2] // k.shape[2]
    if group > 1:
        k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    live = jnp.logical_and(_keep(q.shape[1], True, 0)[None],
                           keep != 0)[:, None]
    s = jnp.where(live, s, NEG_INF)
    p = jnp.where(live, jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True),
                        np.float32(1e-30))
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(
        q.dtype)


def _select_plain(q3, k3, v3, keep, rows, group, scale):
    """``kept_attention`` on the selected pair's operands, o as the pair
    gives it: the branch for every platform but the TPU."""
    b = keep.shape[0]
    kv_heads = k3.shape[0] // b

    def heads_last(x):
        return x.reshape(b, kv_heads, *x.shape[1:]).transpose(0, 2, 1, 3)

    out = _kept_attention(_ungroup_rows(q3, b, rows, group), heads_last(k3),
                          heads_last(v3), keep, scale)
    return _group_rows(out, kv_heads, rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _select(q3, k3, v3, keep, rows, group, block_k, scale, interpret):
    return _select_fwd(q3, k3, v3, keep, rows, group, block_k, scale,
                       interpret)[0]


def _select_fwd(q3, k3, v3, keep, rows, group, block_k, scale, interpret):
    def plain(q3, k3, v3, keep):
        # lse is the kernels' own: the plain form's transpose is its
        # backward
        return (_select_plain(q3, k3, v3, keep, rows, group, scale),
                jnp.zeros(q3.shape[:2] + (1,), jnp.float32))

    out, lse = on_tpu(
        functools.partial(select_fwd_call, rows=rows, group=group,
                          block_k=block_k, scale=scale,
                          edge=select_edge("fwd", rows, block_k)),
        plain, interpret, q3, k3, v3, keep)
    return out, (q3, k3, v3, keep, out, lse)


def _select_bwd(rows, group, block_k, scale, interpret, res, g):
    q3, k3, v3, keep, out, lse = res

    def kernels(q3, k3, v3, keep, out, lse, g, interpret):
        delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1, keepdims=True)
        static = dict(rows=rows, group=group, block_k=block_k, scale=scale,
                      edge=select_edge("bwd", rows, block_k),
                      interpret=interpret)
        keys = select_range(k3.shape[1], rows, group, block_k, q3.shape[2],
                            v3.shape[2], q3.dtype)
        if keys < k3.shape[1]:
            return select_bwd_range_call(
                q3, k3, v3, keep, g.astype(q3.dtype), lse, delta, keys=keys,
                **static)
        return select_bwd_call(
            q3, k3, v3, keep, g.astype(q3.dtype), lse, delta, **static)

    def plain(q3, k3, v3, keep, out, lse, g):
        return jax.vjp(
            lambda *ins: _select_plain(*ins, keep, rows, group, scale),
            q3, k3, v3)[1](g)

    # the keep-mask is data, not a weight: no cotangent
    return on_tpu(kernels, plain, interpret, q3, k3, v3, keep, out, lse,
                  g) + (None,)


_select.defvjp(_select_fwd, _select_bwd)


def flash_select(q, k, v, keep, scale=None, interpret=False):
    """Causal attention over the keys a keep-mask names, as a Pallas kernel
    pair, for the shapes ``flash_select_takes`` admits. q [B, T, H, D], k
    [B, T, G, D], v [B, T, G, Dv] as ``flash_attention`` takes them, keep
    [B, T, T] (any integer or bool type; read as int8, 0 drops the pair)
    -> [B, T, H, Dv]: row t's softmax runs over the keys s <= t with
    ``keep[b, t, s] != 0`` only, a row that keeps none gives zeros. The
    mask is every head's and carries no gradient; q, k and v are
    differentiable.

    A q tile of ``flash_tiles``' rows is ``rows`` = block_q / (H / G)
    positions of EVERY query head of one key/value head's group
    (``_group_rows``: the one transposition ``flash_attention`` makes
    too), so a grid step loads one K tile, one V tile and one [rows,
    block_k] tile of the mask for the whole group, its two products into
    dK and dV sum over the group, and the kernels are ``flash_attention``'s
    arithmetic on [block_q, block_k] scores (``flashsel_fwd_`` /
    ``flashsel_bwd_<operands>_q<block_q>_k<block_k>_g<group>[_e<width>]``).
    Which backward runs is ``select_range``'s word, from the shapes alone:
    one pass with dK and dV of a key/value head resident in VMEM wherever
    they fit beside a step's tiles (``bwd_fuses``: at heads of 128 / 128 in
    bf16, T 8,192 at a group of 8 and up to 12,288 at a group of 16; every
    sequence the pair admitted before PR 79), and past that the same five
    products with dK and dV resident a RANGE of keys at a time
    (``select_bwd_range_call``, named ``..._r<keys>``: ranges of 8,192 keys
    at T 16,384 and a group of 16, and at any longer T: its VMEM does not
    grow with the sequence), each range walking every q tile at or after it
    and writing dq as a float32 partial sum that XLA adds up. A
    selection scattered over a row's keys leaves no tile to skip: every
    live causal tile is computed, under the mask's tile as ONE float32
    bias of [rows, block_k] added beneath each head's scores
    (``_select_bias``), and the backward runs the q tile's diagonal k tile
    over the column blocks of ``select_edge`` its rows can see
    (``_select_steps``). T is padded to whole tiles with rows and keys the
    mask drops. Mosaic where the
    computation is lowered for the TPU and ``kept_attention`` on every
    other platform, the choice made inside the ``custom_vjp``;
    ``interpret=True`` (the kernels' tests) runs the kernels through the
    Pallas interpreter. No partitioning rule: inside a sharded ``jit``,
    call under ``shard_map``."""
    b, t, h, d = q.shape
    g, dv = k.shape[2], v.shape[3]
    tiles = select_tiles(t, h // g, d, dv, q.dtype) if h % g == 0 else None
    if tiles is None:
        raise ValueError(
            "flash_select: no tiles for query %s, key %s, value %s of %s "
            "(flash_select_takes)" % (q.shape, k.shape, v.shape, q.dtype))
    rows, block_k, t_pad = tiles
    if scale is None:
        scale = 1.0 / float(np.sqrt(d))
    q, k, v = (pad_to(x, 1, t_pad)[0] for x in (q, k, v))
    keep = pad_to(pad_to(keep.astype(jnp.int8), 1, t_pad)[0], 2, t_pad)[0]
    out = _select(_group_rows(q, g, rows), _heads_first(k), _heads_first(v),
                  keep, rows, h // g, block_k, float(scale),
                  bool(interpret))
    return _ungroup_rows(out, b, rows, h // g)[:, :t]


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
    mask = _keep(t, causal, window)
    if mask is not None:
        s = jnp.where(mask[None, None], s, NEG_INF)
    if sink is not None:
        s = jnp.concatenate([s, jnp.broadcast_to(
            sink.astype(jnp.float32)[None, :, None, None], (b, h, t, 1))],
            axis=-1)
    p = jax.nn.softmax(s, axis=-1)[..., :t]
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(
        q.dtype
    )


def attention(q, k, v, causal=False, scale=None, mesh=None, window=0,
              sink=None):
    """Shared attention dispatch for every model that wants fused
    attention without hand-picking a kernel: sequence-parallel ring
    attention when the mesh shards the sequence axis, ``flash_attention``
    at T >= 128 (the Pallas kernels where the step is lowered for the
    TPU, the reference's arithmetic elsewhere), the materialized
    reference otherwise.
    q [B, T, H, D], k [B, T, G, D], v [B, T, G, Dv] -> [B, T, H, Dv];
    ``window`` and ``sink`` as ``flash_attention`` takes them."""
    if mesh is not None and mesh.shape.get("sp", 1) > 1:
        from ...parallel.ring_attention import sequence_parallel_attention

        if window or sink is not None or k.shape != q.shape:
            raise ValueError("attention: window, sink and grouped heads "
                             "are not implemented over an sp mesh")
        return sequence_parallel_attention(q, k, v, mesh, causal=causal)
    if mesh is None and q.shape[1] >= FLASH_MIN_BLOCK:
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               window=window, sink=sink,
                               interpret=common.INTERPRET)
    return reference_attention(q, k, v, causal=causal, scale=scale,
                               window=window, sink=sink)
