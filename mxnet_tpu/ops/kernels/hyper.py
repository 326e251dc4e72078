"""The residual streams' passes over a token block (``ops/transformer.py``'s
``HyperCoeff``, scope ``hc_coeff``, and the ``HyperMix`` that writes, scope
``hc_mix``): every pass over the stream ``x`` [tokens, n C]
reads a block of tokens ONCE into VMEM and does all that needs that block.
The read's pair first; the write's (``stream_write``, at the file's end) is
the same set-up on ``hyper_mix``'s sums.

  forward   (n + 1) S. On a block [tb, n C]: the n (n + 2) products as ``x @
            phi^T`` (``phi^T`` [n C, one lane row] the latched operand, the
            tokens the rows that stream), the sum of squares (float32), from
            both the read's row ``pre = sigmoid(alpha_0 raw / rms + b)`` and
            the read ``u = sum_j pre_j x_j`` [tb, C] off the block that is
            there. Out: ``u``; the products and the mean square token-minor
            ([32, tokens] float32: rows 0 .. n (n + 2) - 1 the products, the
            next the mean square; the [tb, 128] tile transposed in the
            kernel), which is what ``coefficients`` wants; and the same tile
            token-major [tokens, 128], the backward's residual.
  backward  (3 n + 1) S. The block of the stream, ``du``, the cotangents of
            the products and of the mean square ([32, tokens], as above) and
            the cotangent the write's backward left for the stream in; ``dx =
            draw phi + (2 / n C) dms x + pre_j du + dx_write`` out, once.
            ``dpre_j = du . x_j`` by row, and through the sigmoid into the
            read's products' and the mean square's cotangents, in the block;
            ``dphi`` accumulated over the token blocks in its float32 output
            block (the grid is sequential); a [8, tokens] output carries
            what ``bias`` and ``alpha_0`` need (rows 0 .. n - 1 the
            pre-sigmoid cotangents, row n their products with ``raw / rms``
            summed over j).
  body      the matrix products on the whole block (a weight tile is then
            used for ``tb`` rows), everything elementwise in a ``fori_loop``
            over ``_ROWS`` rows: load, cast to float32, products, sums,
            one cast, one store.
  set-up    as ``rope``'s and ``gate_norm``'s: ``jax.lax`` primitives in the
            bodies, each ``pallas_call`` behind a ``jax.jit`` (twelve nodes,
            one trace), the ``jax.numpy`` form of the same signature
            (``plain_fwd`` / ``plain_bwd``, which are ``stream_products``
            and ``stream_mix``: the sums ``ops/transformer.py``'s
            ``hyper_coeff`` and ``hyper_mix`` run) on every platform but
            the TPU, inside the ``custom_vjp``.

Products and sums float32, one rounding to the stream's type. The products'
cotangents go to the MXU in the stream's type, as XLA's own backward of
``phi x^T`` sends them at the default precision (on the chip that backward
gives the same bits for a cotangent rounded to bf16 beforehand:
``benchmarks/hyper_mix.py``'s row ``operand``, PERF.md section 7, PR 70);
float32 streams multiply at the highest.
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
    LANES, VMEM_RAISED_LIMIT, VMEM_SCOPED_DEFAULT, dot_highest, no_x64,
    on_tpu, operand_label, sum_keepdims)

# rows of the token-minor statistics (the products, the mean square; whole
# float32 sublane tiles) and of what the backward hands out for bias and
# alpha_0
STAT_ROWS = 32
SMALL_ROWS = 8
# the token blocks tried, largest first: a block's small tiles are
# transposed whole, so it is whole lane rows of tokens
_TOKEN_BLOCKS = (256, 128)
# rows a loop step of the read's bodies takes: a whole block of 128. The
# pair alone at the Xing4.0 cell's shape read 0.234 + 0.590 ms at 32 rows a
# step, 0.229 + 0.600 at 64 and 0.221 + 0.564 at 128 (PERF.md section 7,
# PR 70): a step's chain of sums over a row is latency the next step cannot
# hide
_ROWS = 128
KERNELS = ("read_fwd", "read_bwd", "write_fwd", "write_bwd")


def hyper_vmem_bytes(block, n, c, itemsize, kernel):
    """What a step of ``kernel`` (one of ``KERNELS``) holds: its blocks of
    the streams, of the [block, C] arrays and of the small tiles double
    buffered, ``phi`` (and its gradient's block) whole, its scratch, and
    for Mosaic's own a matrix product's float32 result before it is
    stored or a loop step's float32 rows, and 1 to 3 MiB. At the Xing4.0
    cell's shape (a block of 128, bf16) 15.2, 30.2, 18.8 and 28.4 MiB
    where the compiler for a described v5e needs 9.4, 25.6, 17.7 and
    27.5; over six shapes (``tests/test_hyper_kernels.py`` holds the
    table) the two backward kernels, which decide, are counted 3 to 18%
    over their need and never under."""
    stream = block * c * itemsize
    tile = 4 * block * LANES
    minor = 4 * STAT_ROWS * block
    rows = 4 * min(_ROWS, block) * c
    mib = 1024 * 1024
    if kernel == "read_fwd":
        return (2 * ((n + 1) * stream + tile + minor)
                + LANES * n * c * itemsize + rows + mib)
    if kernel == "read_bwd":
        return (2 * ((3 * n + 1) * stream + tile + 2 * minor)
                + STAT_ROWS * n * c * (itemsize + 4)
                + 3 * tile + 2 * 4 * block * c + mib)
    if kernel == "write_fwd":
        return 2 * ((2 * n + 1) * stream + minor) + tile + 3 * mib
    return (2 * ((3 * n + 2) * stream + 2 * minor) + 2 * tile
            + 4 * block * c + 2 * mib)


def hyper_takes(tokens, n, c, dtype):
    """The token block for ``n`` streams of ``c`` lanes over ``tokens``
    tokens of ``dtype``, or None where the kernels have none: a stream is
    whole lane rows, the type bf16 or float32, the products and the mean
    square fit ``STAT_ROWS`` rows and ``n + 1`` the ``SMALL_ROWS``, a block
    divides the tokens and a step of each kernel is within the raised VMEM
    limit (``hyper_vmem_bytes``). The largest block within the scoped
    default, else the smallest that fits."""
    if (c % LANES or n < 1 or n * (n + 2) >= STAT_ROWS or n >= SMALL_ROWS
            or jnp.dtype(dtype).name not in ("bfloat16", "float32")):
        return None
    itemsize = jnp.dtype(dtype).itemsize
    held = {b: max(hyper_vmem_bytes(b, n, c, itemsize, k) for k in KERNELS)
            for b in _TOKEN_BLOCKS if tokens % b == 0}
    fits = [b for b in held if held[b] <= VMEM_RAISED_LIMIT]
    if not fits:
        return None
    inside = [b for b in fits if held[b] <= VMEM_SCOPED_DEFAULT]
    return inside[0] if inside else fits[-1]


def _row_step(r, rows):
    return pl.ds(pl.multiple_of(lax.mul(r, np.int32(rows)), rows), rows)


def _spread(column, like):
    """A [rows, 1] column over ``like``'s lanes."""
    return lax.broadcast_in_dim(column, like.shape, (0, 1))


def _lane(shape, at, below=False):
    """Where a [rows, lanes] tile's lane is ``at`` (``below``: under it)."""
    lanes = lax.broadcasted_iota(jnp.int32, shape, 1)
    return (lax.lt if below else lax.eq)(lanes, np.int32(at))


def _column(tile, at):
    """Lane ``at`` of a [rows, 128] float32 tile, [rows, 1]: a masked sum,
    no slice inside a lane row."""
    return sum_keepdims(lax.select(
        _lane(tile.shape, at), tile, lax.full(tile.shape, 0, tile.dtype)), 1)


def _gate_rows(gate_ref, rows):
    """``alpha_0`` and the read's bias on lanes 0 .. n - 1, zero beyond,
    over ``rows`` rows."""
    return (lax.broadcast_in_dim(gate_ref[0:1, :], (rows, LANES), (0, 1)),
            lax.broadcast_in_dim(gate_ref[1:2, :], (rows, LANES), (0, 1)))


def _minor_to_major(tile_t, tb):
    """A token-minor [32, tb] tile as [tb, 128], zeros beyond."""
    return lax.transpose(lax.concatenate(
        [tile_t, lax.full((LANES - STAT_ROWS, tb), 0, jnp.float32)], 0),
        (1, 0))


def _read_fwd_kernel(x_ref, phit_ref, gate_ref, stats_t_ref, stats_ref,
                     u_ref, *, n, c, norm_eps):
    """One block [tb, n C] of the stream, ``phi^T`` [n C, 128] (columns
    from n (n + 2) on zero) and the gate's rows -> the statistics
    token-minor [32, tb] and token-major [tb, 128], the read [tb, C]."""
    tb = x_ref.shape[0]
    cast, f32 = lax.convert_element_type, jnp.float32
    mul, add = lax.mul, lax.add
    per = min(_ROWS, tb)
    prod = None
    for j in range(n):
        part = dot_highest(x_ref[:, j * c:(j + 1) * c],
                           phit_ref[j * c:(j + 1) * c, :], (1, 0))
        prod = part if prod is None else add(prod, part)
    stats_ref[...] = prod
    alpha, bias = _gate_rows(gate_ref, per)

    def step(r, carry):
        rows = _row_step(r, per)
        ss = None
        for j in range(n):
            v = cast(x_ref[rows, j * c:(j + 1) * c], f32)
            part = sum_keepdims(mul(v, v), 1)
            ss = part if ss is None else add(ss, part)
        ms = mul(ss, np.float32(1.0 / (n * c)))
        raw = stats_ref[rows, :]
        stat = lax.select(_lane(raw.shape, n * (n + 2)), _spread(ms, raw),
                          raw)
        stats_ref[rows, :] = stat
        rinv = lax.rsqrt(add(ms, np.float32(norm_eps)))
        pre = lax.logistic(add(mul(mul(stat, _spread(rinv, stat)), alpha),
                               bias))
        acc = None
        for j in range(n):
            v = cast(x_ref[rows, j * c:(j + 1) * c], f32)
            term = mul(_spread(_column(pre, j), v), v)
            acc = term if acc is None else add(acc, term)
        u_ref[rows, :] = cast(acc, u_ref.dtype)
        return carry

    lax.fori_loop(0, tb // per, step, np.int32(0))
    stats_t_ref[...] = lax.slice_in_dim(
        lax.transpose(stats_ref[...], (1, 0)), 0, STAT_ROWS, axis=0)


def _read_bwd_kernel(x_ref, phi_ref, gate_ref, stats_ref, g_t_ref, du_ref,
                     dxw_ref, dx_ref, dphi_ref, small_t_ref, d_ref, coef_ref,
                     small_ref, dxm_ref, *, n, c, norm_eps):
    """The same block with the statistics, their cotangents token-minor
    [32, tb], ``du`` [tb, C] and the write's cotangent of the stream [tb, n
    C] -> ``dx``; ``dphi`` [32, n C] float32 summed over the grid; what
    bias and alpha_0 need [8, tb]. Scratch: the products' cotangents
    ``d_ref``, the second sweep's columns ``coef_ref`` and ``small_ref``
    [tb, 128], one stream's products ``dxm_ref`` [tb, C], all float32."""
    tb = x_ref.shape[0]
    cast, f32 = lax.convert_element_type, jnp.float32
    mul, add = lax.mul, lax.add
    per = min(_ROWS, tb)
    dtype = x_ref.dtype
    rows_of_raw = n * (n + 2)

    @pl.when(lax.eq(pl.program_id(0), np.int32(0)))
    def _():
        dphi_ref[...] = lax.full(dphi_ref.shape, 0, f32)

    d_ref[...] = _minor_to_major(g_t_ref[...], tb)
    alpha, bias = _gate_rows(gate_ref, per)
    a0 = _column(alpha, 0)

    def first(r, carry):
        rows = _row_step(r, per)
        du = cast(du_ref[rows, :], f32)
        stat, g = stats_ref[rows, :], d_ref[rows, :]
        zero = lax.full(stat.shape, 0, f32)
        rinv = lax.rsqrt(add(_column(stat, rows_of_raw),
                             np.float32(norm_eps)))
        pre = lax.logistic(add(mul(mul(stat, _spread(rinv, stat)), alpha),
                               bias))
        dpre = zero
        for j in range(n):
            v = cast(x_ref[rows, j * c:(j + 1) * c], f32)
            dpre = lax.select(_lane(stat.shape, j), _spread(
                sum_keepdims(mul(du, v), 1), stat), dpre)
        dz = mul(dpre, mul(pre, lax.sub(np.float32(1), pre)))
        # sum_j dz_j raw_j: lane n (n + 2) of dz is zero, the mean square
        # there adds nothing
        q = sum_keepdims(mul(dz, stat), 1)
        scaled = mul(_spread(rinv, dz), mul(dz, alpha))
        d_ref[rows, :] = add(lax.select(
            _lane(stat.shape, rows_of_raw, below=True), g, zero), scaled)
        dms = lax.sub(_column(g, rows_of_raw), mul(
            mul(np.float32(0.5), mul(a0, q)), mul(rinv, mul(rinv, rinv))))
        coef_ref[rows, :] = lax.select(_lane(stat.shape, n), _spread(
            mul(dms, np.float32(2.0 / (n * c))), stat), pre)
        small_ref[rows, :] = lax.select(
            _lane(stat.shape, n), _spread(mul(q, rinv), stat), dz)
        return carry

    lax.fori_loop(0, tb // per, first, np.int32(0))
    small_t_ref[...] = lax.slice_in_dim(
        lax.transpose(small_ref[...], (1, 0)), 0, SMALL_ROWS, axis=0)
    d = d_ref[...]
    d_rows = cast(lax.slice_in_dim(d, 0, STAT_ROWS, axis=1), dtype)
    d_t = cast(lax.slice_in_dim(lax.transpose(d, (1, 0)), 0, STAT_ROWS,
                                axis=0), dtype)
    for j in range(n):
        lanes = slice(j * c, (j + 1) * c)
        dphi_ref[:, lanes] = add(dphi_ref[:, lanes], dot_highest(
            d_t, x_ref[:, lanes], (1, 0)))
        dxm_ref[...] = dot_highest(d_rows, phi_ref[:, lanes], (1, 0))

        def second(r, carry, j=j, lanes=lanes):
            rows = _row_step(r, per)
            coef = coef_ref[rows, :]
            v = cast(x_ref[rows, lanes], f32)
            out = add(dxm_ref[rows, :], mul(_spread(_column(coef, n), v), v))
            out = add(out, mul(_spread(_column(coef, j), v),
                               cast(du_ref[rows, :], f32)))
            dx_ref[rows, lanes] = cast(
                add(out, cast(dxw_ref[rows, lanes], f32)), dtype)
            return carry

        lax.fori_loop(0, tb // per, second, np.int32(0))


def _name(kernel, dtype, n, c):
    return "hc_%s_%s_n%d_c%d" % (kernel, operand_label(dtype), n, c)


def _params(kernel, block, n, c, dtype, grid="parallel"):
    return pltpu.CompilerParams(
        dimension_semantics=(grid,),
        vmem_limit_bytes=max(VMEM_SCOPED_DEFAULT, hyper_vmem_bytes(
            block, n, c, jnp.dtype(dtype).itemsize, kernel)))


def _padded_rows(phi):
    """``phi`` [n (n + 2), n C] on ``STAT_ROWS`` rows, zeros beyond."""
    return jnp.pad(phi, ((0, STAT_ROWS - phi.shape[0]), (0, 0)))


@functools.partial(jax.jit, static_argnames=("n", "norm_eps", "block",
                                              "interpret"))
def read_fwd_call(x, phi, gate, *, n, norm_eps, block, interpret):
    """x [tokens, n C], phi [n (n + 2), n C] in x's type, gate [8, 128]
    float32 (``gate_rows``) -> the statistics token-minor [32, tokens] and
    token-major [tokens, 128] float32, the read [tokens, C] in x's type."""
    tokens, width = x.shape
    c = width // n
    phit = jnp.pad(phi, ((0, LANES - phi.shape[0]), (0, 0))).T
    whole = lambda *shape: pl.BlockSpec(shape, lambda i: (0, 0))
    rows = lambda lanes: pl.BlockSpec((block, lanes), lambda i: (i, 0))
    with no_x64():
        return pl.pallas_call(
            functools.partial(_read_fwd_kernel, n=n, c=c, norm_eps=norm_eps),
            grid=(tokens // block,),
            in_specs=[rows(width), whole(width, LANES), whole(8, LANES)],
            out_specs=[pl.BlockSpec((STAT_ROWS, block), lambda i: (0, i)),
                       rows(LANES), rows(c)],
            out_shape=[
                jax.ShapeDtypeStruct((STAT_ROWS, tokens), jnp.float32),
                jax.ShapeDtypeStruct((tokens, LANES), jnp.float32),
                jax.ShapeDtypeStruct((tokens, c), x.dtype)],
            compiler_params=_params("read_fwd", block, n, c, x.dtype),
            name=_name("read_fwd", x.dtype, n, c),
            interpret=interpret,
        )(x, phit, gate)


@functools.partial(jax.jit, static_argnames=("n", "norm_eps", "block",
                                              "interpret"))
def read_bwd_call(x, phi, gate, stats, g_t, du, dxw, *, n, norm_eps, block,
                  interpret):
    """The forward's operands, its token-major statistics, their
    cotangents token-minor [32, tokens], the read's [tokens, C] and the
    stream's from its other readers [tokens, n C] -> the stream's
    cotangent, ``dphi`` [32, n C] float32 and [8] float32: the read's
    bias's cotangents and, at n, ``alpha_0``'s (the kernel's [8, tokens]
    rows summed over the tokens)."""
    tokens, width = x.shape
    c = width // n
    whole = lambda *shape: pl.BlockSpec(shape, lambda i: (0, 0))
    rows = lambda lanes: pl.BlockSpec((block, lanes), lambda i: (i, 0))
    minor = lambda height: pl.BlockSpec((height, block), lambda i: (0, i))
    tile = pltpu.VMEM((block, LANES), jnp.float32)
    with no_x64():
        dx, dphi, small_t = pl.pallas_call(
            functools.partial(_read_bwd_kernel, n=n, c=c, norm_eps=norm_eps),
            grid=(tokens // block,),
            in_specs=[rows(width), whole(STAT_ROWS, width), whole(8, LANES),
                      rows(LANES), minor(STAT_ROWS), rows(c), rows(width)],
            out_specs=[rows(width), whole(STAT_ROWS, width),
                       minor(SMALL_ROWS)],
            out_shape=[
                jax.ShapeDtypeStruct((tokens, width), x.dtype),
                jax.ShapeDtypeStruct((STAT_ROWS, width), jnp.float32),
                jax.ShapeDtypeStruct((SMALL_ROWS, tokens), jnp.float32)],
            scratch_shapes=[tile, tile, tile,
                            pltpu.VMEM((block, c), jnp.float32)],
            compiler_params=_params("read_bwd", block, n, c, x.dtype,
                                    "arbitrary"),
            name=_name("read_bwd", x.dtype, n, c),
            interpret=interpret,
        )(x, _padded_rows(phi), gate, stats, g_t, du, dxw)
    return dx, dphi, jnp.sum(small_t, axis=1)


def gate_rows(bias, alpha, n):
    """[8, 128] float32: ``alpha_0`` (row 0) and the read's bias (row 1) on
    lanes 0 .. n - 1, zeros everywhere else."""
    f32 = jnp.float32
    rows = jnp.stack([jnp.broadcast_to(alpha.astype(f32)[0], (n,)),
                      bias.astype(f32)[:n]])
    return jnp.pad(rows, ((0, 8 - 2), (0, LANES - n)))


def stream_products(x, phi):
    """The n (n + 2) products ``phi x^T`` [rows, tokens] and the mean
    square [tokens] of the stream ``x`` [tokens, n C], float32, in
    ``jax.numpy``: ``hyper_coeff``'s pass over the stream."""
    x32 = x.astype(jnp.float32)
    return (dot_highest(phi.astype(x.dtype), x, (1, 1)),
            jnp.mean(x32 * x32, axis=1))


def stream_mix(x, mix, add=None, add_mix=None):
    """``out[t, i] = sum_j mix[i, j, t] x[t, j] (+ add_mix[i, t] add[t])``
    in ``jax.numpy`` (``hyper_mix``, which says what the operands are):
    products and sums float32, one rounding."""
    m, n = mix.shape[0], mix.shape[1]
    c = x.shape[1] // n
    x32 = [x[:, j * c:(j + 1) * c].astype(jnp.float32) for j in range(n)]
    add32 = None if add is None else add.astype(jnp.float32)
    out = []
    for i in range(m):
        acc = functools.reduce(jnp.add, [
            mix[i, j][:, None] * x32[j] for j in range(n)])
        if add32 is not None:
            acc = acc + add_mix[i][:, None] * add32
        out.append(acc.astype(x.dtype))
    return out[0] if m == 1 else jnp.concatenate(out, axis=1)


def _plain(x, phi, gate, n, norm_eps):
    """The forward on ``stream_products`` and ``stream_mix``: (statistics
    [32, tokens], read)."""
    raw, ms = stream_products(x, phi)
    pre = jax.nn.sigmoid(raw[:n] * lax.rsqrt(ms + norm_eps)[None]
                         * gate[0, :n, None] + gate[1, :n, None])
    stats_t = jnp.pad(jnp.concatenate([raw, ms[None]]),
                      ((0, STAT_ROWS - raw.shape[0] - 1), (0, 0)))
    return stats_t, stream_mix(x, pre[None])


@functools.partial(jax.jit, static_argnames=("n", "norm_eps"))
def plain_fwd(x, phi, gate, *, n, norm_eps):
    """``read_fwd_call`` in ``jax.numpy``: the branch for every platform
    but the TPU, and the oracle of the kernels' tests (behind a ``jax.jit``
    as the kernels are: ``on_tpu`` traces both branches, twelve nodes one
    trace)."""
    stats_t, u = _plain(x, phi, gate, n, norm_eps)
    return stats_t, jnp.pad(stats_t.T, ((0, 0), (0, LANES - STAT_ROWS))), u


@functools.partial(jax.jit, static_argnames=("n", "norm_eps"))
def plain_bwd(x, phi, gate, stats, g_t, du, dxw, *, n, norm_eps):
    """``read_bwd_call`` in ``jax.numpy``: autodiff of ``_plain`` (the
    forward computed again)."""
    del stats
    f32 = jnp.float32
    _, pull = jax.vjp(
        lambda x, phi, gate: _plain(x, phi, gate, n, norm_eps), x, phi, gate)
    dx, dphi, dgate = pull((g_t, du))
    by_row = jnp.concatenate([dgate[1, :n], jnp.sum(dgate[0, :n])[None],
                              jnp.zeros((SMALL_ROWS - n - 1,), f32)])
    return ((dx.astype(f32) + dxw.astype(f32)).astype(x.dtype),
            _padded_rows(dphi.astype(f32)), by_row)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _stream_read(x, phi, bias, alpha, n, norm_eps, block, interpret):
    return _stream_read_fwd(x, phi, bias, alpha, n, norm_eps, block,
                            interpret)[0]


def _stream_read_fwd(x, phi, bias, alpha, n, norm_eps, block, interpret):
    def kernels(x, phi, gate, interpret):
        return read_fwd_call(x, phi, gate, n=n, norm_eps=norm_eps,
                             block=block, interpret=interpret)

    stats_t, stats, u = on_tpu(
        kernels, functools.partial(plain_fwd, n=n, norm_eps=norm_eps),
        interpret, x, phi, gate_rows(bias, alpha, n))
    return ((stats_t[:n * (n + 2)], stats_t[n * (n + 2)], u, x),
            (x, phi, bias, alpha, stats))


def _stream_read_bwd(n, norm_eps, block, interpret, saved, cotangents):
    x, phi, bias, alpha, stats = saved
    draw, dms, du, dxw = cotangents
    f32 = jnp.float32
    g_t = jnp.concatenate([
        draw.astype(f32), dms.astype(f32)[None],
        jnp.zeros((STAT_ROWS - n * (n + 2) - 1, x.shape[0]), f32)])

    def kernels(*operands, interpret):
        return read_bwd_call(*operands, n=n, norm_eps=norm_eps, block=block,
                             interpret=interpret)

    dx, dphi, by_row = on_tpu(
        kernels, functools.partial(plain_bwd, n=n, norm_eps=norm_eps),
        interpret, x, phi, gate_rows(bias, alpha, n), stats, g_t, du, dxw)
    dbias = jnp.zeros(bias.shape, f32).at[:n].set(by_row[:n])
    dalpha = jnp.zeros(alpha.shape, f32).at[0].set(by_row[n])
    return (dx, dphi[:n * (n + 2)].astype(phi.dtype),
            dbias.astype(bias.dtype), dalpha.astype(alpha.dtype))


_stream_read.defvjp(_stream_read_fwd, _stream_read_bwd)


def stream_read(x, phi, bias, alpha, n, norm_eps, interpret=False):
    """One pass over the stream ``x`` [tokens, n C]: the products ``phi x``
    [n (n + 2), tokens] and the mean square [tokens] float32 (what
    ``ops/transformer.py``'s ``coefficients`` takes), the read ``u = sum_j
    sigmoid(alpha_0 (phi_j x) / rms + bias_j) x_j`` [tokens, C] in x's
    type, and the stream itself, the same array: whoever reads the stream
    off this result (the write) sends its cotangent INTO the backward's one
    pass instead of beside it. Differentiable in x, phi, bias and alpha
    (of which it reads ``bias[:n]`` and ``alpha[0]``), for the shapes
    ``hyper_takes`` admits; Mosaic where the computation is lowered for the
    TPU and the ``jax.numpy`` forms on every other platform, the choice
    made inside the ``custom_vjp``; ``interpret=True`` (the kernels' tests)
    runs the kernels through the Pallas interpreter. No partitioning rule:
    inside a sharded ``jit``, call under ``shard_map``."""
    block = None if x.shape[1] % n else hyper_takes(
        x.shape[0], n, x.shape[1] // n, x.dtype)
    if block is None:
        raise ValueError(
            "stream_read: no block for %d streams over a stream %s (%s) "
            "(hyper_takes decides)" % (n, x.shape, x.dtype))
    return _stream_read(x, phi.astype(x.dtype), bias, alpha, int(n),
                        float(norm_eps), int(block), bool(interpret))


# -- the write ---------------------------------------------------------------
# ``out_i = sum_j res[i, j] x_j + post[i] y``: the next stream [tokens, n C]
# written ONCE, whole rows (XLA writes it a stream at a time and, where a
# kernel wants the rows whole, concatenates: a pass the bytes do not ask
# for). The mixings arrive token-minor as ONE [32, tokens] float32 tile
# (rows i n + j the carry, rows n n + i the write's column), transposed in
# the kernel; a loop step takes ``_WRITE_ROWS`` rows by ``_WRITE_LANES``
# lanes of every stream, so that each element is cast once. Eight pairs of
# the two from (16, 128) to (64, 512) and (16, 3584) read within 0.5% of
# each other alone (0.403 + 0.607 ms, 656 and 678 GB/s: PERF.md section 7,
# PR 70): the pair waits for its blocks, not for its sums.
_WRITE_ROWS = 16
_WRITE_LANES = 512


def _mix_columns(mix, n):
    """The carry's n n columns and the write's n of a [rows, 128] tile,
    each [rows, 1]."""
    return ([[_column(mix, i * n + j) for j in range(n)] for i in range(n)],
            [_column(mix, n * n + i) for i in range(n)])


def _chunks(c):
    width = _WRITE_LANES if c % _WRITE_LANES == 0 else LANES
    return [(lo, lo + width) for lo in range(0, c, width)]


def _write_fwd_kernel(x_ref, y_ref, mix_t_ref, o_ref, mix_ref, *, n, c):
    """One block: the stream [tb, n C], the sub-layer's output [tb, C] and
    the mixings [32, tb] -> the next stream [tb, n C]."""
    tb = x_ref.shape[0]
    cast, f32 = lax.convert_element_type, jnp.float32
    mul, add = lax.mul, lax.add
    per = min(_WRITE_ROWS, tb)
    mix_ref[...] = _minor_to_major(mix_t_ref[...], tb)

    def step(r, carry):
        rows = _row_step(r, per)
        res, post = _mix_columns(mix_ref[rows, :], n)
        for lo, hi in _chunks(c):
            y = cast(y_ref[rows, lo:hi], f32)
            xs = [cast(x_ref[rows, j * c + lo:j * c + hi], f32)
                  for j in range(n)]
            for i in range(n):
                acc = mul(_spread(post[i], y), y)
                for j in range(n):
                    acc = add(acc, mul(_spread(res[i][j], y), xs[j]))
                o_ref[rows, i * c + lo:i * c + hi] = cast(acc, o_ref.dtype)
        return carry

    lax.fori_loop(0, tb // per, step, np.int32(0))


def _write_bwd_kernel(x_ref, y_ref, mix_t_ref, g_ref, dx_ref, dy_ref,
                      dmix_t_ref, mix_ref, dmix_ref, *, n, c):
    """The same block with the next stream's cotangent [tb, n C] -> the
    stream's (``dx_j = sum_i res[i, j] g_i``) and the output's (``dy =
    sum_i post[i] g_i``), and the mixings' [32, tb]: ``g_i . x_j`` and
    ``g_i . y`` by row, summed a lane in the loop and over the lanes at
    its end."""
    tb = x_ref.shape[0]
    cast, f32 = lax.convert_element_type, jnp.float32
    mul, add = lax.mul, lax.add
    per = min(_WRITE_ROWS, tb)
    dtype = dx_ref.dtype
    mix_ref[...] = _minor_to_major(mix_t_ref[...], tb)

    def fold(v):
        """[rows, lanes] -> [rows, 128]: the lane rows summed."""
        out = lax.slice_in_dim(v, 0, LANES, axis=1)
        for at in range(LANES, v.shape[1], LANES):
            out = add(out, lax.slice_in_dim(v, at, at + LANES, axis=1))
        return out

    def step(r, carry):
        rows = _row_step(r, per)
        res, post = _mix_columns(mix_ref[rows, :], n)
        parts = [None] * (n * n + n)

        def into(at, v):
            parts[at] = v if parts[at] is None else add(parts[at], v)

        for lo, hi in _chunks(c):
            y = cast(y_ref[rows, lo:hi], f32)
            xs = [cast(x_ref[rows, j * c + lo:j * c + hi], f32)
                  for j in range(n)]
            gs = [cast(g_ref[rows, i * c + lo:i * c + hi], f32)
                  for i in range(n)]
            dy = None
            for i in range(n):
                term = mul(_spread(post[i], y), gs[i])
                dy = term if dy is None else add(dy, term)
                into(n * n + i, fold(mul(gs[i], y)))
            dy_ref[rows, lo:hi] = cast(dy, dtype)
            for j in range(n):
                dx = None
                for i in range(n):
                    term = mul(_spread(res[i][j], y), gs[i])
                    dx = term if dx is None else add(dx, term)
                    into(i * n + j, fold(mul(gs[i], xs[j])))
                dx_ref[rows, j * c + lo:j * c + hi] = cast(dx, dtype)
        tile = lax.full((per, LANES), 0, f32)
        for at, part in enumerate(parts):
            tile = lax.select(_lane(tile.shape, at),
                              _spread(sum_keepdims(part, 1), tile), tile)
        dmix_ref[rows, :] = tile
        return carry

    lax.fori_loop(0, tb // per, step, np.int32(0))
    dmix_t_ref[...] = lax.slice_in_dim(
        lax.transpose(dmix_ref[...], (1, 0)), 0, STAT_ROWS, axis=0)


def _write_specs(block, width, c):
    rows = lambda lanes: pl.BlockSpec((block, lanes), lambda i: (i, 0))
    return rows(width), rows(c), pl.BlockSpec((STAT_ROWS, block),
                                              lambda i: (0, i))


@functools.partial(jax.jit, static_argnames=("n", "block", "interpret"))
def write_fwd_call(x, y, mix_t, *, n, block, interpret):
    """x [tokens, n C], y [tokens, C] in x's type, mix_t [32, tokens]
    float32 (``mix_rows``) -> the next stream [tokens, n C]."""
    tokens, width = x.shape
    c = width // n
    stream, output, minor = _write_specs(block, width, c)
    with no_x64():
        return pl.pallas_call(
            functools.partial(_write_fwd_kernel, n=n, c=c),
            grid=(tokens // block,),
            in_specs=[stream, output, minor],
            out_specs=stream,
            out_shape=jax.ShapeDtypeStruct((tokens, width), x.dtype),
            scratch_shapes=[pltpu.VMEM((block, LANES), jnp.float32)],
            compiler_params=_params("write_fwd", block, n, c, x.dtype),
            name=_name("write_fwd", x.dtype, n, c),
            interpret=interpret,
        )(x, y, mix_t)


@functools.partial(jax.jit, static_argnames=("n", "block", "interpret"))
def write_bwd_call(x, y, mix_t, g, *, n, block, interpret):
    """The forward's operands and the next stream's cotangent [tokens, n
    C] -> the stream's, the output's [tokens, C] and the mixings' [32,
    tokens] float32."""
    tokens, width = x.shape
    c = width // n
    stream, output, minor = _write_specs(block, width, c)
    tile = pltpu.VMEM((block, LANES), jnp.float32)
    with no_x64():
        return pl.pallas_call(
            functools.partial(_write_bwd_kernel, n=n, c=c),
            grid=(tokens // block,),
            in_specs=[stream, output, minor, stream],
            out_specs=[stream, output, minor],
            out_shape=[
                jax.ShapeDtypeStruct((tokens, width), x.dtype),
                jax.ShapeDtypeStruct((tokens, c), x.dtype),
                jax.ShapeDtypeStruct((STAT_ROWS, tokens), jnp.float32)],
            scratch_shapes=[tile, tile],
            compiler_params=_params("write_bwd", block, n, c, x.dtype),
            name=_name("write_bwd", x.dtype, n, c),
            interpret=interpret,
        )(x, y, mix_t, g)


def mix_rows(res, post):
    """res [n, n, tokens] and post [n, tokens] as ONE [32, tokens] float32
    tile: rows i n + j the carry, rows n n + i the write's column."""
    n, tokens = post.shape
    f32 = jnp.float32
    return jnp.concatenate([
        res.astype(f32).reshape(n * n, tokens), post.astype(f32),
        jnp.zeros((STAT_ROWS - n * n - n, tokens), f32)])


@functools.partial(jax.jit, static_argnames=("n",))
def plain_write_fwd(x, y, mix_t, *, n):
    """``write_fwd_call`` in ``jax.numpy`` (``stream_mix``): the branch for
    every platform but the TPU."""
    return stream_mix(x, mix_t[:n * n].reshape(n, n, -1), y,
                      mix_t[n * n:n * n + n])


@functools.partial(jax.jit, static_argnames=("n",))
def plain_write_bwd(x, y, mix_t, g, *, n):
    """``write_bwd_call`` in ``jax.numpy``: autodiff of
    ``plain_write_fwd``."""
    _, pull = jax.vjp(functools.partial(plain_write_fwd, n=n), x, y, mix_t)
    return pull(g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _stream_write(x, res, y, post, block, interpret):
    return _stream_write_fwd(x, res, y, post, block, interpret)[0]


def _stream_write_fwd(x, res, y, post, block, interpret):
    n = post.shape[0]
    mix_t = mix_rows(res, post)

    def kernels(x, y, mix_t, interpret):
        return write_fwd_call(x, y, mix_t, n=n, block=block,
                              interpret=interpret)

    out = on_tpu(kernels, functools.partial(plain_write_fwd, n=n),
                 interpret, x, y, mix_t)
    return out, (x, y, mix_t, res, post)


def _stream_write_bwd(block, interpret, saved, g):
    x, y, mix_t, res, post = saved
    n = post.shape[0]

    def kernels(x, y, mix_t, g, interpret):
        return write_bwd_call(x, y, mix_t, g, n=n, block=block,
                              interpret=interpret)

    dx, dy, dmix_t = on_tpu(kernels, functools.partial(plain_write_bwd, n=n),
                            interpret, x, y, mix_t, g)
    return (dx, dmix_t[:n * n].reshape(res.shape).astype(res.dtype), dy,
            dmix_t[n * n:n * n + n].astype(post.dtype))


_stream_write.defvjp(_stream_write_fwd, _stream_write_bwd)


def stream_write(x, res, y, post, interpret=False):
    """The write back beside the carried streams, ``out[t, i] = sum_j
    res[i, j, t] x[t, j] + post[i, t] y[t]`` (``hyper_mix`` with m = n and
    an addend): x [tokens, n C], res [n, n, tokens] and post [n, tokens]
    float32, y [tokens, C] -> [tokens, n C] in x's type, one pass over a
    token block each way; products and sums float32, one rounding.
    Differentiable in all four, for the shapes ``hyper_takes`` admits;
    the platform rule and ``interpret`` are ``stream_read``'s."""
    n = post.shape[0]
    block = None if x.shape[1] % n else hyper_takes(
        x.shape[0], n, x.shape[1] // n, x.dtype)
    if block is None:
        raise ValueError(
            "stream_write: no block for %d streams over a stream %s (%s) "
            "(hyper_takes decides)" % (n, x.shape, x.dtype))
    return _stream_write(x, res, y.astype(x.dtype), post, int(block),
                         bool(interpret))
