"""The rotary embedding of whole heads of whole lane rows
(``ops/transformer.py::rope``'s one-pass form), one pass over the operand
each way and no half of a head ever an array:

  forward   ``y = x * C + turn(x) * S``, ``C = [cos | cos]``, ``S = [-sin |
            sin]`` ([T, D] float32 tables the heads share) and ``turn`` the
            rotation of a head's D lanes by D / 2 (its two halves change
            places): element by element the products and the sum of the
            halves' form, float32, one rounding to ``x``'s type.
  backward  ``dx = g * C - turn(g) * S``, the inverse rotation, the same
            pass; the only residuals are the tables.

The pass is the transposition ``Attention`` needs too. ``x`` arrives
token-major [B, T, H D], as the projection left it; the kernels want heads
first [B H, T, D] (``flash._heads_first``). The forward kernel reads blocks
[rows, H D] and writes each head's [rows, D] into a result laid out [B, H,
T, D]; ``rotate_heads`` hands that on as its transpose, [B, T, H D] to
every reader, and where the reader is ``Attention`` its own transposition
cancels against it and nothing is moved. The backward is the mirror image:
the cotangent is read heads first, where the flash backward wrote it, and
``dx`` is written token-major for the projection's backward products.

  grid      (batch, row tile); a block holds every head of ``rows``
            tokens, a head a static 128-lane slice of it, ``pltpu.roll``
            by D / 2 on the float32 value, the tables a [rows, D] block.
            ``rope_rows`` picks the tile from the bytes a step holds in
            VMEM; the last tile of a T that is no multiple is partial.
  set-up    as ``gate_norm``'s: ``jax.lax`` primitives in the body, the
            ``pallas_call`` behind a ``jax.jit``, the ``jax.numpy`` form
            (``plain_form``) on every platform but the TPU, inside the
            ``custom_vjp``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import LANES, no_x64, on_tpu, operand_label

# What a grid step may hold in VMEM of ``x`` and the result, both double
# buffered (half the scoped default: the tables and Mosaic's own
# temporaries take the rest), and the row tiles tried, largest first.
_BLOCK_BYTES = 8 * 1024 * 1024
_ROW_TILES = (512, 256, 128, 64, 32, 16)


def rope_rows(heads, head_dim, time, dtype):
    """The row tile for ``heads`` heads of ``head_dim`` lanes over ``time``
    tokens of ``dtype``, or None where the kernels have none: a head is
    whole lane rows, the type bf16 or float32, a block within
    ``_BLOCK_BYTES``. A ``time`` under the tile is one block."""
    if head_dim % LANES or jnp.dtype(dtype) not in (jnp.bfloat16,
                                                    jnp.float32):
        return None
    row_bytes = 4 * heads * head_dim * jnp.dtype(dtype).itemsize
    for rows in _ROW_TILES:
        if rows * row_bytes <= _BLOCK_BYTES:
            return min(rows, time)
    return None


def _rope_kernel(x_ref, c_ref, s_ref, o_ref, *, heads, width, inverse):
    c, s = c_ref[...], s_ref[...]
    combine = lax.sub if inverse else lax.add
    for h in range(heads):
        lanes = slice(h * width, (h + 1) * width)
        x = (x_ref[h] if inverse else x_ref[:, lanes]).astype(jnp.float32)
        y = combine(lax.mul(x, c), lax.mul(pltpu.roll(x, width // 2, 1), s))
        if inverse:
            o_ref[:, lanes] = y.astype(o_ref.dtype)
        else:
            o_ref[h] = y.astype(o_ref.dtype)


def _extent(x, heads, inverse):
    """(batch, time, head_dim) of ``rope_call``'s operand."""
    if inverse:
        return x.shape[0], x.shape[2], x.shape[3]
    return x.shape[0], x.shape[1], x.shape[2] // heads


@functools.partial(jax.jit, static_argnames=("heads", "rows", "inverse",
                                              "interpret"))
def rope_call(x, c, s, *, heads, rows, inverse, interpret):
    """The rotation, x [B, T, H D] -> [B, H, T, D]; under ``inverse`` the
    inverse rotation, x [B, H, T, D] -> [B, T, H D]. c, s [T, D] float32."""
    b, t, width = _extent(x, heads, inverse)
    major = pl.BlockSpec((None, rows, heads * width), lambda b, i: (b, i, 0))
    first = pl.BlockSpec((None, heads, rows, width),
                         lambda b, i: (b, 0, i, 0))
    table = pl.BlockSpec((rows, width), lambda b, i: (i, 0))
    shape = (b, t, heads * width) if inverse else (b, heads, t, width)
    with no_x64():
        return pl.pallas_call(
            functools.partial(_rope_kernel, heads=heads, width=width,
                              inverse=inverse),
            grid=(b, pl.cdiv(t, rows)),
            in_specs=[first if inverse else major, table, table],
            out_specs=major if inverse else first,
            out_shape=jax.ShapeDtypeStruct(shape, x.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            name="rope_%s_%s_r%d_h%d_d%d" % (
                "bwd" if inverse else "fwd", operand_label(x.dtype), rows,
                heads, width),
            interpret=interpret,
        )(x, c, s)


def plain_form(x, c, s, *, heads, inverse):
    """``rope_call`` in ``jax.numpy`` on the kernels' operands: the branch
    for every platform but the TPU, and the oracle of the kernels' tests."""
    b, t, width = _extent(x, heads, inverse)
    x4 = x.astype(jnp.float32)
    if inverse:
        c, s = c[None, None], -s[None, None]
    else:
        x4 = x4.reshape(b, t, heads, width)
        c, s = c[None, :, None, :], s[None, :, None, :]
    y = (x4 * c + jnp.roll(x4, width // 2, axis=-1) * s).astype(x.dtype)
    y = y.transpose(0, 2, 1, 3)
    return y.reshape(b, t, heads * width) if inverse else y


def _rotate(x, c, s, heads, rows, inverse, interpret):
    def kernels(x, c, s, interpret):
        return rope_call(x, c, s, heads=heads, rows=rows, inverse=inverse,
                         interpret=interpret)

    return on_tpu(kernels, functools.partial(
        plain_form, heads=heads, inverse=inverse), interpret, x, c, s)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _rotate_heads(x, c, s, heads, rows, interpret):
    return _rotate(x, c, s, heads, rows, False, interpret)


def _rotate_heads_fwd(x, c, s, heads, rows, interpret):
    return _rotate(x, c, s, heads, rows, False, interpret), (c, s)


def _rotate_heads_bwd(heads, rows, interpret, tables, g):
    return _rotate(g, *tables, heads, rows, True, interpret), None, None


_rotate_heads.defvjp(_rotate_heads_fwd, _rotate_heads_bwd)


def rotate_heads(x, c, s, heads, interpret=False):
    """x [B, T, H D] rotated head by head: ``x * c + turn(x) * s`` with c,
    s [T, D] float32 (``ops/transformer.py`` makes them) -> [B, T, H D] in
    x's type, differentiable in x, for the shapes ``rope_rows`` admits.
    Mosaic where the computation is lowered for the TPU and ``plain_form``
    on every other platform, the choice made inside the ``custom_vjp``;
    ``interpret=True`` (the kernels' tests) runs the kernels through the
    Pallas interpreter. The result is the transpose of what the kernel
    wrote heads first, the cotangent reaches the backward kernel as the
    transpose of what it reads: a transposition on either side cancels
    (module docstring). No partitioning rule: inside a sharded ``jit``,
    call under ``shard_map``."""
    b, t, hd = x.shape
    rows = rope_rows(heads, hd // heads, t, x.dtype)
    if rows is None:
        raise ValueError(
            "rotate_heads: no tile for %d heads of %d lanes over %d tokens "
            "(%s) (rope_rows decides)" % (heads, hd // heads, t, x.dtype))
    out = _rotate_heads(x, c, s, int(heads), int(rows), bool(interpret))
    return out.transpose(0, 2, 1, 3).reshape(b, t, hd)
