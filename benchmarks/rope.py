"""The rotary embedding on the chip: `ops/transformer/rotary.py::rope`'s two forms
at the shapes the cells of `BENCHMARK.json` call it with (`CELLS`: Ouro's
and OLMoE's `q` and `k`, Trinity-Mini's and Falcon-H1's `q` and `k`: heads
of 128, the rule's; LFM2's heads of 64 under `halves` alone, the form its
calls take: half a lane row has no one-pass kernel).

  halves    the two halves of a head computed apart and concatenated, under
            autodiff: what every call ran before PR 67 and what a call the
            rule refuses (`_takes_one_pass`) runs now
  one_pass  `x * [cos | cos] + turn(x) * [-sin | sin]` with the inverse
            rotation as its backward rule, the kernel pair of
            `ops/kernels/rope.py` (`rope_fwd_` / `rope_bwd_<operands>_r<row
            tile>_h<heads>_d<head_dim>`): the form a whole head of whole
            lane rows takes. The pair writes its result heads first and
            reads its cotangent so, and hands both on as transposes

Two tables. `alone`: the op by itself, token-major in and out, forward and
forward with backward (`jax.vjp` on a cotangent of `x`'s type), device 0's
busy ms a call from a profiled run, the GB/s those are of the bytes the
op needs (`x` read and the result written, each way) and their share of
the device's HBM peak, after one row `equal` a shape that says whether the
two forms' results ARE equal on the device; `kernels_ms` is the pair's own
part of a one-pass program (the rest is the transposition a token-major
reader makes XLA materialise: `Attention` is no such reader). `between`:
the op where a layer has it, `heads_first(rope(h @ w))` with the projection
in front and `Attention`'s transposition behind, forward with backward,
beside the
neighbours with no rotation (`none`): what a form fuses into a neighbour
or cancels against it costs nothing there, so a form that wins alone and
not between is no gain (the busy ms a call, and op by op).

PERF.md section 7 holds the tables (PR 67).

    chiprun -- python3 benchmarks/rope.py
    python3 benchmarks/rope.py --rehearse-cpu

The platform rule, the clock and the output file are `alone.py`'s.
"""
from unittest import mock

import alone

import jax
import jax.numpy as jnp
import numpy as np

from mxnet_tpu.ops import kernels
from mxnet_tpu.ops.transformer import rotary

THETA = 10000.0
HIDDEN = 2048
# (tokens, heads, head_dim): a cell's q or k as its projection left it
CELLS = {
    "ouro_olmoe_q_k": (4096, 16, 128),
    "trinity_mini_q": (8192, 32, 128),
    "trinity_mini_k": (8192, 4, 128),
    "falcon_h1_q": (4096, 10, 128),
    "falcon_h1_k": (4096, 2, 128),
    "lfm2_q": (8192, 32, 64),
}


def halves(x, heads):
    """`rope` with the rule switched off: the halves' lines."""
    with mock.patch.object(rotary, "_takes_one_pass",
                           lambda *args: False):
        return rotary.rope(x, heads, THETA)


def one_pass(x, heads):
    return rotary._rotate_whole_heads(x, heads, THETA)


FORMS = {"halves": halves, "one_pass": one_pass}


def _with_backward(f):
    """(g, *args) -> ``f(*args)`` and its cotangents under ``g``, one
    program."""
    def both(g, *args):
        out, pull = jax.vjp(f, *args)
        return (out,) + pull(g)
    return jax.jit(both)


def alone_table(run, name, shape, forms, row):
    t, heads, d = shape
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(1, t, heads * d), jnp.bfloat16)
    g = jnp.asarray(rng.randn(1, t, heads * d), jnp.bfloat16)
    nbytes = 2 * x.size * x.dtype.itemsize   # read x, write the result
    timed = {}
    for by, form in forms.items():
        if by == "one_pass" and kernels.rope_rows(heads, d, t,
                                                  x.dtype) is None:
            continue   # the rule refuses the shape: its calls run halves

        def op(x, form=form):
            return form(x, heads)
        timed[by] = (jax.jit(op), _with_backward(op))
    if len(timed) == len(forms):   # the forms' results where they ran
        (out, dx), (want, dx_want) = (
            [np.asarray(a, np.float32) for a in timed[by][1](g, x)]
            for by in ("one_pass", "halves"))
        row(table="equal", shape=name, forward_equal=bool((out == want).all()),
            backward_max_abs_diff=float(np.abs(dx - dx_want).max()))
    for by, (fwd, both) in run.alternate(timed, rounds=2):
        fwd_ms = alone.busy_ms(run.device_ops(fwd, x, reps=10))
        ops = run.device_ops(both, g, x, reps=10)
        both_ms = alone.busy_ms(ops)
        row(table="alone", shape=name, tokens=t, heads=heads, head_dim=d,
            form=by, fwd_ms=fwd_ms, fwd_bwd_ms=both_ms,
            kernels_ms=alone.named(alone.by_kernel(ops, "rope_"),
                                   "rope_") or None,
            fwd_gbs=alone.ratio(nbytes, fwd_ms, 1e-6),
            fwd_bwd_gbs=alone.ratio(2 * nbytes, both_ms, 1e-6),
            fwd_share_of_peak=alone.ratio(run.bound(nbytes=nbytes),
                                          fwd_ms, 100),
            fwd_bwd_share_of_peak=alone.ratio(
                run.bound(nbytes=2 * nbytes), both_ms, 100))


def between_table(run, name, shape, forms, row):
    t, heads, d = shape
    rng = np.random.RandomState(1)
    h = jnp.asarray(rng.randn(1, t, HIDDEN), jnp.bfloat16)
    w = jnp.asarray(rng.randn(HIDDEN, heads * d) / 45.0, jnp.bfloat16)
    g = jnp.asarray(rng.randn(heads, t, d), jnp.bfloat16)
    forms = dict(forms, none=lambda x, heads: x)   # the neighbours alone
    for by, form in forms.items():
        def layer(h, w, form=form):
            return kernels.flash._heads_first(
                form(h @ w, heads).reshape(1, t, heads, d))
        ops = run.device_ops(_with_backward(layer), g, h, w, reps=10)
        row(table="between", shape=name, tokens=t, heads=heads, head_dim=d,
            form=by, fwd_bwd_ms=alone.busy_ms(ops),
            ops_ms={alone.op_name(text): round(own, 4)
                    for text, own, _ in ops})


def main():
    run = alone.Run(__file__)
    run.row(device=run.kind, platform=run.platform)
    if run.rehearse:
        for table in (alone_table, between_table):
            table(run, "toy", (128, 2, 128), FORMS, run.row)
        return
    # a process's first executables run slower for their first calls
    alone_table(run, "discarded", CELLS["falcon_h1_q"], FORMS,
                lambda **kw: None)
    for name, shape in CELLS.items():
        alone_table(run, name, shape, FORMS, run.row)
    for name in ("ouro_olmoe_q_k", "trinity_mini_k"):
        between_table(run, name, CELLS[name], FORMS, run.row)
    run.save()


if __name__ == "__main__":
    main()
