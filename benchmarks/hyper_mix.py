"""The residual streams' mixing on the chip: ONE sub-layer's pass over the
stream at the shape the cell of `BENCHMARK.json` that has one calls it with
(`CELLS`: Xing4.0's 4,096 tokens of 4 streams of 3,584), in the forms
`ops/transformer/hyper.py`'s `HyperCoeff` and `HyperMix` nodes choose between,
and in a third that no node runs.

  plain     `hyper_coeff` (the products `phi x^T`, the mean square, the
            mixings), `hyper_mix(m=1)` (the read) and `hyper_mix(m=n)` (the
            write), `jax.numpy` under autodiff: what every node ran before
            PR 70 and what a node the rule refuses
            (`_takes_one_stream_pass`) runs now
  one_pass  `hyper_coeff_read`: the products, the mean square and the read
            in one pass over a token block each way, the stream's
            cotangents summed in the backward's, and `kernels.stream_write`:
            the next stream written whole rows, one pass each way; the
            kernel pairs of `ops/kernels/hyper.py` (`hc_read_fwd_` /
            `hc_read_bwd_` / `hc_write_fwd_` / `hc_write_bwd_<operands>
            _n<streams>_c<hidden>`); the mixings stay `_coefficients`
  read      the first pair alone, the write `jax.numpy` behind it (the
            `sublayer` table only: what the kernels' whole rows cost a
            write that XLA makes a stream at a time)
  rows      `plain` with the products the other way round, `x @ phi^T`
            with the TOKENS the rows that stream past a latched `phi^T`
            (the kernels' orientation), `jax.numpy` under autodiff: the
            form ISSUE 70 holds the kernels against ("if a `jax.numpy`
            form that XLA lowers to the same passes is within a
            millisecond of the kernels, it wins and the kernels go")

One row `operand` first: what XLA's own backward of `plain`'s products does
with their float32 cotangent on a bf16 stream (the largest distance between
`dphi` and `dx` from the cotangent as it is and from the cotangent rounded
to bf16 beforehand, beside each one's largest magnitude; 0 where the MXU's
one default pass rounds the operand the same way, which is what
`hc_read_bwd_` does itself). Two tables, after one row `equal` a shape and
form (the largest distance
between the form's results and cotangents and `plain`'s on the device,
beside each one's largest magnitude). `pass`: the coefficient pass and the
read alone (results: the read, `post`, `res`). `sublayer`: the same with the
write behind it (on the read as the sub-layer's output), which is where the
stream's three cotangents meet. Each forward and forward with backward
(`jax.vjp` on cotangents of the results' types): device 0's busy ms a call
from a profiled run, the GB/s that is of the bytes the passes must move
(`bench/flops/xing4_symbol.py::hc_mix_bytes`' count, S = tokens x hidden
elements: the read (n + 1) S forward and (3 n + 1) S backward, the write (2
n + 1) S and (3 n + 2) S) and its share of the device's HBM peak;
`kernels_ms` is the kernels' own part of a program, by kernel.

PERF.md section 7 holds the tables (PR 70).

    chiprun -- python3 benchmarks/hyper_mix.py
    python3 benchmarks/hyper_mix.py --rehearse-cpu

The platform rule, the clock and the output file are `alone.py`'s.
"""
import alone

import jax
import jax.numpy as jnp
import numpy as np

from mxnet_tpu.ops import kernels
from mxnet_tpu.ops.transformer import hyper
from mxnet_tpu.ops.kernels.common import dot_highest

ITERS, EPS, CLAMP, NORM_EPS = 20, 1e-6, (-30.0, 30.0), 1e-6
# (tokens, streams, hidden)
CELLS = {"xing4": (4096, 4, 3584)}


def _rows(x, phi, bias, alpha, n):
    """`hyper_coeff` with the tokens the rows of its products."""
    with jax.named_scope("hc_coeff"):
        raw = dot_highest(x, phi.astype(x.dtype), (1, 1)).T
        x32 = x.astype(jnp.float32)
        mean_sq = jnp.mean(x32 * x32, axis=1)
    return hyper._coefficients(raw, mean_sq, bias, alpha, n, ITERS,
                                     EPS, CLAMP, NORM_EPS)


def _form(by, n):
    def parts(x, phi, bias, alpha):
        if by in ("one_pass", "read"):
            return hyper.hyper_coeff_read(
                x, phi, bias, alpha, n, ITERS, EPS, CLAMP, NORM_EPS)
        outs = (_rows(x, phi, bias, alpha, n) if by == "rows"
                else hyper.hyper_coeff(x, phi, bias, alpha, n, ITERS,
                                             EPS, CLAMP, NORM_EPS))
        return outs + (hyper.hyper_mix(x, outs[0]), x)
    return parts


def passes(by, n, write):
    """x, phi, bias, alpha -> (read, post, res), or the next stream where
    the write is behind it."""
    def f(x, phi, bias, alpha):
        _, post, res, _, read, stream = _form(by, n)(x, phi, bias, alpha)
        if not write:
            return read, post, res
        if by == "one_pass":
            return kernels.stream_write(stream, res, read, post)
        return hyper.hyper_mix(stream, res, read, post)
    return f


def _with_backward(f):
    def both(g, *args):
        out, pull = jax.vjp(f, *args)
        return (out,) + pull(g)
    return jax.jit(both)


def _operands(shape, seed):
    t, n, c = shape
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(t, n * c), jnp.bfloat16),
            jnp.asarray(0.02 * rng.randn(n * (n + 2), n * c), jnp.bfloat16),
            jnp.asarray(0.5 * rng.randn(n * (n + 2)), jnp.float32),
            jnp.asarray([0.7, 1.1, 0.9], jnp.float32))


def _cotangent(f, args, seed):
    rng = np.random.RandomState(seed)
    return jax.tree_util.tree_map(
        lambda s: jnp.asarray(rng.randn(*s.shape), s.dtype),
        jax.eval_shape(f, *args))


def table(run, name, shape, write, row):
    t, n, c = shape
    args = _operands(shape, 0)
    elements = (n + 1, 3 * n + 1) if not write else (3 * n + 2, 6 * n + 3)
    fwd_bytes, bwd_bytes = (2 * e * t * c for e in elements)
    g = _cotangent(passes("plain", n, write), args, 1)
    timed = {}
    for by in (("plain", "rows", "read", "one_pass") if write
               else ("plain", "rows", "one_pass")):
        if by in ("read", "one_pass") and kernels.hyper_takes(
                t, n, c, args[0].dtype) is None:
            continue   # the rule refuses the shape: its nodes run plain
        f = passes(by, n, write)
        timed[by] = (jax.jit(f), _with_backward(f))
    kind = "sublayer" if write else "pass"
    want = jax.tree_util.tree_leaves(timed["plain"][1](g, *args))
    for by in timed:
        if by == "plain":
            continue
        got = jax.tree_util.tree_leaves(timed[by][1](g, *args))
        row(table="equal", shape=name, passes=kind, form=by, max_abs_diff=[
            float(jnp.abs(a.astype(jnp.float32)
                          - b.astype(jnp.float32)).max())
            for a, b in zip(got, want)], max_abs=[
                float(jnp.abs(b.astype(jnp.float32)).max()) for b in want])
    for by, (fwd, both) in run.alternate(timed, rounds=2):
        fwd_ms = alone.busy_ms(run.device_ops(fwd, *args, reps=10))
        ops = run.device_ops(both, g, *args, reps=10)
        both_ms = alone.busy_ms(ops)
        total = fwd_bytes + bwd_bytes
        row(table=kind, shape=name, tokens=t, streams=n, hidden=c, form=by,
            fwd_ms=fwd_ms, fwd_bwd_ms=both_ms,
            kernels_ms={k: round(v, 4) for k, v in alone.by_kernel(
                ops, "hc_read_", "hc_write_").items()},
            fwd_gbs=alone.ratio(fwd_bytes, fwd_ms, 1e-6),
            fwd_bwd_gbs=alone.ratio(total, both_ms, 1e-6),
            fwd_share_of_peak=alone.ratio(run.bound(nbytes=fwd_bytes),
                                          fwd_ms, 100),
            fwd_bwd_share_of_peak=alone.ratio(run.bound(nbytes=total),
                                              both_ms, 100))


def operand(shape, row):
    """XLA's backward of `plain`'s products on the cotangent as it is and
    on the cotangent rounded to the stream's type beforehand."""
    t, n, c = shape
    x, phi, _, _ = _operands(shape, 0)
    g = jnp.asarray(np.random.RandomState(2).randn(n * (n + 2), t),
                    jnp.float32)

    @jax.jit
    def pulled(x, phi, g):
        _, pull = jax.vjp(lambda x, phi: kernels.stream_products(x, phi)[0],
                          x, phi)
        return pull(g)
    f32 = jnp.float32
    want = pulled(x, phi, g)
    got = pulled(x, phi, g.astype(x.dtype).astype(f32))
    row(table="operand", tokens=t, streams=n, hidden=c,
        results=["dx", "dphi"], max_abs_diff=[
            float(jnp.abs(a.astype(f32) - b.astype(f32)).max())
            for a, b in zip(got, want)],
        max_abs=[float(jnp.abs(b.astype(f32)).max()) for b in want])


def main():
    run = alone.Run(__file__)
    run.row(device=run.kind, platform=run.platform)
    if run.rehearse:
        operand((256, 4, 128), run.row)
        for write in (False, True):
            table(run, "toy", (256, 4, 128), write, run.row)
        return
    # a process's first executables run slower for their first calls
    table(run, "discarded", CELLS["xing4"], False, lambda **kw: None)
    for name, shape in CELLS.items():
        operand(shape, run.row)
        for write in (False, True):
            table(run, name, shape, write, run.row)
    run.save()


if __name__ == "__main__":
    main()
