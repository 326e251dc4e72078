"""The choice of a row's k largest scores on the chip:
`ops/transformer/latent.py::keep_top_k`'s forms at the shapes the cells of
`BENCHMARK.json` call it with through `KeyIndexer` (`CELLS`: Keye-VL-2's
[1, 8192, 8192] and dots3's [1, 4096, 4096], k 2,048, float32 scores with
-inf past the diagonal as `index_scores` leaves them).

  jnp_parent  what `key_indexer` ran before PR 76: the bisection on the
              scores' bits with -inf an entry like any other, `& (scores >
              -inf)` and the count behind it (the rows before the k-th tie
              on -inf, so the running count is taken on every call)
  jnp         `kernels.topk.plain_form(live=True)`: the branch of every
              platform but the TPU since PR 76 (an -inf is never kept, so
              those rows are no tie rows)
  kernel      `kernels.topk.top_k_call` (`topk_mask_f32_r<rows>_s<width>
              _k<k>_causal|_live`): the row block held in VMEM; by row
              block (32 / 64 / 128 / 256; `top_k_rows` picks 64) and with
              the passes stopped at the block's last live column
              (`causal`) or run over whole rows (`live`)
  ties        the kernel on scores rounded to quarters: every block takes
              the running count (the `pl.when` branch a trained model's
              scores take next to never)

One row `equal` a shape first (whether the kernel's mask and counts ARE the
`jnp_parent` form's on the device, element for element), then a row a form:
device 0's busy ms a call from a profiled run, the kernel's own part of it,
and the share that is of what the choice must move (ONE read of the float32
scores, one write of the int8 mask) at the device's HBM peak.

PERF.md section 7 holds the table (PR 76).

    chiprun -- python3 benchmarks/keep_top_k.py
    python3 benchmarks/keep_top_k.py --rehearse-cpu

The platform rule, the clock and the output file are `alone.py`'s.
"""
import alone

import jax
import jax.numpy as jnp
import numpy as np

from mxnet_tpu.ops.kernels import topk

# (tokens, k): a cell's scores a layer
CELLS = {"keye_vl2": (8192, 2048), "dots3": (4096, 2048)}
ROW_BLOCKS = (32, 64, 128, 256)


def causal_scores(t, quarters=False):
    """[1, t, t] float32, -inf past the diagonal, made on the device."""
    s = jax.random.normal(jax.random.PRNGKey(t), (1, t, t), jnp.float32)
    if quarters:
        s = jnp.round(s * 4) / 4
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)


def parent_form(k):
    def form(scores):
        keep = (topk.plain_form(scores, k=k)[0] != 0) & (scores > -jnp.inf)
        return (keep.astype(jnp.int8),
                jnp.sum(keep, axis=-1, keepdims=True, dtype=jnp.int32))
    return jax.jit(form)


def kernel_form(run, k, rows, causal):
    return jax.jit(lambda scores: topk.top_k_call(
        scores, k=k, rows=rows, live=True, causal=causal,
        interpret=run.rehearse))


def table(run, name, t, k, row_blocks, row):
    scores = causal_scores(t)
    forms = {"jnp_parent": parent_form(k),
             "jnp": jax.jit(lambda s: topk.plain_form(s, k=k, live=True))}
    for rows in row_blocks:
        for causal in (True, False):
            forms["kernel_r%d_%s" % (rows, "causal" if causal else "live")] = (
                kernel_form(run, k, rows, causal))
    want = forms["jnp_parent"](scores)
    row(table="equal", shape=name, **{
        by: bool(all(jax.tree.leaves(jax.tree.map(
            lambda a, b: jnp.array_equal(a, b), form(scores), want))))
        for by, form in forms.items() if by != "jnp_parent"})
    nbytes = scores.size * (4 + 1)
    for by, form in run.alternate(forms, rounds=2):
        ops = run.device_ops(form, scores, reps=5)
        ms = alone.busy_ms(ops)
        row(table="alone", shape=name, tokens=t, k=k, form=by, ms=ms,
            kernel_ms=alone.named(alone.by_kernel(ops, "topk_mask"),
                                  "topk_mask") or None,
            share_of_one_read_one_write=alone.ratio(
                run.bound(nbytes=nbytes), ms, 100))
    tied = causal_scores(t, quarters=True)
    rows = topk.top_k_rows(tied.shape, k)
    form = kernel_form(run, k, rows, True)
    row(table="equal", shape=name, ties=bool(all(jax.tree.leaves(
        jax.tree.map(lambda a, b: jnp.array_equal(a, b), form(tied),
                     forms["jnp_parent"](tied))))))
    row(table="alone", shape=name, tokens=t, k=k,
        form="ties_r%d_causal" % rows,
        ms=alone.busy_ms(run.device_ops(form, tied, reps=5)))


def main():
    run = alone.Run(__file__)
    run.row(device=run.kind, platform=run.platform)
    if run.rehearse:
        table(run, "toy", 256, 48, (32, 64), run.row)
        return
    # a process's first executables run slower for their first calls
    table(run, "discarded", 1024, 256, (64,), lambda **kw: None)
    for name, (t, k) in CELLS.items():
        table(run, name, t, k, ROW_BLOCKS, run.row)
    run.save()


if __name__ == "__main__":
    main()
