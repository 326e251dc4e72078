"""The embedding's gradient on the chip: XLA's scatter-add (the transpose
autodiff gives `jnp.take`, what `Embedding` ran before PR 50) against the
backward rule of `ops/indexing.py::_lookup` (one int32 sort, a gather of
the cotangent's rows into id order and `ops.kernels.sorted_segment_sum`:
the grouped matmul's wgrad kernel over an exact 0 / 1 table), at the seven
LM cells' shapes (`CELLS`: rows looked up a step, the table's width, its
rows) and at `PROBES`, which move one of the three at a time to say what a
scattered row's cost follows. Ids are uniform over the table, as the cells'
traffic draws them. Device 0's busy ms a call from a profiled run, us a
row, and for the rule the kernel's share of it; `write_ms` is the table's
bytes written once at the device's HBM peak.

PERF.md section 5 holds the table (PR 50).

    chiprun -- python3 benchmarks/embedding_grad.py
    python3 benchmarks/embedding_grad.py --rehearse-cpu

The platform rule, the clock and the output file are `alone.py`'s.
"""
import alone

import jax
import jax.numpy as jnp
import numpy as np

from mxnet_tpu.ops import indexing

# (rows, width, vocab): Falcon-H1, Olmo-Hybrid, OLMoE, MiMo-V2-Flash,
# Kanana-2, Nemotron-3-Nano, LFM2
CELLS = {
    "falcon_h1": (4096, 5120, 32640),
    "olmo_hybrid": (4096, 3840, 12544),
    "olmoe": (4096, 2048, 50304),
    "mimo_v2_flash": (4096, 4096, 19072),
    "kanana2": (8192, 2048, 16032),
    "nemotron3_nano": (8192, 2688, 16384),
    "lfm2": (8192, 2048, 8192),
}
# Falcon-H1's shape with one thing moved: the table's rows, its width,
# the rows looked up
PROBES = {
    "vocab_8192": (4096, 5120, 8192),
    "vocab_16384": (4096, 5120, 16384),
    "vocab_32768": (4096, 5120, 32768),
    "width_2048": (4096, 2048, 32640),
    "width_4096": (4096, 4096, 32640),
    "rows_1024": (1024, 5120, 32640),
    "rows_8192": (8192, 5120, 32640),
}


def _transposed(lookup, ids, cot, vocab):
    """``lookup``'s cotangent of the table (the zero table it is taken
    at and the forward are dead code to the compiler)."""
    zeros = jnp.zeros((vocab, cot.shape[1]), cot.dtype)
    return jax.vjp(lambda w: lookup(w, ids), zeros)[1](cot)[0]


def scatter_grad(ids, cot, vocab):
    """What autodiff gives ``jnp.take``: a scatter-add in ``cot``'s type."""
    return _transposed(lambda w, i: jnp.take(w, i, axis=0), ids, cot, vocab)


def rule_grad(ids, cot, vocab):
    return _transposed(lambda w, i: indexing._lookup(w, i, vocab), ids, cot,
                       vocab)


def table(name, shape, row, run, reps=10):
    m, width, vocab = shape
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, vocab, m), jnp.int32)
    for dtype in (jnp.bfloat16, jnp.float32):
        cot = jnp.asarray(rng.randn(m, width), dtype)
        forms = {"scatter": scatter_grad}
        if dtype == jnp.bfloat16:
            forms["segment_product"] = rule_grad
        for by, form in forms.items():
            ops = run.device_ops(jax.jit(form, static_argnums=2), ids, cot,
                                 vocab, reps=reps)
            ms = alone.busy_ms(ops)
            row(shape=name, rows=m, width=width, vocab=vocab,
                dtype=jnp.dtype(dtype).name, by=by, device_ms=ms,
                kernel_ms=alone.busy_ms(
                    [op for op in ops if "gmm_wgrad" in op[0]]),
                us_a_row=alone.ratio(ms, m, 1e3),
                write_ms=run.bound(
                    nbytes=vocab * width * cot.dtype.itemsize))


def main():
    run = alone.Run(__file__)
    run.row(device=run.kind, platform=run.platform)
    if run.rehearse:
        table("toy", (256, 128, 384), run.row, run)
        return
    # a process's first executables run slower for their first calls
    table("discarded", CELLS["lfm2"], lambda **kw: None, run, reps=2)
    for name, shape in list(CELLS.items()) + list(PROBES.items()):
        table(name, shape, run.row, run)
    run.save()


if __name__ == "__main__":
    main()
