"""The expert layer's row moves on the chip: `parallel/moe.py`'s
permutation pair (`_dispatch`, `_combine`: gathers forward and backward)
against the plain `jnp.take` formulation under autodiff (whose backward
is two scatter-adds), at the OLMoE cell's shape: 4,096 tokens of 2,048
bf16 values, top-8 of 64 experts, so 32,768 rows (134 MB) out of and
into 16.8 MB. Two routings: `cell_layer1`, a draw as skewed as layer 1
of a chip run of `olmoe_fit_resident_4k` (eight experts take 2,100-3,900
rows each; the loads of `benchmarks/grouped_matmul.py`), and
`multinomial`, a trained model's near-uniform load. Beside the moves,
the two sorts and the row count (`bincount` against the one-hot sum).
`bwd_ms` is the backward alone (the `jax.vjp` function over residuals
made beforehand). Host clock over 20 calls closed by a fetch, after one
discarded pass over the table (a process's first executables ran 50
times slower for their first calls); `bound_ms` is one move's bytes
(read and write 134 MB) at the device's HBM peak.

**A share's moves** (PR 47), at the three share cells' shapes (`SHARES`:
Kanana 8,192 x 2,048, top-6, 16 of 128 experts held, a buffer of 12,288
rows; LFM2 8,192 x 2,048, top-4, 8 of 64, 8,192; MiMo 4,096 x 4,096,
top-8, 8 of 256, 2,048), uniform routing, three formulations of the same
two moves, forward and backward ms a move: `take`, what the tree had
(`jnp.take` and `jax.ops.segment_sum` under autodiff: two row
scatter-adds and a scalar one); `inverse_gather`, PR 32's cure carried
over (every (token, k) pair gathers its row through a zero row:
`tokens * top_k` rows where the buffer holds `bound`); `segment_product`,
`parallel/moe.py`'s `_share_dispatch` / `_share_combine` (gathers over
the buffer and `ops.kernels.sorted_segment_sum`), with `_share_weights`
(the weights' pick and its placement) a row of its own since the weight
is applied inside the experts there. `plan` is the index arithmetic
(compaction, sorts) of the tree's formulation and of this one;
`bound_ms` of a share row is one move's bytes (the buffer read and the
tokens written, or the reverse) at the same peak.

PERF.md section 7 holds the tables (PR 32, PR 47).

    chiprun -- python3 benchmarks/moe_permute.py [--shares-only]
    python3 benchmarks/moe_permute.py --rehearse-cpu [--shares-only]

The platform rule, the clocks and the output file are `alone.py`'s.
"""
import sys

import alone

import jax
import jax.numpy as jnp
import numpy as np

from mxnet_tpu.parallel import moe

TOKENS, WIDTH, EXPERTS, TOP_K = 4096, 2048, 64, 8
CELL_LAYER1 = [
    310, 238, 68, 3, 17, 0, 6, 180, 0, 3301, 4, 3905, 699, 2, 0, 0, 2111, 3,
    23, 327, 0, 340, 21, 0, 119, 46, 104, 1129, 213, 7, 3014, 0, 1, 15, 5, 1,
    8, 3828, 142, 1, 2, 7, 11, 0, 3, 121, 0, 6, 0, 31, 2665, 106, 3707, 0, 0,
    780, 1, 25, 66, 8, 23, 3287, 7, 1721]
ROUTINGS = {"cell_layer1": [c + 0.5 for c in CELL_LAYER1],
            "multinomial": [1.0] * EXPERTS}


def routing(load, seed):
    """Each token's TOP_K distinct experts, drawn without replacement
    with odds ``load`` (Gumbel top-k): [TOKENS, TOP_K] int32."""
    rng = np.random.RandomState(seed)
    score = np.log(np.asarray(load)) + rng.gumbel(size=(TOKENS, EXPERTS))
    return np.argsort(-score, axis=1)[:, :TOP_K].astype(np.int32)


def sort_pair(experts):
    flat = experts.reshape(-1)
    pairs = jax.lax.iota(jnp.int32, flat.shape[0])
    _, order = jax.lax.sort((flat, pairs), num_keys=1, is_stable=True)
    _, inverse = jax.lax.sort((order, pairs), num_keys=1)
    return order, inverse.reshape(experts.shape)


def take_dispatch(x, order, inverse):
    return jnp.take(x, order // TOP_K, axis=0)


def take_combine(out_rows, weights, order, inverse):
    per_token = jnp.take(out_rows, inverse.reshape(-1), axis=0).reshape(
        TOKENS, TOP_K, WIDTH)
    y = jnp.einsum("tkd,tk->td", per_token.astype(jnp.float32), weights)
    return y.astype(out_rows.dtype)


def backward(f, diff, rest):
    """The transpose of ``f`` in its arguments ``diff`` alone: a jitted
    call of the ``jax.vjp`` function (a pytree of its residuals)."""
    transpose = jax.vjp(lambda *a: f(*a, *rest), *diff)[1]
    return jax.jit(lambda fn, cotangent: fn(cotangent)), transpose


def argsort_pair(experts):
    """The sorts as ``jnp.argsort`` makes them: int64 indices under the
    framework's ``jax_enable_x64``."""
    order = jnp.argsort(experts.reshape(-1), stable=True)
    return order, jnp.argsort(order)


def table(name, load, row, run):
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(TOKENS, WIDTH), jnp.bfloat16)
    rows = jnp.asarray(rng.randn(TOKENS * TOP_K, WIDTH), jnp.bfloat16)
    weights = jnp.asarray(rng.rand(TOKENS, TOP_K), jnp.float32)
    experts = jnp.asarray(routing(load, 0))
    flat = experts.reshape(-1)
    time = run.host_ms
    rest = jax.jit(sort_pair)(experts)
    row(routing=name, what="sorts", by="argsort",
        ms=time(jax.jit(argsort_pair), experts))
    row(routing=name, what="sorts", by="lax_int32",
        ms=time(jax.jit(sort_pair), experts))
    row(routing=name, what="counts", by="bincount", ms=time(jax.jit(
        lambda e: jnp.bincount(e, length=EXPERTS)), flat))
    row(routing=name, what="counts", by="one_hot", ms=time(jax.jit(
        lambda e: jnp.sum(jax.nn.one_hot(e, EXPERTS, dtype=jnp.int32),
                          axis=0, dtype=jnp.int32)), flat))
    for what, by, f, diff, cotangent in (
            ("dispatch", "take", take_dispatch, (x,), rows),
            ("dispatch", "pair", moe._dispatch, (x,), rows),
            ("combine", "take", take_combine, (rows, weights), x),
            ("combine", "pair", moe._combine, (rows, weights), x)):
        row(routing=name, what=what, by=by,
            fwd_ms=time(jax.jit(f), *diff, *rest),
            bwd_ms=time(*backward(f, diff, rest), cotangent))


SHARES = {  # tokens, width, experts, held, top_k, bound
    "kanana": (8192, 2048, 128, 16, 6, 12288),
    "lfm2": (8192, 2048, 64, 8, 4, 8192),
    "mimo": (4096, 4096, 256, 8, 8, 2048),
}


def take_plan(experts, held, bound):
    """The tree's compaction before PR 47: compare-and-count, a gather,
    ``jnp.argsort`` (int64 under x64) and two gathers by it."""
    tokens, top_k = experts.shape
    local = experts.reshape(-1)
    here = (local >= 0) & (local < held)
    running = jnp.cumsum(here.astype(jnp.int32))
    pairs = jnp.searchsorted(
        running, jnp.arange(1, bound + 1, dtype=jnp.int32), side="left",
        method="compare_all")
    group = jnp.take(local, pairs, mode="fill", fill_value=held)
    order = jnp.argsort(group, stable=True)
    pairs, group = pairs[order], group[order]
    used = group < held
    return pairs, used, jnp.where(used, pairs // top_k, 0)


def segment_plan(experts, held, bound):
    """This tree's: compare-and-count, a gather, two int32 sorts."""
    return moe._share_plan(experts, offset=0, held=held, bound=bound)


def share_table(name, shape, row, run, reps=20):
    tokens, width, experts_n, held, top_k, bound = shape
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(tokens, width), jnp.bfloat16)
    rows = jnp.asarray(rng.randn(bound, width), jnp.bfloat16)
    weights = jnp.asarray(rng.rand(tokens, top_k), jnp.float32)
    experts = jnp.asarray(np.argsort(
        rng.rand(tokens, experts_n), axis=1)[:, :top_k].astype(np.int32))
    move_bytes = (bound + tokens) * width * 2

    def device_ms(f, *args, reps):
        """Device 0's busy ms a call: the host clock has a floor of about
        0.3 ms a call on the chip's host, above most of a share's moves."""
        return alone.busy_ms(run.device_ops(f, *args, reps=reps))

    def put(what, by, fwd, bwd):
        """``fwd`` / ``bwd``: (jitted function, arguments) or None."""
        def ms(call, clock):
            return None if call is None else clock(
                call[0], *call[1], reps=reps)

        row(share=name, what=what, by=by,
            fwd_device_ms=ms(fwd, device_ms), bwd_device_ms=ms(
                bwd, device_ms), fwd_ms=ms(fwd, run.host_ms),
            bwd_ms=ms(bwd, run.host_ms),
            bound_ms=run.bound(nbytes=move_bytes))

    for by, plan in (("take", take_plan), ("segment_product", segment_plan)):
        put("plan", by, (jax.jit(
            lambda e, plan=plan: plan(e, held, bound)), (experts,)), None)
    t_pairs, t_used, t_token = jax.jit(
        lambda e: take_plan(e, held, bound))(experts)
    _, token, pairs, inverse, segment, slot = segment_plan(
        experts, held, bound)
    running = jnp.cumsum(experts.reshape(-1) < held, dtype=jnp.int32
                         ).reshape(tokens, top_k)
    held_rows = int(np.asarray(running)[-1, -1])
    row(share=name, what="held_rows", by="routing", rows=held_rows,
        bound=bound)

    # -- the tree's formulation, under autodiff
    def take_dispatch(x):
        return jnp.where(t_used[:, None], jnp.take(x, t_token, axis=0), 0)

    def take_combine(out_rows, weights):
        weight = jnp.where(t_used, jnp.take(
            weights.reshape(-1), t_pairs, mode="fill", fill_value=0), 0)
        weighted = jnp.where(
            t_used[:, None],
            out_rows.astype(jnp.float32) * weight[:, None], 0)
        return jax.ops.segment_sum(
            weighted, t_token, num_segments=tokens).astype(out_rows.dtype)

    # -- PR 32's cure carried over: every pair gathers its row of the
    # buffer, a pair that holds none the zero row after it
    row_of_pair = jnp.where(
        (experts < held) & (running <= bound),
        moe._take_rows(inverse, jnp.clip(running.reshape(-1) - 1, 0,
                                         bound - 1)).reshape(tokens, top_k),
        bound)

    def zero_row(a):
        return jnp.concatenate([a, jnp.zeros((1,) + a.shape[1:], a.dtype)])

    def per_token(rows):
        return moe._take_rows(zero_row(rows), row_of_pair.reshape(-1)
                              ).reshape(tokens, top_k, width)

    def inverse_dispatch_bwd(d_rows):
        return jnp.sum(per_token(d_rows).astype(jnp.float32),
                       axis=1).astype(d_rows.dtype)

    def inverse_combine(out_rows, weights):
        return jnp.einsum("tkd,tk->td", per_token(out_rows).astype(
            jnp.float32), weights).astype(out_rows.dtype)

    def inverse_combine_bwd(out_rows, weights, dy):
        d_weights = jnp.einsum("td,tkd->tk", dy.astype(jnp.float32),
                               per_token(out_rows).astype(jnp.float32))
        scale = jnp.where(pairs < tokens * top_k, moe._take_rows(
            weights.reshape(-1), jnp.minimum(pairs, tokens * top_k - 1)), 0)
        d_rows = moe._take_rows(dy, token).astype(jnp.float32)
        return (d_rows * scale[:, None]).astype(dy.dtype), d_weights

    # -- this tree's
    def segment_dispatch(x):
        return moe._share_dispatch(x, token, inverse, segment, tokens)

    def segment_combine(out_rows):
        return moe._share_combine(out_rows, token, inverse, segment, tokens)

    def segment_weights(weights):
        return moe._share_weights(weights, pairs, inverse, segment, slot,
                                  weights.shape)

    def both(f, diff, cotangent):
        run, transpose = backward(f, diff, ())
        return (jax.jit(f), diff), (run, (transpose, cotangent))

    put("dispatch", "take", *both(take_dispatch, (x,), rows))
    put("dispatch", "inverse_gather",
        (jax.jit(lambda x: moe._take_rows(x, token)), (x,)),
        (jax.jit(inverse_dispatch_bwd), (rows,)))
    put("dispatch", "segment_product", *both(segment_dispatch, (x,), rows))
    put("combine", "take", *both(take_combine, (rows, weights), x))
    put("combine", "inverse_gather",
        (jax.jit(inverse_combine), (rows, weights)),
        (jax.jit(inverse_combine_bwd), (rows, weights, x)))
    put("combine", "segment_product", *both(segment_combine, (rows,), x))
    put("weights", "segment_product", *both(
        segment_weights, (weights,), jnp.asarray(rng.rand(bound),
                                                 jnp.float32)))


def main():
    global TOKENS, WIDTH, EXPERTS, TOP_K
    run = alone.Run(__file__)
    shares, routings = SHARES, ROUTINGS
    if run.rehearse:
        TOKENS, WIDTH, EXPERTS, TOP_K = 256, 128, 8, 2
        shares = {"toy": (512, 128, 16, 4, 4, 640)}
        routings = {"toy_skewed": [c + 0.5 for c in CELL_LAYER1[:EXPERTS]]}
    bound_ms = run.bound(nbytes=2 * TOKENS * TOP_K * WIDTH * 2)
    run.row(device=run.kind, platform=run.platform, bound_ms=bound_ms)

    def discard(**kw):
        pass

    # a process's first executables ran 50 times slower for their first
    # calls: one pass over each table is made and thrown away
    if not run.rehearse:
        for name, shape in shares.items():
            share_table(name, shape, discard, run, reps=2)
    for name, shape in shares.items():
        share_table(name, shape, run.row, run)
    if "--shares-only" not in sys.argv:
        if not run.rehearse:
            table("discarded", routings["multinomial"], discard, run)
        for name, load in routings.items():
            table(name, load, run.row, run)
    run.save(bound_ms=bound_ms)


if __name__ == "__main__":
    main()
