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
(read and write 134 MB) at the chip's 819 GB/s.

Prints one JSON line a row and writes `chiprun_out/moe_permute.json`;
PERF.md section 7 holds the table (PR 32).

    chiprun -- python3 benchmarks/moe_permute.py
"""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from mxnet_tpu.parallel import moe  # noqa: E402

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


def _time(f, *args, reps=20):
    jax.block_until_ready(f(*args))
    jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        r = f(*args)
    jax.block_until_ready(r)
    np.asarray(jax.tree_util.tree_leaves(r)[0][:1])  # closed by a fetch
    return (time.perf_counter() - t0) / reps * 1e3


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


def table(name, load, row):
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(TOKENS, WIDTH), jnp.bfloat16)
    rows = jnp.asarray(rng.randn(TOKENS * TOP_K, WIDTH), jnp.bfloat16)
    weights = jnp.asarray(rng.rand(TOKENS, TOP_K), jnp.float32)
    experts = jnp.asarray(routing(load, 0))
    flat = experts.reshape(-1)
    rest = jax.jit(sort_pair)(experts)
    row(routing=name, what="sorts", by="argsort",
        ms=_time(jax.jit(argsort_pair), experts))
    row(routing=name, what="sorts", by="lax_int32",
        ms=_time(jax.jit(sort_pair), experts))
    row(routing=name, what="counts", by="bincount", ms=_time(jax.jit(
        lambda e: jnp.bincount(e, length=EXPERTS)), flat))
    row(routing=name, what="counts", by="one_hot", ms=_time(jax.jit(
        lambda e: jnp.sum(jax.nn.one_hot(e, EXPERTS, dtype=jnp.int32),
                          axis=0, dtype=jnp.int32)), flat))
    for what, by, f, diff, cotangent in (
            ("dispatch", "take", take_dispatch, (x,), rows),
            ("dispatch", "pair", moe._dispatch, (x,), rows),
            ("combine", "take", take_combine, (rows, weights), x),
            ("combine", "pair", moe._combine, (rows, weights), x)):
        row(routing=name, what=what, by=by,
            fwd_ms=_time(jax.jit(f), *diff, *rest),
            bwd_ms=_time(*backward(f, diff, rest), cotangent))


def main():
    dev = jax.devices()[0]
    move_bytes = 2 * TOKENS * TOP_K * WIDTH * 2
    res = {"device": str(dev.device_kind), "platform": dev.platform,
           "bound_ms": 1e3 * move_bytes / 819e9, "rows": []}
    print(json.dumps({k: v for k, v in res.items() if k != "rows"}),
          flush=True)

    def row(**kw):
        print(json.dumps(kw), flush=True)
        res["rows"].append(kw)

    table("discarded", ROUTINGS["multinomial"], lambda **kw: None)
    for name, load in ROUTINGS.items():
        table(name, load, row)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/moe_permute.json", "w") as f:
        json.dump(res, f, indent=1)


if __name__ == "__main__":
    main()
