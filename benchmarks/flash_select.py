"""The selected flash pair alone on the chip: `ops/kernels/flash.py`'s
`flashsel_fwd_` / `flashsel_bwd_` at the call the Keye-VL-2.0 cell makes a
layer (one sequence of 8,192 tokens, 32 query heads on 4 key/value heads of
128, bf16, an int8 keep-mask of `min(t + 1, 2048)` keys a row drawn from
`--seed`), forward and backward apart, on operands that cross the jit
boundary as `flash_select` hands them to the two calls (`_group_rows`' q,
head-major k and v, the mask; the backward's lse and delta from a forward
made before the clock).

Three tables, one JSON line a row (PERF.md section 7 holds them, PR 77):

- `check`: how far the pair's output and three gradients are, on the chip,
  from the materialised `kept_attention` at a quarter of the length, as a
  share of the largest magnitude, for each width of `edges`;
- `knock_outs`: the pair as `select_edge` runs each pass, with one part of
  a tile step's work taken out at a time (`KNOCK_OUTS`: no mask at all, the
  floor of what the plain arithmetic costs on these tiles; the keep-mask
  without the causal compare; the diagonal tile skipped; no tile step at
  all, which leaves the grid's 512 steps a key/value head and every tile's
  DMA with nothing to hide behind: no part of the whole, whose steps hide
  it). The copies are built HERE, by replacing a name of `flash.py` in
  this process round a fresh trace; their results are wrong and only their
  time is read. The package has no such switch;
- `edges`: each pass at each candidate width of the diagonal tile's
  column blocks (0: the tile whole), the widths alternating.

Each timed row holds the kernel's device ms a call from a profiled run of 5
calls, the pairs a query head it computes, and two shares of the bf16 peak:
of the products over the pairs it COMPUTES (two a pair forward, five
backward) and, as `attn_select_roofline_share` counts, over the KEPT pairs
(two forward, four backward: the recomputed scores do not count).

    chiprun -- python3 benchmarks/flash_select.py [--seed N]
    python3 benchmarks/flash_select.py --rehearse-cpu

The platform rule, the clock and the output file are `alone.py`'s.
"""
import argparse
import contextlib
from unittest import mock

import alone

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from mxnet_tpu.ops.kernels import flash, topk
from mxnet_tpu.ops.kernels.common import affine

T, HEADS, KV_HEADS, D, TOPK = 8192, 32, 4, 128, 2048
EDGES = (0, 128, 256, 512)
# products a computed pair, and the required ones a kept pair
PRODUCTS = {"fwd": (2, 2), "bwd": (5, 4)}


def _scores_alone(q, k_blk, bias, *, group, scale):
    return jnp.float32(scale) * jax.lax.dot_general(
        q, k_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def _interior_steps(step, keep_ref, qi, ki, *, rows, block_k, edge):
    @pl.when(jax.lax.lt(ki, jax.lax.div(affine(qi, rows),
                                        np.int32(block_k))))
    def _():
        step(block_k, flash._select_bias(keep_ref[0]))


_bias = flash._select_bias
KNOCK_OUTS = {
    "whole": {},
    "no_mask": {"_select_scores": _scores_alone,
                "_select_bias": lambda keep, offset=None: None},
    "no_causal_compare": {"_select_bias":
                          lambda keep, offset=None: _bias(keep)},
    "no_diagonal_tile": {"_select_steps": _interior_steps},
    "no_tile_step": {"_select_steps": lambda *a, **kw: None},
}
# the knock-outs that compute other pairs than the pair does: no share
FEWER_PAIRS = ("no_diagonal_tile", "no_tile_step")


@contextlib.contextmanager
def knocked_out(part):
    """``ops/kernels/flash.py`` with one part of the selected kernels' work
    taken out (``KNOCK_OUTS``), for the time alone: what such a kernel
    computes is wrong."""
    def clear_traces():
        flash.select_fwd_call.clear_cache()
        flash.select_bwd_call.clear_cache()

    swaps = KNOCK_OUTS[part]
    try:
        with (mock.patch.multiple(flash, **swaps) if swaps
              else contextlib.nullcontext()):
            clear_traces()
            yield
    finally:
        clear_traces()


def inputs(seed, t, heads, kv_heads, topk_):
    """q [1, t, H, D], k and v [1, t, G, D] in bf16, the int8 keep-mask of a
    row's ``topk_`` largest of random causal scores and a cotangent, all
    made on the device from ``seed``."""
    keys = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)), 5)
    q, k, v, do = (jax.random.normal(key, (1, t, n, D), jnp.bfloat16)
                   for key, n in zip(keys, (heads, kv_heads, kv_heads,
                                            heads)))
    scores = jnp.where(
        jnp.tril(jnp.ones((t, t), bool)),
        jax.random.normal(keys[4], (1, t, t), jnp.float32), -jnp.inf)
    keep, _ = jax.jit(lambda s: topk.plain_form(s, k=topk_, live=True))(
        scores)
    return q, k, v, keep, do


def computed_pairs(t, rows, block_k, edge):
    """Position pairs a query head that the pair's tile steps compute."""
    pairs = 0
    for i in range(t // rows):
        first = i * rows
        seen = first % block_k + rows
        width = -(-seen // edge) * edge if edge else block_k
        pairs += rows * (first // block_k * block_k + width)
    return pairs


def calls(run, operands, rows, block_k, edges):
    """{pass: (jitted call, its arguments)} of the pair on
    ``flash_select``'s operands, ``edges`` the width a pass."""
    q, k, v, keep, do = operands
    group = q.shape[2] // k.shape[2]
    kw = {which: dict(rows=rows, group=group, block_k=block_k,
                      scale=D ** -0.5, edge=edge, interpret=run.rehearse)
          for which, edge in edges.items()}
    q3, do3 = (flash._group_rows(x, k.shape[2], rows) for x in (q, do))
    k3, v3 = flash._heads_first(k), flash._heads_first(v)
    out, lse = flash.select_fwd_call(q3, k3, v3, keep, **kw["fwd"])
    delta = jnp.sum(do3.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)
    return {"fwd": (lambda *a: flash.select_fwd_call(*a, **kw["fwd"]),
                    (q3, k3, v3, keep)),
            "bwd": (lambda *a: flash.select_bwd_call(*a, **kw["bwd"]),
                    (q3, k3, v3, keep, do3, lse, delta))}


def timed(run, operands, rows, block_k, edges, kept, **head):
    """One row a pass: the kernel's device ms a call and its shares."""
    t, heads = operands[0].shape[1:3]
    for which, (f, args) in calls(run, operands, rows, block_k,
                                  edges).items():
        ms = alone.named(alone.by_kernel(run.device_ops(f, *args),
                                         "flashsel_" + which),
                         "flashsel_" + which) or None
        computed, required = PRODUCTS[which]
        pairs = (None if head.get("knocked_out") in FEWER_PAIRS else
                 computed_pairs(t, rows, block_k, edges[which]))
        run.row(**head, **{"pass": which}, edge=edges[which], kernel_ms=ms,
                pairs_a_head=pairs,
                mxu_share_of_computed_pairs=pairs and alone.ratio(
                    run.bound(flops=2.0 * D * computed * heads * pairs),
                    ms, 100),
                mxu_share_of_kept_pairs=alone.ratio(
                    run.bound(flops=2.0 * D * required * heads * kept),
                    ms, 100))


def check(run, seed, t, heads, kv_heads, topk_, edges):
    """The pair against ``kept_attention`` on the device."""
    q, k, v, keep, do = inputs(seed, t, heads, kv_heads, topk_)
    w = do.astype(jnp.float32)

    def both(fn):
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w),
            argnums=(0, 1, 2)))(q, k, v)

    want = both(lambda q, k, v: flash.kept_attention(
        *(x.astype(jnp.float32) for x in (q, k, v)), keep, D ** -0.5))
    for edge in edges:
        with mock_edge(edge):
            got = both(lambda q, k, v: flash.flash_select(
                q, k, v, keep, interpret=run.rehearse))
        run.row(table="check", t=t, edge=edge, **{
            name: float(jnp.abs(g.astype(jnp.float32) - x).max()
                        / jnp.abs(x).max())
            for name, g, x in zip(("loss", "dq", "dk", "dv"),
                                  jax.tree_util.tree_leaves(got),
                                  jax.tree_util.tree_leaves(want))})


def mock_edge(edge):
    """``flash_select`` with its diagonal tiles by blocks of ``edge``, both
    passes."""
    return mock.patch.object(
        flash, "select_edge",
        lambda which, rows, block_k: edge if edge < block_k else 0)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    seed = ap.parse_args().seed
    run = alone.Run(__file__)
    run.row(device=run.kind, platform=run.platform)
    t, heads, kv_heads, topk_, edges = T, HEADS, KV_HEADS, TOPK, EDGES
    if run.rehearse:
        t, heads, kv_heads, topk_, edges = 256, 8, 2, 48, (0, 64, 128)
    group = heads // kv_heads
    check(run, seed, t // 4 if t > 1024 else t, heads, kv_heads,
          max(topk_ // 4, 1), edges)
    rows, block_k, _ = flash.select_tiles(t, group, D, D, jnp.bfloat16)
    operands = inputs(seed, t, heads, kv_heads, topk_)
    kept = sum(min(i + 1, topk_) for i in range(t))
    rule = {which: flash.select_edge(which, rows, block_k)
            for which in PRODUCTS}
    for part, _ in run.alternate(KNOCK_OUTS, rounds=2):
        with knocked_out(part):
            timed(run, operands, rows, block_k, rule, kept,
                  table="knock_outs", knocked_out=part)
    for _, edge in run.alternate({e: e for e in edges}, rounds=2):
        timed(run, operands, rows, block_k, dict(fwd=edge, bwd=edge), kept,
              table="edges")
    run.save(shape=dict(t=t, heads=heads, kv_heads=kv_heads, d=D,
                        topk=topk_, rows=rows, block_k=block_k, seed=seed))


if __name__ == "__main__":
    main()
