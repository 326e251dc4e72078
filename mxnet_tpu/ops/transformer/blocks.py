"""``BlockSelect``: the choice of key BLOCKS a query reads (InfLLM-V2, the
sparse attention of MiniCPM4, arXiv:2506.07900 and arXiv:2509.24663), made
with the attention's OWN queries and keys: no second attention, no weights.
Per key/value head and the ``group`` query heads that read it:

  pool    ``Kc_j = mean(k[stride j : stride j + pool])`` for every window
          that lies inside the sequence;
  score   ``p[t, h, :] = softmax over {j : stride j + pool <= t + 1} of
          q[t, h] . Kc_j / sqrt(D)``, summed over the group's heads; a
          block of ``block`` keys scores the largest ``p`` of the windows
          that overlap it;
  choose  query t keeps its first ``init_blocks`` blocks, every block that
          holds one of its last ``window`` keys, and the ``topk`` best of
          the other blocks before those (``kernels.top_k_mask``: ties to
          the lower index).

The result is ``Attention``'s int8 keep-mask a key ([B, T, T]: a kept
block's keys; the attention applies the causal order itself) and the count
of (query, key) pairs s <= t it names, which ``kept_pairs`` predicts exactly.
Nothing here has a gradient. It is NOT ``KeyIndexer``: that is a second small
attention with heads and weights of its own that chooses single keys."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ... import telemetry as _tm
from ..registry import OpDef, register
from ..utils import first_type, head_width, required_shape

_M_BLOCK_LOWERINGS = _tm.counter(
    "attention.block_select_lowerings", "Traces of a BlockSelect call site "
    "(one per lowering, nothing per step); labels: blocks (key blocks of "
    "the sequence), chosen (topk), window, impl (how the blocks are chosen: "
    "pallas where kernels.topk has a row block for the scores, jnp "
    "elsewhere)")
_M_BLOCK_KEYS = _tm.histogram(
    "attention.block_keys_kept", "(query, key) pairs a BlockSelect node "
    "kept in one execution, summed over its batch: observed once an "
    "execution of the node, from the device, and only by a program traced "
    "while telemetry was on (a host callback in the step; an untraced "
    "program holds none); sum / count is the pairs a node and step",
    buckets=tuple(float(10 ** e) for e in range(3, 13)))

SCORE_BLOCK_ROWS = 512   # query rows a block of [group, rows, windows] scores


def first_local_block(t, block, window):
    """The first block that holds one of the last ``window`` keys of query
    ``t`` (numpy or Python integers)."""
    return np.maximum(t - window + 1, 0) // block


def kept_pairs(seq_len, block=64, topk=64, init_blocks=1, window=2048):
    """(query, key) pairs s <= t that ``block_select`` keeps over one
    sequence: the closed form of its rule. Query t keeps the keys up to its
    own from its first local block on, ``init_blocks`` whole blocks where
    they lie before that, and ``topk`` whole blocks of those between (all of
    them where they are fewer)."""
    t = np.arange(seq_len, dtype=np.int64)
    first = first_local_block(t, block, window)
    init = np.minimum(first, init_blocks)
    return int(np.sum(t - block * first + 1
                      + block * (init + np.minimum(first - init, topk))))


def pooled_keys(key, pool, stride):
    """key [B, T, D] -> float32 [B, J, D], ``J = (T - pool) // stride + 1``
    window means (none where T < pool)."""
    b, t, d = key.shape
    if t < pool:
        return jnp.zeros((b, 0, d), jnp.float32)
    return jax.lax.reduce_window(
        key.astype(jnp.float32), np.float32(0.0), jax.lax.add, (1, pool, 1),
        (1, stride, 1), "VALID") / float(pool)


def window_scores(query, pooled, pool, stride, rows=SCORE_BLOCK_ROWS):
    """query [B, T, H, D], pooled [B, J, D] in the operands' type -> P [B,
    T, J] float32: each head's softmax over the windows that END at or
    before the query (``stride j + pool <= t + 1``; a query before the first
    window's end scores zeros), summed over the heads. A block of ``rows``
    query rows at a time: no [H, T, J] array exists."""
    b, t, h, d = query.shape
    windows = pooled.shape[1]
    scale = float(d) ** -0.5
    ends = stride * np.arange(windows) + pool      # a window's end
    pad = -t % rows
    q = jnp.pad(query, ((0, 0), (0, pad), (0, 0), (0, 0)))
    q = q.reshape(b, (t + pad) // rows, rows, h, d).transpose(1, 0, 2, 3, 4)
    first_rows = rows * np.arange((t + pad) // rows, dtype=np.int32)

    def one(args):
        q_rows, first = args
        s = jnp.einsum("brhd,bjd->bhrj", q_rows, pooled,
                       preferred_element_type=jnp.float32) * scale
        at = first + jnp.arange(rows, dtype=jnp.int32)
        live = (jnp.asarray(ends, jnp.int32)[None, :] <= at[:, None] + 1)
        s = jnp.where(live, s, -jnp.inf)
        top = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.where(live, jnp.exp(s - jnp.where(top > -jnp.inf, top, 0.0)),
                      0.0)
        total = jnp.sum(e, axis=-1, keepdims=True)
        return jnp.sum(e / jnp.maximum(total, 1e-30), axis=1)

    p = jax.lax.map(one, (q, jnp.asarray(first_rows)))
    return p.transpose(1, 0, 2, 3).reshape(b, t + pad, windows)[:, :t]


def block_scores(p, blocks, pool, stride, block):
    """P [B, T, J] (non-negative) -> [B, T, blocks]: a block's largest
    window score over the windows that overlap its keys (``stride j <
    block (i + 1)`` and ``stride j + pool > block i``); 0 where none
    does."""
    ratio, reach = block // stride, pool // stride
    width = ratio * blocks + reach - 1
    lead = reach - 1
    p = jnp.pad(p, ((0, 0), (0, 0), (lead, max(width - lead - p.shape[2],
                                               0))))[..., :width]
    return jax.lax.reduce_window(
        p, np.float32(0.0), jax.lax.max, (1, 1, ratio + reach - 1),
        (1, 1, ratio), "VALID")


def block_select(query, key, num_heads, pool=32, stride=16, block=64,
                 topk=64, init_blocks=1, window=2048):
    """query [B, T, H D] (the ``H`` query heads of ONE key/value head, after
    their norm), key [B, T, D] (that head's keys) -> (keep [B, T, T] int8,
    count [B] float32): the module docstring's rule. ``stride`` divides
    ``pool`` and ``block``. The two products take operands of ``query``'s
    type and accumulate in float32; the means, the softmax, the sum over the
    heads and the compare are float32. Under telemetry the node observes
    its count a step (``attention.block_keys_kept``)."""
    from .. import kernels

    query, key = (jax.lax.stop_gradient(x) for x in (query, key))
    b, t, _ = query.shape
    d = key.shape[2]
    blocks = -(-t // block)
    _M_BLOCK_LOWERINGS.inc(
        blocks=blocks, chosen=topk, window=window,
        impl="pallas" if kernels.top_k_rows((b, t, blocks), topk) else "jnp")
    with jax.named_scope("blocks"):
        with jax.named_scope("pool"):
            pooled = pooled_keys(key, pool, stride).astype(query.dtype)
        with jax.named_scope("score"):
            scores = block_scores(
                window_scores(query.reshape(b, t, num_heads, d), pooled,
                              pool, stride), blocks, pool, stride, block)
        with jax.named_scope("choose"):
            at = np.arange(t)[:, None]
            index = np.arange(blocks)[None, :]
            first = first_local_block(at, block, window)
            local = (index >= first) & (index <= at // block)
            fixed = local | (index < np.minimum(first, init_blocks))
            others = (index >= init_blocks) & (index < first)
            chosen, _ = kernels.top_k_mask(
                jnp.where(others[None], scores, -jnp.inf), topk, live=True,
                interpret=kernels.common.INTERPRET)
            kept = jnp.maximum(chosen, jnp.asarray(fixed[None], jnp.int8))
            # keys past the query in its own block are the attention's to
            # drop; the count leaves them out
            ahead = block * (at[:, 0] // block + 1) - (at[:, 0] + 1)
            count = (block * jnp.sum(kept, axis=(1, 2), dtype=jnp.int32)
                     - int(ahead.sum()))
            keep = jnp.repeat(kept, block, axis=2)[:, :, :t]
    if _tm.enabled():
        jax.debug.callback(_observe_kept, jnp.sum(count))
    return keep, count.astype(jnp.float32)


def _observe_kept(pairs):
    _M_BLOCK_KEYS.observe(float(pairs))


def _block_select(attrs, ins, is_train):
    return list(block_select(
        *ins, **{name: int(attrs[name]) for name in (
            "num_heads", "pool", "stride", "block", "topk", "init_blocks",
            "window")}))


def _block_select_infer(attrs, in_shapes):
    heads = int(attrs["num_heads"])
    pool, stride, block, topk, window = (int(attrs[name]) for name in (
        "pool", "stride", "block", "topk", "window"))
    if (min(heads, pool, stride, block, topk, window) <= 0
            or pool % stride or block % stride
            or int(attrs["init_blocks"]) < 0):
        raise ValueError(
            "BlockSelect: num_heads=%d, pool=%d, stride=%d, block=%d, "
            "topk=%d and window=%d must be positive and the stride divide "
            "the pool and the block" % (heads, pool, stride, block, topk,
                                        window))
    q = required_shape(in_shapes[0], "BlockSelect")
    k = required_shape(in_shapes[1], "BlockSelect")
    d = head_width("BlockSelect", "query", q, heads)
    if len(k) != 3 or k[:2] != q[:2] or k[2] != d:
        raise ValueError(
            "BlockSelect: key %s must be ONE key/value head [batch, time, "
            "%d] over query's positions %s" % (k, d, q[:2]))
    return [q, k], [q[:2] + (q[1],), (q[0],)], []


def _block_select_infer_type(attrs, in_types):
    t = first_type("BlockSelect", in_types)
    return ([t if x is None else x for x in in_types],
            [np.int8, np.float32], [])


register(
    OpDef(
        "_contrib_BlockSelect",
        _block_select,
        arguments=("query", "key"),
        outputs=("keep", "count"),
        defaults={"num_heads": 1, "pool": 32, "stride": 16, "block": 64,
                  "topk": 64, "init_blocks": 1, "window": 2048},
        infer_shape=_block_select_infer,
        infer_type=_block_select_infer_type,
        aliases=("BlockSelect",),
        op_class="attn",
    )
)
