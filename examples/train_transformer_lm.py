#!/usr/bin/env python
"""Train the TPU-native transformer LM — the beyond-reference flagship.

The reference's long-sequence story is bucketed LSTMs plus the
model-parallel LSTM example (SURVEY.md §5.7); this is the idiomatic TPU
equivalent, exposing the full sharding menu from one script:

  --dp/--tp/--sp/--ep     mesh axes (sequence parallel = ring attention,
                          expert parallel = Switch-MoE all-to-alls)
  --moe-experts N         swap every second FFN for a Switch-MoE block
  --seq-len               long-context via flash/ring attention

Runs on a real TPU by default; --cpu routes onto the virtual host mesh
(same trick as tests/conftest.py) so the sharded program is runnable
anywhere. Data is a synthetic char-level corpus so the example is
offline-complete (swap in a token file per the README for real text).

For a transformer that is an ``mx.sym`` graph trained by ``Module.fit``
(OLMoE: top-k sparse experts), see ``examples/train_olmoe_lm.py``.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--d-model", type=int, default=256)
    p.add_argument("--n-heads", type=int, default=8)
    p.add_argument("--n-layers", type=int, default=4)
    p.add_argument("--d-ff", type=int, default=1024)
    p.add_argument("--seq-len", type=int, default=256)
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--lr", type=float, default=3e-2)
    p.add_argument("--moe-experts", type=int, default=0)
    p.add_argument("--dp", type=int, default=None)
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--sp", type=int, default=1)
    p.add_argument("--ep", type=int, default=1)
    p.add_argument("--cpu", action="store_true",
                   help="virtual 8-device host mesh instead of the TPU")
    p.add_argument("--dtype", default="float32",
                   choices=["float32", "bfloat16"])
    args = p.parse_args()

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    if args.cpu:
        from __graft_entry__ import _force_cpu_mesh_platform

        _force_cpu_mesh_platform(8)

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mxnet_tpu.models.transformer import transformer_lm
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.moe import moe_partition_specs

    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    init_fn, apply_fn = transformer_lm(
        vocab=args.vocab, d_model=args.d_model, n_heads=args.n_heads,
        n_layers=args.n_layers, d_ff=args.d_ff, dtype=dtype,
        moe_experts=args.moe_experts)

    mesh = make_mesh(dp=args.dp, tp=args.tp, sp=args.sp, ep=args.ep)
    print("mesh:", dict(mesh.shape))

    # synthetic corpus: next char = (2*c + 1) % vocab with noise — a
    # learnable rule so loss visibly falls in a few dozen steps
    rng = np.random.RandomState(0)
    seq = np.zeros((args.batch_size, args.seq_len + 1), np.int32)
    seq[:, 0] = rng.randint(0, args.vocab, args.batch_size)
    for t in range(args.seq_len):
        nxt = (2 * seq[:, t] + 1) % args.vocab
        noise = rng.rand(args.batch_size) < 0.05
        seq[:, t + 1] = np.where(
            noise, rng.randint(0, args.vocab, args.batch_size), nxt)
    tokens = jnp.asarray(seq[:, :-1])
    targets = jnp.asarray(seq[:, 1:])

    params = jax.tree_util.tree_map(jnp.asarray, init_fn(0))
    moe_specs = moe_partition_specs()

    def spec_for(path_key, leaf):
        if "moe" in path_key:
            return moe_specs[path_key.split("/")[-1]]
        leafname = path_key.split("/")[-1]
        # megatron tp: column-parallel into the nonlinearity, row-parallel
        # out of it (same mapping as the dryrun transformer program)
        if leafname in ("wq", "wk", "wv", "w1"):
            return P(None, "tp")
        if leafname in ("wo", "w2"):
            return P("tp", None)
        return P()

    # shard: tokens over dp(+sp along sequence), experts over ep
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    shardings = []
    for path, leaf in flat:
        key = "/".join(str(getattr(k, "key", k)) for k in path)
        shardings.append(NamedSharding(mesh, spec_for(key, leaf)))
    params = jax.tree_util.tree_unflatten(
        tree, [jax.device_put(v, s) for (_, v), s in zip(flat, shardings)])
    data_spec = P("dp", "sp") if args.sp > 1 else P("dp")
    tokens = jax.device_put(tokens, NamedSharding(mesh, data_spec))
    targets = jax.device_put(targets, NamedSharding(mesh, data_spec))

    def loss_fn(p, tokens, targets):
        out = apply_fn(p, tokens, mesh=mesh if args.sp > 1 else None)
        logits, aux = out if args.moe_experts else (out, 0.0)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32))
        nll = -jnp.mean(jnp.take_along_axis(lp, targets[..., None], -1))
        return nll + 0.01 * aux

    step = jax.jit(jax.value_and_grad(loss_fn))
    with mesh:
        t0 = time.time()
        for i in range(args.steps):
            loss, grads = step(params, tokens, targets)
            params = jax.tree_util.tree_map(
                lambda p, g: p - args.lr * g.astype(p.dtype), params, grads)
            if i % 5 == 0 or i == args.steps - 1:
                print("step %3d  loss %.4f  (%.1fs)"
                      % (i, float(loss), time.time() - t0))
    print("done: final loss %.4f" % float(loss))


if __name__ == "__main__":
    main()
