#!/usr/bin/env python
"""Train an OLMoE language model (sparse experts, top-k, dropless)
through ``Module.fit`` — the entry point the benchmark's
``olmoe_fit_resident_4k`` cell drives, at a size a laptop runs.

``models/olmoe.py`` is an ordinary ``mx.sym`` graph (RMSNorm, RoPE,
Attention and TopKMoE are ``mx.contrib.sym`` ops), so training it is the
same few lines as any other symbol: a mesh and ``kvstore='device'``
select the fused step, ``eval_metric='loss'`` reads the model's own
scalar loss instead of a probability table.

Run:  python examples/train_olmoe_lm.py [--ctx cpu] [--preset tiny]
      --preset olmoe-1b-7b is the published configuration (16 layers;
      one v5e holds 3 of them with SGD momentum, see PERF.md)
"""
from __future__ import annotations

import argparse
import logging

import numpy as np

import common
import mxnet_tpu as mx
from mxnet_tpu.models import olmoe
from mxnet_tpu.parallel import make_mesh

PRESETS = {
    "tiny": dict(vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
                 num_experts=8, experts_per_token=2, expert_width=32,
                 seq_len=32),
    "olmoe-1b-7b": dict(seq_len=4096, dtype="bfloat16"),  # the defaults
}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--ctx", default="tpu", choices=["tpu", "cpu"])
    ap.add_argument("--preset", default="tiny", choices=sorted(PRESETS))
    ap.add_argument("--num-layers", type=int, default=None)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--num-epochs", type=int, default=4)
    ap.add_argument("--lr", type=float, default=0.3)
    args = ap.parse_args()
    args.num_devices = 1
    ctx = common.get_context(args)
    logging.basicConfig(level=logging.INFO)

    kwargs = dict(PRESETS[args.preset])
    if args.num_layers:
        kwargs["num_layers"] = args.num_layers
    sym = olmoe.get_symbol(**kwargs)
    seq_len = kwargs["seq_len"]
    vocab = kwargs.get("vocab_size", 50304)

    # a corpus with something to learn: each token follows from the last
    rng = np.random.RandomState(0)
    step = rng.randint(1, vocab, vocab)
    tokens = np.empty((16 * args.batch_size, seq_len + 1), np.int64)
    tokens[:, 0] = rng.randint(0, vocab, len(tokens))
    for i in range(seq_len):
        tokens[:, i + 1] = (tokens[:, i] + step[tokens[:, i]]) % vocab
    train = mx.io.NDArrayIter(tokens[:, :-1].astype(np.float32),
                              tokens[:, 1:].astype(np.float32),
                              batch_size=args.batch_size, shuffle=True)

    losses = []
    mod = mx.mod.Module(sym, context=ctx, mesh=make_mesh(dp=1))
    mod.fit(train, num_epoch=args.num_epochs, eval_metric="loss",
            optimizer="sgd",
            optimizer_params={"learning_rate": args.lr, "momentum": 0.9},
            initializer=mx.initializer.Normal(0.02), kvstore="device",
            batch_end_callback=lambda p: losses.append(
                p.eval_metric.get()[1]))
    counts = [o.asnumpy() for o in mod.get_outputs()[1:]]
    print("loss %.3f -> %.3f (ln vocab %.3f)" % (
        losses[0], losses[-1], np.log(vocab)))
    print("rows per expert, last step, layer 0: %s"
          % counts[0].astype(int).tolist())


if __name__ == "__main__":
    main()
