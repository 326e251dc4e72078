#!/usr/bin/env python
"""Train ResNet/Inception/VGG/AlexNet on ImageNet RecordIO shards
(parity: reference example/image-classification/train_imagenet.py — the
north-star workload, BASELINE.md resnet-50 109 img/s on K80).

Data: pack ImageNet with ``tools/im2rec.py`` into train.rec/val.rec and
point --data-train/--data-val at them. Runs on TPU by default. With
--num-devices > 1 the whole forward+backward+update step compiles to ONE
XLA program (kvstore 'device', the fused ShardedTrainStep) and gradients
sync via psum over ICI inside the step. With ONE device this script
takes the per-key executor path with a 'local' kvstore, not the fused
step (routing fact recorded in PERF.md / ROADMAP.md; a dp=1 mesh is
what reaches the fused step on one chip).

``--dtype bfloat16`` selects the reference's fp16 path analog (cast-in/
cast-out symbol; MXU-native reduced precision).
"""
from __future__ import annotations

import argparse

from common import add_fit_args, fit
import mxnet_tpu as mx


def get_symbol(args):
    name = args.network or "resnet"
    if name == "resnet":
        from mxnet_tpu.models.resnet import get_symbol as f
        return f(num_classes=args.num_classes,
                 num_layers=args.num_layers, dtype=args.dtype)
    if name == "resnext":
        from mxnet_tpu.models.resnext import get_symbol as f
        return f(num_classes=args.num_classes,
                 num_layers=args.num_layers,
                 num_group=args.num_group,
                 image_shape=args.image_shape)
    if name == "inception-v3":
        from mxnet_tpu.models.inception_v3 import get_symbol as f
        return f(num_classes=args.num_classes)
    if name == "inception-bn":
        from mxnet_tpu.models.inception_bn import get_symbol as f
        return f(num_classes=args.num_classes,
                 image_shape=args.image_shape)
    if name == "googlenet":
        from mxnet_tpu.models.googlenet import get_symbol as f
        return f(num_classes=args.num_classes)
    if name == "inception-resnet-v2":
        from mxnet_tpu.models.inception_resnet_v2 import get_symbol as f
        return f(num_classes=args.num_classes)
    if name == "vgg":
        from mxnet_tpu.models.vgg import get_symbol as f
        return f(num_classes=args.num_classes,
                 num_layers=args.num_layers)
    if name == "alexnet":
        from mxnet_tpu.models.alexnet import get_symbol as f
        return f(num_classes=args.num_classes)
    raise ValueError("unknown network %s" % name)


def get_iters(args):
    if args.benchmark:
        # synthetic data at the training shape (reference common/fit.py
        # --benchmark): one generated batch cycled N times, so memory
        # stays constant however long the measurement runs
        import numpy as np

        shape = tuple(int(x) for x in args.image_shape.split(","))
        rng = np.random.RandomState(0)
        X = rng.rand(args.batch_size, *shape).astype(np.float32)
        y = rng.randint(0, args.num_classes,
                        args.batch_size).astype(np.float32)
        inner = mx.io.NDArrayIter(X, y, batch_size=args.batch_size)
        return mx.io.ResizeIter(inner, args.benchmark), None
    train = mx.io.ImageRecordIter(
        path_imgrec=args.data_train,
        data_shape=tuple(int(x) for x in args.image_shape.split(",")),
        batch_size=args.batch_size,
        shuffle=True, rand_crop=True, rand_mirror=True,
        mean_r=123.68, mean_g=116.779, mean_b=103.939,
        preprocess_threads=args.data_nthreads)
    val = None
    if args.data_val:
        val = mx.io.ImageRecordIter(
            path_imgrec=args.data_val,
            data_shape=tuple(int(x) for x in args.image_shape.split(",")),
            batch_size=args.batch_size,
            mean_r=123.68, mean_g=116.779, mean_b=103.939,
            preprocess_threads=args.data_nthreads)
    return train, val


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__)
    add_fit_args(parser)
    parser.add_argument("--data-train", type=str, default=None)
    parser.add_argument("--data-val", type=str, default=None)
    parser.add_argument("--image-shape", type=str, default="3,224,224")
    parser.add_argument("--num-classes", type=int, default=1000)
    parser.add_argument("--data-nthreads", type=int, default=4)
    parser.add_argument("--benchmark", type=int, default=0,
                        help="train N synthetic batches instead of a "
                             "dataset (reference --benchmark)")
    parser.set_defaults(network="resnet", num_layers=50, batch_size=32,
                        lr_step_epochs="30,60,90")
    args = parser.parse_args()
    if not args.data_train and not args.benchmark:
        parser.error("either --data-train or --benchmark is required")
    train, val = get_iters(args)
    fit(args, get_symbol(args), train, val)
