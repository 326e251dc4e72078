"""CIFAR-scale RecordIO convergence gate through the fused multi-device
path (VERDICT r4 'next' #9 / reference tests/python/train/test_conv.py).

The digits-scale gates (test_train_convergence.py) prove optimizer/grad
correctness but bypass the production input pipeline. This one exercises
the full stack the reference's train tier exercises: pack a JPEG
RecordIO file (recordio.pack_img — the same writer im2rec uses), read
it back through ImageRecordIter (native decode, mean subtract,
shuffle), and train a small convnet via Module.fit on a multi-device
mesh with kvstore='device' (the fused ShardedTrainStep path) —
asserting a real accuracy bar.

Zero egress makes CIFAR itself unavailable, so the classes are
synthetic but genuinely visual: each class is an oriented sinusoidal
grating (angle = class * 18deg) under per-image phase, frequency
jitter, and pixel noise, surviving JPEG round-trips — a texture
classification task a 2-conv net must learn from pixels; labels are
not recoverable from any trivial statistic (mean/std are
class-independent by construction).
"""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import recordio

N_CLASSES = 10
SIZE = 32
# full tier: 4000 train imgs; the default CI tier keeps the suite fast
FULL = os.environ.get("MXNET_TEST_TRAIN_FULL") == "1"
N_TRAIN = 4000 if FULL else 1200
N_VAL = 1000 if FULL else 300
BATCH = 100
EPOCHS = 12 if FULL else 10


def _grating(rng, cls):
    theta = np.pi * cls / N_CLASSES
    freq = 3.0 + rng.uniform(-0.3, 0.3)
    phase = rng.uniform(0, 2 * np.pi)
    yy, xx = np.mgrid[0:SIZE, 0:SIZE].astype(np.float32) / SIZE
    wave = np.sin(2 * np.pi * freq *
                  (np.cos(theta) * xx + np.sin(theta) * yy) + phase)
    img = 127 + 80 * wave[..., None] + rng.randn(SIZE, SIZE, 3) * 25
    return np.clip(img, 0, 255).astype(np.uint8)


def _pack(path_prefix, n, seed):
    rng = np.random.RandomState(seed)
    rec, idx = path_prefix + ".rec", path_prefix + ".idx"
    w = recordio.MXIndexedRecordIO(idx, rec, "w")
    for i in range(n):
        cls = int(rng.randint(N_CLASSES))
        w.write_idx(i, recordio.pack_img(
            recordio.IRHeader(0, float(cls), i, 0), _grating(rng, cls)))
    w.close()
    return rec


def test_recordio_convergence_fused(tmp_path):
    train_rec = _pack(str(tmp_path / "train"), N_TRAIN, 0)
    val_rec = _pack(str(tmp_path / "val"), N_VAL, 1)

    def make_iter(rec, shuffle):
        return mx.io.ImageRecordIter(
            path_imgrec=rec, data_shape=(3, SIZE, SIZE),
            batch_size=BATCH, shuffle=shuffle,
            mean_r=127.0, mean_g=127.0, mean_b=127.0,
            scale=1.0 / 60.0, preprocess_threads=2)

    train = make_iter(train_rec, True)
    val = make_iter(val_rec, False)

    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, kernel=(5, 5), num_filter=16,
                             pad=(2, 2), name="c1")
    net = mx.sym.BatchNorm(net, name="bn1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2),
                         pool_type="max")
    net = mx.sym.Convolution(net, kernel=(3, 3), num_filter=32,
                             pad=(1, 1), name="c2")
    net = mx.sym.BatchNorm(net, name="bn2")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, global_pool=True, pool_type="avg",
                         kernel=(1, 1))
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=N_CLASSES,
                                name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")

    mod = mx.mod.Module(net, context=[mx.cpu(i) for i in range(4)])
    np.random.seed(0)
    mx.random.seed(0)
    mod.fit(train, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9,
                              "wd": 1e-4},
            initializer=mx.init.Xavier(rnd_type="gaussian",
                                       factor_type="in", magnitude=2),
            kvstore="device", num_epoch=EPOCHS)
    assert mod._fused_trainer is not None, "fused path not taken"

    val.reset()
    acc = dict(mod.score(val, mx.metric.Accuracy()))["accuracy"]
    assert acc >= 0.90, "val accuracy %.3f below the convergence bar" % acc
