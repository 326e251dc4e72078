"""KVStore tests (parity: reference test_kvstore.py — single-process
aggregation, custom updater, per-device value lists)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import ndarray as nd
from mxnet_tpu.test_utils import assert_almost_equal

SHAPE = (4, 4)
KEYS = [5, 7, 11]


def init_kv(kv_type="local"):
    kv = mx.kvstore.create(kv_type)
    kv.init(3, nd.zeros(SHAPE))
    kv.init(KEYS, [nd.zeros(SHAPE)] * len(KEYS))
    return kv


def test_single_kv_pair():
    kv = init_kv()
    kv.push(3, nd.ones(SHAPE))
    val = nd.empty(SHAPE)
    kv.pull(3, out=val)
    assert_almost_equal(val.asnumpy(), np.ones(SHAPE))


def test_aggregator():
    """Push a list of per-device values → stored = sum."""
    kv = init_kv()
    num_devs = 4
    vals = [nd.ones(SHAPE) for _ in range(num_devs)]
    kv.push(3, vals)
    out = [nd.empty(SHAPE) for _ in range(num_devs)]
    kv.pull(3, out=out)
    for o in out:
        assert_almost_equal(o.asnumpy(), num_devs * np.ones(SHAPE))
    # list of keys
    kv.push(KEYS, [[nd.ones(SHAPE) * 2] * num_devs] * len(KEYS))
    outs = [[nd.empty(SHAPE) for _ in range(num_devs)] for _ in KEYS]
    kv.pull(KEYS, out=outs)
    for olist in outs:
        for o in olist:
            assert_almost_equal(o.asnumpy(), 2 * num_devs * np.ones(SHAPE))


def test_updater():
    kv = init_kv()

    def updater(key, recv, stored):
        stored += recv * 2

    kv._set_updater(updater)
    kv.push(3, nd.ones(SHAPE))
    val = nd.empty(SHAPE)
    kv.pull(3, out=val)
    assert_almost_equal(val.asnumpy(), 2 * np.ones(SHAPE))
    kv.push(3, [nd.ones(SHAPE)] * 3)
    kv.pull(3, out=val)
    assert_almost_equal(val.asnumpy(), (2 + 6) * np.ones(SHAPE))


def test_optimizer_on_kvstore():
    kv = init_kv()
    kv.set_optimizer(mx.optimizer.create("sgd", learning_rate=0.1))
    grad = nd.ones(SHAPE)
    kv.push(3, grad)
    w = nd.empty(SHAPE)
    kv.pull(3, out=w)
    assert_almost_equal(w.asnumpy(), -0.1 * np.ones(SHAPE), rtol=1e-5)


def test_optimizer_on_kvstore_follows_the_gradient_device():
    """The weight is init()ed where arg_params live (the host) while the
    gradients arrive from the training device. The stored weight moves
    to the reduce's device (parity kvstore_local.h Push) instead of the
    updater mixing devices — what stopped examples/train_imagenet.py
    --ctx tpu on the chip, reproduced here with two host devices."""
    kv = mx.kvstore.create("local")
    kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.5, momentum=0.9))
    kv.init(0, nd.ones(SHAPE, ctx=mx.cpu(0)))
    for _ in range(2):
        kv.push(0, nd.ones(SHAPE, ctx=mx.cpu(1)))
    out = nd.empty(SHAPE, ctx=mx.cpu(1))
    kv.pull(0, out=out)
    # step 1: mom=-0.5, w=0.5; step 2: mom=-0.95, w=-0.45
    assert_almost_equal(out.asnumpy(), -0.45 * np.ones(SHAPE))
    assert out.context == mx.cpu(1)


def test_string_keys_stable():
    kv = mx.kvstore.create("local")
    kv.init("weight", nd.zeros(SHAPE))
    kv.set_optimizer(mx.optimizer.create("sgd", learning_rate=0.1,
                                         momentum=0.9))
    kv.push("weight", nd.ones(SHAPE))
    kv.push("weight", nd.ones(SHAPE))
    w = nd.empty(SHAPE)
    kv.pull("weight", out=w)
    # two momentum steps: -0.1, then -0.1*0.9-0.1 accumulated
    expect = -0.1 + (-0.19)
    assert_almost_equal(w.asnumpy(), expect * np.ones(SHAPE), rtol=1e-4)


def test_rank_and_type():
    kv = mx.kvstore.create("local")
    assert kv.rank == 0
    assert kv.num_workers == 1
    assert kv.type == "local"
    with pytest.raises(mx.MXNetError):
        mx.kvstore.create("bogus")


def test_get_num_dead_node():
    kv = mx.kvstore.create("dist_sync")
    assert kv.get_num_dead_node(0) == 0


def test_all_accepted_types_route():
    """Every reference kvstore type string creates a working store with
    single-process semantics (dist_* fall back to size-1 local when no
    launcher env is present); unknown types raise."""
    for t in ("local", "local_allreduce_cpu", "local_allreduce_device",
              "device", "dist_sync", "dist_device_sync", "dist_async"):
        kv = mx.kvstore.create(t)
        kv.init(7, mx.nd.ones((3,)))
        out = mx.nd.zeros((3,))
        kv.push(7, [mx.nd.ones((3,)) * 2, mx.nd.ones((3,))])
        kv.pull(7, out=out)
        # no updater: the reduced sum (2 + 1) REPLACES the stored value
        np.testing.assert_allclose(out.asnumpy(), 3.0 * np.ones(3))
        assert kv.type == t
        assert kv.num_workers == 1  # no launcher env: size-1 fallback
    with pytest.raises(mx.base.MXNetError):
        mx.kvstore.create("definitely_not_a_store")
