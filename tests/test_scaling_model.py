"""Comm-volume accounting: the collective bytes read out of a compiled
step's HLO must agree with first-principles gradient sizes, so that the
fused dp step is held to one float32 all-reduce a parameter.
"""
import os
import re
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

import mxnet_tpu as mx

DTYPE_BYTES = {"f32": 4, "bf16": 2, "pred": 1, "u8": 1, "s32": 4, "f16": 2}


def hlo_allreduce_bytes(hlo_text):
    """Sum output bytes of every all-reduce / reduce-scatter /
    all-gather in an optimized-HLO dump, keyed by op kind."""
    sizes = {"all-reduce": 0, "reduce-scatter": 0, "all-gather": 0}
    counts = {k: 0 for k in sizes}
    pat = re.compile(
        r"=\s*(?:\(([^)]*)\)|(\S+))\s+(all-reduce|reduce-scatter|all-gather)"
        r"(?:-start)?\(")
    shape_pat = re.compile(r"(\w+)\[([\d,]*)\]")
    for m in pat.finditer(hlo_text):
        shapes_blob = m.group(1) or m.group(2)
        kind = m.group(3)
        total = 0
        for sm in shape_pat.finditer(shapes_blob):
            dt, dims = sm.groups()
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * DTYPE_BYTES.get(dt, 4)
        sizes[kind] += total
        counts[kind] += 1
    return sizes, counts


def test_parser_reads_allreduce_shapes():
    hlo = """
  %ar0 = f32[64,128] all-reduce(f32[64,128] %x), replica_groups={}
  %t = (f32[256], f32[16,4]) all-reduce(f32[256] %a, f32[16,4] %b)
  %rs = bf16[32] reduce-scatter(bf16[256] %c), dimensions={0}
"""
    sizes, counts = hlo_allreduce_bytes(hlo)
    assert counts["all-reduce"] == 2
    assert sizes["all-reduce"] == 64 * 128 * 4 + 256 * 4 + 16 * 4 * 4
    assert counts["reduce-scatter"] == 1
    assert sizes["reduce-scatter"] == 32 * 2


def test_dp_step_allreduce_bytes_match_param_bytes():
    """An 8-way dp MLP step must allreduce exactly one f32 gradient per
    parameter — the property the ResNet-50 accounting relies on."""
    from mxnet_tpu.parallel import ShardedTrainStep, make_mesh

    # this accounts for the per-key schedule; pin it (the default flat
    # bucketed/sharded update coalesces gradients and adds a weight
    # all-gather — tests/test_sharded_update.py holds that one)
    prev = os.environ.get("MXTPU_BUCKET_BYTES")
    os.environ["MXTPU_BUCKET_BYTES"] = "0"
    try:
        _dp_step_allreduce_check(ShardedTrainStep, make_mesh)
    finally:
        if prev is None:
            del os.environ["MXTPU_BUCKET_BYTES"]
        else:
            os.environ["MXTPU_BUCKET_BYTES"] = prev


def _dp_step_allreduce_check(ShardedTrainStep, make_mesh):
    mesh = make_mesh(dp=8)
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=32, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=10, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    opt = mx.optimizer.create("sgd", learning_rate=0.1)
    step = ShardedTrainStep(net, mesh, optimizer=opt)
    rng = np.random.RandomState(0)
    arg_shapes, _, _ = net.infer_shape(data=(16, 8), softmax_label=(16,))
    host = {n: mx.nd.array(rng.randn(*s).astype(np.float32) * 0.1)
            for n, s in zip(net.list_arguments(), arg_shapes)
            if n not in ("data", "softmax_label")}
    params, aux = step.place_params(host, {})
    opt_state = step.make_state(params)
    batch = {
        "data": jax.device_put(rng.rand(16, 8).astype(np.float32),
                               step.batch_sharding()),
        "softmax_label": jax.device_put(np.zeros(16, np.float32),
                                        step.batch_sharding()),
    }
    step.compile()
    hlo = step._step.lower(
        params, aux, opt_state, batch, jnp.zeros((2,), jnp.uint32),
        jnp.asarray(0.1, jnp.float32), jnp.asarray(1.0, jnp.float32),
        jnp.asarray(jnp.inf, jnp.float32)  # guard gate open
    ).compile().as_text()
    sizes, _ = hlo_allreduce_bytes(hlo)
    param_bytes = sum(int(np.prod(v.shape)) * 4 for v in host.values())
    total = sum(sizes.values())
    # one f32 allreduce per gradient; fusion may add a few scalar
    # reductions (loss), hence the loose-but-meaningful band
    assert 0.95 * param_bytes <= total <= 1.2 * param_bytes, (
        total, param_bytes)
