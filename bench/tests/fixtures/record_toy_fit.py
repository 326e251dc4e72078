#!/usr/bin/env python3
"""How ``v5e_toy_fit.xplane.pb`` was recorded (on the chip, through the
chip tool):

    chiprun -- python3 bench/tests/fixtures/record_toy_fit.py

A toy ``Module.fit`` (conv, BatchNorm, relu, max pool, fc, softmax; batch
16 of 3x16x16) through the fused step with telemetry on; the profiler
runs over three steps after three of warm-up, between the benchmark's
slice marks. The profiler's ``/host:metadata`` plane (the HLO protos,
most of the file) is dropped; what stays goes to
``chiprun_out/v5e_toy_fit.xplane.pb``. ``--cpu`` rehearses the flow here
(no device plane comes of it).
"""
import glob
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.dirname(BENCH))
if "--cpu" in sys.argv:
    os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import telemetry  # noqa: E402
from mxnet_tpu.parallel import make_mesh  # noqa: E402

import reduce_scopes  # noqa: E402
import reduce_trace  # noqa: E402

WARMUP, STEPS = 3, 3


def toy_symbol():
    net = mx.sym.Variable("data")
    net = mx.sym.Convolution(net, num_filter=8, kernel=(3, 3), pad=(1, 1),
                             name="conv0")
    net = mx.sym.BatchNorm(net, name="bn0")
    net = mx.sym.Activation(net, act_type="relu", name="relu0")
    net = mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2), pool_type="max",
                         name="pool0")
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=10,
                                name="fc0")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def main():
    dev = jax.devices()[0]
    print("platform=%s kind=%r" % (dev.platform, dev.device_kind))
    trace_dir = tempfile.mkdtemp(prefix="toy_fit_trace_")
    seen = []

    def on_batch(param):
        seen.append(param.nbatch)
        if len(seen) == WARMUP:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation(reduce_trace.SLICE_BEGIN):
                pass
        if len(seen) == WARMUP + STEPS:
            with jax.profiler.TraceAnnotation(reduce_trace.SLICE_END):
                pass
            jax.profiler.stop_trace()

    rng = np.random.RandomState(0)
    n = 16 * (WARMUP + STEPS)
    it = mx.io.NDArrayIter(rng.rand(n, 3, 16, 16).astype("f"),
                           rng.randint(0, 10, n).astype("f"), batch_size=16)
    ctx = mx.cpu(0) if dev.platform == "cpu" else mx.tpu(0)
    mod = mx.mod.Module(toy_symbol(), context=ctx,
                        mesh=make_mesh(dp=1, devices=[dev]))
    telemetry.enable()
    mod.fit(it, eval_metric="ce", optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
            kvstore="device", num_epoch=1, batch_end_callback=on_batch)
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]

    # XSpace.planes is field 1: keep every plane but /host:metadata
    with open(path, "rb") as f:
        space = memoryview(f.read())
    kept = bytearray()
    for field, _, plane in reduce_scopes._fields(space):
        if field != 1:
            continue
        name = [reduce_scopes._text(v)
                for pf, _, v in reduce_scopes._fields(plane) if pf == 2]
        if name != ["/host:metadata"]:
            size, head = len(plane), bytearray([0x0A])
            while size >= 0x80:
                head.append((size & 0x7F) | 0x80)
                size >>= 7
            head.append(size)
            kept += head + bytes(plane)
    out = os.path.join(os.path.dirname(BENCH), "chiprun_out",
                       "v5e_toy_fit.xplane.pb")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "wb") as f:
        f.write(kept)
    shutil.rmtree(trace_dir, ignore_errors=True)
    red = reduce_scopes.reduce(reduce_trace.load(out),
                               reduce_scopes.scope_names(out))
    print("wrote %s (%d bytes)" % (out, len(kept)))
    print(red)


if __name__ == "__main__":
    main()
