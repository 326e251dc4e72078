#!/usr/bin/env python
"""Gradient-synchronization bandwidth benchmark.

Parity: reference ``tools/bandwidth/measure.py`` — measures the KVStore
push+pull bandwidth that bounds data-parallel scaling (SURVEY.md §6,
"allreduce bandwidth").

TPU-native: the synchronization primitive is an XLA all-reduce (psum)
over the device mesh, so this measures jitted psum throughput across
message sizes and reports the standard algorithmic-bandwidth figure
busbw = 2·(n-1)/n · bytes / time per device.

Run (virtual 8-device mesh off-TPU):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python tools/bandwidth/measure.py
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def measure(sizes_mb=(1, 4, 16, 64), iters=10, dtype="float32"):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    devices = jax.devices()
    n = len(devices)
    mesh = Mesh(np.asarray(devices), ("dp",))

    results = []
    for mb in sizes_mb:
        elems = int(mb * 2 ** 20 / np.dtype(dtype).itemsize)
        # per-device shard; allreduce payload = full array
        x = jnp.ones((n, elems), dtype)

        @jax.jit
        def allreduce(v):
            return shard_map(
                lambda s: jax.lax.psum(s, "dp"),
                mesh=mesh, in_specs=P("dp", None), out_specs=P("dp", None),
            )(v)

        allreduce(x).block_until_ready()  # compile
        t0 = time.perf_counter()
        for _ in range(iters):
            out = allreduce(x)
        out.block_until_ready()
        dt = (time.perf_counter() - t0) / iters
        bytes_ = elems * np.dtype(dtype).itemsize
        busbw = 2.0 * (n - 1) / n * bytes_ / dt / 1e9
        results.append({"size_mb": mb, "time_ms": dt * 1e3,
                        "busbw_GBps": busbw, "devices": n})
        print("size %6.1f MB  time %8.3f ms  busbw %7.2f GB/s (n=%d)"
              % (mb, dt * 1e3, busbw, n))
    return results


def measure_kvstore(network="resnet", num_layers=50, ndev=2,
                    kv_store="device", optimizer=None, num_batches=5,
                    image_shape="3,224,224", num_classes=1000,
                    test_results=True):
    """Reference-parity mode: push+pull the REAL per-layer gradient
    arrays of a model through the product KVStore (the path Module.fit
    synchronizes on), check the merged result against a numpy oracle,
    and report the reference's algorithmic-bandwidth figure
    size * 2*(n-1)/n / time (tools/bandwidth/measure.py:115 in the
    reference; their formula, their warmup-batch convention)."""
    import importlib

    import numpy as np

    import mxnet_tpu as mx

    devs = [mx.cpu(i) for i in range(ndev)]
    kv = mx.kv.create(kv_store)
    updater = None
    if optimizer and optimizer != "None":
        kv.set_optimizer(mx.optimizer.Optimizer.create_optimizer(optimizer))
        updater = mx.optimizer.get_updater(
            mx.optimizer.Optimizer.create_optimizer(optimizer))

    mod = importlib.import_module("mxnet_tpu.models." + network)
    kwargs = {"num_classes": num_classes}
    if network == "resnet":
        kwargs.update(num_layers=num_layers, image_shape=image_shape)
    sym = mod.get_symbol(**kwargs)
    data_shape = (32,) + tuple(int(s) for s in image_shape.split(","))
    arg_shapes, _, _ = sym.infer_shape(data=data_shape)
    shapes = [s for n_, s in zip(sym.list_arguments(), arg_shapes)
              if "weight" in n_ or "bias" in n_]
    size_mb = sum(int(np.prod(s)) for s in shapes) * 4 / 1e6
    print("num of arrays = %d, total size = %.3f MB" % (len(shapes), size_mb))

    rng = np.random.RandomState(0)
    grads_np = [[rng.uniform(-1, 1, s).astype(np.float32) for _ in devs]
                for s in shapes]
    grads = [[mx.nd.array(g, ctx=d) for g, d in zip(gs, devs)]
             for gs in grads_np]
    weights = [[mx.nd.zeros(s, d) for d in devs] for s in shapes]
    # numpy oracle: kv merge = sum over device list (scaled by workers)
    oracle = [sum(gs) * kv.num_workers for gs in grads_np]
    oracle_w = [np.zeros(s, np.float32) for s in shapes]

    for i, s in enumerate(shapes):
        kv.init(i, mx.nd.zeros(s))

    results = []
    toc = 0.0
    for b in range(num_batches + 1):
        tic = time.perf_counter()
        for i, g in enumerate(grads):
            kv.push(i, g, i)
        for i, w in enumerate(weights):
            kv.pull(i, w, i)
        for ws in weights:
            for w in ws:
                w.wait_to_read()
        toc += time.perf_counter() - tic
        if test_results:
            if updater is None:
                ref = oracle
            else:
                for i, (w0, g0) in enumerate(zip(oracle_w, oracle)):
                    gnd, wnd = mx.nd.array(g0), mx.nd.array(w0)
                    updater(i, gnd, wnd)
                    oracle_w[i] = wnd.asnumpy()
                ref = oracle_w
            num = sum(float(np.abs(w.asnumpy() - r).sum())
                      for ws, r in zip(weights, ref) for w in ws)
            den = sum(float(np.abs(r).sum()) for r in ref)
            err = num / den
        else:
            err = -1.0
        if b != 0:  # batch 0 is warmup, reference convention
            bw = size_mb * 2 * (len(devs) - 1) / len(devs) / toc / 1e3
            print("iter %d, %f sec, %f GB/sec per device, error %f"
                  % (b, toc, bw, err))
            results.append({"iter": b, "time_s": toc, "bandwidth_GBps": bw,
                            "error": err})
        toc = 0.0
    return results


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--sizes-mb", type=float, nargs="+",
                   default=[1, 4, 16, 64])
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--dtype", default="float32")
    p.add_argument("--network", default=None,
                   help="model-shape KVStore mode (reference semantics): "
                        "e.g. --network resnet --num-layers 152")
    p.add_argument("--num-layers", type=int, default=50)
    p.add_argument("--num-devices", type=int, default=2)
    p.add_argument("--kv-store", default="device")
    p.add_argument("--optimizer", default=None)
    p.add_argument("--num-batches", type=int, default=5)
    p.add_argument("--image-shape", default="3,224,224")
    p.add_argument("--num-classes", type=int, default=1000)
    p.add_argument("--test-results", type=int, default=1)
    args = p.parse_args(argv)
    if os.environ.get("JAX_PLATFORMS", "").strip() == "cpu":
        # pin the config knob too, before the first backend touch
        import jax

        jax.config.update("jax_platforms", "cpu")
    if args.network:
        measure_kvstore(args.network, args.num_layers, args.num_devices,
                        args.kv_store, args.optimizer, args.num_batches,
                        args.image_shape, args.num_classes,
                        bool(args.test_results))
    else:
        measure(tuple(args.sizes_mb), args.iters, args.dtype)


if __name__ == "__main__":
    main()
