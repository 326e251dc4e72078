"""A causal window wider than a tile, on the chip: the flash pair at the
Trinity-Mini cell's window call (one sequence of 8,192 tokens, 32 query
heads on 4 key/value heads of 128, a window of 2,048 keys, bf16) at
square tiles of 256, 512 and 1,024 (``flash_tiles`` takes twice the
window capped at 1,024: the band of a 1,024-row q tile crosses three k
tiles, one wholly inside), and the cell's full call at 1,024 beside it.
Forward + backward a call, the tilings alternating over three rounds:
host clock over 10 calls closed by a fetch, and the kernels' own device
time by their names from a profiler trace of 5 calls. Required operations
are ``bench/flops/afmoe_symbol``'s count (scores and values over the band
or the triangle, three forwards) over the bf16 peak of 197 TFLOP/s.
Prints one JSON line a row and writes
``chiprun_out/flash_window_tiles.json``; PERF.md section 7 holds the
table (PR 55).

    chiprun -- python3 benchmarks/flash_window_tiles.py

``--rehearse-cpu`` runs the same flow at a toy size here (the pair's
branch for other platforms, no trace): it proves the script, not a
number.
"""
import collections
import glob
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from mxnet_tpu.ops.kernels import flash_attention, flash_tiles  # noqa: E402

PEAK = 197e12
REHEARSE = "--rehearse-cpu" in sys.argv
T, H, G, D, WINDOW = (256, 4, 2, 16, 64) if REHEARSE else (8192, 32, 4, 128,
                                                            2048)
TILES = (32, 64) if REHEARSE else (256, 512, 1024)


def required_ms(window):
    w = min(window or T, T)
    pairs = w * (w + 1) / 2.0 + (T - w) * w
    return 3 * 2.0 * H * pairs * 2 * D / PEAK * 1e3


def call(window, block):
    def loss(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=True, window=window, block_q=block,
            block_k=block).astype(jnp.float32))
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))


def host_ms(f, *args, reps=10):
    jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        r = f(*args)
    np.asarray(r[0].ravel()[:1])  # a fetch closes the last call
    return (time.perf_counter() - t0) / reps * 1e3


def device_ms(f, *args, reps=5):
    """Device ms a call of each ``flash_`` kernel and of everything else,
    from a profiler trace of ``reps`` calls."""
    from jax.profiler import ProfileData

    where = tempfile.mkdtemp()
    with jax.profiler.trace(where):
        for _ in range(reps):
            r = f(*args)
        jax.block_until_ready(r)
    trace, = glob.glob(where + "/plugins/profile/*/*.xplane.pb")
    ms = collections.Counter()
    for plane in ProfileData.from_file(trace).planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                name = e.name.split(" = ")[0].lstrip("%")
                ms[name.split(".")[0] if name.startswith("flash_")
                   else "everything else"] += e.duration_ns / 1e6 / reps
    return dict(ms)


def main():
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, T, H, D), jnp.bfloat16)
    k, v = (jnp.asarray(rng.randn(1, T, G, D), jnp.bfloat16)
            for _ in range(2))
    rows = [("window", WINDOW, b) for b in TILES] + [("full", 0, TILES[-1])]
    fns = {row: call(row[1], row[2]) for row in rows}
    host = collections.defaultdict(list)
    for _ in range(3):  # the tilings alternate
        for row in rows:
            host[row].append(host_ms(fns[row], q, k, v))
    out = []
    for row in rows:
        kind, window, block = row
        entry = {"call": kind, "window": window, "block": block,
                 "chosen": flash_tiles(T, D, jnp.bfloat16, window)[0] == block,
                 "host_ms": sorted(host[row]),
                 "required_ms": required_ms(window)}
        if not REHEARSE:
            entry["device_ms"] = device_ms(fns[row], q, k, v)
            kernels = sum(ms for name, ms in entry["device_ms"].items()
                          if name.startswith("flash_"))
            entry["kernels_ms"] = kernels
            entry["roofline_share"] = 100.0 * entry["required_ms"] / kernels
        entry["platform"] = jax.devices()[0].platform
        print(json.dumps(entry), flush=True)
        out.append(entry)
    if not REHEARSE:
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/flash_window_tiles.json", "w") as f:
            json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
