"""A causal window wider than a tile, on the chip: the flash pair at the
Trinity-Mini cell's window call (one sequence of 8,192 tokens, 32 query
heads on 4 key/value heads of 128, a window of 2,048 keys, bf16) at
square tiles of 256, 512 and 1,024 (``flash_tiles`` takes twice the
window capped at 1,024: the band of a 1,024-row q tile crosses three k
tiles, one wholly inside), and the cell's full call at 1,024 beside it;
since PR 58 the tilings of 512 and 1,024 twice, with their cut tiles
whole and by quarters (`flash.cut_steps`): the table `FLASH_MIN_EDGE`
was chosen from (quarters of 256 do not pay; the tiling of 512 runs
them here with the floor lowered for its trace).
Forward + backward a call, the tilings alternating over three rounds:
host clock over 10 calls closed by a fetch, and the kernels' own device
time by their names from a profiler trace of 5 calls. Required operations
are ``bench/flops/afmoe_symbol``'s count (scores and values over the band
or the triangle, three forwards) over the device's bf16 peak. PERF.md
section 7 holds the tables (PR 55; PR 58).

    chiprun -- python3 benchmarks/flash_window_tiles.py
    python3 benchmarks/flash_window_tiles.py --rehearse-cpu

The platform rule, the clocks and the output file are ``alone.py``'s.
"""
import collections
from unittest import mock

import alone

import jax
import jax.numpy as jnp
import numpy as np

from mxnet_tpu.ops.kernels import flash, flash_attention, flash_tiles


def required_flops(t, h, d, window):
    w = min(window or t, t)
    pairs = w * (w + 1) / 2.0 + (t - w) * w
    return 3 * 2.0 * h * pairs * 2 * d


def call(window, block, floor):
    """Forward + backward at square tiles of ``block``, the cut tiles by
    quarters where half a tile is ``floor`` rows at least (the module's
    own floor stands in every other trace)."""
    def loss(q, k, v):
        # read once, as the call site is traced
        with mock.patch.object(flash, "FLASH_MIN_EDGE", floor):
            out = flash_attention(q, k, v, causal=True, window=window,
                                  block_q=block, block_k=block)
        return jnp.sum(out.astype(jnp.float32))
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))


def main():
    run = alone.Run(__file__)
    t, h, g, d, window = ((256, 4, 2, 16, 64) if run.rehearse
                          else (8192, 32, 4, 128, 2048))
    tiles = (32, 64) if run.rehearse else (256, 512, 1024)
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, t, h, d), jnp.bfloat16)
    k, v = (jnp.asarray(rng.randn(1, t, g, d), jnp.bfloat16)
            for _ in range(2))
    rows = [(kind, w, b, form)
            for kind, w, b in [("window", window, b) for b in tiles]
            + [("full", 0, tiles[-1])]
            for form in ("whole", "quarters")
            if form == "whole" or run.rehearse or b >= 512]
    fns = {(kind, w, b, form): call(w, b, b // 2 if form == "quarters"
                                    else b)
           for kind, w, b, form in rows}
    host = collections.defaultdict(list)
    for row, f in run.alternate(fns):
        host[row].append(run.host_ms(f, q, k, v, reps=10))
    for row in rows:
        kind, window, block, form = row
        entry = {"call": kind, "window": window, "block": block,
                 "cut_tiles": form,
                 "chosen": flash_tiles(t, d, jnp.bfloat16, window)[0] == block
                 and (form == "quarters") == bool(
                     flash.cut_half(block, block, True, window)),
                 "host_ms": sorted(host[row]),
                 "required_ms": run.bound(
                     flops=required_flops(t, h, d, window))}
        if not run.rehearse:
            entry["device_ms"] = alone.by_kernel(
                run.device_ops(fns[row], q, k, v), "flash_")
            entry["kernels_ms"] = alone.named(entry["device_ms"], "flash_")
            entry["roofline_share"] = alone.ratio(
                entry["required_ms"], entry["kernels_ms"], 100.0)
        run.row(platform=run.platform, **entry)
    run.save()


if __name__ == "__main__":
    main()
