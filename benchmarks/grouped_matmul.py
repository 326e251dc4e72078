"""The expert layer's grouped matmul on the chip: `jax.lax.ragged_dot` as
XLA lowers it against `ops/kernels/gmm.py::grouped_matmul`'s three
kernels over a table of tiles, forward, dgrad and wgrad apart, at the
OLMoE cell's two shapes (`[32768, 2048] x [64, 2048, 2048]` and
`[32768, 1024] x [64, 1024, 2048]`, bf16) under four loads: the cell's own
group sizes (layers 1 and 0 of a chip run of `olmoe_fit_resident_4k`, seed
2718281829, last step), uniform 512 a group, and a multinomial draw of
32,768 rows over 64 groups. Host clock over 20 calls closed by a fetch.

The group metadata is a run-time input of the kernels, so one compile a
tile serves every load. PERF.md section 7 holds the table (PR 30).

    chiprun -- python3 benchmarks/grouped_matmul.py [--quick]
    python3 benchmarks/grouped_matmul.py --rehearse-cpu

The platform rule, the clock and the output file are `alone.py`'s.
"""
import sys

import alone

import jax
import jax.numpy as jnp
import numpy as np

from mxnet_tpu.ops import kernels as pk

M, GROUPS = 32768, 64
CELL_LAYER1 = [
    310, 238, 68, 3, 17, 0, 6, 180, 0, 3301, 4, 3905, 699, 2, 0, 0, 2111, 3,
    23, 327, 0, 340, 21, 0, 119, 46, 104, 1129, 213, 7, 3014, 0, 1, 15, 5, 1,
    8, 3828, 142, 1, 2, 7, 11, 0, 3, 121, 0, 6, 0, 31, 2665, 106, 3707, 0, 0,
    780, 1, 25, 66, 8, 23, 3287, 7, 1721]
CELL_LAYER0 = [
    911, 81, 781, 326, 50, 101, 118, 116, 800, 60, 1326, 1209, 544, 938, 440,
    151, 273, 1710, 385, 624, 191, 979, 598, 1322, 613, 87, 150, 134, 74, 112,
    394, 577, 582, 319, 121, 225, 2220, 155, 215, 1318, 126, 64, 602, 236,
    327, 1389, 757, 1914, 455, 295, 218, 160, 369, 426, 145, 849, 356, 44,
    1165, 371, 431, 271, 227, 241]
LOADS = {
    "cell_layer1": CELL_LAYER1,
    "cell_layer0": CELL_LAYER0,
    "uniform512": [512] * GROUPS,
    "multinomial": [int(v) for v in np.random.RandomState(0).multinomial(
        M, [1.0 / GROUPS] * GROUPS)],
}


def ragged(mode):
    dot = jax.lax.ragged_dot
    if mode == "fwd":
        return jax.jit(lambda l, r, d, s: dot(l, r, s))
    if mode == "dgrad":
        return jax.jit(lambda l, r, d, s: jax.vjp(
            lambda l_: dot(l_, r, s), l)[1](d)[0])
    return jax.jit(lambda l, r, d, s: jax.vjp(
        lambda r_: dot(l, r_, s), r)[1](d)[0])


def kernel(mode, tiles, interpret):
    tm = tiles[0]

    def f(l, r, d, s):
        meta = pk.gmm_metadata(s, l.shape[0], tm)
        if mode == "wgrad":
            return pk.gmm.gmm_wgrad_call(
                meta[0], *meta[4:], l, d, groups=r.shape[0], tiles=tiles,
                interpret=interpret)
        return pk.gmm.gmm_call(
            *meta[:4], d if mode == "dgrad" else l, r, tiles=tiles,
            transposed=mode == "dgrad", interpret=interpret)
    return jax.jit(f)


def tile_table(k, n, quick):
    rows = (256,) if quick else (128, 256, 512)
    fwd = [(tm, k, tn) for tn in (512, 1024) for tm in rows]
    fwd += [(512, 1024, 1024), (256, 512, 2048)]
    dgrad = [(tm, tk, n) for tk in (512, 1024) for tm in rows]
    dgrad += [(512, 1024, 1024), (256, k, 1024)]
    wgrad = [(tm, k, 512) for tm in rows]
    wgrad += [(tm, 1024, 1024) for tm in rows] + [(256, 512, 2048)]
    if k == 1024:
        fwd += [(tm, k, 2048) for tm in rows]
        wgrad += [(256, 1024, 2048)]
    if quick:
        fwd, dgrad, wgrad = fwd[:2], dgrad[:2], wgrad[:2]
    return {mode: list(dict.fromkeys(tiles)) for mode, tiles in
            (("fwd", fwd), ("dgrad", dgrad), ("wgrad", wgrad))}


def main():
    run = alone.Run(__file__)
    quick = "--quick" in sys.argv
    m, groups, shapes, loads = M, GROUPS, ((2048, 2048), (1024, 2048)), LOADS
    if run.rehearse:
        m, groups, shapes = 512, 4, ((256, 256),)
        loads = {"skewed": [300, 0, 200, 12], "uniform128": [128] * 4}
    rng = np.random.RandomState(1)
    for k, n in shapes:
        lhs = jnp.asarray(rng.randn(m, k), jnp.bfloat16)
        rhs = jnp.asarray(rng.randn(groups, k, n) * 0.02, jnp.bfloat16)
        dout = jnp.asarray(rng.randn(m, n), jnp.bfloat16)
        sizes = {name: jnp.asarray(v, jnp.int32) for name, v in loads.items()}
        gflop = 2.0 * m * k * n / 1e9
        table = (dict.fromkeys(("fwd", "dgrad", "wgrad"), [(128, 128, 128)])
                 if run.rehearse else tile_table(k, n, quick))
        for mode in ("fwd", "dgrad", "wgrad"):
            f = ragged(mode)
            want = {name: f(lhs, rhs, dout, s) for name, s in sizes.items()}
            run.row(k=k, n=n, mode=mode, kernel="ragged_dot", gflop=gflop,
                    ms={name: run.host_ms(f, lhs, rhs, dout, s)
                        for name, s in sizes.items()})
            for tiles in table[mode]:
                try:
                    f = kernel(mode, tiles, run.rehearse)
                    err = {}
                    for name, s in sizes.items():
                        got = f(lhs, rhs, dout, s).astype(jnp.float32)
                        ref = want[name].astype(jnp.float32)
                        err[name] = float(jnp.abs(got - ref).max()
                                          / jnp.abs(ref).max())
                    run.row(k=k, n=n, mode=mode, kernel="pallas", tiles=tiles,
                            ms={name: run.host_ms(f, lhs, rhs, dout, s)
                                for name, s in sizes.items()},
                            max_rel_diff_to_ragged=err)
                except Exception as e:  # noqa: BLE001 — a refused tile
                    run.row(k=k, n=n, mode=mode, kernel="pallas", tiles=tiles,
                            error=str(e)[:300])
            del want
    run.save()


if __name__ == "__main__":
    main()
