"""The chunked state-space scan on the chip: `ops/transformer.py::ssd_scan`
(the `jnp.einsum` form, under `jax.checkpoint(policy=dots_saveable)` as
the Mamba2 op ran it before the kernels, plus the skip) against
`ops/kernels/ssd.py::ssd_scan` (the `ssd_fwd_` / `ssd_bwd_` kernel pair)
at the Nemotron cell's shape (one sequence of 8,192 tokens, 64 heads of 64,
state 128, 8 groups, chunks of 128, bf16), forward and forward + backward,
the two forms alternating. Host clock over 20 calls closed by a fetch;
the arrays cross the jit boundary as the op holds them (``[B, T, H P]``,
``[B, T, G N]``) and are reshaped inside, as ``mamba2`` does: a
``[B, T, H, P]`` array of 64-wide heads has another tiled layout in HBM
and the copies between the two are not the scan's. The kernels' own
device time is read from a profiler trace by their names.

Also prints how far each form's output and gradients are, on the chip,
from the einsum form in float32 with every product at the highest
precision (largest difference over that one's largest magnitude), for
bf16 and for float32 operands, and what a grid step costs against its
MXU work. Prints one JSON line a row and writes
`chiprun_out/ssd_scan_table.json`; PERF.md section 7 holds the table
(PR 39; the kernels are PR 38's, which the ledger holds as refused
for its set-up).

Then the pair alone by shape (``SHAPES``: the Nemotron cell's and the
Falcon-H1 cell's, 4,096 tokens, 16 heads of 128 in one group, state 256):
device ms a call forward and backward from the trace, us a grid step, and
the share of the bytes bound (``x``, ``B``, ``C``, ``dt`` in and ``y`` out
once forward, three times that for forward + backward, over 819 GB/s).
``--shapes-only`` prints that table alone.

``--gate-norm`` prints the table of what follows the scan instead (the
gate and the grouped RMSNorm, ``ops/kernels/gate_norm.py``, PR 52): at
each of ``GATE_NORM_SHAPES`` (the Nemotron, Falcon-H1 and Olmo-Hybrid
cells') the ``jax.numpy`` form alone under its ``jax.checkpoint`` (with
the transposing copy of a head-major ``o``), the kernel pair at the tiles
``gate_norm_tiles`` picks and at every other (row tile, column tile) that
fits, device ms forward and backward from a trace, GB/s over the bytes the
op must move, and how far the pair's results are from the form's on the
chip. Writes ``chiprun_out/gate_norm_table.json``.

    chiprun -- python3 benchmarks/ssd_scan.py [--shapes-only | --gate-norm]
"""
import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from mxnet_tpu.ops import kernels as pk  # noqa: E402
from mxnet_tpu.ops.transformer import ssd_scan  # noqa: E402

B, T, H, P, G, N, CHUNK = 1, 8192, 64, 64, 8, 128, 128
PEAK_TFLOPS = 197.0     # bf16, one v5e chip (Google Cloud documentation)
PEAK_GBS = 819.0        # HBM, the same source
# (t, heads, head_dim, groups, state) of the cells that run the pair
SHAPES = {"nemotron3_nano_fit_share_8k": (8192, 64, 64, 8, 128),
          "falcon_h1_fit_share_4k": (4096, 16, 128, 1, 256)}


def _time(f, *args, reps=20):
    jax.block_until_ready(f(*args))
    jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        r = f(*args)
    jax.block_until_ready(r)
    np.asarray(jax.tree_util.tree_leaves(r)[0].ravel()[:1])  # a fetch
    return (time.perf_counter() - t0) / reps * 1e3


def _kernel_device_ms(g, *args, reps=10, prefix="ssd_"):
    """Device ms a call of each kernel named ``prefix``... and of
    everything else in the program, from a profiler trace of ``reps``
    calls."""
    import collections
    import glob
    import tempfile

    from jax.profiler import ProfileData

    jax.block_until_ready(g(*args))
    where = tempfile.mkdtemp()
    with jax.profiler.trace(where):
        for _ in range(reps):
            r = g(*args)
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
                ms[name.split(".")[0] if name.startswith(prefix)
                   else "everything else"] += e.duration_ns / 1e6 / reps
    return dict(ms)


def inputs(seed, dtype, t=T, h=H, p=P, g=G, n=N):
    """x, B, C as the op's convolution leaves them (unit scale, heads and
    groups side by side in the last dimension), step sizes and rates by
    the published rule."""
    rng = np.random.RandomState(seed)
    step = np.exp(rng.uniform(np.log(0.001), np.log(0.1), h))
    dt_bias = step + np.log(-np.expm1(-step))
    x, bm, cm = (jnp.asarray(rng.randn(B, t, width), dtype)
                 for width in (h * p, g * n, g * n))
    dt = jax.nn.softplus(
        jnp.asarray(rng.randn(B, t, h) + dt_bias, jnp.float32))
    a = -jnp.asarray(rng.uniform(1, 16, h), jnp.float32)
    skip = jnp.asarray(1 + 0.2 * rng.randn(h), jnp.float32)
    return ((x, bm, cm, dt, a, skip),
            jnp.asarray(rng.randn(B, t, h * p), jnp.float32))


def forms():
    scan = jax.checkpoint(
        lambda *a: ssd_scan(*a, CHUNK),
        policy=jax.checkpoint_policies.dots_saveable)

    def einsum(*a):
        return scan(*a[:5]) + a[5][:, None] * a[0].astype(jnp.float32)

    def kernel(*a):
        return pk.ssd_scan(*a, CHUNK)

    def both(form):
        def f(x, bm, cm, *rest):
            t = x.shape[1]
            return form(x.reshape(B, t, H, P), bm.reshape(B, t, G, N),
                        cm.reshape(B, t, G, N), *rest).reshape(B, t, H * P)

        def loss(cot, *a):
            return jnp.sum(f(*a) * cot)
        return (jax.jit(f),
                jax.jit(jax.value_and_grad(loss,
                                           argnums=(1, 2, 3, 4, 5, 6))))
    return {"einsum": both(einsum), "kernel": both(kernel)}


def pair_by_shape(row):
    """The kernel pair alone at each of ``SHAPES``, bf16."""
    for cell, (t, h, p, g, n) in SHAPES.items():
        args, cot = inputs(2, jnp.bfloat16, t, h, p, g, n)

        def loss(cot, x, bm, cm, *rest, h=h, p=p, g=g, n=n, t=t):
            y = pk.ssd_scan(x.reshape(B, t, h, p), bm.reshape(B, t, g, n),
                            cm.reshape(B, t, g, n), *rest, CHUNK)
            return jnp.sum(y.reshape(B, t, h * p) * cot)

        grad = jax.jit(jax.value_and_grad(loss, argnums=(1, 2, 3, 4, 5, 6)))
        ms = _kernel_device_ms(grad, cot, *args)
        fwd = sum(v for k, v in ms.items() if k.startswith("ssd_fwd"))
        bwd = sum(v for k, v in ms.items() if k.startswith("ssd_bwd"))
        steps = B * g * (t // CHUNK)
        bound_ms = 2.0 * t * (2 * h * p + 2 * g * n + h) / PEAK_GBS / 1e6
        row(pair_alone=cell, t=t, heads=h, head_dim=p, groups=g, state=n,
            grid_steps=steps, fwd_ms=fwd, bwd_ms=bwd,
            fwd_us_step=1e3 * fwd / steps, bwd_us_step=1e3 * bwd / steps,
            rest_ms=ms.get("everything else"), bytes_bound_fwd_ms=bound_ms,
            share_of_bytes_bound_fwd=100 * bound_ms / fwd,
            share_of_bytes_bound_fwd_bwd=100 * 3 * bound_ms / (fwd + bwd),
            kernels=sorted(k for k in ms if k.startswith("ssd_")))


# form, groups, group width, T, the gate's array's width, the gate's scale
GATE_NORM_SHAPES = {
    "nemotron3_nano_fit_share_8k": ("gate_first", 8, 512, 8192, 10304, None),
    "falcon_h1_fit_share_4k": ("gate_first", 1, 2048, 4096, 4624, 0.7),
    "olmo_hybrid_fit_stage_4k": ("norm_first", 30, 192, 4096, 5760, None)}


def gate_norm_table(row, rows_tiles=(128, 256, 512, 1024, 2048)):
    """The gate and norm alone at each of ``GATE_NORM_SHAPES``, bf16."""
    from mxnet_tpu.ops.kernels import gate_norm as gn

    f32, bf16 = jnp.float32, jnp.bfloat16

    def gbs(mb, ms):
        return mb / ms if ms else None

    def each_way(f):
        # the result and the three cotangents, the result's cotangent an
        # operand: a loss summed here would fuse into the form
        def both(y, src, gamma, cot):
            out, back = jax.vjp(f, y, src, gamma)
            return (out,) + back(cot)
        return jax.jit(both)

    def fwd_bwd(ms):
        return tuple(sum(v for k, v in ms.items()
                         if k.startswith("gate_norm_" + which))
                     for which in ("fwd", "bwd"))

    for cell, (form, groups, width, t, src_width, scale) in (
            GATE_NORM_SHAPES.items()):
        columns = groups * width
        rng = np.random.RandomState(3)
        y = jnp.asarray(rng.randn(*(
            (B, t, columns) if form == "gate_first"
            else (B, groups, t, width))), f32)
        src = jnp.asarray(rng.randn(B, t, src_width), bf16)
        gamma = jnp.asarray(
            1 + 0.1 * rng.randn(columns if form == "gate_first" else width),
            bf16)
        cot = jnp.asarray(rng.randn(B, t, columns), bf16)
        static = dict(form=form, width=width, eps=1e-5, scale=scale, offset=0)
        # what the op must move: y and the gate in, the result out; then
        # those two and the cotangent in, two cotangents out
        fwd_mb = t * columns * (4 + 2 + 2) / 1e6
        bwd_mb = t * columns * (4 + 2 + 2 + 4 + 2) / 1e6

        plain = jax.checkpoint(functools.partial(gn.plain_form, **static))
        plain_both = each_way(plain)
        ms_fwd = _kernel_device_ms(jax.jit(plain), y, src, gamma,
                                   prefix="gate_norm_")["everything else"]
        ms_both = _kernel_device_ms(plain_both, y, src, gamma, cot,
                                    prefix="gate_norm_")["everything else"]
        row(gate_norm=cell, impl="jnp", form=form, groups=groups, width=width,
            t=t, fwd_ms=ms_fwd, fwd_bwd_ms=ms_both,
            bytes_bound_fwd_ms=fwd_mb / PEAK_GBS,
            bytes_bound_fwd_bwd_ms=(fwd_mb + bwd_mb) / PEAK_GBS,
            fwd_gbs=gbs(fwd_mb, ms_fwd),
            fwd_bwd_gbs=gbs(fwd_mb + bwd_mb, ms_both))
        # the pair through its entry, as the block calls it
        entry = functools.partial(
            pk.gated_rms_norm, form=form, eps=1e-5, groups=groups,
            scale=scale)
        chosen = gn.gate_norm_tiles(form, groups, width, t, bf16, 0,
                                    src_width)
        entry_both = each_way(entry)
        ms = _kernel_device_ms(entry_both, y, src, gamma, cot,
                               prefix="gate_norm_")
        fwd, bwd = fwd_bwd(ms)
        far = {name: float(
            jnp.abs(g.astype(f32) - w.astype(f32)).max()
            / jnp.abs(w.astype(f32)).max())
            for name, g, w in zip(
                ("out", "dy", "dsrc", "dgamma"),
                entry_both(y, src, gamma, cot),
                plain_both(y, src, gamma, cot))}
        row(gate_norm=cell, impl="kernel", tiles=chosen, fwd_ms=fwd,
            bwd_ms=bwd, rest_ms=ms.get("everything else"),
            fwd_gbs=gbs(fwd_mb, fwd), bwd_gbs=gbs(bwd_mb, bwd),
            share_of_bytes_bound=100 * (fwd_mb + bwd_mb) / PEAK_GBS
            / (fwd + bwd), far_from_jnp=far,
            kernels=sorted(k for k in ms if k.startswith("gate_norm_")))
        # every other tile that fits, the two calls alone
        tiles = [(r, c) for r in rows_tiles if t % r == 0
                 for c in ([n * width for n in (1, 2, 4, 8)
                            if groups % n == 0] if form == "gate_first"
                           else [chosen[1]])
                 if gn.gate_norm_vmem_bytes(r, c, width, 2, form)
                 <= pk.common.VMEM_RAISED_LIMIT]
        for tile in tiles:
            def both(y, src, gamma_row, cot, tile=tile):
                kw = dict(tiles=tile, interpret=False, **static)
                return (gn.gate_norm_fwd_call(y, src, gamma_row, **kw),
                        gn.gate_norm_bwd_call(y, src, gamma_row, cot, **kw))
            try:
                ms = _kernel_device_ms(
                    jax.jit(both), y, src,
                    gn._gamma_row(gamma, bf16, form, tile), cot,
                    prefix="gate_norm_")
            except Exception as e:  # noqa: BLE001 — Mosaic refused the tile
                row(gate_norm=cell, tiles=tile, refused=str(e)[:300])
                continue
            fwd, bwd = fwd_bwd(ms)
            row(gate_norm=cell, tiles=tile, fwd_ms=fwd, bwd_ms=bwd,
                fwd_gbs=gbs(fwd_mb, fwd), bwd_gbs=gbs(bwd_mb, bwd))


def main():
    dev = jax.devices()[0]
    res = {"device": str(dev.device_kind), "platform": dev.platform,
           "shape": dict(b=B, t=T, heads=H, head_dim=P, groups=G, state=N,
                         chunk=CHUNK), "rows": []}

    def row(**kw):
        print(json.dumps(kw), flush=True)
        res["rows"].append(kw)

    def save(name="ssd_scan_table.json"):
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/" + name, "w") as f:
            json.dump(res, f, indent=1)

    if "--shapes-only" in sys.argv:
        pair_by_shape(row)
        return save()
    if "--gate-norm" in sys.argv:
        gate_norm_table(row)
        return save("gate_norm_table.json")
    both = forms()
    # how far apart the two forms are, on the chip
    for dtype, t in ((jnp.bfloat16, T), (jnp.float32, 1024)):
        args, cot = inputs(0, dtype, t)
        outs = {name: (f(*args), g(cot, *args)[1])
                for name, (f, g) in both.items()}

        def rel(got, want):
            got, want = (v.astype(jnp.float32) for v in (got, want))
            return float(jnp.abs(got - want).max() / jnp.abs(want).max())

        # the yardstick: the einsum form on the same values in float32
        # with every product at the highest precision (XLA's default for
        # a float32 product on the TPU is one bf16 pass)
        with jax.default_matmul_precision("highest"):
            f, g = forms()["einsum"]
            exact = tuple(v.astype(jnp.float32) for v in args)
            y_x, g_x = f(*exact), g(cot, *exact)[1]
        for name, (y, grads) in outs.items():
            row(check=name + "_against_float32_highest",
                dtype=jnp.dtype(dtype).name, t=t, y=rel(y, y_x),
                **{"d" + n: rel(k, e)
                   for n, k, e in zip(("x", "B", "C", "dt", "a", "skip"),
                                      grads, g_x)})

    args, cot = inputs(1, jnp.bfloat16)
    steps = B * G * (T // CHUNK)
    # a forward step's products: C B^T, a head's [Q, Q] x [Q, P] eight
    # times, the state through C and its update over all heads at once
    e = H // G
    fwd_flop = 2 * CHUNK * (CHUNK * N + e * CHUNK * P + 2 * N * e * P)
    bwd_flop = 2 * CHUNK * (CHUNK * N + 2 * e * CHUNK * P + 4 * N * e * P
                            + 2 * CHUNK * N)
    for _ in range(3):
        for name in ("einsum", "kernel"):
            f, g = both[name]
            fwd = _time(f, *args)
            fwd_bwd = _time(g, cot, *args)
            extra = {}
            if name == "kernel":
                extra = dict(
                    fwd_us_step=fwd * 1e3 / steps,
                    bwd_us_step=(fwd_bwd - fwd) * 1e3 / steps,
                    fwd_mxu_us_step=fwd_flop / PEAK_TFLOPS / 1e6,
                    bwd_mxu_us_step=bwd_flop / PEAK_TFLOPS / 1e6)
            row(form=name, fwd_ms=fwd, fwd_bwd_ms=fwd_bwd, **extra)
    row(kernels_device_ms=_kernel_device_ms(both["kernel"][1], cot, *args),
        steps=steps)
    pair_by_shape(row)
    save()


if __name__ == "__main__":
    main()
