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

    chiprun -- python3 benchmarks/ssd_scan.py [--shapes-only]
"""
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


def _kernel_device_ms(g, *args, reps=10):
    """Device ms a call of each ``ssd_`` kernel and of everything in the
    program, from a profiler trace of ``reps`` calls."""
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
                ms[name.split(".")[0] if name.startswith("ssd_")
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


def main():
    dev = jax.devices()[0]
    res = {"device": str(dev.device_kind), "platform": dev.platform,
           "shape": dict(b=B, t=T, heads=H, head_dim=P, groups=G, state=N,
                         chunk=CHUNK), "rows": []}

    def row(**kw):
        print(json.dumps(kw), flush=True)
        res["rows"].append(kw)

    def save():
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/ssd_scan_table.json", "w") as f:
            json.dump(res, f, indent=1)

    if "--shapes-only" in sys.argv:
        pair_by_shape(row)
        return save()
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
