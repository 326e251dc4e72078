"""The chunked state-space scan on the chip: `ops/transformer/ssm.py::ssd_scan`
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
MXU work. PERF.md section 7 holds the table (PR 39; the kernels are PR
38's, which the ledger holds as refused for its set-up).

Then the pair alone by shape (``SHAPES``: the Nemotron cell's and the
Falcon-H1 cell's, 4,096 tokens, 16 heads of 128 in one group, state 256):
device ms a call forward and backward from the trace, us a grid step, and
the share of the bytes bound (``x``, ``B``, ``C``, ``dt`` in and ``y`` out
once forward, three times that for forward + backward, over the device's
HBM peak). ``--shapes-only`` prints that table alone.

``--gate-norm`` prints the table of what follows the scan instead (the
gate and the grouped RMSNorm, ``ops/kernels/gate_norm.py``, PR 52): at
each of ``GATE_NORM_SHAPES`` (the Nemotron, Falcon-H1 and Olmo-Hybrid
cells') the ``jax.numpy`` form alone under its ``jax.checkpoint`` (with
the transposing copy of a head-major ``o``), the kernel pair at the tiles
``gate_norm_tiles`` picks and at every other (row tile, column tile) that
fits, device ms forward and backward from a trace, GB/s over the bytes the
op must move, and how far the pair's results are from the form's on the
chip (the file's table is ``gate_norm``).

    chiprun -- python3 benchmarks/ssd_scan.py [--shapes-only | --gate-norm]
    python3 benchmarks/ssd_scan.py --rehearse-cpu [--shapes-only | --gate-norm]

The platform rule, the clocks and the output file are ``alone.py``'s.
"""
import functools
import sys

import alone

import jax
import jax.numpy as jnp
import numpy as np

from mxnet_tpu.ops import kernels as pk
from mxnet_tpu.ops.transformer.ssm import ssd_scan

B, CHUNK = 1, 128
# (t, heads, head_dim, groups, state) of the cells that run the pair
SHAPES = {"nemotron3_nano_fit_share_8k": (8192, 64, 64, 8, 128),
          "falcon_h1_fit_share_4k": (4096, 16, 128, 1, 256)}
TOY_SHAPES = {"toy": (256, 4, 64, 2, 128)}


def inputs(seed, dtype, t, h, p, g, n):
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


def forms(h, p, g, n):
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
            return form(x.reshape(B, t, h, p), bm.reshape(B, t, g, n),
                        cm.reshape(B, t, g, n), *rest).reshape(B, t, h * p)

        def loss(cot, *a):
            return jnp.sum(f(*a) * cot)
        return (jax.jit(f),
                jax.jit(jax.value_and_grad(loss,
                                           argnums=(1, 2, 3, 4, 5, 6))))
    return {"einsum": both(einsum), "kernel": both(kernel)}


def pair_by_shape(run):
    """The kernel pair alone at each of ``SHAPES``, bf16."""
    for cell, (t, h, p, g, n) in (
            TOY_SHAPES if run.rehearse else SHAPES).items():
        args, cot = inputs(2, jnp.bfloat16, t, h, p, g, n)

        def loss(cot, x, bm, cm, *rest, h=h, p=p, g=g, n=n, t=t):
            y = pk.ssd_scan(x.reshape(B, t, h, p), bm.reshape(B, t, g, n),
                            cm.reshape(B, t, g, n), *rest, CHUNK)
            return jnp.sum(y.reshape(B, t, h * p) * cot)

        grad = jax.jit(jax.value_and_grad(loss, argnums=(1, 2, 3, 4, 5, 6)))
        ms = alone.by_kernel(run.device_ops(grad, cot, *args, reps=10),
                             "ssd_")
        fwd, bwd = alone.named(ms, "ssd_fwd"), alone.named(ms, "ssd_bwd")
        steps = B * g * (t // CHUNK)
        bound_ms = run.bound(nbytes=2.0 * t * (2 * h * p + 2 * g * n + h))
        run.row(pair_alone=cell, t=t, heads=h, head_dim=p, groups=g, state=n,
                grid_steps=steps, fwd_ms=fwd, bwd_ms=bwd,
                fwd_us_step=1e3 * fwd / steps, bwd_us_step=1e3 * bwd / steps,
                rest_ms=ms.get(alone.REST), bytes_bound_fwd_ms=bound_ms,
                share_of_bytes_bound_fwd=alone.ratio(bound_ms, fwd, 100),
                share_of_bytes_bound_fwd_bwd=alone.ratio(
                    bound_ms, fwd + bwd, 300),
                kernels=sorted(k for k in ms if k.startswith("ssd_")))


# form, groups, group width, T, the gate's array's width, the gate's scale
GATE_NORM_SHAPES = {
    "nemotron3_nano_fit_share_8k": ("gate_first", 8, 512, 8192, 10304, None),
    "falcon_h1_fit_share_4k": ("gate_first", 1, 2048, 4096, 4624, 0.7),
    "olmo_hybrid_fit_stage_4k": ("norm_first", 30, 192, 4096, 5760, None)}
GATE_NORM_TOY_SHAPES = {
    "toy_gate_first": ("gate_first", 2, 128, 256, 384, 0.7),
    "toy_norm_first": ("norm_first", 2, 128, 256, 256, None)}


def gate_norm_table(run, rows_tiles=(128, 256, 512, 1024, 2048)):
    """The gate and norm alone at each of ``GATE_NORM_SHAPES``, bf16."""
    from mxnet_tpu.ops.kernels import gate_norm as gn

    f32, bf16 = jnp.float32, jnp.bfloat16
    gbs = alone.ratio

    def ms_of(f, *args):
        return alone.by_kernel(run.device_ops(f, *args, reps=10),
                               "gate_norm_")

    def each_way(f):
        # the result and the three cotangents, the result's cotangent an
        # operand: a loss summed here would fuse into the form
        def both(y, src, gamma, cot):
            out, back = jax.vjp(f, y, src, gamma)
            return (out,) + back(cot)
        return jax.jit(both)

    def fwd_bwd(ms):
        return tuple(alone.named(ms, "gate_norm_" + which)
                     for which in ("fwd", "bwd"))

    for cell, (form, groups, width, t, src_width, scale) in (
            GATE_NORM_TOY_SHAPES if run.rehearse
            else GATE_NORM_SHAPES).items():
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
        ms_fwd = ms_of(jax.jit(plain), y, src, gamma).get(alone.REST)
        ms_both = ms_of(plain_both, y, src, gamma, cot).get(alone.REST)
        bound_ms = run.bound(nbytes=1e6 * (fwd_mb + bwd_mb))
        run.row(gate_norm=cell, impl="jnp", form=form, groups=groups,
                width=width, t=t, fwd_ms=ms_fwd, fwd_bwd_ms=ms_both,
                bytes_bound_fwd_ms=run.bound(nbytes=1e6 * fwd_mb),
                bytes_bound_fwd_bwd_ms=bound_ms,
                fwd_gbs=gbs(fwd_mb, ms_fwd),
                fwd_bwd_gbs=gbs(fwd_mb + bwd_mb, ms_both))
        # the pair through its entry, as the block calls it
        entry = functools.partial(
            pk.gated_rms_norm, form=form, eps=1e-5, groups=groups,
            scale=scale, interpret=run.rehearse)
        chosen = gn.gate_norm_tiles(form, groups, width, t, bf16, 0,
                                    src_width)
        entry_both = each_way(entry)
        ms = ms_of(entry_both, y, src, gamma, cot)
        fwd, bwd = fwd_bwd(ms)
        far = {name: float(
            jnp.abs(g.astype(f32) - w.astype(f32)).max()
            / jnp.abs(w.astype(f32)).max())
            for name, g, w in zip(
                ("out", "dy", "dsrc", "dgamma"),
                entry_both(y, src, gamma, cot),
                plain_both(y, src, gamma, cot))}
        run.row(gate_norm=cell, impl="kernel", tiles=chosen, fwd_ms=fwd,
                bwd_ms=bwd, rest_ms=ms.get(alone.REST),
                fwd_gbs=gbs(fwd_mb, fwd), bwd_gbs=gbs(bwd_mb, bwd),
                share_of_bytes_bound=alone.ratio(bound_ms, fwd + bwd, 100),
                far_from_jnp=far,
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
                kw = dict(tiles=tile, interpret=run.rehearse, **static)
                return (gn.gate_norm_fwd_call(y, src, gamma_row, **kw),
                        gn.gate_norm_bwd_call(y, src, gamma_row, cot, **kw))
            try:
                ms = ms_of(jax.jit(both), y, src,
                           gn._gamma_row(gamma, bf16, form, tile), cot)
            except Exception as e:  # noqa: BLE001 — Mosaic refused the tile
                run.row(gate_norm=cell, tiles=tile, refused=str(e)[:300])
                continue
            fwd, bwd = fwd_bwd(ms)
            run.row(gate_norm=cell, tiles=tile, fwd_ms=fwd, bwd_ms=bwd,
                    fwd_gbs=gbs(fwd_mb, fwd), bwd_gbs=gbs(bwd_mb, bwd))


def main():
    run = alone.Run(__file__)
    t, h, p, g, n = (TOY_SHAPES["toy"] if run.rehearse
                     else SHAPES["nemotron3_nano_fit_share_8k"])
    shape = dict(b=B, t=t, heads=h, head_dim=p, groups=g, state=n,
                 chunk=CHUNK)
    if "--shapes-only" in sys.argv:
        pair_by_shape(run)
        return run.save(shape=shape)
    if "--gate-norm" in sys.argv:
        gate_norm_table(run)
        return run.save("gate_norm", shape=shape)
    both = forms(h, p, g, n)
    # how far apart the two forms are, on the chip
    for dtype, t_check in ((jnp.bfloat16, t), (jnp.float32, min(t, 1024))):
        args, cot = inputs(0, dtype, t_check, h, p, g, n)
        outs = {name: (f(*args), grad(cot, *args)[1])
                for name, (f, grad) in both.items()}

        def rel(got, want):
            got, want = (v.astype(jnp.float32) for v in (got, want))
            return float(jnp.abs(got - want).max() / jnp.abs(want).max())

        # the yardstick: the einsum form on the same values in float32
        # with every product at the highest precision (XLA's default for
        # a float32 product on the TPU is one bf16 pass)
        with jax.default_matmul_precision("highest"):
            f, grad = forms(h, p, g, n)["einsum"]
            exact = tuple(v.astype(jnp.float32) for v in args)
            y_x, g_x = f(*exact), grad(cot, *exact)[1]
        for name, (y, grads) in outs.items():
            run.row(check=name + "_against_float32_highest",
                    dtype=jnp.dtype(dtype).name, t=t_check, y=rel(y, y_x),
                    **{"d" + key: rel(k, e) for key, k, e in zip(
                        ("x", "B", "C", "dt", "a", "skip"), grads, g_x)})

    args, cot = inputs(1, jnp.bfloat16, t, h, p, g, n)
    steps = B * g * (t // CHUNK)
    # a forward step's products: C B^T, a head's [Q, Q] x [Q, P] eight
    # times, the state through C and its update over all heads at once
    e = h // g
    fwd_flop = 2 * CHUNK * (CHUNK * n + e * CHUNK * p + 2 * n * e * p)
    bwd_flop = 2 * CHUNK * (CHUNK * n + 2 * e * CHUNK * p + 4 * n * e * p
                            + 2 * CHUNK * n)
    for name, (f, grad) in run.alternate(both):
        fwd = run.host_ms(f, *args)
        fwd_bwd = run.host_ms(grad, cot, *args)
        extra = {}
        if name == "kernel":
            extra = dict(
                fwd_us_step=fwd * 1e3 / steps,
                bwd_us_step=(fwd_bwd - fwd) * 1e3 / steps,
                fwd_mxu_us_step=run.bound(flops=fwd_flop, per=1e-6),
                bwd_mxu_us_step=run.bound(flops=bwd_flop, per=1e-6))
        run.row(form=name, fwd_ms=fwd, fwd_bwd_ms=fwd_bwd, **extra)
    run.row(kernels_device_ms=alone.by_kernel(run.device_ops(
        both["kernel"][1], cot, *args, reps=10), "ssd_"), steps=steps)
    pair_by_shape(run)
    run.save(shape=shape)


if __name__ == "__main__":
    main()
