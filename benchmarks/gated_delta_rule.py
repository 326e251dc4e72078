"""The gated delta rule's chunk core on the chip: `ops/transformer/delta.py::
gated_delta_rule` (the `jax.numpy` chunk form, under `jax.checkpoint` with
the unit norms, write strengths and decays before it, as the GatedDeltaNet
op ran its `delta_rule` stage before the kernels) against
`ops/kernels/gdn.py::gated_delta_rule` (the `gdn_fwd_` / `gdn_bwd_`
kernel pair, the same prologue under its own `jax.checkpoint`) at the
Olmo-Hybrid cell's shape (one sequence of 4,096 tokens, 30 heads with keys
of 96 and values of 192, chunks of 64, bf16), forward and forward +
backward, the two forms alternating. Host clock over 20 calls closed by a
fetch; the arrays cross the jit boundary as the op holds them
(``[B, T, H K]``, ``[B, T, H V]``, ``[B, T, H]``). The kernels' own device
time is read from a profiler trace by their names.

Also prints how far each form's output and gradients are, on the chip,
from the chunk form in float32 with every product at the highest
precision (largest difference over that one's largest magnitude), for
bf16 and for float32 operands. `--heads-a-step 1,3,6` times the kernel
pair at other head groups than the rule's own. PERF.md section 7 holds the
table (PR 42).

    chiprun -- python3 benchmarks/gated_delta_rule.py
    python3 benchmarks/gated_delta_rule.py --rehearse-cpu

The platform rule, the clocks and the output file are `alone.py`'s.
"""
import argparse

import alone

import jax
import jax.numpy as jnp
import numpy as np

from mxnet_tpu.ops import kernels as pk
from mxnet_tpu.ops.transformer.delta import gated_delta_rule

B, T, H, K, V, CHUNK = 1, 4096, 30, 96, 192, 64
INPUTS = ("q", "k", "v", "a", "b")


def inputs(seed, dtype, t):
    """q, k, v as the op's convolution leaves them (unit scale, heads side
    by side in the last dimension), a and b as their projections do, decay
    rates by the published rule."""
    rng = np.random.RandomState(seed)
    step = np.exp(rng.uniform(np.log(0.001), np.log(0.1), H))
    q, k, v = (jnp.asarray(rng.randn(B, t, H * w), dtype)
               for w in (K, K, V))
    a = jnp.asarray(rng.randn(B, t, H)
                    + step + np.log(-np.expm1(-step)), dtype)
    b = jnp.asarray(rng.randn(B, t, H), dtype)
    return ((q, k, v, a, b, jnp.asarray(np.log(rng.uniform(1, 16, H)),
                                        jnp.float32)),
            jnp.asarray(rng.randn(B, t, H, V), jnp.float32))


def forms():
    """The ``delta_rule`` stage of ``_gated_delta_block`` both ways."""
    f32 = jnp.float32

    def unit(x):
        x = x.astype(f32).reshape(x.shape[:2] + (H, -1))
        return x * jax.lax.rsqrt(
            jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)

    def before(q, k, v, a, b, a_log):
        g = -jnp.exp(a_log) * jax.nn.softplus(a.astype(f32))
        return ((unit(q) * K ** -0.5).astype(v.dtype),
                unit(k).astype(v.dtype), g,
                2.0 * jax.nn.sigmoid(b.astype(f32)))

    def heads(v):
        return v.reshape(v.shape[:2] + (H, V))

    @jax.checkpoint
    def chunked(q, k, v, a, b, a_log):
        q, k, g, beta = before(q, k, v, a, b, a_log)
        return gated_delta_rule(q, k, heads(v), g, beta, CHUNK)

    def kernel(q, k, v, a, b, a_log):
        q, k, g, beta = jax.checkpoint(before)(q, k, v, a, b, a_log)
        return pk.gated_delta_rule(q, k, heads(v), g, beta, CHUNK)

    def both(f):
        def loss(cot, *ins):
            return jnp.sum(f(*ins) * cot)
        return (jax.jit(f),
                jax.jit(jax.value_and_grad(loss, argnums=(1, 2, 3, 4, 5))))
    return {"chunked": both(chunked), "kernel": both(kernel)}


def main():
    global T, H
    ap = argparse.ArgumentParser()
    ap.add_argument("--heads-a-step", default="")
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args_ = ap.parse_args()
    run = alone.Run(__file__)
    if run.rehearse:
        T, H = 128, 2
    both = forms()
    for dtype, t in ((jnp.bfloat16, T), (jnp.float32, min(T, 1024))):
        args, cot = inputs(0, dtype, t)
        outs = {name: (f(*args), g(cot, *args)[1])
                for name, (f, g) in both.items()}

        def rel(got, want):
            got, want = (v.astype(jnp.float32) for v in (got, want))
            return float(jnp.abs(got - want).max() / jnp.abs(want).max())

        # the yardstick: the chunk form on the same values in float32
        # with every product at the highest precision
        with jax.default_matmul_precision("highest"):
            f, g = forms()["chunked"]
            exact = tuple(v.astype(jnp.float32) for v in args)
            o_x, g_x = f(*exact), g(cot, *exact)[1]
        for name, (o, grads) in outs.items():
            run.row(check=name + "_against_float32_highest",
                    dtype=jnp.dtype(dtype).name, t=t, o=rel(o, o_x),
                    **{"d" + n: rel(k, e)
                       for n, k, e in zip(INPUTS, grads, g_x)})

    args, cot = inputs(1, jnp.bfloat16, T)
    for name, (f, g) in run.alternate(both, rounds=args_.rounds):
        run.row(form=name, fwd_ms=run.host_ms(f, *args),
                fwd_bwd_ms=run.host_ms(g, cot, *args))

    def kernels_ms(g):
        return alone.by_kernel(run.device_ops(g, cot, *args, reps=10),
                               "gdn_")

    run.row(kernels_device_ms=kernels_ms(both["kernel"][1]),
            heads_a_step=pk.gdn.gdn_group(H),
            steps=B * H // pk.gdn.gdn_group(H) * (T // CHUNK))
    for per in [int(p) for p in args_.heads_a_step.split(",") if p]:
        own, pk.gdn.GDN_HEADS_A_STEP = pk.gdn.GDN_HEADS_A_STEP, per
        for f in (pk.gdn.gdn_fwd_call, pk.gdn.gdn_bwd_call,
                  pk.gdn.gdn_forward):
            f.clear_cache()
        f, g = forms()["kernel"]
        run.row(fwd_ms=run.host_ms(f, *args),
                fwd_bwd_ms=run.host_ms(g, cot, *args),
                kernels_device_ms=kernels_ms(g),
                heads_a_step=pk.gdn.gdn_group(H))
        pk.gdn.GDN_HEADS_A_STEP = own
    run.save(shape=dict(b=B, t=T, heads=H, key_dim=K, value_dim=V,
                        chunk=CHUNK))


if __name__ == "__main__":
    main()
