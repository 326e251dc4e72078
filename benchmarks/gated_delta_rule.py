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
bf16 and for float32 operands. `--heads-a-step 2,4,8,16` times the kernel
pair at other head groups than the rule's own (1 / 2 / 4 / 8 pairs of heads
a step). `--shape kimi` takes the Kimi Linear cell's heads instead (8,192
tokens, 32 heads of 128 / 128: the shape at which the scalar pair is the
floor under the channel pair). PERF.md section 7 holds the tables (PR 42,
PR 74).

`--passes` prints the KNOCK-OUT table and nothing else: the pair's device
ms a call with one part of the kernels' work taken out at a time
(`knocked_out`: the substitution replaced by the identity, every float32
product at one MXU pass instead of six; `channel_delta_rule.py --passes`
adds the channel rule's diagonal tiles and its prologue). The copies are
built HERE, by replacing a name of `ops/kernels/gdn.py` in this process
round a fresh trace; their results are wrong and only their time is read.
The package has no such switch.

    chiprun -- python3 benchmarks/gated_delta_rule.py [--shape kimi] [--passes]
    python3 benchmarks/gated_delta_rule.py --rehearse-cpu [--passes]

The platform rule, the clocks and the output file are `alone.py`'s.
"""
import argparse
import contextlib

import alone

import jax
import jax.numpy as jnp
import numpy as np

from mxnet_tpu.ops import kernels as pk
from mxnet_tpu.ops.transformer.delta import gated_delta_rule

B, T, H, K, V, CHUNK = 1, 4096, 30, 96, 192, 64
# (T, heads, keys, values) by cell
SHAPES = {"olmo": (4096, 30, 96, 192), "kimi": (8192, 32, 128, 128)}
INPUTS = ("q", "k", "v", "a", "b")


def _one_pass(lhs, rhs, contract):
    """``common.dot_highest`` without its precision: a float32 product is
    then one bf16 pass of the MXU, not six."""
    return jax.lax.dot_general(
        lhs, rhs, ((contract[:1], contract[1:]), ((), ())),
        preferred_element_type=jnp.float32)


KNOCK_OUTS = {
    "whole": {},
    "substitution": {"_gdn_inverse":
                     lambda low_ref, masks: jax.lax.concatenate(
                         list(masks["eye"]), 0)},
    "float32_products_one_pass": {"dot_highest": _one_pass},
    "diagonal_tiles": {"_kda_tiles": lambda t: iter(())},
}


def clear_traces():
    """Every ``jax.jit`` of ``ops/kernels/gdn.py`` forgets what it traced:
    the next call traces the module as it stands."""
    for name in ("gdn_fwd_call", "gdn_bwd_call", "gdn_forward",
                 "kda_fwd_call", "kda_bwd_call", "kda_forward",
                 "kda_net_forward"):
        getattr(pk.gdn, name).clear_cache()


@contextlib.contextmanager
def knocked_out(part):
    """``ops/kernels/gdn.py`` with one part of its kernels' work taken out
    (``KNOCK_OUTS``), for the time alone: what such a kernel computes is
    wrong."""
    swaps = KNOCK_OUTS[part]
    kept = {name: getattr(pk.gdn, name) for name in swaps}
    for name, f in swaps.items():
        setattr(pk.gdn, name, f)
    clear_traces()
    try:
        yield
    finally:
        for name, f in kept.items():
            setattr(pk.gdn, name, f)
        clear_traces()


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
    global T, H, K, V
    ap = argparse.ArgumentParser()
    ap.add_argument("--heads-a-step", default="")
    ap.add_argument("--shape", default="olmo", choices=sorted(SHAPES))
    ap.add_argument("--passes", action="store_true")
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args_ = ap.parse_args()
    run = alone.Run(__file__)
    T, H, K, V = SHAPES[args_.shape]
    if run.rehearse:
        T, H = 128, 2
    shape = dict(cell=args_.shape, b=B, t=T, heads=H, key_dim=K, value_dim=V,
                 chunk=CHUNK)
    if args_.passes:
        args, cot = inputs(1, jnp.bfloat16, T)
        for part in ("whole", "substitution", "float32_products_one_pass"):
            with knocked_out(part):
                run.row(knocked_out=part, kernels_device_ms=alone.by_kernel(
                    run.device_ops(forms()["kernel"][1], cot, *args),
                    "gdn_"))
        return run.save("passes_" + args_.shape, shape=shape)
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
        clear_traces()
        f, g = forms()["kernel"]
        run.row(fwd_ms=run.host_ms(f, *args),
                fwd_bwd_ms=run.host_ms(g, cot, *args),
                kernels_device_ms=kernels_ms(g),
                heads_a_step=pk.gdn.gdn_group(H))
        pk.gdn.GDN_HEADS_A_STEP = own
        clear_traces()
    run.save(None if args_.shape == "olmo" else args_.shape, shape=shape)


if __name__ == "__main__":
    main()
