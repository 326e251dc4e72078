"""The delta rule with a decay a channel (Kimi Delta Attention) on the chip:
`ops/transformer/delta.py::channel_delta_rule` (the `jax.numpy` chunk form, under
`jax.checkpoint` with the unit norms, write strengths and decays before it,
as the GatedDeltaNet op ran its `delta_rule` stage before the kernels)
against `ops/kernels/gdn.py::channel_delta_rule` (the `kda_fwd_` /
`kda_bwd_` kernel pair, the same prologue under its own `jax.checkpoint`:
`kernel`), against `channel_delta_net` (`fused`: what the op calls, the
pair with the prologue made in VMEM, `kda_*_pre`) and against the scalar
rule's pair (`gdn_fwd_` / `gdn_bwd_`, every channel of a head given the
first one's decay: a floor, the channel rule does strictly more) at the
Kimi Linear cell's shape (one sequence of 8,192 tokens, 32 heads with keys
and values of 128, chunks of 64, bf16), forward and forward + backward,
the forms alternating. Host clock over 10 calls
closed by a fetch; the arrays cross the jit boundary as the op holds them
(``[B, T, H K]``, ``[B, T, H V]``, ``[B, T, H]``). The kernels' own device
time is read from a profiler trace by their names.

Also prints how far each form's output and gradients are, on the chip,
from the chunk form in float32 with every product at the highest precision
(largest difference over that one's largest magnitude), and each pair's
VMEM a step by the accounting. `--heads-a-step=2,4,8,16` times the fused
pair at other head groups than its own (1 / 2 / 4 / 8 pairs of heads a
step). `--shape=solar` takes the Solar-Open2 cell's length (4,096 tokens,
the same 32 heads of 128 / 128). PERF.md section 7 holds the tables (PR 54,
PR 74).

`--passes` prints the KNOCK-OUT table and nothing else: the fused pair's
device ms a call with one part of the kernels' work taken out at a time
(`gated_delta_rule.knocked_out`: the substitution replaced by the identity,
every float32 product at one MXU pass instead of six, the diagonal
sub-blocks' 8-row tiles not walked), and the pair without its prologue
(the form `kernel`, whose kernels are `kda_*` without `_pre`). The copies
are built in this process; the package has no such switch.

`--gate-norm` prints what follows the rule instead (PR 56): the per-head
norm and its sigmoid gate on `o` token-major as the pair wrote it
(`ops/kernels/gate_norm.py::gated_rms_norm`, form `token_major`), the
`jax.numpy` closure under its checkpoint against the kernel pair through
its entry, then the two calls alone by (row tile, column tile) and by the
rows a loop step takes (`gate_norm._ROWS_TOKEN_MAJOR`;
`--rows-a-step=64,128,256`), device ms by kernel name (the file's table
is `gate_norm`).

    chiprun -- python3 benchmarks/channel_delta_rule.py \
        [--gate-norm | --passes] [--shape=solar]
    python3 benchmarks/channel_delta_rule.py --rehearse-cpu \
        [--gate-norm | --passes]

The platform rule, the clocks and the output file are `alone.py`'s.
"""
import functools
import sys

import alone
import gated_delta_rule as scalar_rule  # its knock-outs of gdn.py

import jax
import jax.numpy as jnp
import numpy as np

from mxnet_tpu.ops import kernels as pk
from mxnet_tpu.ops.transformer.delta import channel_delta_rule

B, T, H, K, V, CHUNK = 1, 8192, 32, 128, 128, 64
LENGTHS = {"kimi": 8192, "solar": 4096}   # the cells' tokens a sequence
INPUTS = ("q", "k", "v", "a", "b")


def inputs(seed, dtype, t):
    """q, k, v as the op's convolution leaves them (unit scale, heads side
    by side in the last dimension), a and b as their projections do, a
    decay rate a head and a step size a channel by the published rule."""
    rng = np.random.RandomState(seed)
    step = np.exp(rng.uniform(np.log(0.001), np.log(0.1), H * K))
    q, k, v, a = (jnp.asarray(rng.randn(B, t, H * w), dtype)
                  for w in (K, K, V, K))
    b = jnp.asarray(rng.randn(B, t, H), dtype)
    return ((q, k, v, a, b,
             jnp.asarray(np.log(rng.uniform(1, 16, H)), jnp.float32),
             jnp.asarray(step + np.log(-np.expm1(-step)), jnp.float32)),
            jnp.asarray(rng.randn(B, t, H, V), jnp.float32))


def forms():
    """The ``delta_rule`` stage of ``GatedDeltaNet`` four ways."""
    f32 = jnp.float32

    def unit(x):
        x = x.astype(f32).reshape(x.shape[:2] + (H, -1))
        return x * jax.lax.rsqrt(
            jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)

    def before(q, k, v, a, b, a_log, dt_bias):
        g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
            a.astype(f32).reshape(a.shape[:2] + (H, K))
            + dt_bias.reshape(H, K))
        return ((unit(q) * K ** -0.5).astype(v.dtype),
                unit(k).astype(v.dtype), g, jax.nn.sigmoid(b.astype(f32)))

    def heads(v):
        return v.reshape(v.shape[:2] + (H, V))

    @jax.checkpoint
    def chunked(*ins):
        q, k, g, beta = before(*ins)
        return channel_delta_rule(q, k, heads(ins[2]), g, beta, CHUNK)

    def kernel(*ins):
        q, k, g, beta = jax.checkpoint(before)(*ins)
        return pk.gdn.channel_delta_rule(q, k, heads(ins[2]), g, beta, CHUNK)

    def fused(q, k, v, a, b, a_log, dt_bias):
        beta = jax.checkpoint(
            lambda b: jax.nn.sigmoid(b.astype(f32)))(b)
        return heads(pk.channel_delta_net(q, k, v, a, beta, a_log, dt_bias,
                                          CHUNK))

    def scalar_kernel(*ins):
        q, k, g, beta = jax.checkpoint(before)(*ins)
        return pk.gated_delta_rule(q, k, heads(ins[2]), g[..., 0], beta,
                                   CHUNK)

    def both(f):
        def loss(cot, *ins):
            return jnp.sum(f(*ins) * cot)
        return (jax.jit(f),
                jax.jit(jax.value_and_grad(loss, argnums=(1, 2, 3, 4, 5))))
    return {"chunked": both(chunked), "kernel": both(kernel),
            "fused": both(fused), "scalar_kernel": both(scalar_kernel)}


def gate_norm_table(run, steps, row_tiles=(256, 512, 1024),
                    column_tiles=(512, 1024, 2048, 4096)):
    """The gate and norm behind the channel rule alone, one layer at the
    cell's shape: ``o`` float32 and the gate bf16, both [B, T, H V]."""
    from mxnet_tpu.ops.kernels import gate_norm as gn

    f32, bf16 = jnp.float32, jnp.bfloat16
    rng = np.random.RandomState(3)
    columns = H * V
    o = jnp.asarray(rng.randn(B, T, columns), f32)
    gate, cot = (jnp.asarray(rng.randn(B, T, columns), bf16)
                 for _ in range(2))
    gamma = jnp.asarray(1 + 0.1 * rng.randn(V), bf16)
    static = dict(form="token_major", width=V, eps=1e-5, scale=None,
                  offset=0, act="sigmoid")
    # what the op must move: o and the gate in, the result out; then those
    # two and the cotangent in, two cotangents out
    fwd_mb = T * columns * (4 + 2 + 2) / 1e6
    bwd_mb = T * columns * (4 + 2 + 2 + 4 + 2) / 1e6
    bound_ms = run.bound(nbytes=1e6 * (fwd_mb + bwd_mb))

    def each_way(f):
        # the result's cotangent an operand: a loss summed here would
        # fuse into the form
        def both(o, gate, gamma, cot):
            out, back = jax.vjp(f, o, gate, gamma)
            return (out,) + back(cot)
        return jax.jit(both)

    def ms_of(f, *args):
        return alone.by_kernel(run.device_ops(f, *args), "gate_norm_")

    def fwd_bwd(ms):
        return tuple(alone.named(ms, "gate_norm_" + which)
                     for which in ("fwd", "bwd"))

    plain = each_way(jax.checkpoint(functools.partial(gn.plain_form,
                                                      **static)))
    ms = ms_of(plain, o, gate, gamma, cot).get(alone.REST)
    run.row(gate_norm="jnp", fwd_bwd_ms=ms, bytes_bound_fwd_bwd_ms=bound_ms)
    entry = each_way(functools.partial(
        pk.gated_rms_norm, form="token_major", eps=1e-5, groups=H,
        act="sigmoid", interpret=run.rehearse))
    ms = ms_of(entry, o, gate, gamma, cot)
    fwd, bwd = fwd_bwd(ms)
    far = {name: float(jnp.abs(g.astype(f32) - w.astype(f32)).max()
                       / jnp.abs(w.astype(f32)).max())
           for name, g, w in zip(("out", "do", "dgate", "dgamma"),
                                 entry(o, gate, gamma, cot),
                                 plain(o, gate, gamma, cot))}
    run.row(gate_norm="kernel", rows_a_step=gn._ROWS_TOKEN_MAJOR,
            tiles=gn.gate_norm_tiles("token_major", H, V, T, bf16, 0,
                                     columns),
            fwd_ms=fwd, bwd_ms=bwd, rest_ms=ms.get(alone.REST),
            share_of_bytes_bound=alone.ratio(bound_ms, fwd + bwd, 100),
            far_from_jnp=far,
            kernels=sorted(k for k in ms if k.startswith("gate_norm_")))
    own = gn._ROWS_TOKEN_MAJOR
    for rows in steps:
        gn._ROWS_TOKEN_MAJOR = rows
        gn.gate_norm_fwd_call.clear_cache()
        gn.gate_norm_bwd_call.clear_cache()
        for tile in [(r, c) for r in row_tiles for c in column_tiles
                     if T % r == 0 and columns % c == 0 and r % rows == 0
                     and gn.gate_norm_vmem_bytes(r, c, V, 2, "token_major")
                     <= pk.common.VMEM_RAISED_LIMIT]:
            def both(o, gate, gamma_row, cot, tile=tile):
                kw = dict(tiles=tile, interpret=run.rehearse, **static)
                return (gn.gate_norm_fwd_call(o, gate, gamma_row, **kw),
                        gn.gate_norm_bwd_call(o, gate, gamma_row, cot, **kw))
            try:
                ms = ms_of(
                    jax.jit(both), o, gate,
                    gn._gamma_row(gamma, bf16, "token_major", tile), cot)
            except Exception as e:  # noqa: BLE001 — Mosaic refused the tile
                run.row(gate_norm="calls", rows_a_step=rows, tiles=tile,
                        refused=str(e)[:300])
                continue
            fwd, bwd = fwd_bwd(ms)
            run.row(gate_norm="calls", rows_a_step=rows, tiles=tile,
                    fwd_ms=fwd, bwd_ms=bwd, fwd_gbs=alone.ratio(fwd_mb, fwd),
                    bwd_gbs=alone.ratio(bwd_mb, bwd))
    gn._ROWS_TOKEN_MAJOR = own


def main():
    global T, H
    run = alone.Run(__file__)
    steps = [a.split("=", 1)[1] for a in sys.argv
             if a.startswith("--heads-a-step=")]
    cell = ([a.split("=", 1)[1] for a in sys.argv
             if a.startswith("--shape=")] or ["kimi"])[0]
    T = LENGTHS[cell]
    if run.rehearse:
        T, H = 128, 2
    shape = dict(cell=cell, b=B, t=T, heads=H, key_dim=K, value_dim=V,
                 chunk=CHUNK)
    if "--passes" in sys.argv:
        args, cot = inputs(1, jnp.bfloat16, T)

        def kernels_ms(form):
            return alone.by_kernel(
                run.device_ops(forms()[form][1], cot, *args), "kda_")

        for part in ("whole", "substitution", "float32_products_one_pass",
                     "diagonal_tiles"):
            with scalar_rule.knocked_out(part):
                run.row(knocked_out=part,
                        kernels_device_ms=kernels_ms("fused"))
        run.row(knocked_out="prologue", kernels_device_ms=kernels_ms("kernel"))
        return run.save("passes_" + cell, shape=shape)
    if "--gate-norm" in sys.argv:
        gate_norm_table(run, [
            int(r) for a in sys.argv if a.startswith("--rows-a-step=")
            for r in a.split("=", 1)[1].split(",")] or (64, 128, 256))
        return run.save("gate_norm", shape=shape)
    assert pk.gdn_takes(H, K, V, CHUNK, jnp.bfloat16, "channel")
    both = forms()
    args, cot = inputs(0, jnp.bfloat16, min(T, 2048))
    outs = {name: (f(*args), g(cot, *args)[1])
            for name, (f, g) in both.items() if name != "scalar_kernel"}

    def rel(got, want):
        got, want = (v.astype(jnp.float32) for v in (got, want))
        return float(jnp.abs(got - want).max() / jnp.abs(want).max())

    # the yardstick: the chunk form on the same values in float32 with
    # every product at the highest precision
    with jax.default_matmul_precision("highest"):
        f, g = forms()["chunked"]
        exact = tuple(v.astype(jnp.float32) for v in args)
        o_x, g_x = f(*exact), g(cot, *exact)[1]
    for name, (o, grads) in outs.items():
        run.row(check=name + "_against_float32_highest", dtype="bfloat16",
                t=args[0].shape[1], o=rel(o, o_x),
                **{"d" + n: rel(k, e) for n, k, e in zip(INPUTS, grads, g_x)})

    args, cot = inputs(1, jnp.bfloat16, T)
    for name, (f, g) in run.alternate(both, rounds=2):
        run.row(form=name, fwd_ms=run.host_ms(f, *args, reps=10),
                fwd_bwd_ms=run.host_ms(g, cot, *args, reps=10))
    item = jnp.dtype(jnp.bfloat16).itemsize
    run.row(vmem_by_the_accounting=dict(
        kda=pk.gdn.kda_vmem_bytes(CHUNK, pk.gdn.kda_group(H), H, K, V, item),
        gdn=pk.gdn.gdn_vmem_bytes(CHUNK, pk.gdn.gdn_group(H), K, V, item)))

    def kernels_ms(g):
        return alone.by_kernel(run.device_ops(g, cot, *args),
                               "kda_", "gdn_", "gate_norm_")

    for name in ("kernel", "fused", "scalar_kernel"):
        run.row(form=name, kernels_device_ms=kernels_ms(both[name][1]))

    for per in [int(p) for s in steps for p in s.split(",") if p]:
        own, pk.gdn.KDA_HEADS_A_STEP = pk.gdn.KDA_HEADS_A_STEP, per
        scalar_rule.clear_traces()
        f, g = forms()["fused"]
        run.row(heads_a_step=pk.gdn.kda_group(H),
                fwd_ms=run.host_ms(f, *args, reps=10),
                fwd_bwd_ms=run.host_ms(g, cot, *args, reps=10),
                kernels_device_ms=kernels_ms(g))
        pk.gdn.KDA_HEADS_A_STEP = own
        scalar_rule.clear_traces()
    run.save(None if cell == "kimi" else cell, shape=shape)


if __name__ == "__main__":
    main()
