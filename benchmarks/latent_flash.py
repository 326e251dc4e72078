"""Latent attention on the chip at the Kanana cell's shape (one sequence of
8,192 tokens, hidden 2,048, 32 heads of 128 + 64 query/key and 128 value
dimensions from a latent of 512, interleaved RoPE, bf16): the composition
the op ran before PR 44 (`ops/transformer/latent.py::_latent_composed_path`: the
rotation over the whole query, the rotary key broadcast and concatenated,
`flash_attention` on head-major copies) against the kernel path
(`_latent_kernel_path`: `kernels.latent_flash`, two key operands on
the arrays the neighbouring matmuls leave), the two alternating.

Three tables, one JSON line a row (PERF.md section 7 holds them):

- `block`: `q_proj`, `kv_a_proj`, `LatentAttention`, `o_proj` of one layer
  as one jitted program, so that the op's operands are what a matmul
  writes and its results what one reads, forward and forward + backward by
  the host clock (20 calls closed by a fetch), and the device time of one
  forward + backward by scope (`latent`, `full`, the projections) and by
  op from a profiler trace;
- `kernels`: the attention calls alone on operands that cross the jit
  boundary as their caller holds them: the parent's `flash_attention` on
  `[B, T, H, D]` (its head-major copies inside), `latent_flash` in layout
  (a) (token-major: column blocks of the up-projection's output) and in
  layout (b) (head-major `[B, H, T, .]`, as `'btl,hdl->bhtd'` would emit
  it: run as layout (a) with the heads folded into the batch, so the
  rotary key is held once a head and its gradient summed outside: 1 MB and
  32 MB at this shape, noise beside the 134 MB of keys and values);
  since PR 58 layout (a) also with its diagonal tiles whole
  (`latent_flash_a_whole`: the pair before the quarters of
  `flash.cut_steps`);
- `check`: how far the kernel path's output and its four gradients are
  from the composition's, on the chip, as a share of the largest magnitude.

    chiprun -- python3 benchmarks/latent_flash.py
    python3 benchmarks/latent_flash.py --rehearse-cpu

The platform rule, the clocks and the output file are `alone.py`'s.
"""
import collections
import re
from unittest import mock

import alone

import jax
import jax.numpy as jnp
import numpy as np

from mxnet_tpu.ops import kernels as pk
from mxnet_tpu.ops import transformer as tr
from mxnet_tpu.ops.transformer import latent

B, T, HIDDEN, H, N, R, DV, L = 1, 8192, 2048, 32, 128, 64, 128, 512
THETA, EPS = 1e6, 1e-6


_PART = re.compile(r"[/(](latent|full|q_proj|kv_a_proj|o_proj)[/)]")


def _device_ms(run, g, *args):
    """Device ms of one call of ``g`` by scope (an op's first of latent,
    full and the three projections, forward or under ``transpose(``) and
    op by op, longest first, from a profiler trace of 5 calls."""
    by_scope, by_op = collections.Counter(), collections.Counter()
    for text, ms, scope in run.device_ops(g, *args, scopes=True):
        m = _PART.search(scope)
        part = "%s_%s" % (m.group(1) if m else "other",
                          "bwd" if "transpose(" in scope else "fwd")
        by_scope[part] += ms
        by_op[(alone.op_name(text), part)] += ms
    return ({k: round(v, 4) for k, v in sorted(by_scope.items())},
            [(name, part, round(ms, 4))
             for (name, part), ms in by_op.most_common(40)])


def params(seed):
    rng = np.random.RandomState(seed)

    def draw(*shape, scale):
        return jnp.asarray(rng.randn(*shape) * scale, jnp.bfloat16)

    return dict(
        x=draw(B, T, HIDDEN, scale=1.0),
        wq=draw(H * (N + R), HIDDEN, scale=HIDDEN ** -0.5),
        wa=draw(L + R, HIDDEN, scale=HIDDEN ** -0.5),
        gamma=jnp.ones((L,), jnp.bfloat16),
        wup=draw(H * (N + DV), L, scale=L ** -0.5),
        wo=draw(HIDDEN, H * DV, scale=(H * DV) ** -0.5),
        cot=draw(B, T, HIDDEN, scale=1.0))


def _linear(x, w, scope):
    with jax.named_scope(scope):
        return jax.lax.dot_general(
            x, w, (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).astype(x.dtype)


def block(form):
    """(forward, forward + backward) of one layer's attention block with
    the op's attention in ``form``: "composed" or "kernel"."""
    path = {"composed": latent._latent_composed_path,
            "kernel": latent._latent_kernel_path}[form]

    def fwd(x, wq, wa, gamma, wup, wo):
        query = _linear(x, wq, "q_proj")
        latent = _linear(x, wa, "kv_a_proj")
        with jax.named_scope("latent"):     # ``latent_attention``'s start
            kv = _linear(tr.rms_norm(latent[..., :L], gamma, EPS), wup,
                         "up")
            k_rope = tr.rope(latent[..., L:], 1, THETA, R, 0, True)
        return _linear(path(query, kv, k_rope, H, DV, THETA, True), wo,
                       "o_proj")

    def loss(x, wq, wa, gamma, wup, wo, cot):
        return jnp.sum((fwd(x, wq, wa, gamma, wup, wo) * cot).astype(
            jnp.float32))

    return jax.jit(fwd), jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5)))


def kernel_calls():
    """{form: (forward, forward + backward)} of the attention calls alone;
    the operands as each caller holds them."""
    scale = (N + R) ** -0.5

    def parent(q, k, v):                    # [B, T, H, 192 | 192 | 128]
        return pk.flash_attention(q, k, v, causal=True, scale=scale)

    def layout_a(q, kv, kr):        # [B, T, H 256], [B, T, H 256], [B, T, 128]
        return pk.latent_flash(q, kv, kr, H, N, scale)

    def layout_a_whole(q, kv, kr):
        # the floor is read once, as the call site is traced
        with mock.patch.object(pk.flash, "FLASH_MIN_EDGE", T):
            return pk.latent_flash(q, kv, kr, H, N, scale)

    def layout_b(q, kv, kr):        # [B H, T, 256] twice, [B H, T, 128]
        return pk.latent_flash(q, kv, kr, 1, N, scale)

    def both(f):
        def loss(*a):
            return jnp.sum(f(*a).astype(jnp.float32))
        return jax.jit(f), jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    return {"parent_flash": both(parent), "latent_flash_a": both(layout_a),
            "latent_flash_a_whole": both(layout_a_whole),
            "latent_flash_b": both(layout_b)}


def kernel_inputs(seed):
    rng = np.random.RandomState(seed)

    def draw(*shape):
        return jnp.asarray(rng.randn(*shape), jnp.bfloat16)

    q, kn, kr, v = (draw(B, T, H, N + R), draw(B, T, H, N), draw(B, T, R),
                    draw(B, T, H, DV))
    pad = jnp.zeros((B, T, H, 128 - R), jnp.bfloat16)
    q_pad = jnp.concatenate([q, pad], axis=-1)
    kv = jnp.concatenate([kn, v], axis=-1)
    kr_pad = jnp.concatenate([kr, pad[:, :, 0]], axis=-1)

    def heads_first(x):
        return jnp.moveaxis(x, 2, 1).reshape(B * H, T, x.shape[3])

    layout_a = (q_pad.reshape(B, T, -1), kv.reshape(B, T, -1), kr_pad)
    return {
        "parent_flash": (q, jnp.concatenate(
            [kn, jnp.broadcast_to(kr[:, :, None], (B, T, H, R))], axis=-1),
            v),
        "latent_flash_a": layout_a, "latent_flash_a_whole": layout_a,
        "latent_flash_b": (heads_first(q_pad), heads_first(kv),
                           jnp.repeat(kr_pad, H, axis=0))}


def main():
    global T, HIDDEN, H, L
    run = alone.Run(__file__)
    if run.rehearse:
        T, HIDDEN, H, L = 256, 64, 2, 32
    p = params(0)
    args = tuple(p[k] for k in ("x", "wq", "wa", "gamma", "wup", "wo"))
    blocks = {form: block(form) for form in ("composed", "kernel")}

    def rel(got, want):
        got, want = (v.astype(jnp.float32) for v in (got, want))
        return float(jnp.abs(got - want).max() / jnp.abs(want).max())

    outs = {form: (f(*args), g(*args, p["cot"]))
            for form, (f, g) in blocks.items()}
    run.row(table="check", what="kernel_against_composed",
            y=rel(outs["kernel"][0], outs["composed"][0]),
            **{"d" + n: rel(k, c) for n, k, c in zip(
                ("x", "wq", "wa", "gamma", "wup", "wo"), outs["kernel"][1],
                outs["composed"][1])})
    del outs

    for form, (f, g) in run.alternate(blocks):
        fwd = run.host_ms(f, *args)
        fwd_bwd = run.host_ms(g, *args, p["cot"])
        run.row(table="block", form=form, fwd_ms=fwd, fwd_bwd_ms=fwd_bwd,
                bwd_ms=fwd_bwd - fwd)
    for form, (_, g) in blocks.items():
        by_scope, top = _device_ms(run, g, *args, p["cot"])
        run.row(table="block", form=form, device_ms_by_scope=by_scope)
        run.row(table="block", form=form, ops=top)

    # causal scores and values, forward (2 products) and backward (5)
    pairs = B * H * T * (T + 1) / 2
    fwd_flop, bwd_flop = (2 * pairs * ((N + R) * n + DV * m)
                          for n, m in ((1, 1), (3, 2)))
    calls, ins = kernel_calls(), kernel_inputs(1)
    for form, (f, g) in run.alternate(calls):
        fwd = run.host_ms(f, *ins[form])
        fwd_bwd = run.host_ms(g, *ins[form])
        run.row(table="kernels", form=form, fwd_ms=fwd, fwd_bwd_ms=fwd_bwd,
                bwd_ms=fwd_bwd - fwd, fwd_mxu_ms=run.bound(flops=fwd_flop),
                bwd_mxu_ms=run.bound(flops=bwd_flop))
    for form, (_, g) in calls.items():
        by_scope, top = _device_ms(run, g, *ins[form])
        run.row(table="kernels", form=form,
                device_ms=sum(by_scope.values()), ops=top[:6])
    run.save(shape=dict(b=B, t=T, hidden=HIDDEN, heads=H, nope=N, rope=R,
                        dv=DV, latent=L))


if __name__ == "__main__":
    main()
