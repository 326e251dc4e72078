"""OLMoE on the normal path against its plain reference.

``models/olmoe.py`` (an ``mx.sym`` graph of ``RMSNorm``, ``RoPE``,
``Attention``, ``TopKMoE``) through ``Module.forward/backward`` and
``Module.fit``'s fused step, against ``models/olmoe_reference.py``
(plain float32 ``jax.numpy``: materialised attention, a loop over all
experts) on seeded weights at a tiny size.

Tolerances. Both sides are float32 and compute the same mathematics;
only the order of summation differs (grouped matmuls over sorted rows
against dense masked ones, fused against separate reductions), so the
float32 comparisons use rtol 1e-5 with an atol of a few float32 ulps of
the tensor's own scale (``_close``): a dropped token, a renormalised
routing weight or a wrong rotary pairing is off by orders of magnitude
more. The bf16 case measures its tolerance, see there.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.models import olmoe, olmoe_reference as ref
from mxnet_tpu.parallel import make_mesh, moe
from mxnet_tpu.parallel.moe import topk_moe

TINY = dict(vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
            num_experts=8, experts_per_token=2, expert_width=32, seq_len=32)
CFG = dict(hidden_size=64, num_attention_heads=4, num_hidden_layers=2,
           num_experts=8, num_experts_per_tok=2, intermediate_size=32,
           norm_topk_prob=False, rms_norm_eps=1e-5, rope_theta=10000.0,
           vocab_size=512)
BATCH = 2


def _close(got, want, what, rtol=1e-5, ulps=8):
    """rtol 1e-5, atol ``ulps`` float32 ulps of the largest magnitude of
    ``want`` (summation order is all that differs)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    atol = ulps * np.finfo(np.float32).eps * max(np.abs(want).max(), 1e-30)
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol, err_msg=what)


def _params(sym, seed, batch=BATCH, sigma=0.05):
    """Seeded weights under the symbol's argument names: Normal(sigma),
    gammas near 1 (not exactly, so that their gradients are tested)."""
    rng = np.random.RandomState(seed)
    t = TINY["seq_len"]
    shapes, _, _ = sym.infer_shape(data=(batch, t), softmax_label=(batch, t))
    out = {}
    for name, shape in zip(sym.list_arguments(), shapes):
        if name in ("data", "softmax_label"):
            continue
        if name.endswith("_gamma"):
            out[name] = (1 + 0.1 * rng.randn(*shape)).astype(np.float32)
        else:
            out[name] = (sigma * rng.randn(*shape)).astype(np.float32)
    return out


def _batch(seed, batch=BATCH):
    rng = np.random.RandomState(seed)
    tokens = rng.randint(0, TINY["vocab_size"],
                         (batch, TINY["seq_len"] + 1))
    return tokens[:, :-1].astype(np.float32), tokens[:, 1:].astype(np.float32)


def _module(sym, params, batch=BATCH):
    t = TINY["seq_len"]
    mod = mx.mod.Module(sym, context=mx.cpu(0))
    mod.bind(data_shapes=[("data", (batch, t))],
             label_shapes=[("softmax_label", (batch, t))])
    mod.init_params(arg_params={k: mx.nd.array(v) for k, v in params.items()},
                    aux_params={})
    return mod


# -- the whole model ---------------------------------------------------------

def test_logits_loss_and_every_gradient_match_the_reference():
    sym = olmoe.get_symbol(**TINY)
    params = _params(sym, 1)
    tokens, labels = _batch(2)
    want = ref.forward(params, tokens, CFG, labels=labels)
    loss, grads = ref.loss_and_grads(params, tokens, labels, CFG)

    # logits: the internal the docstring names, bound for inference
    logits_sym = sym.get_internals()["lm_head_f32_output"]
    mod = mx.mod.Module(logits_sym, context=mx.cpu(0), label_names=None)
    mod.bind(data_shapes=[("data", tokens.shape)], for_training=False)
    mod.init_params(arg_params={k: mx.nd.array(v)
                                for k, v in params.items()}, aux_params={})
    mod.forward(mx.io.DataBatch(data=[mx.nd.array(tokens)]), is_train=False)
    logits = mod.get_outputs()[0].asnumpy().reshape(want["logits"].shape)
    _close(logits, want["logits"], "logits")

    mod = _module(sym, params)
    mod.forward(mx.io.DataBatch(data=[mx.nd.array(tokens)],
                                label=[mx.nd.array(labels)]), is_train=True)
    mod.backward()
    outs = [o.asnumpy() for o in mod.get_outputs()]
    _close(outs[0], want["per_sequence"], "per-sequence loss")
    _close(outs[0].mean(), loss, "loss")
    for layer in range(TINY["num_layers"]):
        np.testing.assert_array_equal(
            outs[1 + layer], np.asarray(want["expert_counts"][layer]))
        assert outs[1 + layer].sum() == BATCH * TINY["seq_len"] * 2
    got = mod._exec_group.execs[0].grad_dict
    assert set(grads) == set(params)
    for name, want_g in grads.items():
        # the head sums the sequences' losses (MXNet's convention: the
        # optimizer's rescale_grad = 1/batch makes it the mean)
        _close(got[name].asnumpy() / BATCH, want_g, name)


@pytest.mark.parametrize("steps", [1, 3])
def test_fused_fit_steps_match_the_references_sgd(steps):
    """Module.fit(kvstore='device', mesh dp=1) — the fused
    ShardedTrainStep — against the reference's own SGD with momentum,
    after one and after three steps on the same batch."""
    sym = olmoe.get_symbol(**TINY)
    params = _params(sym, 3)
    tokens, labels = _batch(4)
    lr, momentum = 0.5, 0.9

    want = {k: jnp.asarray(v) for k, v in params.items()}
    moms = {k: jnp.zeros_like(v) for k, v in want.items()}
    losses = []
    for _ in range(steps):
        loss, grads = ref.loss_and_grads(want, tokens, labels, CFG)
        losses.append(float(loss))
        want, moms = ref.sgd_momentum_step(want, moms, grads, lr, momentum)

    # NDArrayIter hands out rows in order: make each step's batch the
    # same two sequences
    it = mx.io.NDArrayIter(np.tile(tokens, (steps, 1)),
                           np.tile(labels, (steps, 1)), batch_size=BATCH)
    seen = []
    mod = mx.mod.Module(sym, context=mx.cpu(0), mesh=make_mesh(dp=1))
    mod.fit(it, num_epoch=1, eval_metric="loss", optimizer="sgd",
            optimizer_params={"learning_rate": lr, "momentum": momentum},
            kvstore="device",
            arg_params={k: mx.nd.array(v) for k, v in params.items()},
            aux_params={}, initializer=None,
            batch_end_callback=lambda p: (
                seen.append(p.eval_metric.get()[1]),
                p.eval_metric.reset()))
    assert mod._fused_trainer is not None
    _close(seen, losses, "loss per step")
    got, _ = mod.get_params()
    for name in params:
        _close(got[name].asnumpy(), want[name], name, ulps=16 * steps)


def test_loss_metric_reads_only_the_loss():
    metric = mx.metric.create("loss")
    metric.update(None, [mx.nd.array([1.0, 3.0]), mx.nd.array([7.0] * 8)])
    assert metric.get() == ("loss", 2.0)


# -- each op against its reference lines -------------------------------------

def test_rope_matches_the_complex_form():
    rng = np.random.RandomState(5)
    b, t, heads, d = 2, 16, 4, 8
    x = rng.randn(b, t, heads * d).astype(np.float32)
    got = mx.contrib.nd.RoPE(mx.nd.array(x), num_heads=heads,
                             theta=10000.0).asnumpy()
    # the half-rotation convention pairs (i, i + d/2): as complex numbers
    x4 = x.reshape(b, t, heads, d).astype(np.float64)
    z = x4[..., : d // 2] + 1j * x4[..., d // 2:]
    inv_freq = 10000.0 ** (-np.arange(0, d, 2) / d)
    z = z * np.exp(1j * np.arange(t)[:, None] * inv_freq[None, :])[
        None, :, None, :]
    want = np.concatenate([z.real, z.imag], axis=-1).reshape(b, t, heads * d)
    _close(got, want, "rope")
    _close(got, ref.rope(jnp.asarray(x).reshape(b, t, heads, d),
                         10000.0).reshape(b, t, heads * d), "rope vs ref")


def test_rms_norm_statistics_are_float32():
    rng = np.random.RandomState(6)
    x = rng.randn(4, 64).astype(np.float32)
    gamma = (1 + 0.1 * rng.randn(64)).astype(np.float32)
    got = mx.contrib.nd.RMSNorm(mx.nd.array(x), mx.nd.array(gamma),
                                eps=1e-5).asnumpy()
    _close(got, ref.rms_norm(jnp.asarray(x), jnp.asarray(gamma), 1e-5),
           "rms_norm")
    xb = mx.nd.array(x).astype("bfloat16")
    out = mx.contrib.nd.RMSNorm(xb, mx.nd.array(gamma).astype("bfloat16"))
    assert out.dtype == xb.dtype


def _moe_weights(rng, d, experts, hidden):
    return {"gate_w": rng.randn(d, experts).astype(np.float32),
            "w_gate_up": (0.3 * rng.randn(experts, d, 2 * hidden)
                          ).astype(np.float32),
            "w_down": (0.3 * rng.randn(experts, hidden, d)
                       ).astype(np.float32)}


def _force_the_kernel(monkeypatch):
    """The expert products through ``grouped_matmul``'s kernels off the
    TPU too, in the Pallas interpreter: the kernel layer's one test seam."""
    from mxnet_tpu.ops.kernels import common

    monkeypatch.setattr(common, "INTERPRET", True)


@pytest.mark.parametrize("experts_by", ["ragged_dot", "kernel"])
@pytest.mark.parametrize("case", ["one_expert_takes_all", "one_takes_none",
                                  "top_k_is_every_expert", "plain"])
def test_topk_moe_is_dropless(case, experts_by, monkeypatch):
    """No capacity: whatever the routing, every (token, expert) pair is
    computed — output, counts and gradients equal the masked loop, with
    XLA's grouped matmul and with the Pallas kernels (interpreted; 144
    tokens there, so that one row tile of 128 is filled and the last is
    partial)."""
    rng = np.random.RandomState(7)
    tokens, d, experts, hidden, top_k = 48, 16, 6, 8, 2
    if experts_by == "kernel":
        tokens = 144
        _force_the_kernel(monkeypatch)
    w = _moe_weights(rng, d, experts, hidden)
    x = rng.randn(tokens, d).astype(np.float32)
    if case == "one_expert_takes_all":
        # top_k = 1 and a router column that always wins: expert 2 gets
        # every token, 48 rows where a capacity of 1.25 * 48 / 6 keeps 10
        top_k = 1
        w["gate_w"][:] = 0
        x[:, 0] = np.abs(x[:, 0]) + 1
        w["gate_w"][0, 2] = 50.0
    elif case == "one_takes_none":
        x[:, 0] = np.abs(x[:, 0]) + 1
        w["gate_w"][0, 4] = -50.0
    elif case == "top_k_is_every_expert":
        top_k = experts

    def system(w, x):
        y, counts = topk_moe(w, x, top_k)
        return jnp.sum(y * y), (y, counts)

    def reference(w, x):
        y, counts, _ = ref.moe(x, w["gate_w"], w["w_gate_up"], w["w_down"],
                               top_k, False)
        return jnp.sum(y * y), (y, counts)

    if experts_by == "kernel":
        jaxpr = str(jax.make_jaxpr(jax.grad(
            lambda w, x: system(w, x)[0]))(w, jnp.asarray(x)))
        assert jaxpr.count("gmm_fwd_f32_m128") and "gmm_wgrad_f32" in jaxpr
    (_, (y, counts)), grads = jax.value_and_grad(
        system, argnums=(0, 1), has_aux=True)(w, jnp.asarray(x))
    (_, (y_ref, counts_ref)), grads_ref = jax.value_and_grad(
        reference, argnums=(0, 1), has_aux=True)(w, jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(counts_ref))
    assert int(counts.sum()) == tokens * top_k
    if case == "one_expert_takes_all":
        assert int(counts[2]) == tokens
    if case == "one_takes_none":
        assert int(counts[4]) == 0
    _close(y, y_ref, "output")
    for name in w:
        _close(grads[0][name], grads_ref[0][name], name, ulps=32)
    _close(grads[1], grads_ref[1], "d/dx", ulps=32)


def _take_moe(w, x, top_k, norm_topk_prob=False, scoring="softmax"):
    """``topk_moe``'s whole-layer path with its row moves as plain
    ``jnp.take`` under autodiff (the formulation before the permutation
    pair: the backward of each gather is a scatter-add) and its count as
    ``jnp.bincount``."""
    tokens, d = x.shape
    hidden = w["w_down"].shape[1]
    weights, experts = moe._route(w, x, top_k, norm_topk_prob, scoring)
    flat = experts.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    counts = jnp.bincount(flat, length=w["gate_w"].shape[1])
    rows = jnp.take(x, order // top_k, axis=0)
    gate_up = jax.lax.ragged_dot(rows, w["w_gate_up"], counts)
    act = jax.nn.silu(gate_up[:, :hidden]) * gate_up[:, hidden:]
    out_rows = jax.lax.ragged_dot(act, w["w_down"], counts)
    per_token = jnp.take(out_rows, jnp.argsort(order), axis=0).reshape(
        tokens, top_k, d)
    y = jnp.einsum("tkd,tk->td", per_token.astype(jnp.float32), weights)
    return y.astype(x.dtype), counts


def _routed(rng, routing, tokens, d, experts, hidden):
    """Weights and tokens whose routing is ``uniform`` (a random
    router), ``skewed`` (three router columns that nearly always win, as
    the seeded OLMoE cell's eight) or leaves one expert ``empty``."""
    w = _moe_weights(rng, d, experts, hidden)
    x = rng.randn(tokens, d).astype(np.float32)
    x[:, 0] = np.abs(x[:, 0]) + 1
    if routing == "skewed":
        w["gate_w"][0, :3] += 10.0
    elif routing == "empty":
        w["gate_w"][0, 4] = -50.0
    return w, x


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("scoring", ["softmax", "sigmoid_norm"])
@pytest.mark.parametrize("routing", ["uniform", "skewed", "empty"])
@pytest.mark.parametrize("top_k", [1, 2, 8])
def test_row_moves_by_gathers_match_autodiff_of_take(top_k, routing,
                                                     scoring, dtype):
    """The permutation pair against ``jnp.take`` under autodiff: output,
    counts and every gradient. float32: equal to summation order. bf16:
    both round the same float32 products once, except that a token's
    ``top_k`` cotangent rows are summed in float32 and rounded once here
    and added one by one in bf16 there, so d/dx may differ by those
    ``top_k - 1`` roundings (2**-9 of the largest element each)."""
    tokens, d, experts, hidden = 64, 32, 16, 16
    w, x = _routed(np.random.RandomState(11), routing, tokens, d, experts,
                   hidden)
    w = {n: jnp.asarray(v, dtype) for n, v in w.items()}
    x = jnp.asarray(x, dtype)
    kwargs = (dict(norm_topk_prob=True, scoring="sigmoid")
              if scoring == "sigmoid_norm"
              else dict(norm_topk_prob=False, scoring="softmax"))
    mix = jnp.asarray(np.random.RandomState(12).randn(tokens, d),
                      jnp.float32)

    def loss(layer):
        def f(w, x):
            y, counts = layer(w, x, top_k, **kwargs)
            return jnp.sum(y.astype(jnp.float32) * mix), (y, counts)
        return jax.value_and_grad(f, argnums=(0, 1), has_aux=True)

    (_, (y, counts)), (dw, dx) = loss(topk_moe)(w, x)
    (_, (y_ref, counts_ref)), (dw_ref, dx_ref) = loss(_take_moe)(w, x)
    assert counts.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(counts_ref))
    flat = np.asarray(moe._route(w, x, top_k, **kwargs)[1]).reshape(-1)
    np.testing.assert_array_equal(
        np.asarray(counts), np.bincount(flat, minlength=experts))
    if routing == "empty":
        assert int(counts[4]) == 0
    if routing == "skewed" and top_k > 1:
        assert int(counts[:3].sum()) > 0.8 * tokens * min(top_k, 3)
    assert y.dtype == x.dtype and dx.dtype == x.dtype
    if dtype == "float32":
        _close(y, y_ref, "output")
        for name in w:
            _close(dw[name], dw_ref[name], name, ulps=32)
        _close(dx, dx_ref, "d/dx", ulps=32)
        return

    def within(got, want, roundings, what):
        got, want = (np.asarray(a.astype(jnp.float32)) for a in (got, want))
        assert np.abs(got - want).max() <= (
            roundings * 2.0 ** -9 * np.abs(want).max()), what

    within(y, y_ref, 2, "output")
    for name in w:
        within(dw[name], dw_ref[name], 2, name)
    within(dx, dx_ref, top_k + 1, "d/dx")


@pytest.mark.parametrize("top_k", [2, 8])
def test_bf16_row_moves_round_once(top_k):
    """The two backward moves on bf16 cotangents against numpy in
    float32: a token's ``top_k`` rows summed and rounded once (nearer
    the float32 sum than the scatter-add of ``jnp.take``'s transpose,
    which rounds after every row), and ``dy[token] * weight`` rounded
    once into expert order."""
    rng = np.random.RandomState(13)
    tokens, d = 96, 24
    order = np.argsort(rng.randint(0, 5, tokens * top_k),
                       kind="stable").astype(np.int32)
    inverse = np.argsort(order).astype(np.int32).reshape(tokens, top_k)
    x = jnp.asarray(rng.randn(tokens, d), jnp.bfloat16)
    d_rows = jnp.asarray(rng.randn(tokens * top_k, d), jnp.bfloat16)
    weights = jnp.asarray(rng.rand(tokens, top_k), jnp.float32)
    dy = jnp.asarray(rng.randn(tokens, d), jnp.bfloat16)

    def f32(a):
        return np.asarray(a.astype(jnp.float32))

    def bf16(a):
        return f32(jnp.asarray(a, jnp.bfloat16))

    exact = f32(d_rows)[inverse].sum(axis=1)
    dx, = jax.vjp(lambda x: moe._dispatch(x, order, inverse), x)[1](d_rows)
    by_take, = jax.vjp(lambda x: jnp.take(x, order // top_k, axis=0),
                       x)[1](d_rows)
    assert dx.dtype == jnp.bfloat16
    # float32 sums of top_k terms differ in their last place by order:
    # one bf16 ulp where that flips the rounding
    assert np.abs(f32(dx) - exact).max() <= 2.0 ** -8 * np.abs(exact).max()
    worse = np.abs(f32(by_take) - exact).mean() - np.abs(f32(dx)
                                                         - exact).mean()
    assert worse > 0 if top_k > 2 else worse == 0  # two rows: one rounding

    out_rows = jnp.asarray(rng.randn(tokens * top_k, d), jnp.bfloat16)
    d_out, d_weights = jax.vjp(
        lambda o, w_: moe._combine(o, w_, order, inverse),
        out_rows, weights)[1](dy)
    want = bf16(f32(dy)[order // top_k]
                * np.asarray(weights).reshape(-1)[order][:, None])
    assert d_out.dtype == jnp.bfloat16 and d_weights.dtype == jnp.float32
    np.testing.assert_array_equal(f32(d_out), want)
    _close(d_weights, np.einsum(
        "td,tkd->tk", f32(dy), f32(out_rows)[inverse]), "d/dweights")


def _scatter_operands(jaxpr):
    """Shapes of the operands of every scatter in ``jaxpr`` and in the
    jaxprs its equations hold."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name.startswith("scatter"):
            found.append(tuple(eqn.invars[0].aval.shape))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found.extend(_scatter_operands(sub))
    return found


def test_the_whole_layer_path_differentiates_without_row_scatters():
    """``jax.grad(topk_moe)`` scatters nothing with ``d_model`` columns
    and nothing over the ``tokens * top_k`` pairs: the one scatter-add
    left is the transpose of the router's ``top_k``, over [tokens,
    experts]. The ``jnp.take`` formulation shows that the search finds
    the others: the count's and the two transposes'. One trace of a
    call site counts once in ``moe.permute_lowerings``."""
    from mxnet_tpu import telemetry

    tokens, d, experts, hidden, top_k = 48, 20, 6, 8, 2
    w, x = _routed(np.random.RandomState(14), "uniform", tokens, d, experts,
                   hidden)

    def grad_of(layer):
        return jax.make_jaxpr(jax.grad(
            lambda w, x: jnp.sum(layer(w, x, top_k)[0] ** 2),
            argnums=(0, 1)))(w, jnp.asarray(x)).jaxpr

    telemetry.reset()
    telemetry.enable()
    try:
        ours = _scatter_operands(grad_of(topk_moe))
        counter = telemetry.REGISTRY.get("moe.permute_lowerings")
        assert counter.value(rows=tokens * top_k, top_k=top_k, width=d) == 1
        assert telemetry.total("moe.permute_lowerings") == 1
    finally:
        telemetry.disable()
        telemetry.reset()
    assert ours == [(tokens, experts)]
    by_take = _scatter_operands(grad_of(_take_moe))
    assert sorted(by_take) == sorted(
        [(tokens, experts), (experts,), (tokens, d), (tokens * top_k, d)])


def test_topk_moe_symbol_op_infers_and_checks():
    moe = mx.contrib.sym.TopKMoE(mx.sym.Variable("x"), num_experts=4,
                                 num_hidden=8, top_k=2, name="moe")
    shapes, outs, _ = moe.infer_shape(x=(10, 16))
    assert dict(zip(moe.list_arguments(), shapes)) == {
        "x": (10, 16), "moe_gate_weight": (16, 4),
        "moe_gate_up_weight": (4, 16, 16), "moe_down_weight": (4, 8, 16)}
    assert outs == [(10, 16), (4,)]
    with pytest.raises(ValueError):
        mx.contrib.sym.TopKMoE(mx.sym.Variable("x"), num_experts=4,
                               num_hidden=8, top_k=5).infer_shape(x=(10, 16))


def test_attention_t256_runs_the_flash_kernel(monkeypatch):
    """The op's one dispatch, at a T the TPU takes through the flash
    kernel: through the Pallas interpreter here, forward and backward."""
    _force_the_kernel(monkeypatch)
    rng = np.random.RandomState(8)
    b, t, heads, d = 1, 256, 2, 32
    q, k, v = (jnp.asarray(0.5 * rng.randn(b, t, heads * d), jnp.float32)
               for _ in range(3))
    from mxnet_tpu.ops import registry

    op = registry.get("_contrib_Attention")
    attrs = op.canon_attrs({"num_heads": heads, "causal": True})

    def system(q, k, v):
        return jnp.sum(jnp.sin(op.fcompute(attrs, [q, k, v], True)[0]))

    def reference(q, k, v):
        s = (b, t, heads, d)
        return jnp.sum(jnp.sin(ref.attention(
            q.reshape(s), k.reshape(s), v.reshape(s)).reshape(b, t, -1)))

    got = jax.value_and_grad(system, argnums=(0, 1, 2))(q, k, v)
    want = jax.value_and_grad(reference, argnums=(0, 1, 2))(q, k, v)
    # the flash kernel's online softmax (one 256 x 256 tile here, the
    # chosen tiling) against one softmax per row: same float32
    # arithmetic in another order, over up to 256 keys
    _close(got[0], want[0], "value", rtol=1e-5)
    for g, w_, name in zip(got[1], want[1], "qkv"):
        _close(g, w_, "d/d" + name, rtol=1e-4, ulps=64)


# -- bf16 --------------------------------------------------------------------

@pytest.mark.parametrize("experts_by", ["ragged_dot", "kernel"])
def test_bf16_topk_moe_keeps_its_router_in_float32(experts_by, monkeypatch):
    """bf16 activations and weights, router in float32: on the same
    bf16-rounded inputs the float32 reference takes the same routing
    decision for EVERY token (equal counts; the two float32 routers
    differ by rounding in the last place), and the output is off by
    bf16 matmul error only — measured over seeds 0..3: 0.047-0.065
    standard deviations of the output at the worst element. The
    reference computed in bf16 throughout (the nearest precision below:
    a bf16 router) misroutes 26-32 of 8192 rows and reads 0.11-0.23.
    The limit 0.09 lies between, and the counts must be equal. The Pallas
    kernels (interpreted) keep the rounding of XLA's grouped matmul, bf16
    in, float32 accumulation, bf16 out: the same limit, and within
    accumulation order of that path."""
    if experts_by == "kernel":
        _force_the_kernel(monkeypatch)
    for seed in range(3):
        rng = np.random.RandomState(seed)
        tokens, d, experts, hidden, top_k = 2048, 64, 16, 32, 4
        w = {n: jnp.asarray(v, jnp.bfloat16)
             for n, v in _moe_weights(rng, d, experts, hidden).items()}
        x = jnp.asarray(rng.randn(tokens, d), jnp.bfloat16)

        def f32(a):
            return a.astype(jnp.float32)

        want, want_counts, _ = ref.moe(
            f32(x), f32(w["gate_w"]), f32(w["w_gate_up"]), f32(w["w_down"]),
            top_k, False)

        def error(y):
            return float(jnp.abs(f32(y) - want).max() / want.std())

        y, counts = topk_moe(w, x, top_k)
        assert y.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(counts),
                                      np.asarray(want_counts))
        assert error(y) < 0.09, (seed, error(y))
        if experts_by == "kernel":
            monkeypatch.undo()
            by_xla, _ = topk_moe(w, x, top_k)
            _force_the_kernel(monkeypatch)
            # one bf16 ulp of the largest output where the two float32
            # sums round to different sides
            assert float(jnp.abs(f32(y) - f32(by_xla)).max()) <= (
                2.0 ** -7 * float(jnp.abs(f32(by_xla)).max()))
        low, low_counts, _ = ref.moe(x, w["gate_w"], w["w_gate_up"],
                                     w["w_down"], top_k, False)
        assert int(jnp.abs(low_counts - want_counts).sum()) > 0
        assert error(low) > 0.09, (seed, error(low))


def _bf16_logit_error(seed, drop_expert=False):
    """Per-token largest |logit difference| to the float32 reference, in
    standard deviations of the logits: its 90th percentile over the
    tokens (robust against the one token whose routing bf16 activations
    legitimately flip) and its largest value. The bf16 symbol runs on
    the reference's weights rounded to bf16."""
    sym = olmoe.get_symbol(dtype="bfloat16", **TINY)
    params = _params(sym, seed, sigma=0.08)
    rounded = {k: np.asarray(jnp.asarray(v, jnp.bfloat16).astype(jnp.float32))
               for k, v in params.items()}
    tokens, _ = _batch(seed + 100)
    out = ref.forward(rounded, tokens, CFG)
    want = np.asarray(out["logits"]).reshape(-1, TINY["vocab_size"])
    logits_sym = sym.get_internals()["lm_head_f32_output"]
    mod = mx.mod.Module(logits_sym, context=mx.cpu(0), label_names=None)
    mod.bind(data_shapes=[("data", tokens.shape)], for_training=False)
    args = {k: mx.nd.array(v).astype("bfloat16") for k, v in rounded.items()}
    if drop_expert:
        # what a capacity limit does to a token: one of its experts
        # contributes nothing (the busiest expert of one layer: over a
        # quarter of the tokens)
        busiest = int(np.argmax(np.asarray(out["expert_counts"][1])))
        args["layer1_moe_down_weight"][busiest] = 0
    mod.init_params(arg_params=args, aux_params={})
    mod.forward(mx.io.DataBatch(data=[mx.nd.array(tokens)]), is_train=False)
    got = mod.get_outputs()[0].asnumpy().reshape(want.shape)
    per_token = np.abs(got - want).max(axis=1) / want.std()
    return float(np.percentile(per_token, 90)), float(per_token.max())


def test_bf16_symbol_is_close_and_a_dropped_expert_is_not():
    """Measured here over seeds 0..5 (90th percentile, largest): the
    bf16 symbol 0.028-0.059, 0.035-0.254 (the largest is one token whose
    routing bf16 activations flipped); with the busiest expert of one
    layer zeroed 0.283-0.555, 0.39-1.31. The limit on the 90th
    percentile, 0.12, lies between the two with a factor two each way."""
    limit = 0.12
    for seed in range(3):
        ours, _ = _bf16_logit_error(seed)
        dropped, _ = _bf16_logit_error(seed, drop_expert=True)
        assert ours < limit < dropped, (seed, ours, dropped)


# -- what 1.5 G parameters forced in Module ----------------------------------

def _blobs():
    rng = np.random.RandomState(0)
    x = rng.randn(64, 10).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        mx.sym.Variable("data"), num_hidden=2, name="fc"), name="softmax")
    return net, x, y


def _released(mod, name="fc_weight"):
    from mxnet_tpu.ndarray import _Deferred

    held = {isinstance(arr._buf, _Deferred)
            for exe in mod._exec_group.execs
            for arr in (exe.arg_dict[name], exe.grad_dict[name])}
    assert len(held) == 1, "weights and gradients go and come together"
    return held.pop()


def test_fused_fit_releases_the_executors_buffers_and_eval_fills_them():
    """From bind on and while the fused step trains, the executor group
    holds no buffer for the weights or for their gradients (two
    parameter-sized buffers per device otherwise); an executor-path
    forward finds the trained weights on its devices, and the next
    fused update drops them again."""
    net, x, y = _blobs()
    ctx = [mx.cpu(1), mx.cpu(2)]
    mod = mx.mod.Module(net, context=ctx)
    it = mx.io.NDArrayIter(x, y, batch_size=16)
    # absent from bind on, not only after the fused step was built
    mod.bind(it.provide_data, it.provide_label)
    assert _released(mod)
    mod.init_params(mx.init.Uniform(0.1))
    assert _released(mod)
    mod.init_optimizer(kvstore="device",
                       optimizer_params={"learning_rate": 0.5})
    assert mod._fused_trainer is not None and _released(mod)
    # an eval between two fused updates finds the weights the first left
    batches = list(it)
    it.reset()
    mod.forward(batches[0], is_train=True)
    mod.update()
    after_one = {n: v.asnumpy() for n, v in mod.get_params()[0].items()}
    assert _released(mod)
    mod.forward(batches[1], is_train=False)
    for exe, c in zip(mod._exec_group.execs, ctx):
        weight = exe.arg_dict["fc_weight"]
        assert weight._data.device == c.jax_device
        np.testing.assert_array_equal(weight.asnumpy(),
                                      after_one["fc_weight"])
    mod.forward(batches[1], is_train=True)
    mod.update()
    assert _released(mod)
    assert not np.array_equal(mod.get_params()[0]["fc_weight"].asnumpy(),
                              after_one["fc_weight"])
    released = []
    mod.fit(mx.io.NDArrayIter(x, y, batch_size=16), num_epoch=4,
            eval_data=mx.io.NDArrayIter(x, y, batch_size=16),
            optimizer="sgd", optimizer_params={"learning_rate": 0.5},
            kvstore="device",
            batch_end_callback=lambda p: released.append(_released(mod)),
            eval_end_callback=lambda p: released.append(
                mod._exec_group.execs[0].arg_dict["fc_weight"]
                ._data.device))
    assert mod._fused_trainer is not None
    # four training steps released, then the epoch's score filled
    assert released == ([True] * 4 + [ctx[0].jax_device]) * 4
    acc = mod.score(mx.io.NDArrayIter(x, y, batch_size=16), "acc")[0][1]
    assert acc > 0.9
    trained, _ = mod.get_params()
    for exe, c in zip(mod._exec_group.execs, ctx):
        weight = exe.arg_dict["fc_weight"]
        assert weight._data.device == c.jax_device
        np.testing.assert_array_equal(weight.asnumpy(),
                                      trained["fc_weight"].asnumpy())


@pytest.mark.parametrize("first", ["forward", "score", "get_outputs"])
def test_eval_between_init_optimizer_and_the_first_fused_step(first):
    """The executor path must find weights on its device before any
    fused update has run (a device other than the host's: every
    TPU)."""
    from mxnet_tpu.parallel import make_mesh

    net, x, y = _blobs()
    it = mx.io.NDArrayIter(x, y, batch_size=16)
    mod = mx.mod.Module(net, context=mx.cpu(1), mesh=make_mesh(
        dp=1, devices=[mx.cpu(1).jax_device]))
    mod.bind(it.provide_data, it.provide_label)
    mod.init_params(mx.init.Uniform(0.1))
    mod.init_optimizer(kvstore="device",
                       optimizer_params={"learning_rate": 0.5})
    assert mod._fused_trainer is not None and _released(mod)
    with pytest.raises(mx.MXNetError, match="fused step"):
        mod._exec_group.execs[0].arg_dict["fc_weight"].asnumpy()
    batch = next(iter(it))
    want, _ = mod.get_params()
    if first == "forward":
        mod.forward(batch, is_train=False)
    elif first == "score":
        assert 0.0 <= mod.score(it, "acc")[0][1] <= 1.0
    else:  # a deferred training forward served through the executors
        mod.forward(batch, is_train=True)
    assert mod.get_outputs()[0].shape == (16, 2)
    weight = mod._exec_group.execs[0].arg_dict["fc_weight"]
    assert weight._data.device == mx.cpu(1).jax_device
    np.testing.assert_array_equal(weight.asnumpy(),
                                  want["fc_weight"].asnumpy())
    it.reset()
    mod.fit(it, num_epoch=4, optimizer_params={"learning_rate": 0.5},
            kvstore="device")
    assert _released(mod)
    assert mod.score(it, "acc")[0][1] > 0.9

