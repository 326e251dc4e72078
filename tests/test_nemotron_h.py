"""Nemotron-3-Nano-30B-A3B on the normal path against its plain
reference.

``models/nemotron_h.py`` (an ``mx.sym`` graph of one-mixer blocks:
``Mamba2`` between ``in_proj`` and ``out_proj``, ``Attention`` over
grouped heads without positions, ``TopKMoE`` with un-gated relu² experts
beside a shared one) through ``Module.forward/backward`` and
``Module.fit``'s fused step, against ``models/nemotron_h_reference.py``
(plain float32 ``jax.numpy``: the state-space layer as the token-by-token
recurrence, attention by an explicit mask, a loop over the experts held)
on seeded weights at a tiny size: hidden 48, Mamba-2 of 4 heads of 8,
state 16, 2 groups, 4 taps, chunks of 8; 4 query heads on 2 key/value
heads of 8; 16 experts top-3 of width 24, a shared one of 40; T 30 (not
a multiple of the chunk) or 32 (four chunks).

Tolerances as in ``tests/test_kanana2.py``: both sides are float32 and
only the order of summation differs (the chunked form sums a chunk's
tokens by matrix products and crosses chunks through ``exp`` of summed
log decays where the recurrence multiplies decay by decay), so rtol 1e-5
with an atol of a few float32 ulps of the tensor's own scale
(``_close``); ``ulps`` is raised where a result is a long sum of such
terms (gradients through four chunks). A dropped carried state, a decay
taken from the wrong token, a tap in the wrong order or a norm over the
wrong group is off by orders of magnitude more: the carried-state case
measures that distance.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.models import nemotron_h, nemotron_h_reference as ref
from mxnet_tpu.ops.transformer import mamba2, ssd_scan
from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.parallel.moe import topk_moe

T, BATCH = 30, 2
H, P, N, G, TAPS, CHUNK = 4, 8, 16, 2, 4, 8
D_IN, CONV_DIM = H * P, H * P + 2 * G * N
CFG = dict(
    model_type="nemotron_h", hidden_size=48, num_hidden_layers=5,
    hybrid_override_pattern="MEM*E", mamba_num_heads=H, mamba_head_dim=P,
    ssm_state_size=N, n_groups=G, conv_kernel=TAPS, chunk_size=CHUNK,
    expand=2, time_step_min=0.001, time_step_max=0.1, time_step_floor=1e-4,
    use_conv_bias=True, mamba_proj_bias=False, mamba_hidden_act="silu",
    num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    attention_bias=False, rope_theta=10000, partial_rotary_factor=1,
    intermediate_size=24, moe_intermediate_size=24,
    moe_shared_expert_intermediate_size=40, n_routed_experts=16,
    n_shared_experts=1, num_experts_per_tok=3, norm_topk_prob=True,
    n_group=1, topk_group=1, routed_scaling_factor=2.5,
    mlp_hidden_act="relu2", mlp_bias=False, use_bias=False,
    layer_norm_epsilon=1e-5, norm_eps=1e-5, vocab_size=512,
    tie_word_embeddings=False, sliding_window=None,
    max_position_embeddings=T)
# one chip's share of the same model: 4 of the 16 experts from the 8th
# on, a buffer that holds every row
SHARE = dict(CFG, n_routed_experts=4, share=dict(
    experts_of=16, expert_offset=8, share_rows_bound=BATCH * T * 3))
# the share at a hidden size of one whole lane row over experts of 24: the
# published widths' kind (2,688 over 1,856), an up weight the chip holds
# transposed (``ops.kernels.held_transposed``)
SHARE_WIDE = dict(SHARE, hidden_size=128)
EXPERT_LAYERS, MAMBA_LAYERS = 2, 2
SSM = {k: CFG[k] for k in ("mamba_num_heads", "mamba_head_dim", "n_groups",
                           "ssm_state_size", "layer_norm_epsilon")}


def _close(got, want, what, rtol=1e-5, ulps=8):
    """rtol 1e-5, atol ``ulps`` float32 ulps of the largest magnitude of
    ``want`` (summation order is all that differs)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    atol = ulps * np.finfo(np.float32).eps * max(np.abs(want).max(), 1e-30)
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol, err_msg=what)


def _dynamics(rng, heads):
    """``a_log`` and ``dt_bias`` by the published rule (what
    ``init.LogOfUniform`` and ``init.InverseSoftplus`` draw)."""
    dt = np.exp(rng.uniform(np.log(0.001), np.log(0.1), heads))
    return (np.log(rng.uniform(1, 16, heads)).astype(np.float32),
            (dt + np.log(-np.expm1(-dt))).astype(np.float32))


def _params(sym, seed, sigma=0.08, data=(BATCH, T)):
    """Seeded weights under the symbol's argument names: Normal(sigma),
    a unit embedding as the model states it, gammas and skips near 1,
    taps of the published spread, ``a_log`` and ``dt_bias`` by the
    published rule, selection biases away from 0 (so that their part is
    tested)."""
    rng = np.random.RandomState(seed)
    shapes, _, _ = sym.infer_shape(data=data, softmax_label=data)
    out = {}
    for name, shape in zip(sym.list_arguments(), shapes):
        if name in ("data", "softmax_label"):
            continue
        if name.endswith(("ssm_a_log", "ssm_dt_bias")):
            out[name] = _dynamics(rng, shape[0])[name.endswith("dt_bias")]
            continue
        scale = {"embed_weight": 1.0, "conv_weight": 0.3}.get(
            name if name == "embed_weight" else name.split("ssm_")[-1],
            0.05 if name.endswith("bias") else sigma)
        out[name] = (scale * rng.randn(*shape)
                     + name.endswith(("_gamma", "ssm_d"))).astype(np.float32)
    return out


def _batch(seed):
    rng = np.random.RandomState(seed)
    tokens = rng.randint(0, CFG["vocab_size"], (BATCH, T + 1))
    return tokens[:, :-1].astype(np.float32), tokens[:, 1:].astype(np.float32)


def _module(sym, params):
    mod = mx.mod.Module(sym, context=mx.cpu(0))
    mod.bind(data_shapes=[("data", (BATCH, T))],
             label_shapes=[("softmax_label", (BATCH, T))])
    mod.init_params(arg_params={k: mx.nd.array(v) for k, v in params.items()},
                    aux_params={})
    return mod


# -- the whole model, uncut and as a share -----------------------------------

@pytest.mark.parametrize("cfg", [CFG, SHARE, SHARE_WIDE],
                         ids=["whole", "share", "share_held_transposed"])
def test_logits_loss_and_every_gradient_match_the_reference(cfg):
    sym = nemotron_h.from_config(cfg, seq_len=T)
    params = _params(sym, 1)
    from mxnet_tpu.ops import kernels

    assert kernels.held_transposed(
        params["layer1_moe_gate_up_weight"].shape) == (cfg is SHARE_WIDE)
    tokens, labels = _batch(2)
    want = ref.forward(params, tokens, cfg, labels=labels)
    loss, grads = ref.loss_and_grads(params, tokens, labels, cfg)

    mod = _module(sym, params)
    mod.forward(mx.io.DataBatch(data=[mx.nd.array(tokens)],
                                label=[mx.nd.array(labels)]), is_train=True)
    mod.backward()
    outs = [o.asnumpy() for o in mod.get_outputs()]
    assert len(outs) == 1 + EXPERT_LAYERS
    _close(outs[0], want["per_sequence"], "per-sequence loss")
    _close(outs[0].mean(), loss, "loss")
    for layer in range(EXPERT_LAYERS):
        # over all 16 of the router's experts, share or not
        np.testing.assert_array_equal(
            outs[1 + layer], np.asarray(want["expert_counts"][layer]))
        assert outs[1 + layer].sum() == BATCH * T * 3
    got = mod._exec_group.execs[0].grad_dict
    assert set(grads) == set(params)
    for name, want_g in grads.items():
        # the head sums the sequences' losses (MXNet's convention);
        # through two chunked scans of four chunks each, 64 ulps
        _close(got[name].asnumpy() / BATCH, want_g, name, ulps=64)
        if "select_bias" in name:
            assert not np.asarray(want_g).any()  # it moves the choice only
        elif "ssm_" in name or "shared" in name:
            assert np.abs(np.asarray(want_g)).max() > 1e-7, name

    logits_sym = sym.get_internals()["lm_head_f32_output"]
    mod = mx.mod.Module(logits_sym, context=mx.cpu(0), label_names=None)
    mod.bind(data_shapes=[("data", tokens.shape)], for_training=False)
    mod.init_params(arg_params={k: mx.nd.array(v)
                                for k, v in params.items()}, aux_params={})
    mod.forward(mx.io.DataBatch(data=[mx.nd.array(tokens)]), is_train=False)
    logits = mod.get_outputs()[0].asnumpy().reshape(want["logits"].shape)
    _close(logits, want["logits"], "logits", ulps=16)


def test_fused_fit_trains_the_share_and_the_loss_falls():
    """Module.fit(kvstore='device', mesh dp=1) — the fused
    ShardedTrainStep — on the share: the first steps follow the
    reference's own SGD with momentum, and the loss falls."""
    sym = nemotron_h.from_config(SHARE, seq_len=T)
    params = _params(sym, 3)
    tokens, labels = _batch(4)
    lr, momentum, steps = 0.05, 0.9, 6

    want = {k: jnp.asarray(v) for k, v in params.items()}
    moms = {k: jnp.zeros_like(v) for k, v in want.items()}
    losses = []
    for _ in range(2):
        loss, grads = ref.loss_and_grads(want, tokens, labels, SHARE)
        losses.append(float(loss))
        want, moms = ref.sgd_momentum_step(want, moms, grads, lr, momentum)

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
    _close(seen[:2], losses, "loss of the first two steps")
    assert seen[-1] < seen[0] - 0.05, seen
    got, _ = mod.get_params()
    for name in params:  # no gradient and no rule moves the bias
        if "select_bias" in name:
            np.testing.assert_array_equal(got[name].asnumpy(), params[name])
    # the dynamics are trained like any weight
    assert np.abs(got["layer0_ssm_a_log"].asnumpy()
                  - params["layer0_ssm_a_log"]).max() > 0


def test_the_model_states_its_own_initialisation_and_counts_its_call_sites():
    telemetry.reset()
    telemetry.enable()
    try:
        sym = nemotron_h.from_config(dict(SHARE, mamba_num_heads=64,
                                          n_groups=8), seq_len=T)
        mod = mx.mod.Module(sym, context=mx.cpu(0))
        mod.bind(data_shapes=[("data", (BATCH, T))],
                 label_shapes=[("softmax_label", (BATCH, T))],
                 for_training=False)
        mx.random.seed(5)
        np.random.seed(5)
        mod.init_params(initializer=mx.init.Normal(sigma=0.02))
        tokens, labels = _batch(6)
        batch = mx.io.DataBatch(data=[mx.nd.array(tokens)],
                                label=[mx.nd.array(labels)])
        mod.forward(batch, is_train=False)
        # one per layer's call site, nothing per step
        scan = telemetry.REGISTRY.get("ssm.scan_lowerings")
        # state 16 in chunks of 8: no tiles for the kernel pair
        assert scan.value(heads=64, head_dim=P, state=N, groups=8,
                          chunk=CHUNK, conv=TAPS,
                          impl="einsum") == MAMBA_LAYERS
        share = telemetry.REGISTRY.get("moe.share_lowerings")
        assert share.value(held=4, of=16, bound=BATCH * T * 3, scale=2.5,
                           sum="segment_product", act="relu2") == EXPERT_LAYERS
        mod.forward(batch, is_train=False)
        assert telemetry.total("ssm.scan_lowerings") == MAMBA_LAYERS
    finally:
        telemetry.disable()
        telemetry.reset()
    got = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    assert 0.9 < got["embed_weight"].std() < 1.1
    assert 0.015 < got["layer0_in_proj_weight"].std() < 0.025
    assert not got["layer1_moe_select_bias"].any()
    assert got["layer1_moe_gate_up_weight"].shape == (4, 48, 24)  # no gate
    assert "layer1_shared_gate_proj_weight" not in got
    assert (got["layer0_ssm_d"] == 1).all()
    assert (got["layer0_ssm_norm_gamma"] == 1).all()
    assert not got["layer0_ssm_conv_bias"].any()
    taps = got["layer0_ssm_conv_weight"]
    assert taps.shape == (TAPS, 64 * P + 2 * 8 * N)
    assert 0.45 < np.abs(taps).max() <= 0.5 and abs(taps.mean()) < 0.02
    # the published rule: rates in [1, 16], step sizes in [0.001, 0.1];
    # over one published chunk of 128 tokens a head keeps between 0.88
    # (rate 1, step 0.001) and nothing of its state, and some of 64
    # drawn heads keep a tenth or more: what crosses a chunk is part of y
    rate = np.exp(got["layer0_ssm_a_log"])
    step = np.log1p(np.exp(got["layer0_ssm_dt_bias"]))
    assert rate.min() >= 1 and rate.max() <= 16 and rate.std() > 2
    assert step.min() >= 0.00099 and step.max() <= 0.101
    kept = np.exp(-128 * rate * step)
    assert kept.max() > 0.1 and kept.min() < 1e-3, (kept.min(), kept.max())
    assert got["layer2_ssm_a_log"].tolist() != got["layer0_ssm_a_log"].tolist()


def test_the_cells_widths_take_the_kernel_pair():
    """Trace only, at the Nemotron cell's widths (64 heads of 64, state
    128, 8 groups, chunks of 128, bf16): the call site counts itself
    under ``impl="kernel"``, and the einsum form at any width the kernels
    have no tiles for."""
    heads, p, n, g, chunk = 64, 64, 128, 8, 128
    conv_dim = heads * p + 2 * g * n

    def trace(chunk, dtype):
        spec = lambda *shape: jax.ShapeDtypeStruct(shape, dtype)
        jax.eval_shape(
            lambda *ins: mamba2(*ins, num_heads=heads, head_dim=p,
                                state_size=n, num_groups=g,
                                chunk_size=chunk, eps=1e-5),
            spec(1, 2 * chunk, heads * p + conv_dim + heads),
            spec(TAPS, conv_dim), spec(conv_dim), spec(heads), spec(heads),
            spec(heads), spec(heads * p))

    telemetry.reset()
    telemetry.enable()
    try:
        trace(chunk, jnp.bfloat16)
        trace(chunk, jnp.float32)
        trace(64, jnp.bfloat16)
        trace(chunk, jnp.float16)
        scan = telemetry.REGISTRY.get("ssm.scan_lowerings")
        labels = dict(heads=heads, head_dim=p, state=n, groups=g, conv=TAPS)
        assert scan.value(chunk=chunk, impl="kernel", **labels) == 2
        assert scan.value(chunk=chunk, impl="einsum", **labels) == 1
        assert scan.value(chunk=64, impl="einsum", **labels) == 1
        assert telemetry.total("ssm.scan_lowerings") == 4
    finally:
        telemetry.disable()
        telemetry.reset()


def test_three_blocks_of_one_shape_trace_the_block_and_the_kernels_once():
    """Three ``Mamba2`` nodes of one shape the kernel pair takes (4 heads
    of 32 in one lane row, state 128, chunks of 128) through the fused
    step: each node counts its call site, the block behind them is one
    ``jax.jit`` and each kernel's ``pallas_call`` is traced once, not once
    a node nor once a branch; and the step (off the TPU: the einsum
    branch inside the kernels' ``custom_vjp``) follows the reference."""
    from mxnet_tpu.ops import kernels as pk
    from mxnet_tpu.ops.transformer import ssm

    t, heads, p, n, chunk = 256, 4, 32, 128, 128
    cfg = dict(CFG, num_hidden_layers=4, hybrid_override_pattern="MMME",
               mamba_num_heads=heads, mamba_head_dim=p, ssm_state_size=n,
               n_groups=1, chunk_size=chunk, max_position_embeddings=t)
    assert pk.ssd_takes(heads, p, n, 1, chunk, jnp.float32)
    sym = nemotron_h.from_config(cfg, seq_len=t)
    params = _params(sym, 7, data=(1, t))
    tokens = np.random.RandomState(8).randint(0, CFG["vocab_size"],
                                              (1, t + 1))
    tokens, labels = (tokens[:, :-1].astype(np.float32),
                      tokens[:, 1:].astype(np.float32))
    lr, momentum, steps = 0.05, 0.9, 3
    want = {k: jnp.asarray(v) for k, v in params.items()}
    moms = {k: jnp.zeros_like(v) for k, v in want.items()}
    losses = []
    for _ in range(2):
        loss, grads = ref.loss_and_grads(want, tokens, labels, cfg)
        losses.append(float(loss))
        want, moms = ref.sgd_momentum_step(want, moms, grads, lr, momentum)

    for jitted in (ssm._mamba2_block, pk.ssd.ssd_fwd_call,
                   pk.ssd.ssd_bwd_call):
        jitted.clear_cache()    # another test's trace is not this one's
    telemetry.reset()
    telemetry.enable()
    try:
        seen = []
        mod = mx.mod.Module(sym, context=mx.cpu(0), mesh=make_mesh(dp=1))
        mod.fit(mx.io.NDArrayIter(np.tile(tokens, (steps, 1)),
                                  np.tile(labels, (steps, 1)), batch_size=1),
                num_epoch=1, eval_metric="loss", optimizer="sgd",
                optimizer_params={"learning_rate": lr, "momentum": momentum},
                kvstore="device",
                arg_params={k: mx.nd.array(v) for k, v in params.items()},
                aux_params={}, initializer=None,
                batch_end_callback=lambda b: (
                    seen.append(b.eval_metric.get()[1]),
                    b.eval_metric.reset()))
        assert mod._fused_trainer is not None
        scan = telemetry.REGISTRY.get("ssm.scan_lowerings")
        assert scan.value(heads=heads, head_dim=p, state=n, groups=1,
                          chunk=chunk, conv=TAPS, impl="kernel") == 3
        assert telemetry.total("ssm.scan_lowerings") == 3
        traces = telemetry.REGISTRY.get("ssm.scan_kernel_traces")
        assert (traces.value(mode="fwd"), traces.value(mode="bwd")) == (1, 1)
        # the gate and norm behind each scan: a node and lowering, not a step
        norm = telemetry.REGISTRY.get("gate_norm.lowerings")
        assert norm.value(site="mamba2", groups=1, width=heads * p,
                          impl="kernel") == 3
        assert telemetry.total("gate_norm.lowerings") == 3
    finally:
        telemetry.disable()
        telemetry.reset()
    _close(seen[:2], losses, "loss of the first two steps")
    assert seen[-1] < seen[0], seen


def test_from_config_refuses_what_it_does_not_implement():
    for key, value in [("hybrid_override_pattern", "ME-*E"), ("n_group", 2),
                       ("topk_group", 2), ("attention_bias", True),
                       ("mlp_bias", True), ("use_bias", True),
                       ("mamba_proj_bias", True), ("use_conv_bias", False),
                       ("tie_word_embeddings", True),
                       ("mamba_hidden_act", "gelu"),
                       ("mlp_hidden_act", "silu"), ("sliding_window", 4096),
                       ("norm_eps", 1e-6), ("num_hidden_layers", 4)]:
        with pytest.raises(ValueError, match=(
                "pattern" if key == "hybrid_override_pattern" else key)):
            nemotron_h.from_config(dict(CFG, **{key: value}), seq_len=T)


def test_the_new_symbol_is_built_from_the_shared_blocks():
    """One norm and one mixer a block, the head's nodes under the names
    every LM symbol has, no positional op anywhere."""
    sym = nemotron_h.from_config(CFG, seq_len=T)
    internals = sym.get_internals().list_outputs()
    for name in ("layer0_norm_output", "layer0_in_proj_output",
                 "layer0_ssm_output", "layer0_out_proj_output",
                 "layer1_moe_output", "layer1_shared_up_proj_output",
                 "layer3_attn_output", "final_norm_output",
                 "lm_head_f32_output", "loss_output"):
        assert name in internals, name
    assert sum(n.endswith("norm_output") for n in internals) == 5 + 1
    assert not [n for n in internals if "rope" in n.lower()]
    assert mx.executor.op_class("_contrib_Mamba2") == "ssm"


# -- Mamba2: the chunked scan against the recurrence -------------------------

def _ssm_inputs(seed, t, dtype=jnp.float32, heads=H, groups=G):
    rng = np.random.RandomState(seed)
    conv_dim = heads * P + 2 * groups * N
    a_log, dt_bias = _dynamics(rng, heads)

    def draw(*shape, scale=1.0, shift=0.0):
        return jnp.asarray(shift + scale * rng.randn(*shape), dtype)

    return (draw(BATCH, t, heads * P + conv_dim + heads),
            draw(TAPS, conv_dim, scale=0.3), draw(conv_dim, scale=0.1),
            jnp.asarray(dt_bias, dtype), jnp.asarray(a_log, dtype),
            draw(heads, scale=0.2, shift=1.0),
            draw(heads * P, scale=0.1, shift=1.0),
            jnp.asarray(rng.randn(BATCH, t, heads * P), jnp.float32))


def _op(*ins, chunk=CHUNK, heads=H, groups=G):
    return mamba2(*ins, num_heads=heads, head_dim=P, state_size=N,
                  num_groups=groups, chunk_size=chunk, eps=1e-5)


def _plain(*ins, heads=H, groups=G):
    return ref.mamba2(*ins, dict(SSM, mamba_num_heads=heads,
                                 n_groups=groups))


@pytest.mark.parametrize("t", [30, 32, 5],
                         ids=["ragged_last_chunk", "four_chunks",
                              "under_a_chunk"])
def test_mamba2_matches_the_token_by_token_recurrence(t):
    """Forward and the gradient with respect to every input (the
    projection's output, the taps and their bias, the step sizes' bias,
    the rates, the skip, the gated norm's scale), float32 to summation
    order, at a length that is not a multiple of the chunk, at one of
    four whole chunks and at one shorter than a chunk."""
    *ins, cot = _ssm_inputs(0, t)
    _close(_op(*ins), _plain(*ins), "out", ulps=16)
    every = tuple(range(len(ins)))
    got = jax.grad(lambda *a: jnp.sum(_op(*a) * cot), every)(*ins)
    want = jax.grad(lambda *a: jnp.sum(_plain(*a) * cot), every)(*ins)
    for name, g, w in zip(("dproj", "dconv_weight", "dconv_bias", "ddt_bias",
                           "da_log", "dd", "dnorm_gamma"), got, want):
        assert g.shape == w.shape
        assert float(jnp.abs(w).max()) > 1e-4, name
        _close(g, w, name, ulps=64)


def test_the_chunk_size_changes_nothing_but_the_order_of_summation():
    *ins, _ = _ssm_inputs(1, 32)
    want = _plain(*ins)
    for chunk in (4, 8, 16, 32, 128):
        _close(_op(*ins, chunk=chunk), want, "chunk %d" % chunk, ulps=16)


def test_dropping_the_state_carried_between_chunks_is_caught():
    """THE CARRIED-STATE TEST. With ``a_log`` and ``dt_bias`` by the
    published rule, what a chunk inherits from the chunks before it is a
    measurable share of ``y``: the scan run chunk by chunk from a zero
    state (the carried state dropped) differs from the whole scan by
    more than a tenth of y's standard deviation past the first chunk,
    ten thousand times the tolerance the whole scan meets against the
    token-by-token recurrence."""
    chunk, t = 8, 32
    rng = np.random.RandomState(2)
    a_log, dt_bias = _dynamics(rng, H)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    x = f32(rng.randn(BATCH, t, H, P))
    bmat, cmat = f32(rng.randn(2, BATCH, t, G, N))
    dt = jax.nn.softplus(f32(rng.randn(BATCH, t, H)) + dt_bias)
    a = -jnp.exp(f32(a_log))

    def recurrence(x, bmat, cmat, dt):
        def token(state, at):
            x_t, b_t, c_t, dt_t = at
            b_t, c_t = (jnp.repeat(v, H // G, axis=1) for v in (b_t, c_t))
            state = (jnp.exp(dt_t * a)[:, :, None, None] * state
                     + (dt_t[:, :, None] * x_t)[..., None]
                     * b_t[:, :, None, :])
            return state, jnp.sum(state * c_t[:, :, None, :], axis=-1)
        _, y = jax.lax.scan(
            token, jnp.zeros((BATCH, H, P, N)),
            tuple(jnp.moveaxis(v, 1, 0) for v in (x, bmat, cmat, dt)))
        return jnp.moveaxis(y, 0, 1)

    whole = ssd_scan(x, bmat, cmat, dt, a, chunk)
    want = recurrence(x, bmat, cmat, dt)
    _close(whole, want, "the chunked scan", ulps=16)
    dropped = jnp.concatenate(
        [ssd_scan(x[:, s:s + chunk], bmat[:, s:s + chunk],
                  cmat[:, s:s + chunk], dt[:, s:s + chunk], a, chunk)
         for s in range(0, t, chunk)], axis=1)
    np.testing.assert_array_equal(np.asarray(dropped[:, :chunk]),
                                  np.asarray(whole[:, :chunk]))
    carried = float(jnp.sqrt(jnp.mean(
        (whole - dropped)[:, chunk:] ** 2)) / want[:, chunk:].std())
    assert carried > 0.1, carried
    with pytest.raises(AssertionError):
        _close(dropped, want, "the carried state dropped", ulps=16)


def test_mamba2_in_bf16_keeps_its_decays_and_state_in_float32():
    """bf16 inputs; the op's convolution sum, step sizes, decays,
    carried state, gate and norm statistics are float32: against the
    float32 reference on the same bf16-rounded inputs the output's rms
    error is that of rounding the scan's operands and the result to
    bf16. The reference one precision below (all of those in bf16, the
    state carried through 256 tokens in bf16) is further off on every
    seed; the limit 0.004 lies between the two readings, 1.4x from
    either (measured here, seeds 0..5: ours 0.00271-0.00279 of the
    output's standard deviation, the bf16 recurrence 0.00583-0.00594)."""
    for seed in range(3):
        *ins, _ = _ssm_inputs(seed, 256, jnp.bfloat16, heads=8, groups=2)
        want = _plain(*[a.astype(jnp.float32) for a in ins], heads=8)

        def rms(out):
            return float(jnp.sqrt(jnp.mean(
                (out.astype(jnp.float32) - want) ** 2)) / want.std())

        got = _op(*ins, chunk=64, heads=8)
        assert got.dtype == jnp.bfloat16
        ours, theirs = rms(got), rms(_plain(*ins, heads=8))
        assert ours < 0.004 < theirs, (seed, ours, theirs)


def test_mamba2_op_checks_its_inputs():
    data = mx.sym.Variable("data")

    def infer(shape, **attrs):
        attrs = dict(dict(num_heads=H, head_dim=P, state_size=N,
                          num_groups=G, conv_kernel=TAPS, chunk_size=CHUNK),
                     **attrs)
        op = mx.contrib.sym.Mamba2(data, name="ssm", **attrs)
        return op.list_arguments(), op.infer_shape(data=shape)

    names, (ins, outs, _) = infer((2, T, D_IN + CONV_DIM + H))
    assert names == ["data", "ssm_conv_weight", "ssm_conv_bias",
                     "ssm_dt_bias", "ssm_a_log", "ssm_d", "ssm_norm_gamma"]
    assert ins[1:] == [(TAPS, CONV_DIM), (CONV_DIM,), (H,), (H,), (H,),
                       (D_IN,)]
    assert outs == [(2, T, D_IN)]
    for bad, what in [(dict(shape=(2, T, D_IN + CONV_DIM)), "data must be"),
                      (dict(shape=(2 * T, D_IN + CONV_DIM + H)),
                       "data must be"),
                      (dict(shape=(2, T, 3 * P * 3 + 2 * G * N + 3),
                            num_heads=3), "groups divide"),
                      (dict(shape=(2, T, D_IN + CONV_DIM + H),
                            chunk_size=0), "positive")]:
        with pytest.raises(Exception, match=what):
            infer(**bad)


# -- TopKMoE with un-gated relu² experts -------------------------------------

def _moe_weights(seed, tokens=96, d=48, experts=16, hidden=24):
    rng = np.random.RandomState(seed)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    return (f32(rng.randn(tokens, d)),
            {"gate_w": f32(rng.randn(d, experts) * 0.3),
             "w_gate_up": f32(rng.randn(experts, d, hidden) * 0.1),
             "w_down": f32(rng.randn(experts, hidden, d) * 0.1),
             "select_bias": f32(rng.randn(experts) * 0.05)})


def _held(w, offset, held):
    return dict(w, w_gate_up=w["w_gate_up"][offset:offset + held],
                w_down=w["w_down"][offset:offset + held])


@pytest.mark.parametrize("d,interpret", [(48, False), (128, False),
                                         (128, True)],
                         ids=["declared", "held_transposed",
                              "held_transposed_kernels"])
@pytest.mark.parametrize("held", [16, 4], ids=["whole_layer", "share"])
def test_relu2_experts_match_the_reference(held, d, interpret, monkeypatch):
    """``activation="relu2"``: ``w_gate_up`` is the up projection alone
    ([E, d, h]), an expert is ``down(relu(up(x))^2)``; output and the
    gradient of every input, whole and as a share; at the tiny hidden
    size and at one of a whole lane row over experts of 24, the
    published widths' kind (2,688 over 1,856), which the chip holds
    transposed and the grouped matmul takes so (here its ``ragged_dot``
    branch over the swap, and its kernels through the interpreter)."""
    from mxnet_tpu.ops import kernels

    monkeypatch.setattr(kernels.common, "INTERPRET", interpret)
    x, w = _moe_weights(0, d=d)
    assert kernels.held_transposed(w["w_gate_up"].shape) == (d == 128)
    offset = 8 if held < 16 else 0
    part = _held(w, offset, held)

    def run(x, part):
        return topk_moe(part, x, 3, norm_topk_prob=True, scoring="sigmoid",
                        expert_offset=offset, routed_scale=2.5,
                        share_rows_bound=x.shape[0] * 3 if held < 16 else 0,
                        activation="relu2")[0]

    def plain(x, part):
        return ref.moe(x, part["gate_w"], part["w_gate_up"], part["w_down"],
                       part["select_bias"], 3, True, offset, 2.5)[0]

    _close(run(x, part), plain(x, part), "relu2")
    cot = jnp.asarray(np.random.RandomState(1).randn(*x.shape), jnp.float32)
    got = jax.grad(lambda x, p: jnp.sum(run(x, p) * cot), (0, 1))(x, part)
    want = jax.grad(lambda x, p: jnp.sum(plain(x, p) * cot), (0, 1))(x, part)
    _close(got[0], want[0], "dx", ulps=32)
    for name in ("gate_w", "w_gate_up", "w_down"):
        _close(got[1][name], want[1][name], name, ulps=32)
    with pytest.raises(ValueError, match="activation"):
        topk_moe(w, x, 3, activation="gelu")


def test_topk_moe_op_infers_the_ungated_width():
    data = mx.sym.Variable("data")

    def shapes(**attrs):
        op = mx.contrib.sym.TopKMoE(data, num_experts=16, num_hidden=24,
                                    top_k=3, name="moe", **attrs)
        return op.infer_shape(data=(96, 48))[0]

    assert shapes()[2] == (16, 48, 48)                  # gate and up
    assert shapes(activation="relu2")[2] == (16, 48, 24)
    assert shapes(activation="relu2", experts_held=4, expert_offset=8,
                  share_rows_bound=96)[2:] == [(4, 48, 24), (4, 24, 48)]
    with pytest.raises(Exception, match="activation"):
        shapes(activation="gelu")


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """THE SHARE-SUM TEST. One expert block of the model at the tiny
    size: the residual ``h``, the router over all 16 experts, the shared
    expert and the routed ones. Sixteen shares of one expert each route
    over all 16 and compute their own expert's part; the shared expert
    (and the residual) are what every chip computes alike and count
    once. The sum is the uncut reference's block."""
    rng = np.random.RandomState(5)
    x, w = _moe_weights(5)
    shared = [jnp.asarray(rng.randn(*s) * 0.1, jnp.float32)
              for s in ((40, 48), (48, 40))]
    whole, counts, _ = ref.moe(
        x, w["gate_w"], w["w_gate_up"], w["w_down"], w["select_bias"], 3,
        True, 0, 2.5)
    want = x + ref.relu2(x, *shared) + whole

    total = x + ref.relu2(x, *shared)           # counted once
    for offset in range(16):
        part, part_counts = topk_moe(
            _held(w, offset, 1), x, 3, norm_topk_prob=True,
            scoring="sigmoid", expert_offset=offset,
            share_rows_bound=x.shape[0], routed_scale=2.5,
            activation="relu2")
        np.testing.assert_array_equal(np.asarray(part_counts),
                                      np.asarray(counts))
        mine, _, _ = ref.moe(
            x, w["gate_w"], w["w_gate_up"][offset:offset + 1],
            w["w_down"][offset:offset + 1], w["select_bias"], 3, True,
            offset, 2.5)
        _close(part, mine, "share at %d" % offset)
        total = total + part
    _close(total, want, "sum of the sixteen shares", ulps=32)
    # adding the shared expert in every share would count it 16 times
    assert float(jnp.abs(ref.relu2(x, *shared)).max()) > 1e-2
