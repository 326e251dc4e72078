"""Phi-4-mini-flash-reasoning (SambaY) on the normal path against its plain
reference.

``models/phi4_flash.py`` (an ``mx.sym`` graph: ``Mamba1`` between its two
projections, ``DiffAttention`` between its four, gated memory units and
cross-attention layers that read ONE earlier layer's scan output, keys and
values, a dense SwiGLU in every block, LayerNorm with bias, a tied head)
through ``Module.forward/backward`` and ``Module.fit``'s fused step,
against ``models/phi4_flash_reference.py`` (plain float32 ``jax.numpy``:
the Mamba layer as the token-by-token recurrence, attention by explicit
masks) on seeded weights at a tiny size: hidden 64, 8 layers in the
published order (two Mamba / window pairs, the memory and the cache, one
GMU / cross pair), 8 query heads on 4 key/value heads of 8, a window of
12, Mamba of 128 channels, state 16, dt_rank 4, SwiGLU of 96, vocabulary
512, T 40.

Tolerances as in ``tests/test_olmo_hybrid.py``: both sides are float32
and only the order of summation differs, so rtol 1e-5 with an atol of a
few float32 ulps of the tensor's own scale (``_close``). Gradients get 256
ulps (3e-5 of the tensor's largest entry): a gradient passes sixteen
LayerNorms' ``1 / std``, five sub-norms' ``1 / rms`` and three 40-token
recurrences on its way down; measured under 40 ulps at these weights. A
key projection's bias has NO gradient (a constant added to every key moves
every score of a query alike, and the softmax does not see it): both
sides hold float32 noise there, held to 1e-6 of the query bias's scale.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.models import phi4_flash, phi4_flash_reference as ref
from mxnet_tpu.ops import kernels as pk
from mxnet_tpu.ops.transformer import ssm
from mxnet_tpu.parallel import make_mesh

T, BATCH = 40, 2
CFG = dict(
    model_type="phi4flash", vocab_size=512, hidden_size=64,
    intermediate_size=96, num_hidden_layers=8, num_attention_heads=8,
    num_key_value_heads=4, sliding_window=12, mb_per_layer=2,
    layer_norm_eps=1e-5, tie_word_embeddings=True, mlp_bias=False,
    lm_head_bias=False, hidden_act="silu", embd_pdrop=0, resid_pdrop=0,
    max_position_embeddings=T)
MEMORY, FULL, GMU, CROSS = 4, 5, 6, 7      # the tiny model's layer numbers


def _close(got, want, what, rtol=1e-5, ulps=8):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    atol = ulps * np.finfo(np.float32).eps * max(np.abs(want).max(), 1e-30)
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol, err_msg=what)


def _params(sym, seed, sigma=0.08):
    """Seeded weights under the symbol's argument names: Normal(sigma),
    gammas and ``d`` near 1, taps of the published spread, ``a_log`` near
    log(1..16) and ``dt_bias`` by the published rule; biases and betas
    drawn too, so that every term is seen."""
    rng = np.random.RandomState(seed)
    shapes, _, _ = sym.infer_shape(data=(BATCH, T), softmax_label=(BATCH, T))
    out = {}
    for name, shape in zip(sym.list_arguments(), shapes):
        if name in ("data", "softmax_label"):
            continue
        if name.endswith("a_log"):
            out[name] = (np.log(np.broadcast_to(
                np.arange(1, shape[1] + 1), shape))
                + 0.1 * rng.randn(*shape)).astype(np.float32)
        elif name.endswith("dt_bias"):
            dt = np.exp(rng.uniform(np.log(0.001), np.log(0.1), shape))
            out[name] = (dt + np.log(-np.expm1(-dt))).astype(np.float32)
        else:
            scale = 0.3 if name.endswith("conv_weight") else sigma
            out[name] = (scale * rng.randn(*shape) + (
                name.endswith("_gamma") or name.endswith("_d"))
            ).astype(np.float32)
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


def _symbol_grads(sym, params, tokens, labels):
    mod = _module(sym, params)
    mod.forward(mx.io.DataBatch(data=[mx.nd.array(tokens)],
                                label=[mx.nd.array(labels)]), is_train=True)
    mod.backward()
    outs = [o.asnumpy() for o in mod.get_outputs()]
    grads = mod._exec_group.execs[0].grad_dict
    # the head sums the sequences' losses (MXNet's convention)
    return outs, {k: grads[k].asnumpy() / BATCH for k in params}


def _check_grads(got, want, params):
    assert set(want) == set(params)
    for name, want_g in want.items():
        want_g = np.asarray(want_g)
        if name.endswith("k_proj_bias"):        # no gradient: noise
            scale = np.abs(np.asarray(want[name.replace("k_proj", "q_proj")])
                           ).max()
            assert np.abs(got[name]).max() < 1e-6 * scale, name
            assert np.abs(want_g).max() < 1e-6 * scale, name
            continue
        _close(got[name], want_g, name, ulps=256)
        assert np.abs(want_g).max() > 1e-8, name


def test_logits_loss_and_every_gradient_match_the_reference():
    sym = phi4_flash.from_config(CFG, seq_len=T)
    params = _params(sym, 1)
    tokens, labels = _batch(2)
    want = ref.forward(params, tokens, CFG, labels=labels)
    loss, grads = ref.loss_and_grads(params, tokens, labels, CFG)

    outs, got = _symbol_grads(sym, params, tokens, labels)
    assert len(outs) == 1                       # the loss and nothing else
    _close(outs[0], want["per_sequence"], "per-sequence loss")
    _close(outs[0].mean(), loss, "loss")
    _check_grads(got, grads, params)

    logits_sym = sym.get_internals()["lm_head_f32_output"]
    mod = mx.mod.Module(logits_sym, context=mx.cpu(0), label_names=None)
    mod.bind(data_shapes=[("data", tokens.shape)], for_training=False)
    mod.init_params(arg_params={k: mx.nd.array(v)
                                for k, v in params.items()}, aux_params={})
    mod.forward(mx.io.DataBatch(data=[mx.nd.array(tokens)]), is_train=False)
    logits = mod.get_outputs()[0].asnumpy().reshape(want["logits"].shape)
    # forward through seventeen norms and three recurrences: 64 allowed
    _close(logits, want["logits"], "logits", ulps=64)
    last = ref.forward(params, tokens, CFG, labels=labels, last=7)
    _close(last["logits"], want["logits"][:, -7:], "the last positions")
    assert np.isinf(np.asarray(last["router_gap"])).all()   # no experts


def test_a_stage_keeps_its_layers_published_numbers():
    """Published layers 0, 1, 4, 5, 6, 7 of the 8 held: the kinds and
    ``lambda_init`` read the published number, not the position."""
    cfg = dict(CFG, num_hidden_layers=6, layers_held=[0, 1, 4, 5, 6, 7],
               published={"num_hidden_layers": 8})
    sym = phi4_flash.from_config(cfg, seq_len=T)
    names = sym.list_arguments()
    assert "layer4_mamba_a_log" in names and "layer7_q_proj_weight" in names
    assert not [n for n in names if n.startswith(("layer2_", "layer3_"))]
    params = _params(sym, 3)
    tokens, labels = _batch(4)
    loss, grads = ref.loss_and_grads(params, tokens, labels, cfg)
    outs, got = _symbol_grads(sym, params, tokens, labels)
    _close(outs[0].mean(), loss, "loss")
    _check_grads(got, grads, params)
    # the same weights read as layers 0..5 of a 6-layer model are another
    # model: the numbers decide
    assert [k for _, k in ref.layer_kinds(cfg)] == [
        "mamba", "window", "memory", "full", "gmu", "cross"]
    with pytest.raises(ValueError, match="without layers 4 and 5"):
        phi4_flash.from_config(dict(cfg, num_hidden_layers=3,
                                    layers_held=[0, 1, 6]), seq_len=T)


def _loss_with_own_copies(params, tokens, labels):
    """The model in which every reader has ITS OWN copy of what it reads:
    the GMU layer runs a Mamba mixer of its own (``layer6_mamba_*``) on
    layer 4's normed input, the cross layer its own key and value
    projections (``layer7_{k,v}_proj_*``) on layer 5's. Built from the
    reference's pieces."""
    eps = CFG["layer_norm_eps"]
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    normed = {}

    def mamba(a, number):
        m = "layer%d_mamba_" % number
        return ref.mamba(a, *(p[m + name] for name in (
            "in_proj_weight", "conv_weight", "conv_bias", "x_proj_weight",
            "dt_proj_weight", "dt_bias", "a_log", "d", "out_proj_weight")))

    def proj(a, number, name):
        n = "layer%d_%s_proj_" % (number, name)
        return a @ p[n + "weight"].T + p[n + "bias"]

    with jax.default_matmul_precision("highest"):
        h = p["embed_weight"][jnp.asarray(tokens, jnp.int32)]
        for number, kind in ref.layer_kinds(CFG):
            n = "layer%d_" % number
            a = ref.layer_norm(h, p[n + "norm_gamma"], p[n + "norm_beta"],
                               eps)
            normed[number] = a
            if kind in ("mamba", "memory"):
                y, _ = mamba(a, number)
            elif kind == "gmu":
                _, memory = mamba(normed[MEMORY], number)     # its own copy
                y = (memory * jax.nn.silu(a @ p[n + "gmu_in_proj_weight"].T)
                     ) @ p[n + "gmu_out_proj_weight"].T
            else:
                source = normed[FULL] if kind == "cross" else a
                y = ref.diff_attention(
                    proj(a, number, "q"), proj(source, number, "k"),
                    proj(source, number, "v"),
                    [p[n + "attn_lambda_" + v] for v in
                     ("q1", "k1", "q2", "k2")], p[n + "attn_subln_gamma"],
                    CFG, number,
                    CFG["sliding_window"] if kind == "window" else 0)
                y = y @ p[n + "o_proj_weight"].T + p[n + "o_proj_bias"]
            h = h + y
            a = ref.layer_norm(h, p[n + "ffn_norm_gamma"],
                               p[n + "ffn_norm_beta"], eps)
            h = h + ref.swiglu(a, p[n + "gate_proj_weight"],
                               p[n + "up_proj_weight"],
                               p[n + "down_proj_weight"])
        h = ref.layer_norm(h, p["final_norm_gamma"], p["final_norm_beta"],
                           eps)
        logp = jax.nn.log_softmax(h @ p["embed_weight"].T, axis=-1)
        return -jnp.mean(jnp.take_along_axis(
            logp, jnp.asarray(labels, jnp.int32)[..., None], axis=-1))


def test_a_shared_activations_gradient_is_the_sum_over_its_readers():
    """``K*``, ``V*`` (layer 5's) and ``m`` (layer 4's) are ONE node each
    in the graph, read by layers 7 and 6 as well as their own layer; the
    gradient of what made them is the sum of the copies' gradients in the
    model where every reader has its own."""
    sym = phi4_flash.from_config(CFG, seq_len=T)
    nodes = json.loads(sym.tojson())["nodes"]
    by_name = {n["name"]: n for n in nodes}

    def inputs(name):
        return [(nodes[i[0]]["name"], i[1]) for i in by_name[name]["inputs"]]

    def source(name, index):        # through the Reshape in between
        node, _ = inputs(name)[index]
        return inputs(node)[0] if by_name[node]["op"] == "Reshape" else None

    for reader in ("layer5_attn", "layer7_attn"):
        assert source(reader, 1) == ("layer5_k_proj", 0), reader
        assert source(reader, 2) == ("layer5_v_proj", 0), reader
    assert source("layer6_gmu", 0) == ("layer4_mamba", 1)   # the memory
    assert not [n for n in sym.list_arguments()
                if n.startswith(("layer7_k_proj", "layer7_v_proj",
                                 "layer6_mamba"))]

    params = _params(sym, 5)
    tokens, labels = _batch(6)
    _, got = _symbol_grads(sym, params, tokens, labels)
    copies = {}
    for name, value in params.items():
        if name.startswith("layer%d_mamba_" % MEMORY):
            copies[name.replace("layer%d_" % MEMORY, "layer%d_" % GMU)] = value
        if name.startswith(("layer%d_k_proj" % FULL, "layer%d_v_proj" % FULL)):
            copies[name.replace("layer%d_" % FULL, "layer%d_" % CROSS)] = value
    own = jax.grad(_loss_with_own_copies)(dict(params, **copies), tokens,
                                          labels)
    for copy in copies:
        made = copy.replace("layer%d_" % GMU, "layer%d_" % MEMORY).replace(
            "layer%d_" % CROSS, "layer%d_" % FULL)
        if made.endswith(("out_proj_weight", "k_proj_bias")):
            continue    # not on the memory's path / no gradient at all
        total = np.asarray(own[made]) + np.asarray(own[copy])
        _close(got[made], total, made, ulps=256)
        # the second reader's part is no rounding error
        assert np.abs(np.asarray(own[copy])).max() > 1e-3 * np.abs(
            total).max(), copy


def _dots_by_output_shape(jaxpr, shape):
    count = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" and \
                tuple(eqn.outvars[0].aval.shape) == shape:
            count += 1
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    count += _dots_by_output_shape(inner, shape)
    return count


def test_the_training_step_makes_the_shared_keys_and_values_once():
    """Forward and backward of the whole graph hold one ``k_proj`` and one
    ``v_proj`` product a layer that has them (layers 1, 3, 5: six products
    of [tokens, 32]): neither the cross layer nor a ``jax.checkpoint``
    inside an op computes layer 5's again."""
    from mxnet_tpu.executor import _GraphProgram

    sym = phi4_flash.from_config(CFG, seq_len=T)
    params = {k: jnp.asarray(v) for k, v in _params(sym, 7).items()}
    tokens, labels = (jnp.asarray(v) for v in _batch(8))
    program = _GraphProgram(sym)

    def loss(ps):
        outs, _ = program(dict(ps, data=tokens, softmax_label=labels), {},
                          jax.random.PRNGKey(0), True)
        return outs[0].sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss))(params).jaxpr
    kv_width = 4 * 8
    assert _dots_by_output_shape(jaxpr, (BATCH * T, kv_width)) == 6


def test_fused_fit_trains_the_stage_and_the_loss_falls():
    """Module.fit(kvstore='device', mesh dp=1) — the fused
    ShardedTrainStep: the first steps follow the reference's own SGD with
    momentum, and the loss falls."""
    sym = phi4_flash.from_config(CFG, seq_len=T)
    params = _params(sym, 3)
    tokens, labels = _batch(4)
    lr, momentum, steps = 0.05, 0.9, 6

    want = {k: jnp.asarray(v) for k, v in params.items()}
    moms = {k: jnp.zeros_like(v) for k, v in want.items()}
    losses = []
    for _ in range(2):
        loss, grads = ref.loss_and_grads(want, tokens, labels, CFG)
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
    # the dynamics, the taps and lambda's vectors are trained like any weight
    for name in ("layer0_mamba_a_log", "layer4_mamba_dt_bias",
                 "layer2_mamba_conv_weight", "layer4_mamba_d",
                 "layer5_attn_lambda_q1", "layer7_attn_subln_gamma",
                 "layer5_k_proj_weight"):
        assert np.abs(got[name].asnumpy() - params[name]).max() > 0, name


def test_the_kernel_pair_inside_the_model_gives_the_same_step(monkeypatch):
    """The tiny model's Mamba mixers are one lane row of 128 channels: by
    the one seam their scans run the Pallas pair through the interpreter,
    and the loss and the Mamba layers' gradients are the ``jax.numpy``
    form's."""
    sym = phi4_flash.from_config(CFG, seq_len=T)
    params = _params(sym, 9)
    tokens, labels = _batch(10)
    ssm._mamba1_block.clear_cache()
    outs, want = _symbol_grads(sym, params, tokens, labels)
    monkeypatch.setattr(pk.common, "INTERPRET", True)
    ssm._mamba1_block.clear_cache()
    try:
        outs_k, got = _symbol_grads(sym, params, tokens, labels)
    finally:
        ssm._mamba1_block.clear_cache()
    _close(outs_k[0], outs[0], "loss", ulps=16)
    for name in want:
        if "mamba" in name or name == "embed_weight":
            _close(got[name], want[name], name, ulps=256)


def test_the_model_states_its_own_initialisation_and_counts_its_call_sites():
    telemetry.reset()
    telemetry.enable()
    try:
        sym = phi4_flash.from_config(CFG, seq_len=T)
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
        scan = telemetry.REGISTRY.get("ssm.selective_lowerings")
        assert scan.value(channels=128, state=16, dt_rank=4, conv=4,
                          impl="kernel") == 3
        diff = telemetry.REGISTRY.get("attention.diff_lowerings")
        assert diff.value(heads=8, window=12, cross=0) == 2
        assert diff.value(heads=8, window=0, cross=0) == 1
        assert diff.value(heads=8, window=0, cross=1) == 1
        readers = telemetry.REGISTRY.get("attention.shared_kv_readers")
        assert readers.value(source="layer5") == 1
        mod.forward(batch, is_train=False)          # nothing a step
        assert telemetry.total("ssm.selective_lowerings") == 3
        assert telemetry.total("attention.diff_lowerings") == 4
    finally:
        telemetry.disable()
        telemetry.reset()
    got = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    assert 0.015 < got["embed_weight"].std() < 0.025      # tied: not 1
    assert 0.015 < got["layer0_mamba_in_proj_weight"].std() < 0.025
    np.testing.assert_allclose(
        got["layer4_mamba_a_log"],
        np.tile(np.log(np.arange(1, 17.0)), (128, 1)), rtol=1e-6)
    dt = np.log1p(np.exp(got["layer0_mamba_dt_bias"]))
    assert 0.001 * 0.99 <= dt.min() and dt.max() <= 0.1 * 1.01
    assert (got["layer2_mamba_d"] == 1).all()
    assert np.abs(got["layer0_mamba_conv_weight"]).max() <= 0.5
    assert not got["layer0_mamba_conv_bias"].any()
    lambdas = np.concatenate([v for k, v in got.items() if "_lambda_" in k])
    assert lambdas.size == 16 * 8 and 0.07 < lambdas.std() < 0.13
    assert (got["layer1_attn_subln_gamma"] == 1).all()
    assert (got["layer3_norm_gamma"] == 1).all()
    assert not got["layer3_norm_beta"].any()
    assert not got["layer1_q_proj_bias"].any()

    internals = sym.get_internals().list_outputs()
    for name in ("layer0_mamba_in_proj_output", "layer0_mamba_output",
                 "layer4_mamba_memory", "layer0_mamba_out_proj_output",
                 "layer1_attn_output", "layer6_gmu_output",
                 "layer7_attn_output", "final_norm_output",
                 "lm_head_f32_output", "loss_output"):
        assert name in internals, name
    # two norms a block and the final one, every one a LayerNorm
    nodes = json.loads(sym.tojson())["nodes"]
    assert sum(n["op"] == "_contrib_LayerNorm" for n in nodes) == 2 * 8 + 1
    assert not [n for n in nodes if n["op"] == "_contrib_RMSNorm"]
    assert not [n for n in internals if "rope" in n.lower()]


def test_from_config_refuses_what_it_does_not_build():
    for key, value in (("tie_word_embeddings", False), ("mlp_bias", True),
                       ("mb_per_layer", 1), ("hidden_act", "gelu"),
                       ("resid_pdrop", 0.1)):
        with pytest.raises(ValueError, match="%s=.* is not supported" % key):
            phi4_flash.from_config(dict(CFG, **{key: value}), seq_len=T)
    with pytest.raises(ValueError, match="layers_held has 2 entries"):
        phi4_flash.from_config(dict(CFG, layers_held=[0, 1]), seq_len=T)
    with pytest.raises(ValueError, match="must be a multiple of 4"):
        phi4_flash.from_config(dict(CFG, num_hidden_layers=6), seq_len=T)
    assert [phi4_flash.layer_kind(n, 32) for n in (0, 15, 16, 17, 18, 31)] \
        == ["mamba", "window_attention", "mamba_memory", "full_attention",
            "gmu", "cross_attention"]


def test_the_benchmarks_copy_of_the_reference_is_the_programs():
    """``bench/reference/phi4_flash.py`` is this file byte for byte: the
    benchmark may not import the program's reference (it would then
    compare the program with itself across a refactor), and nothing else
    held the two equal."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "bench", "reference",
                           "phi4_flash.py"), "rb") as ours, \
            open(ref.__file__, "rb") as theirs:
        assert ours.read() == theirs.read()
