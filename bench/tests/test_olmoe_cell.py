"""What the ``olmoe_1b_7b`` configuration brought: its operations count
against the hand count, the benchmark's copy of the reference against
the program's, its configuration file against the published keys, and
the class reduction of ``lm_scopes`` on a scope table."""
import numpy as np
import pytest

import lib
import lm_scopes

CFG = lib.load_json(lib.find("configs", "olmoe_1b_7b", ".json"))
# OLMoE-1B-7B-0125-Instruct's config.json, the keys that say its shape
PUBLISHED = {
    "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 1024,
    "max_position_embeddings": 4096, "model_type": "olmoe",
    "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 16,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304}


def test_configuration_keeps_every_published_key_but_the_depth():
    changed = {k for k, v in PUBLISHED.items() if CFG[k] != v}
    assert changed == set(CFG["reduced"]) == {"num_hidden_layers"}
    assert CFG["num_hidden_layers"] in (2, 3, 4)
    assert CFG["kwargs"]["seq_len"] == CFG["input_shape"][2] == 4096
    assert CFG["num_classes"] == CFG["vocab_size"]


def test_forward_flops_match_the_hand_count():
    """Multiply-adds per token and layer at T 4096: experts 8 x 3 x 2048
    x 1024 = 50,331,648; the four projections 16,777,216; causal scores
    and values at (T+1)/2 keys 8,390,656; router 131,072: 75,630,592.
    The head 2048 x 50304 = 103,022,592. At 3 layers 329.9 M
    multiply-adds, 659.8 MFLOP forward per token."""
    fn = lib.load_module("flops", CFG["flops"])
    per_layer, head, t = 75630592, 103022592, 4096
    for layers in (2, 3, 16):
        cfg = dict(CFG, num_hidden_layers=layers)
        assert fn.forward_flops_per_sample(cfg) == pytest.approx(
            2.0 * t * (layers * per_layer + head), rel=1e-12)
    three = fn.forward_flops_per_sample(dict(CFG, num_hidden_layers=3))
    assert three / t / 1e6 == pytest.approx(659.8, abs=0.05)
    assert fn.moe_flops(CFG) == 2.0 * t * (50331648 + 131072)
    assert fn.attn_kernel_flops(CFG) == 2.0 * t * 8390656
    assert fn.TRAIN_MULTIPLIER == 3


def test_the_two_copies_of_the_reference_agree():
    import jax.numpy as jnp

    from mxnet_tpu.models import olmoe, olmoe_reference

    ours = lib.load_module("reference", CFG["reference"])
    tiny = lib.merge(CFG, lib.load_json(lib.find(
        "tests/rehearsal", "olmoe_fit_resident_4k", ".json"))["config"])
    sym = olmoe.from_config(tiny, **tiny["kwargs"])
    t = tiny["kwargs"]["seq_len"]
    shapes, _, _ = sym.infer_shape(data=(2, t), softmax_label=(2, t))
    rng = np.random.RandomState(0)
    params = {n: (0.1 * rng.randn(*s)).astype(np.float32)
              for n, s in zip(sym.list_arguments(), shapes)
              if n not in ("data", "softmax_label")}
    tokens = rng.randint(0, tiny["vocab_size"], (2, t + 1))
    for dtype in (jnp.float32, jnp.bfloat16):
        a = ours.forward(params, tokens[:, :-1], tiny,
                         labels=tokens[:, 1:], dtype=dtype, last=16)
        b = olmoe_reference.forward(params, tokens[:, :-1], tiny,
                                    labels=tokens[:, 1:], dtype=dtype,
                                    last=16)
        assert set(a) == set(b)
        for key in a:
            np.testing.assert_array_equal(
                np.asarray(a[key], np.float32), np.asarray(b[key], np.float32),
                err_msg=key)
    assert a["logits"].shape == (2, 16, tiny["vocab_size"])


def test_lm_scopes_files_time_by_class_head_and_moe_part():
    # (name, start, duration): back to back, 10..70 ns long
    ops = [("a", 0, 10), ("b", 10, 20), ("c", 30, 30), ("d", 60, 40),
           ("e", 100, 50), ("f", 150, 60), ("g", 210, 70), ("h", 280, 80)]
    scopes = {0: {
        "a": "jit(step)/fwd_bwd/attn/layer0_attn/pallas_call",
        "b": "jit(step)/fwd_bwd/transpose(jvp(moe/layer0_moe))/experts/x",
        "c": "jit(step)/fwd_bwd/moe/layer0_moe/dispatch/sort",
        "d": "jit(step)/fwd_bwd/fc/lm_head/dot_general",
        "e": "jit(step)/fwd_bwd/transpose(jvp(other/lm_head_logp))/sub",
        "f": "jit(step)/fwd_bwd/fc/layer0_q_proj/dot_general",
        "g": "jit(step)/fwd_bwd/norm/final_norm/mul",
        "h": "ragged-dot-none"}}  # XLA's own name: the scope is gone
    raw = {"host": [(0, "bench.slice_begin", 0, 0),
                    (0, "bench.slice_end", 360, 0)],
           "devices": {0: {"ops": ops}}}
    red = lm_scopes.reduce(raw, scopes)
    assert red["class_s"] == {"attn": 10e-9, "moe": 130e-9, "norm": 70e-9,
                              "embed": 0.0}
    assert red["head_loss_s"] == pytest.approx(90e-9)
    assert red["moe_part_s"] == {"experts": 100e-9, "dispatch": 30e-9}
    run = {"lm_scopes": red, "trace_steps": 1, "flops_multiplier": 3,
           "batch": 1, "chips": 1, "peak": {"bf16_flops": 197e12},
           "cfg": CFG}
    assert lib.load_module("layer_metrics", "moe_device_ms").compute(
        {"devices": {}}, {}, run) == pytest.approx(130e-6)
    assert lib.load_module("layer_metrics", "head_loss_device_ms").compute(
        {"devices": {}}, {}, run) == pytest.approx(90e-6)
    share = lib.load_module("layer_metrics", "moe_roofline_share").compute(
        {"devices": {}}, {}, run)
    fn = lib.load_module("flops", CFG["flops"])
    least_s = 3 * fn.moe_flops(CFG) * CFG["num_hidden_layers"] / 197e12
    assert share == pytest.approx(100 * least_s / 130e-9)
    # a conv net's trace has none of these scopes: nothing, not zero
    assert lm_scopes.reduce(raw, {0: {"a": "jit(step)/fwd_bwd/conv/c1"}}) \
        is None


def test_moe_load_reader():
    reader = lib.load_module("layer_metrics", "moe_load_max_over_mean")
    assert reader.compute(None, {}, {"expert_counts": [[4, 4, 4, 4],
                                                       [8, 4, 2, 2]]}) == 2.0
    assert reader.compute(None, {}, {}) is None
