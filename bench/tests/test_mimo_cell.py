"""What the ``mimo_v2_flash`` configuration brought: its file against
the published keys, its operations count against the hand count, the
benchmark's copy of the reference against the program's, the scope
reduction of ``share_scopes`` on a scope table, the six readers on
handed-in reductions, and the share kind's own checks."""
import pytest

import lib
import share_scopes

CFG = lib.load_json(lib.find("configs", "mimo_v2_flash", ".json"))
CELL = "mimo_v2_flash_fit_share_4k"
# XiaomiMiMo/MiMo-V2-Flash's config.json, the keys that say its shape
# (the two layer lists apart: see the configuration's ``published``)
PUBLISHED = {
    "attention_value_scale": 0.707, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 16384,
    "max_position_embeddings": 262144, "model_type": "mimo_v2_flash",
    "num_attention_heads": 64, "head_dim": 192, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "layernorm_epsilon": 1e-05,
    "rope_theta": 5000000, "tie_word_embeddings": False,
    "vocab_size": 152576, "partial_rotary_factor": 0.334,
    "sliding_window": 128, "swa_rope_theta": 10000,
    "attention_bias": False, "v_head_dim": 128,
    "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False, "sliding_window_size": 128,
    "attention_chunk_size": 128, "moe_intermediate_size": 2048,
    "n_routed_experts": 256, "n_shared_experts": None,
    "num_experts_per_tok": 8, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1,
    "topk_method": "noaux_tc", "routed_scaling_factor": None,
    "swa_num_attention_heads": 64, "swa_num_key_value_heads": 8,
    "swa_head_dim": 192, "swa_v_head_dim": 128}
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "head_dim", "v_head_dim", "swa_head_dim", "swa_v_head_dim",
          "num_experts_per_tok", "sliding_window", "partial_rotary_factor")


def test_configuration_keeps_every_published_width_and_states_its_cut():
    changed = {k for k, v in PUBLISHED.items() if CFG[k] != v}
    lists = {"hybrid_layer_pattern", "moe_layer_freq"}
    assert changed | lists == set(CFG["reduced"]) == set(CFG["reduced_why"])
    assert not changed & set(WIDTHS)
    for key in changed:           # the uncut count stands beside the held
        assert CFG["published"][key] == PUBLISHED[key]
    # the floors: a whole period after the dense layer, 8 experts, an
    # eighth of the vocabulary
    assert CFG["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 1, 0]
    assert CFG["moe_layer_freq"] == [0, 1, 1, 1, 1, 1, 1]
    assert CFG["num_hidden_layers"] == 7 and CFG["n_routed_experts"] == 8
    assert CFG["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert CFG["share"] == {"experts_of": 256, "expert_offset": 0,
                            "share_rows_bound": 2048,
                            "dense_columns_held": 2048}
    assert "32 chips share each layer" in CFG["deployment"]
    assert CFG["kwargs"]["seq_len"] == CFG["input_shape"][2] == 4096
    assert CFG["num_classes"] == CFG["vocab_size"]
    manifest = [c for c in lib.load_json(lib.MANIFEST)["configs"]
                if c["name"] == "mimo_v2_flash"][0]
    assert manifest["reduced"] == CFG["reduced"]
    assert manifest["source"] == CFG["source"]


def test_the_symbol_holds_the_parameters_the_cut_counted():
    """1,478 M parameters: six expert layers of 214.2 M, the dense layer
    37.0 M, embedding and head 156.2 M (ISSUE 31's arithmetic)."""
    import numpy as np

    sym = lib.resolve(CFG["factory"])(CFG, **CFG["kwargs"])
    shapes, _, _ = sym.infer_shape(data=(1, 4096), softmax_label=(1, 4096))
    sizes = {n: int(np.prod(s)) for n, s in zip(sym.list_arguments(), shapes)
             if n not in ("data", "softmax_label")}
    assert sizes["layer1_moe_gate_up_weight"] == 8 * 4096 * 2 * 2048
    assert sizes["layer1_moe_gate_weight"] == 4096 * 256
    assert sizes["layer1_q_proj_weight"] == 8 * 192 * 4096
    assert sizes["layer1_v_proj_weight"] == 128 * 4096
    assert sizes["layer0_gate_proj_weight"] == 2048 * 4096
    assert sizes["embed_weight"] == sizes["lm_head_weight"] == 19072 * 4096
    assert sum(sizes.values()) == pytest.approx(1478e6, rel=2e-3)


def test_forward_flops_match_the_hand_count():
    """Per sequence of 4096, forward: head 2 x 4096 x 4096 x 19072 =
    0.640 T; a layer's projections 2 x 4096 x 4096 x (1536 + 192 + 128 +
    1024) = 0.0966 T; full scores and values 2 x 8 x 320 x 4096 x 4097 /
    2 = 0.0430 T; the band 2 x 8 x 320 x (128 x 129 / 2 + 3968 x 128) =
    0.00264 T; a router 2 x 4096 x 4096 x 256 = 0.0086 T and 1,024 rows
    of an expert 2 x 1024 x 3 x 4096 x 2048 = 0.0515 T; the dense
    columns 2 x 4096 x 3 x 4096 x 2048 = 0.206 T."""
    fn = lib.load_module("flops", CFG["flops"])
    t, d = 4096, 4096
    assert fn.expected_share_rows(CFG) == 1024
    assert fn.window_layers(CFG) == 5 and fn.expert_layers(CFG) == 6
    assert fn.attn_full_flops(CFG) == 2.0 * 8 * 320 * t * (t + 1) / 2
    assert fn.attn_window_flops(CFG) == 2.0 * 8 * 320 * (
        128 * 129 / 2 + (t - 128) * 128)
    assert fn.moe_share_flops(CFG) == 2.0 * (
        t * d * 256 + 1024 * 3 * d * 2048)
    assert fn.moe_share_flops(CFG, 2048) - fn.moe_share_flops(CFG) == (
        2.0 * 1024 * 3 * d * 2048)
    want = (2.0 * t * d * 19072 + 7 * 2.0 * t * d * 2880
            + 2 * fn.attn_full_flops(CFG) + 5 * fn.attn_window_flops(CFG)
            + 6 * fn.moe_share_flops(CFG) + 2.0 * t * 3 * d * 2048)
    assert fn.forward_flops_per_sample(CFG) == pytest.approx(want, rel=1e-12)
    assert 3 * want / 1e12 == pytest.approx(5.93, abs=0.02)  # ISSUE: ~5.9
    assert fn.TRAIN_MULTIPLIER == 3


def test_the_two_copies_of_the_reference_are_one_text():
    import mxnet_tpu.models.mimo_v2_reference as theirs

    with open(lib.find("reference", CFG["reference"], ".py")) as ours, \
            open(theirs.__file__) as program:
        assert ours.read() == program.read()


# scope paths as the chip's trace has them (my chip run, PR 31)
SCOPES = {
    "fusion.1": "jit(step)/fwd_bwd/jvp(attn/layer4_attn)/window/cond/"
                "branch_0_fun/cond/branch_0_fun/"
                "flash_fwd_bf16_q256_k256_w128/pallas_call:",
    "fusion.2": "jit(step)/fwd_bwd/transpose(jvp(attn/layer6_attn))/full/"
                "cond/branch_0_fun/cond/branch_0_fun/"
                "flash_dkv_bf16_q1024_k1024/pallas_call:",
    "fusion.3": "jit(step)/fwd_bwd/transpose(jvp(attn/layer0_q_rope))/"
                "convert_element_type:",
    "fusion.4": "jit(step)/fwd_bwd/jvp(moe/layer4_moe)/dispatch/"
                "scatter-add:",
    "fusion.5": "jit(step)/fwd_bwd/transpose(jvp(attn/layer4_attn))/window/"
                "cond/branch_0_fun/reduce_sum:",
    "fusion.6": "jit(step)/fwd_bwd/jvp(attn/layer0_attn)/full/cond/"
                "branch_0_fun/reshape:",
    "fusion.7": "jit(step)/fwd_bwd/jvp(fc/window_proj)/dot_general:",
}


def test_scope_names_split_attention_by_the_kind_of_layer():
    kinds = {k: (m.group(1) if m else None) for k, m in (
        (k, share_scopes._KIND.search(v)) for k, v in SCOPES.items())}
    assert kinds == {"fusion.1": "window", "fusion.2": "full",
                     "fusion.3": None, "fusion.4": None,
                     "fusion.5": "window", "fusion.6": "full",
                     "fusion.7": None}


def _run(**over):
    run = {"cfg": CFG, "cell": {"name": CELL}, "trace_steps": 5,
           "batch": 1, "chips": 1, "flops_multiplier": 3,
           "peak": {"bf16_flops": 197e12},
           "share_scopes": {"window": 0.010, "full": 0.020},
           "lm_scopes": {"class_s": {"attn": 0.04, "moe": 0.050,
                                     "norm": 0, "embed": 0},
                         "head_loss_s": 0, "moe_part_s": {}},
           "expert_counts": [[128] * 256] * 5 + [[256] * 8 + [124] * 248]}
    run.update(over)
    return run


def _read(name, run, trace=True):
    return lib.load_module("layer_metrics", name).compute(
        {"devices": {}} if trace else None, {"telemetry": {}}, run)


def test_the_six_readers_read_what_they_say():
    fn = lib.load_module("flops", CFG["flops"])
    run = _run()
    assert _read("attn_window_device_ms", run) == pytest.approx(2.0)
    assert _read("attn_full_device_ms", run) == pytest.approx(4.0)
    assert _read("moe_share_device_ms", run) == pytest.approx(10.0)
    least_ms = 1e3 * 3 * 5 * fn.attn_window_flops(CFG) / 197e12
    assert _read("attn_window_roofline_share", run) == pytest.approx(
        100 * least_ms / 2.0)
    rows = [1024] * 5 + [2048]
    least_ms = 1e3 * 3 * sum(fn.moe_share_flops(CFG, r) for r in rows) / 197e12
    assert _read("moe_share_roofline_share", run) == pytest.approx(
        100 * least_ms / 10.0)
    # the sum over the six layers since PR 68 (the busiest layer's 2.0
    # until then): five at the expected 1,024 rows and one at twice
    assert _read("moe_share_rows_over_expected", run) == pytest.approx(
        7.0 / 6.0)


@pytest.mark.parametrize("name", [
    "attn_window_device_ms", "attn_full_device_ms",
    "attn_window_roofline_share", "moe_share_device_ms",
    "moe_share_roofline_share", "moe_share_rows_over_expected"])
def test_a_reader_finds_nothing_where_there_is_nothing(name):
    """No slice, no such scopes, no counts, a configuration without a
    share or with another operations module: None, never zero, never a
    raise (the benchmark's files are laid over older checkouts)."""
    olmoe = lib.load_json(lib.find("configs", "olmoe_1b_7b", ".json"))
    assert _read(name, _run(share_scopes=None, lm_scopes=None,
                            expert_counts=None)) is None
    assert _read(name, _run(cfg=olmoe, share_scopes=None)) is None
    if name != "moe_share_rows_over_expected":
        assert _read(name, _run(), trace=False) is None
    entry = [m for m in lib.load_json(lib.MANIFEST)["per_layer"]
             if m["name"] == name][0]
    assert CELL in entry["workloads"] if name.startswith("moe_share") \
        else entry["workloads"] == [CELL]
    assert entry["moves"] == "train_samples_s"


class _Out:
    def __init__(self, values):
        self.values = values

    def asnumpy(self):
        return self.values


def test_the_share_kind_fails_a_run_past_its_bound(monkeypatch):
    kind = lib.load_module("traffic", "fit_tokens_share")
    cell = lib.load_json(lib.find("cells", CELL, ".json"))
    cell["traffic"] = lib.load_json(lib.find(
        "traffic", cell["traffic"], ".json"))

    def run_with(held_rows):
        rest = (32768 - 8 * held_rows) // 248
        layer = [held_rows] * 8 + [rest] * 248
        layer[-1] += 32768 - sum(layer)

        class Mod:
            def get_outputs(self):
                return [None] + [_Out(layer)] * 6

        monkeypatch.setattr(kind.fit_tokens.fit, "run", lambda *a: {
            "checks": [], "series": {"losses": [10.7]}, "report": ()})
        state = {"cfg": CFG, "cell": cell, "mod": Mod(), "classes": 19072}
        trace = type("T", (), {"tracing": False})()
        return {name: ok for name, ok, _ in
                kind.run(state, 1.0, trace)["checks"]}

    assert all(run_with(128).values())
    over = run_with(300)                  # 2,400 rows to the held experts
    assert over["experts_routed_over_all"]
    assert not over["held_rows_within_bound"]
    assert not over["held_rows_near_expected"]
    assert not run_with(60)["held_rows_near_expected"]   # 0.47 of 1,024
