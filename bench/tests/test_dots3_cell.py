"""What the ``dots3_note_prev`` configuration brought: its file against
the published keys, the parameters the cut counted, its operations
against the hand count (the numbers of ISSUE 59, at the cell's size and
at the rehearsal's), the benchmark's copy of the reference against the
program's, the table of ``dots3_scopes`` on scope paths, the ten readers
on handed-in reductions, the new kind's split of the model's outputs, the
cell beside the one whose mix it follows, and the cell's rehearsal end to
end."""
import numpy as np
import pytest

import dots3_scopes
import lib
from helpers import check_rehearsal, run_bench

CFG = lib.load_json(lib.find("configs", "dots3_note_prev", ".json"))
CELL = "dots3_note_fit_share_4k"
F, S = "full_attention", "sliding_attention"
FLOPS = lib.load_module("flops", "dots3_symbol")
# dots-studio/dots3-note-prev's config.json (the model-configs catalog's
# ``config``)
PUBLISHED = {
    "apply_mla_qkv_lora_rescale": True, "attention_bias": False,
    "attention_gate_type": "headwise", "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 5120, "index_head_dim": 128,
    "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 13824,
    "kv_lora_rank": 512, "layer_types": [F, F] + [S, S, S, F] * 11,
    "max_position_embeddings": 524288, "model_type": "dots3_note",
    "moe_intermediate_size": 1536, "moe_layer_freq": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 46, "num_key_value_heads": 128,
    "q_lora_rank": 1024, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 80000000,
    "routed_scaling_factor": 1, "scoring_func": "sigmoid",
    "sliding_window_size": 513, "swa_attention_gate_type": "headwise",
    "swa_kv_lora_rank": 1024, "swa_num_attention_heads": 64,
    "swa_num_key_value_heads": 64, "swa_q_lora_rank": 1024,
    "swa_qk_nope_head_dim": 192, "swa_qk_rope_head_dim": 64,
    "swa_rope_theta": 50000, "swa_v_head_dim": 128,
    "tie_word_embeddings": False, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 152064}
REDUCED = {"num_hidden_layers", "layer_types", "n_routed_experts",
           "num_attention_heads", "num_key_value_heads",
           "swa_num_attention_heads", "swa_num_key_value_heads",
           "vocab_size"}
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "q_lora_rank", "kv_lora_rank", "swa_q_lora_rank",
          "swa_kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
          "v_head_dim", "swa_qk_nope_head_dim", "swa_qk_rope_head_dim",
          "swa_v_head_dim", "index_head_dim", "index_n_heads", "index_topk",
          "sliding_window_size", "num_experts_per_tok", "n_shared_experts")
SELECTED = 6292480          # sum_t min(t + 1, 2048) over 4,096 rows


def test_configuration_keeps_every_published_width_and_states_its_cut():
    assert len(PUBLISHED["layer_types"]) == 46
    assert PUBLISHED["layer_types"].count(F) == 13
    changed = {k for k, v in PUBLISHED.items() if CFG[k] != v}
    assert changed == set(CFG["reduced"]) == set(CFG["reduced_why"]) \
        == REDUCED
    assert not changed & set(WIDTHS)
    for key in changed - {"layer_types"}:   # the uncut count beside the held
        assert CFG["published"][key] == PUBLISHED[key]
    # the floors: the leading dense layer and one whole period of four,
    # 8 experts, an eighth of the vocabulary
    assert CFG["layer_types"] == PUBLISHED["layer_types"][:5] == [
        F, F, S, S, S]
    assert CFG["num_hidden_layers"] == 5
    assert CFG["n_routed_experts"] * 32 == PUBLISHED["n_routed_experts"]
    assert CFG["num_attention_heads"] * 8 == CFG["num_key_value_heads"] * 8 \
        == PUBLISHED["num_attention_heads"]
    assert CFG["swa_num_attention_heads"] * 8 \
        == CFG["swa_num_key_value_heads"] * 8 \
        == PUBLISHED["swa_num_attention_heads"]
    assert CFG["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    share = CFG["share"]
    assert share["dense_columns_held"] * 8 == PUBLISHED["intermediate_size"]
    assert (share["experts_of"], share["expert_offset"]) == (256, 0)
    assert share["share_rows_bound"] == 2 * 4096 * 8 * 8 // 256
    assert "32 chips share each layer" in CFG["deployment"]
    assert CFG["env"] == {}
    # everything the issue marks as assumed is said
    for key in ("apply_mla_qkv_lora_rescale", "rope", "indexer",
                "indexer_training", "gate", "window", "optimizer",
                "weights", "objective", "left_out"):
        assert CFG["assumed"][key], key
    assert "LongCat" in CFG["assumed"]["apply_mla_qkv_lora_rescale"]
    entry = [c for c in lib.load_json(lib.MANIFEST)["configs"]
             if c["name"] == "dots3_note_prev"][0]
    assert set(entry["reduced"]) == REDUCED
    assert entry["source"] == CFG["source"]


def test_the_symbol_holds_the_parameters_the_cut_counted():
    """ISSUE 59's arithmetic: a full layer with 16 heads 33.4 M (the
    indexer whole 9.37 M), a window layer with 8 heads 20.8 M, an expert
    layer's feed-forward 213.6 M, layer 0's SwiGLU at 1,728 columns 26.5
    M, embedding + head 194.6 M: 1,205 M parameters."""
    sym = lib.resolve(CFG["factory"])(CFG, **CFG["kwargs"])
    shapes, _, _ = sym.infer_shape(data=(1, 4096), softmax_label=(1, 4096))
    sizes = {n: int(np.prod(s)) for n, s in zip(sym.list_arguments(), shapes)
             if n not in ("data", "softmax_label")}

    def layer(i, *only):
        return sum(v for k, v in sizes.items()
                   if k.startswith("layer%d_" % i)
                   and any(o in k for o in only or ("",)))

    attn = ("q_a_", "q_b_", "kv_a_", "attn_latent", "attn_up", "attn_gate",
            "o_proj", "index_")
    indexer = 1024 * 64 * 128 + 5120 * 128 + 5120 * 64 + 2 * 128
    assert layer(0, "index_") == indexer == pytest.approx(9.37e6, rel=1e-3)
    full = (5120 * 1024 + 1024 + 1024 * 16 * 192 + 5120 * 576 + 512
            + 512 * 16 * 256 + 16 * 128 * 5120 + 5120 * 16 + indexer)
    assert layer(0, *attn) == layer(1, *attn) == full
    assert full == pytest.approx(33.4e6, rel=2e-3)
    window = (5120 * 1024 + 1024 + 1024 * 8 * 256 + 5120 * 1088 + 1024
              + 1024 * 8 * 320 + 8 * 128 * 5120 + 5120 * 8)
    assert layer(2, *attn) == layer(4, *attn) == window
    assert window == pytest.approx(20.8e6, rel=2e-3)
    experts = 9 * 3 * 5120 * 1536 + 5120 * 256 + 256
    assert layer(1, "moe_", "shared_") == experts
    assert experts == pytest.approx(213.6e6, rel=1e-3)
    assert layer(0, "gate_proj", "up_proj", "down_proj") \
        - sizes["layer0_attn_gate_proj_weight"] == 3 * 5120 * 1728
    assert sizes["embed_weight"] == sizes["lm_head_weight"] == 19008 * 5120
    assert sum(sizes.values()) == pytest.approx(1205e6, rel=5e-4)
    assert sum(sizes.values()) * 8 == pytest.approx(9.64e9, rel=1e-3)


def test_forward_flops_match_the_hand_count():
    """The numbers of ISSUE 59, by hand: forward 3.52 TFLOP; a training
    step three times the differentiated parts and the indexer once."""
    t = 4096
    assert FLOPS.selected_pairs(CFG) == SELECTED == sum(
        min(i + 1, 2048) for i in range(t))
    assert FLOPS.band_pairs(CFG) == sum(min(i + 1, 513) for i in range(t))
    p = FLOPS.parts(CFG)
    full_proj = 2.0 * t * (5120 * 1024 + 1024 * 16 * 192 + 5120 * 576
                           + 512 * 16 * 256 + 16 * 128 * 5120 + 5120 * 16)
    index_proj = 2.0 * t * (1024 * 64 * 128 + 5120 * 128 + 5120 * 64)
    index_scores = 2.0 * 64 * 128 * (t * (t + 1) // 2)
    assert p["full_projections"] == 2 * full_proj
    assert p["index"] == 2 * (index_proj + index_scores)
    assert (full_proj + index_proj) / 1e12 == pytest.approx(0.27, abs=0.005)
    assert index_scores / 1e12 == pytest.approx(0.137, abs=0.001)
    assert p["select_pairs"] == 2 * 2.0 * 16 * (192 + 128) * SELECTED
    assert p["select_pairs"] / 2e12 == pytest.approx(0.064, abs=0.001)
    full = p["full_projections"] + p["index"] + p["select_pairs"]
    assert full / 1e12 == pytest.approx(0.95, abs=0.005)
    window_proj = 2.0 * t * (5120 * 1024 + 1024 * 8 * 256 + 5120 * 1088
                             + 1024 * 8 * 320 + 8 * 128 * 5120 + 5120 * 8)
    assert p["window_projections"] == 3 * window_proj
    assert p["window_pairs"] == 3 * 2.0 * 8 * (256 + 128) * 1969920
    assert (p["window_projections"] + p["window_pairs"]) / 1e12 \
        == pytest.approx(0.55, abs=0.005)
    assert p["dense"] == 2.0 * t * 3 * 5120 * 1728
    assert FLOPS.expected_share_rows(CFG) == 1024
    assert p["experts"] == 4 * 2.0 * (t * 3 * 5120 * 1536 + t * 5120 * 256
                                      + 1024 * 3 * 5120 * 1536)
    assert (p["dense"] + p["experts"]) / 1e12 == pytest.approx(1.22, abs=0.01)
    assert p["head"] / 1e12 == pytest.approx(0.80, abs=0.005)
    forward = FLOPS.true_forward_flops_per_sample(CFG)
    assert forward == sum(p.values())
    assert forward / 1e12 == pytest.approx(3.52, abs=0.005)
    # the indexer has no backward: 9.71 TFLOP a step, not 3 x 3.52
    step = FLOPS.train_flops_per_sample(CFG)
    assert step == 3 * (forward - p["index"]) + p["index"]
    assert step / 1e12 == pytest.approx(9.71, abs=0.01)
    assert FLOPS.forward_flops_per_sample(CFG) * FLOPS.TRAIN_MULTIPLIER \
        == pytest.approx(step)
    assert 1e3 * step / 197e12 == pytest.approx(49.3, abs=0.1)   # ms


def test_forward_flops_at_the_rehearsal_size_match_a_hand_count():
    tiny = lib.merge(CFG, lib.load_json(lib.find(
        "tests/rehearsal", CELL, ".json"))["config"])
    t = 128
    assert FLOPS.full_layers(tiny) == 2 and FLOPS.window_layers(tiny) == 1
    assert FLOPS.expert_layers(tiny) == 2
    assert FLOPS.selected_pairs(tiny) == sum(min(i + 1, 48)
                                             for i in range(t))
    assert FLOPS.band_pairs(tiny) == sum(min(i + 1, 33) for i in range(t))
    assert FLOPS.attn_select_flops(tiny) \
        == 2.0 * 4 * 40 * FLOPS.selected_pairs(tiny)
    assert FLOPS.index_score_flops(tiny) \
        == 2.0 * 4 * 16 * (t * (t + 1) // 2)
    assert FLOPS.expected_share_rows(tiny) == t * 3 * 4 / 16.0
    assert FLOPS.dense_flops(tiny) == 2.0 * t * 3 * 64 * 48


def test_the_two_copies_of_the_reference_are_one_text():
    import mxnet_tpu.models.dots3_reference as theirs

    with open(lib.find("reference", CFG["reference"], ".py")) as ours, \
            open(theirs.__file__) as program:
        assert ours.read() == program.read()
    ref = lib.load_module("reference", CFG["reference"])
    assert ref.expert_layers(CFG) == [False] + [True] * 4


# scope paths as the step compiled for the chip names them
SCOPES = {
    "fusion.1": "jit(step)/fwd_bwd/jvp(attn/layer0_index)/index/dot_general:",
    "fusion.2": "jit(step)/fwd_bwd/attn/layer1_index/index/topk/while/"
                "body/reduce_sum:",
    "fusion.3": "jit(step)/fwd_bwd/jvp(attn/layer0_attn)/select/"
                "flash2sel_fwd_bf16_q1024_k1024/pallas_call:",
    "fusion.4": "jit(step)/fwd_bwd/transpose(jvp(attn/layer1_attn))/select/"
                "flash2sel_bwd_bf16_q1024_k1024/pallas_call:",
    "fusion.5": "jit(step)/fwd_bwd/jvp(attn/layer2_attn)/window/"
                "flash_fwd_bf16_q1024_k1024_w513/pallas_call:",
    "fusion.6": "jit(step)/fwd_bwd/transpose(jvp(attn/layer3_attn))/gate/"
                "mul:",
    "fusion.7": "jit(step)/fwd_bwd/jvp(attn/layer0_attn)/latent/"
                "dot_general:",
    "fusion.8": "jit(step)/fwd_bwd/jvp(fc/layer0_q_a_proj)/dot_general:",
    "fusion.9": "jit(step)/fwd_bwd/transpose(jvp(fc/layer2_attn_gate_proj))/"
                "dot_general:",
    "fusion.10": "jit(step)/fwd_bwd/jvp(norm/layer4_q_a_norm)/mul:",
    "fusion.11": "jit(step)/fwd_bwd/jvp(act/layer0_q_a_scale)/mul:",
    "fusion.12": "jit(step)/fwd_bwd/jvp(fc/layer0_gate_proj)/dot_general:",
    "fusion.13": "jit(step)/fwd_bwd/jvp(fc/layer1_shared_gate_proj)/"
                 "dot_general:",
    "fusion.14": "jit(step)/fwd_bwd/jvp(norm/layer0_attn_norm)/mul:",
    "fusion.15": "jit(step)/fwd_bwd/jvp(attn/layer0_attn)/full/"
                 "flash2_fwd_bf16_q1024_k1024_e512/pallas_call:",
    "fusion.16": "jit(step)/fwd_bwd/jvp(fc/layer4_o_proj)/dot_general:",
}


def test_the_table_files_the_indexer_the_kernels_and_what_stands_round():
    assert {k: dots3_scopes.part_of(v) for k, v in SCOPES.items()} == {
        "fusion.1": "index", "fusion.2": "index_topk", "fusion.3": "select",
        "fusion.4": "select", "fusion.5": "window", "fusion.6": "gate",
        "fusion.7": "latent", "fusion.8": "proj", "fusion.9": "proj",
        "fusion.10": "proj", "fusion.11": "proj", "fusion.12": None,
        "fusion.13": None, "fusion.14": None, "fusion.15": None,
        "fusion.16": "proj"}
    assert list(dots3_scopes.TABLE) == [
        "index_topk", "index", "select", "window", "gate", "latent", "proj"]


def test_the_reduction_sums_the_parts_and_needs_a_selection():
    import reduce_trace

    ops = [("fusion.%d" % i, 1000 * i, 100) for i in range(1, 17)]
    raw = {"host": [(0, reduce_trace.SLICE_BEGIN, 0, 10),
                    (0, reduce_trace.SLICE_END, 30000, 10)],
           "devices": {0: {"ops": ops}}}
    red = dots3_scopes.reduce(raw, {0: SCOPES})
    assert red["index"] == red["index_topk"] == red["window"] \
        == red["gate"] == red["latent"] == pytest.approx(100e-9)
    assert red["select"] == pytest.approx(200e-9)
    assert red["proj"] == pytest.approx(500e-9)
    # a latent-attention model without an indexer or a selection (Kanana,
    # Kimi Linear): nothing, whatever its projections are named
    rest = {k: v for k, v in SCOPES.items()
            if "/index/" not in v and "/select/" not in v}
    assert dots3_scopes.reduce(raw, {0: rest}) is None
    assert dots3_scopes.reduce(dict(raw, host=[]), {0: SCOPES}) is None


COUNTS = [[100] * 8 + [128] * 248, [140] * 8 + [128] * 248] + [
    [128] * 256] * 2                    # 4 expert layers over 256 experts


def _run(**over):
    run = {"cfg": CFG, "cell": {"name": CELL}, "trace_steps": 5,
           "batch": 1, "chips": 1, "flops_multiplier": 3,
           "peak": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
           "expert_counts": COUNTS, "keys_selected": [[SELECTED]] * 2,
           "dots3_scopes": {"index_topk": 0.010, "index": 0.020,
                            "select": 0.030, "window": 0.015,
                            "gate": 0.005, "latent": 0.025, "proj": 0.060},
           "mla_scopes": {"mla": None, "latent": None, "full": None,
                          "shared": 0.040},
           "lm_scopes": {"class_s": {"attn": 0.340, "moe": 0.110,
                                     "norm": 0.062, "embed": 0.010},
                         "head_loss_s": 0.120, "moe_part_s": {}}}
    run.update(over)
    return run


def _read(name, run, trace=True):
    return lib.load_module("layer_metrics", name).compute(
        {"devices": {}} if trace else None, {"telemetry": {}}, run)


TRACE_READERS = ["dots3_index_device_ms", "dots3_index_topk_device_ms",
                 "dots3_attn_select_device_ms",
                 "dots3_attn_select_roofline_share",
                 "dots3_attn_window_device_ms",
                 "dots3_attn_window_roofline_share",
                 "dots3_attn_proj_device_ms", "moe_share_device_ms",
                 "shared_expert_device_ms"]
READERS = TRACE_READERS + ["moe_share_rows_over_expected",
                           "dots3_keys_selected_over_expected"]
# every share's entries since PR 68 (``dots3_moe_device_ms``, routed +
# shared, and ``dots3_held_rows_over_expected`` until then)
SHARED = ["moe_share_device_ms", "shared_expert_device_ms",
          "moe_share_rows_over_expected"]


def test_the_eleven_readers_read_what_they_say():
    run = _run()
    assert _read("dots3_index_device_ms", run) == pytest.approx(6.0)
    assert _read("dots3_index_topk_device_ms", run) == pytest.approx(2.0)
    assert _read("dots3_attn_select_device_ms", run) == pytest.approx(6.0)
    assert _read("dots3_attn_window_device_ms", run) == pytest.approx(3.0)
    assert _read("dots3_attn_proj_device_ms", run) == pytest.approx(18.0)
    assert _read("moe_share_device_ms", run) == pytest.approx(22.0)
    assert _read("shared_expert_device_ms", run) == pytest.approx(8.0)
    # two full layers, three forwards each of the selected pairs x 16
    # heads x 320 multiply-adds at 197 T/s, of 6 ms
    share = _read("dots3_attn_select_roofline_share", run)
    assert share == pytest.approx(
        100 * (3 * 2 * 2.0 * 16 * 320 * SELECTED / 197e12 * 1e3) / 6.0,
        rel=1e-9)
    assert share == pytest.approx(32.71, abs=0.01) and 0 < share < 100
    share = _read("dots3_attn_window_roofline_share", run)
    assert share == pytest.approx(
        100 * (3 * 3 * 2.0 * 8 * 384 * 1969920 / 197e12 * 1e3) / 3.0,
        rel=1e-9)
    assert 0 < share < 100
    # the held experts are the first eight: (8 x 100 + 8 x 140 + 2 x
    # 1024) rows of 4 x 1024
    assert _read("moe_share_rows_over_expected", run) == pytest.approx(
        (800 + 1120 + 2048) / 4096.0)
    assert _read("moe_share_rows_over_expected", run, trace=False) \
        == pytest.approx(0.96875)               # a model output, no trace
    value, ok, why = _read("dots3_keys_selected_over_expected", run)
    assert (value, ok) == (1.0, True) and "6292480" in why
    value, ok, _ = _read("dots3_keys_selected_over_expected",
                         _run(keys_selected=[[SELECTED], [SELECTED - 1]]))
    assert value < 1.0 and not ok               # the run is not correct


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_where_there_is_nothing(name):
    """No slice, no such scopes (the parent's program, another model's),
    a configuration with another operations module: None, never zero,
    never a raise (the benchmark's files are laid over older
    checkouts)."""
    kanana = lib.load_json(lib.find("configs", "kanana_2_30b_a3b", ".json"))
    nothing = dict(dots3_scopes=None, lm_scopes=None, mla_scopes=None,
                   expert_counts=None, keys_selected=None)
    assert _read(name, _run(**nothing)) is None
    assert _read(name, _run(cfg=kanana, **nothing)) is None
    # another model's run, whatever its scopes hold: only the readers of
    # the dots3 scopes alone would read them
    assert _read(name, _run(cfg=kanana)) is None or name in [
        "dots3_index_device_ms", "dots3_index_topk_device_ms",
        "dots3_attn_select_device_ms", "dots3_attn_window_device_ms",
        "dots3_attn_proj_device_ms"] + SHARED
    if name in TRACE_READERS:
        assert _read(name, _run(), trace=False) is None
        assert _read(name, _run(trace_steps=0)) is None
    if name.endswith("roofline_share"):
        assert _read(name, _run(peak=None)) is None
    entry = [m for m in lib.load_json(lib.MANIFEST)["per_layer"]
             if m["name"] == name][0]
    assert CELL in entry["workloads"] if name in SHARED \
        else entry["workloads"] == [CELL]
    assert entry["moves"] == "train_samples_s"
    assert entry["layer"] == "ops and kernels"
    assert entry["source"] == ("device_trace" if name in TRACE_READERS
                               else "program_counter")
    assert entry["unit"] == ("%" if name.endswith("roofline_share")
                             else "ratio" if name.endswith("expected")
                             else "ms/step")
    assert entry["better"] == ("higher" if name.endswith(
        ("roofline_share", "selected_over_expected")) else "lower")


def test_the_kind_splits_the_selection_counts_off_the_models_outputs():
    kind = lib.load_module("traffic", "fit_tokens_share_select")

    class Mod:
        context = "kept"

        def get_outputs(self):
            return ["loss", "e1", "e2", "e3", "e4", "s0", "s1"]

    wrapped = kind._WithoutSelection(Mod(), 2)
    assert wrapped.get_outputs() == ["loss", "e1", "e2", "e3", "e4"]
    assert wrapped.selected == ["s0", "s1"]
    assert wrapped.context == "kept"        # everything else is the module's


def test_the_cell_follows_the_mimo_cells_mix_value_for_value():
    cell = lib.load_json(lib.find("cells", CELL, ".json"))
    mimo = lib.load_json(lib.find("cells", "mimo_v2_flash_fit_share_4k",
                                  ".json"))
    assert cell["traffic"] == "fit_tokens_share_select_resident_b1_t4096"
    assert cell["chips"] == 1
    mix = lib.load_json(lib.find("traffic", cell["traffic"], ".json"))
    theirs = lib.load_json(lib.find("traffic", mimo["traffic"], ".json"))
    assert mix["kind"] == "fit_tokens_share_select"
    assert {k: v for k, v in mix.items() if k != "kind"} \
        == {k: v for k, v in theirs.items() if k != "kind"}
    assert (mix["batch"], mix["check_last_positions"]) == (1, 256)
    # the kinds under this one read these names of the configuration
    for key in ("n_routed_experts", "first_k_dense_replace",
                "moe_layer_freq", "num_experts_per_tok", "num_hidden_layers",
                "layer_types", "index_topk", "share", "reference"):
        assert key in CFG
    assert set(cell["expect"]["reference"]) == set(
        mimo["expect"]["reference"]) | {"select_near_tie_eps",
                                        "select_near_tie_share_max"}
    # half the variance of logits from a Normal(0.02) head over a
    # unit-rms vector of 5120
    assert cell["expect"]["first_loss_excess"] == pytest.approx(
        0.5 * 5120 * 0.02 ** 2)
    assert len(cell["why"]) <= 200 and cell["who"] and cell["distorts"]
    manifest = lib.load_json(lib.MANIFEST)
    entry = [w for w in manifest["workloads"] if w["name"] == CELL][0]
    assert entry == {"name": CELL, "config": "dots3_note_prev",
                     "traffic": cell["traffic"], "chips": 1,
                     "why": cell["why"]}
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    assert len(manifest["workloads"]) >= 13


def test_rehearsal_runs_the_cell_end_to_end_with_the_trace_on():
    """The whole path at a tiny size on the CPU: from_config, Module.fit
    through the fused step (two full layers choosing 48 of 128 keys, one
    under a window of 33), the share kind's checks and this kind's two,
    the reference check in float32 (where the program and the reference
    agree to summation order, and the bf16 reference does not) and every
    reader returning nothing or a value without a raise."""
    proc = run_bench(["--workload", CELL, "--seed", "3000000019",
                      "--seconds", "1", "--trace", "1", "--rehearse-cpu"])
    result = check_rehearsal(proc, ["fused_step_share",
                                    "fit_lookahead_share",
                                    "moe_share_rows_over_expected",
                                    "dots3_keys_selected_over_expected"])
    assert "matches_reference ok=True" in proc.stdout
    assert '"within_limits": false' in proc.stdout
    assert "experts_routed_over_all ok=True" in proc.stdout
    assert "2 expert layers of 2" in proc.stdout
    assert "keys_selected_exact ok=True" in proc.stdout
    assert "2 full layers of 2" in proc.stdout
    assert "selection_ties_bounded ok=True" in proc.stdout
    assert "dots3_keys_selected_over_expected ok=True" in proc.stdout
    assert "held_rows_near_expected ok=True" in proc.stdout
    assert "first_loss_near_expected ok=True" in proc.stdout
    assert "window_compiles=0" in proc.stdout
    assert not set(TRACE_READERS) & set(result["metrics"])  # no device
