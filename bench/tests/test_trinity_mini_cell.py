"""What the ``trinity_mini`` configuration brought: its file against the
published keys, the parameters the cut counted, its operations against
the hand count (at the cell's size and at the rehearsal's), the
benchmark's copy of the reference against the program's, the table of
``afmoe_scopes`` on scope paths, the eight readers on handed-in
reductions, the cell beside the one whose mix it shares, and the cell's
rehearsal end to end."""
import pytest

import afmoe_scopes
import lib
from helpers import check_rehearsal, run_bench

CFG = lib.load_json(lib.find("configs", "trinity_mini", ".json"))
CELL = "trinity_mini_fit_share_8k"
S, F = "sliding_attention", "full_attention"
# arcee-ai/Trinity-Mini's config.json, the keys that say its shape (the
# model-configs catalog's ``config``)
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "layer_types": [S, S, S, F] * 8, "load_balance_coeff": 0.001,
    "max_position_embeddings": 131072, "model_type": "afmoe",
    "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
    "num_attention_heads": 32, "num_dense_layers": 2,
    "num_expert_groups": 1, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 32, "num_key_value_heads": 4,
    "num_limited_groups": 1, "num_shared_experts": 1, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
    "route_scale": 2.826, "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192}
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "head_dim", "num_attention_heads", "num_key_value_heads",
          "sliding_window", "num_experts_per_tok", "num_shared_experts",
          "route_scale")


def test_configuration_keeps_every_published_width_and_states_its_cut():
    changed = {k for k, v in PUBLISHED.items() if CFG[k] != v}
    assert changed == set(CFG["reduced"]) == set(CFG["reduced_why"]) == {
        "num_hidden_layers", "layer_types", "num_dense_layers",
        "num_experts", "vocab_size"}
    assert not changed & set(WIDTHS)
    for key in changed:           # the uncut count stands beside the held
        assert CFG["published"][key] == PUBLISHED[key]
    # the floors: the leading dense layers once, then a whole period and
    # four expert layers, at least 8 experts, an eighth of the vocabulary
    assert CFG["layer_types"] == PUBLISHED["layer_types"][:5] == [
        S, S, S, F, S]
    assert CFG["num_hidden_layers"] == 5 and CFG["num_dense_layers"] == 1
    assert sorted(CFG["layer_types"][1:]) == sorted([S, S, S, F])
    assert CFG["num_experts"] * 8 == PUBLISHED["num_experts"]
    assert CFG["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    expected = 8192 * 8 * CFG["num_experts"] // 128
    assert CFG["share"]["experts_of"] == 128
    assert CFG["share"]["expert_offset"] == 0
    assert CFG["share"]["share_rows_bound"] in (2 * expected, 3 * expected)
    assert "%d chips share each layer" % (128 // CFG["num_experts"]) \
        in CFG["deployment"]
    assert CFG["kwargs"] == {"seq_len": 8192, "dtype": "bfloat16"}
    assert CFG["input_shape"][2] == 8192
    assert CFG["num_classes"] == CFG["vocab_size"]
    for topic in ("gate", "head_norms", "rotation", "window", "block",
                  "mup", "embedding", "router", "shared_experts", "unread",
                  "weights", "dtype", "optimizer", "objective",
                  "share_rows_bound"):
        assert CFG["assumed"][topic]
    manifest = [c for c in lib.load_json(lib.MANIFEST)["configs"]
                if c["name"] == "trinity_mini"][0]
    assert manifest["reduced"] == CFG["reduced"]
    assert manifest["source"] == CFG["source"]
    assert manifest["file"] == "bench/configs/trinity_mini.json"


def test_the_symbol_holds_the_parameters_the_cut_counted():
    """ISSUE 55's arithmetic: attention 27.26 M a layer with its gate
    projection (q, gate and o 8.39 M each, k and v 1.05 M each, two
    gammas of 128), four norms of 2048; the dense SwiGLU 3 x 2048 x 6144
    = 37.75 M; an expert layer 16 x 6.291 M + shared 6.291 M + router
    0.262 M + 128 bias values; embedding and head 2 x 25024 x 2048 =
    102.5 M: 705.5 M."""
    import numpy as np

    sym = lib.resolve(CFG["factory"])(CFG, **CFG["kwargs"])
    shapes, _, _ = sym.infer_shape(data=(1, 8192), softmax_label=(1, 8192))
    sizes = {n: int(np.prod(s)) for n, s in zip(sym.list_arguments(), shapes)
             if n not in ("data", "softmax_label")}
    for i in range(5):
        p = "layer%d_" % i
        assert sizes[p + "q_proj_weight"] == sizes[p + "o_proj_weight"] \
            == sizes[p + "attn_gate_proj_weight"] == 2048 * 4096
        assert sizes[p + "k_proj_weight"] == sizes[p + "v_proj_weight"] \
            == 2048 * 512
        assert sizes[p + "q_norm_gamma"] == sizes[p + "k_norm_gamma"] == 128
        for norm in ("attn_norm", "attn_post_norm", "ffn_norm",
                     "ffn_post_norm"):
            assert sizes[p + norm + "_gamma"] == 2048
    assert sizes["layer0_gate_proj_weight"] == 2048 * 6144
    assert "layer0_moe_gate_weight" not in sizes
    assert sizes["layer1_moe_gate_weight"] == 2048 * 128
    assert sizes["layer1_moe_gate_up_weight"] == 16 * 2048 * 2048
    assert sizes["layer1_moe_down_weight"] == 16 * 1024 * 2048
    assert sizes["layer1_moe_select_bias"] == 128
    assert sizes["layer1_shared_gate_proj_weight"] == 1024 * 2048
    assert sizes["embed_weight"] == sizes["lm_head_weight"] == 25024 * 2048

    def layer(i, only=""):
        return sum(v for k, v in sizes.items()
                   if k.startswith("layer%d_" % i) and only in k)

    attention = 3 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128
    assert attention == pytest.approx(27.26e6, rel=1e-3)
    assert layer(0) == attention + 4 * 2048 + 3 * 2048 * 6144
    assert layer(1) == layer(4) == attention + 4 * 2048 + 17 * 3 * 2048 \
        * 1024 + 2048 * 128 + 128
    assert sum(sizes.values()) == 705474304       # 5.64 GB at 8 bytes


def test_forward_flops_match_the_hand_count():
    """Per sequence of 8192, forward: the head 2 x 8192 x 2048 x 25024 =
    0.840 T; a layer's five projections 2 x 8192 x 2048 x 13312 = 0.447
    T; a window layer's scores and values over 14.68 M pairs a head 2 x
    32 x 256 x 14.68 M = 0.2405 T, the full layer's over 33.56 M 0.5498
    T; the dense SwiGLU 0.618 T; an expert layer's shared expert 0.103 T
    and its routed part 2 x (8192 x 2048 x 128 + 8192 x 3 x 2048 x 1024)
    = 0.1074 T. 18.14 T a training step."""
    fn = lib.load_module("flops", CFG["flops"])
    t, d = 8192, 2048
    assert (fn.window_layers(CFG), fn.full_layers(CFG),
            fn.expert_layers(CFG)) == (4, 1, 4)
    pairs = 2048 * 2049 / 2.0 + (t - 2048) * 2048
    assert fn.window_pairs(CFG) == pairs == 14681088
    assert pairs / (t * (t + 1) / 2.0) == pytest.approx(0.4375, abs=1e-3)
    assert fn.attn_window_flops(CFG) == 2.0 * 32 * pairs * 256
    assert fn.attn_full_flops(CFG) == 2.0 * 32 * 256 * t * (t + 1) / 2
    assert fn.projection_flops(CFG) == 2.0 * t * d * (3 * 4096 + 2 * 512)
    assert fn.shared_expert_flops(CFG) == 2.0 * t * 3 * d * 1024
    assert fn.expected_share_rows(CFG) == 8192
    assert fn.moe_share_flops(CFG) == 2.0 * (t * d * 128
                                             + 8192 * 3 * d * 1024)
    assert fn.moe_share_flops(CFG, rows=0) == 2.0 * t * d * 128
    want = (2.0 * t * d * 25024 + 5 * fn.projection_flops(CFG)
            + 4 * fn.attn_window_flops(CFG) + fn.attn_full_flops(CFG)
            + 2.0 * t * 3 * d * 6144
            + 4 * (fn.shared_expert_flops(CFG) + fn.moe_share_flops(CFG)))
    assert fn.forward_flops_per_sample(CFG) == pytest.approx(want, rel=1e-12)
    assert 3 * want == pytest.approx(18.14e12, rel=1e-3)
    # the projections are the largest part, the attention kernels next
    assert 5 * fn.projection_flops(CFG) / want == pytest.approx(0.369,
                                                                abs=0.002)
    assert (4 * fn.attn_window_flops(CFG) + fn.attn_full_flops(CFG)) / want \
        == pytest.approx(0.250, abs=0.002)
    # a window layer's pair on the v5e's bf16 peak: 3.66 ms a step
    peaks = lib.load_json(lib.BENCH + "/peaks.json")["TPU v5 lite"]
    assert 3e3 * fn.attn_window_flops(CFG) / peaks["bf16_flops"] \
        == pytest.approx(3.663, abs=0.002)
    assert fn.TRAIN_MULTIPLIER == 3


def test_forward_flops_at_the_rehearsal_size_match_a_hand_count():
    """The rehearsal's tiny model (hidden 48, SwiGLU 96, 4 heads on 2 of
    16, a window of 40, 4 of 16 experts of 32 top-3 beside a shared one,
    vocabulary 512, T 160; sliding x3, full, sliding with one dense
    layer), by hand."""
    fn = lib.load_module("flops", CFG["flops"])
    tiny = lib.load_json(lib.BENCH + "/tests/rehearsal/%s.json" % CELL)
    cfg = lib.merge(CFG, tiny["config"])
    t = 160
    head = 2 * t * 48 * 512
    proj = 2 * t * 48 * (3 * 4 + 2 * 2) * 16
    window = 2 * 4 * 2 * 16 * (40 * 41 // 2 + (t - 40) * 40)
    full = 2 * 4 * 2 * 16 * (t * (t + 1) // 2)
    dense = 2 * t * 3 * 48 * 96
    rows = t * 3 * 4 / 16.0
    moe = 2 * (t * 48 * 16 + rows * 3 * 48 * 32) + 2 * t * 3 * 48 * 32
    assert fn.expected_share_rows(cfg) == rows
    assert fn.forward_flops_per_sample(cfg) == head + 5 * proj \
        + 4 * window + full + dense + 4 * moe


def test_the_two_copies_of_the_reference_are_one_text():
    import mxnet_tpu.models.afmoe_reference as theirs

    with open(lib.find("reference", CFG["reference"], ".py")) as ours, \
            open(theirs.__file__) as program:
        assert ours.read() == program.read()
    ref = lib.load_module("reference", CFG["reference"])
    assert ref.expert_layers(CFG) == [False] + [True] * 4


# scope paths as the step compiled for the chip names them
SCOPES = {
    "fusion.1": "jit(step)/fwd_bwd/jvp(attn/layer0_attn)/gate/mul:",
    "fusion.2": "jit(step)/fwd_bwd/transpose(jvp(attn/layer3_attn))/gate/"
                "mul:",
    "fusion.3": "jit(step)/fwd_bwd/jvp(attn/layer0_attn)/window/"
                "flash_fwd_bf16_q1024_k1024_w2048/pallas_call:",
    "fusion.4": "jit(step)/fwd_bwd/transpose(jvp(attn/layer3_attn))/full/"
                "flash_bwd_bf16_q1024_k1024/pallas_call:",
    "fusion.5": "jit(step)/fwd_bwd/jvp(fc/layer0_q_proj)/dot_general:",
    "fusion.6": "jit(step)/fwd_bwd/transpose(jvp(fc/layer2_attn_gate_proj))/"
                "dot_general:",
    "fusion.7": "jit(step)/fwd_bwd/jvp(fc/layer4_o_proj)/dot_general:",
    "fusion.8": "jit(step)/fwd_bwd/jvp(fc/layer0_gate_proj)/dot_general:",
    "fusion.9": "jit(step)/fwd_bwd/jvp(fc/layer1_shared_gate_proj)/"
                "dot_general:",
    "fusion.10": "jit(step)/fwd_bwd/jvp(norm/layer0_q_norm)/mul:",
    "fusion.11": "jit(step)/fwd_bwd/transpose(jvp(norm/"
                 "layer1_ffn_post_norm))/mul:",
    "fusion.12": "jit(step)/fwd_bwd/jvp(norm/final_norm)/mul:",
    "fusion.13": "jit(step)/fwd_bwd/jvp(attn/layer0_q_rope)/mul:",
    "fusion.14": "jit(step)/fwd_bwd/jvp(act/embed_scale)/mul:",
    "fusion.15": "jit(step)/fwd_bwd/jvp(fc/layer0_kda_g_a_proj)/"
                 "dot_general:",
    "fusion.16": "jit(step)/fwd_bwd/jvp(gdn/layer0_kda)/gate_norm/mul:",
}


def test_the_table_files_the_gate_the_projections_and_the_norms():
    assert {k: afmoe_scopes.part_of(v) for k, v in SCOPES.items()} == {
        "fusion.1": "gate", "fusion.2": "gate", "fusion.3": None,
        "fusion.4": None, "fusion.5": "attn_proj", "fusion.6": "attn_proj",
        "fusion.7": "attn_proj", "fusion.8": None, "fusion.9": None,
        "fusion.10": "norm", "fusion.11": "norm", "fusion.12": None,
        "fusion.13": None, "fusion.14": None, "fusion.15": None,
        "fusion.16": None}
    assert list(afmoe_scopes.TABLE) == ["gate", "attn_proj", "norm"]
    # the kernels' scopes are share_scopes': window and full
    import share_scopes

    assert share_scopes._KIND.search(SCOPES["fusion.3"]).group(1) == "window"
    assert share_scopes._KIND.search(SCOPES["fusion.4"]).group(1) == "full"
    assert not share_scopes._KIND.search(SCOPES["fusion.1"])


def test_the_reduction_sums_the_parts_and_needs_a_gate():
    import reduce_trace

    ops = [("fusion.%d" % i, 1000 * i, 100) for i in range(1, 17)]
    raw = {"host": [(0, reduce_trace.SLICE_BEGIN, 0, 10),
                    (0, reduce_trace.SLICE_END, 30000, 10)],
           "devices": {0: {"ops": ops}}}
    red = afmoe_scopes.reduce(raw, {0: SCOPES})
    assert red["gate"] == red["norm"] == pytest.approx(200e-9)
    assert red["attn_proj"] == pytest.approx(300e-9)
    # projections and norms named alike in a model without a gated
    # attention node: nothing
    rest = {k: v for k, v in SCOPES.items() if "/gate/" not in v}
    assert afmoe_scopes.reduce(raw, {0: rest}) is None
    assert afmoe_scopes.reduce(dict(raw, host=[]), {0: SCOPES}) is None


COUNTS = [[500] * 16 + [512] * 112, [520] * 16 + [512] * 112] + [
    [512] * 128] * 2                    # 4 expert layers over 128 experts


def _run(**over):
    run = {"cfg": CFG, "cell": {"name": CELL}, "trace_steps": 5,
           "batch": 1, "chips": 1, "flops_multiplier": 3,
           "peak": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
           "expert_counts": COUNTS,
           "afmoe_scopes": {"gate": 0.025, "attn_proj": 0.225,
                            "norm": 0.060},
           "share_scopes": {"window": 0.200, "full": 0.090},
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


TRACE_READERS = ["trinity_attn_window_device_ms",
                 "trinity_attn_window_roofline_share",
                 "trinity_attn_full_device_ms",
                 "trinity_attn_gate_device_ms",
                 "trinity_attn_proj_device_ms", "trinity_norm_device_ms",
                 "moe_share_device_ms", "shared_expert_device_ms"]
READERS = TRACE_READERS + ["moe_share_rows_over_expected"]
# every share's entries since PR 68 (``trinity_moe_device_ms``, routed +
# shared, and ``trinity_held_rows_over_expected`` until then)
SHARED = READERS[-3:]


def test_the_nine_readers_read_what_they_say():
    run = _run()
    assert _read("trinity_attn_window_device_ms", run) == pytest.approx(40.0)
    assert _read("trinity_attn_full_device_ms", run) == pytest.approx(18.0)
    assert _read("trinity_attn_gate_device_ms", run) == pytest.approx(5.0)
    assert _read("trinity_attn_proj_device_ms", run) == pytest.approx(45.0)
    assert _read("trinity_norm_device_ms", run) == pytest.approx(12.0)
    assert _read("moe_share_device_ms", run) == pytest.approx(22.0)
    assert _read("shared_expert_device_ms", run) == pytest.approx(8.0)
    # four layers, three forwards each of 0.2405 T at 197 T/s, of 40 ms
    share = _read("trinity_attn_window_roofline_share", run)
    assert share == pytest.approx(
        100 * (3 * 4 * 2.0 * 32 * 14681088 * 256 / 197e12 * 1e3) / 40.0,
        rel=1e-9)
    assert share == pytest.approx(36.63, abs=0.01) and 0 < share < 100
    # the held experts are the first sixteen: (16 x 500 + 16 x 520 + 2 x
    # 8192) rows of 4 x 8192
    assert _read("moe_share_rows_over_expected", run) == pytest.approx(
        (8000 + 8320 + 2 * 8192) / 32768.0)
    assert _read("moe_share_rows_over_expected", run, trace=False) \
        == pytest.approx(0.99805, abs=1e-5)      # a model output, no trace


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_where_there_is_nothing(name):
    """No slice, no such scopes (the parent's program, another model's),
    a configuration with another operations module: None, never zero,
    never a raise (the benchmark's files are laid over older
    checkouts)."""
    mimo = lib.load_json(lib.find("configs", "mimo_v2_flash", ".json"))
    nothing = dict(afmoe_scopes=None, share_scopes=None, lm_scopes=None,
                   mla_scopes=None, expert_counts=None)
    assert _read(name, _run(**nothing)) is None
    assert _read(name, _run(cfg=mimo, **nothing)) is None
    # another model's run, whatever its scopes hold: only the readers of
    # the afmoe scopes alone would read them (MiMo's window and full
    # kernels are its own metrics')
    assert _read(name, _run(cfg=mimo)) is None or name in [
        "trinity_attn_gate_device_ms", "trinity_attn_proj_device_ms",
        "trinity_norm_device_ms"] + SHARED
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


def test_the_cell_shares_the_lfm2_cells_mix_letter_for_letter():
    cell = lib.load_json(lib.find("cells", CELL, ".json"))
    lfm2 = lib.load_json(lib.find("cells", "lfm2_fit_share_8k", ".json"))
    assert cell["traffic"] == lfm2["traffic"] \
        == "fit_tokens_share_keys_resident_b1_t8192"
    assert cell["chips"] == 1
    mix = lib.load_json(lib.find("traffic", cell["traffic"], ".json"))
    assert mix["kind"] == "fit_tokens_share_keys"
    assert (mix["batch"], mix["check_last_positions"]) == (1, 4096)
    assert mix["optimizer_params"] == {"learning_rate": 0.01,
                                       "momentum": 0.9}
    # the kind reads these names of the configuration
    for key in ("num_experts", "num_dense_layers", "num_experts_per_tok",
                "num_hidden_layers", "share", "reference"):
        assert key in CFG
    assert set(cell["expect"]["reference"]) == set(
        lfm2["expect"]["reference"])
    # half the variance of logits from a Normal(0.02) head over a
    # unit-rms vector of 2048
    assert cell["expect"]["first_loss_excess"] == pytest.approx(
        0.5 * 2048 * 0.02 ** 2)
    assert len(cell["why"]) <= 200
    manifest = lib.load_json(lib.MANIFEST)
    entry = [w for w in manifest["workloads"] if w["name"] == CELL][0]
    assert entry == {"name": CELL, "config": "trinity_mini",
                     "traffic": cell["traffic"], "chips": 1,
                     "why": cell["why"]}
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1


def test_rehearsal_runs_the_cell_end_to_end_with_the_trace_on():
    """The whole path at a tiny size on the CPU: from_config, Module.fit
    through the fused step (T 160 under a window of 40: the attention
    dispatch's flash branch), the share kind's checks, the reference
    check in float32 (where the program and the reference agree to
    summation order, and the bf16 reference does not) and every reader
    returning nothing or a value without a raise."""
    proc = run_bench(["--workload", CELL, "--seed", "3000000019",
                      "--seconds", "1", "--trace", "1", "--rehearse-cpu"])
    result = check_rehearsal(proc, ["fused_step_share",
                                    "fit_lookahead_share",
                                    "moe_share_rows_over_expected"])
    assert "matches_reference ok=True" in proc.stdout
    assert '"within_limits": false' in proc.stdout
    assert "experts_routed_over_all ok=True" in proc.stdout
    assert "4 expert layers of 4" in proc.stdout
    assert "held_rows_near_expected ok=True" in proc.stdout
    assert "first_loss_near_expected ok=True" in proc.stdout
    assert "window_compiles=0" in proc.stdout
    assert not set(TRACE_READERS) & set(result["metrics"])  # no device
