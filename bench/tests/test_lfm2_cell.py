"""What the ``lfm2_24b_a2b`` configuration brought: its file against the
published keys, the parameters the cut counted, its operations and bytes
against the hand count (at the cell's size and at the rehearsal's), the
benchmark's copy of the reference against the program's, the table of
``sconv_scopes`` on scope paths, the seven readers on handed-in
reductions, the new traffic kind's parts, and the cell's rehearsal end to
end."""
import pytest

import lib
import sconv_scopes
from helpers import check_rehearsal, run_bench

CFG = lib.load_json(lib.find("configs", "lfm2_24b_a2b", ".json"))
CELL = "lfm2_fit_share_8k"
C, F = "conv", "full_attention"
# LiquidAI/LFM2-24B-A2B's config.json, the keys that say its shape (the
# model-configs catalog's ``config``)
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776, "layer_types": [C, C, F, C] * 10,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536}
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "num_attention_heads", "num_key_value_heads", "conv_L_cache",
          "num_experts_per_tok", "routed_scaling_factor")


def test_configuration_keeps_every_published_width_and_states_its_cut():
    changed = {k for k, v in PUBLISHED.items() if CFG[k] != v}
    assert changed == set(CFG["reduced"]) == set(CFG["reduced_why"]) == {
        "num_hidden_layers", "num_dense_layers", "layer_types",
        "num_experts", "vocab_size"}
    assert not changed & set(WIDTHS)
    for key in changed:           # the uncut count stands beside the held
        assert CFG["published"][key] == PUBLISHED[key]
    # the floors: the leading dense layers once, then whole periods and
    # at least four sparse layers, 8 experts, an eighth of the vocabulary
    kept = [0] + list(range(2, 10))
    assert CFG["layer_types"] == [PUBLISHED["layer_types"][i] for i in kept]
    assert CFG["layer_types"][1:] == [F, C, C, C] * 2
    assert CFG["num_hidden_layers"] == 9 and CFG["num_dense_layers"] == 1
    assert PUBLISHED["layer_types"].count(F) == 10
    assert CFG["num_experts"] == 8
    assert CFG["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    # twice the rows the held experts expect; 64 / 8 chips a layer
    assert CFG["share"] == {"experts_of": 64, "expert_offset": 0,
                            "share_rows_bound": 2 * 8192 * 4 * 8 // 64}
    assert "8 chips share each layer" in CFG["deployment"]
    assert CFG["kwargs"] == {"seq_len": 8192, "dtype": "bfloat16"}
    assert CFG["input_shape"][2] == 8192
    assert CFG["num_classes"] == CFG["vocab_size"]
    assert "head_dim" not in CFG         # the catalog's row gives none
    for topic in ("head_dim", "tie_word_embeddings", "block", "conv",
                  "conv_weight", "attention", "experts", "expert_bias",
                  "weights", "dtype", "optimizer", "objective", "depth"):
        assert CFG["assumed"][topic]
    assert "No activation anywhere" in CFG["assumed"]["conv"]
    assert "THEN RoPE" in CFG["assumed"]["attention"]
    assert "+ 1e-6" in CFG["assumed"]["experts"]
    assert "embedding Normal(0.02)" in CFG["assumed"]["weights"]
    manifest = [c for c in lib.load_json(lib.MANIFEST)["configs"]
                if c["name"] == "lfm2_24b_a2b"][0]
    assert manifest["reduced"] == CFG["reduced"]
    assert manifest["source"] == CFG["source"]


def test_the_symbol_holds_the_parameters_the_cut_counted():
    """ISSUE 46's arithmetic: a conv layer's operator 16.78 M (in_proj
    2048 x 6144 = 12.58 M, out_proj 4.19 M, taps 6 k), an attention
    layer's 10.49 M (q and o 4.19 M each, k and v 1.05 M each, two
    gammas of 64); the dense SwiGLU 3 x 2048 x 11776 = 72.35 M; a sparse
    feed-forward 75.63 M (router 0.13 M, 8 experts of 3 x 2048 x 1536 =
    9.44 M, 64 bias values); the one tied matrix 8192 x 2048 = 16.78 M:
    832.6 M."""
    import numpy as np

    sym = lib.resolve(CFG["factory"])(CFG, **CFG["kwargs"])
    shapes, _, _ = sym.infer_shape(data=(1, 8192), softmax_label=(1, 8192))
    sizes = {n: int(np.prod(s)) for n, s in zip(sym.list_arguments(), shapes)
             if n not in ("data", "softmax_label")}
    assert sizes["layer0_conv_in_proj_weight"] == 2048 * 6144
    assert sizes["layer0_conv_out_proj_weight"] == 2048 * 2048
    assert sizes["layer0_conv_weight"] == 3 * 2048
    assert sizes["layer0_gate_proj_weight"] == 2048 * 11776
    assert "layer0_conv_bias" not in sizes and "layer0_moe_gate_weight" \
        not in sizes
    assert sizes["layer1_q_proj_weight"] == sizes["layer1_o_proj_weight"] \
        == 2048 * 2048
    assert sizes["layer1_k_proj_weight"] == sizes["layer1_v_proj_weight"] \
        == 2048 * 512
    assert sizes["layer1_q_norm_gamma"] == sizes["layer1_k_norm_gamma"] == 64
    assert sizes["layer1_moe_gate_weight"] == 2048 * 64
    assert sizes["layer1_moe_gate_up_weight"] == 8 * 2048 * 3072
    assert sizes["layer1_moe_down_weight"] == 8 * 1536 * 2048
    assert sizes["layer1_moe_select_bias"] == 64
    assert sizes["embed_weight"] == 8192 * 2048
    assert "lm_head_weight" not in sizes        # the head is the embedding

    def layer(i):
        return sum(v for k, v in sizes.items()
                   if k.startswith("layer%d_" % i))

    assert layer(0) == pytest.approx(89.13e6, rel=1e-3)
    assert layer(1) == pytest.approx(86.12e6, rel=1e-3)
    assert layer(2) == pytest.approx(92.41e6, rel=1e-3)
    assert sum(sizes.values()) == pytest.approx(832.6e6, rel=2e-4)


def test_forward_flops_and_bytes_match_the_hand_count():
    """Per sequence of 8192, forward: the tied head 2 x 8192 x 2048 x
    8192 = 0.275 T; a conv operator's projections 2 x 8192 x 2048 x 8192
    = 0.275 T; an attention layer's projections 2 x 8192 x 2048 x 64 x
    80 = 0.172 T and its scores and values 2 x 32 x 128 x 8192 x 8193 /
    2 = 0.275 T; the dense SwiGLU 2 x 8192 x 3 x 2048 x 11776 = 1.185 T;
    a sparse layer 2 x (8192 x 2048 x 64 + 4096 x 3 x 2048 x 1536) =
    0.0795 T. 14.74 T a training step."""
    fn = lib.load_module("flops", CFG["flops"])
    t, d = 8192, 2048
    assert [fn.layers(CFG, k) for k in (fn.CONV, fn.FULL)] == [7, 2]
    assert fn.expert_layers(CFG) == 8 and fn.head_dim(CFG) == 64
    assert fn.sconv_projection_flops(CFG) == 2.0 * t * d * 4 * d
    assert fn.sconv_bytes(CFG) == 2.0 * t * d * 4
    assert fn.sconv_bytes(CFG, backward=True) == 2.0 * t * d * 7
    assert fn.attention_projection_flops(CFG) == 2.0 * t * d * 64 * 80
    assert fn.attn64_kernel_flops(CFG) == 2.0 * 32 * 128 * t * (t + 1) / 2
    assert fn.dense_mlp_flops(CFG) == 2.0 * t * 3 * d * 11776
    assert fn.expected_share_rows(CFG) == 4096
    assert fn.moe_share_flops(CFG) == 2.0 * (t * d * 64 + 4096 * 3 * d * 1536)
    assert fn.moe_share_flops(CFG, rows=0) == 2.0 * t * d * 64
    want = (2.0 * t * d * 8192 + 7 * fn.sconv_projection_flops(CFG)
            + 2 * (fn.attention_projection_flops(CFG)
                   + fn.attn64_kernel_flops(CFG))
            + fn.dense_mlp_flops(CFG) + 8 * fn.moe_share_flops(CFG))
    assert fn.forward_flops_per_sample(CFG) == pytest.approx(want, rel=1e-12)
    assert 3 * want == pytest.approx(14.74e12, rel=1e-3)
    # the new mixer's projections are two fifths of the step's
    # operations, attention on heads of 64 a fifth with its projections
    assert 7 * fn.sconv_projection_flops(CFG) / want == pytest.approx(
        0.392, abs=0.002)
    assert 2 * fn.attn64_kernel_flops(CFG) / want == pytest.approx(
        0.112, abs=0.002)
    # the op is bound by its bytes: 0.164 ms a layer forward and 0.287
    # backward on the v5e's HBM peak
    peaks = lib.load_json(lib.BENCH + "/peaks.json")["TPU v5 lite"]
    assert 1e3 * fn.sconv_bytes(CFG) / peaks["hbm_bytes_s"] == pytest.approx(
        0.1639, abs=0.001)
    assert 1e3 * fn.sconv_bytes(CFG, backward=True) / peaks["hbm_bytes_s"] \
        == pytest.approx(0.2868, abs=0.001)
    assert fn.TRAIN_MULTIPLIER == 3


def test_forward_flops_at_the_rehearsal_size_match_a_hand_count():
    """The rehearsal's tiny model (hidden 48, SwiGLU 40, 4 heads on 2 of
    12, 4 of 16 experts of 24 top-3, vocabulary 512, T 120; conv, full,
    conv, conv, conv with one dense layer), by hand."""
    fn = lib.load_module("flops", CFG["flops"])
    tiny = lib.load_json(lib.BENCH + "/tests/rehearsal/%s.json" % CELL)
    cfg = lib.merge(CFG, tiny["config"])
    t = 120
    head = 2 * t * 48 * 512
    conv = 2 * t * 48 * 4 * 48
    attn = 2 * t * 48 * 12 * 12 + 2 * 4 * 24 * t * (t + 1) // 2
    dense = 2 * t * 3 * 48 * 40
    rows = t * 3 * 4 / 16.0
    moe = 2 * (t * 48 * 16 + rows * 3 * 48 * 24)
    assert fn.expected_share_rows(cfg) == rows
    assert fn.forward_flops_per_sample(cfg) == head + 4 * conv + attn \
        + dense + 4 * moe
    assert fn.sconv_bytes(cfg, itemsize=4) == 4 * t * 48 * 4


def test_the_two_copies_of_the_reference_are_one_text():
    import mxnet_tpu.models.lfm2_reference as theirs

    with open(lib.find("reference", CFG["reference"], ".py")) as ours, \
            open(theirs.__file__) as program:
        assert ours.read() == program.read()
    ref = lib.load_module("reference", CFG["reference"])
    assert ref.expert_layers(CFG) == [False] + [True] * 8


# scope paths as the step compiled for the chip names them
SCOPES = {
    "fusion.1": "jit(step)/fwd_bwd/jvp(sconv/layer0_conv)/gate_in/mul:",
    "fusion.2": "jit(step)/fwd_bwd/jvp(sconv/layer2_conv)/conv1d/add:",
    "fusion.3": "jit(step)/fwd_bwd/transpose(jvp(sconv/layer3_conv))/"
                "checkpoint/rematted_computation/conv1d/mul:",
    "fusion.4": "jit(step)/fwd_bwd/transpose(jvp(sconv/layer0_conv))/"
                "checkpoint/gate_out/mul:",
    "fusion.5": "jit(step)/fwd_bwd/jvp(sconv/layer0_conv)/checkpoint/"
                "gate_out/convert_element_type:",
    "fusion.6": "jit(step)/fwd_bwd/jvp(sconv/layer0_conv)/slice:",
    "fusion.7": "jit(step)/fwd_bwd/jvp(fc/layer0_conv_in_proj)/dot_general:",
    "fusion.8": "jit(step)/fwd_bwd/transpose(jvp(fc/layer4_conv_out_proj))/"
                "dot_general:",
    "fusion.9": "jit(step)/fwd_bwd/jvp(fc/layer1_q_proj)/dot_general:",
    "fusion.10": "jit(step)/fwd_bwd/jvp(fc/layer0_gate_proj)/dot_general:",
    "fusion.11": "jit(step)/fwd_bwd/jvp(ssm/layer0_ssm)/conv1d/jit(silu):",
    "fusion.12": "jit(step)/fwd_bwd/jvp(gdn/layer0_gdn)/"
                 "jit(_gated_delta_block)/conv1d/jit(silu):",
    "fusion.13": "jit(step)/fwd_bwd/jvp(conv/stage1_conv1)/"
                 "conv_general_dilated:",
    "fusion.14": "jit(step)/fwd_bwd/jvp(fc/layer0_in_proj)/dot_general:",
}


def test_the_table_files_the_nodes_scopes_and_finds_the_projections():
    assert {k: sconv_scopes.part_of(v) for k, v in SCOPES.items()} == {
        "fusion.1": "gate_in", "fusion.2": "conv1d", "fusion.3": "conv1d",
        "fusion.4": "gate_out", "fusion.5": "gate_out", "fusion.6": "other",
        "fusion.7": "proj", "fusion.8": "proj", "fusion.9": None,
        "fusion.10": None, "fusion.11": None, "fusion.12": None,
        "fusion.13": None, "fusion.14": None}
    # neither the node's class nor the convolution's scope is the class
    # of the Convolution nodes
    import reduce_scopes

    for key in ("fusion.1", "fusion.2", "fusion.3", "fusion.7"):
        assert not reduce_scopes._CLASS.search(SCOPES[key]) or \
            reduce_scopes._CLASS.search(SCOPES[key]).group(1) == "fc"
    assert reduce_scopes._CLASS.search(SCOPES["fusion.13"]).group(1) == "conv"
    assert list(sconv_scopes.TABLE) == ["gate_in", "conv1d", "gate_out",
                                        "other", "proj"]


def test_the_reduction_sums_the_parts_and_needs_a_short_conv_node():
    import reduce_trace

    ops = [("fusion.%d" % i, 1000 * i, 100) for i in range(1, 15)]
    raw = {"host": [(0, reduce_trace.SLICE_BEGIN, 0, 10),
                    (0, reduce_trace.SLICE_END, 30000, 10)],
           "devices": {0: {"ops": ops}}}
    red = sconv_scopes.reduce(raw, {0: SCOPES})
    assert red["sconv"] == pytest.approx(600e-9)
    assert red["gate_in"] == red["other"] == pytest.approx(100e-9)
    assert red["conv1d"] == red["gate_out"] == pytest.approx(200e-9)
    assert red["proj"] == pytest.approx(200e-9)
    # projections named alike in a model without the node: nothing
    rest = {k: v for k, v in SCOPES.items() if "sconv/" not in v}
    assert sconv_scopes.reduce(raw, {0: rest}) is None
    assert sconv_scopes.reduce(dict(raw, host=[]), {0: SCOPES}) is None


COUNTS = [[500] * 8 + [512] * 56, [520] * 8 + [512] * 56] + [
    [512] * 64] * 6                     # 8 sparse layers over 64 experts


def _run(**over):
    run = {"cfg": CFG, "cell": {"name": CELL}, "trace_steps": 5,
           "batch": 1, "chips": 1, "flops_multiplier": 3,
           "peak": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
           "expert_counts": COUNTS,
           "sconv_scopes": {"sconv": 0.100, "gate_in": 0.020,
                            "conv1d": 0.050, "gate_out": 0.020,
                            "other": 0.010, "proj": 0.400},
           "share_scopes": {"window": 0.0, "full": 0.150},
           "lm_scopes": {"class_s": {"attn": 0.170, "moe": 0.200,
                                     "norm": 0.010, "embed": 0.010},
                         "head_loss_s": 0.040, "moe_part_s": {}}}
    run.update(over)
    return run


def _read(name, run, trace=True):
    return lib.load_module("layer_metrics", name).compute(
        {"devices": {}} if trace else None, {"telemetry": {}}, run)


TRACE_READERS = ["sconv_device_ms", "sconv_proj_device_ms",
                 "sconv_roofline_share", "attn64_device_ms",
                 "attn64_roofline_share", "moe_share_device_ms"]
READERS = TRACE_READERS + ["moe_share_rows_over_expected"]
# every share's entries since PR 68 (``lfm2_moe_device_ms`` and
# ``lfm2_held_rows_over_expected`` until then)
SHARED = READERS[-2:]


def test_the_seven_readers_read_what_they_say():
    run = _run()
    assert _read("sconv_device_ms", run) == pytest.approx(20.0)
    assert _read("sconv_proj_device_ms", run) == pytest.approx(80.0)
    assert _read("attn64_device_ms", run) == pytest.approx(30.0)
    assert _read("moe_share_device_ms", run) == pytest.approx(40.0)
    # seven layers, bound by bytes: 7 x (0.1639 + 0.2868) ms of 20
    assert _read("sconv_roofline_share", run) == pytest.approx(
        100 * 7 * 0.45067 / 20.0, rel=1e-3)
    # two layers, three forwards each of 0.2749 T at 197 T/s, of 30 ms
    assert _read("attn64_roofline_share", run) == pytest.approx(
        100 * (3 * 2 * 2.0 * 32 * 128 * 8192 * 8193 / 2 / 197e12 * 1e3)
        / 30.0, rel=1e-9)
    for name in ("sconv_roofline_share", "attn64_roofline_share"):
        assert 0 < _read(name, run) < 100
    # the held experts are the first eight: (8 x 500 + 8 x 520 + 6 x
    # 4096) rows of 8 x 4096
    assert _read("moe_share_rows_over_expected", run) == pytest.approx(
        (4000 + 4160 + 6 * 4096) / 32768.0)
    assert _read("moe_share_rows_over_expected", run, trace=False) \
        == pytest.approx(0.99902, abs=1e-5)      # a model output, no trace


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_where_there_is_nothing(name):
    """No slice, no such scopes (the parent's program, another model's),
    a configuration with another operations module: None, never zero,
    never a raise (the benchmark's files are laid over older
    checkouts)."""
    nemotron = lib.load_json(lib.find(
        "configs", "nemotron_3_nano_30b_a3b", ".json"))
    nothing = dict(sconv_scopes=None, share_scopes=None, lm_scopes=None,
                   expert_counts=None)
    assert _read(name, _run(**nothing)) is None
    assert _read(name, _run(cfg=nemotron, **nothing)) is None
    # another model's run, whatever its scopes hold: only the two
    # readers of the sconv scopes alone would read them
    assert _read(name, _run(cfg=nemotron)) is None or name in [
        "sconv_device_ms", "sconv_proj_device_ms"] + SHARED
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


def test_the_cell_the_mix_and_the_kind():
    cell = lib.load_json(lib.find("cells", CELL, ".json"))
    assert cell["traffic"] == "fit_tokens_share_keys_resident_b1_t8192"
    assert cell["chips"] == 1
    mix = lib.load_json(lib.find("traffic", cell["traffic"], ".json"))
    kanana = lib.load_json(lib.find(
        "traffic", "fit_tokens_share_resident_b1_t8192", ".json"))
    # the Kanana and Nemotron cells' parameters under the new kind; one
    # number differs: the reference check reads the last 4096 positions
    # (over 256 the 90th percentile's sampling noise is wider than the
    # distance between the system and the bf16 reference: the cell file)
    assert kanana["check_last_positions"] == 256
    assert mix == dict(kanana, kind="fit_tokens_share_keys",
                       check_last_positions=4096)
    kind = lib.load_module("traffic", mix["kind"])
    # fit_tokens_share's own set-up and checks, loaded, not copied
    assert kind.setup is kind.share.setup
    assert kind.share.__file__ == lib.find("traffic", "fit_tokens_share",
                                           ".py")
    text = open(kind.__file__).read()
    assert "def setup" not in text and "checks" not in text.split('"""')[2]
    nemotron = lib.load_json(lib.find(
        "cells", "nemotron3_nano_fit_share_8k", ".json"))
    assert set(cell["expect"]["reference"]) == set(
        nemotron["expect"]["reference"])
    # half the variance of logits from the Normal(0.02) embedding as a
    # head over a unit-rms vector of 2048
    assert cell["expect"]["first_loss_excess"] == pytest.approx(
        0.5 * 2048 * 0.02 ** 2)
    manifest = lib.load_json(lib.MANIFEST)
    entry = [w for w in manifest["workloads"] if w["name"] == CELL][0]
    assert entry == {"name": CELL, "config": "lfm2_24b_a2b",
                     "traffic": cell["traffic"], "chips": 1,
                     "why": cell["why"]}
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1


def test_rehearsal_runs_the_cell_end_to_end_with_the_trace_on():
    """The whole path at a tiny size on the CPU: from_config, Module.fit
    through the fused step, the share kind's checks under the names this
    configuration spells, the reference check in float32 (where the
    program and the reference agree to summation order, and the bf16
    reference does not) and every reader returning nothing or a value
    without a raise."""
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
