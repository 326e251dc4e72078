"""What the ``olmo_hybrid_7b`` configuration brought: its file against the
published keys, the parameters the cut counted, its operations and bytes
against the hand count (at the cell's size and at the rehearsal's), the
benchmark's copy of the reference against the program's, the table of
``gdn_scopes`` on scope paths, the five readers on handed-in reductions,
the new traffic kind's parts, and the cell's rehearsal end to end."""
import pytest

import gdn_scopes
import lib
from helpers import check_rehearsal, run_bench

CFG = lib.load_json(lib.find("configs", "olmo_hybrid_7b", ".json"))
CELL = "olmo_hybrid_fit_stage_4k"
L, F = "linear_attention", "full_attention"
# allenai/Olmo-Hybrid-7B's config.json, the keys that say its shape (the
# model-configs catalog's ``config``)
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "layer_types": [L, L, L, F] * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}
WIDTHS = ("hidden_size", "intermediate_size", "num_attention_heads",
          "num_key_value_heads", "linear_num_key_heads",
          "linear_num_value_heads", "linear_key_head_dim",
          "linear_value_head_dim", "linear_conv_kernel_dim")


def test_configuration_keeps_every_published_width_and_states_its_cut():
    changed = {k for k, v in PUBLISHED.items() if CFG[k] != v}
    assert changed == set(CFG["reduced"]) == set(CFG["reduced_why"]) == {
        "num_hidden_layers", "layer_types", "vocab_size"}
    assert not changed & set(WIDTHS)
    for key in changed:           # the uncut value stands beside the held
        assert CFG["published"][key] == PUBLISHED[key]
    # the floors: one whole period of the pattern at the published 3 : 1,
    # four layers, an eighth of the vocabulary
    assert CFG["layer_types"] == PUBLISHED["layer_types"][:4] == [L, L, L, F]
    assert CFG["num_hidden_layers"] == 4
    assert CFG["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert CFG["deployment"].startswith("Eight pipeline stages of four")
    assert "vocabulary parallelism" in CFG["deployment"]
    assert CFG["kwargs"] == {"seq_len": 4096, "dtype": "bfloat16"}
    assert CFG["input_shape"] == [1, 1, 4096]
    assert CFG["num_classes"] == CFG["vocab_size"]
    for topic in ("block", "linear_attention", "conv_weight", "attention",
                  "weights", "dtype", "optimizer", "objective"):
        assert CFG["assumed"][topic]
    assert "pre-norm" in CFG["assumed"]["block"]
    assert "NO rotary embedding" in CFG["assumed"]["attention"]
    assert "head_dim 128 = 3840 / 30" in CFG["assumed"]["attention"]
    assert "NO bias" in CFG["assumed"]["linear_attention"]
    assert "BEFORE the gate" in CFG["assumed"]["linear_attention"]
    assert "A_log = log(U(1, 16))" in CFG["assumed"]["weights"]
    assert "dt_bias = softplus^-1(dt)" in CFG["assumed"]["weights"]
    manifest = [c for c in lib.load_json(lib.MANIFEST)["configs"]
                if c["name"] == "olmo_hybrid_7b"][0]
    assert manifest["reduced"] == CFG["reduced"]
    assert manifest["source"] == CFG["source"]


def test_the_symbol_holds_the_parameters_the_cut_counted():
    """ISSUE 41's arithmetic: a linear-attention layer's mixer 88.75 M
    (q and k 3840 x 2880 = 11.06 M each; v, gate and o 3840 x 5760 =
    22.12 M each; the two 3840 x 30, taps and scalars 0.28 M), a full
    layer's 4 x 3840^2 = 58.98 M (and two gammas of 3840), every layer's
    SwiGLU 3 x 3840 x 11008 = 126.81 M, embedding and head 12544 x 3840
    = 48.17 M each: 928.8 M."""
    import numpy as np

    sym = lib.resolve(CFG["factory"])(CFG, **CFG["kwargs"])
    shapes, _, _ = sym.infer_shape(data=(1, 4096), softmax_label=(1, 4096))
    sizes = {n: int(np.prod(s)) for n, s in zip(sym.list_arguments(), shapes)
             if n not in ("data", "softmax_label")}
    assert sizes["layer0_gdn_q_proj_weight"] == 3840 * 2880
    assert sizes["layer0_gdn_k_proj_weight"] == 3840 * 2880
    for name in ("v", "g", "o"):
        assert sizes["layer0_gdn_%s_proj_weight" % name] == 3840 * 5760
    assert sizes["layer0_gdn_a_proj_weight"] == 3840 * 30
    assert sizes["layer0_gdn_b_proj_weight"] == 3840 * 30
    assert sizes["layer0_gdn_conv_weight"] == 4 * 11520
    assert sizes["layer0_gdn_a_log"] == sizes["layer0_gdn_dt_bias"] == 30
    assert sizes["layer0_gdn_norm_gamma"] == 192
    assert "layer0_gdn_conv_bias" not in sizes
    for name in ("q", "k", "v", "o"):
        assert sizes["layer3_%s_proj_weight" % name] == 3840 * 3840
    assert sizes["layer3_q_norm_gamma"] == sizes["layer3_k_norm_gamma"] == 3840
    assert sizes["layer2_gate_proj_weight"] == 3840 * 11008
    assert sizes["embed_weight"] == sizes["lm_head_weight"] == 12544 * 3840

    def part(i, keep):
        return sum(v for k, v in sizes.items()
                   if k.startswith("layer%d_" % i) and keep(k))

    assert part(0, lambda k: "_gdn_" in k) == pytest.approx(88.75e6, rel=1e-4)
    assert part(0, lambda k: True) == pytest.approx(215.6e6, rel=1e-3)
    assert part(3, lambda k: True) == pytest.approx(185.8e6, rel=1e-3)
    assert sum(sizes.values()) == pytest.approx(928.8e6, rel=1e-4)


def test_forward_flops_and_bytes_match_the_hand_count():
    """Per sequence of 4096, forward: head 2 x 4096 x 3840 x 12544 =
    0.395 T; a linear layer's projections 2 x 4096 x 3840 x (2 x 2880 +
    3 x 5760 + 60) = 0.727 T and its delta rule as the recurrence 7 x 96
    x 192 x 30 x 4096 = 0.0159 T; the full layer's projections 2 x 4096
    x 4 x 3840^2 = 0.483 T and its scores and values 2 x 2 x 3840 x 4096
    x 4097 / 2 = 0.129 T; a SwiGLU 2 x 4096 x 3 x 3840 x 11008 = 1.039
    T. 22.1 T a training step."""
    fn = lib.load_module("flops", CFG["flops"])
    t, d = 4096, 3840
    assert [fn.layers(CFG, k) for k in (fn.LINEAR, fn.FULL)] == [3, 1]
    assert fn.linear_projection_flops(CFG) == 2.0 * t * d * (
        2 * 2880 + 3 * 5760 + 2 * 30)
    assert fn.core_flops(CFG) == 7.0 * t * 30 * 96 * 192
    assert fn.core_bytes(CFG) == 2.0 * t * 30 * (2 * 96 + 2 * 192 + 2)
    assert fn.attention_projection_flops(CFG) == 2.0 * t * 4 * d * d
    assert fn.attention_kernel_flops(CFG) == 2.0 * 30 * 256 * t * (t + 1) / 2
    assert fn.mlp_flops(CFG) == 2.0 * t * 3 * d * 11008
    want = (2.0 * t * d * 12544
            + 3 * (fn.linear_projection_flops(CFG) + fn.core_flops(CFG))
            + fn.attention_projection_flops(CFG)
            + fn.attention_kernel_flops(CFG) + 4 * fn.mlp_flops(CFG))
    assert fn.forward_flops_per_sample(CFG) == pytest.approx(want, rel=1e-12)
    assert 3 * want == pytest.approx(22.1e12, rel=5e-3)
    # the linear layers' projections are 29-30% of the step's operations,
    # the head 5.4%, the delta rule itself 0.65%
    assert 3 * fn.linear_projection_flops(CFG) / want == pytest.approx(
        0.296, abs=0.005)
    assert 2.0 * t * d * 12544 / want == pytest.approx(0.054, abs=0.002)
    assert 3 * fn.core_flops(CFG) / want < 0.01
    # the rule is bound by its bytes: 0.173 ms a layer forward on the
    # v5e's peaks against 0.081 ms of operations
    peaks = lib.load_json(lib.BENCH + "/peaks.json")["TPU v5 lite"]
    assert 1e3 * fn.core_bytes(CFG) / peaks["hbm_bytes_s"] == pytest.approx(
        0.1734, abs=0.001)
    assert 1e3 * fn.core_flops(CFG) / peaks["bf16_flops"] == pytest.approx(
        0.0805, abs=0.001)
    assert fn.TRAIN_MULTIPLIER == 3


def test_forward_flops_at_the_rehearsal_size_match_a_hand_count():
    """The rehearsal's tiny model (hidden 48, SwiGLU 40, 3 linear heads
    of 8 / 16, 4 attention heads of 12, vocabulary 512, T 120), by
    hand."""
    fn = lib.load_module("flops", CFG["flops"])
    tiny = lib.load_json(lib.BENCH + "/tests/rehearsal/%s.json" % CELL)
    cfg = lib.merge(CFG, tiny["config"])
    t = 120
    head = 2 * t * 48 * 512
    proj = 2 * t * 48 * (2 * 24 + 3 * 48 + 6)
    core = 7 * t * 3 * 8 * 16
    attn = 2 * t * 48 * 4 * 48 + 2 * 2 * 48 * t * (t + 1) // 2
    mlp = 2 * t * 3 * 48 * 40
    assert fn.forward_flops_per_sample(cfg) == head + 3 * (proj + core) \
        + attn + 4 * mlp
    assert fn.core_bytes(cfg) == 2 * t * 3 * (16 + 32 + 2)


def test_the_two_copies_of_the_reference_are_one_text():
    import mxnet_tpu.models.olmo_hybrid_reference as theirs

    with open(lib.find("reference", CFG["reference"], ".py")) as ours, \
            open(theirs.__file__) as program:
        assert ours.read() == program.read()


# scope paths as the step compiled for the chip names them
SCOPES = {
    "fusion.1": "jit(step)/fwd_bwd/jvp(gdn/layer0_gdn)/"
                "jit(_gated_delta_block)/delta_rule/mul:",
    "fusion.2": "jit(step)/fwd_bwd/jvp(gdn/layer1_gdn)/"
                "jit(_gated_delta_block)/conv1d/jit(silu):",
    "fusion.3": "jit(step)/fwd_bwd/transpose(jvp(gdn/layer2_gdn))/"
                "jit(_gated_delta_block)/delta_rule/delta_rule/checkpoint/"
                "rematted_computation/jit(_solve_triangular)/"
                "triangular_solve:",
    "fusion.4": "jit(step)/fwd_bwd/transpose(jvp(gdn/layer0_gdn))/"
                "jit(_gated_delta_block)/gate_norm/gate_norm/checkpoint/mul:",
    "fusion.5": "jit(step)/fwd_bwd/jvp(gdn/layer0_gdn)/"
                "jit(_gated_delta_block)/delta_rule/while/body/closed_call/"
                "add:",
    "fusion.6": "jit(step)/fwd_bwd/jvp(gdn/layer0_gdn)/slice:",
    "fusion.7": "jit(step)/fwd_bwd/jvp(fc/layer0_gdn_q_proj)/dot_general:",
    "fusion.8": "jit(step)/fwd_bwd/transpose(jvp(fc/layer2_gdn_o_proj))/"
                "dot_general:",
    "fusion.9": "jit(step)/fwd_bwd/jvp(fc/layer1_gdn_a_proj)/dot_general:",
    "fusion.10": "jit(step)/fwd_bwd/jvp(fc/layer3_q_proj)/dot_general:",
    "fusion.11": "jit(step)/fwd_bwd/jvp(fc/layer3_down_proj)/dot_general:",
    "fusion.12": "jit(step)/fwd_bwd/transpose(jvp(fc/layer0_gate_proj))/"
                 "dot_general:",
    "fusion.13": "jit(step)/fwd_bwd/jvp(fc/layer1_shared_gate_proj)/"
                 "dot_general:",
    "fusion.14": "jit(step)/fwd_bwd/jvp(ssm/layer0_ssm)/conv1d/jit(silu):",
    "fusion.15": "jit(step)/fwd_bwd/jvp(conv/stage1_conv1)/"
                 "conv_general_dilated:",
}


def test_the_table_files_the_nodes_scopes_and_finds_the_projections():
    assert {k: gdn_scopes.part_of(v) for k, v in SCOPES.items()} == {
        "fusion.1": "delta_rule", "fusion.2": "conv1d",
        "fusion.3": "delta_rule", "fusion.4": "gate_norm",
        "fusion.5": "delta_rule", "fusion.6": "other", "fusion.7": "proj",
        "fusion.8": "proj", "fusion.9": None, "fusion.10": None,
        "fusion.11": "mlp", "fusion.12": "mlp", "fusion.13": None,
        "fusion.14": None, "fusion.15": None}
    # the convolution's scope is not the class of the Convolution nodes
    import reduce_scopes

    assert not reduce_scopes._CLASS.search(SCOPES["fusion.2"])
    assert list(gdn_scopes.TABLE) == ["conv1d", "delta_rule", "gate_norm",
                                      "other", "proj", "mlp"]


def test_the_reduction_sums_the_parts_and_needs_a_delta_rule_node():
    import reduce_trace

    ops = [("fusion.%d" % i, 1000 * i, 100) for i in range(1, 16)]
    raw = {"host": [(0, reduce_trace.SLICE_BEGIN, 0, 10),
                    (0, reduce_trace.SLICE_END, 30000, 10)],
           "devices": {0: {"ops": ops}}}
    red = gdn_scopes.reduce(raw, {0: SCOPES})
    assert red["gdn"] == pytest.approx(600e-9)
    assert red["delta_rule"] == pytest.approx(300e-9)
    assert red["conv1d"] == red["gate_norm"] == pytest.approx(100e-9)
    assert red["other"] == pytest.approx(100e-9)
    assert red["proj"] == red["mlp"] == pytest.approx(200e-9)
    # a dense SwiGLU named alike in a model without the node: nothing
    rest = {k: v for k, v in SCOPES.items() if "gdn/" not in v}
    assert gdn_scopes.reduce(raw, {0: rest}) is None
    assert gdn_scopes.reduce(dict(raw, host=[]), {0: SCOPES}) is None


def _run(**over):
    run = {"cfg": CFG, "cell": {"name": CELL}, "trace_steps": 5,
           "batch": 1, "chips": 1, "flops_multiplier": 3,
           "peak": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
           "gdn_scopes": {"gdn": 0.500, "conv1d": 0.100, "delta_rule": 0.300,
                          "gate_norm": 0.080, "other": 0.020, "proj": 0.280,
                          "mlp": 0.520}}
    run.update(over)
    return run


def _read(name, run, trace=True):
    return lib.load_module("layer_metrics", name).compute(
        {"devices": {}} if trace else None, {"telemetry": {}}, run)


READERS = ["gdn_device_ms", "gdn_core_device_ms", "gdn_core_roofline_share",
           "gdn_proj_device_ms", "dense_mlp_device_ms"]


def test_the_five_readers_read_what_they_say():
    run = _run()
    assert _read("gdn_device_ms", run) == pytest.approx(100.0)
    assert _read("gdn_core_device_ms", run) == pytest.approx(60.0)
    assert _read("gdn_proj_device_ms", run) == pytest.approx(56.0)
    assert _read("dense_mlp_device_ms", run) == pytest.approx(104.0)
    # three layers, three forwards each, bound by bytes: 9 x 0.1734 ms
    # of 60
    assert _read("gdn_core_roofline_share", run) == pytest.approx(
        100 * 9 * 0.17343 / 60.0, rel=1e-3)
    assert _read("gdn_core_roofline_share", run) < 100


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_where_there_is_nothing(name):
    """No slice, no such scopes (the parent's program, another model's),
    a configuration with another operations module: None, never zero,
    never a raise (the benchmark's files are laid over older
    checkouts)."""
    nemotron = lib.load_json(lib.find(
        "configs", "nemotron_3_nano_30b_a3b", ".json"))
    assert _read(name, _run(gdn_scopes=None)) is None
    assert _read(name, _run(), trace=False) is None
    assert _read(name, _run(cfg=nemotron, gdn_scopes=None)) is None
    assert _read(name, _run(trace_steps=0)) is None
    if name == "gdn_core_roofline_share":
        assert _read(name, _run(cfg=nemotron)) is None
        assert _read(name, _run(peak=None)) is None
    entry = [m for m in lib.load_json(lib.MANIFEST)["per_layer"]
             if m["name"] == name][0]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "train_samples_s"
    assert entry["layer"] == "ops and kernels"
    assert entry["source"] == "device_trace"


def test_the_cell_the_mix_and_the_kind():
    cell = lib.load_json(lib.find("cells", CELL, ".json"))
    assert cell["traffic"] == "fit_tokens_dense_resident_b1_t4096"
    assert cell["chips"] == 1
    mix = lib.load_json(lib.find("traffic", cell["traffic"], ".json"))
    olmoe = lib.load_json(lib.find(
        "traffic", "fit_tokens_resident_b1_t4096", ".json"))
    # the OLMoE cell's parameters, letter for letter, under the new kind
    assert mix == dict(olmoe, kind="fit_tokens_dense")
    kind = lib.load_module("traffic", mix["kind"])
    # fit_tokens' own set-up and reference check, loaded, not copied
    assert kind.setup is kind.fit_tokens.setup
    assert kind.fit_tokens.__file__ == lib.find("traffic", "fit_tokens", ".py")
    assert "def reference_check" not in open(kind.__file__).read()
    nemotron = lib.load_json(lib.find(
        "cells", "nemotron3_nano_fit_share_8k", ".json"))
    assert set(cell["expect"]["reference"]) == set(
        nemotron["expect"]["reference"])
    assert cell["expect"]["reference"]["near_tie_share_max"] == 0.0
    # half the variance of logits from a Normal(0.02) head over a
    # unit-rms vector of 3840
    assert cell["expect"]["first_loss_excess"] == pytest.approx(
        0.5 * 3840 * 0.02 ** 2)
    manifest = lib.load_json(lib.MANIFEST)
    assert CELL in [w["name"] for w in manifest["workloads"]]
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1


def test_rehearsal_runs_the_cell_end_to_end_with_the_trace_on():
    """The whole path at a tiny size on the CPU: from_config, Module.fit
    through the fused step, the dense kind's checks, the reference check
    in float32 (where the program and the reference agree to summation
    order, and the bf16 reference does not) and every reader returning
    nothing or a value without a raise."""
    proc = run_bench(["--workload", CELL, "--seed", "3000000019",
                      "--seconds", "1", "--trace", "1", "--rehearse-cpu"])
    result = check_rehearsal(proc, ["fused_step_share",
                                    "fit_lookahead_share"])
    assert "matches_reference ok=True" in proc.stdout
    assert '"within_limits": false' in proc.stdout
    assert "loss_is_the_only_output ok=True" in proc.stdout
    assert "first_loss_near_expected ok=True" in proc.stdout
    assert "window_compiles=0" in proc.stdout
    assert not set(READERS) & set(result["metrics"])  # no device, no value
