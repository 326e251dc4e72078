"""What the ``nemotron_3_nano_30b_a3b`` configuration brought: its file
against the published keys, the parameters the cut counted, its
operations and bytes against the hand count, the benchmark's copy of the
reference against the program's, the scope reduction of ``ssm_scopes``
on a scope table, the five readers on handed-in reductions, and the
cell's rehearsal end to end."""
import pytest

import lib
import ssm_scopes
from helpers import check_rehearsal, run_bench

CFG = lib.load_json(lib.find("configs", "nemotron_3_nano_30b_a3b", ".json"))
CELL = "nemotron3_nano_fit_share_8k"
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
# nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16's config.json, the keys that
# say its shape (the model-configs catalog's ``config``)
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern": PATTERN, "intermediate_size": 1856,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1,
    "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
    "use_conv_bias": True, "use_mamba_kernels": True, "vocab_size": 131072}
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "moe_shared_expert_intermediate_size", "head_dim",
          "mamba_head_dim", "mamba_num_heads", "ssm_state_size", "n_groups",
          "conv_kernel", "chunk_size", "expand", "num_attention_heads",
          "num_key_value_heads", "num_experts_per_tok", "n_shared_experts",
          "routed_scaling_factor")
HELD = CFG["n_routed_experts"]


def test_configuration_keeps_every_published_width_and_states_its_cut():
    changed = {k for k, v in PUBLISHED.items() if CFG[k] != v}
    assert changed == set(CFG["reduced"]) == set(CFG["reduced_why"]) == {
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"}
    assert not changed & set(WIDTHS)
    for key in changed:           # the uncut count stands beside the held
        assert CFG["published"][key] == PUBLISHED[key]
    # the floors: one whole period of the pattern (4 : 4 : 1 against the
    # published 23 : 23 : 6), 8 experts or more, an eighth of the
    # vocabulary
    assert CFG["hybrid_override_pattern"] == PATTERN[:9] == "MEMEM*EME"
    assert CFG["num_hidden_layers"] == 9
    assert [PATTERN.count(k) for k in "ME*-"] == [23, 23, 6, 0]
    assert HELD in (8, 16)
    assert CFG["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    # twice the rows the held experts expect; 128 / held chips a layer
    assert CFG["share"] == {"experts_of": 128, "expert_offset": 0,
                            "share_rows_bound": 2 * 8192 * 6 * HELD // 128}
    assert "%d chips share each layer" % (128 // HELD) in CFG["deployment"]
    assert CFG["kwargs"] == {"seq_len": 8192, "dtype": "bfloat16"}
    assert CFG["input_shape"][2] == 8192
    assert CFG["num_classes"] == CFG["vocab_size"]
    for topic in ("block", "mamba2", "conv_weight", "attention", "experts",
                  "weights", "dtype", "optimizer", "objective"):
        assert CFG["assumed"][topic]
    assert "NO rotary embedding" in CFG["assumed"]["attention"]
    assert "A_log = log(U(1, 16))" in CFG["assumed"]["weights"]
    assert "dt_bias = softplus^-1(dt)" in CFG["assumed"]["weights"]
    manifest = [c for c in lib.load_json(lib.MANIFEST)["configs"]
                if c["name"] == "nemotron_3_nano_30b_a3b"][0]
    assert manifest["reduced"] == CFG["reduced"]
    assert manifest["source"] == CFG["source"]


def test_the_symbol_holds_the_parameters_the_cut_counted():
    """ISSUE 37's arithmetic: a Mamba-2 block 38.74 M (in_proj 2688 x
    10304 = 27.70 M, out_proj 4096 x 2688 = 11.01 M, conv / dt / A / D /
    gate norm 0.04 M), the attention block 23.40 M, an expert block
    20.30 M outside its routed experts (shared 19.96 M, router 0.34 M)
    and 9.978 M a routed expert, embedding and head 88.08 M: 667.0 M
    with 8 experts held, 986.3 M with 16."""
    import numpy as np

    sym = lib.resolve(CFG["factory"])(CFG, **CFG["kwargs"])
    shapes, _, _ = sym.infer_shape(data=(1, 8192), softmax_label=(1, 8192))
    sizes = {n: int(np.prod(s)) for n, s in zip(sym.list_arguments(), shapes)
             if n not in ("data", "softmax_label")}
    assert sizes["layer0_in_proj_weight"] == 2688 * 10304
    assert sizes["layer0_out_proj_weight"] == 4096 * 2688
    assert sizes["layer0_ssm_conv_weight"] == 4 * 6144
    assert sizes["layer0_ssm_a_log"] == sizes["layer0_ssm_dt_bias"] == 64
    assert sizes["layer0_ssm_norm_gamma"] == 4096
    assert sizes["layer5_q_proj_weight"] == 2688 * 4096
    assert sizes["layer5_k_proj_weight"] == 2688 * 256
    assert sizes["layer1_moe_gate_weight"] == 2688 * 128
    assert sizes["layer1_moe_gate_up_weight"] == HELD * 2688 * 1856  # no gate
    assert sizes["layer1_shared_up_proj_weight"] == 2688 * 3712
    assert "layer1_shared_gate_proj_weight" not in sizes
    assert sizes["embed_weight"] == sizes["lm_head_weight"] == 16384 * 2688

    def block(i):
        return sum(v for k, v in sizes.items()
                   if k.startswith("layer%d_" % i))

    assert block(0) == pytest.approx(38.74e6, rel=1e-3)
    assert block(5) == pytest.approx(23.40e6, rel=1e-3)
    assert block(1) == pytest.approx(20.30e6 + HELD * 9.978e6, rel=1e-3)
    assert sum(sizes.values()) == pytest.approx(
        {8: 667.0e6, 16: 986.3e6}[HELD], rel=1e-3)


def test_forward_flops_and_bytes_match_the_hand_count():
    """Per sequence of 8192, forward: head 2 x 8192 x 2688 x 16384 =
    0.722 T; a Mamba-2 block's projections 2 x 8192 x 2688 x (10304 +
    4096) = 0.634 T and its scan 2 x 8192 x (64.5 x (1024 + 4096) + 2 x
    524288) = 0.0226 T (2.76 M a token: 0.33 M of it the triangle of
    ``C B^T`` and of the masked product, 2.10 M the two products with
    the state); the attention block's projections 0.383 T and its
    scores and values 2 x 32 x 256 x 8192 x 8193 / 2 = 0.550 T; an
    expert block's shared expert 0.327 T, its router 0.0056 T and the
    expected rows through an un-gated expert."""
    fn = lib.load_module("flops", CFG["flops"])
    t, d = 8192, 2688
    rows = 8192 * 6 * HELD / 128.0
    assert fn.expected_share_rows(CFG) == rows
    assert [fn.blocks(CFG, k) for k in "ME*"] == [4, 4, 1]
    assert fn.mamba_projection_flops(CFG) == 2.0 * t * d * (10304 + 4096)
    assert fn.scan_flops(CFG) == 2.0 * t * (
        64.5 * (8 * 128 + 64 * 64) + 2 * 64 * 64 * 128)
    assert fn.scan_flops(CFG) / t == pytest.approx(2.76e6, rel=2e-3)
    assert fn.scan_bytes(CFG) == 2.0 * t * (2 * 4096 + 2 * 1024 + 64)
    assert fn.attention_projection_flops(CFG) == 2.0 * t * d * (
        2 * 4096 + 2 * 256)
    assert fn.attention_kernel_flops(CFG) == 2.0 * 32 * 256 * t * (t + 1) / 2
    assert fn.shared_expert_flops(CFG) == 2.0 * t * 2 * d * 3712
    assert fn.moe_share_flops(CFG) == 2.0 * (t * d * 128
                                             + rows * 2 * d * 1856)
    assert fn.moe_share_flops(CFG, 2 * rows) - fn.moe_share_flops(CFG) == (
        2.0 * rows * 2 * d * 1856)
    want = (2.0 * t * d * 16384
            + 4 * (fn.mamba_projection_flops(CFG) + fn.scan_flops(CFG))
            + fn.attention_projection_flops(CFG)
            + fn.attention_kernel_flops(CFG)
            + 4 * (fn.shared_expert_flops(CFG) + fn.moe_share_flops(CFG)))
    assert fn.forward_flops_per_sample(CFG) == pytest.approx(want, rel=1e-12)
    # the state-space blocks are the largest part of the step's
    # operations (45% at 8 held experts, 43% at 16; ISSUE 37 counted
    # 43% with a larger routed part), nearly all of it their projections
    share = 4 * (fn.mamba_projection_flops(CFG) + fn.scan_flops(CFG)) / want
    assert share == pytest.approx({8: 0.45, 16: 0.43}[HELD], abs=0.01)
    assert 4 * fn.scan_flops(CFG) / want < 0.02
    # the scan is bound by its bytes: 0.62 ms a block forward and
    # backward on the v5e's peaks, 0.34 ms of operations
    peaks = lib.load_json(lib.BENCH + "/peaks.json")["TPU v5 lite"]
    assert 3e3 * fn.scan_bytes(CFG) / peaks["hbm_bytes_s"] == pytest.approx(
        0.618, abs=0.002)
    assert 3e3 * fn.scan_flops(CFG) / peaks["bf16_flops"] == pytest.approx(
        0.344, abs=0.002)
    assert fn.TRAIN_MULTIPLIER == 3


def test_the_two_copies_of_the_reference_are_one_text():
    import mxnet_tpu.models.nemotron_h_reference as theirs

    with open(lib.find("reference", CFG["reference"], ".py")) as ours, \
            open(theirs.__file__) as program:
        assert ours.read() == program.read()
    ref = lib.load_module("reference", CFG["reference"])
    assert ref.expert_layers(CFG) == [k == "E" for k in "MEMEM*EME"]


# scope paths as the step compiled for the chip names them
SCOPES = {
    "fusion.1": "jit(step)/fwd_bwd/jvp(ssm/layer0_ssm)/scan/mul:",
    "fusion.2": "jit(step)/fwd_bwd/jvp(ssm/layer2_ssm)/conv1d/jit(silu):",
    "fusion.3": "jit(step)/fwd_bwd/transpose(jvp(ssm/layer4_ssm))/"
                "jvp(ssm/layer4_ssm)/checkpoint/rematted_computation/scan/"
                "exp:",
    "fusion.4": "jit(step)/fwd_bwd/transpose(jvp(ssm/layer7_ssm))/"
                "jvp(ssm/layer7_ssm)/checkpoint/gate_norm/mul:",
    "fusion.5": "jit(step)/fwd_bwd/jvp(ssm/layer0_ssm)/scan/closed_call/"
                "while/body/add:",
    "fusion.6": "jit(step)/fwd_bwd/jvp(ssm/layer0_ssm)/slice:",
    "fusion.7": "jit(step)/fwd_bwd/jvp(fc/layer0_in_proj)/dot_general:",
    "fusion.8": "jit(step)/fwd_bwd/transpose(jvp(fc/layer7_out_proj))/"
                "dot_general:",
    "fusion.9": "jit(step)/fwd_bwd/jvp(fc/layer5_o_proj)/dot_general:",
    "fusion.10": "jit(step)/fwd_bwd/jvp(moe/layer1_moe)/experts/"
                 "gmm_fwd_bf16_m256_k896_n1856/pallas_call:",
    "fusion.11": "jit(step)/fwd_bwd/jvp(conv/stage1_conv1)/"
                 "conv_general_dilated:",
}


def test_scope_names_split_the_state_space_node_and_find_its_projections():
    assert {k: ssm_scopes.part_of(v) for k, v in SCOPES.items()} == {
        "fusion.1": ("ssm", "scan"), "fusion.2": ("ssm", "conv1d"),
        "fusion.3": ("ssm", "scan"), "fusion.4": ("ssm", "gate_norm"),
        "fusion.5": ("ssm", "scan"), "fusion.6": ("ssm", "other"),
        "fusion.7": ("proj", None), "fusion.8": ("proj", None),
        "fusion.9": None, "fusion.10": None, "fusion.11": None}
    # the convolution's scope is not the class of the Convolution nodes
    import reduce_scopes

    assert not reduce_scopes._CLASS.search(SCOPES["fusion.2"])


def test_the_reduction_sums_the_parts_and_needs_a_state_space_node():
    import reduce_trace

    ops = [("fusion.%d" % i, 1000 * i, 100) for i in range(1, 12)]
    raw = {"host": [(0, reduce_trace.SLICE_BEGIN, 0, 10),
                    (0, reduce_trace.SLICE_END, 30000, 10)],
           "devices": {0: {"ops": ops}}}
    red = ssm_scopes.reduce(raw, {0: SCOPES})
    assert red["ssm"] == pytest.approx(600e-9)
    assert red["scan"] == pytest.approx(300e-9)
    assert red["conv1d"] == red["gate_norm"] == pytest.approx(100e-9)
    assert red["proj"] == pytest.approx(200e-9)
    # projections named alike in a model without the node: nothing
    rest = {k: v for k, v in SCOPES.items() if "ssm/" not in v}
    assert ssm_scopes.reduce(raw, {0: rest}) is None


def _run(**over):
    run = {"cfg": CFG, "cell": {"name": CELL}, "trace_steps": 5,
           "batch": 1, "chips": 1, "flops_multiplier": 3,
           "peak": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
           "ssm_scopes": {"ssm": 0.500, "conv1d": 0.050, "scan": 0.400,
                          "gate_norm": 0.040, "proj": 0.150},
           "lm_scopes": {"class_s": {"attn": 0.1, "moe": 0.2, "norm": 0.01,
                                     "embed": 0.001},
                         "head_loss_s": 0.05, "moe_part_s": {}}}
    run.update(over)
    return run


def _read(name, run, trace=True):
    return lib.load_module("layer_metrics", name).compute(
        {"devices": {}} if trace else None, {"telemetry": {}}, run)


OWN = ["ssm_device_ms", "ssm_scan_device_ms", "ssm_scan_roofline_share",
       "ssm_proj_device_ms"]
# the un-gated experts' class ``moe`` is every share's entry since PR 68
# (``moe_relu2_device_ms`` until then)
READERS = OWN + ["moe_share_device_ms"]


def test_the_five_readers_read_what_they_say():
    run = _run()
    assert _read("ssm_device_ms", run) == pytest.approx(100.0)
    assert _read("ssm_scan_device_ms", run) == pytest.approx(80.0)
    assert _read("ssm_proj_device_ms", run) == pytest.approx(30.0)
    assert _read("moe_share_device_ms", run) == pytest.approx(40.0)
    # four blocks, bound by bytes: 4 x 0.618 ms of 80
    assert _read("ssm_scan_roofline_share", run) == pytest.approx(
        100 * 4 * 0.6183 / 80.0, rel=1e-3)
    assert _read("ssm_scan_roofline_share", run) < 100


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_where_there_is_nothing(name):
    """No slice, no such scopes (the parent's program, another model's),
    a configuration with another operations module or gated experts:
    None, never zero, never a raise (the benchmark's files are laid over
    older checkouts)."""
    kanana = lib.load_json(lib.find("configs", "kanana_2_30b_a3b", ".json"))
    nothing = _run(ssm_scopes=None, lm_scopes=None)
    assert _read(name, nothing) is None
    assert _read(name, _run(), trace=False) is None
    if name in OWN:
        assert _read(name, _run(cfg=kanana, ssm_scopes=None)) is None
    if name == "ssm_scan_roofline_share":
        assert _read(name, _run(cfg=kanana)) is None
        assert _read(name, _run(peak=None)) is None
    entry = [m for m in lib.load_json(lib.MANIFEST)["per_layer"]
             if m["name"] == name][0]
    assert entry["workloads"] == [CELL] if name in OWN \
        else CELL in entry["workloads"]
    assert entry["moves"] == "train_samples_s"
    assert entry["layer"] == "ops and kernels"
    assert entry["source"] == "device_trace"


def test_the_cell_is_the_existing_mix_and_kind_unchanged():
    cell = lib.load_json(lib.find("cells", CELL, ".json"))
    assert cell["traffic"] == "fit_tokens_share_resident_b1_t8192"
    mix = lib.load_json(lib.find("traffic", cell["traffic"], ".json"))
    assert mix["kind"] == "fit_tokens_share_layers"
    assert mix["batch"] == 1 and mix["check_last_positions"] == 256
    kanana = lib.load_json(lib.find("cells", "kanana2_fit_share_8k", ".json"))
    assert kanana["traffic"] == cell["traffic"]
    assert set(cell["expect"]["reference"]) == set(
        kanana["expect"]["reference"])
    manifest = lib.load_json(lib.MANIFEST)
    assert CELL in [w["name"] for w in manifest["workloads"]]
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1


def test_rehearsal_runs_the_cell_end_to_end_with_the_trace_on():
    """The whole path at a tiny size on the CPU: from_config, Module.fit
    through the fused step, the share's checks, the reference check in
    float32 (where the program and the reference agree to summation
    order) and every reader returning nothing or a value without a
    raise."""
    proc = run_bench(["--workload", CELL, "--seed", "3000000019",
                      "--seconds", "1", "--trace", "1", "--rehearse-cpu"])
    result = check_rehearsal(proc, ["fused_step_share"])
    assert "matches_reference ok=True" in proc.stdout
    assert "experts_routed_over_all ok=True" in proc.stdout
    assert not set(READERS) & set(result["metrics"])  # no device, no value
