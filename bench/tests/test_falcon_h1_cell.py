"""What the ``falcon_h1_34b`` configuration brought: its file against the
published keys, the parameters the cut counted, its operations and bytes
against the hand count (at the cell's size and at the rehearsal's), the
benchmark's copy of the reference against the program's, the table of
``h1_scopes`` on scope paths, the six readers on handed-in reductions,
the cell with the mix it shares, and the cell's rehearsal end to end.
Every entry of the manifest is found by name, never by position or
count."""
import pytest

import h1_scopes
import lib
from helpers import check_rehearsal, run_bench

CFG = lib.load_json(lib.find("configs", "falcon_h1_34b", ".json"))
CELL = "falcon_h1_fit_share_4k"
# tiiuae/Falcon-H1-34B-Instruct's config.json, the keys that say its
# shape (the model-configs catalog's ``config``)
PUBLISHED = {
    "attention_bias": False, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
    "embedding_multiplier": 5.656854249492381, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804,
    "lm_head_multiplier": 0.0078125, "mamba_chunk_size": 128,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 128,
    "mamba_d_ssm": 4096, "mamba_d_state": 256, "mamba_expand": 2,
    "mamba_n_groups": 2, "mamba_n_heads": 32,
    "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_use_mlp": True,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_expansion_factor": 8,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "model_type": "falcon_h1", "num_attention_heads": 20,
    "num_hidden_layers": 72, "num_key_value_heads": 4,
    "num_logits_to_keep": 1, "projectors_bias": False,
    "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000000000, "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845,
    "tie_word_embeddings": False, "vocab_size": 261120}
REDUCED = {"num_hidden_layers", "num_attention_heads", "num_key_value_heads",
           "mamba_n_heads", "mamba_n_groups", "vocab_size"}
MULTIPLIERS = ("embedding_multiplier", "lm_head_multiplier",
               "attention_in_multiplier", "attention_out_multiplier",
               "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier",
               "ssm_multipliers", "mlp_multipliers")


def _entry(section, name):
    found = [e for e in lib.load_json(lib.MANIFEST)[section]
             if e["name"] == name]
    assert len(found) == 1, (section, name)
    return found[0]


def test_configuration_keeps_every_published_width_and_states_its_cut():
    changed = {k for k, v in PUBLISHED.items() if CFG[k] != v}
    assert changed == set(CFG["reduced"]) == set(CFG["reduced_why"]) \
        == REDUCED
    # no width among them: a hidden, intermediate, state or head size, a
    # key that ends in _dim or _rank, an expansion factor
    assert not [k for k in changed if k.endswith(("_dim", "_rank"))
                or k in ("hidden_size", "intermediate_size")
                or k.startswith("mamba_d_") or "expan" in k
                or "multiplier" in k]
    for key in changed:           # the uncut value stands beside the held
        assert CFG["published"][key] == PUBLISHED[key]
    # the floors: four layers, an eighth of the vocabulary; one of two
    # chips: half the heads with whole groups, half the columns
    assert CFG["num_hidden_layers"] == 4
    assert CFG["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    share = CFG["share"]
    assert share["chips"] == 2
    assert CFG["mamba_n_heads"] * 2 == share["mamba_heads_of"] == 32
    assert CFG["mamba_n_groups"] * 2 == share["mamba_groups_of"] == 2
    assert CFG["num_attention_heads"] * 2 == share["attention_heads_of"] == 20
    assert CFG["num_key_value_heads"] * 2 == share["kv_heads_of"] == 4
    assert share["ssm_columns_held"] * 2 == CFG["mamba_d_ssm"] == 4096
    assert share["dense_columns_held"] * 2 == CFG["intermediate_size"]
    assert CFG["deployment"].startswith("Two chips share each layer by "
                                        "tensor parallelism and 18 pipeline")
    assert "vocabulary parallelism" in CFG["deployment"]
    assert "no code stands in" in CFG["deployment"]
    assert CFG["kwargs"] == {"seq_len": 4096, "dtype": "bfloat16"}
    assert CFG["input_shape"] == [1, 1, 4096]
    assert CFG["num_classes"] == CFG["vocab_size"]
    # every multiplier's placement, with its source
    for key in MULTIPLIERS:
        assert "FalconH1" in CFG["assumed"][key], key
    for topic in ("block", "mamba2", "conv_weight", "attention", "weights",
                  "dtype", "optimizer", "objective"):
        assert CFG["assumed"][topic]
    assert "ONE n = RMSNorm(h)" in CFG["assumed"]["block"]
    assert "the bias is NOT scaled" in CFG["assumed"]["ssm_multipliers"]
    assert "the gate first" in CFG["assumed"]["mamba2"]
    assert "MEASURED" in CFG["assumed"]["weights"]
    assert "A_log = log(U(1, 16))" in CFG["assumed"]["weights"]
    manifest = _entry("configs", "falcon_h1_34b")
    assert manifest["reduced"] == CFG["reduced"]
    assert manifest["source"] == CFG["source"]
    assert manifest["file"] == "bench/configs/falcon_h1_34b.json"


def test_the_symbol_holds_the_parameters_the_cut_counted():
    """ISSUE 49's arithmetic: a held layer 215.07 M (attention 15.73 M:
    6.55 + 1.31 + 1.31 + 6.55; Mamba-2 34.18 M: ``in_proj`` 5120 x 4624
    = 23.67, ``out_proj`` 10.49; MLP 165.15 M), four 860.3 M; embedding
    and head 334.2 M: 1,194.5 M."""
    import numpy as np

    sym = lib.resolve(CFG["factory"])(CFG, **CFG["kwargs"])
    shapes, _, _ = sym.infer_shape(data=(1, 4096), softmax_label=(1, 4096))
    sizes = {n: int(np.prod(s)) for n, s in zip(sym.list_arguments(), shapes)
             if n not in ("data", "softmax_label")}
    assert sizes["layer0_in_proj_weight"] == 5120 * 4624
    assert sizes["layer0_out_proj_weight"] == 2048 * 5120
    assert sizes["layer0_ssm_conv_weight"] == 4 * 2560
    assert sizes["layer0_ssm_conv_bias"] == 2560
    assert sizes["layer0_ssm_norm_gamma"] == 2048
    for name in ("a_log", "dt_bias", "d"):
        assert sizes["layer0_ssm_" + name] == 16
    assert sizes["layer3_q_proj_weight"] == sizes["layer3_o_proj_weight"] \
        == 5120 * 1280
    assert sizes["layer3_k_proj_weight"] == sizes["layer3_v_proj_weight"] \
        == 5120 * 256
    for name in ("gate", "up", "down"):
        assert sizes["layer2_%s_proj_weight" % name] == 5120 * 10752
    assert sizes["embed_weight"] == sizes["lm_head_weight"] == 32640 * 5120

    def part(keep):
        return sum(v for k, v in sizes.items()
                   if k.startswith("layer0_") and keep(k))

    assert part(lambda k: k[7] in "qkvo" and k[8] == "_") \
        == pytest.approx(15.73e6, rel=1e-3)
    assert part(lambda k: "ssm" in k or "in_proj" in k or "out_proj" in k) \
        == pytest.approx(34.18e6, rel=1e-3)
    assert part(lambda k: k[7:11] in ("gate", "up_p", "down")) \
        == pytest.approx(165.15e6, rel=1e-4)
    assert part(lambda k: True) == pytest.approx(215.07e6, rel=1e-4)
    assert sum(sizes.values()) == 1194499264


def test_forward_flops_and_bytes_match_the_hand_count():
    """Per sequence of 4096, forward, THIS chip's: head 2 x 4096 x 5120 x
    32640 = 1.369 T; a layer's SwiGLU 2 x 4096 x 3 x 5120 x 10752 = 1.353
    T, Mamba-2's projections 2 x 4096 x 5120 x (4624 + 2048) = 0.280 T,
    attention's 2 x 4096 x 5120 x 128 x 24 = 0.129 T and its kernel 2 x 10
    x 256 x 4096 x 4097 / 2 = 0.043 T, the scan 2 x 4096 x (64.5 x 2304 +
    2 x 2048 x 256) = 0.0098 T. 25.9 T a training step."""
    fn = lib.load_module("flops", CFG["flops"])
    t, d = 4096, 5120
    assert fn.layers(CFG) == 4
    assert fn.mamba_projection_flops(CFG) == 2.0 * t * d * (4624 + 2048)
    assert fn.scan_flops(CFG) == 2.0 * t * (64.5 * (256 + 2048)
                                            + 2 * 2048 * 256)
    assert fn.scan_bytes(CFG) == 2.0 * t * (2 * 2048 + 2 * 256 + 16)
    assert fn.attention_projection_flops(CFG) == 2.0 * t * d * 128 * 24
    assert fn.attn_kernel_flops(CFG) == 2.0 * 10 * 256 * t * (t + 1) / 2
    assert fn.mlp_flops(CFG) == 2.0 * t * 3 * d * 10752
    assert fn.head_flops(CFG) == 2.0 * t * d * 32640
    want = fn.head_flops(CFG) + 4 * (
        fn.mamba_projection_flops(CFG) + fn.scan_flops(CFG)
        + fn.attention_projection_flops(CFG) + fn.attn_kernel_flops(CFG)
        + fn.mlp_flops(CFG))
    assert fn.forward_flops_per_sample(CFG) == pytest.approx(want, rel=1e-12)
    assert 3 * want == pytest.approx(25.9e12, rel=5e-3)
    # ISSUE 49's shares of the step's operations
    for part, share in ((4 * fn.mlp_flops(CFG), 0.626),
                        (4 * fn.mamba_projection_flops(CFG), 0.130),
                        (4 * fn.attention_projection_flops(CFG), 0.060),
                        (4 * fn.attn_kernel_flops(CFG), 0.020),
                        (fn.head_flops(CFG), 0.158)):
        assert part / want == pytest.approx(share, abs=0.003)
    assert 4 * fn.scan_flops(CFG) / want < 0.01
    # at state 256 on heads of 128 the scan's two bounds are near each
    # other on the v5e's peaks: 0.0498 ms of operations, 0.0462 of bytes
    peaks = lib.load_json(lib.BENCH + "/peaks.json")["TPU v5 lite"]
    assert 1e3 * fn.scan_flops(CFG) / peaks["bf16_flops"] == pytest.approx(
        0.0498, abs=0.0005)
    assert 1e3 * fn.scan_bytes(CFG) / peaks["hbm_bytes_s"] == pytest.approx(
        0.0462, abs=0.0005)
    assert fn.TRAIN_MULTIPLIER == 3


def test_forward_flops_at_the_rehearsal_size_match_a_hand_count():
    """The rehearsal's tiny share (hidden 48, 2 Mamba-2 heads of 8 in one
    group on state 16, chunks of 8, 5 query heads on 1 of 8, 40 of 80
    columns, vocabulary 512, T 120, two layers), by hand."""
    fn = lib.load_module("flops", CFG["flops"])
    tiny = lib.load_json(lib.BENCH + "/tests/rehearsal/%s.json" % CELL)
    cfg = lib.merge(CFG, tiny["config"])
    t = 120
    head = 2 * t * 48 * 512
    proj = 2 * t * 48 * ((2 * 16 + 2 * 16 + 2) + 16)
    scan = 2 * t * (4.5 * (16 + 16) + 2 * 16 * 16)
    attn = 2 * t * 48 * 8 * 12 + 2 * 5 * 16 * t * (t + 1) // 2
    mlp = 2 * t * 3 * 48 * 40
    assert fn.forward_flops_per_sample(cfg) == head + 2 * (
        proj + scan + attn + mlp)
    assert fn.scan_bytes(cfg) == 2 * t * (32 + 32 + 2)


def test_the_two_copies_of_the_reference_are_one_text():
    import mxnet_tpu.models.falcon_h1_reference as theirs

    with open(lib.find("reference", CFG["reference"], ".py")) as ours, \
            open(theirs.__file__) as program:
        assert ours.read() == program.read()


# scope paths as the step compiled for the chip names them
_STEP = "jit(step)/fwd_bwd/"
SCOPES = {
    "fusion.1": _STEP + "jvp(ssm/layer0_ssm)/jit(_mamba2_block)/scan/mul:",
    "fusion.2": _STEP + "jvp(ssm/layer1_ssm)/jit(_mamba2_block)/conv1d/"
                        "taps_fwd_bf16_t1024_c512_k4_bias_silu/pallas_call:",
    "fusion.3": _STEP + "transpose(jvp(ssm/layer2_ssm))/"
                        "jit(_mamba2_block)/scan/ssd_bwd_bf16_q128_p128_"
                        "n256/pallas_call:",
    "fusion.4": _STEP + "transpose(jvp(ssm/layer0_ssm))/"
                        "jit(_mamba2_block)/gate_norm/gate_norm/checkpoint/"
                        "mul:",
    "fusion.5": _STEP + "jvp(ssm/layer0_ssm)/slice:",
    "fusion.6": _STEP + "jvp(attn/layer0_attn)/full/flash_fwd_bf16_q1024_"
                        "k1024/pallas_call:",
    "fusion.7": _STEP + "transpose(jvp(attn/layer3_attn))/full/flash_bwd_"
                        "bf16_q1024_k1024/pallas_call:",
    "fusion.8": _STEP + "jvp(attn/layer0_attn)/transpose:",
    "fusion.9": _STEP + "jvp(attn/layer0_k_rope)/concatenate:",
    "fusion.10": _STEP + "jvp(fc/layer0_in_proj)/dot_general:",
    "fusion.11": _STEP + "transpose(jvp(fc/layer2_o_proj))/dot_general:",
    "fusion.12": _STEP + "jvp(act/layer1_k_proj_scale)/mul:",
    "fusion.13": _STEP + "jvp(act/layer1_mixer_sum)/add:",
    "fusion.14": _STEP + "jvp(act/layer1_mixer_add)/add:",
    "fusion.15": _STEP + "jvp(fc/layer3_down_proj)/dot_general:",
    "fusion.16": _STEP + "transpose(jvp(act/layer0_gate_proj_scale))/mul:",
    "fusion.17": _STEP + "jvp(act/layer0_down_proj_scale)/convert:",
    "fusion.18": _STEP + "jvp(fc/layer1_shared_gate_proj)/dot_general:",
    "fusion.19": _STEP + "jvp(act/activation3)/logistic:",
    "fusion.20": _STEP + "jvp(norm/layer0_norm)/rsqrt:",
    "fusion.21": _STEP + "jvp(fc/lm_head)/dot_general:",
    "fusion.22": _STEP + "jvp(act/embed_scale)/mul:",
    "fusion.23": _STEP + "jvp(fc/layer0_gdn_q_proj)/dot_general:",
}
FILED = {
    "fusion.1": "scan", "fusion.2": "conv1d", "fusion.3": "scan",
    "fusion.4": "gate_norm", "fusion.5": "ssm_other", "fusion.6": "attn_full",
    "fusion.7": "attn_full", "fusion.8": "attn_other",
    "fusion.9": "attn_other", "fusion.10": "mixer_proj",
    "fusion.11": "mixer_proj", "fusion.12": "mixer_scale",
    "fusion.13": "mixer_scale", "fusion.14": "mixer_scale",
    "fusion.15": "mlp", "fusion.16": "mlp", "fusion.17": "mlp",
    "fusion.18": None, "fusion.19": None, "fusion.20": None,
    "fusion.21": None, "fusion.22": None, "fusion.23": None}


def test_the_table_files_every_op_of_the_block_under_its_owner():
    assert {k: h1_scopes.part_of(v) for k, v in SCOPES.items()} == FILED
    # the convolution's scope is not the class of the Convolution nodes
    import reduce_scopes

    assert not reduce_scopes._CLASS.search(SCOPES["fusion.2"])
    assert list(h1_scopes.TABLE) == [
        "conv1d", "scan", "gate_norm", "ssm_other", "attn_full",
        "attn_other", "mixer_proj", "mixer_scale", "mlp"]


def test_the_reduction_sums_the_parts_and_needs_both_mixers_in_a_layer():
    import reduce_trace

    ops = [("fusion.%d" % i, 1000 * i, 100) for i in range(1, 24)]
    raw = {"host": [(0, reduce_trace.SLICE_BEGIN, 0, 10),
                    (0, reduce_trace.SLICE_END, 40000, 10)],
           "devices": {0: {"ops": ops}}}
    red = h1_scopes.reduce(raw, {0: SCOPES})
    assert red["scan"] == red["attn_full"] == pytest.approx(200e-9)
    assert red["ssm"] == pytest.approx(500e-9)
    assert red["mixer"] == pytest.approx(1400e-9)
    assert red["mlp"] == red["mixer_scale"] == pytest.approx(300e-9)
    # a model whose layers hold one mixer or the other (Nemotron's): the
    # scopes are all there, no layer holds both
    apart = {k: v.replace("attn/layer0", "attn/layer5").replace(
        "attn/layer3", "attn/layer12") for k, v in SCOPES.items()
        if "layer1_ssm" not in v and "layer2_ssm" not in v}
    assert h1_scopes.reduce(raw, {0: apart}) is None
    rest = {k: v for k, v in SCOPES.items() if "ssm/" not in v}
    assert h1_scopes.reduce(raw, {0: rest}) is None
    assert h1_scopes.reduce(dict(raw, host=[]), {0: SCOPES}) is None


def _run(**over):
    run = {"cfg": CFG, "cell": {"name": CELL}, "trace_steps": 5,
           "batch": 1, "chips": 1, "flops_multiplier": 3,
           "peak": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
           "h1_scopes": {"conv1d": 0.010, "scan": 0.025, "gate_norm": 0.040,
                         "ssm_other": 0.005, "attn_full": 0.045,
                         "attn_other": 0.005, "mixer_proj": 0.200,
                         "mixer_scale": 0.010, "mlp": 0.500, "ssm": 0.080,
                         "mixer": 0.340}}
    run.update(over)
    return run


def _read(name, run, trace=True):
    return lib.load_module("layer_metrics", name).compute(
        {"devices": {}} if trace else None, {"telemetry": {}}, run)


READERS = ["h1_mixer_device_ms", "h1_ssm_device_ms", "h1_scan_device_ms",
           "h1_scan_roofline_share", "h1_attn_device_ms", "h1_mlp_device_ms"]


def test_the_six_readers_read_what_they_say():
    run = _run()
    assert _read("h1_mixer_device_ms", run) == pytest.approx(68.0)
    assert _read("h1_ssm_device_ms", run) == pytest.approx(16.0)
    assert _read("h1_scan_device_ms", run) == pytest.approx(5.0)
    assert _read("h1_attn_device_ms", run) == pytest.approx(9.0)
    assert _read("h1_mlp_device_ms", run) == pytest.approx(100.0)
    # four layers, three forwards each, bound by operations: 12 x 0.0498
    # ms of 5
    assert _read("h1_scan_roofline_share", run) == pytest.approx(
        100 * 12 * 0.04978 / 5.0, rel=1e-3)
    assert _read("h1_scan_roofline_share", run) < 100


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_where_there_is_nothing(name):
    """No slice, no such scopes (the parent's program, another model's),
    a configuration with another operations module: None, never zero,
    never a raise (the benchmark's files are laid over older
    checkouts)."""
    nemotron = lib.load_json(lib.find(
        "configs", "nemotron_3_nano_30b_a3b", ".json"))
    assert _read(name, _run(h1_scopes=None)) is None
    assert _read(name, _run(), trace=False) is None
    assert _read(name, _run(cfg=nemotron, h1_scopes=None)) is None
    assert _read(name, _run(trace_steps=0)) is None
    if name == "h1_scan_roofline_share":
        assert _read(name, _run(cfg=nemotron)) is None
        assert _read(name, _run(peak=None)) is None
    entry = _entry("per_layer", name)
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "train_samples_s"
    assert entry["layer"] == "ops and kernels"
    assert entry["source"] == "device_trace"
    assert entry["unit"] == ("%" if name.endswith("share") else "ms/step")


def test_the_cell_and_the_mix_it_shares():
    cell = lib.load_json(lib.find("cells", CELL, ".json"))
    olmo = lib.load_json(lib.find("cells", "olmo_hybrid_fit_stage_4k",
                                  ".json"))
    # the Olmo-Hybrid cell's mix and kind as they stand: nothing new
    assert cell["traffic"] == olmo["traffic"] \
        == "fit_tokens_dense_resident_b1_t4096"
    assert cell["chips"] == 1 and cell["config"] == "falcon_h1_34b"
    mix = lib.load_json(lib.find("traffic", cell["traffic"], ".json"))
    assert mix["kind"] == "fit_tokens_dense" and mix["batch"] == 1
    assert mix["optimizer_params"] == {"learning_rate": 0.01,
                                       "momentum": 0.9}
    assert set(cell["expect"]) == set(olmo["expect"])
    assert set(cell["expect"]["reference"]) == set(
        olmo["expect"]["reference"])
    assert cell["expect"]["reference"]["near_tie_share_max"] == 0.0
    # half the variance of the logits at the stated initialisation: the
    # head is Normal(1 / (sqrt(5120) / 128)), so that the scaled logits
    # of a unit-rms vector have unit variance
    assert cell["expect"]["first_loss_excess"] == pytest.approx(0.5)
    assert _entry("workloads", CELL) == {
        "name": CELL, "config": "falcon_h1_34b", "traffic": cell["traffic"],
        "chips": 1, "why": cell["why"]}
    manifest = lib.load_json(lib.MANIFEST)
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    # the metrics without a list of cells read here as they stand
    for name in ("model_mfu", "step_device_ms", "step_unscoped_device_ms",
                 "device_idle_share", "hbm_peak_gb"):
        assert "workloads" not in _entry("per_layer", name)


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
