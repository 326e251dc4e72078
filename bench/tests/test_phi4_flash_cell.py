"""What the ``phi4_mini_flash`` configuration brought: its file against the
published keys, the parameters the cut counted, its operations and bytes
against the hand count (at the cell's size and at the rehearsal's), the
benchmark's copy of the reference against the program's, the table of
``sscan_scopes`` on scope paths, the seven readers on handed-in reductions,
the cell on the dense kind's mix, and the cell's rehearsal end to end."""
import pytest

import lib
import sscan_scopes
from helpers import check_rehearsal, run_bench

CFG = lib.load_json(lib.find("configs", "phi4_mini_flash", ".json"))
CELL = "phi4_flash_fit_stage_4k"
# microsoft/Phi-4-mini-flash-reasoning's config.json, the keys that say its
# shape (the model-configs catalog's ``config``)
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}
WIDTHS = ("hidden_size", "intermediate_size", "num_attention_heads",
          "num_key_value_heads", "sliding_window", "mb_per_layer")
HELD = [0, 1, 2, 3, 16, 17, 18, 19, 20, 21]


def test_configuration_keeps_every_published_width_and_states_its_cut():
    changed = {k for k, v in PUBLISHED.items() if CFG[k] != v}
    assert changed == {"num_hidden_layers", "vocab_size"}
    # the key that lists the kept layers is the file's own: no published
    # key, and in ``reduced`` beside the two
    assert set(CFG["reduced"]) == set(CFG["reduced_why"]) == changed | {
        "layers_held"}
    assert not changed & set(WIDTHS)
    for key in changed:           # the uncut value stands beside the held
        assert CFG["published"][key] == PUBLISHED[key]
    # 2 : 1 : 2 pairs of the published 8 : 1 : 7, every kind of layer, the
    # floor of an eighth of the vocabulary
    assert CFG["layers_held"] == HELD
    assert CFG["num_hidden_layers"] == len(HELD) == 10
    assert CFG["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert CFG["assumed_sizes"] == {"state_size": 16, "conv_kernel": 4,
                                    "expand": 2, "dt_rank": 160}
    assert "vocabulary parallelism" in CFG["deployment"]
    assert "K*, V* and m" in CFG["deployment"]
    assert CFG["kwargs"] == {"seq_len": 4096, "dtype": "bfloat16"}
    assert CFG["input_shape"] == [1, 1, 4096]
    assert CFG["num_classes"] == CFG["vocab_size"]
    for topic in ("mamba_sizes", "order", "mamba", "attention",
                  "conv_weight", "weights", "dtype", "optimizer",
                  "objective"):
        assert CFG["assumed"][topic]
    assert "NEIGHBOURING heads" in CFG["assumed"]["attention"]
    assert "lambda_init = 0.8 - 0.6 exp(-0.3 l)" in CFG["assumed"]["attention"]
    assert "no positional signal" in CFG["assumed"]["order"]
    assert "A_log = log(1..16)" in CFG["assumed"]["weights"]
    assert "Normal(0.02), NOT the Normal(1)" in CFG["assumed"]["weights"]
    assert "GB" in CFG["reduced_why"]["num_hidden_layers"]
    manifest = [c for c in lib.load_json(lib.MANIFEST)["configs"]
                if c["name"] == "phi4_mini_flash"][0]
    assert manifest["reduced"] == CFG["reduced"]
    assert manifest["source"] == CFG["source"]


def test_the_symbol_holds_the_parameters_the_cut_counted():
    """ISSUE 73's arithmetic: a Mamba mixer 41.2 M (in_proj 2560 x 10240 =
    26.2 M, x_proj 5120 x 192 = 0.98 M, dt_proj 160 x 5120 = 0.82 M,
    out_proj 5120 x 2560 = 13.1 M, taps and vectors 0.12 M), a
    self-attention mixer 19.7 M (q and o 2560^2, k and v 2560 x 1280, four
    biases), a cross mixer 13.1 M (q and o alone), a GMU 26.2 M, every
    layer's SwiGLU 3 x 2560 x 10240 = 78.6 M, the tied matrix 25008 x 2560
    = 64.0 M: 1,111.9 M."""
    import numpy as np

    sym = lib.resolve(CFG["factory"])(CFG, **CFG["kwargs"])
    shapes, _, _ = sym.infer_shape(data=(1, 4096), softmax_label=(1, 4096))
    sizes = {n: int(np.prod(s)) for n, s in zip(sym.list_arguments(), shapes)
             if n not in ("data", "softmax_label")}
    assert sizes["layer0_mamba_in_proj_weight"] == 2560 * 10240
    assert sizes["layer0_mamba_x_proj_weight"] == 5120 * (160 + 32)
    assert sizes["layer16_mamba_dt_proj_weight"] == 160 * 5120
    assert sizes["layer2_mamba_out_proj_weight"] == 5120 * 2560
    assert sizes["layer0_mamba_conv_weight"] == 4 * 5120
    assert sizes["layer0_mamba_a_log"] == 5120 * 16
    for name in ("conv_bias", "dt_bias", "d"):
        assert sizes["layer0_mamba_" + name] == 5120
    for name, width in (("q", 2560), ("k", 1280), ("v", 1280), ("o", 2560)):
        assert sizes["layer17_%s_proj_weight" % name] == 2560 * width
        assert sizes["layer1_%s_proj_bias" % name] == width
    assert "layer19_k_proj_weight" not in sizes          # layer 17's
    assert "layer21_v_proj_bias" not in sizes
    assert sizes["layer19_q_proj_weight"] == 2560 * 2560
    for name in ("q1", "k1", "q2", "k2"):
        assert sizes["layer3_attn_lambda_" + name] == 64
    assert sizes["layer3_attn_subln_gamma"] == 128
    assert sizes["layer18_gmu_in_proj_weight"] == 2560 * 5120
    assert sizes["layer20_gmu_out_proj_weight"] == 5120 * 2560
    assert sizes["layer21_gate_proj_weight"] == 2560 * 10240
    assert sizes["embed_weight"] == 25008 * 2560
    assert "lm_head_weight" not in sizes                  # tied
    assert sizes["final_norm_gamma"] == sizes["final_norm_beta"] == 2560

    def part(i, keep=lambda k: True):
        return sum(v for k, v in sizes.items()
                   if k.startswith("layer%d_" % i) and keep(k))

    assert part(0, lambda k: "_mamba_" in k) == pytest.approx(41.2e6, rel=2e-3)
    assert part(0) == pytest.approx(119.8e6, rel=1e-3)
    assert part(17) == pytest.approx(98.3e6, rel=1e-3)
    assert part(18) == pytest.approx(104.9e6, rel=1e-3)
    assert part(19) == pytest.approx(91.7e6, rel=1e-3)
    assert sorted({int(k[5:k.index("_")]) for k in sizes
                   if k.startswith("layer")}) == HELD
    assert sum(sizes.values()) == pytest.approx(1111.9e6, rel=1e-4)


def test_forward_flops_and_bytes_match_the_hand_count():
    """Per sequence of 4096, forward: head 2 x 4096 x 2560 x 25008 = 0.524
    T; a Mamba layer's four projections 2 x 4096 x (2560 x 15360 + 5120 x
    192 + 160 x 5120) = 0.337 T; an attention layer's projections 0.161 T
    (0.107 T in a cross layer) and its two maps a pair over the triangle
    2 x 4096 x 2048.5 x 20 x 2 x 192 = 0.129 T (0.0302 T under the
    window); a GMU 0.215 T; a SwiGLU 0.644 T. 28.7 T a training step."""
    fn = lib.load_module("flops", CFG["flops"])
    t, d = 4096, 2560
    assert [fn.layers(CFG, k) for k in fn.KINDS] == [2, 2, 1, 1, 2, 2]
    assert fn.layers(CFG) == 10 and fn.mamba_layers(CFG) == 3
    assert fn.diff_layers(CFG) == 5
    assert fn.mamba_projection_flops(CFG) == 2.0 * t * (
        d * 15360 + 5120 * 192 + 160 * 5120)
    assert fn.diff_projection_flops(CFG, "full") == 2.0 * t * d * (
        2 * 2560 + 2 * 1280)
    assert fn.diff_projection_flops(CFG, "cross") == 2.0 * t * d * 2 * 2560
    assert fn.diff_attn_flops(CFG, "full") == \
        2.0 * t * (t + 1) / 2 * 20 * 2 * 192
    assert fn.diff_attn_flops(CFG, "cross") == fn.diff_attn_flops(CFG, "full")
    band = (512 * 513 / 2 + (t - 512) * 512) / t
    assert fn.diff_attn_flops(CFG, "window") == pytest.approx(
        2.0 * t * band * 20 * 2 * 192, rel=1e-12)
    assert fn.gmu_flops(CFG) == 2.0 * t * d * 2 * 5120
    assert fn.mlp_flops(CFG) == 2.0 * t * 3 * d * 10240
    want = (2.0 * t * d * 25008 + 3 * fn.mamba_projection_flops(CFG)
            + 3 * fn.diff_projection_flops(CFG, "full")
            + 2 * fn.diff_projection_flops(CFG, "cross")
            + 3 * fn.diff_attn_flops(CFG, "full")
            + 2 * fn.diff_attn_flops(CFG, "window")
            + 2 * fn.gmu_flops(CFG) + 10 * fn.mlp_flops(CFG))
    assert fn.forward_flops_per_sample(CFG) == pytest.approx(want, rel=1e-12)
    assert 3 * want == pytest.approx(28.66e12, rel=2e-3)
    assert fn.diff_attn_flops_held(CFG) == pytest.approx(
        3 * fn.diff_attn_flops(CFG, "full")
        + 2 * fn.diff_attn_flops(CFG, "window"), rel=1e-12)
    # the dense SwiGLUs are two thirds of the step's operations, the head
    # 5.5%, the ten flash calls 4.7%
    assert 10 * fn.mlp_flops(CFG) / want == pytest.approx(0.674, abs=0.005)
    assert 2.0 * t * d * 25008 / want == pytest.approx(0.055, abs=0.002)
    assert fn.diff_attn_flops_held(CFG) / want == pytest.approx(
        0.047, abs=0.002)
    # the selective scan: x and m in bf16, dt in float32, B and C: 168 MB,
    # 0.205 ms a layer forward at the HBM peak; 2.41 G elementwise
    # operations that peaks.json has no peak for
    assert fn.sscan_bytes(CFG) == t * (5120 * 8 + 2 * 16 * 2)
    assert fn.sscan_flops(CFG) == t * 5120 * (7 * 16 + 3)
    peaks = lib.load_json(lib.BENCH + "/peaks.json")["TPU v5 lite"]
    assert 1e3 * fn.sscan_bytes(CFG) / peaks["hbm_bytes_s"] == pytest.approx(
        0.2052, abs=0.001)
    assert set(peaks) >= {"bf16_flops", "hbm_bytes_s"}
    assert not [k for k in peaks if "vector" in k or "vpu" in k]
    assert fn.TRAIN_MULTIPLIER == 3


def test_forward_flops_at_the_rehearsal_size_match_a_hand_count():
    """The rehearsal's tiny model (hidden 64, SwiGLU 96, Mamba of 128
    channels with dt_rank 4, 8 heads on 4 of 8 under a window of 40,
    vocabulary 512, T 160; the ten held layers), by hand."""
    fn = lib.load_module("flops", CFG["flops"])
    tiny = lib.load_json(lib.BENCH + "/tests/rehearsal/%s.json" % CELL)
    cfg = lib.merge(CFG, tiny["config"])
    t = 160
    head = 2 * t * 64 * 512
    mamba = 2 * t * (64 * 384 + 128 * 36 + 4 * 128)
    own = 2 * t * 64 * (128 + 64)
    cross = 2 * t * 64 * 128
    full = 2 * t * (t + 1) // 2 * 4 * 2 * 24
    window = 2 * (40 * 41 // 2 + (t - 40) * 40) * 4 * 2 * 24
    gmu = 2 * t * 64 * 256
    mlp = 2 * t * 3 * 64 * 96
    assert fn.forward_flops_per_sample(cfg) == pytest.approx(
        head + 3 * mamba + 3 * own + 2 * cross + 3 * full + 2 * window
        + 2 * gmu + 10 * mlp, rel=1e-12)
    assert fn.sscan_bytes(cfg) == t * (128 * 8 + 64)


def test_the_two_copies_of_the_reference_are_one_text():
    import mxnet_tpu.models.phi4_flash_reference as theirs

    with open(lib.find("reference", CFG["reference"], ".py")) as ours, \
            open(theirs.__file__) as program:
        assert ours.read() == program.read()


# scope paths as the step compiled for the chip names them
SCOPES = {
    "fusion.1": "jit(step)/fwd_bwd/jvp(ssm/layer0_mamba)/"
                "jit(_mamba1_block)/sscan/jit(_sscan_forward)/"
                "sscan_fwd_bf16_q128_w512_n16:",
    "fusion.2": "jit(step)/fwd_bwd/transpose(jvp(ssm/layer16_mamba))/"
                "jit(_mamba1_block)/sscan/sscan_bwd_bf16_q128_w512_n16:",
    "fusion.3": "jit(step)/fwd_bwd/jvp(ssm/layer2_mamba)/"
                "jit(_mamba1_block)/conv1d/jit(silu):",
    "fusion.4": "jit(step)/fwd_bwd/jvp(ssm/layer2_mamba)/"
                "jit(_mamba1_block)/dt_proj/jit(softplus)/add:",
    "fusion.5": "jit(step)/fwd_bwd/transpose(jvp(ssm/layer2_mamba))/"
                "jit(_mamba1_block)/x_proj/dot_general:",
    "fusion.6": "jit(step)/fwd_bwd/transpose(jvp(ssm/layer0_mamba))/"
                "jit(_mamba1_block)/gate/gate/checkpoint/mul:",
    "fusion.7": "jit(step)/fwd_bwd/jvp(ssm/layer0_mamba)/slice:",
    "fusion.8": "jit(step)/fwd_bwd/jvp(fc/layer16_mamba_in_proj)/"
                "dot_general:",
    "fusion.9": "jit(step)/fwd_bwd/transpose(jvp(fc/layer0_mamba_out_proj))/"
                "dot_general:",
    "fusion.10": "jit(step)/fwd_bwd/jvp(attn/layer1_attn)/diff/window/"
                 "flash_fwd_bf16_q512_k512_w512:",
    "fusion.11": "jit(step)/fwd_bwd/transpose(jvp(attn/layer17_attn))/"
                 "diff/full/flash_bwd_bf16_q1024_k1024:",
    "fusion.12": "jit(step)/fwd_bwd/transpose(jvp(attn/layer19_attn))/"
                 "diff/cross/flash_bwd_bf16_q1024_k1024:",
    "fusion.13": "jit(step)/fwd_bwd/jvp(attn/layer21_attn)/diff/combine/"
                 "rsqrt:",
    "fusion.14": "jit(step)/fwd_bwd/jvp(fc/layer18_gmu_in_proj)/dot_general:",
    "fusion.15": "jit(step)/fwd_bwd/transpose(jvp(act/layer20_gmu))/mul:",
    "fusion.16": "jit(step)/fwd_bwd/jvp(act/layer18_gmu_gate)/logistic:",
    "fusion.17": "jit(step)/fwd_bwd/jvp(fc/layer18_gate_proj)/dot_general:",
    "fusion.18": "jit(step)/fwd_bwd/jvp(attn/layer3_attn)/window/"
                 "flash_fwd_bf16_q512_k512_w128:",
    "fusion.19": "jit(step)/fwd_bwd/jvp(ssm/layer0_ssm)/scan/mul:",
    "fusion.20": "jit(step)/fwd_bwd/jvp(fc/layer1_q_proj)/dot_general:",
}


def test_the_table_files_the_nodes_scopes_and_finds_the_projections():
    assert {k: sscan_scopes.part_of(v) for k, v in SCOPES.items()} == {
        "fusion.1": "sscan", "fusion.2": "sscan", "fusion.3": "conv1d",
        "fusion.4": "dt_proj", "fusion.5": "x_proj", "fusion.6": "gate",
        "fusion.7": "mamba_other", "fusion.8": "mamba_proj",
        "fusion.9": "mamba_proj", "fusion.10": "diff_window",
        "fusion.11": "diff_full", "fusion.12": "diff_cross",
        "fusion.13": "diff_combine", "fusion.14": "gmu", "fusion.15": "gmu",
        "fusion.16": "gmu", "fusion.17": None,
        # another model's window attention is an attn node without diff/;
        # a Mamba2 node files under mamba_other, and the reduction below
        # refuses a program that has no sscan scope at all
        "fusion.18": None, "fusion.19": "mamba_other", "fusion.20": None}
    # share_scopes' window and full are not reached by diff/window
    import share_scopes

    assert not share_scopes._KIND.search(SCOPES["fusion.10"])
    assert share_scopes._KIND.search(SCOPES["fusion.18"])


def test_the_reduction_sums_the_parts_and_needs_a_selective_scan():
    import reduce_trace

    ops = [("fusion.%d" % i, 1000 * i, 100) for i in range(1, 21)]
    raw = {"host": [(0, reduce_trace.SLICE_BEGIN, 0, 10),
                    (0, reduce_trace.SLICE_END, 40000, 10)],
           "devices": {0: {"ops": ops}}}
    red = sscan_scopes.reduce(raw, {0: SCOPES})
    assert red["sscan"] == pytest.approx(200e-9)
    assert red["mamba_proj"] == pytest.approx(200e-9)
    # seven of its own and the Mamba2 node of fusion.19
    assert red["mamba"] == pytest.approx(1000e-9)
    assert red["flash"] == pytest.approx(300e-9)
    assert red["diff"] == pytest.approx(400e-9)
    assert red["diff_cross"] == pytest.approx(100e-9)
    assert red["gmu"] == pytest.approx(300e-9)
    rest = {k: v for k, v in SCOPES.items() if "/sscan/" not in v}
    assert sscan_scopes.reduce(raw, {0: rest}) is None
    assert sscan_scopes.reduce(dict(raw, host=[]), {0: SCOPES}) is None


def _run(**over):
    run = {"cfg": CFG, "cell": {"name": CELL}, "trace_steps": 5,
           "batch": 1, "chips": 1, "flops_multiplier": 3,
           "peak": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
           "sscan_scopes": {"sscan": 0.060, "conv1d": 0.007,
                            "x_proj": 0.003, "dt_proj": 0.009,
                            "gate": 0.006, "mamba_other": None,
                            "mamba_proj": 0.082, "mamba": 0.167,
                            "diff_window": 0.021, "diff_full": 0.021,
                            "diff_cross": 0.042, "diff_combine": 0.005,
                            "flash": 0.084, "diff": 0.089, "gmu": 0.037}}
    run.update(over)
    return run


def _read(name, run, trace=True):
    return lib.load_module("layer_metrics", name).compute(
        {"devices": {}} if trace else None, {"telemetry": {}}, run)


READERS = ["sscan_device_ms", "sscan_roofline_share", "mamba1_device_ms",
           "diff_attn_device_ms", "diff_attn_roofline_share",
           "cross_attn_device_ms", "gmu_device_ms"]


def test_the_seven_readers_read_what_they_say():
    run = _run()
    assert _read("sscan_device_ms", run) == pytest.approx(12.0)
    assert _read("mamba1_device_ms", run) == pytest.approx(33.4)
    assert _read("diff_attn_device_ms", run) == pytest.approx(17.8)
    assert _read("cross_attn_device_ms", run) == pytest.approx(8.4)
    assert _read("gmu_device_ms", run) == pytest.approx(7.4)
    # three layers, three forwards each, bytes alone: 9 x 0.2052 ms of 12
    assert _read("sscan_roofline_share", run) == pytest.approx(
        100 * 9 * 0.20517 / 12.0, rel=1e-3)
    # 3 x (3 full-sized layers at 0.6546 ms + 2 window layers at 0.1534)
    # of 16.8 ms, bound by operations
    fn = lib.load_module("flops", CFG["flops"])
    least = 3 * fn.diff_attn_flops_held(CFG) / 197e12
    assert _read("diff_attn_roofline_share", run) == pytest.approx(
        100 * 1e3 * least / 16.8, rel=1e-6)
    assert 35 < _read("diff_attn_roofline_share", run) < 45
    assert _read("sscan_roofline_share", run) < 100


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_where_there_is_nothing(name):
    """No slice, no such scopes (the parent's program, another model's),
    a configuration with another operations module: None, never zero,
    never a raise (the benchmark's files are laid over older
    checkouts)."""
    nemotron = lib.load_json(lib.find(
        "configs", "nemotron_3_nano_30b_a3b", ".json"))
    assert _read(name, _run(sscan_scopes=None)) is None
    assert _read(name, _run(), trace=False) is None
    assert _read(name, _run(cfg=nemotron, sscan_scopes=None)) is None
    assert _read(name, _run(trace_steps=0)) is None
    if name.endswith("roofline_share"):
        assert _read(name, _run(cfg=nemotron)) is None
        assert _read(name, _run(peak=None)) is None
    entry = [m for m in lib.load_json(lib.MANIFEST)["per_layer"]
             if m["name"] == name][0]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "train_samples_s"
    assert entry["layer"] == "ops and kernels"
    assert entry["source"] == "device_trace"


def test_the_cell_takes_the_dense_kinds_mix_as_it_stands():
    cell = lib.load_json(lib.find("cells", CELL, ".json"))
    olmo = lib.load_json(lib.find("cells", "olmo_hybrid_fit_stage_4k",
                                  ".json"))
    assert cell["traffic"] == olmo["traffic"] == \
        "fit_tokens_dense_resident_b1_t4096"
    assert cell["chips"] == 1
    assert set(cell["expect"]) == set(olmo["expect"])
    assert set(cell["expect"]["reference"]) == set(
        olmo["expect"]["reference"])
    assert cell["expect"]["reference"]["near_tie_share_max"] == 0.0
    # half the variance of logits from the tied Normal(0.02) embedding over
    # a unit-variance vector of 2560
    assert cell["expect"]["first_loss_excess"] == pytest.approx(
        0.5 * 2560 * 0.02 ** 2)
    manifest = lib.load_json(lib.MANIFEST)
    entry = [w for w in manifest["workloads"] if w["name"] == CELL][0]
    assert entry == {"name": CELL, "config": "phi4_mini_flash",
                     "traffic": cell["traffic"], "chips": 1,
                     "why": cell["why"]}
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1


def test_rehearsal_runs_the_cell_end_to_end_with_the_trace_on():
    """The whole path at a tiny size on the CPU: from_config with the ten
    held layers' published numbers, Module.fit through the fused step, the
    dense kind's checks, the reference check in float32 (where the program
    and the reference agree to summation order, and the bf16 reference does
    not) and every reader returning nothing or a value without a raise."""
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
