"""What the ``kimi_linear_48b_a3b`` configuration brought: its file against
the published keys, the parameters the cut counted, its operations and
bytes against the hand count (at the cell's size and at the
rehearsal's), the benchmark's copy of the reference against the
program's, the table of ``kda_scopes`` on scope paths, the seven readers
on handed-in reductions, the thin kind's key map, and the cell's
rehearsal end to end."""
import pytest

import gdn_scopes
import kda_scopes
import lib
import mla_scopes
from helpers import check_rehearsal, run_bench

CFG = lib.load_json(lib.find("configs", "kimi_linear_48b_a3b", ".json"))
CELL = "kimi_linear_fit_share_8k"
# moonshotai/Kimi-Linear-48B-A3B-Instruct's config.json, the keys that say
# its shape (the model-configs catalog's ``config``)
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "kv_lora_rank", "head_dim", "qk_nope_head_dim", "qk_rope_head_dim",
          "v_head_dim", "num_experts_per_token", "num_attention_heads",
          "num_shared_experts", "routed_scaling_factor")
T, D = 8192, 2304


def test_configuration_keeps_every_published_width_and_states_its_cut():
    changed = {k for k, v in PUBLISHED.items() if CFG[k] != v}
    assert changed == set(CFG["reduced"]) == set(CFG["reduced_why"]) == {
        "num_hidden_layers", "linear_attn_config", "num_experts",
        "vocab_size"}
    assert not changed & set(WIDTHS)
    for key in changed:           # the uncut value stands beside the held
        assert CFG["published"][key] == PUBLISHED[key]
    # inside the nested group only the two layer lists moved
    held, uncut = CFG["linear_attn_config"], PUBLISHED["linear_attn_config"]
    assert {k for k in uncut if held[k] != uncut[k]} == {
        "kda_layers", "full_attn_layers"}
    assert (held["num_heads"], held["head_dim"],
            held["short_conv_kernel_size"]) == (32, 128, 4)
    # the floors: the leading dense layer once and one whole period of
    # the expert layers at the published 3 : 1, 8 experts, an eighth of
    # the vocabulary
    assert CFG["num_hidden_layers"] == 5
    assert held["kda_layers"] == [1, 2, 3, 5]
    assert held["full_attn_layers"] == [4]
    assert [i in uncut["full_attn_layers"] for i in (2, 3, 4, 5)] == [
        i in held["full_attn_layers"] for i in (2, 3, 4, 5)]
    assert CFG["num_experts"] >= 8
    assert CFG["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    share = CFG["share"]
    assert share["experts_of"] == 256 and share["expert_offset"] == 0
    # three times the expected rows, not the other share cells' twice: the
    # chip read a layer's held rows up to 1.64 of the expected (the file)
    assert share["share_rows_bound"] == 3 * T * 8 * CFG["num_experts"] // 256
    assert "chips share each layer" in CFG["deployment"]
    assert "memory_peak_bytes" in CFG["deployment"]
    assert CFG["kwargs"] == {"seq_len": T, "dtype": "bfloat16"}
    assert CFG["input_shape"] == [1, 1, T]
    assert CFG["num_classes"] == CFG["vocab_size"]
    for topic in ("unread", "block", "chunk", "low_rank", "beta", "gate",
                  "unit_norm", "conv_weight", "attention", "router",
                  "shared_experts", "moe_layer_freq", "weights", "dtype",
                  "optimizer", "objective", "share_rows_bound"):
        assert CFG["assumed"][topic]
    manifest = [c for c in lib.load_json(lib.MANIFEST)["configs"]
                if c["name"] == "kimi_linear_48b_a3b"][0]
    assert manifest["reduced"] == CFG["reduced"]
    assert manifest["source"] == CFG["source"]
    assert manifest["file"] == "bench/configs/kimi_linear_48b_a3b.json"


def test_the_symbol_holds_the_parameters_the_cut_counted():
    """ISSUE 53's arithmetic: a KDA mixer 39.51 M (q, k, v, o 2304 x 4096
    = 9.44 M each; the low-rank pairs 2 x (2304 x 128 + 128 x 4096) =
    1.64 M; b 2304 x 32; taps, decays and gamma 0.05 M), the latent mixer
    29.11 M, the dense SwiGLU 3 x 2304 x 9216 = 63.70 M, an expert layer's
    shared expert 7.08 M, router 0.59 M and 7.08 M a held expert,
    embedding and head 20480 x 2304 = 47.19 M each: 602.4 M at 8 held,
    828.9 M at 16."""
    import numpy as np

    sym = lib.resolve(CFG["factory"])(CFG, **CFG["kwargs"])
    shapes, _, _ = sym.infer_shape(data=(1, T), softmax_label=(1, T))
    sizes = {n: int(np.prod(s)) for n, s in zip(sym.list_arguments(), shapes)
             if n not in ("data", "softmax_label")}
    for name in ("q", "k", "v", "o"):
        assert sizes["layer0_kda_%s_proj_weight" % name] == D * 4096
    for name in ("f", "g"):
        assert sizes["layer0_kda_%s_a_proj_weight" % name] == D * 128
        assert sizes["layer0_kda_%s_b_proj_weight" % name] == 128 * 4096
    assert sizes["layer0_kda_b_proj_weight"] == D * 32
    assert sizes["layer0_kda_conv_weight"] == 4 * 3 * 4096
    assert sizes["layer0_kda_a_log"] == 32
    assert sizes["layer0_kda_dt_bias"] == 4096
    assert sizes["layer0_kda_norm_gamma"] == 128
    assert not [n for n in sizes if n.endswith("_bias")
                and "select" not in n and "dt_bias" not in n]
    assert sizes["layer3_q_proj_weight"] == D * 32 * 192
    assert sizes["layer3_kv_a_proj_weight"] == D * (512 + 64)
    assert sizes["layer3_attn_up_weight"] == 512 * 32 * 256
    assert sizes["layer3_o_proj_weight"] == 4096 * D
    assert sizes["layer0_gate_proj_weight"] == D * 9216
    held = CFG["num_experts"]
    assert sizes["layer1_moe_gate_weight"] == D * 256
    assert sizes["layer1_moe_gate_up_weight"] == held * D * 2048
    assert sizes["layer1_moe_down_weight"] == held * 1024 * D
    assert sizes["layer1_shared_gate_proj_weight"] == D * 1024
    assert sizes["embed_weight"] == sizes["lm_head_weight"] == 20480 * D

    def part(i, keep):
        return sum(v for k, v in sizes.items()
                   if k.startswith("layer%d_" % i) and keep(k))

    assert part(0, lambda k: "_kda_" in k) == pytest.approx(39.51e6,
                                                            rel=1e-3)
    assert part(0, lambda k: True) == pytest.approx(103.2e6, rel=1e-3)
    mla = part(3, lambda k: "moe" not in k and "shared" not in k
               and "ffn" not in k)
    assert mla == pytest.approx(29.12e6, rel=1e-3)
    experts = held * 3 * D * 1024
    assert part(1, lambda k: True) - experts == pytest.approx(47.19e6,
                                                              rel=1e-3)
    assert part(3, lambda k: True) - experts == pytest.approx(36.79e6,
                                                              rel=1e-3)
    total = {8: 602.4e6, 16: 828.9e6}[held]
    assert sum(sizes.values()) == pytest.approx(total, rel=1e-4)


def test_forward_flops_and_bytes_match_the_hand_count():
    """Per sequence of 8192, forward: head 2 x 8192 x 2304 x 20480 = 0.773
    T; a KDA layer's nine projections 2 x 8192 x (4 x 2304 x 4096 + 2 x
    (2304 x 128 + 128 x 4096) + 2304 x 32) = 0.646 T and its rule in
    chunks of 64, 8192 x 32 x (6 x 64 x 128 + 6 x 128^2) = 0.039 T; the
    latent layer's projections 0.389 T and its scores and values 2 x 32 x
    320 x 8192 x 8193 / 2 = 0.687 T; the dense SwiGLU 1.044 T; an expert
    layer's shared expert 0.116 T, router 0.0097 T and 2,048 (4,096 at 16
    held) rows through an expert 0.029 T. 19.0 T a training step at 8
    held."""
    fn = lib.load_module("flops", CFG["flops"])
    assert (fn.kda_layers(CFG), fn.mla_layers(CFG),
            fn.expert_layers(CFG)) == (4, 1, 4)
    assert fn.kda_projection_flops(CFG) == 2.0 * T * (
        4 * D * 4096 + 2 * (D * 128 + 128 * 4096) + D * 32)
    assert fn.kda_chunk_flops(CFG) == float(T) * 32 * (
        6 * 64 * 128 + 6 * 128 * 128)
    assert fn.kda_core_flops(CFG) == 7.0 * T * 32 * 128 * 128
    assert fn.kda_core_bytes(CFG) == 2.0 * T * 32 * (5 * 128 + 1)
    assert fn.mla_projection_flops(CFG) == 2.0 * T * (
        D * 32 * 192 + D * 576 + 512 * 32 * 256 + 4096 * D)
    assert fn.mla_kernel_flops(CFG) == 2.0 * 32 * 320 * T * (T + 1) / 2
    assert fn.shared_expert_flops(CFG) == 2.0 * T * 3 * D * 1024
    rows = T * 8 * CFG["num_experts"] / 256.0
    assert fn.expected_share_rows(CFG) == rows
    assert fn.moe_share_flops(CFG) == 2.0 * (T * D * 256
                                             + rows * 3 * D * 1024)
    assert fn.moe_share_flops(CFG, rows=100) == 2.0 * (
        T * D * 256 + 100 * 3 * D * 1024)
    want = (2.0 * T * D * 20480
            + 4 * (fn.kda_projection_flops(CFG) + fn.kda_chunk_flops(CFG))
            + fn.mla_projection_flops(CFG) + fn.mla_kernel_flops(CFG)
            + 2.0 * T * 3 * D * 9216
            + 4 * (fn.shared_expert_flops(CFG) + fn.moe_share_flops(CFG)))
    assert fn.forward_flops_per_sample(CFG) == pytest.approx(want, rel=1e-12)
    assert 3 * want == pytest.approx(
        {8: 19.02e12, 16: 19.37e12}[CFG["num_experts"]], rel=5e-3)
    # the KDA layers' projections are 41% of the step's operations, the
    # rule's chunk form 2.4%, the latent layer's kernel 11%
    assert 4 * fn.kda_projection_flops(CFG) / want == pytest.approx(
        0.40, abs=0.02)
    assert 4 * fn.kda_chunk_flops(CFG) / want < 0.03
    # the rule is bound by its bytes: 0.410 ms a layer forward on the
    # v5e's peaks against 0.153 ms of operations
    peaks = lib.load_json(lib.BENCH + "/peaks.json")["TPU v5 lite"]
    assert 1e3 * fn.kda_core_bytes(CFG) / peaks["hbm_bytes_s"] == \
        pytest.approx(0.4103, abs=0.001)
    assert 1e3 * fn.kda_core_flops(CFG) / peaks["bf16_flops"] == \
        pytest.approx(0.1526, abs=0.001)
    assert fn.TRAIN_MULTIPLIER == 3


def test_forward_flops_at_the_rehearsal_size_match_a_hand_count():
    """The rehearsal's tiny model (hidden 48, dense 96, 3 KDA heads of 8,
    4 latent heads of 16 + 8 / 16 from a latent of 32, 4 of 16 experts of
    32 top-3, 1 shared, vocabulary 512, T 120), by hand."""
    fn = lib.load_module("flops", CFG["flops"])
    tiny = lib.load_json(lib.BENCH + "/tests/rehearsal/%s.json" % CELL)
    cfg = lib.merge(CFG, tiny["config"])
    t = 120
    head = 2 * t * 48 * 512
    kda = (2 * t * (4 * 48 * 24 + 2 * (48 * 8 + 8 * 24) + 48 * 3)
           + t * 3 * (6 * 64 * 8 + 6 * 8 * 8))
    mla = (2 * t * (48 * 4 * 24 + 48 * 40 + 32 * 4 * 32 + 64 * 48)
           + 2 * 4 * 40 * t * (t + 1) // 2)
    dense = 2 * t * 3 * 48 * 96
    rows = t * 3 * 4 / 16.0
    experts = 2 * t * 3 * 48 * 32 + 2 * (t * 48 * 16 + rows * 3 * 48 * 32)
    assert fn.forward_flops_per_sample(cfg) == pytest.approx(
        head + 4 * kda + mla + dense + 4 * experts, rel=1e-12)
    assert fn.kda_core_bytes(cfg) == 2 * t * 3 * (5 * 8 + 1)


def test_the_two_copies_of_the_reference_are_one_text():
    import mxnet_tpu.models.kimi_linear_reference as theirs

    with open(lib.find("reference", CFG["reference"], ".py")) as ours, \
            open(theirs.__file__) as program:
        assert ours.read() == program.read()


# scope paths as the step compiled for the chip names them
SCOPES = {
    "fusion.1": "jit(step)/fwd_bwd/jvp(gdn/layer0_kda)/"
                "jit(_gated_delta_block)/delta_rule/mul:",
    "fusion.2": "jit(step)/fwd_bwd/jvp(gdn/layer1_kda)/"
                "jit(_gated_delta_block)/conv1d/taps_fwd_bf16:",
    "fusion.3": "jit(step)/fwd_bwd/transpose(jvp(gdn/layer2_kda))/"
                "jit(_gated_delta_block)/delta_rule/delta_rule/checkpoint/"
                "rematted_computation/jit(_solve_triangular)/"
                "triangular_solve:",
    "fusion.4": "jit(step)/fwd_bwd/transpose(jvp(gdn/layer4_kda))/"
                "jit(_gated_delta_block)/gate_norm/gate_norm/checkpoint/mul:",
    "fusion.5": "jit(step)/fwd_bwd/jvp(fc/layer0_kda_q_proj)/dot_general:",
    "fusion.6": "jit(step)/fwd_bwd/transpose(jvp(fc/layer2_kda_o_proj))/"
                "dot_general:",
    "fusion.7": "jit(step)/fwd_bwd/jvp(fc/layer1_kda_f_a_proj)/dot_general:",
    "fusion.8": "jit(step)/fwd_bwd/transpose(jvp(fc/layer1_kda_g_b_proj))/"
                "dot_general:",
    "fusion.9": "jit(step)/fwd_bwd/jvp(fc/layer4_kda_b_proj)/dot_general:",
    "fusion.10": "jit(step)/fwd_bwd/jvp(fc/layer3_q_proj)/dot_general:",
    "fusion.11": "jit(step)/fwd_bwd/transpose(jvp(fc/layer3_kv_a_proj))/"
                 "dot_general:",
    "fusion.12": "jit(step)/fwd_bwd/jvp(fc/layer3_o_proj)/dot_general:",
    "fusion.13": "jit(step)/fwd_bwd/jvp(attn/layer3_attn)/latent/mul:",
    "fusion.14": "jit(step)/fwd_bwd/transpose(jvp(attn/layer3_attn))/full/"
                 "flash2_bwd_bf16_q1024_k1024:",
    "fusion.15": "jit(step)/fwd_bwd/jvp(fc/layer1_shared_gate_proj)/"
                 "dot_general:",
    "fusion.16": "jit(step)/fwd_bwd/jvp(moe/layer1_moe)/experts/"
                 "gmm_fwd_bf16_m256_k2304_n1024:",
    "fusion.17": "jit(step)/fwd_bwd/jvp(fc/layer0_gate_proj)/dot_general:",
    "fusion.18": "jit(step)/fwd_bwd/jvp(fc/layer0_gdn_q_proj)/dot_general:",
}


def test_the_table_files_the_projections_and_nothing_else():
    assert {k: kda_scopes.part_of(v) for k, v in SCOPES.items()} == dict(
        {"fusion.%d" % i: None for i in (1, 2, 3, 4, 13, 14, 15, 16, 17,
                                         18)},
        **dict({"fusion.%d" % i: "kda_proj" for i in (5, 6, 7, 8, 9)},
               **{"fusion.%d" % i: "mla_proj" for i in (10, 11, 12)}))
    assert list(kda_scopes.TABLE) == ["kda_proj", "mla_proj"]
    # the node's own scopes are gdn_scopes' as they stand
    assert [gdn_scopes.part_of(SCOPES["fusion.%d" % i])
            for i in (1, 2, 3, 4)] == ["delta_rule", "conv1d", "delta_rule",
                                       "gate_norm"]
    assert gdn_scopes.part_of(SCOPES["fusion.5"]) is None


def _raw():
    import reduce_trace

    ops = [("fusion.%d" % i, 1000 * i, 100) for i in range(1, 19)]
    return {"host": [(0, reduce_trace.SLICE_BEGIN, 0, 10),
                     (0, reduce_trace.SLICE_END, 30000, 10)],
            "devices": {0: {"ops": ops}}}


def test_the_reduction_sums_the_parts_and_needs_a_kda_projection():
    raw = _raw()
    red = kda_scopes.reduce(raw, {0: SCOPES})
    assert red["kda_proj"] == pytest.approx(500e-9)
    assert red["mla_proj"] == pytest.approx(300e-9)
    assert gdn_scopes.reduce(raw, {0: SCOPES})["gdn"] == pytest.approx(400e-9)
    mla = mla_scopes.reduce(raw, {0: SCOPES})
    assert mla["mla"] == pytest.approx(200e-9)
    assert mla["shared"] == pytest.approx(100e-9)
    # Kanana's latent layers carry the same three names: without a KDA
    # projection there is nothing of this model to read
    rest = {k: v for k, v in SCOPES.items() if "_kda_" not in v}
    assert kda_scopes.reduce(raw, {0: rest}) is None
    assert kda_scopes.reduce(dict(raw, host=[]), {0: SCOPES}) is None


def _run(**over):
    counts = [[40] * 8 + [254] * 248 for _ in range(4)]   # 320 held a layer
    run = {"cfg": CFG, "cell": {"name": CELL}, "trace_steps": 5,
           "batch": 1, "chips": 1, "flops_multiplier": 3,
           "peak": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
           "expert_counts": counts,
           "gdn_scopes": {"gdn": 1.000, "conv1d": 0.050, "delta_rule": 0.800,
                          "gate_norm": 0.100, "other": 0.050, "proj": None,
                          "mlp": 0.100},
           "kda_scopes": {"kda_proj": 0.250, "mla_proj": 0.040},
           "solar2_scopes": {"kda_fwd": 0.250, "kda_bwd": 0.500,
                             "flash_fwd": 0.030, "flash_bwd": 0.060},
           "mla_scopes": {"mla": 0.110, "latent": 0.020, "full": 0.090,
                          "shared": 0.030},
           "lm_scopes": {"class_s": {"moe": 0.070, "attn": 0.110}}}
    run.update(over)
    return run


def _read(name, run, trace=True):
    return lib.load_module("layer_metrics", name).compute(
        {"devices": {}} if trace else None, {"telemetry": {}}, run)


TRACE_READERS = ["kda_device_ms", "kda_core_device_ms",
                 "kda_core_roofline_share", "kda_proj_device_ms",
                 "mla_device_ms", "mla_latent_device_ms",
                 "mla_kernel_roofline_share", "moe_share_device_ms",
                 "shared_expert_device_ms"]
READERS = TRACE_READERS + ["moe_share_rows_over_expected"]
# one entry a mechanism since PR 68: the four ``kda_*`` are Solar's too,
# the latent node's three Kanana's (``kimi_mla_device_ms`` was the node
# plus its three projections, which no entry reads now), the experts'
# three every share's (``kimi_moe_device_ms`` was routed + shared)
KDA = TRACE_READERS[:4]


def test_the_ten_readers_read_what_they_say():
    run = _run()
    assert _read("kda_device_ms", run) == pytest.approx(200.0)
    assert _read("kda_core_device_ms", run) == pytest.approx(160.0)
    assert _read("kda_proj_device_ms", run) == pytest.approx(50.0)
    assert _read("mla_device_ms", run) == pytest.approx(22.0)
    assert _read("mla_latent_device_ms", run) == pytest.approx(4.0)
    assert _read("moe_share_device_ms", run) == pytest.approx(14.0)
    assert _read("shared_expert_device_ms", run) == pytest.approx(6.0)
    # four layers, three forwards each, bound by bytes: 12 x 0.4103 ms
    # of 160; the kernel pair holds 150 of the scope's 160 ms
    share, ok, why = _read("kda_core_roofline_share", run)
    assert share == pytest.approx(100 * 12 * 0.41034 / 160.0, rel=1e-3)
    assert share < 100 and ok, why
    # the chunk form in place of the kernels: the share is not theirs
    _, ok, why = _read("kda_core_roofline_share", _run(
        solar2_scopes={"kda_fwd": 0.1, "kda_bwd": 0.2, "flash_fwd": 0,
                       "flash_bwd": 0}))
    assert not ok and "want 0.5" in why
    # ONE latent layer of the five has the node: its kernel's operations
    # count once (``mla_layers``), not ``num_hidden_layers`` times
    flops = lib.load_module("flops", CFG["flops"])
    assert flops.mla_layers(CFG) == 1
    assert _read("mla_kernel_roofline_share", run) == pytest.approx(
        100 * (3 * flops.mla_kernel_flops(CFG) / 197e12 * 1e3) / 18.0)
    held = CFG["num_experts"]
    assert _read("moe_share_rows_over_expected", run) == pytest.approx(
        4 * 40 * min(held, 8) / (4.0 * 8192 * 8 * held / 256))


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_where_there_is_nothing(name):
    """No slice, no such scopes (the parent's program, another model's),
    a configuration with another operations module: None, never zero,
    never a raise (the benchmark's files are laid over older
    checkouts)."""
    kanana = lib.load_json(lib.find("configs", "kanana_2_30b_a3b", ".json"))
    olmo = lib.load_json(lib.find("configs", "olmo_hybrid_7b", ".json"))
    bare = dict(gdn_scopes=None, kda_scopes=None, mla_scopes=None,
                lm_scopes=None, expert_counts=None)
    assert _read(name, _run(**bare)) is None
    assert _read(name, _run(cfg=kanana, **bare)) is None
    assert _read(name, _run(cfg=olmo, **bare)) is None
    if name in TRACE_READERS:
        assert _read(name, _run(), trace=False) is None
        assert _read(name, _run(trace_steps=0)) is None
    if name in KDA and name != "kda_proj_device_ms":
        # another model's operations module counts no KDA core
        assert _read(name, _run(cfg=olmo)) is None
        assert _read(name, _run(cfg=kanana)) is None
    if name.endswith("roofline_share"):
        assert _read(name, _run(peak=None)) is None
    entry = [m for m in lib.load_json(lib.MANIFEST)["per_layer"]
             if m["name"] == name][0]
    assert CELL in entry["workloads"]
    if name in KDA:
        assert entry["workloads"] == [CELL, "solar_open2_fit_share_4k"]
    assert entry["moves"] == "train_samples_s"
    assert entry["layer"] == "ops and kernels"
    assert entry["source"] == ("device_trace" if name in TRACE_READERS
                               else "program_counter")


class _Out:
    def __init__(self, values):
        self.values = values

    def asnumpy(self):
        return self.values


def test_the_kind_hands_the_share_kind_the_three_names_it_reads(monkeypatch):
    """``num_experts``, ``num_experts_per_token`` and the integer
    ``moe_layer_freq`` stay as published in the file; ``fit_tokens_share``
    reads ``n_routed_experts``, ``num_experts_per_tok`` and a list, and
    gets them. Its checks then hold this cell's counts to four expert
    layers of 256 experts, 65,536 rows a layer and the share's buffer."""
    kind = lib.load_module("traffic", "fit_tokens_share_kimi")
    assert kind.setup is kind.share.setup
    assert kind.share.__file__ == lib.find("traffic", "fit_tokens_share",
                                           ".py")
    text = open(kind.__file__).read()
    assert "def setup" not in text and "checks" not in text.split('"""')[2]
    cell = lib.load_json(lib.find("cells", CELL, ".json"))
    cell["traffic"] = lib.load_json(lib.find(
        "traffic", cell["traffic"], ".json"))
    assert cell["traffic"]["kind"] == "fit_tokens_share_kimi"
    assert CFG["moe_layer_freq"] == 1
    held, seen = CFG["num_experts"], {}
    expected = 8192 * 8 * held // 256

    def run_with(held_rows):
        rest = (65536 - held * held_rows) // (256 - held)
        layer = [held_rows] * held + [rest] * (256 - held)
        layer[-1] += 65536 - sum(layer)

        class Mod:
            def get_outputs(self):
                return [None] + [_Out(layer)] * 4

        def fit_run(state, *a):
            seen.update({k: state["cfg"][k] for k in (
                "moe_layer_freq", "n_routed_experts",
                "num_experts_per_tok")})
            return {"checks": [], "series": {"losses": [10.39]},
                    "report": ()}

        monkeypatch.setattr(kind.share.fit_tokens.fit, "run", fit_run)
        state = {"cfg": CFG, "cell": cell, "mod": Mod(), "classes": 20480}

        class Trace:
            tracing = False

        out = kind.run(state, 1, Trace())
        return {name: ok for name, ok, _ in out["checks"]}

    ok = run_with(expected // held)
    assert seen == {"moe_layer_freq": [0, 1, 1, 1, 1],
                    "n_routed_experts": held, "num_experts_per_tok": 8}
    assert ok == {"experts_routed_over_all": True,
                  "held_rows_within_bound": True,
                  "held_rows_near_expected": True,
                  "first_loss_near_expected": True}
    over = run_with(4 * expected // held)       # past the 3x buffer
    assert not over["held_rows_within_bound"]
    assert not over["held_rows_near_expected"]


def test_the_cell_the_mix_and_the_manifest():
    cell = lib.load_json(lib.find("cells", CELL, ".json"))
    assert cell["traffic"] == "fit_tokens_share_kimi_resident_b1_t8192"
    assert cell["chips"] == 1
    mix = lib.load_json(lib.find("traffic", cell["traffic"], ".json"))
    keys = lib.load_json(lib.find(
        "traffic", "fit_tokens_share_keys_resident_b1_t8192", ".json"))
    # the LFM2 cell's parameters, letter for letter, under the new kind
    assert mix == dict(keys, kind="fit_tokens_share_kimi")
    lfm2 = lib.load_json(lib.find("cells", "lfm2_fit_share_8k", ".json"))
    assert set(cell["expect"]["reference"]) == set(
        lfm2["expect"]["reference"])
    # half the variance of logits from a Normal(0.02) head over a
    # unit-rms vector of 2304
    assert cell["expect"]["first_loss_excess"] == pytest.approx(
        0.5 * 2304 * 0.02 ** 2)
    manifest = lib.load_json(lib.MANIFEST)
    entry = [w for w in manifest["workloads"] if w["name"] == CELL][0]
    assert entry == {"name": CELL, "config": "kimi_linear_48b_a3b",
                     "traffic": cell["traffic"], "chips": 1,
                     "why": cell["why"]}
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    # since PR 68 every entry whose reader finds something in the cell
    # lists it; none names the model
    listed = [m["name"] for m in manifest["per_layer"]
              if CELL in m.get("workloads", [])]
    assert set(READERS) | {"embed_device_ms", "head_loss_device_ms",
                           "moe_permute_device_ms",
                           "moe_share_roofline_share"} == set(listed)
    assert not [n for n in listed if n.startswith("kimi")]


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
