"""What the ``solar_open2_250b`` configuration brought: its file against
the published keys, the parameters the cut counted, its operations and
bytes against the hand count (at the cell's size and at the
rehearsal's), the benchmark's copy of the reference against the
program's, ``solar2_scopes``' kernel times, the nine readers on
handed-in reductions, the cell, its mix and the manifest, and the cell's
rehearsal end to end."""
import pytest

import lib
import solar2_scopes
from helpers import check_rehearsal, run_bench

CFG = lib.load_json(lib.find("configs", "solar_open2_250b", ".json"))
CELL = "solar_open2_fit_share_4k"
# upstage/Solar-Open2-250B's config.json, the keys that say its shape
# (the model-configs catalog's ``config``)
PUBLISHED = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
    "hidden_size": 4096, "num_hidden_layers": 48,
    "num_attention_heads": 64, "head_dim": 128, "num_key_value_heads": 8,
    "vocab_size": 196608, "intermediate_size": 10240,
    "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05,
    "rope_theta": 10000, "tie_word_embeddings": False,
    "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3,
    "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44],
    "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "n_routed_experts": 320,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 8}
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "head_dim", "num_experts_per_tok", "n_shared_experts",
          "routed_scaling_factor")
T, D = 4096, 4096


def test_configuration_keeps_every_published_width_and_states_its_cut():
    changed = {k for k, v in PUBLISHED.items() if CFG[k] != v}
    assert changed == set(CFG["reduced"]) == set(CFG["reduced_why"]) == {
        "num_hidden_layers", "gqa_layers", "n_routed_experts",
        "linear_attn_config", "num_attention_heads", "num_key_value_heads",
        "vocab_size"}
    assert not changed & set(WIDTHS)
    for key in changed:           # the uncut value stands beside the held
        assert CFG["published"][key] == PUBLISHED[key]
    # inside the nested group only the heads held moved, no width
    held, uncut = CFG["linear_attn_config"], PUBLISHED["linear_attn_config"]
    assert {k for k in uncut if held[k] != uncut[k]} == {"num_heads"}
    assert (held["head_dim"], held["short_conv_kernel_size"],
            held["num_kv_heads"]) == (128, 4, None)
    # the floors: one whole period of four layers at the published 3 : 1
    # (no leading dense layer), at least 8 experts, an eighth of the
    # vocabulary; the same share of both mixers' heads, whole groups
    assert CFG["num_hidden_layers"] == 4 and CFG["gqa_layers"] == [0]
    assert [i in PUBLISHED["gqa_layers"] for i in range(4)] == [
        i in CFG["gqa_layers"] for i in range(4)]
    assert CFG["n_routed_experts"] >= 8
    assert CFG["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    ways = PUBLISHED["num_attention_heads"] // CFG["num_attention_heads"]
    assert ways == 2
    assert held["num_heads"] * ways == uncut["num_heads"]
    assert CFG["num_key_value_heads"] * ways == 8
    share = CFG["share"]
    assert share["experts_of"] == 320 and share["expert_offset"] == 0
    # three times the expected rows, as Kimi's cell found KDA-fed routers
    # need
    assert share["share_rows_bound"] == (
        3 * T * 8 * CFG["n_routed_experts"] // 320)
    assert "chips share each layer" in CFG["deployment"]
    assert "memory_peak_bytes" in CFG["deployment"]
    assert CFG["kwargs"] == {"seq_len": T, "dtype": "bfloat16"}
    assert CFG["input_shape"] == [1, 1, T]
    assert CFG["num_classes"] == CFG["vocab_size"]
    for topic in ("unread", "block", "gqa_gate", "attention", "beta",
                  "chunk", "low_rank", "gate", "unit_norm", "conv_weight",
                  "router", "shared_experts", "weights", "dtype",
                  "optimizer", "objective", "share_rows_bound"):
        assert CFG["assumed"][topic]
    manifest = [c for c in lib.load_json(lib.MANIFEST)["configs"]
                if c["name"] == "solar_open2_250b"][0]
    assert manifest["reduced"] == CFG["reduced"]
    assert manifest["source"] == CFG["source"]
    assert manifest["file"] == "bench/configs/solar_open2_250b.json"


def test_the_symbol_holds_the_parameters_the_cut_counted():
    """ISSUE 62's arithmetic at the 32 heads and 10 experts that stand: a
    KDA mixer 69.4 M (q, k, v, o 4096 x 4096 = 16.78 M each; f_a, g_a 4096
    x 128 whole and f_b, g_b 128 x 4096: 2.10 M; b 4096 x 32; taps, decays
    and gamma 0.05 M), the grouped-attention mixer 54.5 M (q, the gate and
    o 4096 x 4096 each, k and v 4096 x 512 each), every layer's
    feed-forward 174.3 M (10 experts of 15.73 M, the shared one, the
    router 1.31 M), embedding and head 24576 x 4096 = 100.7 M each:
    1,161 M (1,031 M at the 16 heads the issue started from)."""
    import numpy as np

    sym = lib.resolve(CFG["factory"])(CFG, **CFG["kwargs"])
    shapes, _, _ = sym.infer_shape(data=(1, T), softmax_label=(1, T))
    sizes = {n: int(np.prod(s)) for n, s in zip(sym.list_arguments(), shapes)
             if n not in ("data", "softmax_label")}
    heads = CFG["linear_attn_config"]["num_heads"]
    for name in ("q", "k", "v", "o"):
        assert sizes["layer1_kda_%s_proj_weight" % name] == D * heads * 128
    for name in ("f", "g"):
        assert sizes["layer1_kda_%s_a_proj_weight" % name] == D * 128
        assert sizes["layer1_kda_%s_b_proj_weight" % name] == (
            128 * heads * 128)
    assert sizes["layer1_kda_b_proj_weight"] == D * heads
    assert sizes["layer1_kda_conv_weight"] == 4 * 3 * heads * 128
    assert sizes["layer1_kda_a_log"] == heads
    assert sizes["layer1_kda_dt_bias"] == heads * 128
    assert sizes["layer1_kda_norm_gamma"] == 128
    assert not [n for n in sizes if n.endswith("_bias")
                and "select" not in n and "dt_bias" not in n]
    q, kv = CFG["num_attention_heads"] * 128, CFG["num_key_value_heads"] * 128
    for name, width in (("q", q), ("attn_gate", q), ("k", kv), ("v", kv)):
        assert sizes["layer0_%s_proj_weight" % name] == D * width
    assert sizes["layer0_o_proj_weight"] == q * D
    assert "layer0_kda_q_proj_weight" not in sizes   # layer 0 is GQA
    held = CFG["n_routed_experts"]
    for i in range(4):                               # experts in EVERY layer
        assert sizes["layer%d_moe_gate_weight" % i] == D * 320
        assert sizes["layer%d_moe_gate_up_weight" % i] == held * D * 2560
        assert sizes["layer%d_moe_down_weight" % i] == held * 1280 * D
        assert sizes["layer%d_shared_gate_proj_weight" % i] == D * 1280
    assert "layer0_gate_proj_weight" not in sizes    # no dense layer
    assert sizes["embed_weight"] == sizes["lm_head_weight"] == 24576 * D

    def part(i, keep):
        return sum(v for k, v in sizes.items()
                   if k.startswith("layer%d_" % i) and keep(k))

    assert (heads, held) == (32, 10)
    assert part(1, lambda k: "_kda_" in k) == pytest.approx(69.4e6, rel=2e-3)
    assert part(0, lambda k: "moe" not in k and "shared" not in k
                and "norm" not in k) == pytest.approx(54.5e6, rel=2e-3)
    assert part(2, lambda k: "moe" in k or "shared" in k) == \
        pytest.approx(174.3e6, rel=1e-3)
    assert sum(sizes.values()) == pytest.approx(1161.4e6, rel=1e-3)


def test_forward_flops_and_bytes_match_the_hand_count():
    """Per sequence of 4096, forward, at 32 KDA heads, 32 query heads on 4
    and 10 experts held: head 2 x 4096 x 4096 x 24576 = 0.825 T; a KDA
    layer's nine projections 2 x 4096 x (4 x 4096 x 4096 + 2 x (4096 x 128
    + 128 x 4096) + 4096 x 32) = 0.568 T and its rule in chunks of 64,
    4096 x 32 x (6 x 64 x 128 + 6 x 128^2) = 0.0193 T; the grouped layer's
    projections 2 x 4096 x 4096 x 13312 = 0.447 T and its scores and
    values 2 x 32 x 256 x 4096 x 4097 / 2 = 0.137 T; a layer's shared
    expert 0.129 T, router 0.0107 T and 1,024 rows through an expert
    0.0322 T. 11.57 T a training step."""
    fn = lib.load_module("flops", CFG["flops"])
    h = CFG["linear_attn_config"]["num_heads"]
    qh, kvh = CFG["num_attention_heads"], CFG["num_key_value_heads"]
    held = CFG["n_routed_experts"]
    assert (fn.kda_layers(CFG), fn.gqa_layers(CFG)) == (3, 1)
    assert fn.kda_projection_flops(CFG) == 2.0 * T * (
        4 * D * h * 128 + 2 * (D * 128 + 128 * h * 128) + D * h)
    assert fn.kda_chunk_flops(CFG) == float(T) * h * (
        6 * 64 * 128 + 6 * 128 * 128)
    assert fn.kda_core_flops(CFG) == 7.0 * T * h * 128 * 128
    assert fn.kda_core_bytes(CFG) == 2.0 * T * h * (5 * 128 + 1)
    assert fn.gqa_projection_flops(CFG) == 2.0 * T * D * (
        3 * qh + 2 * kvh) * 128
    assert fn.gqa_kernel_flops(CFG) == 2.0 * qh * 256 * T * (T + 1) / 2
    assert fn.gqa_kernel_bytes(CFG) == 2.0 * T * (2 * qh + 2 * kvh) * 128
    assert fn.shared_expert_flops(CFG) == 2.0 * T * 3 * D * 1280
    rows = T * 8 * held / 320.0
    assert fn.expected_share_rows(CFG) == rows
    assert fn.moe_share_flops(CFG) == 2.0 * (T * D * 320
                                             + rows * 3 * D * 1280)
    assert fn.moe_share_flops(CFG, rows=100) == 2.0 * (
        T * D * 320 + 100 * 3 * D * 1280)
    want = (2.0 * T * D * 24576
            + 3 * (fn.kda_projection_flops(CFG) + fn.kda_chunk_flops(CFG))
            + fn.gqa_projection_flops(CFG) + fn.gqa_kernel_flops(CFG)
            + 4 * (fn.shared_expert_flops(CFG) + fn.moe_share_flops(CFG)))
    assert fn.forward_flops_per_sample(CFG) == pytest.approx(want, rel=1e-12)
    assert fn.TRAIN_MULTIPLIER == 3
    peaks = lib.load_json(lib.BENCH + "/peaks.json")["TPU v5 lite"]
    # the rule is bound by its bytes, the attention kernel by operations
    assert fn.kda_core_bytes(CFG) / peaks["hbm_bytes_s"] > \
        2 * fn.kda_core_flops(CFG) / peaks["bf16_flops"]
    assert fn.gqa_kernel_flops(CFG) / peaks["bf16_flops"] > \
        5 * fn.gqa_kernel_bytes(CFG) / peaks["hbm_bytes_s"]
    assert (h, qh, kvh, held) == (32, 32, 4, 10)
    assert 3 * want == pytest.approx(11.57e12, rel=5e-3)
    # the KDA layers' projections are 44% of the step's operations, the
    # rule's chunk form 1.5%, the attention kernel 3.6%
    assert 3 * fn.kda_projection_flops(CFG) / want == pytest.approx(
        0.44, abs=0.02)
    assert 3 * fn.kda_chunk_flops(CFG) / want < 0.02
    assert 1e3 * fn.kda_core_bytes(CFG) / peaks["hbm_bytes_s"] == \
        pytest.approx(0.2052, abs=0.001)
    assert 1e3 * fn.gqa_kernel_flops(CFG) / peaks["bf16_flops"] == \
        pytest.approx(0.6978, abs=0.001)


def test_forward_flops_at_the_rehearsal_size_match_a_hand_count():
    """The rehearsal's tiny model (hidden 48, 3 KDA heads of 8, 4 query
    heads on 2 of 16, 5 of 20 experts of 32 top-3, 1 shared, vocabulary
    512, T 120), by hand."""
    fn = lib.load_module("flops", CFG["flops"])
    tiny = lib.load_json(lib.BENCH + "/tests/rehearsal/%s.json" % CELL)
    cfg = lib.merge(CFG, tiny["config"])
    t = 120
    head = 2 * t * 48 * 512
    kda = (2 * t * (4 * 48 * 24 + 2 * (48 * 8 + 8 * 24) + 48 * 3)
           + t * 3 * (6 * 64 * 8 + 6 * 8 * 8))
    gqa = (2 * t * 48 * (3 * 4 + 2 * 2) * 16
           + 2 * 4 * 32 * t * (t + 1) // 2)
    rows = t * 3 * 5 / 20.0
    experts = 2 * t * 3 * 48 * 32 + 2 * (t * 48 * 20 + rows * 3 * 48 * 32)
    assert fn.forward_flops_per_sample(cfg) == pytest.approx(
        head + 3 * kda + gqa + 4 * experts, rel=1e-12)
    assert fn.kda_core_bytes(cfg) == 2 * t * 3 * (5 * 8 + 1)
    assert fn.gqa_kernel_bytes(cfg) == 2 * t * (2 * 4 + 2 * 2) * 16


def test_the_two_copies_of_the_reference_are_one_text():
    import mxnet_tpu.models.solar_open2_reference as theirs

    with open(lib.find("reference", CFG["reference"], ".py")) as ours, \
            open(theirs.__file__) as program:
        assert ours.read() == program.read()
    # the kind asks the reference which layers have experts: all four
    ref = lib.load_module("reference", CFG["reference"])
    assert ref.expert_layers(CFG) == [True] * 4


# device ops as the step compiled for the chip names them
OPS = ["%kda_fwd_bf16_c64_k128_v128_pre.1 = bf16[1,4096,4096] custom-call(",
       "%kda_bwd_bf16_c64_k128_v128_pre.1 = bf16[1,4096,4096] custom-call(",
       "%flash_fwd_bf16_q1024_k1024_e512.1 = bf16[32,4096,128] custom-call(",
       "%flash_dq_bf16_q1024_k1024.1 = bf16[32,4096,128] custom-call(",
       "%flash_bwd_bf16_q1024_k1024_e512.1 = bf16[4,4096,128] custom-call(",
       "%flash2_fwd_bf16_q1024_k1024_e512.1 = bf16[1] custom-call(",
       "%gdn_fwd_bf16_c64_k96_v192.1 = bf16[1] custom-call(",
       "%fusion.7 = bf16[4096,4096] fusion("]


def _raw(ops):
    import reduce_trace

    events = [(text, 1000 * (i + 1), 100) for i, text in enumerate(ops)]
    return {"host": [(0, reduce_trace.SLICE_BEGIN, 0, 10),
                     (0, reduce_trace.SLICE_END, 90000, 10)],
            "devices": {0: {"ops": events}}}


def test_the_kernel_times_name_the_pairs_and_nothing_else():
    red = solar2_scopes.reduce(_raw(OPS + OPS[:2]))
    assert red == pytest.approx({"kda_fwd": 200e-9, "kda_bwd": 200e-9,
                                 "flash_fwd": 100e-9, "flash_bwd": 200e-9})
    assert solar2_scopes.reduce(dict(_raw(OPS), host=[])) is None
    none = solar2_scopes.reduce(_raw(OPS[5:]))   # another model's kernels
    assert none == {"kda_fwd": 0, "kda_bwd": 0, "flash_fwd": 0,
                    "flash_bwd": 0}
    assert list(solar2_scopes.KERNELS) == ["kda_fwd", "kda_bwd",
                                           "flash_fwd", "flash_bwd"]
    # an op the slice's edge cuts counts for the part inside it
    cut = _raw(OPS[:1])
    cut["host"][1] = (0, cut["host"][1][1], 1050, 10)
    assert solar2_scopes.reduce(cut)["kda_fwd"] == pytest.approx(50e-9)


def _run(**over):
    held = CFG["n_routed_experts"]
    counts = [[80] * held + [(T * 8 - 80 * held) // (320 - held)]
              * (320 - held) for _ in range(4)]
    run = {"cfg": CFG, "cell": {"name": CELL}, "trace_steps": 5,
           "batch": 1, "chips": 1, "flops_multiplier": 3,
           "peak": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
           "expert_counts": counts,
           "gdn_scopes": {"gdn": 0.100, "conv1d": 0.010, "delta_rule": 0.060,
                          "gate_norm": 0.020, "other": 0.010, "proj": None,
                          "mlp": None},
           "kda_scopes": {"kda_proj": 0.075, "mla_proj": None},
           "share_scopes": {"window": 0.0, "full": 0.025},
           "afmoe_scopes": {"gate": 0.005, "attn_proj": 0.030, "norm": None},
           "mla_scopes": {"mla": None, "latent": None, "full": None,
                          "shared": 0.040},
           "lm_scopes": {"class_s": {"moe": 0.035, "attn": 0.030}},
           "solar2_scopes": {"kda_fwd": 0.026, "kda_bwd": 0.032,
                             "flash_fwd": 0.008, "flash_bwd": 0.016}}
    run.update(over)
    return run


def _read(name, run, trace=True):
    return lib.load_module("layer_metrics", name).compute(
        {"devices": {}} if trace else None, {"telemetry": {}}, run)


# one entry a mechanism since PR 68: the four ``kda_*`` are Kimi Linear's
# too (``solar2_kda_*`` until then), the experts' three every share's
# (``solar2_moe_device_ms`` was routed + shared)
SHARED = ["kda_device_ms", "kda_core_device_ms", "kda_core_roofline_share",
          "kda_proj_device_ms", "moe_share_device_ms",
          "shared_expert_device_ms", "moe_share_rows_over_expected"]
TRACE_READERS = SHARED[:4] + [
    "solar2_gqa_device_ms", "solar2_gqa_roofline_share",
    "solar2_gqa_proj_device_ms"] + SHARED[4:6]
READERS = TRACE_READERS + SHARED[6:]


def test_the_ten_readers_read_what_they_say():
    fn = lib.load_module("flops", CFG["flops"])
    run = _run()
    assert _read("kda_device_ms", run) == pytest.approx(20.0)
    assert _read("kda_core_device_ms", run) == pytest.approx(12.0)
    assert _read("kda_proj_device_ms", run) == pytest.approx(15.0)
    assert _read("solar2_gqa_device_ms", run) == pytest.approx(5.0 + 1.0)
    assert _read("solar2_gqa_proj_device_ms", run) == pytest.approx(6.0)
    assert _read("moe_share_device_ms", run) == pytest.approx(7.0)
    assert _read("shared_expert_device_ms", run) == pytest.approx(8.0)
    # three layers, three forwards each, bound by bytes, of 12 ms
    value, ok, why = _read("kda_core_roofline_share", run)
    assert value == pytest.approx(
        100 * 9 * 1e3 * fn.kda_core_bytes(CFG) / 819e9 / 12.0, rel=1e-6)
    assert 0 < value < 100 and ok, why
    # one layer, three forwards, bound by operations, of 5 ms
    value, ok, why = _read("solar2_gqa_roofline_share", run)
    assert value == pytest.approx(
        100 * 3 * 1e3 * fn.gqa_kernel_flops(CFG) / 197e12 / 5.0, rel=1e-6)
    assert 0 < value < 100 and ok, why
    held = CFG["n_routed_experts"]
    assert _read("moe_share_rows_over_expected", run) == pytest.approx(
        80 * held / (T * 8 * held / 320.0))


@pytest.mark.parametrize("seconds,reader", [
    (dict(kda_fwd=0.009, kda_bwd=0.011), "kda_core_roofline_share"),
    (dict(kda_fwd=0.026, kda_bwd=0.0), "kda_core_roofline_share"),
    (dict(flash_fwd=0.0, flash_bwd=0.0), "solar2_gqa_roofline_share"),
], ids=["a_node_in_the_chunk_form", "no_backward_kernel", "no_flash_pair"])
def test_a_roofline_reader_fails_the_run_where_its_kernels_did_not_run(
        seconds, reader):
    """One KDA node of three in the chunk form (four to five times a
    kernel's time) leaves the pair a third of the scope; the share is
    still a number, and the run is not correct."""
    base = _run()["solar2_scopes"]
    value, ok, why = _read(reader, _run(solar2_scopes=dict(base, **seconds)))
    assert value > 0 and not ok and "of the scope's" in why


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_where_there_is_nothing(name):
    """No slice, no such scopes (the parent's program, another model's),
    a configuration with another operations module: None, never zero,
    never a raise (the benchmark's files are laid over older
    checkouts)."""
    kimi = lib.load_json(lib.find("configs", "kimi_linear_48b_a3b", ".json"))
    trinity = lib.load_json(lib.find("configs", "trinity_mini", ".json"))
    bare = dict(gdn_scopes=None, kda_scopes=None, mla_scopes=None,
                lm_scopes=None, share_scopes=None, afmoe_scopes=None,
                solar2_scopes=None, expert_counts=None)
    assert _read(name, _run(**bare)) is None
    for other in (kimi, trinity):   # another model's operations module
        assert _read(name, _run(cfg=other, **bare)) is None
        assert _read(name, _run(cfg=other)) is None or name in SHARED
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
    assert entry["unit"] == ("%" if name.endswith("roofline_share") else
                             "ratio" if name.endswith("expected")
                             else "ms/step")
    assert entry["source"] == ("device_trace" if name in TRACE_READERS
                               else "program_counter")


def test_the_cell_the_mix_and_the_manifest():
    cell = lib.load_json(lib.find("cells", CELL, ".json"))
    assert cell["traffic"] == "fit_tokens_share_layers_resident_b1_t4096"
    assert cell["chips"] == 1
    mix = lib.load_json(lib.find("traffic", cell["traffic"], ".json"))
    kanana = lib.load_json(lib.find(
        "traffic", "fit_tokens_share_resident_b1_t8192", ".json"))
    # the Kanana cell's mix of the same kind, letter for letter, but for
    # the positions the reference comparison covers
    assert mix["kind"] == "fit_tokens_share_layers"
    assert mix == dict(kanana, check_last_positions=2048)
    kimi = lib.load_json(lib.find("cells", "kimi_linear_fit_share_8k",
                                  ".json"))
    assert set(cell["expect"]["reference"]) == set(
        kimi["expect"]["reference"])
    # half the variance of logits from a Normal(0.02) head over a
    # unit-rms vector of 4096
    assert cell["expect"]["first_loss_excess"] == pytest.approx(
        0.5 * 4096 * 0.02 ** 2)
    manifest = lib.load_json(lib.MANIFEST)
    entry = [w for w in manifest["workloads"] if w["name"] == CELL][0]
    assert entry == {"name": CELL, "config": "solar_open2_250b",
                     "traffic": cell["traffic"], "chips": 1,
                     "why": cell["why"]}
    # since PR 68 every entry whose reader finds something in the cell
    # lists it, found by name; only the gated grouped attention's three
    # still name the model (PERF.md section 7 says why)
    listed = [m["name"] for m in manifest["per_layer"]
              if CELL in m.get("workloads", [])]
    assert set(READERS) | {"embed_device_ms", "head_loss_device_ms",
                           "moe_permute_device_ms",
                           "moe_share_roofline_share"} == set(listed)
    assert [n for n in listed if n.startswith("solar2")] == TRACE_READERS[4:7]


def test_rehearsal_runs_the_cell_end_to_end_with_the_trace_on():
    """The whole path at a tiny size on the CPU: from_config, Module.fit
    through the fused step, the share kind's checks, the reference check
    in float32 (where the program and the reference agree to summation
    order, and the bf16 reference does not) and every reader returning
    nothing or a value without a raise."""
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
