"""What the ``minicpm_sala_9b`` configuration brought: its file against the
published keys, the parameters the cut counted, its operations and bytes
against the hand count (at the cell's size and at the rehearsal's), the
benchmark's copy of the reference against the program's, the table of
``linblock_scopes`` on scope paths, the seven readers on handed-in
reductions and counters, the cell with its mix, and the cell's rehearsal end
to end. Every entry of the manifest is found by name, never by position or
count."""
import pytest

import lib
import linblock_scopes
from helpers import check_rehearsal, run_bench, step_events

CFG = lib.load_json(lib.find("configs", "minicpm_sala_9b", ".json"))
CELL = "minicpm_sala_fit_share_16k"
MIX = "fit_tokens_dense_resident_b1_t16384"
# openbmb/MiniCPM-SALA's config.json, the keys that say its shape (the
# model-configs catalog's ``config``)
PUBLISHED = {
    "attention_bias": False, "attn_use_rope": False, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 16384,
    "lightning_head_dim": 128, "lightning_nh": 32, "lightning_nkv": 32,
    "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
    "max_position_embeddings": 524288, "model_type": "minicpm_sala",
    "mixer_types": ["minicpm4" if i in (0, 9, 16, 17, 22, 29, 30, 31)
                    else "lightning-attn" for i in range(32)],
    "num_attention_heads": 32, "num_hidden_layers": 32,
    "num_key_value_heads": 2, "qk_norm": True, "rand_init": False,
    "rms_norm_eps": 1e-06, "vocab_size": 73448, "rope_theta": 10000,
    "scale_emb": 12, "scale_depth": 1.4, "mup_denominator": 32,
    "dim_model_base": 256, "tie_word_embeddings": False,
    "use_output_gate": True, "use_output_norm": True,
    "attn_use_output_gate": True}
REDUCED = {"num_hidden_layers", "mixer_types", "num_attention_heads",
           "num_key_value_heads", "lightning_nh", "lightning_nkv",
           "vocab_size"}
ASSUMED = ("sparse_config", "slopes", "output_norm", "qk_norm", "scale_depth",
           "unread_keys")
READERS = ["linattn_device_ms", "linattn_core_device_ms",
           "linattn_core_roofline_share", "linattn_proj_device_ms",
           "block_select_device_ms", "block_select_roofline_share",
           "block_keys_over_expected"]
KEPT = 82765888       # (query, key) pairs a head keeps of 16,384 positions


def _entry(section, name):
    found = [e for e in lib.load_json(lib.MANIFEST)[section]
             if e["name"] == name]
    assert len(found) == 1, (section, name)
    return found[0]


def test_configuration_keeps_every_published_width_and_states_its_cut():
    changed = {k for k, v in PUBLISHED.items() if CFG[k] != v}
    assert changed == set(CFG["reduced"]) == set(CFG["reduced_why"]) \
        == REDUCED
    # no width among them: a hidden, intermediate or head size, a key that
    # ends in _dim or _rank
    assert not [k for k in changed if k.endswith(("_dim", "_rank"))
                or k in ("hidden_size", "intermediate_size",
                         "dim_model_base")]
    for key in changed - {"mixer_types"}:  # the uncut value beside the held
        assert CFG["published"][key] == PUBLISHED[key]
    # the floors: a whole period and four layers, an eighth of the
    # vocabulary; one of two chips: half the heads with a whole group
    assert CFG["num_hidden_layers"] == 4
    assert CFG["mixer_types"] == PUBLISHED["mixer_types"][:4] == [
        "minicpm4", "lightning-attn", "lightning-attn", "lightning-attn"]
    assert CFG["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    share = CFG["share"]
    assert share["chips"] == 2 and share["layers_of"] == 32
    assert CFG["lightning_nh"] * 2 == share["lightning_heads_of"] == 32
    assert CFG["lightning_nkv"] == CFG["lightning_nh"]
    assert CFG["num_attention_heads"] * 2 == share["attention_heads_of"] == 32
    assert CFG["num_key_value_heads"] * 2 == share["kv_heads_of"] == 2
    assert share["dense_columns_held"] * 2 == CFG["intermediate_size"]
    assert share["first_layer"] == share["first_lightning_head"] == 0
    assert CFG["deployment"].startswith("Two chips share each layer by "
                                        "tensor parallelism and 8 pipeline")
    assert "vocabulary parallelism" in CFG["deployment"]
    assert "no code stands in" in CFG["deployment"]
    assert "Two ways and no more" in CFG["deployment"]
    assert CFG["kwargs"] == {"seq_len": 16384, "dtype": "bfloat16"}
    assert CFG["input_shape"] == [1, 1, 16384]
    assert CFG["num_classes"] == CFG["vocab_size"]
    assert CFG["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64, "topk": 64,
        "init_blocks": 1, "window_size": 2048, "dense_len": 8192}
    # each assumed size names the test that holds the program to it
    for key in ASSUMED:
        assert "tests/test_minicpm_sala.py::test_assumed_" in \
            CFG["assumed"][key], key
    for topic in ("factory", "lightning", "weights", "dtype", "optimizer",
                  "objective", "input_shape"):
        assert CFG["assumed"][topic]
    manifest = _entry("configs", "minicpm_sala_9b")
    assert manifest["reduced"] == CFG["reduced"]
    assert manifest["source"] == CFG["source"] == (
        "https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json")
    assert manifest["file"] == "bench/configs/minicpm_sala_9b.json"


def test_the_symbol_holds_the_parameters_the_cut_counted():
    """ISSUE 79's arithmetic: a lightning layer 25.2 M (q, k, v) + 8.4 (o) +
    8.4 (gate) + 100.7 (SwiGLU) = 142.6 M; the sparse layer 8.4 + 1.05 +
    8.4 + 8.4 + 100.7 = 126.9 M; four layers 554.7 M; embedding and head
    75.2 M; 629.9 M with the norms."""
    import numpy as np

    sym = lib.resolve(CFG["factory"])(CFG, **CFG["kwargs"])
    shapes, _, _ = sym.infer_shape(data=(1, 16384),
                                   softmax_label=(1, 16384))
    sizes = {n: int(np.prod(s)) for n, s in zip(sym.list_arguments(), shapes)
             if n not in ("data", "softmax_label")}
    for name in ("q", "k", "v", "g", "o"):
        assert sizes["layer1_linattn_%s_proj_weight" % name] == 4096 * 2048
    for name in ("q", "attn_gate", "o"):
        assert sizes["layer0_%s_proj_weight" % name] == 4096 * 2048
    assert sizes["layer0_k_proj_weight"] == sizes["layer0_v_proj_weight"] \
        == 4096 * 128
    for name in ("gate", "up", "down"):
        assert sizes["layer2_%s_proj_weight" % name] == 4096 * 8192
    assert sizes["embed_weight"] == sizes["lm_head_weight"] == 9181 * 4096
    for name in ("layer0_q_norm_gamma", "layer3_linattn_o_norm_gamma"):
        assert sizes[name] == 128

    def layer(i):
        return sum(v for k, v in sizes.items()
                   if k.startswith("layer%d_" % i))

    assert layer(1) == layer(2) == layer(3) == pytest.approx(142.6e6,
                                                             rel=1e-3)
    assert layer(0) == pytest.approx(126.9e6, rel=1e-3)
    assert sum(sizes.values()) == 629945728


def test_forward_flops_and_bytes_match_the_hand_count():
    """Per sequence of 16,384, forward, THIS chip's: head 2 x 16384 x 4096 x
    9181 = 1.232 T; a layer's SwiGLU 2 x 16384 x 3 x 4096 x 8192 = 3.299 T;
    a lightning layer's projections 2 x 16384 x 4096 x 5 x 2048 = 1.374 T
    and its core 2 x 16384 x (64.5 x 4096 + 2 x 16 x 128 x 128) = 0.0258 T;
    the sparse layer's projections 2 x 16384 x 4096 x 128 x 50 = 0.859 T,
    its scorer 2 x 16 x 128 x 8,365,071 = 0.034 T (forward only) and its
    kept pairs 2 x 16 x 256 x 82,765,888 = 0.678 T (61.7% of the causal
    134,225,920). 60.5 T a training step."""
    fn = lib.load_module("flops", CFG["flops"])
    t, d = 16384, 4096
    assert (fn.scan_layers(CFG), fn.layers(CFG), fn.full_layers(CFG)) == (
        3, 1, 0)
    assert fn.kept_pairs(CFG) == KEPT
    assert fn.causal_pairs(CFG) == 134225920
    assert fn.scored_windows(CFG) == 8365071
    assert fn.linattn_projection_flops(CFG) == 2.0 * t * d * 5 * 2048
    assert fn.scan_flops(CFG) == 2.0 * t * (64.5 * 4096 + 2 * 16 * 128 * 128)
    assert fn.scan_bytes(CFG) == 2.0 * t * 4 * 2048
    assert fn.attention_projection_flops(CFG) == 2.0 * t * d * 128 * 50
    assert fn.block_select_flops(CFG) == 2.0 * 16 * 128 * 8365071
    assert fn.block_select_bytes(CFG) == 2.0 * t * 128 * 17 + t * 256
    assert fn.select_flops(CFG) == 2.0 * 16 * 256 * KEPT
    assert fn.select_bytes(CFG) == 2 * t * 128 * (64 + 4) + 2 * 134225920
    assert fn.mlp_flops(CFG) == 2.0 * t * 3 * d * 8192
    assert fn.head_flops(CFG) == 2.0 * t * d * 9181
    differentiated = fn.head_flops(CFG) + 4 * fn.mlp_flops(CFG) + 3 * (
        fn.linattn_projection_flops(CFG) + fn.scan_flops(CFG)) \
        + fn.attention_projection_flops(CFG) + fn.select_flops(CFG)
    assert fn.train_flops_per_sample(CFG) == pytest.approx(
        3 * differentiated + fn.block_select_flops(CFG), rel=1e-12)
    assert fn.forward_flops_per_sample(CFG) * 3 == pytest.approx(
        fn.train_flops_per_sample(CFG), rel=1e-12)
    assert fn.train_flops_per_sample(CFG) == pytest.approx(60.5e12, rel=2e-3)
    parts = fn.parts(CFG)
    whole = sum(parts.values())
    for part, share in (("mlp", 0.653), ("linattn_projections", 0.204),
                        ("head", 0.061), ("attention_projections", 0.043),
                        ("attended_pairs", 0.034), ("linattn_core", 0.004),
                        ("block_select", 0.002)):
        assert parts[part] / whole == pytest.approx(share, abs=0.001), part
    assert KEPT / 134225920.0 == pytest.approx(0.617, abs=0.001)
    # the linear core is bound by bytes at heads of 128 / 128, the scorer
    # by its products on the v5e's peaks
    peaks = lib.load_json(lib.BENCH + "/peaks.json")["TPU v5 lite"]
    assert 1e3 * fn.scan_flops(CFG) / peaks["bf16_flops"] == pytest.approx(
        0.1312, abs=0.0005)
    assert 1e3 * fn.scan_bytes(CFG) / peaks["hbm_bytes_s"] == pytest.approx(
        0.3278, abs=0.0005)
    assert 1e3 * fn.block_select_flops(CFG) / peaks["bf16_flops"] == \
        pytest.approx(0.1739, abs=0.0005)
    assert 1e3 * fn.block_select_bytes(CFG) / peaks["hbm_bytes_s"] == \
        pytest.approx(0.0922, abs=0.0005)
    assert fn.TRAIN_MULTIPLIER == 3


def test_forward_flops_at_the_rehearsal_size_match_a_hand_count():
    """The rehearsal's tiny share (hidden 48, 2 lightning heads of 8, 2
    query heads on 1 of 8, 40 of 80 columns, vocabulary 512, T 120: windows
    of 8 every 4, blocks of 16, 2 chosen, a window of 24), by hand."""
    fn = lib.load_module("flops", CFG["flops"])
    tiny = lib.load_json(lib.BENCH + "/tests/rehearsal/%s.json" % CELL)
    cfg = lib.merge(CFG, tiny["config"])
    t = 120
    head = 2 * t * 48 * 512
    lin = 2 * t * 48 * 5 * 16 + 2 * t * (64.5 * 32 + 2 * 2 * 8 * 8)
    mlp = 2 * t * 3 * 48 * 40
    proj = 2 * t * 48 * 8 * (3 * 2 + 2)
    # query t keeps t - 16 f + 1 keys from its first local block f, block
    # 0 where f > 0 and up to 2 of the f - 1 blocks between
    kept = 0
    for at in range(t):
        f = max(at - 24 + 1, 0) // 16
        kept += at - 16 * f + 1 + 16 * (min(f, 1) + min(max(f - 1, 0), 2))
    assert fn.kept_pairs(cfg) == kept == 6444
    windows = sum(max((at + 1 - 8) // 4 + 1, 0) for at in range(t))
    assert fn.scored_windows(cfg) == windows == 1653
    score = 2 * 2 * 8 * windows
    pairs = 2 * 2 * 16 * kept
    assert fn.train_flops_per_sample(cfg) == 3 * (
        head + 3 * lin + 4 * mlp + proj + pairs) + score
    assert fn.scan_bytes(cfg) == 2 * t * 4 * 16


def test_the_two_copies_of_the_reference_are_one_text():
    import mxnet_tpu.models.minicpm_sala_reference as theirs

    with open(lib.find("reference", CFG["reference"], ".py")) as ours, \
            open(theirs.__file__) as program:
        assert ours.read() == program.read()


# scope paths as the step compiled for the chip names them
_STEP = "jit(step)/fwd_bwd/"
SCOPES = {
    "fusion.1": _STEP + "jvp(linattn/layer1_linattn)/jit(_linattn_block)/"
                        "core/ssd_fwd_bf16_q128_p128_n128/pallas_call:",
    "fusion.2": _STEP + "transpose(jvp(linattn/layer2_linattn))/"
                        "jit(_linattn_block)/core/ssd_bwd_bf16_q128_p128_"
                        "n128/pallas_call:",
    "fusion.3": _STEP + "jvp(linattn/layer1_linattn)/jit(_linattn_block)/"
                        "checkpoint/norm/rsqrt:",
    "fusion.4": _STEP + "transpose(jvp(linattn/layer3_linattn))/"
                        "jit(_linattn_block)/rematted_computation/gate/"
                        "logistic:",
    "fusion.5": _STEP + "jvp(linattn/layer1_linattn)/jit(_linattn_block)/"
                        "reshape:",
    "fusion.6": _STEP + "jvp(fc/layer1_linattn_q_proj)/dot_general:",
    "fusion.7": _STEP + "transpose(jvp(fc/layer3_linattn_o_proj))/"
                        "dot_general:",
    "fusion.8": _STEP + "jvp(attn/layer0_blocks)/blocks/pool/reduce_window:",
    "fusion.9": _STEP + "jvp(attn/layer0_blocks)/blocks/score/while/body/"
                        "dot_general:",
    "fusion.10": _STEP + "jvp(attn/layer0_blocks)/blocks/choose/"
                         "topk_mask_f32_r128_s256_k64_live/pallas_call:",
    "fusion.11": _STEP + "jvp(attn/layer0_blocks)/blocks/reshape:",
    "fusion.12": _STEP + "jvp(attn/layer0_attn)/select/flashsel_fwd_bf16_"
                         "q1024_k1024_g16/pallas_call:",
    "fusion.13": _STEP + "jvp(attn/layer1_linattn_q_rope)/rope_fwd_bf16_"
                         "r512_h16_d128/pallas_call:",
    "fusion.14": _STEP + "jvp(norm/layer1_linattn_q_norm)/rsqrt:",
    "fusion.15": _STEP + "jvp(fc/layer0_q_proj)/dot_general:",
    "fusion.16": _STEP + "jvp(fc/layer0_gdn_q_proj)/dot_general:",
    "fusion.17": _STEP + "jvp(ssm/layer0_ssm)/jit(_mamba2_block)/scan/mul:",
    "fusion.18": _STEP + "jvp(attn/layer0_index)/index/topk/topk_mask_f32_"
                         "r128_s8192_k2048_causal/pallas_call:",
}
FILED = {
    "fusion.1": "linattn_core", "fusion.2": "linattn_core",
    "fusion.3": "linattn_norm", "fusion.4": "linattn_gate",
    "fusion.5": "linattn_other", "fusion.6": "linattn_proj",
    "fusion.7": "linattn_proj", "fusion.8": "blocks_pool",
    "fusion.9": "blocks_score", "fusion.10": "blocks_choose",
    "fusion.11": "blocks_other", "fusion.12": None, "fusion.13": None,
    "fusion.14": None, "fusion.15": None, "fusion.16": None,
    "fusion.17": None, "fusion.18": None}


def test_the_table_files_every_op_of_the_two_nodes_under_its_owner():
    assert {k: linblock_scopes.part_of(v)
            for k, v in SCOPES.items()} == FILED
    assert list(linblock_scopes.TABLE) == [
        "linattn_core", "linattn_norm", "linattn_gate", "linattn_other",
        "linattn_proj", "blocks_pool", "blocks_score", "blocks_choose",
        "blocks_other"]


def test_the_reduction_sums_the_parts_and_needs_one_of_the_two_nodes():
    import reduce_trace

    ops = [("fusion.%d" % i, 1000 * i, 100) for i in range(1, 19)]
    raw = {"host": [(0, reduce_trace.SLICE_BEGIN, 0, 10),
                    (0, reduce_trace.SLICE_END, 40000, 10)],
           "devices": {0: {"ops": ops,
                           "modules": step_events(10, 40000)}}}
    red = linblock_scopes.reduce(raw, {0: SCOPES})
    assert red["linattn_core"] == red["linattn_proj"] == pytest.approx(
        200e-9)
    assert red["linattn"] == pytest.approx(500e-9)
    assert red["blocks"] == pytest.approx(400e-9)
    assert red["blocks_choose"] == pytest.approx(100e-9)
    # another model's program: projections and scans, neither node
    rest = {k: v for k, v in SCOPES.items()
            if "linattn/" not in v and "/blocks" not in v}
    assert linblock_scopes.reduce(raw, {0: rest}) is None
    # a model with the one and not the other reads the one
    lin_only = {k: v for k, v in SCOPES.items() if "/blocks" not in v}
    red = linblock_scopes.reduce(raw, {0: lin_only})
    assert red["blocks"] is None and red["linattn"] == pytest.approx(500e-9)
    assert linblock_scopes.reduce(dict(raw, host=[]), {0: SCOPES}) is None


def _run(**over):
    run = {"cfg": CFG, "cell": {"name": CELL}, "whole_steps": 4,
           "batch": 1, "chips": 1, "flops_multiplier": 3,
           "peak": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
           "linblock_scopes": {
               "linattn_core": 0.054, "linattn_norm": 0.016,
               "linattn_gate": 0.008, "linattn_other": 0.002,
               "linattn": 0.080, "linattn_proj": 0.288,
               "blocks_pool": 0.001, "blocks_score": 0.007,
               "blocks_choose": 0.005, "blocks_other": 0.001,
               "blocks": 0.014}}
    run.update(over)
    return run


def _kept(steps=21, off=0):
    return {"telemetry": {"attention.block_keys_kept": {
        "sum": float(steps * KEPT + off), "count": steps,
        "counts": [], "buckets": []}}}


def _read(name, run, trace=True, counters=None):
    return lib.load_module("layer_metrics", name).compute(
        {"devices": {}} if trace else None,
        _kept() if counters is None else counters, run)


def test_the_seven_readers_read_what_they_say():
    run = _run()
    assert _read("linattn_device_ms", run) == pytest.approx(20.0)
    assert _read("linattn_core_device_ms", run) == pytest.approx(13.5)
    assert _read("linattn_proj_device_ms", run) == pytest.approx(72.0)
    assert _read("block_select_device_ms", run) == pytest.approx(3.5)
    # three layers, three forwards each, bound by bytes: 9 x 0.3278 ms of
    # 13.5
    assert _read("linattn_core_roofline_share", run) == pytest.approx(
        100 * 9 * 0.32776 / 13.5, rel=1e-3)
    # one layer, once a step, bound by its products: 0.1739 ms of 3.5
    assert _read("block_select_roofline_share", run) == pytest.approx(
        100 * 0.17393 / 3.5, rel=1e-3)
    assert 0 < _read("block_select_roofline_share", run) < 100
    value, ok, why = _read("block_keys_over_expected", run)
    assert (value, ok) == (1.0, True) and "21 executions" in why
    # one key too many in one step of the window fails the run
    value, ok, _ = _read("block_keys_over_expected", run,
                         counters=_kept(off=1))
    assert value > 1.0 and ok is False


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_where_there_is_nothing(name):
    """No slice, no such scopes or counter (the parent's program, another
    model's), a configuration with another operations module: None, never
    zero, never a raise (the benchmark's files are laid over older
    checkouts)."""
    falcon = lib.load_json(lib.find("configs", "falcon_h1_34b", ".json"))
    nothing = {"telemetry": {}}
    bare = dict(linblock_scopes=None)
    assert _read(name, _run(**bare), counters=nothing) is None
    assert _read(name, _run(cfg=falcon, **bare), counters=nothing) is None
    if name == "block_keys_over_expected":
        assert _read(name, _run(), counters={}) is None
        assert _read(name, _run(cfg=falcon)) is None
    else:
        assert _read(name, _run(), trace=False) is None
        assert _read(name, _run(whole_steps=0)) is None
    if name.endswith("roofline_share"):
        # an operations module that counts no scan and no scorer
        keye = lib.load_json(lib.find("configs", "keye_vl2_30b_a3b",
                                      ".json"))
        assert _read(name, _run(peak=None)) is None
        assert _read(name, _run(cfg=keye)) is None
    entry = _entry("per_layer", name)
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "train_samples_s"
    assert entry["layer"] == "ops and kernels"
    assert (entry["source"], entry["unit"]) == (
        ("program_counter", "ratio") if name == "block_keys_over_expected"
        else ("device_trace", "%" if name.endswith("share") else "ms/step"))


def test_the_cell_and_its_mix():
    cell = lib.load_json(lib.find("cells", CELL, ".json"))
    falcon = lib.load_json(lib.find("cells", "falcon_h1_fit_share_4k",
                                    ".json"))
    assert cell["traffic"] == MIX
    assert cell["chips"] == 1 and cell["config"] == "minicpm_sala_9b"
    # the dense kind as it stands, under a mix that differs from the 4k
    # one in nothing: the sequence length is the configuration's
    mix = lib.load_json(lib.find("traffic", MIX, ".json"))
    assert mix == lib.load_json(lib.find(
        "traffic", "fit_tokens_dense_resident_b1_t4096", ".json"))
    assert mix["kind"] == "fit_tokens_dense" and mix["batch"] == 1
    assert mix["check_last_positions"] == 256
    assert set(cell["expect"]) == set(falcon["expect"])
    assert set(cell["expect"]["reference"]) == set(
        falcon["expect"]["reference"])
    # half the variance of the logits at the stated initialisation: the
    # head is Normal(sqrt(4096) / 256), the logits are divided by 16
    assert cell["expect"]["first_loss_excess"] == pytest.approx(0.5)
    assert _entry("workloads", CELL) == {
        "name": CELL, "config": "minicpm_sala_9b", "traffic": MIX,
        "chips": 1, "why": cell["why"]}
    manifest = lib.load_json(lib.MANIFEST)
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    assert len(manifest["workloads"]) == 19
    # the metrics without a list of cells read here as they stand
    for name in ("model_mfu", "step_device_ms", "step_unscoped_device_ms",
                 "device_idle_share", "hbm_peak_gb"):
        assert "workloads" not in _entry("per_layer", name)
    # an accepted entry's list is closed to a model_config PR
    for name in ("attn_select_device_ms", "attn_select_roofline_share",
                 "ssm_scan_device_ms", "attn_proj_device_ms",
                 "dense_mlp_device_ms"):
        assert CELL not in _entry("per_layer", name)["workloads"]


def test_rehearsal_runs_the_cell_end_to_end_with_the_trace_on():
    """The whole path at a tiny size on the CPU: from_config, Module.fit
    through the fused step, the dense kind's checks, the reference check
    in float32 (where the program and the reference agree to summation
    order and choose the same blocks, and the bf16 reference does not),
    the count of kept pairs observed from inside the step and every reader
    returning nothing or a value without a raise."""
    proc = run_bench(["--workload", CELL, "--seed", "3000000019",
                      "--seconds", "1", "--trace", "1", "--rehearse-cpu"])
    result = check_rehearsal(proc, ["fused_step_share",
                                    "fit_lookahead_share",
                                    "block_keys_over_expected"])
    assert "matches_reference ok=True" in proc.stdout
    assert '"within_limits": false' in proc.stdout
    assert "loss_is_the_only_output ok=True" in proc.stdout
    assert "first_loss_near_expected ok=True" in proc.stdout
    assert "block_keys_over_expected ok=True" in proc.stdout
    assert "want 12888 each" in proc.stdout      # 2 sequences of 6,444
    assert "window_compiles=0" in proc.stdout
    device_only = set(READERS) - {"block_keys_over_expected"}
    assert not device_only & set(result["metrics"])  # no device, no value
