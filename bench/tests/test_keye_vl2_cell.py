"""What the ``keye_vl2_30b_a3b`` configuration brought: its file against
the published keys, the parameters the cut counted, its operations against
a hand count (the numbers of ISSUE 75, at the cell's size and at the
rehearsal's), the benchmark's copy of the reference against the program's,
``select_scopes`` on scope paths, the three readers on handed-in
reductions, the new kind's renaming of the keys, the cell beside the one
whose mix it follows, and the cell's rehearsal end to end."""
import numpy as np
import pytest

import lib
import select_scopes
from helpers import check_rehearsal, run_bench

CFG = lib.load_json(lib.find("configs", "keye_vl2_30b_a3b", ".json"))
CELL = "keye_vl2_fit_share_8k"
FLOPS = lib.load_module("flops", "keye_vl2_symbol")
# Kwai-Keye/Keye-VL-2.0-30B-A3B's config.json (the model-configs catalog's
# ``config``)
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
REDUCED = {"num_hidden_layers", "num_experts", "num_local_experts",
           "vocab_size"}
WIDTHS = ("hidden_size", "head_dim", "intermediate_size",
          "moe_intermediate_size", "num_attention_heads",
          "num_key_value_heads", "num_experts_per_tok", "sa_config",
          "rope_scaling", "rope_theta", "rms_norm_eps")
SELECTED = 14681088         # sum_t min(t + 1, 2048) over 8,192 rows
CAUSAL = 33558528           # 8192 x 8193 / 2


def test_configuration_keeps_every_published_width_and_states_its_cut():
    changed = {k for k, v in PUBLISHED.items() if CFG[k] != v}
    assert changed == set(CFG["reduced"]) == set(CFG["reduced_why"]) \
        == REDUCED
    assert not changed & set(WIDTHS)
    for key in changed:             # the uncut count beside the held
        assert CFG["published"][key] == PUBLISHED[key]
    # the floors: four layers of a period of one, 8 experts, an eighth of
    # the vocabulary
    assert CFG["num_hidden_layers"] == 5
    assert CFG["num_experts"] * 8 == CFG["num_local_experts"] * 8 \
        == PUBLISHED["num_experts"]
    assert CFG["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    share = CFG["share"]
    assert (share["experts_of"], share["expert_offset"]) == (128, 0)
    assert share["share_rows_bound"] == 2 * 8192 * 8 * 16 // 128
    assert "8 chips share each layer" in CFG["deployment"]
    assert CFG["env"] == {}
    # everything the issue marks as assumed is said
    for key in ("indexer", "indexer_rope", "indexer_training", "chunks",
                "head_norms", "rotation", "router", "left_out", "embedding",
                "optimizer", "weights", "objective"):
        assert CFG["assumed"][key], key
    assert "class Indexer" in CFG["assumed"]["indexer"]
    entry = [c for c in lib.load_json(lib.MANIFEST)["configs"]
             if c["name"] == "keye_vl2_30b_a3b"][0]
    assert set(entry["reduced"]) == REDUCED
    assert entry["source"] == CFG["source"]


def test_the_symbol_holds_the_parameters_the_cut_counted():
    """ISSUE 75's arithmetic: a layer holds 8.39 + 1.05 + 1.05 + 8.39 M of
    attention, 2.26 M of indexer, 0.26 M of router and 16 x 4.72 M of
    experts: 96.9 M; embedding and head 77.8 M: 562 M parameters, 4.50 GB
    of state."""
    sym = lib.resolve(CFG["factory"])(CFG, **CFG["kwargs"])
    shapes, _, _ = sym.infer_shape(data=(1, 8192), softmax_label=(1, 8192))
    sizes = {n: int(np.prod(s)) for n, s in zip(sym.list_arguments(), shapes)
             if n not in ("data", "softmax_label")}

    def layer(i, *only):
        return sum(v for k, v in sizes.items()
                   if k.startswith("layer%d_" % i)
                   and any(o in k for o in only or ("",)))

    attn = 2 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128
    assert layer(0, "q_proj", "k_proj", "v_proj", "o_proj", "q_norm",
                 "k_norm") == attn == pytest.approx(18.88e6, rel=1e-3)
    indexer = 2048 * 1024 + 2048 * 64 + 2048 * 16 + 2 * 64
    assert layer(0, "index_") == layer(4, "index_") == indexer \
        == pytest.approx(2.26e6, rel=2e-3)
    experts = 16 * 3 * 2048 * 768 + 2048 * 128
    assert layer(2, "moe_") == experts == pytest.approx(75.76e6, rel=1e-3)
    assert layer(0) == layer(4) == attn + indexer + experts + 2 * 2048
    assert layer(0) == pytest.approx(96.9e6, rel=1e-3)
    assert sizes["embed_weight"] == sizes["lm_head_weight"] == 18992 * 2048
    assert sum(sizes.values()) == pytest.approx(562.3e6, rel=5e-4)
    assert sum(sizes.values()) * 8 == pytest.approx(4.50e9, rel=1e-3)


def test_forward_flops_match_the_hand_count():
    """By hand at the cell's shape: the kept pairs are those of a
    2,048-key window (3.66 ms of required operations a layer at 32 heads
    of 128); the indexer scores all 33.6 M causal pairs once."""
    t = 8192
    assert FLOPS.selected_pairs(CFG) == SELECTED == sum(
        min(i + 1, 2048) for i in range(t))
    assert FLOPS.causal_pairs(CFG) == CAUSAL
    assert SELECTED / CAUSAL == pytest.approx(0.4375, abs=1e-4)
    assert FLOPS.layers(CFG) == 5
    assert FLOPS.select_flops(CFG) == 2.0 * 32 * 256 * SELECTED
    assert 3 * FLOPS.select_flops(CFG) / 197e12 * 1e3 == pytest.approx(
        3.66, abs=0.01)                                     # ms a layer
    assert FLOPS.select_bytes(CFG) == (
        2 * t * 128 * (4 * 32 + 4 * 4) + 2 * 4 * CAUSAL)
    # the pair is bound by its operations: the bytes take 0.7 ms
    assert FLOPS.select_bytes(CFG) / 819e9 * 1e3 == pytest.approx(
        0.70, abs=0.01)
    index_proj = 2.0 * t * 2048 * (16 * 64 + 64 + 16)
    index_scores = 2.0 * 16 * 64 * CAUSAL
    assert FLOPS.index_flops(CFG) == index_proj + index_scores
    assert index_scores / 1e9 == pytest.approx(68.7, abs=0.1)
    p = FLOPS.parts(CFG)
    assert p["projections"] == 5 * 2.0 * t * 2048 * (64 + 8) * 128
    assert p["index"] == 5 * (index_proj + index_scores)
    assert p["select_pairs"] == 5 * FLOPS.select_flops(CFG)
    assert FLOPS.expected_share_rows(CFG) == 8192
    assert p["experts"] == 5 * 2.0 * (t * 2048 * 128
                                      + 8192 * 3 * 2048 * 768)
    assert p["head"] == 2.0 * t * 2048 * 18992
    forward = FLOPS.true_forward_flops_per_sample(CFG)
    assert forward == sum(p.values())
    assert forward / 1e12 == pytest.approx(4.32, abs=0.01)
    # the indexer has no backward
    step = FLOPS.train_flops_per_sample(CFG)
    assert step == 3 * (forward - p["index"]) + p["index"]
    assert step / 1e12 == pytest.approx(11.91, abs=0.01)
    assert FLOPS.forward_flops_per_sample(CFG) * FLOPS.TRAIN_MULTIPLIER \
        == pytest.approx(step)
    assert 1e3 * step / 197e12 == pytest.approx(60.5, abs=0.1)   # ms


def test_forward_flops_at_the_rehearsal_size_match_a_hand_count():
    tiny = lib.merge(CFG, lib.load_json(lib.find(
        "tests/rehearsal", CELL, ".json"))["config"])
    t = 128
    assert FLOPS.layers(tiny) == 2
    assert FLOPS.selected_pairs(tiny) == sum(min(i + 1, 48)
                                             for i in range(t))
    assert FLOPS.select_flops(tiny) \
        == 2.0 * 8 * 32 * FLOPS.selected_pairs(tiny)
    assert FLOPS.index_score_flops(tiny) == 2.0 * 4 * 8 * (t * (t + 1) // 2)
    assert FLOPS.expected_share_rows(tiny) == t * 3 * 4 / 16.0


def test_the_two_copies_of_the_reference_are_one_text():
    import mxnet_tpu.models.keye_vl2_reference as theirs

    with open(lib.find("reference", CFG["reference"], ".py")) as ours, \
            open(theirs.__file__) as program:
        assert ours.read() == program.read()
    ref = lib.load_module("reference", CFG["reference"])
    assert ref.expert_layers(CFG) == [True] * 5


# scope paths as the step compiled for the chip names them
SCOPES = {
    "fusion.1": "jit(step)/fwd_bwd/jvp(attn/layer0_index)/index/dot_general:",
    "fusion.2": "jit(step)/fwd_bwd/jvp(attn/layer1_index)/index/topk/while/"
                "body/reduce_sum:",
    "fusion.3": "jit(step)/fwd_bwd/jvp(attn/layer0_attn)/select/"
                "flashsel_fwd_bf16_q1024_k1024_g8/pallas_call:",
    "fusion.4": "jit(step)/fwd_bwd/transpose(jvp(attn/layer1_attn))/select/"
                "flashsel_bwd_bf16_q1024_k1024_g8/pallas_call:",
    "fusion.5": "jit(step)/fwd_bwd/jvp(attn/layer0_q_rope)/mul:",
    "fusion.6": "jit(step)/fwd_bwd/jvp(fc/layer0_q_proj)/dot_general:",
    "fusion.7": "jit(step)/fwd_bwd/jvp(norm/layer0_q_norm)/mul:",
    "fusion.8": "jit(step)/fwd_bwd/jvp(attn/layer2_attn)/full/"
                "flash_fwd_bf16_q1024_k1024_e512/pallas_call:",
    # a latent-attention node's selected kernels (dots3): entries of
    # their own read them
    "fusion.9": "jit(step)/fwd_bwd/jvp(attn/layer3_attn)/select/"
                "flash2sel_fwd_bf16_q1024_k1024/pallas_call:",
    "fusion.10": "jit(step)/fwd_bwd/jvp(attn/layer3_attn)/latent/"
                 "dot_general:",
}


def test_the_table_files_the_indexer_and_the_selected_kernels_by_node():
    assert {k: select_scopes.part_of(v) for k, v in SCOPES.items()} == {
        "fusion.1": ("layer0_index", "index"),
        "fusion.2": ("layer1_index", "index_topk"),
        "fusion.3": ("layer0_attn", "select"),
        "fusion.4": ("layer1_attn", "select"),
        "fusion.5": None, "fusion.6": None, "fusion.7": None,
        "fusion.8": None,
        "fusion.9": ("layer3_attn", "select"),
        "fusion.10": ("layer3_attn", "latent")}
    assert select_scopes.part_of(None) is None


def _raw():
    import reduce_trace

    ops = [("fusion.%d" % i, 1000 * i, 100) for i in range(1, 11)]
    return {"host": [(0, reduce_trace.SLICE_BEGIN, 0, 10),
                     (0, reduce_trace.SLICE_END, 30000, 10)],
            "devices": {0: {"ops": ops}}}


def test_the_reduction_files_by_node_and_needs_a_mask():
    red = select_scopes.reduce(_raw(), {0: SCOPES})
    assert red == {
        "layer0_index": {"index": pytest.approx(100e-9)},
        "layer1_index": {"index_topk": pytest.approx(100e-9)},
        "layer0_attn": {"select": pytest.approx(100e-9)},
        "layer1_attn": {"select": pytest.approx(100e-9)},
        "layer3_attn": {"select": pytest.approx(100e-9),
                        "latent": pytest.approx(100e-9)}}
    run = {"trace_steps": 1, "select_scopes": red}
    # plain Attention's nodes only: layer3_attn traces ``latent``
    traced = {"devices": {}}
    assert select_scopes.ms(traced, run, ("select",), plain_only=True) \
        == pytest.approx(200e-6)
    assert select_scopes.ms(traced, run, ("select",)) \
        == pytest.approx(300e-6)
    # a model without an indexer or a selection (Trinity-Mini, OLMoE):
    # nothing, whatever its attention nodes are named
    rest = {k: v for k, v in SCOPES.items()
            if "/index/" not in v and "/select/" not in v}
    assert select_scopes.reduce(_raw(), {0: rest}) is None
    assert select_scopes.reduce(dict(_raw(), host=[]), {0: SCOPES}) is None


def _run(**over):
    run = {"cfg": CFG, "cell": {"name": CELL}, "trace_steps": 5,
           "batch": 1, "chips": 1, "flops_multiplier": 3,
           "peak": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
           "select_scopes": dict(
               [("layer%d_index" % i, {"index": 0.010, "index_topk": 0.020})
                for i in range(5)]
               + [("layer%d_attn" % i, {"select": 0.080})
                  for i in range(5)])}
    run.update(over)
    return run


def _read(name, run, trace=True):
    return lib.load_module("layer_metrics", name).compute(
        {"devices": {}} if trace else None, {"telemetry": {}}, run)


READERS = ["attn_select_device_ms", "attn_select_roofline_share",
           "key_index_device_ms"]


def test_the_three_readers_read_what_they_say():
    run = _run()
    assert _read("attn_select_device_ms", run) == pytest.approx(80.0)
    assert _read("key_index_device_ms", run) == pytest.approx(30.0)
    # five layers, three forwards each of the kept pairs x 32 heads x 256
    # multiply-adds at 197 T/s, of 80 ms
    share = _read("attn_select_roofline_share", run)
    assert share == pytest.approx(
        100 * (5 * 3 * 2.0 * 32 * 256 * SELECTED / 197e12 * 1e3) / 80.0,
        rel=1e-9)
    assert share == pytest.approx(22.89, abs=0.01)
    # at the required time of every live causal pair the share reads what
    # the entry's docstring says is the most a mask can read: 43.7%
    causal_ms = 5 * 3 * 2.0 * 32 * 256 * CAUSAL / 197e12 * 1e3
    at_best = _run(select_scopes={
        "layer0_attn": {"select": causal_ms * 5 / 1e3}})
    assert _read("attn_select_roofline_share", at_best) == pytest.approx(
        43.75, abs=0.01)
    # a latent node's selected kernels are not plain Attention's
    latent = _run(select_scopes={"layer0_attn": {"select": 0.1,
                                                 "latent": 0.1},
                                 "layer0_index": {"index": 0.05}})
    assert _read("attn_select_device_ms", latent) is None
    assert _read("attn_select_roofline_share", latent) is None
    assert _read("key_index_device_ms", latent) == pytest.approx(10.0)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_where_there_is_nothing(name):
    """No slice, no such scopes (the parent's program, another model's),
    a configuration with another operations module: None, never zero,
    never a raise (the benchmark's files are laid over older
    checkouts)."""
    trinity = lib.load_json(lib.find("configs", "trinity_mini", ".json"))
    assert _read(name, _run(select_scopes=None)) is None
    assert _read(name, _run(cfg=trinity, select_scopes=None)) is None
    assert _read(name, _run(), trace=False) is None
    assert _read(name, _run(trace_steps=0)) is None
    if name.endswith("roofline_share"):
        assert _read(name, _run(peak=None)) is None
        # another model's operations module counts no selection
        assert _read(name, _run(cfg=trinity)) is None
    entry = [m for m in lib.load_json(lib.MANIFEST)["per_layer"]
             if m["name"] == name][0]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "train_samples_s"
    assert entry["layer"] == "ops and kernels"
    assert entry["source"] == "device_trace"
    assert entry["unit"] == ("%" if name.endswith("roofline_share")
                             else "ms/step")
    assert entry["better"] == ("higher" if name.endswith("roofline_share")
                               else "lower")
    # named for the mechanism: no model's name, no file under
    # tests/named_for_a_model/
    assert "keye" not in name


def test_the_kind_hands_the_select_kind_the_names_it_reads(monkeypatch):
    kind = lib.load_module("traffic", "fit_tokens_share_sa")
    seen = {}

    def run(state, seconds, trace):
        seen.update(state["cfg"])
        return "the select kind's"

    monkeypatch.setattr(kind.select, "run", run)
    assert kind.run({"cfg": CFG}, 1.0, None) == "the select kind's"
    assert seen["n_routed_experts"] == 16
    assert seen["index_topk"] == 2048
    assert seen["layer_types"] == ["full_attention"] * 5
    assert {k: v for k, v in seen.items() if k not in (
        "n_routed_experts", "index_topk", "layer_types")} == CFG
    assert kind.setup is kind.select.setup


def test_the_cell_follows_the_dots3_cells_mix_at_twice_the_length():
    cell = lib.load_json(lib.find("cells", CELL, ".json"))
    dots3 = lib.load_json(lib.find("cells", "dots3_note_fit_share_4k",
                                   ".json"))
    assert cell["traffic"] == "fit_tokens_share_sa_resident_b1_t8192"
    assert cell["chips"] == 1
    mix = lib.load_json(lib.find("traffic", cell["traffic"], ".json"))
    theirs = lib.load_json(lib.find("traffic", dots3["traffic"], ".json"))
    assert mix["kind"] == "fit_tokens_share_sa"
    assert {k: v for k, v in mix.items()
            if k not in ("kind", "check_last_positions")} \
        == {k: v for k, v in theirs.items()
            if k not in ("kind", "check_last_positions")}
    assert mix["batch"] == 1 and CFG["kwargs"]["seq_len"] == 8192
    # the kinds under this one read these names of the configuration
    for key in ("num_experts", "sa_config", "num_experts_per_tok",
                "num_hidden_layers", "share", "reference"):
        assert key in CFG
    assert set(cell["expect"]["reference"]) == set(
        dots3["expect"]["reference"])
    # half the variance of logits from a Normal(0.02) head over a
    # unit-rms vector of 2048
    assert cell["expect"]["first_loss_excess"] == pytest.approx(
        0.5 * 2048 * 0.02 ** 2)
    assert len(cell["why"]) <= 200 and cell["who"] and cell["distorts"]
    manifest = lib.load_json(lib.MANIFEST)
    entry = [w for w in manifest["workloads"] if w["name"] == CELL][0]
    assert entry == {"name": CELL, "config": "keye_vl2_30b_a3b",
                     "traffic": cell["traffic"], "chips": 1,
                     "why": cell["why"]}


def test_rehearsal_runs_the_cell_end_to_end_with_the_trace_on():
    """The whole path at a tiny size on the CPU: from_config, Module.fit
    through the fused step (two layers choosing 48 of 128 keys for 8
    heads on 2), the share kind's checks and the select kind's two, the
    reference check in float32 (where the program and the reference agree
    to summation order, and the bf16 reference does not) and every reader
    returning nothing or a value without a raise."""
    proc = run_bench(["--workload", CELL, "--seed", "3000000019",
                      "--seconds", "1", "--trace", "1", "--rehearse-cpu"])
    check_rehearsal(proc, ["fused_step_share", "fit_lookahead_share"])
    assert "matches_reference ok=True" in proc.stdout
    assert '"within_limits": false' in proc.stdout
    assert "experts_routed_over_all ok=True" in proc.stdout
    assert "2 expert layers of 2" in proc.stdout
    assert "keys_selected_exact ok=True" in proc.stdout
    assert "2 full layers of 2" in proc.stdout
    assert "selection_ties_bounded ok=True" in proc.stdout
