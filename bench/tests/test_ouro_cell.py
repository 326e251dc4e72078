"""What the ``ouro_2_6b`` configuration brought: its file against the
published keys, the parameters the cut counted (each weight ONCE though
four passes read it), its operations and bytes against the hand count
(a layer once a VISIT; at the cell's size, at the rehearsal's, and T = 1
against a plain stack), the benchmark's copy of the reference against the
program's, the table of ``ouro_scopes`` on scope paths, the nine readers
on handed-in reductions, the new traffic kind's parts, and the cell's
rehearsal end to end."""
import math

import pytest

import lib
import ouro_scopes
from helpers import check_rehearsal, run_bench

CFG = lib.load_json(lib.find("configs", "ouro_2_6b", ".json"))
CELL = "ouro_fit_loop_4k"
F = "full_attention"
# ByteDance/Ouro-2.6B's config.json, the keys that say its shape (the
# model-configs catalog's ``config``)
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": [F] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152}
WIDTHS = ("hidden_size", "intermediate_size", "num_attention_heads",
          "num_key_value_heads", "head_dim")


def test_configuration_keeps_every_published_width_and_states_its_cut():
    changed = {k for k, v in PUBLISHED.items() if CFG[k] != v}
    assert changed == set(CFG["reduced"]) == set(CFG["reduced_why"]) == {
        "num_hidden_layers", "layer_types", "vocab_size"}
    assert not changed & set(WIDTHS)
    assert CFG["total_ut_steps"] == 4           # the loop is not cut
    for key in changed:           # the uncut value stands beside the held
        assert CFG["published"][key] == PUBLISHED[key]
    # the floors: a whole period (one layer) and four more, an eighth of
    # the vocabulary
    assert CFG["layer_types"] == [F] * 6 and CFG["num_hidden_layers"] == 6
    assert CFG["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert CFG["num_hidden_layers"] * 8 == PUBLISHED["num_hidden_layers"]
    assert CFG["deployment"].startswith("Eight pipeline stages of six")
    assert "vocabulary parallelism" in CFG["deployment"]
    assert CFG["kwargs"] == {"seq_len": 4096, "dtype": "bfloat16"}
    assert CFG["input_shape"] == [1, 1, 4096]
    assert CFG["num_classes"] == CFG["vocab_size"]
    assert CFG["assumed"]["exit_beta"] == 0.05
    for topic in ("exit_beta_why", "block", "loop", "exit_gate", "objective",
                  "attention", "unread", "weights", "dtype", "optimizer"):
        assert CFG["assumed"][topic]
    assert "NORMED state" in CFG["assumed"]["loop"]
    assert "WITH a bias" in CFG["assumed"]["exit_gate"]
    assert "read by nothing" in CFG["assumed"]["exit_gate"]
    assert "Stage II" in CFG["assumed"]["objective"]
    assert "early_exit_threshold" in CFG["assumed"]["objective"]
    assert "no query/key norm" in CFG["assumed"]["attention"]
    assert "ONE momentum" in CFG["assumed"]["optimizer"]
    manifest = [c for c in lib.load_json(lib.MANIFEST)["configs"]
                if c["name"] == "ouro_2_6b"][0]
    assert manifest["reduced"] == CFG["reduced"]
    assert manifest["source"] == CFG["source"]


def test_the_symbol_holds_each_weight_once_and_the_parameters_the_cut_counted():
    """ISSUE 64's arithmetic: a layer 4 x 2048^2 + 3 x 2048 x 5632 =
    16.78 + 34.60 = 51.38 M, six 308.3 M; embedding and head 6144 x 2048
    = 12.58 M each; four gammas a layer, the final norm's and the gate
    under 0.1 M: 333.6 M, each argument ONCE though four passes read it
    (7 matrices and 4 gammas a layer and 5 beside them: the issue's
    "nine matrices" counts two that are not there)."""
    import numpy as np

    sym = lib.resolve(CFG["factory"])(CFG, **CFG["kwargs"])
    names = sym.list_arguments()
    assert len(names) == len(set(names)) == 2 + 11 * 6 + 5
    shapes, outs, _ = sym.infer_shape(data=(1, 4096),
                                      softmax_label=(1, 4096))
    assert outs == [(1,), (4,)]
    sizes = {n: int(np.prod(s)) for n, s in zip(names, shapes)
             if n not in ("data", "softmax_label")}
    for name in ("q", "k", "v", "o"):
        assert sizes["layer5_%s_proj_weight" % name] == 2048 * 2048
    for name in ("gate", "up", "down"):
        assert sizes["layer0_%s_proj_weight" % name] == 2048 * 5632
    for name in ("input_layernorm", "input_layernorm_2",
                 "post_attention_layernorm", "post_attention_layernorm_2"):
        assert sizes["layer3_%s_gamma" % name] == 2048
    assert sizes["embed_weight"] == sizes["lm_head_weight"] == 6144 * 2048
    assert sizes["exit_gate_weight"] == 2048 and sizes["exit_gate_bias"] == 1
    assert sizes["final_norm_gamma"] == 2048
    layer = sum(v for k, v in sizes.items() if k.startswith("layer0_"))
    assert layer == pytest.approx(51.38e6, rel=1e-3)
    assert sum(sizes.values()) == pytest.approx(333.6e6, rel=1e-3)
    # the graph is four times the nodes a parameter: 24 visits' nodes
    internals = sym.get_internals().list_outputs()
    assert sum(1 for n in internals if n.endswith("_q_proj_output")) == 24
    assert sum(1 for n in internals if "norm" in n
               and n.endswith("_output")) == 100


def test_forward_flops_and_bytes_match_the_hand_count():
    """Per sequence of 4096, forward, a VISIT: projections 2 x 4096 x 4
    x 2048^2 = 137.4 G, SwiGLU 2 x 4096 x 3 x 2048 x 5632 = 283.5 G,
    scores and values 2 x 2 x 2048 x 4096 x 4097 / 2 = 68.7 G: 489.6 G;
    24 visits 11.75 T; a pass's head and gate 2 x 4096 x 2048 x 6145 =
    0.103 T, four 0.41 T: 36.5 T a training step."""
    fn = lib.load_module("flops", CFG["flops"])
    t, d = 4096, 2048
    assert fn.passes(CFG) == 4 and fn.visits(CFG) == 24
    assert fn.projection_flops(CFG) == 2.0 * t * 4 * d * d
    assert fn.mlp_flops(CFG) == 2.0 * t * 3 * d * 5632
    assert fn.attention_flops(CFG) == 2.0 * 16 * 256 * t * (t + 1) / 2
    assert fn.attention_bytes(CFG) == 2.0 * t * 128 * (2 * 16 + 2 * 16)
    assert fn.exit_flops(CFG) == 2.0 * t * d * (6144 + 1)
    visit = (fn.projection_flops(CFG) + fn.mlp_flops(CFG)
             + fn.attention_flops(CFG))
    assert visit == pytest.approx(489.6e9, rel=1e-3)
    want = 24 * visit + 4 * fn.exit_flops(CFG)
    assert fn.forward_flops_per_sample(CFG) == pytest.approx(want, rel=1e-12)
    assert 3 * want == pytest.approx(36.5e12, rel=2e-3)
    # the four heads are 3.4% of the step, the SwiGLU 56%
    assert 4 * fn.exit_flops(CFG) / want == pytest.approx(0.034, abs=0.002)
    assert 24 * fn.mlp_flops(CFG) / want == pytest.approx(0.559, abs=0.005)
    # attention is bound by its operations: 0.349 ms a visit forward on
    # the v5e's peaks against 0.082 ms of bytes
    peaks = lib.load_json(lib.BENCH + "/peaks.json")["TPU v5 lite"]
    assert 1e3 * fn.attention_flops(CFG) / peaks["bf16_flops"] == \
        pytest.approx(0.3489, abs=0.001)
    assert 1e3 * fn.attention_bytes(CFG) / peaks["hbm_bytes_s"] == \
        pytest.approx(0.0819, abs=0.001)
    assert fn.TRAIN_MULTIPLIER == 3


def test_one_pass_counts_as_a_plain_stack_and_four_count_four_times():
    """T = 1 is a plain dense stack's count (OLMoE's function without its
    experts gives the same projections, triangle and head); T = 4 is
    four times the layers' and the heads' part: a count by parameters
    would read a quarter."""
    fn = lib.load_module("flops", CFG["flops"])
    olmoe = lib.load_module("flops", "olmoe_symbol")
    one = dict(CFG, total_ut_steps=1)
    t, d, layers = 4096, 2048, 6
    plain = (layers * (2.0 * t * 4 * d * d + olmoe.attn_kernel_flops(one)
                       + 2.0 * t * 3 * d * 5632)
             + 2.0 * t * d * 6144)
    gate = 2.0 * t * d
    assert fn.forward_flops_per_sample(one) == pytest.approx(plain + gate,
                                                             rel=1e-12)
    assert fn.forward_flops_per_sample(CFG) == pytest.approx(
        4 * fn.forward_flops_per_sample(one), rel=1e-12)
    assert fn.attention_flops(CFG) == olmoe.attn_kernel_flops(one)


def test_forward_flops_at_the_rehearsal_size_match_a_hand_count():
    """The rehearsal's tiny model (hidden 64, SwiGLU 48, 4 heads of 16,
    2 layers, 4 passes, vocabulary 512, T 40), by hand."""
    fn = lib.load_module("flops", CFG["flops"])
    tiny = lib.load_json(lib.BENCH + "/tests/rehearsal/%s.json" % CELL)
    cfg = lib.merge(CFG, tiny["config"])
    t = 40
    visit = (2 * t * 64 * 4 * 64 + 2 * 2 * 64 * t * (t + 1) // 2
             + 2 * t * 3 * 64 * 48)
    assert fn.visits(cfg) == 8
    assert fn.forward_flops_per_sample(cfg) == 8 * visit \
        + 4 * 2 * t * 64 * 513
    assert fn.attention_bytes(cfg) == 2 * t * 16 * 16


def test_the_two_copies_of_the_reference_are_one_text():
    import mxnet_tpu.models.ouro_reference as theirs

    with open(lib.find("reference", CFG["reference"], ".py")) as ours, \
            open(theirs.__file__) as program:
        assert ours.read() == program.read()


# scope paths as the step compiled for the chip names them
SCOPES = {
    "fusion.1": "jit(step)/fwd_bwd/jvp(fc/loop1_layer0_q_proj)/dot_general:",
    "fusion.2": "jit(step)/fwd_bwd/transpose(jvp(fc/loop4_layer5_o_proj))/"
                "dot_general:",
    "fusion.3": "jit(step)/fwd_bwd/jvp(fc/loop2_layer3_gate_proj)/"
                "dot_general:",
    "fusion.4": "jit(step)/fwd_bwd/transpose(jvp(fc/loop4_layer0_down_proj))/"
                "dot_general:",
    "fusion.5": "jit(step)/fwd_bwd/jvp(attn/loop1_layer2_attn)/full/"
                "pallas_call:",
    "fusion.6": "jit(step)/fwd_bwd/jvp(attn/loop3_layer1_k_rope)/mul:",
    "fusion.7": "jit(step)/fwd_bwd/transpose(jvp(norm/"
                "loop4_layer4_input_layernorm_2))/mul:",
    "fusion.8": "jit(step)/fwd_bwd/jvp(norm/loop2_final_norm)/mul:",
    "fusion.9": "jit(step)/fwd_bwd/jvp(fc/loop3_lm_head)/dot_general:",
    "fusion.10": "jit(step)/fwd_bwd/transpose(jvp(other/"
                 "loop1_lm_head_logp))/sub:",
    "fusion.11": "jit(step)/fwd_bwd/jvp(fc/loop2_exit_gate)/dot_general:",
    "fusion.12": "jit(step)/fwd_bwd/jvp(loss/exit_mix)/exp:",
    "fusion.13": "jit(step)/fwd_bwd/transpose(jvp(other/exit_nll))/slice:",
    "fusion.14": "jit(step)/fwd_bwd/jvp(loss/loss)/mul:",
    "fusion.15": "jit(step)/fwd_bwd/jvp(embed/embed)/gather:",
    "fusion.16": "jit(step)/fwd_bwd/jvp(fc/layer3_q_proj)/dot_general:",
    "fusion.17": "jit(step)/fwd_bwd/jvp(fc/lm_head)/dot_general:",
    "fusion.18": "jit(step)/update/mul:",
}


def test_the_table_files_the_nodes_scopes_by_part_and_by_pass():
    assert {k: ouro_scopes.parts_of(v) for k, v in SCOPES.items()} == {
        "fusion.1": (["layers", "proj"], 1),
        "fusion.2": (["layers", "proj"], 4),
        "fusion.3": (["layers", "mlp"], 2),
        "fusion.4": (["layers", "mlp"], 4),
        "fusion.5": (["layers"], 1), "fusion.6": (["layers"], 3),
        "fusion.7": (["layers"], 4), "fusion.8": ([], None),
        "fusion.9": (["exit"], None), "fusion.10": (["exit"], None),
        "fusion.11": (["exit"], None), "fusion.12": (["exit"], None),
        "fusion.13": (["exit"], None), "fusion.14": (["exit"], None),
        "fusion.15": ([], None), "fusion.16": ([], None),
        "fusion.17": ([], None), "fusion.18": ([], None)}
    assert list(ouro_scopes.PARTS) == ["layers", "proj", "mlp", "exit"]


def test_the_reduction_sums_the_parts_and_needs_a_looped_node():
    import reduce_trace

    ops = [("fusion.%d" % i, 1000 * i, 100) for i in range(1, 19)]
    raw = {"host": [(0, reduce_trace.SLICE_BEGIN, 0, 10),
                    (0, reduce_trace.SLICE_END, 30000, 10)],
           "devices": {0: {"ops": ops}}}
    red = ouro_scopes.reduce(raw, {0: SCOPES})
    assert red["layers"] == pytest.approx(700e-9)
    assert red["proj"] == red["mlp"] == pytest.approx(200e-9)
    assert red["exit"] == pytest.approx(600e-9)
    assert red["pass_s"] == {1: pytest.approx(200e-9),
                             2: pytest.approx(100e-9),
                             3: pytest.approx(100e-9),
                             4: pytest.approx(300e-9)}
    # another model's nodes, an exit-free stack: nothing
    rest = {k: v for k, v in SCOPES.items() if "/loop" not in v}
    assert ouro_scopes.reduce(raw, {0: rest}) is None
    assert ouro_scopes.reduce(dict(raw, host=[]), {0: SCOPES}) is None


def _run(**over):
    run = {"cfg": CFG, "cell": {"name": CELL}, "trace_steps": 5,
           "batch": 1, "chips": 1, "flops_multiplier": 3,
           "peak": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
           "exit_mass": [0.4, 0.3, 0.2, 0.1],
           "ouro_scopes": {"layers": 1.400, "proj": 0.300, "mlp": 0.600,
                           "exit": 0.050,
                           "pass_s": {1: 0.34, 2: 0.35, 3: 0.35, 4: 0.36}},
           "lm_scopes": {"class_s": {"attn": 0.250, "moe": 0.0, "norm": 0.150,
                                     "embed": 0.001},
                         "head_loss_s": 0.0, "moe_part_s": {}},
           "share_scopes": {"window": 0.0, "full": 0.200},
           "solar2_scopes": {"kda_fwd": 0.0, "kda_bwd": 0.0,
                             "flash_fwd": 0.060, "flash_bwd": 0.130}}
    run.update(over)
    return run


def _read(name, run, trace=True):
    return lib.load_module("layer_metrics", name).compute(
        {"devices": {}} if trace else None, {"telemetry": {}}, run)


READERS = ["ouro_layers_device_ms", "ouro_attn_device_ms",
           "ouro_attn_roofline_share", "ouro_proj_device_ms",
           "ouro_mlp_device_ms", "ouro_norm_device_ms",
           "ouro_exit_device_ms", "ouro_pass_last_over_first",
           "ouro_exit_mass_last"]


def test_the_nine_readers_read_what_they_say():
    run = _run()
    assert _read("ouro_layers_device_ms", run) == pytest.approx(280.0)
    assert _read("ouro_attn_device_ms", run) == pytest.approx(50.0)
    assert _read("ouro_proj_device_ms", run) == pytest.approx(60.0)
    assert _read("ouro_mlp_device_ms", run) == pytest.approx(120.0)
    assert _read("ouro_norm_device_ms", run) == pytest.approx(30.0)
    assert _read("ouro_exit_device_ms", run) == pytest.approx(10.0)
    # 24 visits, three forwards each, bound by operations: 72 x 0.3489 ms
    # of the scope's 40 ms a step; the flash ops hold 38 of the 40
    share, ok, why = _read("ouro_attn_roofline_share", run)
    assert share == pytest.approx(100 * 72 * 0.34891 / 40.0, rel=1e-3)
    assert share < 100 and ok, why
    _, ok, why = _read("ouro_attn_roofline_share", _run(solar2_scopes={
        "kda_fwd": 0.0, "kda_bwd": 0.0, "flash_fwd": 0.01,
        "flash_bwd": 0.02}))
    assert not ok and "share" in why             # a fallback, not the pair
    ratio, ok, why = _read("ouro_pass_last_over_first", run)
    assert ratio == pytest.approx(0.36 / 0.34) and ok, why
    scopes = dict(run["ouro_scopes"], pass_s={1: 0.2, 2: 0.35, 3: 0.35,
                                              4: 0.5})
    ratio, ok, why = _read("ouro_pass_last_over_first",
                           _run(ouro_scopes=scopes))
    assert ratio == pytest.approx(2.5) and not ok and "1.25" in why
    scopes = dict(run["ouro_scopes"], pass_s={1: 0.7, 2: 0.7})
    _, ok, why = _read("ouro_pass_last_over_first", _run(ouro_scopes=scopes))
    assert not ok and "want 1..4" in why
    value, ok, _ = _read("ouro_exit_mass_last", run)
    assert value == pytest.approx(10.0) and ok
    _, ok, why = _read("ouro_exit_mass_last",
                       _run(exit_mass=[0.4, 0.3, 0.2, 0.2]))
    assert not ok and "sum 1.1" in why


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_where_there_is_nothing(name):
    """No slice, no such scopes (the parent's program, another model's),
    a configuration with another operations module, no ``exit_mass``:
    None, never zero, never a raise (the benchmark's files are laid over
    older checkouts)."""
    olmo = lib.load_json(lib.find("configs", "olmo_hybrid_7b", ".json"))
    if name == "ouro_exit_mass_last":
        assert _read(name, _run(exit_mass=None)) is None
        assert _read(name, _run(exit_mass=[])) is None
    else:
        assert _read(name, _run(ouro_scopes=None)) is None \
            or name == "ouro_attn_roofline_share"
        assert _read(name, _run(), trace=False) is None
        assert _read(name, _run(cfg=olmo, ouro_scopes=None)) is None
        assert _read(name, _run(trace_steps=0)) is None
    if name == "ouro_attn_roofline_share":
        assert _read(name, _run(cfg=olmo)) is None
        assert _read(name, _run(peak=None)) is None
        assert _read(name, _run(share_scopes=None)) is None
    entry = [m for m in lib.load_json(lib.MANIFEST)["per_layer"]
             if m["name"] == name][0]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "train_samples_s"
    assert entry["layer"] == "ops and kernels"
    assert entry["source"] == ("program_counter"
                               if name == "ouro_exit_mass_last"
                               else "device_trace")


def test_the_cell_the_mix_and_the_kind():
    cell = lib.load_json(lib.find("cells", CELL, ".json"))
    assert cell["traffic"] == "fit_tokens_loop_resident_b1_t4096"
    assert cell["chips"] == 1
    mix = lib.load_json(lib.find("traffic", cell["traffic"], ".json"))
    olmoe = lib.load_json(lib.find(
        "traffic", "fit_tokens_resident_b1_t4096", ".json"))
    # the OLMoE cell's parameters, letter for letter, under the new kind
    assert mix == dict(olmoe, kind="fit_tokens_loop")
    kind = lib.load_module("traffic", mix["kind"])
    # fit_tokens' own set-up and window, loaded, not copied; a check of
    # its own over every exit
    assert kind.setup is kind.fit_tokens.setup
    assert kind.fit_tokens.__file__ == lib.find("traffic", "fit_tokens", ".py")
    assert "def reference_check" in open(kind.__file__).read()
    assert set(cell["expect"]["reference"]) == {
        "logits_p90_first_max", "logits_p90_max", "logits_max_max",
        "loss_abs_max", "exit_mass_abs_max"}
    # the first exit's limit is the one that separates (``measured``)
    assert cell["expect"]["reference"]["logits_p90_first_max"] < \
        cell["expect"]["reference"]["logits_p90_max"] / 2
    # half the variance of logits from a Normal(0.02) head over a
    # unit-rms vector of 2048, and the entropy of the halving exits
    assert cell["expect"]["first_loss_excess"] == pytest.approx(
        0.5 * 2048 * 0.02 ** 2)
    assert kind.halving_entropy(4) == pytest.approx(1.2130, abs=1e-4)
    assert kind.halving_entropy(1) == 0.0
    assert math.log(6144) + 0.4096 - 0.05 * kind.halving_entropy(4) == \
        pytest.approx(9.072, abs=1e-3)
    # (no word on where the cell stands in the manifest: five older tests
    # say "last" of their own and have been wrong since the next cell)
    entry = [w for w in lib.load_json(lib.MANIFEST)["workloads"]
             if w["name"] == CELL]
    assert len(entry) == 1 and entry[0]["config"] == "ouro_2_6b"


def test_rehearsal_runs_the_cell_end_to_end_with_the_trace_on():
    """The whole path at a tiny size on the CPU: from_config, Module.fit
    through the fused step on a symbol whose weights four passes read,
    the loop kind's checks, the reference check in float32 over all four
    exits (where the program and the reference agree to summation order,
    and the bf16 reference does not) and every reader returning nothing
    or a value without a raise."""
    proc = run_bench(["--workload", CELL, "--seed", "3000000019",
                      "--seconds", "1", "--trace", "1", "--rehearse-cpu"])
    result = check_rehearsal(proc, ["fused_step_share",
                                    "fit_lookahead_share",
                                    "ouro_exit_mass_last"])
    assert "matches_reference ok=True" in proc.stdout
    assert '"within_limits": false' in proc.stdout
    assert '"exits": 4' in proc.stdout
    assert "loss_and_exit_mass_are_the_outputs ok=True" in proc.stdout
    assert "first_loss_near_expected ok=True" in proc.stdout
    assert "ouro_exit_mass_last ok=True" in proc.stdout
    assert "window_compiles=0" in proc.stdout
    # no device, no value read from a trace
    assert set(READERS) & set(result["metrics"]) == {"ouro_exit_mass_last"}
