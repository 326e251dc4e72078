"""What the ``xing4_29b_a4b`` configuration brought: its file against the
published keys, the parameters its cut counted, its operations and bytes
counts against the hand counts, the benchmark's copy of the reference
against the program's, the scope reduction of ``hc_scopes`` on a scope
table, the eight readers on handed-in reductions (a number where the scope
is present, ``None`` where it is not), and the kind that fronts the
share kind."""
import math

import pytest

import hc_scopes
import lib

CFG = lib.load_json(lib.find("configs", "xing4_29b_a4b", ".json"))
CELL = "xing4_fit_share_4k"
# XingChen-AGI/Xing4.0-29B-A4B's config.json (the model-configs catalog's
# ``config``)
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "q_lora_rank": 768, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
          "qk_rope_head_dim", "v_head_dim", "num_experts_per_tok",
          "num_attention_heads", "n_shared_experts", "routed_scaling_factor",
          "hc_mult", "hc_sinkhorn_iters", "rope_scaling",
          "num_nextn_predict_layers")
READERS = ["hc_mix_device_ms", "hc_mix_roofline_share", "hc_coeff_device_ms",
           "hc_sinkhorn_device_ms", "hc_res_sum_err", "mtp_device_ms",
           "mtp_loss_over_main", "mla_q_latent_device_ms"]


def test_configuration_keeps_every_published_width_and_states_its_cut():
    changed = {k for k, v in PUBLISHED.items() if CFG[k] != v}
    assert changed == set(CFG["reduced"]) == set(CFG["reduced_why"]) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size"}
    assert not changed & set(WIDTHS)
    for key in changed:           # the uncut count stands beside the held
        assert CFG["published"][key] == PUBLISHED[key]
    # the floors: four expert layers after the dense one, 8 experts, an
    # eighth of the vocabulary
    assert CFG["num_hidden_layers"] - CFG["first_k_dense_replace"] == 4
    assert CFG["n_routed_experts"] == 8
    assert CFG["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert CFG["share"] == {"experts_of": 64, "expert_offset": 0,
                            "share_rows_bound": 4096}
    assert "8 chips share each layer" in CFG["deployment"]
    assert CFG["kwargs"] == {"seq_len": 4096, "dtype": "bfloat16"}
    assert CFG["num_classes"] == CFG["vocab_size"]
    for topic in ("stream_replication", "stream_exit", "stream_norm",
                  "hc_eps", "res_clamp", "sinkhorn_order", "mtp_input",
                  "mtp_block", "mtp_sharing", "mtp_loss_weight",
                  "rope_interleave", "rope_scaling", "weights", "router",
                  "optimizer", "objective", "dtype"):
        assert CFG["assumed"][topic]
    manifest = lib.load_json(lib.MANIFEST)
    entry = [c for c in manifest["configs"] if c["name"] == "xing4_29b_a4b"][0]
    assert entry["reduced"] == CFG["reduced"]
    assert entry["source"] == CFG["source"]
    assert manifest["configs"][-1] is entry       # added at the end
    assert manifest["workloads"][-1]["name"] == CELL
    assert [m["name"] for m in manifest["per_layer"][-8:]] == READERS


def test_the_symbol_holds_the_parameters_the_cut_counted():
    """913.4 M parameters (ISSUE 69's arithmetic): latent attention 28.41 M
    a block, the mixing 0.69 M, the shared expert 11.01 M, the router
    0.23 M, 8 routed experts of 11.01 M: 128.42 M an expert block, four
    of them; the dense block 128.19 M; the module one expert block and a
    7168 x 3584 projection, 154.11 M; embedding and head 117.44 M."""
    import numpy as np

    sym = lib.resolve(CFG["factory"])(CFG, **CFG["kwargs"])
    shapes, _, _ = sym.infer_shape(data=(1, 4096), softmax_label=(1, 4096))
    names = sym.list_arguments()
    sizes = {n: int(np.prod(s)) for n, s in zip(names, shapes)
             if n not in ("data", "softmax_label")}
    assert names.count("embed_weight") == names.count("lm_head_weight") == 1
    assert sizes["layer1_q_latent_a_proj_weight"] == 3584 * 768
    assert sizes["layer1_q_latent_b_proj_weight"] == 768 * 6144
    assert sizes["layer1_kv_a_proj_weight"] == 3584 * 576
    assert sizes["layer1_attn_up_weight"] == 512 * 8192
    assert sizes["layer1_o_proj_weight"] == 4096 * 3584
    assert sizes["layer1_attn_hc_phi"] == 24 * 14336
    assert sizes["layer1_moe_gate_weight"] == 3584 * 64
    assert sizes["layer1_moe_gate_up_weight"] == 8 * 3584 * 2 * 1024
    assert sizes["layer0_gate_proj_weight"] == 3584 * 9216
    assert "layer0_moe_gate_weight" not in sizes
    assert sizes["mtp0_proj_weight"] == 7168 * 3584
    assert sizes["embed_weight"] == sizes["lm_head_weight"] == 16384 * 3584

    def total(prefix):
        return sum(v for k, v in sizes.items() if k.startswith(prefix))

    assert total("layer1_") == pytest.approx(128.42e6, rel=1e-3)
    assert total("layer0_") == pytest.approx(128.19e6, rel=1e-3)
    assert total("mtp0_") == pytest.approx(154.11e6, rel=1e-3)
    assert sum(sizes.values()) == pytest.approx(913.4e6, rel=1e-3)


def test_forward_flops_and_mixing_bytes_match_the_hand_count():
    """A token, forward (ISSUE 69): an expert block 92 MFLOP of products
    (five latent projections 56.8, the shared expert 22.0, the router 0.46,
    half a routed expert 11.0, the coefficient products 1.38) + 42 of
    causal scores; the dense block 298; the module 185 (an expert block
    and 51.4 of projection); two heads 235: 1.25 GFLOP a token, 15.4 TFLOP
    a step. The mixing: (9 x 4 + 5) x 4096 x 3584 elements a sub-layer,
    12 sub-layers, 2 bytes: 14.4 GB a step."""
    fn = lib.load_module("flops", CFG["flops"])
    t, d = 4096, 3584
    assert fn.blocks(CFG) == fn.mla_layers(CFG) == 6
    assert fn.expert_layers(CFG) == 5 and fn.modules(CFG) == 1
    assert fn.expected_share_rows(CFG) == 2048
    assert fn.mla_kernel_flops(CFG) == 2.0 * 32 * 320 * t * (t + 1) / 2
    assert fn.mla_projection_flops(CFG) == 2.0 * t * (
        d * 768 + 768 * 6144 + d * 576 + 512 * 8192 + 4096 * d)
    assert fn.mla_projection_flops(CFG) / t == pytest.approx(56.8e6, rel=2e-3)
    assert fn.shared_expert_flops(CFG) == 2.0 * t * 3 * d * 1024
    assert fn.moe_share_flops(CFG) == 2.0 * (t * d * 64 + 2048 * 3 * d * 1024)
    assert fn.hc_coeff_flops(CFG) == 2.0 * 2 * t * 14336 * 24
    expert = (fn.mla_projection_flops(CFG) + fn.shared_expert_flops(CFG)
              + fn.moe_share_flops(CFG) + fn.hc_coeff_flops(CFG))
    assert expert / t == pytest.approx(92e6, rel=5e-3)
    assert fn.mla_kernel_flops(CFG) / t == pytest.approx(42e6, rel=2e-3)
    dense = (fn.mla_projection_flops(CFG) + fn.hc_coeff_flops(CFG)
             + 2.0 * t * 3 * d * 9216 + fn.mla_kernel_flops(CFG))
    assert dense / t == pytest.approx(298e6, rel=5e-3)
    module = expert + fn.mla_kernel_flops(CFG) + 2.0 * t * 7168 * d
    assert module / t == pytest.approx(185e6, rel=5e-3)
    heads = 2 * 2.0 * t * d * 16384
    assert heads / t == pytest.approx(235e6, rel=2e-3)
    want = dense + 4 * (expert + fn.mla_kernel_flops(CFG)) + module + heads
    assert fn.forward_flops_per_sample(CFG) == pytest.approx(want, rel=1e-12)
    assert want / t == pytest.approx(1.254e9, rel=2e-3)
    assert 3 * want / 1e12 == pytest.approx(15.4, abs=0.05)
    assert fn.TRAIN_MULTIPLIER == 3
    # without the module: one head, no projection, five blocks
    bare = dict(CFG, num_nextn_predict_layers=0)
    assert fn.forward_flops_per_sample(bare) == pytest.approx(
        want - module - heads / 2, rel=1e-12)
    assert fn.hc_mix_bytes(CFG) == 2.0 * 12 * 41 * t * d
    assert fn.hc_mix_bytes(CFG) / 1e9 == pytest.approx(14.4, abs=0.05)
    assert fn.hc_mix_bytes(CFG, itemsize=4) == 2 * fn.hc_mix_bytes(CFG)
    # 17.6 ms at the v5e's 819 GB/s
    assert 1e3 * fn.hc_mix_bytes(CFG) / 819e9 == pytest.approx(17.6, abs=0.1)


def test_the_two_copies_of_the_reference_are_one_text():
    import mxnet_tpu.models.xing4_reference as theirs

    with open(lib.find("reference", CFG["reference"], ".py")) as ours, \
            open(theirs.__file__) as program:
        assert ours.read() == program.read()


# scope paths as the step compiled for the chip names them
SCOPES = {
    "fusion.1": "jit(step)/fwd_bwd/jvp(hc/layer4_attn_hc)/hc_coeff/"
                "dot_general:",
    "fusion.2": "jit(step)/fwd_bwd/transpose(jvp(hc/layer0_ffn_hc))/"
                "checkpoint/hc_sinkhorn/while/body/div:",
    "fusion.3": "jit(step)/fwd_bwd/jvp(hc/layer2_attn_hc_read)/hc_mix/add:",
    "fusion.4": "jit(step)/fwd_bwd/transpose(jvp(hc/mtp0_ffn_hc_write))/"
                "hc_mix/reduce_sum:",
    "fusion.5": "jit(step)/fwd_bwd/jvp(hc/layer2_attn_hc)/reshape:",
    "fusion.6": "jit(step)/fwd_bwd/jvp(fc/layer3_q_latent_a_proj)/"
                "dot_general:",
    "fusion.7": "jit(step)/fwd_bwd/transpose(jvp(norm/mtp0_q_latent_norm))/"
                "mul:",
    "fusion.8": "jit(step)/fwd_bwd/jvp(fc/mtp0_proj)/dot_general:",
    "fusion.9": "jit(step)/fwd_bwd/jvp(fc/layer3_o_proj)/dot_general:",
    "fusion.10": "jit(step)/fwd_bwd/jvp(moe/layer3_moe)/experts/"
                 "gmm_fwd_bf16_m256_k3584_n1024/pallas_call:",
}


def test_the_reduction_files_the_new_scopes_and_nothing_else():
    import reduce_trace

    ops = [("fusion.%d" % i, 1000 * i, 100 * i) for i in range(1, 11)]
    raw = {"host": [(0, reduce_trace.SLICE_BEGIN, 0, 10),
                    (0, reduce_trace.SLICE_END, 30000, 10)],
           "devices": {0: {"ops": ops}}}
    red = hc_scopes.reduce(raw, {0: SCOPES})
    assert red["hc_coeff"] == pytest.approx(100e-9)
    assert red["hc_sinkhorn"] == pytest.approx(200e-9)
    assert red["hc_mix"] == pytest.approx(700e-9)          # 3 and 4
    assert red["hc_other"] == pytest.approx(500e-9)
    assert red["q_latent"] == pytest.approx(1300e-9)       # 6 and 7
    # every node of the module, whatever else it is: 4, 7 and 8
    assert red["mtp"] == pytest.approx(1900e-9)
    other = {"fusion.9": SCOPES["fusion.9"], "fusion.10": SCOPES["fusion.10"]}
    assert hc_scopes.reduce(raw, {0: other}) is None
    only = hc_scopes.reduce(raw, {0: {"fusion.6": SCOPES["fusion.6"]}})
    assert only["q_latent"] == pytest.approx(600e-9)
    assert only["hc_mix"] is None and only["mtp"] is None


def _run(**over):
    cell = lib.load_json(lib.find("cells", CELL, ".json"))
    run = {"cfg": CFG, "cell": dict(cell, name=CELL), "trace_steps": 5,
           "batch": 1, "chips": 1, "flops_multiplier": 3,
           "peak": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
           "hc_scopes": {"hc_coeff": 0.080, "hc_sinkhorn": 0.020,
                         "hc_mix": 0.200, "hc_other": 0.001,
                         "q_latent": 0.035, "mtp": 0.210},
           "loss_parts": [9.9, 10.0], "hc_res_sum_err": 5e-5}
    run.update(over)
    return run


def _read(name, run, trace=True):
    return lib.load_module("layer_metrics", name).compute(
        {"devices": {}} if trace else None, {"telemetry": {}}, run)


def test_the_eight_readers_read_what_they_say():
    run = _run()
    assert _read("hc_mix_device_ms", run) == pytest.approx(40.0)
    assert _read("hc_coeff_device_ms", run) == pytest.approx(16.0)
    assert _read("hc_sinkhorn_device_ms", run) == pytest.approx(4.0)
    assert _read("mtp_device_ms", run) == pytest.approx(42.0)
    assert _read("mla_q_latent_device_ms", run) == pytest.approx(7.0)
    # 14.4 GB at 819 GB/s are 17.6 ms of the 40 + 16 + 0.2 under the two
    # scopes that pass over the streams (the iterations' 4 are left out)
    assert _read("hc_mix_roofline_share", run) == pytest.approx(
        100 * (1e3 * 2.0 * 12 * 41 * 4096 * 3584 / 819e9) / 56.2, rel=1e-9)
    assert _read("hc_mix_roofline_share", run) == pytest.approx(31.4, abs=0.1)
    assert _read("hc_mix_roofline_share", run) < 100
    assert _read("mtp_loss_over_main", run) == pytest.approx(10.0 / 9.9)
    value, ok, why = _read("hc_res_sum_err", run)
    assert value == 5e-5 and ok and "limit" in why
    limit = run["cell"]["expect"]["hc_res_sum_err_max"]
    value, ok, _ = _read("hc_res_sum_err", _run(hc_res_sum_err=2 * limit))
    assert not ok


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_where_there_is_nothing(name):
    """No slice, no such scopes (the parent's program, another model's),
    no such outputs, a configuration with another operations module:
    None, never zero, never a raise (the benchmark's files are laid over
    older checkouts)."""
    kanana = lib.load_json(lib.find("configs", "kanana_2_30b_a3b", ".json"))
    nothing = dict.fromkeys(("hc_coeff", "hc_sinkhorn", "hc_mix",
                             "hc_other", "q_latent", "mtp"))
    counters = name in ("hc_res_sum_err", "mtp_loss_over_main")
    bare = {"cfg": kanana, "cell": {"name": "kanana2_fit_share_8k",
                                    "expect": {}},
            "trace_steps": 5, "batch": 1, "chips": 1, "flops_multiplier": 3,
            "peak": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
            "hc_scopes": None}
    assert _read(name, bare) is None
    assert _read(name, dict(bare, hc_scopes=nothing)) is None
    if not counters:
        assert _read(name, _run(hc_scopes=None)) is None
        assert _read(name, _run(hc_scopes=nothing)) is None
        assert _read(name, _run(), trace=False) is None
    if name == "hc_mix_roofline_share":
        assert _read(name, _run(cfg=kanana)) is None
    entry = [m for m in lib.load_json(lib.MANIFEST)["per_layer"]
             if m["name"] == name][0]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "train_samples_s"
    assert entry["layer"] == "ops and kernels"
    assert entry["source"] == ("program_counter" if counters
                               else "device_trace")


class _Out:
    def __init__(self, values):
        self.values = values

    def asnumpy(self):
        import numpy as np
        return np.asarray(self.values, np.float32)


def test_the_kind_fronts_the_share_kind_and_adds_its_own_checks(monkeypatch):
    """``fit_tokens_share_layers`` sees the loss and five count vectors
    (the module's block as one more expert layer) and none of this kind's
    three last outputs; the two losses and the carry's error are this
    kind's own checks; whatever happens, ``fit_tokens.reference_check`` is
    put back."""
    kind = lib.load_module("traffic", "fit_tokens_share_mtp")
    assert kind.setup is kind.layers.setup
    cell = lib.load_json(lib.find("cells", CELL, ".json"))
    cell["traffic"] = lib.load_json(lib.find(
        "traffic", cell["traffic"], ".json"))
    assert cell["traffic"]["kind"] == "fit_tokens_share_mtp"
    assert cell["traffic"]["check_last_positions"] == 1024
    theirs = kind.fit_tokens.reference_check
    seen = {}

    def run_with(main, module, err, held_rows=256):
        rest = (16384 - 8 * held_rows) // 56
        layer = [held_rows] * 8 + [rest] * 56
        layer[-1] += 16384 - sum(layer)

        class Mod:
            def get_outputs(self):
                return ([None] + [_Out(layer)] * 5
                        + [_Out([main]), _Out([module]), _Out([err])])

        def fit_run(state, *a):
            seen["freq"] = state["cfg"]["moe_layer_freq"]
            seen["layers"] = state["cfg"]["num_hidden_layers"]
            seen["outputs"] = len(state["mod"].get_outputs())
            return {"checks": [], "report": (), "series": {
                "losses": [13.55, main + 0.3 * module]}}

        monkeypatch.setattr(kind.fit_tokens.fit, "run", fit_run)
        state = {"cfg": CFG, "cell": cell, "mod": Mod(), "classes": 16384}
        trace = type("T", (), {"tracing": False})()
        out = kind.run(state, 1.0, trace)
        assert state["cfg"] is CFG and state["mod"] is None
        assert kind.fit_tokens.reference_check is theirs
        return out, {name: ok for name, ok, _ in out["checks"]}

    out, checks = run_with(10.4, 10.5, 5e-5)
    assert all(checks.values()), checks
    assert seen == {"freq": [0, 1, 1, 1, 1, 1], "layers": 6, "outputs": 6}
    assert out["loss_parts"] == pytest.approx([10.4, 10.5])
    assert out["hc_res_sum_err"] == pytest.approx(5e-5)
    assert len(out["expert_counts"]) == 5
    assert set(checks) >= {"experts_routed_over_all", "held_rows_within_bound",
                           "held_rows_near_expected",
                           "first_loss_near_expected",
                           "two_losses_make_the_one",
                           "carry_is_doubly_stochastic"}
    assert not run_with(10.4, 13.0, 5e-5)[1]["two_losses_make_the_one"]
    assert not run_with(10.4, 8.0, 5e-5)[1]["two_losses_make_the_one"]
    assert all(run_with(0.97, 7.9, 5e-5)[1].values())  # the chip's, PR 69
    assert not run_with(10.4, 10.5, 0.5)[1]["carry_is_doubly_stochastic"]
    assert not run_with(10.4, 10.5, 5e-5, 600)[1]["held_rows_within_bound"]
    assert not run_with(float("nan"), 10.5, 5e-5)[1][
        "two_losses_make_the_one"]
    # the first loss the cell expects: 1.3 x (ln 16384 + half the logits'
    # variance 0.717 at a unit-rms norm through a Normal(0.02) head of
    # 3584 inputs)
    expect = cell["expect"]
    assert math.log(16384) + expect["first_loss_excess"] == pytest.approx(
        1.3 * (math.log(16384) + 0.5 * 3584 * 0.02 ** 2), abs=2e-3)
