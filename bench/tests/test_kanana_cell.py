"""What the ``kanana_2_30b_a3b`` configuration brought: its file against
the published keys, its operations count against the hand count, the
benchmark's copy of the reference against the program's, the scope
reduction of ``mla_scopes`` on a scope table, the four readers on
handed-in reductions, and the thin kind that expands the layer list."""
import pytest

import lib
import mla_scopes

CFG = lib.load_json(lib.find("configs", "kanana_2_30b_a3b", ".json"))
CELL = "kanana2_fit_share_8k"
# kakaocorp/kanana-2-30b-a3b-instruct-2601's config.json, the keys that
# say its shape (the model-configs catalog's ``config``)
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_hidden_layers": 48, "num_key_value_heads": 32,
    "q_lora_rank": None, "qk_head_dim": 192, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_interleave": True,
    "rope_scaling": None, "rope_theta": 1000000,
    "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 128256}
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "kv_lora_rank", "head_dim", "qk_head_dim", "qk_nope_head_dim",
          "qk_rope_head_dim", "v_head_dim", "num_experts_per_tok",
          "num_attention_heads", "n_shared_experts",
          "routed_scaling_factor")


def test_configuration_keeps_every_published_width_and_states_its_cut():
    changed = {k for k, v in PUBLISHED.items() if CFG[k] != v}
    assert changed == set(CFG["reduced"]) == set(CFG["reduced_why"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert not changed & set(WIDTHS)
    for key in changed:           # the uncut count stands beside the held
        assert CFG["published"][key] == PUBLISHED[key]
    # the floors: four expert layers after the dense one, 8 experts or
    # more, an eighth of the vocabulary
    assert CFG["num_hidden_layers"] - CFG["first_k_dense_replace"] >= 4
    assert CFG["n_routed_experts"] == 16
    assert CFG["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert CFG["share"] == {"experts_of": 128, "expert_offset": 0,
                            "share_rows_bound": 12288}
    assert "8 chips share each layer" in CFG["deployment"]
    assert CFG["kwargs"] == {"seq_len": 8192, "dtype": "bfloat16"}
    assert CFG["input_shape"][2] == 8192
    assert CFG["num_classes"] == CFG["vocab_size"]
    for topic in ("rope", "router", "shared_experts", "optimizer",
                  "objective", "dtype", "weights", "moe_layer_freq"):
        assert CFG["assumed"][topic]
    manifest = [c for c in lib.load_json(lib.MANIFEST)["configs"]
                if c["name"] == "kanana_2_30b_a3b"][0]
    assert manifest["reduced"] == CFG["reduced"]
    assert manifest["source"] == CFG["source"]


def test_the_symbol_holds_the_parameters_the_cut_counted():
    """687.5 M parameters (ISSUE 33's arithmetic, at the sixth layer it
    takes where the chip reads under 10.5 GB): attention 26.35 M a
    layer, shared experts 9.44 M, router 0.26 M, 16 routed experts of
    4.72 M: 111.5 M an expert layer, five of them; layer 0 64.1 M;
    embedding and head 65.7 M."""
    import numpy as np

    sym = lib.resolve(CFG["factory"])(CFG, **CFG["kwargs"])
    shapes, _, _ = sym.infer_shape(data=(1, 8192), softmax_label=(1, 8192))
    sizes = {n: int(np.prod(s)) for n, s in zip(sym.list_arguments(), shapes)
             if n not in ("data", "softmax_label")}
    assert sizes["layer1_q_proj_weight"] == 2048 * 32 * 192
    assert sizes["layer1_kv_a_proj_weight"] == 2048 * 576
    assert sizes["layer1_attn_latent_gamma"] == 512
    assert sizes["layer1_attn_up_weight"] == 512 * 32 * 256
    assert sizes["layer1_o_proj_weight"] == 4096 * 2048
    assert sizes["layer1_shared_gate_proj_weight"] == 2048 * 1536
    assert sizes["layer1_moe_gate_weight"] == 2048 * 128
    assert sizes["layer1_moe_gate_up_weight"] == 16 * 2048 * 2 * 768
    assert sizes["layer0_gate_proj_weight"] == 2048 * 6144
    assert "layer0_moe_gate_weight" not in sizes
    assert sizes["embed_weight"] == sizes["lm_head_weight"] == 16032 * 2048
    layer1 = sum(v for k, v in sizes.items() if k.startswith("layer1_"))
    layer0 = sum(v for k, v in sizes.items() if k.startswith("layer0_"))
    assert layer1 == pytest.approx(111.5e6, rel=2e-3)
    assert layer0 == pytest.approx(64.1e6, rel=2e-3)
    assert sum(sizes.values()) == pytest.approx(687.5e6, rel=2e-3)


def test_forward_flops_match_the_hand_count():
    """Per sequence of 8192, forward: head 2 x 8192 x 2048 x 16032 =
    0.538 T; a layer's four projections 2 x 8192 x 26.35 M = 0.4317 T;
    its scores and values 2 x 32 x 320 x 8192 x 8193 / 2 = 0.687 T; the
    dense layer 2 x 8192 x 3 x 2048 x 6144 = 0.6185 T; the shared
    experts 0.1546 T; a router 2 x 8192 x 2048 x 128 = 0.0043 T and
    6,144 rows of an expert 2 x 6144 x 3 x 2048 x 768 = 0.058 T: 7.62 T
    at the issue's 5 layers (22.9 T a training step), 8.95 T at the 6
    the configuration holds (26.9 T)."""
    fn = lib.load_module("flops", CFG["flops"])
    t, d = 8192, 2048
    assert fn.expected_share_rows(CFG) == 6144
    assert fn.expert_layers(CFG) == 5
    assert fn.expert_layers(dict(CFG, num_hidden_layers=5)) == 4
    assert fn.mla_kernel_flops(CFG) == 2.0 * 32 * 320 * t * (t + 1) / 2
    assert fn.mla_projection_flops(CFG) == 2.0 * t * (
        d * 6144 + d * 576 + 512 * 8192 + 4096 * d)
    assert fn.shared_expert_flops(CFG) == 2.0 * t * 3 * d * 1536
    assert fn.moe_share_flops(CFG) == 2.0 * (
        t * d * 128 + 6144 * 3 * d * 768)
    assert fn.moe_share_flops(CFG, 12288) - fn.moe_share_flops(CFG) == (
        2.0 * 6144 * 3 * d * 768)
    def hand(layers):
        return (2.0 * t * d * 16032
                + layers * (fn.mla_projection_flops(CFG)
                            + fn.mla_kernel_flops(CFG))
                + 2.0 * t * 3 * d * 6144
                + (layers - 1) * (fn.shared_expert_flops(CFG)
                                  + fn.moe_share_flops(CFG)))

    for layers in (5, 6):
        assert fn.forward_flops_per_sample(
            dict(CFG, num_hidden_layers=layers)) == pytest.approx(
                hand(layers), rel=1e-12)
    assert 3 * hand(5) / 1e12 == pytest.approx(22.9, abs=0.05)  # ISSUE 33
    want = hand(CFG["num_hidden_layers"])
    assert 3 * want / 1e12 == pytest.approx(26.9, abs=0.05)
    # the kernel is 46% of the step's operations, the latent's four
    # projections 29%, the shared experts 9%, the routed ones 3%
    assert 6 * fn.mla_kernel_flops(CFG) / want == pytest.approx(0.46, abs=0.01)
    assert 6 * fn.mla_projection_flops(CFG) / want == pytest.approx(
        0.29, abs=0.01)
    assert 5 * fn.shared_expert_flops(CFG) / want == pytest.approx(
        0.086, abs=0.005)
    assert 5 * fn.moe_share_flops(CFG) / want == pytest.approx(
        0.035, abs=0.005)
    assert fn.TRAIN_MULTIPLIER == 3


def test_the_two_copies_of_the_reference_are_one_text():
    import mxnet_tpu.models.kanana2_reference as theirs

    with open(lib.find("reference", CFG["reference"], ".py")) as ours, \
            open(theirs.__file__) as program:
        assert ours.read() == program.read()


# scope paths as the step compiled for the chip names them
SCOPES = {
    "fusion.1": "jit(step)/fwd_bwd/jvp(attn/layer4_attn)/full/cond/"
                "branch_0_fun/flash_fwd_bf16_q1024_k1024/pallas_call:",
    "fusion.2": "jit(step)/fwd_bwd/transpose(jvp(attn/layer0_attn))/full/"
                "cond/branch_0_fun/flash_dkv_bf16_q1024_k1024/pallas_call:",
    "fusion.3": "jit(step)/fwd_bwd/jvp(attn/layer2_attn)/latent/"
                "dot_general:",
    "fusion.4": "jit(step)/fwd_bwd/transpose(jvp(attn/layer2_attn))/latent/"
                "concatenate:",
    "fusion.5": "jit(step)/fwd_bwd/jvp(attn/layer2_attn)/reshape:",
    "fusion.6": "jit(step)/fwd_bwd/jvp(fc/layer3_shared_gate_proj)/"
                "dot_general:",
    "fusion.7": "jit(step)/fwd_bwd/transpose(jvp(fc/layer3_shared_down_proj))"
                "/dot_general:",
    "fusion.8": "jit(step)/fwd_bwd/jvp(fc/layer3_q_proj)/dot_general:",
    "fusion.9": "jit(step)/fwd_bwd/jvp(moe/layer3_moe)/experts/"
                "gmm_fwd_bf16_m256_k2048_n768/pallas_call:",
}


def test_scope_names_split_the_latent_node_and_find_the_shared_experts():
    def kind(scope):
        m = mla_scopes._NODE.search(scope)
        if m:
            return m.group(1), m.group(2)
        return "shared" if mla_scopes._SHARED.search(scope) else None

    assert {k: kind(v) for k, v in SCOPES.items()} == {
        "fusion.1": ("layer4_attn", "full"),
        "fusion.2": ("layer0_attn", "full"),
        "fusion.3": ("layer2_attn", "latent"),
        "fusion.4": ("layer2_attn", "latent"),
        "fusion.5": ("layer2_attn", None),
        "fusion.6": "shared", "fusion.7": "shared",
        "fusion.8": None, "fusion.9": None}


def test_the_reduction_counts_only_nodes_that_have_a_latent_scope():
    """An ``Attention`` node (MiMo's, OLMoE's) scopes its kernels
    ``full`` too; only a node with ops under ``latent`` is a latent
    one."""
    import reduce_trace

    ops = [("fusion.%d" % i, 1000 * i, 100) for i in range(1, 10)]
    ops.append(("plain", 20000, 400))      # (name, start, duration)
    names = dict(SCOPES, plain="jit(step)/fwd_bwd/jvp(attn/layer9_attn)/"
                               "full/pallas_call:")
    raw = {"host": [(0, reduce_trace.SLICE_BEGIN, 0, 10),
                    (0, reduce_trace.SLICE_END, 30000, 10)],
           "devices": {0: {"ops": ops}}}
    red = mla_scopes.reduce(raw, {0: names})
    assert red is not None
    # layer2 (latent, latent, other) is a latent node: 300 ns; layer4,
    # layer0 and layer9 have no latent op in this table and are left out
    assert red["mla"] == pytest.approx(300e-9)
    assert red["latent"] == pytest.approx(200e-9)
    assert red["full"] == 0
    assert red["shared"] == pytest.approx(200e-9)
    none = mla_scopes.reduce(raw, {0: {"plain": names["plain"]}})
    assert none is None


def _run(**over):
    run = {"cfg": CFG, "cell": {"name": CELL}, "trace_steps": 5,
           "batch": 1, "chips": 1, "flops_multiplier": 3,
           "peak": {"bf16_flops": 197e12},
           "mla_scopes": {"mla": 0.700, "latent": 0.100, "full": 0.590,
                          "shared": 0.070}}
    run.update(over)
    return run


def _read(name, run, trace=True):
    return lib.load_module("layer_metrics", name).compute(
        {"devices": {}} if trace else None, {"telemetry": {}}, run)


READERS = ["mla_device_ms", "mla_latent_device_ms",
           "mla_kernel_roofline_share", "shared_expert_device_ms"]


def test_the_four_readers_read_what_they_say():
    fn = lib.load_module("flops", CFG["flops"])
    run = _run()
    assert _read("mla_device_ms", run) == pytest.approx(140.0)
    assert _read("mla_latent_device_ms", run) == pytest.approx(20.0)
    assert _read("shared_expert_device_ms", run) == pytest.approx(14.0)
    least_ms = 1e3 * 3 * 6 * fn.mla_kernel_flops(CFG) / 197e12
    assert least_ms == pytest.approx(62.8, abs=0.1)
    assert _read("mla_kernel_roofline_share", run) == pytest.approx(
        100 * least_ms / 118.0)
    assert _read("mla_kernel_roofline_share", run) < 100
    # every layer has the node and the operations module offers no
    # ``mla_layers``: all six count
    assert not hasattr(fn, "mla_layers") and CFG["num_hidden_layers"] == 6


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_where_there_is_nothing(name):
    """No slice, no such scopes (the parent's program, another model's),
    a configuration with another operations module: None, never zero,
    never a raise (the benchmark's files are laid over older
    checkouts)."""
    mimo = lib.load_json(lib.find("configs", "mimo_v2_flash", ".json"))
    assert _read(name, _run(mla_scopes=None)) is None
    assert _read(name, _run(mla_scopes={"mla": None, "latent": None,
                                        "full": None, "shared": None})) is None
    assert _read(name, _run(), trace=False) is None
    if name == "mla_kernel_roofline_share":
        assert _read(name, _run(cfg=mimo)) is None
    entry = [m for m in lib.load_json(lib.MANIFEST)["per_layer"]
             if m["name"] == name][0]
    # the latent node's three are Kimi Linear's too since PR 68, the
    # shared experts' every share's that has them
    assert entry["workloads"][0] == CELL
    assert "kimi_linear_fit_share_8k" in entry["workloads"]
    assert entry["moves"] == "train_samples_s"
    assert entry["layer"] == "ops and kernels"


class _Out:
    def __init__(self, values):
        self.values = values

    def asnumpy(self):
        return self.values


def test_the_kind_hands_the_share_kind_the_expanded_layer_list(monkeypatch):
    """``moe_layer_freq`` stays the published integer in the file;
    ``fit_tokens_share`` reads a list, and gets [0, 1, 1, 1, 1, 1]. Its
    checks then hold this cell's counts to five expert layers of 128
    experts, 49,152 rows a layer and the 12,288-row buffer."""
    kind = lib.load_module("traffic", "fit_tokens_share_layers")
    assert kind.setup is kind.share.setup
    cell = lib.load_json(lib.find("cells", CELL, ".json"))
    cell["traffic"] = lib.load_json(lib.find(
        "traffic", cell["traffic"], ".json"))
    assert cell["traffic"]["kind"] == "fit_tokens_share_layers"
    assert CFG["moe_layer_freq"] == 1
    seen = {}

    def run_with(held_rows):
        rest = (49152 - 16 * held_rows) // 112
        layer = [held_rows] * 16 + [rest] * 112
        layer[-1] += 49152 - sum(layer)

        class Mod:
            def get_outputs(self):
                return [None] + [_Out(layer)] * 5

        def fit_run(state, *a):
            seen["freq"] = state["cfg"]["moe_layer_freq"]
            return {"checks": [], "series": {"losses": [10.09]},
                    "report": ()}

        monkeypatch.setattr(kind.share.fit_tokens.fit, "run", fit_run)
        state = {"cfg": CFG, "cell": cell, "mod": Mod(), "classes": 16032}
        trace = type("T", (), {"tracing": False})()
        return {name: ok for name, ok, _ in
                kind.run(state, 1.0, trace)["checks"]}

    assert all(run_with(384).values())
    assert seen["freq"] == [0, 1, 1, 1, 1, 1]
    over = run_with(800)                  # 12,800 rows to the held experts
    assert over["experts_routed_over_all"]
    assert not over["held_rows_within_bound"]
    assert not over["held_rows_near_expected"]
    assert not run_with(200)["held_rows_near_expected"]  # 0.52 of 6,144
