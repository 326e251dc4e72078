"""A share's row moves (``parallel/moe.py::_topk_moe_share``) against the
formulation the tree had before them, kept here as the plain reference.

The reference (``_take_share``) moves rows by ``jnp.take`` and
``jax.ops.segment_sum`` under autodiff (two row scatter-adds and a
scalar one on the way back) and applies the routing weight to the
experts' rounded output in float32. The share gathers over its buffer,
sums a token's rows as a sorted segment sum
(``ops.kernels.sorted_segment_sum``: ``jax.ops.segment_sum`` here on the
CPU, the grouped matmul's wgrad kernel over an exact 0 / 1 table where the
step is lowered for the TPU, through the Pallas interpreter under the
kernel layer's one seam) and applies the weight, in float32, where the
experts' activation is rounded. In float32 the two differ by the order of
summation; in bf16 by where one rounding stands, so each is held to the
float32 reference and the share may not be further from it than the
tree's formulation was.

The shapes are the four share cells' ratios at toy size (tokens, top_k,
held / of, bound, d, activation): Kanana 8,192 / 6 / 16 of 128 / 12,288
/ 2,048, LFM2 8,192 / 4 / 8 of 64 / 8,192 / 2,048, Nemotron as Kanana at
2,688 (21 lane rows: three n tiles) with ``relu2``, MiMo 4,096 / 8 / 8 of
256 / 2,048 / 4,096: a buffer of twice the rows expected.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import telemetry
from mxnet_tpu.ops import kernels
from mxnet_tpu.parallel import moe

CELLS = {
    "kanana": (512, 6, 2, 16, 768, 128, "swiglu"),
    "lfm2": (512, 4, 2, 16, 512, 128, "swiglu"),
    "nemotron": (512, 6, 2, 16, 768, 384, "relu2"),
    "mimo": (512, 8, 1, 32, 256, 256, "swiglu"),
}
HIDDEN = 64


def _take_share(params, x, weights, experts, offset, bound, activation):
    """``_topk_moe_share`` as the tree had it before PR 47."""
    tokens = x.shape[0]
    top_k = experts.shape[1]
    num_experts = params["gate_w"].shape[1]
    held = params["w_down"].shape[0]
    flat_expert = experts.reshape(-1)
    counts = jnp.sum(jax.nn.one_hot(
        flat_expert, num_experts, dtype=jnp.int32), axis=0)
    local = flat_expert - offset
    here = (local >= 0) & (local < held)
    running = jnp.cumsum(here.astype(jnp.int32))
    pairs = jnp.searchsorted(
        running, jnp.arange(1, bound + 1, dtype=jnp.int32),
        side="left", method="compare_all")
    group = jnp.take(local, pairs, mode="fill", fill_value=held)
    order = jnp.argsort(group, stable=True)
    pairs, group = pairs[order], group[order]
    used = group < held
    sizes = jnp.sum(jax.nn.one_hot(group, held, dtype=jnp.int32), axis=0)
    token = jnp.where(used, pairs // top_k, 0)
    rows = jnp.where(used[:, None], jnp.take(x, token, axis=0), 0)
    dtype = rows.dtype
    hidden = params["w_down"].shape[1]
    up = jax.lax.ragged_dot(rows, params["w_gate_up"].astype(dtype), sizes)
    if activation == "relu2":
        act = jnp.square(jax.nn.relu(up.astype(jnp.float32))).astype(dtype)
    else:
        act = jax.nn.silu(up[:, :hidden]) * up[:, hidden:]
    out_rows = jax.lax.ragged_dot(act, params["w_down"].astype(dtype), sizes)
    weight = jnp.where(used, jnp.take(
        weights.reshape(-1), pairs, mode="fill", fill_value=0), 0)
    weighted = jnp.where(
        used[:, None], out_rows.astype(jnp.float32) * weight[:, None], 0)
    y = jax.ops.segment_sum(weighted, token, num_segments=tokens)
    return y.astype(x.dtype), counts


def _inputs(seed, tokens, top_k, held, of, d, activation, dtype,
            experts=None):
    rng = np.random.RandomState(seed)
    two = 2 if activation == "swiglu" else 1
    params = {
        "gate_w": jnp.zeros((d, of), jnp.float32),   # its shape alone
        "w_gate_up": jnp.asarray(
            rng.randn(held, d, two * HIDDEN) / np.sqrt(d), dtype),
        "w_down": jnp.asarray(
            rng.randn(held, HIDDEN, d) / np.sqrt(HIDDEN), dtype)}
    x = jnp.asarray(rng.randn(tokens, d), dtype)
    weights = jnp.asarray(rng.rand(tokens, top_k) + 0.1, jnp.float32)
    if experts is None:
        experts = np.argsort(rng.rand(tokens, of), axis=1)[:, :top_k]
    cot = jnp.asarray(rng.randn(tokens, d), jnp.float32)
    return params, x, weights, jnp.asarray(experts, jnp.int32), cot


def _run(share, params, x, weights, experts, cot, offset, bound, activation):
    """(y, counts, dx, d_weights, d w_gate_up, d w_down)."""
    def loss(x, weights, params):
        y, counts = share(params, x, weights, experts, offset, bound,
                          activation)
        return jnp.sum(y.astype(jnp.float32) * cot), (y, counts)

    (_, (y, counts)), (dx, dw, dp) = jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True)(x, weights, params)
    return y, counts, dx, dw, dp["w_gate_up"], dp["w_down"]


NAMES = ("y", "counts", "dx", "d_weights", "d w_gate_up", "d w_down")


def _off(got, want, norm=np.max):
    """Largest (or, ``norm=_rms``, root-mean-square) difference in units
    of the reference's largest value."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(norm(np.abs(got - want)) / max(np.abs(want).max(), 1e-30))


def _rms(a):
    return np.sqrt(np.mean(np.square(a)))


def _hold(args, offset, bound, activation, dtype):
    """The share against the tree's formulation on ``args`` (float32
    inputs): in float32 to 1e-5 of each tensor's scale; in bf16 each
    against the float32 reference: the share's root-mean-square
    distance is no more than the tree's formulation's was (measured
    0.75-0.98 of it: the activation is float32 up to its one rounding),
    its largest 2e-2 of the tensor's scale at most."""
    params, x, weights, experts, cot = args
    want = _run(_take_share, params, x, weights, experts, cot, offset,
                bound, activation)
    if dtype == jnp.float32:
        got = _run(moe._topk_moe_share, params, x, weights, experts, cot,
                   offset, bound, activation)
        np.testing.assert_array_equal(np.asarray(got[1]),
                                      np.asarray(want[1]))
        for name, g, w in zip(NAMES, got, want):
            assert g.shape == w.shape and _off(g, w) < 1e-5, (name,
                                                              _off(g, w))
        return got
    low = jax.tree_util.tree_map(lambda a: a.astype(dtype), (params, x))
    got = _run(moe._topk_moe_share, low[0], low[1], weights, experts, cot,
               offset, bound, activation)
    tree = _run(_take_share, low[0], low[1], weights, experts, cot, offset,
                bound, activation)
    assert got[0].dtype == dtype and got[2].dtype == dtype
    assert got[3].dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    for name, g, t, w in zip(NAMES, got, tree, want):
        if name != "counts":
            ours, theirs = _off(g, w, _rms), _off(t, w, _rms)
            assert ours < 1.05 * theirs and _off(g, w) < 2e-2, (
                name, ours, theirs, _off(g, w))
    return got


@pytest.fixture(params=["segment_sum", "interpreted_kernel"])
def path(request, monkeypatch):
    """The branch of every other platform, and the kernel's own through
    the Pallas interpreter (the kernel layer's one seam)."""
    if request.param == "interpreted_kernel":
        monkeypatch.setattr(kernels.common, "INTERPRET", True)
    return request.param


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_share_matches_the_trees_formulation(cell, dtype, path):
    tokens, top_k, held, of, bound, d, activation = CELLS[cell]
    assert kernels.gmm_runs_kernel(bound, dtype)
    args = _inputs(3, tokens, top_k, held, of, d, activation, jnp.float32)
    _hold(args, 1, bound, activation, dtype)


def _edge(name):
    """(experts [tokens, top_k], bound or a function of the held rows)
    for the cases a random routing does not hold."""
    top_k, held, of = 4, 4, 16
    rng = np.random.RandomState(11)

    def draw(tokens):
        return np.argsort(rng.rand(tokens, of), axis=1)[:, :top_k]

    if name == "bound_smaller_than_the_held_rows":
        return draw(512), lambda rows: rows // 2
    if name == "bound_with_free_rows":
        return draw(512), lambda rows: 512 * top_k
    if name == "a_token_with_every_pair_held_and_one_with_none":
        experts = draw(512)
        experts[0] = [1, 2, 3, 4]           # offset 1: all four held
        experts[1] = [0, 5, 6, 7]           # none
        experts[511] = [4, 3, 2, 1]
        return experts, lambda rows: rows + 7
    if name == "an_empty_token_tile":
        experts = draw(768)
        experts[256:512] = np.arange(8, 12)  # tile 1 holds no row
        return experts, lambda rows: rows + 40
    if name == "tokens_not_a_multiple_of_the_tile":
        return draw(300), lambda rows: rows + 1
    raise KeyError(name)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", [
    "bound_smaller_than_the_held_rows", "bound_with_free_rows",
    "a_token_with_every_pair_held_and_one_with_none",
    "an_empty_token_tile", "tokens_not_a_multiple_of_the_tile"])
def test_the_edges_of_the_buffer(case, dtype, path):
    experts, bound_of = _edge(case)
    tokens, top_k = experts.shape
    rows = int(np.isin(experts, np.arange(1, 5)).sum())
    bound = bound_of(rows)
    assert kernels.gmm_runs_kernel(bound, dtype)
    args = _inputs(5, tokens, top_k, 4, 16, 128, "swiglu", jnp.float32,
                   experts=experts)
    y, counts, dx, dw = _hold(args, 1, bound, "swiglu", dtype)[:4]
    assert int(np.asarray(counts)[1:5].sum()) == rows
    held_pairs = np.isin(experts, np.arange(1, 5))
    inside = np.cumsum(held_pairs.reshape(-1)).reshape(
        tokens, top_k) <= bound
    computed = held_pairs & inside
    # a pair that is not held, or lies past the bound, has no row: its
    # weight has no gradient; a token none of whose pairs has one gets
    # nothing and gives nothing
    assert not np.asarray(dw)[~computed].any()
    assert np.asarray(dw)[computed].all()
    nothing = ~computed.any(axis=1)
    assert nothing.any() or case == "bound_with_free_rows"
    assert not np.asarray(y, np.float32)[nothing].any()
    assert not np.asarray(dx, np.float32)[nothing].any()


def test_sorted_segment_sum_keeps_a_row_of_no_segment_out(path):
    """Rows past the segments (a buffer's free rows: whatever the grouped
    products left there) contribute nothing, be they NaN; an empty
    segment is zero."""
    rng = np.random.RandomState(2)
    rows = rng.randn(384, 128).astype(np.float32)
    segment = np.sort(rng.randint(0, 300, 384)).astype(np.int32)
    segment[segment == 17] = 18
    segment[-50:] = 300
    rows[-50:] = np.nan
    got = kernels.sorted_segment_sum(
        jnp.asarray(rows), jnp.asarray(segment), 300,
        interpret=kernels.common.INTERPRET)
    want = np.zeros((300, 128), np.float64)
    np.add.at(want, segment[:-50], rows[:-50].astype(np.float64))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
    assert not np.asarray(got)[17].any()


def test_the_call_sites_count_themselves():
    """``moe.share_lowerings`` says how the share's rows are summed and
    ``moe.gmm_lowerings`` counts the segment product's two calls a layer
    (``combine`` forward, ``dispatch`` backward) as ``wgrad`` at their
    tiles, beside the experts' own."""
    tokens, top_k, held, of, bound, d, activation = CELLS["lfm2"]
    params, x, _, _, cot = _inputs(1, tokens, top_k, held, of, d,
                                   activation, jnp.bfloat16)
    params["gate_w"] = jnp.asarray(
        np.random.RandomState(0).randn(d, of), jnp.bfloat16)

    def loss(params, x):
        y, _ = moe.topk_moe(params, x, top_k, scoring="sigmoid",
                            share_rows_bound=bound)
        return jnp.sum(y.astype(jnp.float32) * cot)

    telemetry.reset()
    telemetry.enable()
    try:
        jax.jit(jax.grad(loss, argnums=(0, 1))).lower(params, x)
        share = telemetry.REGISTRY.get("moe.share_lowerings")
        assert share.value(held=held, of=of, bound=bound,
                           sum="segment_product") == 1
        tiles = kernels.gmm_tiles(bound, kernels.gmm.SEGMENT_TILE, d,
                                  tokens // kernels.gmm.SEGMENT_TILE,
                                  jnp.bfloat16, wgrad=True)
        assert tiles == (128, 256, 128)
        gmm = telemetry.REGISTRY.get("moe.gmm_lowerings")
        assert gmm.value(mode="wgrad", operands="bf16", tm=tiles[0],
                         tk=tiles[1], tn=tiles[2]) == 2
        # a buffer under one row tile: the kernels take none of it
        moe.topk_moe(params, x[:16], top_k, scoring="sigmoid",
                     share_rows_bound=32)
        assert share.value(held=held, of=of, bound=32,
                           sum="segment_sum") == 1
    finally:
        telemetry.disable()
        telemetry.reset()


@pytest.mark.parametrize("seam", [True, False],
                         ids=["kernels_branch", "cpu_branch"])
def test_a_share_models_step_scatters_nothing_in_its_expert_layers(
        monkeypatch, seam):
    """The LFM2 share symbol at T 128 (two expert layers, a buffer of 768
    rows each), forward and backward as a step traces it. With the
    kernels' branch taken (the seam) the program has no scatter at all
    (the embedding's gradient is a sorted segment sum since PR 50, and the
    loss's pick over the vocabulary, the last one, went with PR 65's
    ``pick_log_softmax``), where the tree's formulation had two a layer
    of the tokens' shape (and a scalar one). On the CPU's
    own branch the segment sums are the scatters: two a layer of that
    shape, one of [tokens, top_k] and the embedding's over the table, each
    over sorted indices."""
    from mxnet_tpu.executor import _GraphProgram
    from mxnet_tpu.models import lfm2

    t, batch, hidden, vocab = 128, 2, 128, 320
    cfg = dict(
        model_type="lfm2_moe", hidden_size=hidden, num_hidden_layers=3,
        layer_types=["conv", "full_attention", "conv"], num_dense_layers=1,
        conv_L_cache=3, conv_bias=False, num_attention_heads=4,
        num_key_value_heads=2,
        rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
        intermediate_size=64, moe_intermediate_size=32, num_experts=4,
        num_experts_per_tok=3, norm_topk_prob=True, use_expert_bias=True,
        routed_scaling_factor=1, norm_eps=1e-5, vocab_size=vocab,
        max_position_embeddings=t,
        share=dict(experts_of=16, expert_offset=8,
                   share_rows_bound=batch * t * 3))
    sym = lfm2.from_config(cfg, seq_len=t)
    shapes, _, _ = sym.infer_shape(data=(batch, t), softmax_label=(batch, t))
    args = {name: jax.ShapeDtypeStruct(shape, jnp.float32)
            for name, shape in zip(sym.list_arguments(), shapes)}
    feeds = {n: args.pop(n) for n in ("data", "softmax_label")}
    program = _GraphProgram(sym)
    monkeypatch.setattr(kernels.common, "INTERPRET", seam)

    def loss(params, feeds):
        outs, _ = program(dict(params, **feeds), {}, jax.random.PRNGKey(0),
                          True)
        return sum(jnp.sum(o) for o in outs)

    text = jax.jit(jax.grad(loss)).lower(args, feeds).compiler_ir(
        dialect="hlo").as_hlo_text()
    scatters = re.findall(r"= (\S+?)\{[^ ]*\} scatter\((.*)", text)
    others = []
    # a token's rows, the weights' cotangents placed the same way, and the
    # table's rows
    summed = ["f32[%d,%d]" % (batch * t, hidden), "f32[%d,3]" % (batch * t),
              "f32[%d,%d]" % (vocab, hidden)]
    assert sorted(s for s, _ in scatters if s not in summed) == sorted(others)
    sums = [(s, rest) for s, rest in scatters if s in summed]
    if seam:
        assert not sums
    else:
        # (a function behind a ``jax.jit`` is lowered once however many
        # layers call it: what is counted is shapes, not calls)
        assert {s for s, _ in sums} == set(summed)
        assert all("indices_are_sorted=true" in rest for _, rest in sums)


# -- a weight handed to the kernels in the order the chip holds it -----------

def _plain_experts(params, rows, sizes, activation, scale):
    """``_experts`` in float32 over ``ragged_dot``, the weights as they are
    declared."""
    dot = lambda lhs, w: jax.lax.ragged_dot(
        lhs, w, sizes, precision=jax.lax.Precision.HIGHEST)
    up = dot(rows, params["w_gate_up"])
    if activation == "relu2":
        act = jnp.square(jax.nn.relu(up))
    else:
        half = up.shape[1] // 2
        act = jax.nn.silu(up[:, :half]) * up[:, half:]
    if scale is not None:
        act = act * scale[:, None]
    return dot(act, params["w_down"])


# (d_model, columns of the up product, which weights the chip holds
# transposed): Nemotron's kind (the up columns a lane row and a half), the
# other cells' (whole lane rows both), and the same rule met by ``w_down``
EXPERT_WIDTHS = {
    "up_held_transposed": (256, 192, ["w_gate_up"]),
    "declared": (256, 128, []),
    "down_held_transposed": (192, 256, ["w_down"]),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("scaled", [False, True], ids=["full", "share"])
@pytest.mark.parametrize("activation", ["relu2", "swiglu"])
@pytest.mark.parametrize("widths", sorted(EXPERT_WIDTHS))
def test_the_experts_take_a_weight_in_the_order_the_chip_holds_it(
        widths, activation, scaled, dtype, path):
    """``_experts`` on the full path and on a share's (``scale``) against
    the plain float32 products over the declared weights: the value and
    the gradient of every input, to the kernels' own tolerances, whether
    ``held_transposed`` fires for a weight (it then reaches
    ``grouped_matmul`` as the swap of its last two axes) or for none; the
    gradients come back in the declared shapes."""
    d, columns, held = EXPERT_WIDTHS[widths]
    hidden = columns if activation == "relu2" else columns // 2
    sizes = [3, 0, 210, 7, 160, 4]
    rng = np.random.RandomState(7)
    params = {
        "w_gate_up": jnp.asarray(
            rng.randn(len(sizes), d, columns) / np.sqrt(d), jnp.float32),
        "w_down": jnp.asarray(
            rng.randn(len(sizes), hidden, d) / np.sqrt(hidden), jnp.float32)}
    assert [name for name in sorted(params)
            if kernels.held_transposed(params[name].shape)] == held
    rows = jnp.asarray(rng.randn(sum(sizes), d), jnp.float32)
    scale = (jnp.asarray(rng.rand(sum(sizes)) + 0.1, jnp.float32)
             if scaled else None)
    cot = jnp.asarray(rng.randn(sum(sizes), d), jnp.float32)
    counts = jnp.asarray(sizes, jnp.int32)

    def run(fn, params, rows):
        def loss(params, rows, scale):
            out = fn(params, rows, counts, activation, scale)
            return jnp.sum(out.astype(jnp.float32) * cot), out
        argnums = (0, 1, 2) if scaled else (0, 1)
        (_, out), grads = jax.value_and_grad(
            loss, argnums=argnums, has_aux=True)(params, rows, scale)
        return [out, grads[1], grads[0]["w_gate_up"], grads[0]["w_down"]
                ] + list(grads[2:])

    want = run(_plain_experts, params, rows)
    low = jax.tree_util.tree_map(lambda a: a.astype(dtype), (params, rows))
    got = run(moe._experts, *low)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    names = ("out", "d rows", "d w_gate_up", "d w_down", "d scale")
    for name, g, w in zip(names, got, want):
        assert g.shape == w.shape, name
        assert g.dtype == (jnp.float32 if name == "d scale" else dtype)
        assert _off(g, w) < tol, (name, _off(g, w))
    # an expert with no rows: both its weights' gradients exactly zero
    assert not np.asarray(got[2][1], np.float32).any()
    assert not np.asarray(got[3][1], np.float32).any()


def _benchmark_expert_layers():
    """(configuration, w_gate_up shape, w_down shape, rows of the experts'
    products) of every benchmark configuration that has expert layers, at
    its published widths, from the symbol its factory builds (shapes
    only)."""
    import glob
    import importlib
    import json
    import os

    root = os.path.join(os.path.dirname(__file__), "..", "bench", "configs")
    out = []
    for path in sorted(glob.glob(os.path.join(root, "*.json"))):
        with open(path) as f:
            cfg = json.load(f)
        module, _, factory = cfg["factory"].partition(":")
        if factory != "from_config":
            continue
        sym = getattr(importlib.import_module(module), factory)(
            cfg, **cfg["kwargs"])
        batch, _, t = cfg["input_shape"]
        shapes, _, _ = sym.infer_shape(data=(batch, t),
                                       softmax_label=(batch, t))
        by_name = dict(zip(sym.list_arguments(), shapes))
        ups = sorted(n for n in by_name if n.endswith("moe_gate_up_weight"))
        if not ups:
            continue
        share = cfg.get("share") or {}
        rows = share.get("share_rows_bound") or (
            batch * t * cfg["num_experts_per_tok"])
        out.append((os.path.basename(path)[:-len(".json")], by_name[ups[0]],
                    by_name[ups[0].replace("gate_up", "down")], rows))
    return out


def test_only_nemotrons_up_product_is_handed_over_held_transposed():
    """``moe.gmm_lowerings{rhs}`` over one expert layer of every benchmark
    configuration at its published widths, forward and backward, by
    ``jax.eval_shape`` (shapes only, nothing computed): Nemotron-3-Nano's
    un-gated up product (2,688 -> 1,856 = 14.5 lane rows) counts its three
    modes as ``held_transposed`` and its down product as ``declared``;
    in every other configuration both widths of both weights are whole
    lane rows and all six are ``declared``."""
    layers = _benchmark_expert_layers()
    # the eleventh since PR 75: Keye-VL-2.0's experts of 768, six lane rows
    assert len(layers) == 11, [name for name, *_ in layers]
    spec = jax.ShapeDtypeStruct
    for name, up, down, rows in layers:
        activation = "relu2" if up[2] == down[1] else "swiglu"
        params = {"w_gate_up": spec(up, jnp.bfloat16),
                  "w_down": spec(down, jnp.bfloat16)}

        def grads(params, x, counts, scale):
            return jax.grad(lambda p, x: jnp.sum(moe._experts(
                p, x, counts, activation, scale).astype(jnp.float32)),
                argnums=(0, 1))(params, x)

        telemetry.reset()
        telemetry.enable()
        try:
            got = jax.eval_shape(
                grads, params, spec((rows, up[1]), jnp.bfloat16),
                spec((up[0],), jnp.int32), spec((rows,), jnp.float32))
            c = telemetry.REGISTRY.get("moe.gmm_lowerings")
            by_rhs = {"declared": 0, "held_transposed": 0}
            for key in c.label_sets():
                by_rhs[dict(key)["rhs"]] += c.value(**dict(key))
        finally:
            telemetry.disable()
            telemetry.reset()
        # the gradients keep the declared shapes
        assert got[0]["w_gate_up"].shape == up, name
        assert got[0]["w_down"].shape == down, name
        held = 3 if name == "nemotron_3_nano_30b_a3b" else 0
        assert by_rhs == {"declared": 6 - held,
                          "held_transposed": held}, (name, by_rhs)
        assert kernels.held_transposed(up) == bool(held), name
        assert not kernels.held_transposed(down), name
