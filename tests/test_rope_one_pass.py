"""``ops/transformer.py::rope``'s one-pass form (PR 67): a whole head of
whole lane rows under ``rotate_half`` is rotated by ``x * [cos | cos] +
turn(x) * [-sin | sin]`` with the inverse rotation as its backward rule;
every other call takes the halves' lines. The forward is EQUAL to the
halves' form's, the backward within one rounding, the rule reads the call's
own arguments alone and ``rope.lowerings{form}`` says which form a call
site took: the cells' symbols are held to it at the cells' own widths.
"""
import importlib
import json
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.ops import kernels, registry
from mxnet_tpu.ops import transformer as tr
from mxnet_tpu.ops.transformer import rotary

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
THETA = 10000.0


def halves_form(x, heads, theta=THETA):
    """``rope`` with the rule switched off: the lines every call ran
    before the one-pass form."""
    with mock.patch.object(rotary, "_takes_one_pass", lambda *args: False):
        return tr.rope(x, heads, theta)


@pytest.fixture
def lowerings():
    """form -> the call sites ``rope.lowerings`` counted under it."""
    telemetry.reset()
    telemetry.enable()

    def by_form(**labels):
        """Over the streams that carry ``labels`` (any heads and width
        where none is given)."""
        counted = {"one_pass": 0, "halves": 0}
        dump = telemetry.REGISTRY.snapshot().get("rope.lowerings", {})
        for stream in dump.get("streams", ()):
            have = stream["labels"]
            if all(str(have.get(k)) == str(v) for k, v in labels.items()):
                counted[have["form"]] += stream["value"]
        return counted
    try:
        yield by_form
    finally:
        telemetry.disable()
        telemetry.reset()


def _ulp(dtype):
    return float(jnp.finfo(dtype).eps)


def _within_one_rounding(got, want, dtype):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    room = _ulp(dtype) * np.maximum(np.abs(want), 1.0)
    assert (np.abs(got - want) <= room).all()


# -- the one pass against the halves ------------------------------------------

@pytest.mark.parametrize("t", [128, 200], ids=["t128", "t200"])
@pytest.mark.parametrize("heads", [1, 4, 16])
@pytest.mark.parametrize("d", [128, 256])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_one_pass_equals_the_halves(dtype, d, heads, t):
    rng = np.random.RandomState(d + heads + t)
    x = jnp.asarray(rng.randn(2, t, heads * d), dtype)
    g = jnp.asarray(rng.randn(2, t, heads * d), dtype)
    got, pull = jax.vjp(lambda x: tr.rope(x, heads, THETA), x)
    want, pull_halves = jax.vjp(lambda x: halves_form(x, heads), x)
    assert got.dtype == dtype and got.shape == x.shape
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    # the backward: the inverse rotation of the cotangent, the same
    # products and sum in float32: within one rounding of the type
    dx, = pull(g)
    dx_halves, = pull_halves(g)
    assert dx.dtype == dtype
    _within_one_rounding(dx, dx_halves, dtype)


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
def test_the_one_pass_matches_the_complex_form(jit):
    rng = np.random.RandomState(5)
    b, t, heads, d = 2, 24, 3, 128
    x = rng.randn(b, t, heads * d).astype(np.float32)
    f = (lambda x: tr.rope(x, heads, THETA))
    got = np.asarray((jax.jit(f) if jit else f)(jnp.asarray(x)))
    x4 = x.reshape(b, t, heads, d).astype(np.float64)
    z = x4[..., : d // 2] + 1j * x4[..., d // 2:]
    inv_freq = THETA ** (-np.arange(0, d, 2) / d)
    z = z * np.exp(1j * np.arange(t)[:, None] * inv_freq[None, :])[
        None, :, None, :]
    want = np.concatenate([z.real, z.imag], axis=-1).reshape(b, t, heads * d)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)


def test_the_backward_is_the_inverse_rotation():
    """Rotating and then pulling the result back returns ``x``: the rule
    is the transpose of an orthogonal map."""
    rng = np.random.RandomState(9)
    x = jnp.asarray(rng.randn(1, 40, 2 * 128), jnp.float32)
    out, pull = jax.vjp(lambda x: tr.rope(x, 2, THETA), x)
    back, = pull(out)
    np.testing.assert_allclose(np.asarray(back), np.asarray(x), rtol=0,
                               atol=4e-6)


# -- the kernels, through the interpreter -----------------------------------

# a whole tile; a partial last tile behind a whole one; one block under
# the tile, two lane rows a head; the cells' sixteen heads
@pytest.mark.parametrize("t,heads,d", [(128, 4, 128), (600, 2, 128),
                                       (40, 1, 256), (136, 16, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_kernel_pair_is_the_plain_form(dtype, t, heads, d):
    """``ops/kernels/rope.py``'s two bodies through the Pallas interpreter
    against ``plain_form``, what every platform but the TPU runs (the
    interpreter's float32 sum may fuse a product in: one rounding)."""
    rng = np.random.RandomState(t + heads)
    x = jnp.asarray(rng.randn(2, t, heads * d), dtype)
    g = jnp.asarray(rng.randn(2, t, heads * d), dtype)
    c, s = rotary._whole_head_tables(t, d, THETA)
    assert kernels.rope_rows(heads, d, t, dtype) == min(t, 512)

    def both(interpret):
        out, pull = jax.vjp(lambda x: kernels.rotate_heads(
            x, c, s, heads, interpret=interpret), x)
        return out, pull(g)[0]

    for got, want in zip(both(True), both(False)):
        assert got.dtype == dtype and got.shape == x.shape
        _within_one_rounding(got, want, dtype)


def test_a_model_reaches_the_kernels_through_the_seam(monkeypatch):
    x = jnp.asarray(np.random.RandomState(3).randn(1, 64, 256), jnp.float32)
    want = tr.rope(x, 2, THETA)
    monkeypatch.setattr(kernels.common, "INTERPRET", True)
    _within_one_rounding(tr.rope(x, 2, THETA), want, jnp.float32)


@pytest.mark.parametrize("why,heads,d,t,dtype,rows", [
    ("the_cells", 16, 128, 4096, jnp.bfloat16, 512),
    ("trinity_minis_query", 32, 128, 8192, jnp.bfloat16, 256),
    ("float32", 16, 128, 4096, jnp.float32, 256),
    ("a_short_sequence_is_one_block", 2, 128, 40, jnp.bfloat16, 40),
    ("half_a_lane_row", 32, 64, 8192, jnp.bfloat16, None),
    ("another_type", 16, 128, 4096, jnp.float16, None),
    ("a_row_of_tokens_too_wide_for_a_block", 1024, 128, 4096, jnp.float32,
     None),
])
def test_the_row_tile_follows_the_bytes_a_block_holds(why, heads, d, t,
                                                      dtype, rows):
    assert kernels.rope_rows(heads, d, t, dtype) == rows


# -- the rule ---------------------------------------------------------------

@pytest.mark.parametrize("why,d,kw,form", [
    ("a_whole_head_of_one_lane_row", 128, {}, "one_pass"),
    ("rotary_dim_spelled_out", 128, {"rotary_dim": 128}, "one_pass"),
    ("two_lane_rows", 256, {}, "one_pass"),
    ("half_a_lane_row", 64, {}, "halves"),
    ("a_lane_row_and_a_half", 192, {}, "halves"),
    ("interleaved_pairs", 128, {"interleave": True}, "halves"),
    ("an_offset", 128, {"rotary_dim": 64, "offset": 64}, "halves"),
    ("part_of_the_head", 128, {"rotary_dim": 64}, "halves"),
])
def test_the_rule_reads_the_calls_own_arguments(lowerings, why, d, kw, form):
    heads = 2
    x = jnp.zeros((1, 16, heads * d), jnp.bfloat16)
    jax.eval_shape(lambda x: tr.rope(x, heads, THETA, **kw), x)
    want = {"one_pass": 0, "halves": 0, form: 1}
    assert lowerings(heads=heads, head_dim=d) == want
    assert telemetry.total("rope.lowerings") == 1


def test_a_program_the_partitioner_splits_takes_the_halves(lowerings):
    """The kernels have no partitioning rule: under the trace of a step
    laid over several devices a whole head takes the halves' lines, as on
    the parent."""
    x = jnp.zeros((1, 16, 256), jnp.bfloat16)
    with kernels.common.partitioned_trace(4):
        jax.eval_shape(lambda x: tr.rope(x, 2, THETA), x)
    assert lowerings() == {"one_pass": 0, "halves": 1}


def test_a_site_is_counted_where_it_is_traced_and_nothing_a_step(lowerings):
    f = jax.jit(lambda x: tr.rope(x, 2, THETA))
    x = jnp.ones((1, 16, 256), jnp.float32)
    for _ in range(3):
        f(x).block_until_ready()
    assert lowerings(heads=2, head_dim=128) == {"one_pass": 1, "halves": 0}


# -- through the ops ----------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_gradient_through_rope_and_attention_is_the_halves(dtype):
    """``RoPE`` -> ``Attention`` as a layer has them: the loss and the
    gradients of the two rotated operands under the one-pass form against
    the halves' form (the same attention behind both)."""
    rng = np.random.RandomState(11)
    b, t, heads, d = 1, 32, 2, 128
    q, k, v = (jnp.asarray(rng.randn(b, t, heads * d) * 0.3, dtype)
               for _ in range(3))
    rope_op = registry.get("_contrib_RoPE").fcompute
    attn_op = registry.get("_contrib_Attention").fcompute
    attrs = {"num_heads": heads, "theta": THETA}

    def loss(rotate):
        def f(q, k):
            out, = attn_op({"num_heads": heads, "causal": True},
                           [rotate(q), rotate(k), v], True)
            return jnp.sum(jnp.square(out.astype(jnp.float32)))
        return jax.value_and_grad(f, argnums=(0, 1))(q, k)

    got, got_grads = loss(lambda x: rope_op(attrs, [x], True)[0])
    want, want_grads = loss(lambda x: halves_form(x, heads))
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    for g, w in zip(got_grads, want_grads):
        assert g.dtype == dtype
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        scale = np.abs(w).max()
        assert np.abs(g - w).max() <= 2 * _ulp(dtype) * scale


# -- the cells' symbols -----------------------------------------------------

# configuration file, model module, form its RoPE nodes take, nodes
CELLS = [
    ("ouro_2_6b", "ouro", "one_pass", 48),
    ("olmoe_1b_7b", "olmoe", "one_pass", 6),
    ("trinity_mini", "afmoe", "one_pass", 8),
    ("falcon_h1_34b", "falcon_h1", "one_pass", 8),
    ("lfm2_24b_a2b", "lfm2", "halves", 4),
    ("mimo_v2_flash", "mimo_v2", "halves", 14),
]


@pytest.mark.parametrize("config,model,form,count", CELLS,
                         ids=[c[0] for c in CELLS])
def test_a_cells_rope_nodes_take_one_form(lowerings, config, model, form,
                                          count):
    """Every ``RoPE`` node of the symbol a cell trains, traced at the
    cell's own shape (shapes only: nothing is computed), counts once, all
    under one form."""
    with open(os.path.join(REPO, "bench", "configs", config + ".json")) as f:
        cfg = json.load(f)
    builder = importlib.import_module("mxnet_tpu.models." + model)
    sym = builder.from_config(cfg, **cfg["kwargs"])
    t = cfg["kwargs"]["seq_len"]
    internals = sym.get_internals()
    _, shapes, _ = internals.infer_shape(data=(1, t), softmax_label=(1, t))
    shape_of = dict(zip(internals.list_outputs(), shapes))
    rope_op = registry.get("_contrib_RoPE")
    nodes = [n for n in json.loads(sym.tojson())["nodes"]
             if n["op"] == rope_op.name]
    assert len(nodes) == count
    for node in nodes:
        x = jax.ShapeDtypeStruct(shape_of[node["name"] + "_output"],
                                 jnp.bfloat16)
        jax.eval_shape(
            lambda x, attrs=node["attr"]: rope_op.fcompute(attrs, [x], True),
            x)
    want = {"one_pass": 0, "halves": 0, form: count}
    assert lowerings() == want


@pytest.mark.parametrize("model,kw", [
    ("olmoe", dict(vocab_size=64, hidden_size=256, num_layers=2, num_heads=2,
                   num_experts=4, experts_per_token=2, expert_width=32)),
    ("ouro", dict(vocab_size=64, hidden_size=256, intermediate_size=64,
                  num_layers=1, num_heads=2, num_kv_heads=2, head_dim=128,
                  total_ut_steps=2)),
], ids=["olmoe", "ouro"])
def test_a_toy_symbol_runs_the_one_pass_once_a_node(lowerings, model, kw):
    """Heads of 128 at toy depth through the executor: two ``RoPE`` nodes a
    layer visit, each counted once, and a finite loss."""
    t = 16
    builder = importlib.import_module("mxnet_tpu.models." + model)
    sym = builder.get_symbol(seq_len=t, **kw)
    nodes = [n for n in json.loads(sym.tojson())["nodes"]
             if n["op"] == "_contrib_RoPE"]
    ex = sym.simple_bind(mx.cpu(0), data=(1, t), softmax_label=(1, t))
    rng = np.random.RandomState(2)
    for name, arr in ex.arg_dict.items():
        if name not in ("data", "softmax_label"):
            arr[:] = 0.05 * rng.randn(*arr.shape)
    ex.arg_dict["data"][:] = rng.randint(0, 64, (1, t))
    ex.arg_dict["softmax_label"][:] = rng.randint(0, 64, (1, t))
    ex.forward(is_train=False)
    assert np.isfinite(ex.outputs[0].asnumpy()).all()
    assert len(nodes) >= 2
    assert lowerings(heads=2, head_dim=128) == {
        "one_pass": len(nodes), "halves": 0}
