"""``pick_log_softmax`` (``ops/indexing.py``): a row's log-probability of
its label with a backward rule of its own, against autodiff of
``log_softmax`` + ``pick``, which it took the place of in every language
model's head (``models/lm_blocks.py::head_and_loss``, ``models/ouro.py``).

The old graph is still buildable: ``the_old_head`` makes ``mx.sym.
pick_log_softmax`` build the two nodes the head had (``lm_head_logp``,
``lm_head_pick``), so every model's symbol of before stands beside the one
it has now.
"""
import contextlib
import importlib
import inspect
import json
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax._src.ad_checkpoint import saved_residuals

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.executor import _GraphProgram
from mxnet_tpu.models import lfm2, lm_blocks, olmoe
from mxnet_tpu.ops.indexing import picked_log_prob

from test_ouro import _fused_step  # two sequences a batch

ROWS, VOCAB = 24, 131


def _two_ops(logits, labels):
    """What the head was: autodiff of ``log_softmax`` and a gather."""
    return jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                               labels[..., None], axis=-1)[..., 0]


def _labels(kind, rows=ROWS, vocab=VOCAB):
    if kind == "first":
        return jnp.zeros((rows,), jnp.int32)
    if kind == "last":
        return jnp.full((rows,), vocab - 1, jnp.int32)
    if kind == "ends":
        return jnp.asarray([0, vocab - 1] * (rows // 2), jnp.int32)
    return jnp.asarray(np.random.RandomState(5).randint(0, vocab, rows),
                       jnp.int32)


def _logits(seed=0, rows=ROWS, vocab=VOCAB):
    return 4.0 * jax.random.normal(jax.random.PRNGKey(seed), (rows, vocab),
                                   jnp.float32)


@contextlib.contextmanager
def the_old_head():
    """Symbols built inside have the head of before: the nodes
    ``<prefix>lm_head_logp`` and ``<prefix>lm_head_pick``."""
    def two_nodes(logits, label, name):
        assert name.endswith("lm_head_pick")
        logp = mx.sym.log_softmax(logits, name=name[:-len("pick")] + "logp")
        return mx.sym.pick(logp, label, axis=1, name=name)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(mx.symbol, "pick_log_softmax", two_nodes)
        yield


# -- the rule against autodiff of the two ops ---------------------------------

@pytest.mark.parametrize("labels", ["random", "first", "last", "ends"])
@pytest.mark.parametrize("logits_are", ["float32", "cast_of_bfloat16"])
def test_value_and_gradient_are_those_of_the_two_ops(logits_are, labels):
    """Value and gradient under a cotangent that differs by row. The
    head's case is float32 logits that are a cast of a bf16 product: the
    gradient with respect to THAT is rounded to bf16 once on both sides."""
    lab = _labels(labels)
    weight = jnp.linspace(-1.5, 2.0, ROWS, dtype=jnp.float32)
    x = _logits()
    if logits_are == "cast_of_bfloat16":
        x = x.astype(jnp.bfloat16)

    def through(fn):
        return lambda x: jnp.sum(fn(x.astype(jnp.float32), lab) * weight)

    got = picked_log_prob(x.astype(jnp.float32), lab)
    want = _two_ops(x.astype(jnp.float32), lab)
    assert got.dtype == jnp.float32 and got.shape == (ROWS,)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=2e-6)
    g_got = jax.grad(through(picked_log_prob))(x)
    g_want = jax.grad(through(_two_ops))(x)
    assert g_got.dtype == x.dtype and g_got.shape == x.shape
    # one bf16 ulp where the two float32 cotangents straddle a rounding
    tol = 1e-6 if x.dtype == jnp.float32 else 2.0 ** -7
    np.testing.assert_allclose(np.asarray(g_got, np.float32),
                               np.asarray(g_want, np.float32),
                               rtol=tol, atol=tol * 1e-2)
    # softmax less one-hot: a row's gradient sums to zero, and only the
    # label's column has the cotangent's sign
    g32 = np.asarray(jax.grad(through(picked_log_prob))(
        x.astype(jnp.float32)))
    np.testing.assert_allclose(g32.sum(axis=1), 0, atol=1e-5)
    at_label = g32[np.arange(ROWS), np.asarray(lab)]
    assert (np.sign(at_label) == np.sign(np.asarray(weight))).all()


@pytest.mark.parametrize("shape", [(ROWS, VOCAB), (3, 8, 37)],
                         ids=["rows_by_vocab", "batch_time_vocab"])
def test_the_symbol_op_reads_labels_as_pick_does(shape):
    """``mx.nd.pick_log_softmax``: float labels, a negative one counting
    from the end, any leading shape; shapes inferred from the data's."""
    rng = np.random.RandomState(2)
    data = rng.randn(*shape).astype(np.float32) * 3
    index = rng.randint(-shape[-1], shape[-1], shape[:-1])
    got = mx.nd.pick_log_softmax(mx.nd.array(data), mx.nd.array(index))
    want = mx.nd.pick(mx.nd.log_softmax(mx.nd.array(data), axis=-1),
                      mx.nd.array(index), axis=-1)
    assert got.shape == shape[:-1]
    np.testing.assert_allclose(got.asnumpy(), want.asnumpy(), rtol=1e-6,
                               atol=2e-6)
    sym = mx.sym.pick_log_softmax(mx.sym.Variable("data"),
                                  mx.sym.Variable("index"), name="picked")
    args, outs, _ = sym.infer_shape(data=shape)
    assert args == [shape, shape[:-1]] and outs == [shape[:-1]]


# -- what lives from the forward to the backward ------------------------------

def _table_sized(residuals, shape, but=()):
    return [(aval, what) for aval, what in residuals
            if aval.shape == shape and aval.dtype == jnp.float32
            and not any(b in what for b in but)]


@pytest.mark.parametrize("form", ["the_rule", "the_two_ops"])
def test_kept_for_the_backward(form):
    """The rule keeps its own input, the labels and one float32 a row;
    autodiff of the two ops keeps a float32 [rows, vocab] table that is
    not the input (the premise: were that gone, the rule would be for
    nothing)."""
    x, lab = _logits(), _labels("random")
    fn = picked_log_prob if form == "the_rule" else _two_ops
    kept = saved_residuals(lambda x: jnp.sum(fn(x, lab)), x)
    tables = _table_sized(kept, x.shape, but=("from the argument",))
    if form == "the_rule":
        assert tables == []
        assert sorted(str(aval) for aval, _ in kept) == sorted(
            ["float32[%d,%d]" % x.shape, "int32[%d]" % ROWS,
             "float32[%d]" % ROWS])
    else:
        assert tables


def test_behind_a_cast_the_only_table_kept_is_the_cast():
    """The head's case: the float32 logits are a ``convert`` of the bf16
    product, the one [rows, vocab] array kept (inside one program XLA
    fuses it into its readers and the bf16 product is what lives)."""
    x, lab = _logits().astype(jnp.bfloat16), _labels("random")
    kept = saved_residuals(
        lambda x: jnp.sum(picked_log_prob(x.astype(jnp.float32), lab)), x)
    tables = _table_sized(kept, x.shape)
    assert len(tables) == 1 and "convert_element_type" in tables[0][1]


def _primitives(jaxpr):
    """Names of every primitive of a jaxpr, sub-jaxprs included."""
    names = []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    names.extend(_primitives(inner))
    return names


@pytest.mark.parametrize("form", ["the_rule", "the_two_ops"])
def test_the_backward_has_no_scatter(form):
    """Neither a scatter nor a gather in value-and-gradient of the rule;
    autodiff of the two ops transposes its gather to a scatter-add."""
    x, lab = _logits().astype(jnp.bfloat16), _labels("random")
    fn = picked_log_prob if form == "the_rule" else _two_ops
    names = _primitives(jax.make_jaxpr(jax.value_and_grad(
        lambda x: jnp.sum(fn(x.astype(jnp.float32), lab))))(x).jaxpr)
    moves = [n for n in names if "scatter" in n or "gather" in n]
    if form == "the_rule":
        assert moves == []
        assert "exp" in names and "iota" in names
    else:
        assert any("scatter" in n for n in moves)


def test_a_trace_of_the_node_is_counted():
    """``lm.picked_logp_traces``: one a node and lowering, labelled with
    the rows and the vocabulary."""
    telemetry.reset()
    telemetry.enable()
    try:
        mx.nd.pick_log_softmax(mx.nd.ones((6, 4, 9)), mx.nd.zeros((6, 4)))
        streams = telemetry.snapshot()["lm.picked_logp_traces"]["streams"]
        assert [(s["labels"], s["value"]) for s in streams] == [
            ({"rows": 24, "vocab": 9}, 1)]
    finally:
        telemetry.disable()
        telemetry.reset()


# -- through head_and_loss ----------------------------------------------------

def _head_symbol(tied, logit_scale, dtype, vocab=96, seq=16, width=32):
    embed = mx.sym.Variable("embed_weight")
    h = mx.sym.Embedding(
        mx.sym.Reshape(mx.sym.Variable("data"), shape=(-1,)), weight=embed,
        input_dim=vocab, output_dim=width, dtype=dtype, name="embed")
    return lm_blocks.head_and_loss(
        h, mx.sym.Variable("softmax_label"), [], vocab, seq, 1e-5,
        tied_to=embed if tied else None, logit_scale=logit_scale)


def _loss_and_grads(sym, feeds, dtype, seed=3):
    """(per-sequence losses, gradients by name) of the summed loss, as a
    training step traces the graph."""
    shapes, _, _ = sym.infer_shape(**{k: v.shape for k, v in feeds.items()})
    rng = np.random.RandomState(seed)
    params = {name: jnp.asarray(0.3 * rng.randn(*shape), dtype)
              for name, shape in zip(sym.list_arguments(), shapes)
              if name not in feeds}
    program = _GraphProgram(sym)

    def loss(params):
        outs, _ = program(dict(params, **feeds), {}, jax.random.PRNGKey(0),
                          True)
        return jnp.sum(outs[0].astype(jnp.float32)), outs[0]

    (_, losses), grads = jax.value_and_grad(loss, has_aux=True)(params)
    return losses, grads


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("logit_scale", [1.0, 0.25],
                         ids=["unscaled", "logit_scale"])
@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_head_and_loss_gives_the_old_graphs_loss_and_gradients(
        tied, logit_scale, dtype):
    """``logit_scale`` and the tied head sit before the node and go
    through unchanged: loss and every gradient of the graph of before.
    The labels hold both ends of the vocabulary."""
    vocab, seq, batch = 96, 16, 4
    new = _head_symbol(tied, logit_scale, dtype)
    with the_old_head():
        old = _head_symbol(tied, logit_scale, dtype)
    assert "lm_head_logp_output" in old.get_internals().list_outputs()
    assert "lm_head_logp_output" not in new.get_internals().list_outputs()
    assert new.list_arguments() == old.list_arguments()
    rng = np.random.RandomState(1)
    label = rng.randint(0, vocab, (batch, seq))
    label[0, :2] = (0, vocab - 1)
    feeds = {"data": jnp.asarray(rng.randint(0, vocab, (batch, seq)),
                                 jnp.float32),
             "softmax_label": jnp.asarray(label, jnp.float32)}
    want_loss, want = _loss_and_grads(old, feeds, jnp.dtype(dtype))
    got_loss, got = _loss_and_grads(new, feeds, jnp.dtype(dtype))
    np.testing.assert_allclose(got_loss, want_loss, rtol=2e-6)
    assert set(got) == set(want)
    tol = 2e-6 if dtype == "float32" else 2.0 ** -6
    for name in want:
        assert got[name].dtype == want[name].dtype == jnp.dtype(dtype)
        w = np.asarray(want[name], np.float32)
        assert np.abs(w).max() > 1e-6, name
        np.testing.assert_allclose(
            np.asarray(got[name], np.float32), w, rtol=tol,
            atol=tol * np.abs(w).max(), err_msg=name)



# -- every model's symbol -----------------------------------------------------

MODELS = ("olmoe", "mimo_v2", "kanana2", "nemotron_h", "olmo_hybrid", "lfm2",
          "falcon_h1", "kimi_linear", "afmoe", "dots3", "solar_open2", "ouro")


def _tiny(model):
    """The model's symbol at the size its own test file builds it."""
    test = importlib.import_module("test_" + model)
    module = importlib.import_module("mxnet_tpu.models." + model)
    if model == "olmoe":
        return module.get_symbol(**test.TINY)
    if model == "ouro":
        return module.from_config(test._cfg(4), seq_len=test.T)
    more = {}
    if "chunk_size" in inspect.signature(module.from_config).parameters:
        more["chunk_size"] = test.CHUNK
    return module.from_config(test.CFG, seq_len=test.T, **more)


def _without_counts(names):
    """A node nobody named is ``<op><count of the process>``: the count
    goes (``tests/test_ouro.py`` levels its digests so), so that two
    builds of one symbol list the same names."""
    return [re.sub(r"^([a-z_]+)\d+(_output)?$", r"\1\2", name)
            for name in names]


@pytest.mark.parametrize("model", MODELS)
def test_a_models_symbol_keeps_its_arguments_outputs_and_nodes(model):
    """Against the same builder under the head of before: the same
    arguments in the same order, the same outputs, and the same internals
    but for ``lm_head_logp``, which is gone; ``lm_head_f32_output`` (what
    the benchmark's reference check binds) and ``loss_output`` stand."""
    new = _tiny(model)
    with the_old_head():
        old = _tiny(model)
    assert new.list_arguments() == old.list_arguments()
    assert new.list_outputs() == old.list_outputs()
    was = old.get_internals().list_outputs()
    now = new.get_internals().list_outputs()
    passes = ["loop%d_" % t for t in range(1, 5)] if model == "ouro" else [""]
    gone = [p + "lm_head_logp_output" for p in passes]
    assert [name for name in was if "lm_head_logp" in name] == gone
    assert _without_counts(now) == _without_counts(
        [name for name in was if name not in gone])
    ops = {n["name"]: n["op"] for n in json.loads(new.tojson())["nodes"]}
    for p in passes:
        assert p + "lm_head_f32_output" in now
        assert ops[p + "lm_head_pick"] == "pick_log_softmax"
    assert "loss_output" in now and ops["loss"] == "MakeLoss"


# -- one Module.fit step ------------------------------------------------------

def _untied():
    return olmoe.get_symbol(vocab_size=96, hidden_size=32, num_layers=1,
                            num_heads=2, num_experts=4, experts_per_token=2,
                            expert_width=16, seq_len=8), "lm_head_weight"


def _tied():
    return lfm2.get_symbol(
        vocab_size=64, hidden_size=32, layer_types=("conv", "full_attention"),
        dense_layers=2, num_heads=2, num_kv_heads=1, head_dim=16,
        dense_width=16, seq_len=8), "embed_weight"


@pytest.mark.parametrize("build", [_untied, _tied], ids=["untied", "tied"])
def test_one_fit_step_gives_the_old_graphs_loss_and_head_gradient(build):
    """``Module.fit``'s fused step over an untied and a tied symbol: the
    loss it reports and the head's gradient (read off the weight one SGD
    step moved) are the old graph's within float32 rounding."""
    new, head = build()
    with the_old_head():
        old, _ = build()
    assert "lm_head_logp_output" in old.get_internals().list_outputs()
    batch, seq, lr = 2, 8, 0.5
    shapes, _, _ = new.infer_shape(data=(batch, seq),
                                   softmax_label=(batch, seq))
    rng = np.random.RandomState(7)
    params = {}
    for name, shape in zip(new.list_arguments(), shapes):
        if name in ("data", "softmax_label"):
            continue
        params[name] = ((1 + 0.1 * rng.randn(*shape))
                        if name.endswith("_gamma")
                        else 0.2 * rng.randn(*shape)).astype(np.float32)
    vocab = params[head].shape[0]
    tokens = rng.randint(0, vocab, (batch, seq + 1))
    tokens[0, 1:3] = (0, vocab - 1)
    data, labels = (tokens[:, :-1].astype(np.float32),
                    tokens[:, 1:].astype(np.float32))
    want_loss, want = _fused_step(old, params, data, labels, lr=lr)
    got_loss, got = _fused_step(new, params, data, labels, lr=lr)
    want, got = ({k: v.asnumpy() for k, v in mod.get_params()[0].items()}
                 for mod in (want, got))
    np.testing.assert_allclose(got_loss, want_loss, rtol=2e-6)
    g_want = (params[head] - want[head]) / lr
    g_got = (params[head] - got[head]) / lr
    assert np.abs(g_want).max() > 1e-4
    np.testing.assert_allclose(g_got, g_want, rtol=1e-5,
                               atol=1e-5 * np.abs(g_want).max())
    for name in params:
        np.testing.assert_allclose(got[name], want[name], rtol=1e-5,
                                   atol=1e-6, err_msg=name)
