"""``Embedding``'s backward rule (``ops/indexing.py::_lookup``) against
what autodiff gives ``jnp.take``.

Autodiff transposes the lookup to a scatter-add of the cotangent's rows
into the table, in the table's type: a bf16 table rounds at every row
added. The rule sorts the ids and sums each id's rows as a sorted segment
sum (``ops.kernels.sorted_segment_sum``: ``jax.ops.segment_sum`` here on
the CPU, the grouped matmul's wgrad kernel over an exact 0 / 1 table where
the step is lowered for the TPU, through the Pallas interpreter under the
kernel layer's one seam): float32 accumulation and one rounding. In
float32 the two differ by the order of summation; in bf16 the rule is held
bit for bit to a float32 sum rounded once, over cotangents whose sums are
exact in float32 whatever their order.

The vocabularies keep the cells' half group of 256: 32,640 (127.5 groups)
is 384 here, 50,304 (196.5) is 640.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.executor import _GraphProgram
from mxnet_tpu.models import lm_blocks
from mxnet_tpu.ops import indexing, kernels

WIDTH = 128


def _ids(name, vocab):
    """data (any rank, int32) for the cases a uniform draw does not hold."""
    rng = np.random.RandomState(5)
    if name == "uniform":
        return rng.randint(0, vocab, 512)
    if name == "duplicates":
        return rng.randint(0, 40, 512)
    if name == "every_id_the_same":
        return np.full(256, vocab - 3)
    if name == "whole_groups_empty":
        # nothing in the first group of 256 nor in the table's last rows
        return rng.randint(256, min(vocab, 512) - 64, 384)
    if name == "rank_2":
        return rng.randint(0, vocab, (4, 64))
    if name == "rank_3":
        return rng.randint(0, vocab, (2, 4, 32))
    if name == "out_of_range_and_negative":
        ids = rng.randint(0, vocab, 256)
        # -1 and -vocab are the table's last and first rows; the others
        # read no row
        ids[:6] = [-1, -vocab, -vocab - 1, vocab, vocab + 300, -5 * vocab]
        return ids
    raise KeyError(name)


CASES = ["uniform", "duplicates", "every_id_the_same", "whole_groups_empty",
         "rank_2", "rank_3", "out_of_range_and_negative"]


@pytest.fixture(params=["segment_sum", "interpreted_kernel"])
def path(request, monkeypatch):
    """The branch of every other platform, and the kernel's own through
    the Pallas interpreter (the kernel layer's one seam)."""
    if request.param == "interpreted_kernel":
        monkeypatch.setattr(kernels.common, "INTERPRET", True)
    return request.param


def _exact_cotangent(shape, dtype, seed=2):
    """Quarters within +-8: any sum of a few thousand of them is exact in
    float32, and past 256 of them it no longer fits bf16's eight bits."""
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randint(-32, 33, shape) / 4.0, dtype)


def _rule_grad(table, ids, cot):
    def loss(w):
        out = indexing._lookup(w, jnp.asarray(ids, jnp.int32), w.shape[0])
        return jnp.sum(out.astype(jnp.float32) * cot.astype(jnp.float32))

    return jax.grad(loss)(table)


def _once_rounded(ids, cot, vocab, dtype):
    """The table's gradient summed in float32 and rounded once, ids as
    ``jnp.take`` reads them."""
    ids = np.asarray(ids).reshape(-1)
    ids = np.where(ids < 0, ids + vocab, ids)
    keep = (ids >= 0) & (ids < vocab)
    want = np.zeros((vocab, cot.shape[-1]), np.float32)
    rows = np.asarray(cot, np.float32).reshape(-1, cot.shape[-1])
    np.add.at(want, ids[keep], rows[keep])
    return np.asarray(jnp.asarray(want).astype(dtype), np.float32)


@pytest.mark.parametrize("vocab", [384, 640, 512])
@pytest.mark.parametrize("case", CASES)
def test_float32_equals_autodiffs_gradient(case, vocab, path):
    ids = _ids(case, vocab)
    table = jnp.asarray(
        np.random.RandomState(1).randn(vocab, WIDTH), jnp.float32)
    cot = jnp.asarray(np.random.RandomState(2).randn(*ids.shape, WIDTH),
                      jnp.float32)
    got = _rule_grad(table, ids, cot)
    want = jax.grad(lambda w: jnp.sum(
        jnp.take(w, jnp.asarray(ids, jnp.int32), axis=0) * cot))(table)
    assert got.dtype == table.dtype and got.shape == table.shape
    # equal up to the order of summation: within 1e-6 of what a row's
    # summands add up to in magnitude
    room = _once_rounded(ids, jnp.abs(cot), vocab, jnp.float32)
    assert (np.abs(np.asarray(got) - np.asarray(want))
            <= 1e-6 * room).all()
    untouched = np.setdiff1d(
        np.arange(vocab), np.where(ids < 0, ids + vocab, ids))
    assert not np.asarray(got)[untouched].any()


@pytest.mark.parametrize("vocab", [384, 640, 512])
@pytest.mark.parametrize("case", CASES)
def test_bf16_is_a_float32_sum_rounded_once(case, vocab, path):
    ids = _ids(case, vocab)
    table = jnp.zeros((vocab, WIDTH), jnp.bfloat16)
    cot = _exact_cotangent(ids.shape + (WIDTH,), jnp.bfloat16)
    got = _rule_grad(table, ids, cot)
    assert got.dtype == jnp.bfloat16
    want = _once_rounded(ids, cot, vocab, jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(got, np.float32), want)
    if case == "every_id_the_same":
        # XLA's bf16 scatter rounds at every row it adds: not this sum
        scattered = jax.grad(lambda w: jnp.sum(
            (jnp.take(w, jnp.asarray(ids, jnp.int32), axis=0)
             * cot).astype(jnp.float32)))(table)
        assert not np.array_equal(np.asarray(scattered, np.float32), want)


def test_the_forward_is_jnp_takes(path):
    """Same values, type and out-of-range reading: a row of NaN."""
    vocab = 384
    ids = _ids("out_of_range_and_negative", vocab)
    table = jnp.asarray(
        np.random.RandomState(1).randn(vocab, WIDTH), jnp.bfloat16)
    got = indexing._lookup(table, jnp.asarray(ids, jnp.int32), vocab)
    want = jnp.take(table, jnp.asarray(ids, jnp.int32), axis=0)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    assert np.isnan(np.asarray(got, np.float32)[2:6]).all()


def test_an_empty_lookup_has_a_zero_gradient():
    table = jnp.ones((384, WIDTH), jnp.float32)
    got = _rule_grad(table, np.zeros((0,), np.int32),
                     jnp.zeros((0, WIDTH), jnp.float32))
    assert got.shape == table.shape and not np.asarray(got).any()


def _program_grads(sym, feeds, dtype=jnp.float32, seed=3):
    """(gradients by name, parameters) of ``sym``'s summed outputs, as a
    training step traces it."""
    shapes, _, _ = sym.infer_shape(**{k: v.shape for k, v in feeds.items()})
    rng = np.random.RandomState(seed)
    params = {name: jnp.asarray(0.1 * rng.randn(*shape), dtype)
              for name, shape in zip(sym.list_arguments(), shapes)
              if name not in feeds}
    program = _GraphProgram(sym)

    def loss(params):
        outs, _ = program(dict(params, **feeds), {}, jax.random.PRNGKey(0),
                          True)
        return sum(jnp.sum(o.astype(jnp.float32)) for o in outs)

    return jax.grad(loss)(params), params


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_tied_heads_two_gradients_sum(dtype, path):
    """``lm_blocks.head_and_loss(tied_to=)``: one ``Variable`` read by the
    lookup and by the head's product; its gradient is the sum of the rule's
    and the product's, against the same model over ``jnp.take``."""
    tokens, vocab, seq = 256, 384, 64
    name = jnp.dtype(dtype).name
    embed = mx.sym.Variable("embed_weight")
    h = mx.sym.Embedding(
        mx.sym.Reshape(mx.sym.Variable("data"), shape=(-1,)), weight=embed,
        input_dim=vocab, output_dim=WIDTH, dtype=name, name="embed")
    sym = lm_blocks.head_and_loss(
        h, mx.sym.Variable("softmax_label"), [], vocab, seq, 1e-5,
        tied_to=embed)
    rng = np.random.RandomState(0)
    feeds = {"data": jnp.asarray(rng.randint(0, 48, (tokens // seq, seq)),
                                 jnp.float32),
             "softmax_label": jnp.asarray(
                 rng.randint(0, vocab, (tokens // seq, seq)), jnp.float32)}
    grads, params = _program_grads(sym, feeds, dtype)

    def plain(params):
        w = params["embed_weight"]
        x = jnp.take(w, feeds["data"].reshape(-1).astype(jnp.int32), axis=0)
        x32 = x.astype(jnp.float32)
        normed = (x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + 1e-5)
            * params["final_norm_gamma"].astype(jnp.float32)).astype(dtype)
        logits = jnp.dot(normed, w.T).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits)
        label = feeds["softmax_label"].reshape(-1).astype(jnp.int32)
        nll = -jnp.take_along_axis(logp, label[:, None], axis=1)[:, 0]
        return jnp.sum(jnp.mean(nll.reshape(-1, seq), axis=1))

    want = jax.grad(plain)(params)
    assert grads["embed_weight"].dtype == dtype
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(grads["embed_weight"], np.float32),
        np.asarray(want["embed_weight"], np.float32), rtol=tol, atol=tol)
    # rows no token reads still carry the head's gradient: the rule's
    # zeros there add nothing
    assert np.asarray(grads["embed_weight"], np.float32)[48:].any()


def test_two_nodes_over_one_table_sum(path):
    vocab = 384
    table = mx.sym.Variable("table")
    first = mx.sym.Embedding(mx.sym.Variable("a"), weight=table,
                             input_dim=vocab, output_dim=WIDTH, name="first")
    second = mx.sym.Embedding(mx.sym.Variable("b"), weight=table,
                              input_dim=vocab, output_dim=WIDTH,
                              name="second")
    sym = mx.sym.MakeLoss(mx.sym.sum(first * first) + 3 * mx.sym.sum(second))
    rng = np.random.RandomState(4)
    a, b = rng.randint(0, 64, 256), rng.randint(32, vocab, 128)
    feeds = {"a": jnp.asarray(a, jnp.float32),
             "b": jnp.asarray(b, jnp.float32)}
    grads, params = _program_grads(sym, feeds)
    w = np.asarray(params["table"], np.float64)
    want = np.zeros_like(w)
    np.add.at(want, a, 2 * w[a])
    np.add.at(want, b, 3.0)
    np.testing.assert_allclose(np.asarray(grads["table"]), want,
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("seam", [True, False],
                         ids=["kernels_branch", "cpu_branch"])
def test_the_rules_ops_keep_the_nodes_scope(monkeypatch, seam):
    """What ``bench/lm_scopes.py`` files under ``embed``: the sort, the
    gather and the sum carry ``transpose(jvp(embed/<node>))``, and with
    the kernels' branch taken the program scatters nothing."""
    monkeypatch.setattr(kernels.common, "INTERPRET", seam)
    vocab = 384
    sym = mx.sym.MakeLoss(mx.sym.sum(mx.sym.Embedding(
        mx.sym.Variable("data"), input_dim=vocab, output_dim=WIDTH,
        name="tok")))
    program = _GraphProgram(sym)
    data = jnp.asarray(np.arange(256) % 7, jnp.float32)

    def loss(params, data):
        outs, _ = program(dict(params, data=data), {},
                          jax.random.PRNGKey(0), True)
        return jnp.sum(outs[0])

    text = jax.jit(jax.grad(loss)).lower(
        {"tok_weight": jnp.ones((vocab, WIDTH), jnp.bfloat16)}, data
    ).compile().as_text()
    ops = re.findall(r' ([a-z-]+)\(.*op_name="([^"]*)"', text)
    scoped = {op for op, name in ops if "transpose(jvp(embed/tok))" in name}
    assert {"sort", "gather"} <= scoped
    scatters = [name for op, name in ops if op == "scatter"]
    if seam:
        assert not scatters
    else:
        assert scatters and all(
            "transpose(jvp(embed/tok))" in name for name in scatters)


def _count(**labels):
    return telemetry.REGISTRY.get("embedding.grad_lowerings").value(**labels)


def test_the_rule_counts_its_lowerings():
    """One a traced backward rule, labelled by what it sums and how:
    ``segment_product`` where ``sorted_segment_sum`` takes the rows (a
    product where the step is lowered for the TPU), ``segment_sum`` under
    one row tile; nothing for a forward."""
    table = jnp.ones((384, WIDTH), jnp.bfloat16)
    telemetry.reset()
    telemetry.enable()
    try:
        grad = jax.jit(lambda w, i: jax.grad(lambda w: jnp.sum(
            indexing._lookup(w, i, 384).astype(jnp.float32)))(w))
        grad.lower(table, jnp.zeros((4, 64), jnp.int32))
        labels = dict(rows=256, vocab=384, width=WIDTH, dtype="bfloat16")
        assert _count(impl="segment_product", **labels) == 1
        gmm = telemetry.REGISTRY.get("moe.gmm_lowerings")
        tiles = kernels.gmm_tiles(256, kernels.gmm.SEGMENT_TILE, WIDTH, 2,
                                  jnp.bfloat16, wgrad=True)
        assert gmm.value(mode="wgrad", operands="bf16", tm=tiles[0],
                         tk=tiles[1], tn=tiles[2]) == 1
        grad.lower(table.astype(jnp.float32), jnp.zeros((64,), jnp.int32))
        assert _count(impl="segment_sum", rows=64, vocab=384, width=WIDTH,
                      dtype="float32") == 1
        jax.jit(lambda w, i: indexing._lookup(w, i, 384)).lower(
            table, jnp.zeros((256,), jnp.int32))
        assert _count(impl="segment_product", **labels) == 1
    finally:
        telemetry.disable()
        telemetry.reset()


def _fit_embedding(mesh, tokens=256, vocab=384):
    """One SGD step of a summed lookup through ``ShardedTrainStep`` on
    ``mesh`` -> the updated table."""
    from mxnet_tpu import optimizer as opt
    from mxnet_tpu.initializer import Uniform
    from mxnet_tpu.parallel.train_step import ShardedTrainStep

    emb = mx.sym.Embedding(mx.sym.Variable("data"), input_dim=vocab,
                           output_dim=WIDTH, name="tok")
    sym = mx.sym.MakeLoss(mx.sym.sum(emb * emb, axis=1), name="loss")
    step = ShardedTrainStep(
        sym, mesh, optimizer=opt.create("sgd", learning_rate=0.5))
    np.random.seed(0)
    params, aux, state = step.init({"data": (tokens,),
                                    "tok_weight": (vocab, WIDTH)},
                                   Uniform(0.1))
    step.compile({"data": None})
    ids = np.random.RandomState(1).randint(0, 96, tokens).astype("f")
    new, _, _, _ = step(params, aux, state, {"data": jnp.asarray(ids)}, None)
    return np.asarray(new["tok_weight"])


def test_a_partitioned_step_keeps_the_scatter():
    """The kernels have no partitioning rule: under a mesh of more than
    one device the rule is today's transpose (``impl="scatter"``), and the
    step's result is the one-device step's."""
    from mxnet_tpu.parallel.mesh import make_mesh

    telemetry.reset()
    telemetry.enable()
    try:
        labels = dict(rows=256, vocab=384, width=WIDTH, dtype="float32")
        one = _fit_embedding(make_mesh(dp=1, devices=jax.devices()[:1]))
        assert _count(impl="segment_product", **labels) == 1
        assert _count(impl="scatter", **labels) == 0
        four = _fit_embedding(make_mesh(dp=4, devices=jax.devices()[:4]))
        assert _count(impl="scatter", **labels) == 1
        assert _count(impl="segment_product", **labels) == 1
    finally:
        telemetry.disable()
        telemetry.reset()
    np.testing.assert_allclose(four, one, rtol=1e-5, atol=1e-6)
