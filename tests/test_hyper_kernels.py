"""The residual streams' one pass (PR 70, ``ops/kernels/hyper.py``): the
coefficient products, the mean square and the read of a ``HyperCoeff`` node
in one kernel pass over a token block each way, the stream's cotangents (the products', the mean square's, the read's and the WRITE's)
summed in the backward's; and the write of the next stream (``HyperMix``
with an addend), whole rows, one pass each way. Through the Pallas
interpreter on the CPU at small shapes: the forward is ``hyper_coeff`` +
``hyper_mix(m=1)``, the write ``hyper_mix(m=n)``, the cotangents are
autodiff's of the ``jax.numpy`` forms with a write behind the read, the
rule (``kernels.hyper_takes``, ``_takes_one_stream_pass``) reads
the call's own arguments, ``lm.hc_lowerings{form}`` says which form a node
took, and the Xing4 model on the kernels still matches its reference.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.models import xing4, xing4_reference as ref
from mxnet_tpu.ops import kernels
from mxnet_tpu.ops import transformer as tr
from mxnet_tpu.ops.transformer import hyper as hyper_ops
from mxnet_tpu.ops.kernels import hyper

ITERS, EPS, CLAMP, NORM_EPS = 20, 1e-6, (-30.0, 30.0), 1e-6
TOKENS, N = 256, 4


def _operands(c, dtype, seed, tokens=TOKENS, n=N):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(tokens, n * c), dtype),
            jnp.asarray(0.05 * rng.randn(n * (n + 2), n * c), dtype),
            jnp.asarray(0.5 * rng.randn(n * (n + 2)), jnp.float32),
            jnp.asarray([0.7, 1.1, 0.9], jnp.float32),
            jnp.asarray(rng.randn(tokens, c), dtype))


def plain(x, phi, bias, alpha, n=N):
    """What a node ran before the one pass: (pre, post, res, err, read,
    stream)."""
    outs = tr.hyper_coeff(x, phi, bias, alpha, n, ITERS, EPS, CLAMP,
                          NORM_EPS)
    return outs + (tr.hyper_mix(x, outs[0]), x)


def one_pass(x, phi, bias, alpha, n=N):
    return tr.hyper_coeff_read(x, phi, bias, alpha, n, ITERS, EPS, CLAMP,
                               NORM_EPS)


def sublayer(form):
    """The read, a sub-layer that is a product with ``y``, and the write
    behind it (the kernels' where the read is): the stream's cotangent is
    the sum of the write's, the read's and the coefficients'."""
    def f(x, phi, bias, alpha, y):
        _, post, res, _, read, stream = form(x, phi, bias, alpha)
        out = (1.5 * read.astype(jnp.float32) * y.astype(jnp.float32))
        if form is one_pass:
            return kernels.stream_write(stream, res, out.astype(x.dtype),
                                        post, interpret=True)
        return tr.hyper_mix(stream, res, out.astype(x.dtype), post)
    return f


def _close(got, want, dtype, what, ulps):
    """Within ``ulps`` roundings of ``dtype`` at the array's own scale (a
    carry's distance from doubly stochastic, ``err``: at the scale of the
    sums it is a difference of, 1): the sums are the same float32 sums in
    another order."""
    got, want = (np.asarray(a, np.float64) for a in (got, want))
    scale = 1.0 if what.endswith("err") else max(np.abs(want).max(), 1e-30)
    room = ulps * float(jnp.finfo(dtype).eps) * scale
    np.testing.assert_allclose(got, want, rtol=0, atol=room, err_msg=what)


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(kernels.common, "INTERPRET", True)


@pytest.fixture
def lowerings():
    """form -> the nodes ``lm.hc_lowerings`` counted under it."""
    telemetry.reset()
    telemetry.enable()

    def by_form(node="coeff"):
        counter = telemetry.REGISTRY.get("lm.hc_lowerings")
        return {form: counter.value(node=node, form=form)
                for form in ("one_pass", "plain")}
    try:
        yield by_form
    finally:
        telemetry.disable()
        telemetry.reset()


# -- the one pass against the jax.numpy forms ---------------------------------

@pytest.mark.parametrize("c", [128, 256])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_forward_is_the_coefficients_and_the_read(interpreted, dtype, c):
    x, phi, bias, alpha, _ = _operands(c, dtype, c)
    got, want = one_pass(x, phi, bias, alpha), plain(x, phi, bias, alpha)
    for name, a, b in zip(("pre", "post", "res", "err", "read"), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        _close(a, b, jnp.float32 if name != "read" else dtype, name,
               ulps=64 if name in ("res", "err") else 8)
    # the stream is handed on as it came
    assert got[5] is x or (np.asarray(got[5]) == np.asarray(x)).all()
    # the statistics as the two layouts hold them
    gate = hyper.gate_rows(bias, alpha, N)
    stats_t, stats, _ = hyper.read_fwd_call(
        x, phi, gate, n=N, norm_eps=NORM_EPS,
        block=hyper.hyper_takes(TOKENS, N, c, dtype), interpret=True)
    np.testing.assert_array_equal(np.asarray(stats_t),
                                  np.asarray(stats)[:, :hyper.STAT_ROWS].T)
    assert not np.asarray(stats)[:, N * (N + 2) + 1:].any()
    want_t, want_stats, _ = hyper.plain_fwd(x, phi, gate, n=N,
                                            norm_eps=NORM_EPS)
    _close(stats, want_stats, jnp.float32, "statistics", ulps=8)


@pytest.mark.parametrize("c", [128, 256])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_cotangents_with_a_write_behind_are_autodiffs(interpreted, dtype,
                                                          c):
    """The stream's three cotangents arrive summed once; ``phi``, ``bias``
    and ``alpha`` get the read's part beside the mixings'."""
    args = _operands(c, dtype, 7 + c)
    g = jnp.asarray(np.random.RandomState(c).randn(TOKENS, N * c), dtype)
    out, pull = jax.vjp(sublayer(one_pass), *args)
    want_out, pull_plain = jax.vjp(sublayer(plain), *args)
    _close(out, want_out, dtype, "the next stream", ulps=4)
    # bf16: the plain form rounds each of the stream's cotangents to bf16
    # before it adds them, the kernel adds them in float32
    for name, got, want in zip(("stream", "phi", "bias", "alpha", "y"),
                               pull(g), pull_plain(g)):
        assert got.shape == want.shape and got.dtype == want.dtype, name
        _close(got, want, got.dtype if name in ("stream", "phi", "y")
               else dtype, name, ulps=64 if dtype == jnp.float32 else 4)
        assert np.abs(np.asarray(want, np.float32)).max() > 1e-3, name


def test_the_backward_kernel_is_the_plain_backward(interpreted):
    """``read_bwd_call`` against ``plain_bwd`` on the same operands (bf16:
    the branch a platform other than the TPU takes inside the
    ``custom_vjp``), ``dphi`` summed over two token blocks."""
    c, dtype = 128, jnp.bfloat16
    x, phi, bias, alpha, du = _operands(c, dtype, 3)
    rng = np.random.RandomState(4)
    gate = hyper.gate_rows(bias, alpha, N)
    block = hyper.hyper_takes(TOKENS, N, c, dtype)
    assert block == 256 and hyper.hyper_takes(128, N, c, dtype) == 128
    _, stats, _ = hyper.plain_fwd(x, phi, gate, n=N, norm_eps=NORM_EPS)
    g_t = jnp.asarray(rng.randn(hyper.STAT_ROWS, TOKENS), jnp.float32)
    g_t = g_t.at[N * (N + 2) + 1:].set(0)
    dxw = jnp.asarray(rng.randn(TOKENS, N * c), dtype)
    operands = (x, phi, gate, stats, g_t, du, dxw)
    want = hyper.plain_bwd(*operands, n=N, norm_eps=NORM_EPS)
    for block in (128, 256):
        got = hyper.read_bwd_call(*operands, n=N, norm_eps=NORM_EPS,
                                  block=block, interpret=True)
        for name, a, b in zip(("dx", "dphi", "by_row"), got, want):
            assert a.shape == b.shape and a.dtype == b.dtype, name
            _close(a, b, dtype, name, ulps=4)
        assert not np.asarray(got[1])[N * (N + 2):].any()


@pytest.mark.parametrize("c", [128, 256, 512, 1024])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_write_is_hyper_mix_with_an_addend(dtype, c):
    """``stream_write`` against ``hyper_mix(x, res, y, post)``: the next
    stream and the four cotangents (a hidden size that is and is not whole
    loop-step chunks)."""
    x, phi, bias, alpha, y = _operands(c, dtype, 20 + c)
    _, post, res, _ = tr.hyper_coeff(x, phi, bias, alpha, N, ITERS, EPS,
                                     CLAMP, NORM_EPS)
    g = jnp.asarray(np.random.RandomState(c).randn(TOKENS, N * c), dtype)
    got, pull = jax.vjp(lambda *a: kernels.stream_write(
        *a, interpret=True), x, res, y, post)
    want, pull_plain = jax.vjp(tr.hyper_mix, x, res, y, post)
    assert got.shape == want.shape and got.dtype == want.dtype
    _close(got, want, dtype, "the next stream", ulps=2)
    for name, a, b in zip(("stream", "res", "y", "post"), pull(g),
                          pull_plain(g)):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        _close(a, b, a.dtype if name in ("stream", "y") else jnp.float32,
               name, ulps=2 if a.dtype == jnp.bfloat16 else 64)
    # the token-minor tile the kernels read: the carry row-major, then the
    # write's column, zeros beyond
    tile = np.asarray(hyper.mix_rows(res, post))
    assert tile.shape == (hyper.STAT_ROWS, TOKENS)
    np.testing.assert_array_equal(tile[:N * N].reshape(N, N, -1), res)
    np.testing.assert_array_equal(tile[N * N:N * N + N], post)
    assert not tile[N * N + N:].any()


# -- the rule -----------------------------------------------------------------

@pytest.mark.parametrize("tokens,n,c,dtype,block", [
    (4096, 4, 3584, "bfloat16", 128),     # the Xing4.0 cell's
    (256, 4, 128, "float32", 256),
    (384, 4, 128, "bfloat16", 128),
    (256, 2, 256, "bfloat16", 256),
    (256, 4, 64, "bfloat16", None),       # half a lane row
    (256, 4, 192, "float32", None),       # a lane row and a half
    (200, 4, 128, "bfloat16", None),      # no block divides the tokens
    (64, 4, 128, "bfloat16", None),
    (256, 4, 128, "float16", None),
    (256, 4, 128, "float64", None),
    (256, 5, 128, "bfloat16", None),      # 35 products: over the tile
    (4096, 4, 16384, "bfloat16", None),   # a block over the raised limit
])
def test_the_rule_reads_the_shapes_and_the_type(tokens, n, c, dtype, block):
    assert kernels.hyper_takes(tokens, n, c, dtype) == block
    if block is not None:
        for kernel in hyper.KERNELS:
            assert hyper.hyper_vmem_bytes(
                block, n, c, jnp.dtype(dtype).itemsize,
                kernel) <= kernels.common.VMEM_RAISED_LIMIT
    else:
        x = jnp.zeros((tokens, n * c), dtype)
        with pytest.raises(ValueError, match="hyper_takes decides"):
            kernels.stream_read(x, x[:n * (n + 2)], jnp.zeros(n * (n + 2)),
                                jnp.zeros(3), n, 1e-6)


# MiB under which the compiler for a described v5e takes each kernel (the
# smallest ``vmem_limit_bytes`` to half a MiB, PR 70), in ``hyper.KERNELS``'
# order, by (streams, hidden, type, block)
_COMPILER_NEEDS = {
    (4, 3584, "bfloat16", 128): (9.4, 25.6, 17.7, 27.5),    # the cell's
    (4, 3584, "bfloat16", 256): (19.6, 54.9, 33.5, 52.1),
    (4, 3584, "float32", 128): (18.2, 48.4, 33.1, 50.3),
    (4, 1024, "bfloat16", 256): (6.1, 15.9, 10.3, 15.4),
    (4, 7168, "bfloat16", 128): (18.7, 51.2, 32.6, 52.6),
    (2, 2048, "bfloat16", 256): (7.0, 18.2, 10.3, 16.8)}


@pytest.mark.parametrize("kernel", hyper.KERNELS)
@pytest.mark.parametrize("shape", _COMPILER_NEEDS,
                         ids=lambda s: "n%d-c%d-%s-b%d" % s)
def test_a_kernels_count_follows_what_the_compiler_needs(shape, kernel):
    """``hyper_vmem_bytes`` is each kernel's own count: never under what
    the compiler needs (the kernel's limit is its count), and the two
    backward kernels, which decide what ``hyper_takes`` admits, within a
    fifth over it."""
    n, c, dtype, block = shape
    need = _COMPILER_NEEDS[shape][hyper.KERNELS.index(kernel)]
    count = hyper.hyper_vmem_bytes(block, n, c, jnp.dtype(dtype).itemsize,
                                   kernel) / 2.0 ** 20
    assert need <= count
    if kernel.endswith("bwd"):
        assert count <= 1.2 * need + 1.0


@pytest.mark.parametrize("c,tokens,dtype,form", [
    (128, 256, "bfloat16", "one_pass"), (256, 128, "float32", "one_pass"),
    (64, 256, "bfloat16", "plain"), (128, 200, "bfloat16", "plain"),
    (128, 256, "float16", "plain")])
def test_a_node_takes_the_form_its_call_admits_and_counts_it(
        interpreted, lowerings, c, tokens, dtype, form):
    """Through the symbol layer: a ``HyperCoeff`` node's six results are
    the ``jax.numpy`` forms' either way, and ``lm.hc_lowerings`` counts the
    node once under the form it took."""
    data = mx.sym.Variable("data")
    node = mx.contrib.sym.HyperCoeff(data, streams=N, name="hc")
    assert node.list_outputs() == [
        "hc_pre", "hc_post", "hc_res", "hc_err", "hc_read", "hc_stream"]
    shapes = node.infer_shape(data=(tokens, N * c))[1]
    assert shapes[4:] == [(tokens, c), (tokens, N * c)]
    types = node.infer_type(data=jnp.dtype(dtype).type)[1]
    assert types[:4] == [np.float32] * 4
    assert [jnp.dtype(t) for t in types[4:]] == [jnp.dtype(dtype)] * 2
    x, phi, bias, alpha, _ = _operands(c, dtype, 11, tokens=tokens)
    exe = node.bind(mx.cpu(0), {
        "data": mx.nd.array(x, dtype=dtype),
        "hc_phi": mx.nd.array(phi, dtype=dtype),
        "hc_bias": mx.nd.array(bias), "hc_alpha": mx.nd.array(alpha)})
    got = [o.asnumpy() for o in exe.forward(is_train=False)]
    want = plain(x, phi, bias, alpha)
    for name, a, b in zip(node.list_outputs(), got, want):
        _close(a, b, jnp.float32 if a.dtype == np.float32 else dtype, name,
               ulps=64)
    counted = lowerings()
    assert counted[form] == 1 and sum(counted.values()) == 1
    # the write behind it takes the same form; a mixing that is no write
    # of all n streams (the read of a graph that still has a node for it)
    # has no kernel and is not counted
    y = _operands(c, dtype, 12, tokens=tokens)[4]
    write = mx.contrib.sym.HyperMix(
        node[5], node[2], mx.sym.Variable("y"), node[1], with_add=True,
        name="write")
    exe = mx.sym.Group([write, mx.contrib.sym.HyperMix(
        data, node[0], name="read")]).bind(mx.cpu(0), {
            "data": mx.nd.array(x, dtype=dtype),
            "hc_phi": mx.nd.array(phi, dtype=dtype),
            "hc_bias": mx.nd.array(bias), "hc_alpha": mx.nd.array(alpha),
            "y": mx.nd.array(y, dtype=dtype)})
    got = [o.asnumpy() for o in exe.forward(is_train=False)]
    _close(got[0], tr.hyper_mix(x, want[2], y, want[1]), dtype, "write",
           ulps=8)
    _close(got[1], want[4], dtype, "read", ulps=8)
    counted = lowerings("write")
    assert counted[form] == 1 and sum(counted.values()) == 1
    assert lowerings("read") == {"one_pass": 0, "plain": 0}


def test_a_partitioned_program_takes_the_plain_form(lowerings):
    x, phi, bias, alpha, _ = _operands(128, jnp.bfloat16, 5)
    with kernels.common.partitioned_trace(4):
        assert not hyper_ops._takes_one_stream_pass("coeff", x, N)
    assert hyper_ops._takes_one_stream_pass("coeff", x, N)
    assert lowerings() == {"one_pass": 1, "plain": 1}


def test_the_kernels_say_what_they_run_and_where(interpreted):
    """A device trace names the pair for its operands, streams and width
    (under the node's scope and ``hc_coeff`` both ways:
    ``tests/test_flash_compile_tpu.py`` reads the compiled ops' names)."""
    args = _operands(128, jnp.bfloat16, 2)
    g = jnp.ones((TOKENS, N * 128), jnp.bfloat16)

    def step(*args):
        with jax.named_scope("hc/layer0_attn_hc"):
            out, pull = jax.vjp(sublayer(one_pass), *args)
        return out, pull(g)
    text = jax.jit(step).lower(*args).as_text(debug_info=True)
    for which in ("fwd", "bwd"):
        assert "hc_read_%s_bf16_n4_c128" % which in text
        assert "hc_write_%s_bf16_n4_c128" % which in text
    assert "hc_coeff" in text and "hc/layer0_attn_hc" in text


# -- the model on the kernels -------------------------------------------------

def test_xing4_on_the_kernels_matches_its_reference(interpreted, lowerings,
                                                     monkeypatch):
    """The tiny Xing4 of ``tests/test_xing4.py`` at a hidden size of one
    lane row and ONE sequence of 128 tokens, float32: every sub-layer's
    node takes the one pass (through the interpreter), and losses, the
    carry's error and every gradient match ``models/xing4_reference.py``."""
    import test_xing4 as small

    t = 128
    monkeypatch.setattr(small, "T", t)
    monkeypatch.setattr(small, "BATCH", 1)
    cfg = dict(small.SHARE, hidden_size=128, max_position_embeddings=t,
               share=dict(small.SHARE["share"], share_rows_bound=t))
    sym = xing4.from_config(cfg, seq_len=t)
    params = small._params(sym, 1)
    tokens, labels = small._batch(2)
    want = ref.forward(params, tokens, cfg, labels=labels)
    loss, grads = ref.loss_and_grads(params, tokens, labels, cfg)

    mod = small._module(sym, params)
    mod.forward(small._data_batch(tokens, labels), is_train=True)
    mod.backward()
    outs = [o.asnumpy() for o in mod.get_outputs()]
    assert lowerings() == {"one_pass": 6, "plain": 0}
    assert lowerings("write") == {"one_pass": 6, "plain": 0}
    small._close(outs[0].mean(), loss, "loss")
    small._close(outs[3].mean(), want["loss_main"], "main loss")
    small._close(outs[4].mean(), want["loss_mtp"], "module's loss")
    small._close(outs[5], [float(want["hc_res_sum_err"])], "hc_res_sum_err",
                 rtol=0.05, ulps=4)
    got = mod._exec_group.execs[0].grad_dict
    assert set(grads) == set(params)
    for name, want_g in grads.items():
        small._close(got[name].asnumpy(), want_g, name, rtol=1e-4, ulps=64)
        if "_hc_" in name:
            assert np.abs(np.asarray(want_g)).max() > 1e-7, name
    nodes = json.loads(sym.tojson())["nodes"]
    assert not [n["name"] for n in nodes if n["name"].endswith("_hc_read")]
