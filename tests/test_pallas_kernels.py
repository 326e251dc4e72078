"""Pallas flash-attention kernel tests (interpret mode on CPU — the same
kernel code that compiles via Mosaic on TPU; the backend-equivalence trick
mirrors the reference's cpu-vs-gpu check_consistency harness,
tests/python/gpu/test_operator_gpu.py)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.ops import kernels as pk
from mxnet_tpu.ops.kernels import common, flash_tiles, reference_attention

# off the TPU an entry's own branch is its plain form: these tests mean
# the kernels, through the Pallas interpreter
flash_attention = functools.partial(pk.flash_attention, interpret=True)
grouped_matmul = functools.partial(pk.grouped_matmul, interpret=True)


CASES = [
    (2, 64, 2, 32, False),
    (1, 100, 3, 16, True),   # non-multiple T exercises padding+masking
    (2, 128, 2, 64, True),
]


@pytest.mark.parametrize("b,t,h,d,causal", CASES)
def test_flash_forward_matches_reference(b, t, h, d, causal):
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("b,t,h,d,causal", CASES[:2])
def test_flash_backward_matches_reference(b, t, h, d, causal):
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=causal, block_q=32, block_k=32
    )
    ref = lambda q, k, v: reference_attention(q, k, v, causal=causal)
    g_f = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    for name, a, b_ in zip("dq dk dv".split(), g_f, g_r):
        rel = float(
            jnp.abs(a - b_).max() / (jnp.abs(b_).max() + 1e-9)
        )
        assert rel < 5e-4, (name, rel)


def test_flash_small_t_fallback_blocks():
    # T smaller than the block size: wrapper shrinks blocks instead of
    # exploding the pad
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(1, 8, 1, 8), jnp.float32)
    out = flash_attention(q, q, q, causal=False)
    ref = reference_attention(q, q, q, causal=False)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
    )


def test_transformer_uses_flash_shapes_consistent():
    # the model path that selects flash on TPU falls back to jnp here (CPU)
    # — this asserts the two paths agree through the full model interface
    from mxnet_tpu.models.transformer import transformer_lm

    init_fn, apply_fn = transformer_lm(
        vocab=50, d_model=32, n_layers=1, n_heads=2, dtype=jnp.float32,
    )
    params = init_fn(seed=0)
    toks = np.random.RandomState(1).randint(0, 50, (2, 16))
    logits = apply_fn(params, jnp.asarray(toks))
    assert logits.shape == (2, 16, 50)


def test_transformer_flash_branch_matches_reference(monkeypatch):
    # send the model's flash branch through the Pallas interpreter (the
    # kernel layer's one test seam) and check it agrees with the plain
    # branch a CPU step runs — this executes
    # the actual flash_attention call site in the transformer, so a
    # swapped q/k/v argument or wrong keyword there fails here, not on
    # hardware
    from mxnet_tpu.models.transformer import transformer_lm

    init_fn, apply_fn = transformer_lm(
        vocab=50, d_model=32, n_layers=1, n_heads=2, dtype=jnp.float32,
    )
    params = init_fn(seed=0)
    # T 128: the shortest sequence ``attention`` sends to flash_attention
    toks = jnp.asarray(np.random.RandomState(1).randint(0, 50, (2, 128)))
    ref_logits = apply_fn(params, toks)
    monkeypatch.setattr(common, "INTERPRET", True)
    flash_logits = apply_fn(params, toks)
    np.testing.assert_allclose(
        np.asarray(flash_logits), np.asarray(ref_logits),
        rtol=2e-4, atol=2e-4,
    )


# ---------------------------------------------------------------------------
# bf16 operands, large tiles, causal tiles that cost what they hold
# ---------------------------------------------------------------------------

def _bf16_inputs(seed, b, t, h, d):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(b, t, h, d), jnp.bfloat16)
                 for _ in range(3))


def _loss(fn):
    return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)


def _rel(a, b):
    a = jnp.asarray(a, jnp.float32)
    return float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-9))


# (b, t, h, d, causal, block_q, block_k): T = 1024 spans several large
# tiles, so dead steps (clamped index maps), diagonal tiles (masked body)
# and tiles below the diagonal (unmasked body) all run; T = 600 and 200
# leave padding inside a large tile; None takes the chosen tiles.
BF16_CASES = [
    (1, 1024, 1, 128, True, 256, 512),
    (1, 1024, 1, 128, True, 512, 256),
    (1, 1024, 1, 128, False, 256, 512),
    (1, 600, 2, 64, True, 256, 256),
    (1, 600, 2, 64, False, 256, 512),
    (2, 200, 2, 32, True, None, None),
]
# stated tolerance, relative to the largest reference value: the output
# is rounded to bf16 (2^-9 = 2.0e-3 half an ulp), and p / dS are rounded
# to bf16 before the four products that consume them
BF16_FWD_TOL = 1e-2
BF16_BWD_TOL = 2e-2


@pytest.mark.parametrize("b,t,h,d,causal,bq,bk", BF16_CASES)
def test_flash_bf16_forward_matches_f32_reference(b, t, h, d, causal, bq,
                                                  bk):
    q, k, v = _bf16_inputs(3, b, t, h, d)
    out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk)
    assert out.dtype == jnp.bfloat16
    ref = reference_attention(*(x.astype(jnp.float32) for x in (q, k, v)),
                              causal=causal)
    assert _rel(out, ref) < BF16_FWD_TOL


@pytest.mark.parametrize("b,t,h,d,causal,bq,bk", BF16_CASES)
def test_flash_bf16_backward_matches_f32_reference(b, t, h, d, causal, bq,
                                                   bk):
    q, k, v = _bf16_inputs(4, b, t, h, d)
    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=causal, block_q=bq, block_k=bk)
    ref = lambda q, k, v: reference_attention(q, k, v, causal=causal)
    g_f = jax.grad(_loss(flash), argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(_loss(ref), argnums=(0, 1, 2))(
        *(x.astype(jnp.float32) for x in (q, k, v)))
    for name, a, r in zip("dq dk dv".split(), g_f, g_r):
        assert a.dtype == jnp.bfloat16
        assert _rel(a, r) < BF16_BWD_TOL, (name, _rel(a, r))


# -- the selected pair: Attention's keep-mask ---------------------------------

def _select_case(seed, t, heads, kv_heads, d, dv, dtype, topk):
    """q, k, v, an int8 keep-mask of ``topk`` keys a row (fewer above the
    diagonal's reach) whose late rows keep NO key of the first quarter of
    the keys: their first live tile holds none of theirs."""
    rng = np.random.RandomState(seed)
    draw = lambda *s: jnp.asarray(rng.randn(*s), dtype)
    scores = rng.randn(1, t, t)
    causal = np.tril(np.ones((t, t), bool))
    scores = np.where(causal[None], scores, -np.inf)
    kth = np.sort(scores, axis=-1)[..., -topk][..., None]
    keep = (scores >= kth) & causal[None]
    keep[:, t // 2:, : t // 4] = False
    return (draw(1, t, heads, d), draw(1, t, kv_heads, d),
            draw(1, t, kv_heads, dv), jnp.asarray(keep, jnp.int8),
            jnp.asarray(rng.randn(1, t, heads, dv), jnp.float32))


SELECT_CASES = {
    # 8 query heads on ONE key/value head of 128, whole tiles of 256
    "8_on_1": (256, 8, 1, 128, 128, jnp.float32, 48, 1e-5),
    # T no multiple of the tile (200 -> 256: padded rows and keys the
    # mask drops), 4 on 1 twice over, a value narrower than the key
    "padded": (200, 8, 2, 64, 32, jnp.float32, 48, 1e-5),
    # bf16 operands on tiles of 512: p and dS round to 2^-9 once each
    "bf16": (512, 16, 2, 128, 128, jnp.bfloat16, 100, 2.0 ** -6),
}


@pytest.mark.parametrize("case", sorted(SELECT_CASES))
def test_the_selected_pair_matches_kept_attention(case):
    """``flash_select`` through the interpreter against the materialised
    ``kept_attention`` (what ``Attention(keep=)`` runs where the pair has
    no tiles): the output's weighted sum and dq, dk, dv. A q tile holds a
    group's heads one under another, so dk and dv here are sums over the
    group made inside the kernel's products."""
    t, heads, kv_heads, d, dv, dtype, topk, tol = SELECT_CASES[case]
    q, k, v, keep, w = _select_case(3, t, heads, kv_heads, d, dv, dtype,
                                    topk)
    assert pk.flash_select_takes(t, heads, kv_heads, d, dv, dtype)

    def run(fn):
        return jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w),
            argnums=(0, 1, 2))(q, k, v)

    got = run(lambda q, k, v: pk.flash_select(q, k, v, keep,
                                              interpret=True))
    want = run(lambda q, k, v: pk.kept_attention(q, k, v, keep, d ** -0.5))
    plain = run(lambda q, k, v: pk.flash_select(q, k, v, keep))
    for g, p, x in zip(*(jax.tree_util.tree_leaves(r)
                         for r in (got, plain, want))):
        g, p, x = (np.asarray(a, np.float32) for a in (g, p, x))
        scale = max(np.abs(x).max(), 1.0)
        assert np.abs(g - x).max() <= tol * scale, np.abs(g - x).max()
        # off the TPU the entry IS kept_attention on re-ordered rows
        assert np.abs(p - x).max() <= 1e-5 * scale


# What a tile step masks and which columns of a q tile's diagonal k tile it
# computes (PR 77). T 1,024 in float32 is ONE k tile of 1,024 keys under q
# tiles of 1,024 / group positions: a q tile at every position of its
# diagonal tile; T 2,000 pads to two k tiles: an interior tile, which takes
# the keep-mask alone, before the diagonal one.
# name: (t, heads, kv_heads, dtype, SELECT_EDGE, (rows, edge) the traces
# must say, what ``_mask_case`` does to the mask, tolerance)
MASK_CASES = {
    "edge128": (1024, 8, 1, jnp.float32, 128, (128, 128), None, 2e-5),
    "edge256": (1024, 8, 1, jnp.float32, 256, (128, 256), None, 2e-5),
    "edge512": (1024, 8, 1, jnp.float32, 512, (128, 512), None, 2e-5),
    "bf16_edge256": (1024, 8, 1, jnp.bfloat16, 256, (128, 256), None,
                     2.0 ** -6),
    # four heads a group: a q tile's 256 positions are one column block
    "group4": (1024, 8, 2, jnp.float32, 256, (256, 256), None, 2e-5),
    # no group: square tiles, nothing lopsided, the tile whole
    "group1": (1024, 2, 2, jnp.float32, 256, (1024, 0), None, 2e-5),
    # T no multiple of a tile: padded rows and keys the mask drops
    "padded": (2000, 8, 1, jnp.float32, 256, (128, 256), None, 2e-5),
    "no_key_rows": (1024, 8, 1, jnp.float32, 256, (128, 256), "empty_rows",
                    2e-5),
    # the finite floor's case: every earlier tile of the row all dropped
    "first_key_in_last_tile": (2000, 8, 1, jnp.float32, 256, (128, 256),
                               "late_first_key", 2e-5),
}


def _mask_case(keep, how):
    keep = np.array(keep)
    t = keep.shape[1]
    if how == "empty_rows":
        keep[:, 300:340] = 0
        keep[:, 0] = 0
    elif how == "late_first_key":
        # the second k tile's rows keep nothing of the first k tile, and
        # some of them only their own position
        keep[:, 1024:, :1024] = 0
        keep[:, 1500:1600] = np.eye(t, dtype=keep.dtype)[1500:1600]
    return jnp.asarray(keep)


@pytest.mark.parametrize("case", sorted(MASK_CASES))
def test_a_tile_step_masks_at_the_masks_size_and_cuts_the_diagonal(
        case, monkeypatch):
    """``flash_select`` through the interpreter against ``kept_attention``,
    output and dq, dk, dv, where a tile step's mask and columns differ: q
    tiles at every position ``i % (block_k / rows)`` of their diagonal k
    tile at each width of its column blocks (``select_edge`` under another
    ``SELECT_EDGE``; ``attention.select_kernel_traces`` says which width
    each kernel was traced at: the backward's, the forward's tile whole),
    groups of 1, 4 and 8, bf16 operands, a
    padded T, rows that keep no key at all, and rows whose first kept key
    lies in their last k tile."""
    t, heads, kv_heads, dtype, min_edge, said, how, tol = MASK_CASES[case]
    d = 64
    q, k, v, keep, w = _select_case(11, t, heads, kv_heads, d, d, dtype, 96)
    keep = _mask_case(keep, how)
    monkeypatch.setattr(pk.flash, "SELECT_EDGE", min_edge)
    rows, edge = said
    group = heads // kv_heads
    assert pk.flash.select_tiles(t, group, d, d, dtype)[:2] == (rows, 1024)
    assert pk.flash.select_edge("bwd", rows, 1024) == edge
    assert pk.flash.select_edge("fwd", rows, 1024) == 0

    def run(fn):
        def loss(*a):
            out = fn(*a).astype(jnp.float32)
            return jnp.sum(out * w), out
        (_, out), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return (out,) + grads

    for jitted in (pk.flash.select_fwd_call, pk.flash.select_bwd_call):
        jitted.clear_cache()    # another case's trace is not this one's
    telemetry.reset()
    telemetry.enable()
    try:
        got = run(lambda q, k, v: pk.flash_select(q, k, v, keep,
                                                  interpret=True))
        traces = telemetry.REGISTRY.get("attention.select_kernel_traces")
        assert [traces.value(**{"pass": which}, group=group, rows=rows,
                             edge=by)
                for which, by in (("fwd", 0), ("bwd", edge))] == [1, 1]
        assert telemetry.total("attention.select_kernel_traces") == 2
    finally:
        telemetry.disable()
        telemetry.reset()
    want = run(lambda q, k, v: pk.kept_attention(q, k, v, keep, d ** -0.5))
    for g, x in zip(got, want):
        g, x = (np.asarray(a, np.float32) for a in (g, x))
        assert np.isfinite(g).all()
        assert np.abs(g - x).max() <= tol * max(np.abs(x).max(), 1.0), \
            np.abs(g - x).max()
    if how == "empty_rows":
        out, dq = got[:2]
        assert not np.asarray(out[:, 300:340], np.float32).any()
        assert not np.asarray(dq[:, 300:340], np.float32).any()


# The backward that keeps dK / dV a range of keys at a time (PR 79), at
# sizes where the one-pass backward exists too: ``VMEM_RAISED_LIMIT`` is
# lowered so that ``select_range`` finds ``keys`` resident and no more.
# name: (t, heads, kv_heads, dtype, keys a range, tolerance: the one-pass
# backward's, ``MASK_CASES``)
RANGE_CASES = {
    # three ranges of one k tile; q tiles before a range write zeros
    "three_ranges": (3000, 8, 1, jnp.float32, 1024, 2e-5),
    # two k tiles a range: the walk down from the diagonal inside a range
    "two_tiles_a_range": (4096, 4, 1, jnp.float32, 2048, 2e-5),
    # the MiniCPM-SALA cell's group: 64 positions of 16 heads a q tile
    "bf16_group16": (2048, 16, 1, jnp.bfloat16, 1024, 2.0 ** -6),
}


@pytest.mark.parametrize("case", sorted(RANGE_CASES))
def test_the_ranged_backward_matches_kept_attention(case, monkeypatch):
    """``flash_select`` through the interpreter where a key/value head's dK
    / dV do not fit VMEM whole: dq (the ranges' float32 partial sums), dk
    and dv against ``kept_attention`` at the one-pass backward's tolerance,
    and against the one-pass backward itself on the same operands."""
    t, heads, kv_heads, dtype, keys, tol = RANGE_CASES[case]
    d, group = 64, heads // kv_heads
    q, k, v, keep, w = _select_case(17, t, heads, kv_heads, d, d, dtype, 96)
    rows, block_k, t_pad = pk.flash.select_tiles(t, group, d, d, dtype)
    assert pk.flash.select_range(t_pad, rows, group, block_k, d, d,
                                 dtype) == t_pad

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w),
                        argnums=(0, 1, 2))(q, k, v)

    for jitted in (pk.flash.select_bwd_call, pk.flash.select_bwd_range_call):
        jitted.clear_cache()
    one_pass = grads(lambda q, k, v: pk.flash_select(q, k, v, keep,
                                                     interpret=True))
    itemsize = jnp.dtype(dtype).itemsize
    monkeypatch.setattr(
        pk.flash, "VMEM_RAISED_LIMIT", pk.flash.select_range_vmem_bytes(
            keys, rows, group, block_k, d, d, itemsize))
    assert pk.flash.select_tiles(t, group, d, d, dtype) == (rows, block_k,
                                                            t_pad)
    assert pk.flash.select_range(t_pad, rows, group, block_k, d, d,
                                 dtype) == keys
    telemetry.reset()
    telemetry.enable()
    try:
        got = grads(lambda q, k, v: pk.flash_select(q, k, v, keep,
                                                    interpret=True))
        traces = telemetry.REGISTRY.get("attention.select_kernel_traces")
        assert traces.value(
            **{"pass": "bwd_ranges"}, group=group, rows=rows, range=keys,
            edge=pk.flash.select_edge("bwd", rows, block_k)) == 1
        assert traces.value(
            **{"pass": "bwd"}, group=group, rows=rows,
            edge=pk.flash.select_edge("bwd", rows, block_k)) == 0
    finally:
        telemetry.disable()
        telemetry.reset()
    want = grads(lambda q, k, v: pk.kept_attention(q, k, v, keep, d ** -0.5))
    for g, o, x in zip(got, one_pass, want):
        g, o, x = (np.asarray(a, np.float32) for a in (g, o, x))
        assert np.isfinite(g).all()
        scale = max(np.abs(x).max(), 1.0)
        assert np.abs(g - x).max() <= tol * scale, np.abs(g - x).max()
        assert np.abs(g - o).max() <= tol * scale, np.abs(g - o).max()


def _inner_jaxprs(jaxpr):
    """``jaxpr`` and every jaxpr inside its equations' parameters."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (tuple, list))
                        else (param,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _inner_jaxprs(sub)


@pytest.mark.parametrize("which", ["fwd", "bwd"])
def test_the_selected_bodies_build_nothing_integer_of_a_tiles_size(which):
    """The traced body of each selected kernel at the Keye cell's tiles (a
    q tile of 8 x 128 rows against 1,024 keys, column blocks of 256): no
    integer or bool value larger than the keep-mask's own [rows, block_k]
    tile (the mask widened over the group's rows cannot come back unseen),
    and the diagonal tile's four widths are four products."""
    t, g, group, rows, block_k, d = 2048, 1, 8, 128, 1024, 128

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype)

    q3, k3, row = shape(g, group * t, d), shape(g, t, d), shape(
        g, group * t, 1, dtype=jnp.float32)
    kw = dict(rows=rows, group=group, block_k=block_k, scale=d ** -0.5,
              edge=256, interpret=False)
    call, args = {
        "fwd": (pk.flash.select_fwd_call,
                (q3, k3, k3, shape(1, t, t, dtype=jnp.int8))),
        "bwd": (pk.flash.select_bwd_call,
                (q3, k3, k3, shape(1, t, t, dtype=jnp.int8), q3, row, row)),
    }[which]
    kernel, = [eqn.params["jaxpr"]
               for jaxpr in _inner_jaxprs(
                   jax.make_jaxpr(lambda *a: call(*a, **kw))(*args).jaxpr)
               for eqn in jaxpr.eqns if eqn.primitive.name == "pallas_call"]
    widths = set()
    for jaxpr in _inner_jaxprs(kernel):
        for eqn in jaxpr.eqns:
            for var in eqn.outvars:
                aval = var.aval
                if not hasattr(aval, "shape"):   # a ref
                    continue
                if not jnp.issubdtype(aval.dtype, jnp.floating):
                    assert np.prod(aval.shape) <= rows * block_k, eqn
                if (eqn.primitive.name == "dot_general"
                        and aval.shape[0] == group * rows):
                    widths.add(aval.shape[1])
    # [block_q, width] scores (and dP) and the [block_q, d] products
    assert widths == {256, 512, 768, 1024, d}


def test_a_row_that_keeps_nothing_reads_zeros_and_moves_nothing():
    """Rows whose mask is empty (and the padding rows are such rows):
    zeros out, a zero dq, and nothing added to dk or dv."""
    t, heads, d = 256, 8, 128
    q, k, v, keep, w = _select_case(5, t, heads, 1, d, d, jnp.float32, 32)
    keep = keep.at[:, 100:140].set(0)

    def loss(q, k, v):
        return jnp.sum(pk.flash_select(q, k, v, keep, interpret=True) * w)

    out = pk.flash_select(q, k, v, keep, interpret=True)
    assert not np.asarray(out[:, 100:140]).any()
    assert np.isfinite(np.asarray(out)).all()
    dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    assert not np.asarray(dq[:, 100:140]).any()
    for g in (dq, dk, dv):
        assert np.isfinite(np.asarray(g)).all()


@pytest.mark.parametrize("t,heads,kv_heads,d,dtype,takes", [
    (8192, 32, 4, 128, jnp.bfloat16, True),    # the Keye cell
    (4096, 16, 16, 128, jnp.bfloat16, True),   # no group: a 1 MB mask tile
    (8192, 32, 4, 128, jnp.float16, False),    # no type Mosaic takes
    (100, 8, 1, 128, jnp.float32, False),      # shorter than a tile
    (384, 16, 2, 128, jnp.float32, False),     # 16 rows a head: no int8 tile
    (32768, 32, 4, 128, jnp.bfloat16, True),   # dK / dV by ranges of keys
    (16384, 16, 1, 128, jnp.bfloat16, True),   # the MiniCPM-SALA cell
])
def test_the_selected_pair_takes_what_the_shapes_say(t, heads, kv_heads, d,
                                                     dtype, takes):
    assert pk.flash_select_takes(t, heads, kv_heads, d, d, dtype) is takes
    if not takes:
        q = jnp.zeros((1, t, heads, d), dtype)
        kv = jnp.zeros((1, t, kv_heads, d), dtype)
        with pytest.raises(ValueError, match="flash_select_takes"):
            jax.eval_shape(lambda: pk.flash_select(
                q, kv, kv, jnp.zeros((1, t, t), jnp.int8)))


def test_the_vmem_count_holds_the_keep_masks_tile():
    """``flash_vmem_bytes(select_rows=)``: two int8 buffers of [rows,
    block_k] and the tile's float32 bias, nothing of the group's size."""
    base = pk.flash.flash_vmem_bytes(1024, 1024, 128, 2,
                                     resident=(8192, 128, 128))
    got = pk.flash.flash_vmem_bytes(1024, 1024, 128, 2,
                                    resident=(8192, 128, 128),
                                    select_rows=128)
    assert got - base == (2 + 4) * 128 * 1024
    assert pk.flash.flash_vmem_bytes(1024, 1024, 128, 2) == \
        pk.flash.flash_vmem_bytes(1024, 1024, 128, 2, select_rows=0)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 1e-2)])
def test_flash_default_tiles_match_explicit_128(dtype, tol):
    # the chosen tiles against an explicit 128 x 128 on the same inputs,
    # forward and the three gradients
    t, d = 768, 64
    assert flash_tiles(t, d, dtype) != (128, 128)
    rng = np.random.RandomState(5)
    q, k, v = (jnp.asarray(rng.randn(1, t, 2, d), dtype) for _ in range(3))
    auto = lambda q, k, v: flash_attention(q, k, v, causal=True)
    pinned = lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=128, block_k=128)
    assert _rel(auto(q, k, v), pinned(q, k, v).astype(jnp.float32)) < tol
    g_a = jax.grad(_loss(auto), argnums=(0, 1, 2))(q, k, v)
    g_p = jax.grad(_loss(pinned), argnums=(0, 1, 2))(q, k, v)
    for name, a, p_ in zip("dq dk dv".split(), g_a, g_p):
        assert _rel(a, p_.astype(jnp.float32)) < 5 * tol, name


def _dots_by_kernel(jaxpr):
    """kernel name -> (operand dtypes, result dtype) of every dot_general
    inside a ``pallas_call`` of that name."""
    found = {}

    def walk(jp, kernel):
        for eqn in jp.eqns:
            name = kernel
            if eqn.primitive.name == "pallas_call":
                name = eqn.params["name"]
            if eqn.primitive.name == "dot_general" and kernel:
                found.setdefault(kernel, []).append(
                    (tuple(v.aval.dtype for v in eqn.invars),
                     eqn.outvars[0].aval.dtype))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, name)

    walk(jaxpr.jaxpr, None)
    return found


def _kernel_dots(dtype):
    """``_dots_by_kernel`` of the three kernels of a causal flash call on
    ``dtype`` inputs."""
    q = jnp.zeros((1, 256, 1, 64), dtype)
    fn = lambda q, k, v: flash_attention(q, k, v, causal=True)
    return _dots_by_kernel(
        jax.make_jaxpr(jax.grad(_loss(fn), argnums=(0, 1, 2)))(q, q, q))


def test_flash_kernels_feed_mxu_operands_as_given():
    bf16 = _kernel_dots(jnp.bfloat16)
    assert sorted(bf16) == ["flash_bwd_bf16_q256_k256",
                            "flash_fwd_bf16_q256_k256"]
    # 2 products a tile pair forward and 5 backward (P and dS are built
    # once), in the masked and the unmasked body; each body traced once
    assert {k: len(v) for k, v in bf16.items()} == {
        "flash_fwd_bf16_q256_k256": 4, "flash_bwd_bf16_q256_k256": 10}
    for dots in bf16.values():
        for operands, result in dots:
            assert operands == (jnp.bfloat16, jnp.bfloat16)
            assert result == jnp.float32
    f32 = _kernel_dots(jnp.float32)
    assert sorted(f32) == ["flash_bwd_f32_q256_k256",
                           "flash_fwd_f32_q256_k256"]
    for dots in f32.values():
        for operands, result in dots:
            assert operands == (jnp.float32, jnp.float32)
            assert result == jnp.float32


@pytest.mark.parametrize("t,d,dtype", [
    (4096, 128, jnp.bfloat16), (8192, 128, jnp.bfloat16),
    (2048, 64, jnp.float32), (100, 16, jnp.float32), (8, 8, jnp.float32),
    (2176, 128, jnp.bfloat16), (4096, 256, jnp.float32),
])
def test_flash_tiles_divide_and_fit(t, d, dtype):
    bq, bk = flash_tiles(t, d, dtype)
    for blk in (bq, bk):
        assert blk >= 8 and blk & (blk - 1) == 0
    t_pad = -(-t // max(bq, bk)) * max(bq, bk)
    assert t_pad % bq == 0 and t_pad % bk == 0
    if t >= 128:
        assert min(bq, bk) >= 128
        # no more than an eighth over what 128-wide tiles would pad to
        assert t_pad * 8 <= -(-t // 128) * 128 * 9
    else:
        assert bq == bk and t <= bq < 2 * max(t, 8)
    assert pk.flash.flash_vmem_bytes(
        bq, bk, d, jnp.dtype(dtype).itemsize) <= pk.common.VMEM_SCOPED_DEFAULT


def test_flash_tiles_at_the_cell_shape_are_large():
    # OLMoE's attention: 16,384 grid steps a call at 128 x 128
    bq, bk = flash_tiles(4096, 128, jnp.bfloat16)
    assert bq >= 256 and bk >= 512
    assert (4096 // bq) * (4096 // bk) <= 1024


def test_flash_tiles_under_a_window_wider_than_the_cap():
    # Trinity-Mini's window of 2,048 at T 8,192: twice the window is past
    # the measured cap, so the band of a q tile is three k tiles wide
    assert flash_tiles(8192, 128, jnp.bfloat16, window=2048) == (1024, 1024)
    assert pk.flash._band_steps(8, 8, 1024, 1024, 2048, "k") == 3
    assert pk.flash._band_steps(8, 8, 1024, 1024, 2048, "q") == 3


def test_flash_lowerings_counter_counts_one_per_lowering():
    telemetry.reset()
    telemetry.enable()
    try:
        q = jnp.zeros((1, 256, 1, 64), jnp.bfloat16)
        step = jax.jit(jax.grad(_loss(
            lambda q, k, v: flash_attention(q, k, v, causal=True)),
            argnums=(0, 1, 2)))
        step(q, q, q)
        step(q, q, q)  # a second step of one lowering counts nothing
        jax.jit(lambda q: flash_attention(
            q, q, q, block_q=128, block_k=128))(q.astype(jnp.float32))
        c = telemetry.REGISTRY.get("attention.flash_lowerings")
        assert c.value(operands="bf16", block_q=256, block_k=256) == 1
        assert c.value(operands="f32", block_q=128, block_k=128) == 1
        # the differentiated site counts once more, under its backward's
        # form; the forward-only site has no backward to count
        assert c.value(operands="bf16", block_q=256, block_k=256,
                       window=0, bwd="fused") == 1
        assert telemetry.total("attention.flash_lowerings") == 3
    finally:
        telemetry.disable()
        telemetry.reset()


# ---------------------------------------------------------------------------
# the backward as one pass (dq, dk, dv from one P and dS a tile pair)
# against the split form (dq and dkv) and the reference's gradients
# ---------------------------------------------------------------------------

# (b, t, heads, kv heads, d, dv, causal, window, sink, block_q, block_k,
# dtype): causal and not; T no multiple of the tile (padding keys);
# several tiles a side, square and not; grouped heads (dK / dV sum over
# the group in the resident buffer); a value width of its own; a window
# with a sink (the band's inner steps); bf16 and float32
FUSED_BWD_CASES = {
    "causal_several_tiles": (1, 256, 2, 2, 32, 32, True, 0, False, 64, 64,
                             jnp.float32),
    "full_padded_keys": (1, 200, 2, 2, 32, 32, False, 0, False, 64, 32,
                         jnp.float32),
    "causal_padded_wide_k_tile": (2, 300, 2, 2, 32, 32, True, 0, False,
                                  32, 64, jnp.float32),
    "grouped_heads": (2, 300, 4, 2, 32, 32, True, 0, False, 64, 64,
                      jnp.float32),
    "grouped_heads_full": (1, 192, 6, 2, 16, 16, False, 0, False, 64, 64,
                           jnp.float32),
    "value_width_of_its_own": (1, 256, 2, 2, 48, 32, True, 0, False, 64,
                               64, jnp.float32),
    "window_with_sink": (1, 512, 4, 1, 32, 16, True, 48, True, 64, 64,
                         jnp.float32),
    "window_wider_than_a_tile": (1, 400, 2, 2, 32, 32, True, 100, True,
                                 64, 64, jnp.float32),
    "bf16_causal": (1, 512, 2, 2, 64, 64, True, 0, False, 128, 128,
                    jnp.bfloat16),
    "bf16_grouped_window_sink": (1, 512, 4, 1, 48, 32, True, 96, True,
                                 128, 128, jnp.bfloat16),
    "bf16_padded_full": (2, 200, 2, 1, 32, 32, False, 0, False, 64, 128,
                         jnp.bfloat16),
    # the Trinity-Mini cell's window call scaled down (8 query heads on
    # 1, no sink, a window of two tiles as 2,048 is of 1,024): the band
    # of a q tile crosses three k tiles, one wholly inside; a window of a
    # tile and a half; T a multiple of the tile and not
    "window_of_two_tiles_8_on_1": (1, 512, 8, 1, 32, 32, True, 128, False,
                                   64, 64, jnp.float32),
    "window_of_two_tiles_padded": (1, 450, 8, 1, 32, 32, True, 128, False,
                                   64, 64, jnp.float32),
    "window_of_a_tile_and_a_half": (1, 448, 8, 1, 32, 32, True, 96, False,
                                    64, 64, jnp.float32),
    "bf16_window_of_two_tiles_8_on_1": (1, 768, 8, 1, 64, 64, True, 256,
                                        False, 128, 128, jnp.bfloat16),
    "bf16_window_of_a_tile_and_a_half": (1, 700, 8, 1, 64, 64, True, 192,
                                         False, 128, 128, jnp.bfloat16),
}


def _fused_case(name):
    (b, t, h, g, d, dv, causal, window, sink, bq, bk,
     dtype) = FUSED_BWD_CASES[name]
    rng = np.random.RandomState(sorted(FUSED_BWD_CASES).index(name))
    q, k, v, do = (jnp.asarray(rng.randn(*shape), dtype) for shape in (
        (b, t, h, d), (b, t, g, d), (b, t, g, dv), (b, t, h, dv)))
    sink = jnp.asarray(rng.randn(h), jnp.float32) if sink else None
    kw = dict(causal=causal, window=window, block_q=bq, block_k=bk)
    return (q, k, v, do, sink), kw


@pytest.mark.parametrize("name", sorted(FUSED_BWD_CASES))
def test_fused_backward_matches_the_split_form(name, monkeypatch):
    (q, k, v, do, sink), kw = _fused_case(name)

    def grads(fuses):
        monkeypatch.setattr(pk.flash, "bwd_fuses", lambda *a: fuses)
        _, vjp = jax.vjp(
            lambda q, k, v: flash_attention(q, k, v, sink=sink, **kw),
            q, k, v)
        return vjp(do)

    # the same products on the same operands: only dq's sum over k tiles
    # runs in another order (float32 accumulation noise, then one
    # rounding to the operand type)
    tol = 1e-6 if q.dtype == jnp.float32 else 1e-2
    for which, a, r in zip("dq dk dv".split(), grads(True), grads(False)):
        assert a.dtype == r.dtype == q.dtype and a.shape == r.shape
        assert _rel(a, r.astype(jnp.float32)) < tol, which


@pytest.mark.parametrize("name", sorted(FUSED_BWD_CASES))
def test_fused_backward_matches_the_reference_gradients(name):
    (q, k, v, do, sink), kw = _fused_case(name)
    assert pk.flash.bwd_fuses(q.shape[1], kw["block_q"], kw["block_k"],
                         q.shape[3], v.shape[3], q.dtype)
    extra = () if sink is None else (sink,)

    def grads(fn, q, k, v, **kw):
        _, vjp = jax.vjp(lambda q, k, v, *s: fn(
            q, k, v, sink=s[0] if s else None, **kw), q, k, v, *extra)
        return vjp(do.astype(q.dtype))

    got = grads(flash_attention, q, k, v, **kw)
    want = grads(reference_attention,
                 *(x.astype(jnp.float32) for x in (q, k, v)),
                 causal=kw["causal"], window=kw["window"])
    tol = 5e-4 if q.dtype == jnp.float32 else BF16_BWD_TOL
    for which, a, r in zip("dq dk dv dsink".split(), got, want):
        assert _rel(a, r) < tol, (which, _rel(a, r))


# the benchmark's attention calls (T, d, dv, window), bf16: Kanana's
# latent attention, OLMoE's, MiMo's full and window layers; then what the
# resident dK / dV cannot hold
@pytest.mark.parametrize("t,d,dv,window,dtype,fuses", [
    (8192, 192, 128, 0, jnp.bfloat16, True),
    (4096, 128, 128, 0, jnp.bfloat16, True),
    (4096, 192, 128, 0, jnp.bfloat16, True),
    (4096, 192, 128, 128, jnp.bfloat16, True),
    (8192, 128, 128, 2048, jnp.bfloat16, True),   # Trinity-Mini's window
    (8192, 128, 128, 0, jnp.bfloat16, True),      # and its full layer
    (32768, 192, 128, 0, jnp.bfloat16, False),
    (16384, 192, 128, 0, jnp.bfloat16, False),
    (16384, 128, 128, 0, jnp.float32, False),
])
def test_backward_form_follows_the_shapes(t, d, dv, window, dtype, fuses):
    bq, bk = flash_tiles(t, max(d, dv), dtype, window)
    assert pk.flash.bwd_fuses(t, bq, bk, d, dv, dtype) is fuses
    counted = pk.flash.flash_vmem_bytes(
        bq, bk, max(d, dv), jnp.dtype(dtype).itemsize, resident=(t, d, dv))
    assert (counted <= pk.common.VMEM_RAISED_LIMIT) is fuses
    # the resident part alone: dK and dV in float32 and their two
    # output buffers, lane-padded
    assert counted - pk.flash.flash_vmem_bytes(
        bq, bk, max(d, dv), jnp.dtype(dtype).itemsize) >= t * (
            d + dv) * (4 + 2 * jnp.dtype(dtype).itemsize)


def test_split_backward_is_taken_where_the_rule_says(monkeypatch):
    # a sequence the rule sends to dq and dkv, scaled down: the limit is
    # lowered under a small call's count and the two kernels are traced
    monkeypatch.setattr(pk.flash, "VMEM_RAISED_LIMIT", 1024)
    telemetry.reset()
    telemetry.enable()
    try:
        q = jnp.zeros((1, 256, 1, 64), jnp.float32)
        fn = lambda q, k, v: flash_attention(q, k, v, causal=True)
        dots = _dots_by_kernel(jax.make_jaxpr(
            jax.grad(_loss(fn), argnums=(0, 1, 2)))(q, q, q))
        assert sorted(dots) == ["flash_dkv_f32_q256_k256",
                                "flash_dq_f32_q256_k256",
                                "flash_fwd_f32_q256_k256"]
        # 3 / 4 products a tile pair in dq / dkv: seven where five do
        assert len(dots["flash_dq_f32_q256_k256"]) == 6
        assert len(dots["flash_dkv_f32_q256_k256"]) == 8
        c = telemetry.REGISTRY.get("attention.flash_lowerings")
        assert c.value(operands="f32", block_q=256, block_k=256, window=0,
                       bwd="split") == 1
    finally:
        telemetry.disable()
        telemetry.reset()


# ---------------------------------------------------------------------------
# cut tiles by quarters: a square tile that the diagonal or the window's
# edge cuts corner to corner runs its live quarters (``cut_steps``)
# ---------------------------------------------------------------------------

# (t, heads, kv heads, d, window) at tiles of 512, scaled down from the
# cells' 1,024 (``_cut_case`` lowers the floor to quarters of 256 for
# the test): the diagonal alone; a window of two tiles (Trinity-Mini's: the
# band's lower edge tile is the diagonal's mirror), of one tile (both of
# a band's tiles cut) and of half a tile (three cut quarters on the
# diagonal, one under it); four query heads on one key/value head; a
# last tile that holds padding keys and falls back to the whole mask
CUT_CASES = {
    "full_causal": (1024, 2, 2, 32, 0),
    "window_of_two_tiles": (2048, 1, 1, 32, 1024),
    "window_of_a_tile": (1536, 1, 1, 32, 512),
    "window_of_half_a_tile": (1536, 1, 1, 32, 256),
    "grouped_heads": (1024, 4, 1, 32, 0),
    "grouped_heads_window_padded": (1300, 4, 2, 32, 512),
    "padded_last_tile": (1300, 1, 1, 32, 0),
}


def _cut_case(name, monkeypatch):
    monkeypatch.setattr(pk.flash, "FLASH_MIN_EDGE", 256)
    t, h, g, d, window = CUT_CASES[name]
    rng = np.random.RandomState(sorted(CUT_CASES).index(name))
    q, k, v, do = (jnp.asarray(rng.randn(*shape), jnp.float32)
                   for shape in ((1, t, h, d), (1, t, g, d), (1, t, g, d),
                                 (1, t, h, d)))
    kw = dict(causal=True, window=window, block_q=512, block_k=512)
    assert pk.flash.cut_half(512, 512, True, window) == 256
    return (q, k, v, do), kw


def _kernel_names(jaxpr):
    return sorted(_dots_by_kernel(jaxpr))


@pytest.mark.parametrize("name", sorted(CUT_CASES))
def test_cut_tiles_forward_matches_the_reference_and_the_whole_form(
        name, monkeypatch):
    (q, k, v, _), kw = _cut_case(name, monkeypatch)
    fn = lambda q, k, v: flash_attention(q, k, v, **kw)
    whole = lambda q, k, v: flash_attention(q, k, v, **kw)
    window = kw["window"]
    assert _kernel_names(jax.make_jaxpr(fn)(q, k, v)) == [
        "flash_fwd_f32_q512_k512%s_e256" % ("_w%d" % window if window
                                            else "")]
    got = fn(q, k, v)
    want = reference_attention(q, k, v, causal=True, window=window)
    assert _rel(got, want) < 2e-6
    # the same scores in the same float32 arithmetic: only a row's
    # online-softmax steps differ
    monkeypatch.undo()
    assert pk.flash.FLASH_MIN_EDGE == 512
    assert _kernel_names(jax.make_jaxpr(whole)(q, k, v)) == [
        "flash_fwd_f32_q512_k512" + ("_w%d" % window if window else "")]
    assert _rel(got, whole(q, k, v)) < 2e-6


@pytest.mark.parametrize("fused", [True, False], ids=["one_pass", "split"])
@pytest.mark.parametrize("name", sorted(CUT_CASES))
def test_cut_tiles_backward_matches_the_reference_gradients(
        name, fused, monkeypatch):
    (q, k, v, do), kw = _cut_case(name, monkeypatch)
    monkeypatch.setattr(pk.flash, "bwd_fuses", lambda *a: fused)

    def grads(fn, **kw):
        out, vjp = jax.vjp(lambda q, k, v: fn(q, k, v, **kw), q, k, v)
        return vjp(do)

    names = _kernel_names(jax.make_jaxpr(
        lambda *a: grads(flash_attention, **kw))())
    assert all(n.endswith("_e256") for n in names), names
    assert [n.split("_")[1] for n in names] == (
        ["bwd", "fwd"] if fused else ["dkv", "dq", "fwd"])
    got = grads(flash_attention, **kw)
    want = grads(reference_attention, causal=True, window=kw["window"])
    for which, a, r in zip("dq dk dv".split(), got, want):
        assert a.shape == r.shape
        assert _rel(a, r) < 5e-6, (which, _rel(a, r))


@pytest.mark.parametrize("window", [0, 2048], ids=["full", "window"])
def test_the_cells_tiles_run_quarters_of_512_to_the_reference(window):
    """The floor as it stands: tiles of 1,024 (the cells'), full causal
    and under Trinity-Mini's window of two tiles, forward and the
    one-pass backward."""
    t = 3072 if window else 2048
    rng = np.random.RandomState(t)
    q, k, v, do = (jnp.asarray(rng.randn(1, t, 1, 32), jnp.float32)
                   for _ in range(4))
    assert flash_tiles(8192, 128, jnp.bfloat16, window) == (1024, 1024)

    def grads(fn, **kw):
        out, vjp = jax.vjp(lambda q, k, v: fn(q, k, v, **kw), q, k, v)
        return (out,) + vjp(do)

    kw = dict(causal=True, window=window, block_q=1024, block_k=1024)
    names = _kernel_names(jax.make_jaxpr(
        lambda: grads(flash_attention, **kw))())
    tail = "_f32_q1024_k1024%s_e512" % ("_w2048" if window else "")
    assert names == ["flash_bwd" + tail, "flash_fwd" + tail]
    for which, a, r in zip("out dq dk dv".split(),
                           grads(flash_attention, **kw),
                           grads(reference_attention, causal=True,
                                 window=window)):
        assert _rel(a, r) < 5e-6, (which, _rel(a, r))


@pytest.mark.parametrize("by_rows", [False, True],
                         ids=["quarters", "by_rows"])
@pytest.mark.parametrize("block", [8, 512, 1024])
def test_the_classifier_is_the_keep_mask_on_the_quarters_corners(block,
                                                                 by_rows):
    """``quarter_states`` and the steps ``cut_steps`` makes of them against
    ``_keep``'s [T, T] mask, for every window that is 0 or a multiple of
    half a tile and every tile offset a band can hold: a dead quarter
    holds no live score, a whole one no dead score, the steps' masks are
    the mask on their rectangles and together they cover every live
    score of the tile once; ``by_rows`` (the forward's) a row half's
    live quarters are one step."""
    h = block // 2
    for window in [0] + [n * h for n in range(1, 7)]:
        offsets = range(window // block + 2) if window else range(2)
        t = (max(offsets) + 1) * block
        keep = pk.flash._keep(t, True, window)
        steps = pk.flash.cut_steps(block, window, by_rows)
        for offset in offsets:
            tile = keep[offset * block:(offset + 1) * block, :block]
            states = pk.flash.quarter_states(block, window, offset)
            for a in range(2):
                for b in range(2):
                    quarter = tile[a * h:(a + 1) * h, b * h:(b + 1) * h]
                    assert states[a][b] == (
                        "dead" if not quarter.any() else
                        "whole" if quarter.all() else "cut"), (
                            window, offset, a, b)
            flat = sum(states, [])
            assert (offset in steps) is (0 < flat.count("dead") < 4)
            if offset not in steps:
                continue
            covered = np.zeros_like(tile, dtype=np.int32)
            assert len(steps[offset]) == (
                sum(any(s != "dead" for s in row) for row in states)
                if by_rows else 4 - flat.count("dead"))
            for masked, part in steps[offset]:
                want = tile[part.rows, part.cols]
                assert masked is (not want.all())
                covered[part.rows, part.cols] += 1
                if block > 8 and not masked:
                    continue        # the mask itself: at the toy size
                mask = pk.flash.part_mask(*want.shape, part.delta, window)
                if masked:
                    np.testing.assert_array_equal(np.asarray(mask), want)
                else:
                    assert mask is None
            np.testing.assert_array_equal(covered > 0, tile | (covered > 0))
            assert covered.max() == 1 and (covered[tile] == 1).all()
    assert pk.flash.cut_steps(block, 3 * h).keys() == {0, 2}
    assert pk.flash.cut_steps(block, 2 * block).keys() == {0, 2}


@pytest.mark.parametrize("block_q,block_k,causal,window,half", [
    (1024, 1024, True, 0, 512), (1024, 1024, True, 2048, 512),
    (1024, 1024, True, 1536, 512), (1024, 1024, True, 512, 512),
    (1024, 1024, True, 256, 0), (1024, 1024, True, 1000, 0),
    (512, 512, True, 0, 0), (512, 512, True, 1024, 0),
    (256, 256, True, 128, 0), (1024, 512, True, 0, 0),
    (1024, 1024, False, 0, 0)])
def test_quarters_are_taken_where_the_shapes_say(block_q, block_k, causal,
                                                 window, half):
    assert pk.flash.cut_half(block_q, block_k, causal, window) == half


def _pallas_kernels(jaxpr):
    """(name, kernel jaxpr) of every ``pallas_call`` of a jaxpr, nested
    ones included, as text."""
    found = []

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append((str(eqn.params["name"]),
                              str(eqn.params["jaxpr"])))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    return sorted(found)


def test_tiles_under_the_floor_lower_as_they_did(monkeypatch):
    """MiMo's window call scaled down (tiles of 256 under a window of
    128, 8 query heads on one): halves of 128 are under the floor, so
    the kernels keep their names and their bodies are the ones the
    classifier never touched."""
    q = jnp.zeros((1, 512, 8, 64), jnp.bfloat16)
    k = jnp.zeros((1, 512, 1, 64), jnp.bfloat16)
    assert flash_tiles(4096, 192, jnp.bfloat16, 128) == (256, 256)

    def kernels():
        for call in (pk.flash.fwd_call, pk.flash.bwd_call):
            call.clear_cache()      # one trace a signature: trace anew
        return _pallas_kernels(jax.make_jaxpr(jax.grad(_loss(
            lambda q, k, v: flash_attention(
                q, k, v, causal=True, window=128, block_q=256,
                block_k=256)), argnums=(0, 1, 2)))(q, k, k))

    own = kernels()
    assert [name for name, _ in own] == ["flash_bwd_bf16_q256_k256_w128",
                                         "flash_fwd_bf16_q256_k256_w128"]
    with monkeypatch.context() as m:
        m.setattr(pk.flash, "cut_steps", None)      # never asked
        assert kernels() == own
    # the same call over the floor is another pair of kernels
    monkeypatch.setattr(pk.flash, "FLASH_MIN_EDGE", 128)
    assert [name for name, _ in kernels()] == [
        "flash_bwd_bf16_q256_k256_w128_e128",
        "flash_fwd_bf16_q256_k256_w128_e128"]


def test_a_call_without_padding_traces_no_whole_tile_mask():
    """Where every masked tile is a cut one (whole tiles of keys, a
    window that leaves no offset cut without a dead quarter) the
    whole-tile masked body is not in the kernel: its four iota tables
    are the quarters'; with padding keys, or under a window of a tile
    and a half, it stays."""
    def iotas(t, window):
        q3 = jnp.zeros((1, -(-t // 1024) * 1024, 32), jnp.float32)
        (_, body), = _pallas_kernels(jax.make_jaxpr(
            lambda q3: pk.flash.fwd_call(
                q3, q3, q3, t_real=t, scale=1.0, causal=True,
                window=window, block_q=1024, block_k=1024, interpret=True,
                edge=512))(q3))
        return body.count(" iota[")

    # two position tables a masked step; the forward steps by row halves
    assert iotas(2048, 0) == 4          # the diagonal tile's two steps
    assert iotas(2000, 0) == 6          # ... and the padded tile's mask
    assert iotas(3072, 2048) == 8       # the diagonal's and the edge's
    # offset 1 is cut and none of it dead: three steps and the mask
    assert iotas(3072, 1536) == 6 + 2


def test_flash_lowerings_say_which_sites_run_quarters(monkeypatch):
    monkeypatch.setattr(pk.flash, "FLASH_MIN_EDGE", 256)
    telemetry.reset()
    telemetry.enable()
    try:
        q = jnp.zeros((1, 1024, 1, 32), jnp.float32)
        jax.make_jaxpr(jax.grad(_loss(lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=1024, block_q=512, block_k=512)),
            argnums=(0, 1, 2)))(q, q, q)
        jax.make_jaxpr(lambda q: flash_attention(
            q, q, q, block_q=512, block_k=512))(q)      # not causal
        c = telemetry.REGISTRY.get("attention.flash_lowerings")
        assert c.value(operands="f32", block_q=512, block_k=512,
                       window=1024, kv_heads=1, dv=32, edge=256) == 1
        assert c.value(operands="f32", block_q=512, block_k=512,
                       window=1024, bwd="fused", edge=256) == 1
        assert c.value(operands="f32", block_q=512, block_k=512) == 1
        assert telemetry.total("attention.flash_lowerings") == 3
    finally:
        telemetry.disable()
        telemetry.reset()


# ---------------------------------------------------------------------------
# grouped matmul (the expert layer's products): interpret mode against a
# per-group dense product
# ---------------------------------------------------------------------------

def _dense_grouped(lhs, rhs, sizes):
    """Row r of group g times rhs[g], group by group, in float32."""
    lhs, rhs = lhs.astype(jnp.float32), rhs.astype(jnp.float32)
    ends = np.cumsum(sizes)
    rows = np.arange(lhs.shape[0])
    out = 0
    for g, (start, end) in enumerate(zip(ends - np.asarray(sizes), ends)):
        mine = jnp.asarray((rows >= start) & (rows < end))[:, None]
        out = out + jnp.dot(jnp.where(mine, lhs, 0), rhs[g],
                            precision=jax.lax.Precision.HIGHEST)
    return out


# group sizes over row tiles of 128: uniform and aligned; the OLMoE
# cell's skew scaled down (a few fat groups, many of a handful of rows,
# some of none); one group takes all; empty groups first, last and in a
# run; every boundary inside a tile; m no multiple of the tile
GMM_SIZES = {
    "uniform": [128] * 4,
    "cell_skew": [3, 0, 410, 7, 1, 0, 395, 2, 0, 190, 12, 4],
    "one_takes_all": [0, 0, 384, 0],
    "empty_first_and_last": [0, 0, 200, 0, 0, 56, 0],
    "straddles_tiles": [100, 130, 27, 255],
    "m_no_multiple_of_the_tile": [70, 150, 81],
}


def _gmm_inputs(sizes, k, n, dtype, seed=0):
    rng = np.random.RandomState(seed)
    lhs = jnp.asarray(rng.randn(sum(sizes), k), dtype)
    rhs = jnp.asarray(rng.randn(len(sizes), k, n), dtype)
    return lhs, rhs, jnp.asarray(sizes, jnp.int32)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 1e-2)])
@pytest.mark.parametrize("load", sorted(GMM_SIZES))
def test_grouped_matmul_matches_the_dense_product(load, dtype, tol):
    """Forward and both gradients (and the cotangent's own, through the
    squared loss) against the per-group float32 product. bf16: the output
    and the gradients are rounded to bf16 once (half an ulp 2e-3) from a
    float32 accumulator, the rounding ``ragged_dot`` has."""
    sizes = GMM_SIZES[load]
    lhs, rhs, group_sizes = _gmm_inputs(sizes, 48, 160, dtype)
    assert pk.gmm_runs_kernel(lhs.shape[0], dtype)

    def loss(fn):
        return lambda l, r: jnp.sum(fn(l, r).astype(jnp.float32) ** 2)

    kernel = lambda l, r: grouped_matmul(l, r, group_sizes)
    dense = lambda l, r: _dense_grouped(l, r, sizes)
    out = kernel(lhs, rhs)
    assert out.dtype == dtype and out.shape == (sum(sizes), 160)
    assert _rel(out, dense(lhs, rhs)) < tol
    got = jax.grad(loss(kernel), argnums=(0, 1))(lhs, rhs)
    want = jax.grad(loss(dense), argnums=(0, 1))(
        lhs.astype(jnp.float32), rhs.astype(jnp.float32))
    for name, a, w in zip(("d/dlhs", "d/drhs"), got, want):
        assert a.dtype == dtype
        assert _rel(a, w) < 2 * tol, (name, _rel(a, w))
    # the weight gradient of a group with no rows is exactly zero
    for g, size in enumerate(sizes):
        if size == 0:
            assert not np.asarray(got[1][g], np.float32).any(), g


# (k, n) of the declared weight: 256 x 192 is held transposed (192 is a
# lane row and a half, 256 two), the other two are not (both whole lane
# rows; neither)
HELD_WIDTHS = [(256, 192), (256, 384), (48, 160)]


@pytest.mark.parametrize("k,n", HELD_WIDTHS)
def test_held_transposed_reads_the_shape_alone(k, n):
    assert pk.held_transposed((4, k, n)) == ((k, n) == (256, 192))
    # the swap of a held-transposed weight is a declared one
    assert not pk.held_transposed((4, 192, 256))
    assert pk.held_transposed((16, 2688, 1856))
    assert not pk.held_transposed((16, 1856, 2688))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 1e-2)])
@pytest.mark.parametrize("k,n", HELD_WIDTHS)
@pytest.mark.parametrize("load", ["cell_skew", "straddles_tiles"])
def test_held_transposed_product_matches_ragged_dot(load, k, n, dtype, tol):
    """``grouped_matmul(lhs, swap(rhs), rhs_transposed=True)`` against
    ``jax.lax.ragged_dot(lhs, rhs)`` in float32: the value, the rows'
    gradient and the weight's (in the held order, ``[g, n, k]``), whatever
    ``held_transposed`` says of the widths; an empty group's weight
    gradient exactly zero."""
    sizes = GMM_SIZES[load]
    lhs, rhs, group_sizes = _gmm_inputs(sizes, k, n, dtype, seed=5)
    held = jnp.swapaxes(rhs, 1, 2)
    cot = jnp.asarray(
        np.random.RandomState(6).randn(sum(sizes), n), jnp.float32)

    def loss(fn):
        return lambda l, r: jnp.sum(fn(l, r).astype(jnp.float32) * cot)

    kernel = lambda l, r: grouped_matmul(l, r, group_sizes,
                                         rhs_transposed=True)
    plain = lambda l, r: jax.lax.ragged_dot(
        l, r, group_sizes, precision=jax.lax.Precision.HIGHEST)
    wide = lhs.astype(jnp.float32), rhs.astype(jnp.float32)
    out = kernel(lhs, held)
    assert out.dtype == dtype and out.shape == (sum(sizes), n)
    assert _rel(out, plain(*wide)) < tol
    got = jax.grad(loss(kernel), argnums=(0, 1))(lhs, held)
    want = jax.grad(loss(plain), argnums=(0, 1))(*wide)
    assert got[1].shape == held.shape and got[1].dtype == dtype
    assert _rel(got[0], want[0]) < 2 * tol
    assert _rel(got[1], jnp.swapaxes(want[1], 1, 2)) < 2 * tol
    for g, size in enumerate(sizes):
        if size == 0:
            assert not np.asarray(got[1][g], np.float32).any(), g


def test_held_transposed_product_runs_the_same_kernels_with_roles_swapped():
    """Over a weight held ``[g, n, k]`` the forward is the kernel that
    indexes the transposed block (named ``gmm_dgrad_*``: the name says
    the indexing, not the pass), the rows' gradient the straight one, the
    weight's the ragged contraction into ``[g, n, k]``: the three
    signatures, tiles and all, of a declared ``[g, n, k]`` weight; small
    calls go to ``ragged_dot`` over the swap."""
    sizes = GMM_SIZES["straddles_tiles"]
    lhs, rhs, group_sizes = _gmm_inputs(sizes, 256, 192, jnp.bfloat16)
    held = jnp.swapaxes(rhs, 1, 2)

    def kernels_of(fn, *args):
        return sorted(_dots_by_kernel(jax.make_jaxpr(jax.grad(
            lambda *a: jnp.sum(fn(*a).astype(jnp.float32) ** 2),
            argnums=(0, 1)))(*args)))

    across = kernels_of(lambda l, r: grouped_matmul(
        l, r, group_sizes, rhs_transposed=True), lhs, held)
    straight = kernels_of(
        lambda l, r: grouped_matmul(l, r, group_sizes),
        jnp.zeros((sum(sizes), 192), jnp.bfloat16), held)
    assert across == straight and len(across) == 3
    small = _gmm_inputs([10, 0, 30], 256, 192, jnp.float32)
    jaxpr = jax.make_jaxpr(lambda l, r: grouped_matmul(
        l, r, small[2], rhs_transposed=True))(
            small[0], jnp.swapaxes(small[1], 1, 2))
    assert "pallas_call" not in str(jaxpr) and "ragged_dot" in str(jaxpr)


def test_grouped_matmul_splits_k_and_n_like_the_whole():
    """A contraction walked in several k steps (the float32 scratch
    accumulator) and several n tiles against the whole-k, whole-n call."""
    sizes = GMM_SIZES["straddles_tiles"]
    lhs, rhs, group_sizes = _gmm_inputs(sizes, 256, 384, jnp.float32, 3)
    meta = pk.gmm_metadata(group_sizes, lhs.shape[0], 128)
    dout = jnp.asarray(np.random.RandomState(4).randn(lhs.shape[0], 384),
                       jnp.float32)
    want = _dense_grouped(lhs, rhs, sizes)
    got = pk.gmm.gmm_call(*meta[:4], lhs, rhs, tiles=(128, 128, 128),
                       transposed=False, interpret=True)
    assert _rel(got, want) < 1e-5
    d_want, w_want = jax.vjp(
        lambda l, r: _dense_grouped(l, r, sizes), lhs, rhs)[1](dout)
    d_got = pk.gmm.gmm_call(*meta[:4], dout, rhs, tiles=(128, 128, 128),
                         transposed=True, interpret=True)
    assert _rel(d_got, d_want) < 1e-5
    w_got = pk.gmm.gmm_wgrad_call(meta[0], *meta[4:], lhs, dout, groups=4,
                               tiles=(128, 128, 128), interpret=True)
    assert _rel(w_got, w_want) < 1e-5


def test_grouped_matmul_hands_small_or_odd_calls_to_ragged_dot():
    """Fewer rows than one row tile, or an operand type Mosaic does not
    take: ``jax.lax.ragged_dot``, chosen from the shapes, no kernel."""
    for sizes, dtype in (([10, 0, 30], jnp.float32),
                         ([100, 100], jnp.float16)):
        lhs, rhs, group_sizes = _gmm_inputs(sizes, 16, 24, dtype)
        assert not pk.gmm_runs_kernel(lhs.shape[0], dtype)
        jaxpr = jax.make_jaxpr(grouped_matmul)(lhs, rhs, group_sizes)
        assert "pallas_call" not in str(jaxpr)
        assert _rel(grouped_matmul(lhs, rhs, group_sizes),
                    _dense_grouped(lhs, rhs, sizes)) < 1e-2


def test_gmm_metadata_visits_every_tile_a_group_owns():
    sizes = GMM_SIZES["empty_first_and_last"]      # 256 rows, tm 128
    offsets, gids, tids, visits, w_gids, w_tids, w_visits = (
        np.asarray(a) for a in pk.gmm_metadata(
            jnp.asarray(sizes, jnp.int32), sum(sizes), 128))
    assert offsets.tolist() == [0, 0, 0, 200, 200, 200, 256, 256]
    # group 2 owns rows in tiles 0 and 1, group 5 in tile 1
    assert int(visits) == 3
    assert gids[:3].tolist() == [2, 2, 5] and tids[:3].tolist() == [0, 1, 1]
    # wgrad visits the five empty groups too, in order
    assert int(w_visits) == 8
    assert w_gids[:8].tolist() == [0, 1, 2, 2, 3, 4, 5, 6]
    assert w_tids[:8].tolist() == [0, 0, 0, 1, 1, 1, 1, 1]
    assert len(gids) == 2 + len(sizes) - 1


# the OLMoE cell's two products and their transposes; a float32 caller;
# a narrow n; k and n no multiple of a lane row; few rows a group
GMM_TILE_SHAPES = [
    (32768, 2048, 2048, 64, jnp.bfloat16),
    (32768, 1024, 2048, 64, jnp.bfloat16),
    (32768, 2048, 1024, 64, jnp.bfloat16),
    (32768, 2048, 2048, 64, jnp.float32),
    (8192, 4096, 128, 8, jnp.bfloat16),
    (1000, 200, 72, 4, jnp.float32),
    (4096, 14336, 4096, 64, jnp.bfloat16),
]


@pytest.mark.parametrize("m,k,n,groups,dtype", GMM_TILE_SHAPES)
def test_gmm_tiles_divide_or_mask_and_fit(m, k, n, groups, dtype):
    itemsize = jnp.dtype(dtype).itemsize
    for wgrad in (False, True):
        tm, tk, tn = pk.gmm_tiles(m, k, n, groups, dtype, wgrad=wgrad)
        # rows: a power of two between the measured bounds, no more than
        # the mean rows a group where that is above the least tile (the
        # last tile of an m that is no multiple is masked, not padded)
        assert tm & (tm - 1) == 0
        assert pk.gmm.GMM_MIN_ROW_TILE <= tm <= pk.gmm.GMM_MAX_ROW_TILE
        assert tm <= max(m // groups, pk.gmm.GMM_MIN_ROW_TILE)
        assert tm == pk.gmm_row_tile(m, groups)
        # the contraction admits no partial tile; a partial tile of a
        # result dimension is masked
        assert tk == k or tk % 128 == 0
        assert tn == n or tn % 128 == 0
        if not wgrad:
            assert k % tk == 0
        assert pk.gmm.gmm_vmem_bytes(
            tm, tk, tn, itemsize, wgrad,
            split=tk < k) <= pk.common.VMEM_SCOPED_DEFAULT


def test_gmm_tiles_at_the_cell_shape_keep_k_whole():
    # forward and dgrad: a row block fetched once per n tile, a weight
    # block once a group; wgrad: a square result block
    bf16 = jnp.bfloat16
    assert pk.gmm_tiles(32768, 2048, 2048, 64, bf16) == (256, 2048, 1024)
    assert pk.gmm_tiles(32768, 1024, 2048, 64, bf16) == (256, 1024, 2048)
    assert pk.gmm_tiles(32768, 2048, 1024, 64, bf16) == (256, 2048, 1024)
    for k in (2048, 1024):
        assert pk.gmm_tiles(32768, k, 2048, 64, bf16, wgrad=True) == (
            256, 1024, 1024)


def test_gmm_tiles_count_a_third_table_where_the_contraction_is_split():
    """The LFM2 share cell's buffer of 8,192 rows over 8 experts is the
    first row tile of 512: the dgrad of the 3,072 gate-and-up columns
    (the transposed problem, k 3072 n 2048) walks its contraction in two
    steps of 1536, and Mosaic needs 16.22 MiB for the (512, 1536, 1024)
    that two float32 tables count at 15.0. With the third table it gives
    up half its result tile; the same tiles with the contraction whole
    (the down projection's forward) stay, and so does every tile of the
    cells that were there (a split contraction at a row tile of 256 or
    128 counts 14.5 MiB at the most)."""
    bf16 = jnp.bfloat16
    count = pk.gmm.gmm_vmem_bytes
    assert count(512, 1536, 1024, 2) == 15 * 2 ** 20
    assert count(512, 1536, 1024, 2, split=True) == 17 * 2 ** 20
    assert pk.gmm_tiles(8192, 3072, 2048, 8, bf16) == (512, 1536, 512)
    assert pk.gmm_tiles(8192, 1536, 2048, 8, bf16) == (512, 1536, 1024)
    assert pk.gmm_tiles(8192, 2048, 3072, 8, bf16) == (512, 2048, 768)
    # Nemotron's, MiMo's and Kanana's: split or whole, unchanged
    assert pk.gmm_tiles(12288, 2688, 1856, 16, bf16) == (256, 896, 1856)
    assert pk.gmm_tiles(12288, 1856, 2688, 16, bf16) == (256, 1856, 896)
    assert pk.gmm_tiles(2048, 4096, 4096, 8, bf16) == (128, 2048, 1024)
    assert pk.gmm_tiles(12288, 1536, 2048, 16, bf16) == (256, 1536, 1024)


def test_gmm_lowerings_counter_counts_one_per_call_site_and_mode():
    telemetry.reset()
    telemetry.enable()
    try:
        sizes = GMM_SIZES["straddles_tiles"]
        lhs, rhs, group_sizes = _gmm_inputs(sizes, 32, 64, jnp.bfloat16)
        fn = lambda l, r: grouped_matmul(l, r, group_sizes)
        step = jax.jit(jax.grad(
            lambda l, r: jnp.sum(fn(l, r).astype(jnp.float32) ** 2),
            argnums=(0, 1)))
        step(lhs, rhs)
        step(lhs, rhs)  # a second step of one lowering counts nothing
        jax.jit(fn)(lhs.astype(jnp.float32), rhs.astype(jnp.float32))
        c = telemetry.REGISTRY.get("moe.gmm_lowerings")
        for mode in ("fwd", "dgrad", "wgrad"):
            assert c.value(mode=mode, operands="bf16", tm=128, tk=32,
                           tn=64, rhs="declared") == 1, mode
        assert c.value(mode="fwd", operands="f32", tm=128, tk=32,
                       tn=64, rhs="declared") == 1
        assert telemetry.total("moe.gmm_lowerings") == 4
        # a weight handed over as it is held: the same tiles' labels as
        # the kernels' names carry, ``mode`` the pass
        jax.jit(jax.grad(lambda l, r: jnp.sum(grouped_matmul(
            l, r, group_sizes, rhs_transposed=True).astype(jnp.float32)),
            argnums=(0, 1))).lower(lhs, jnp.swapaxes(rhs, 1, 2))
        for mode, tk, tn in (("fwd", 64, 32), ("dgrad", 64, 32),
                             ("wgrad", 64, 32)):
            assert c.value(mode=mode, operands="bf16", tm=128, tk=tk,
                           tn=tn, rhs="held_transposed") == 1, mode
        assert telemetry.total("moe.gmm_lowerings") == 7
    finally:
        telemetry.disable()
        telemetry.reset()


def test_gmm_kernels_feed_mxu_operands_as_given():
    """Every product inside the three kernels takes the operands' own
    type and accumulates in float32; the kernels are named for what they
    run."""
    sizes = GMM_SIZES["straddles_tiles"]
    for dtype, label in ((jnp.bfloat16, "bf16"), (jnp.float32, "f32")):
        lhs, rhs, group_sizes = _gmm_inputs(sizes, 32, 64, dtype)
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda l, r: jnp.sum(grouped_matmul(
                l, r, group_sizes).astype(jnp.float32) ** 2),
            argnums=(0, 1)))(lhs, rhs)
        found = _dots_by_kernel(jaxpr)
        assert sorted(found) == [
            "gmm_%s_%s_m128_k32_n64" % (mode, label)
            for mode in ("dgrad", "fwd", "wgrad")]
        for dots in found.values():
            for operands, result in dots:
                assert operands == (dtype, dtype)
                assert result == jnp.float32


# ---------------------------------------------------------------------------
# the one platform switch (``common.on_tpu``), entry by entry: off the TPU
# and without ``interpret`` an entry IS its plain form and traces no
# interpreter copy of a body (what a step lowered for the TPU must not pay
# for); with ``interpret=True`` it is the kernels alone, no switch
# ---------------------------------------------------------------------------

def _switch_case(name):
    """(entry(*args, interpret=...), plain(*args), args, the arguments a
    gradient is taken in: none where the entry is itself a backward)."""
    from mxnet_tpu.ops import transformer as tr

    rng = np.random.RandomState(sorted(SWITCH_CASES).index(name))

    def draw(*shape, scale=1.0, dtype=jnp.float32):
        return jnp.asarray(scale * rng.randn(*shape), dtype)

    def flash_plain(q, k, v, window=0):
        # the pair's plain form on the operands heads first, as
        # ``flash_attention`` hands them over (``reference_attention``'s
        # arithmetic; one product in another layout, so an ulp from it)
        b, t, h, d = q.shape
        out, _ = pk.flash.plain_fwd(
            *(x.transpose(0, 2, 1, 3).reshape(-1, t, x.shape[3])
              for x in (q, k, v)),
            t_real=t, scale=1.0 / float(np.sqrt(d)), causal=True,
            window=window)
        return out.reshape(b, h, t, -1).transpose(0, 2, 1, 3)

    if name == "flash_attention":
        return (functools.partial(pk.flash_attention, causal=True,
                                  window=40),
                functools.partial(flash_plain, window=40),
                (draw(1, 200, 4, 32), draw(1, 200, 2, 32),
                 draw(1, 200, 2, 16)), (0, 1, 2))
    if name == "attention":
        def entry(q, k, v, interpret):
            # ``attention`` reads the seam where it traces
            with pytest.MonkeyPatch.context() as m:
                m.setattr(common, "INTERPRET", interpret)
                return pk.attention(q, k, v, causal=True)
        return (entry, flash_plain,
                tuple(draw(2, 128, 2, 32) for _ in range(3)), (0, 1, 2))
    if name == "grouped_matmul":
        sizes = GMM_SIZES["straddles_tiles"]
        lhs, rhs, group_sizes = _gmm_inputs(sizes, 32, 64, jnp.float32)
        return (lambda l, r, interpret: pk.grouped_matmul(
                    l, r, group_sizes, interpret=interpret),
                lambda l, r: jax.lax.ragged_dot(l, r, group_sizes),
                (lhs, rhs), (0, 1))
    if name == "ssd_scan":
        b, t, h, p, n, chunk = 1, 256, 4, 32, 128, 128
        assert pk.ssd_takes(h, p, n, 1, chunk, jnp.float32)
        args = (draw(b, t, h, p), draw(b, t, 1, n, scale=0.3),
                draw(b, t, 1, n, scale=0.3),
                jnp.abs(draw(b, t, h, scale=0.1)) + 0.01,
                -jnp.abs(draw(h)) - 0.1, draw(h))

        def plain(x, bm, cm, dt, a, skip):
            return (tr.ssd_scan(x, bm, cm, dt, a, chunk)
                    + skip[:, None] * x.astype(jnp.float32))
        return (lambda *a, interpret: pk.ssd_scan(
                    *a, chunk, interpret=interpret),
                plain, args, tuple(range(6)))
    if name == "gated_delta_rule":
        b, t, h, dk, dv, chunk = 1, 64, 3, 32, 64, 16
        assert pk.gdn_takes(h, dk, dv, chunk, jnp.float32)
        q, k = (draw(b, t, h, dk) for _ in range(2))
        q, k = (x / jnp.linalg.norm(x, axis=-1, keepdims=True)
                for x in (q, k))
        args = (q, k, draw(b, t, h, dv), -jnp.abs(draw(b, t, h, scale=0.1)),
                jax.nn.sigmoid(draw(b, t, h)))
        return (lambda *a, interpret: pk.gated_delta_rule(
                    *a, chunk, interpret=interpret),
                lambda *a: tr.gated_delta_rule(*a, chunk), args,
                tuple(range(5)))
    if name == "latent_flash":
        b, t, heads, nope, rp, dv = 1, 128, 2, 128, 128, 128
        assert pk.latent_flash_takes(t, nope, 64, dv, jnp.float32)
        scale = (nope + 64) ** -0.5
        return (lambda *a, interpret: pk.latent_flash(
                    *a, heads, nope, scale, interpret=interpret),
                lambda *a: pk.latent.latent_composed(*a, heads, nope, scale),
                (draw(b, t, heads * (nope + rp), scale=0.3),
                 draw(b, t, heads * (nope + dv), scale=0.3),
                 draw(b, t, rp, scale=0.3)), (0, 1, 2))
    dshape, wshape, pad = (2, 8, 10, 10), (16, 8, 3, 3), (1, 1)
    x, w, g = draw(*dshape), draw(*wshape, scale=0.1), draw(2, 16, 10, 10)

    def conv(x, w):
        return jax.lax.conv_general_dilated(
            x, w, (1, 1), [(1, 1), (1, 1)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
    if name == "conv_bwd_filter":
        return (lambda x, g, interpret: pk.conv_bwd_filter(
                    x, g, wshape, pad, interpret=interpret),
                lambda x, g: jax.vjp(conv, x, w)[1](g)[1], (x, g), ())
    assert name == "conv_bwd_input"
    return (lambda g, w, interpret: pk.conv_bwd_input(
                g, w, dshape, pad, interpret=interpret),
            lambda g, w: jax.vjp(conv, x, w)[1](g)[0], (g, w), ())


SWITCH_CASES = ("attention", "conv_bwd_filter", "conv_bwd_input",
                "flash_attention", "gated_delta_rule",
                "grouped_matmul", "latent_flash", "ssd_scan")


def _primitives(jaxpr):
    """Every equation of a jaxpr, nested ones included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub)


@pytest.mark.parametrize("name", SWITCH_CASES)
def test_an_entry_off_the_tpu_is_its_plain_form_and_interprets_only_when_told(
        name):
    entry, plain, args, argnums = _switch_case(name)

    def traced(interpret):
        fn = value = functools.partial(entry, interpret=interpret)
        if argnums:
            # the backward rule's branches are part of the contract
            fn = jax.value_and_grad(
                lambda *a: jnp.sum(jnp.sin(value(*a).astype(jnp.float32))),
                argnums)
        eqns = list(_primitives(jax.make_jaxpr(fn)(*args).jaxpr))
        return ([e for e in eqns if e.primitive.name == "pallas_call"],
                [e for e in eqns if e.primitive.name == "platform_index"])

    calls, switches = traced(False)
    # the Mosaic branch is there for a lowering for the TPU to take, and
    # no interpreter copy of it beside it
    assert calls and switches
    assert not any(c.params["interpret"] for c in calls)
    interpreted, switches = traced(True)
    assert interpreted and not switches
    assert all(c.params["interpret"] for c in interpreted)
    assert (sorted(str(c.params["name"]) for c in interpreted)
            == sorted(str(c.params["name"]) for c in calls))

    got = jax.jit(functools.partial(entry, interpret=False))(*args)
    want = jax.jit(plain)(*args)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ---------------------------------------------------------------------------
# the flash pair's plain form (``flash.plain_fwd`` / ``plain_bwd``: what
# every platform but the TPU runs inside the ``custom_vjp``) against
# ``jax.vjp(reference_attention)`` on the kernels' padded operands
# ---------------------------------------------------------------------------

# (b, t, t_pad, heads, kv heads, d, dv, causal, window)
PLAIN_PAIR_CASES = {
    "causal": (2, 64, 64, 2, 2, 16, 16, True, 0),
    "full_padded": (1, 50, 64, 2, 2, 16, 16, False, 0),
    "grouped_window_padded": (2, 100, 128, 4, 2, 16, 16, True, 24),
    "one_kv_head_value_width_of_its_own": (1, 64, 64, 4, 1, 24, 16, True,
                                           0),
}


@pytest.mark.parametrize("name", sorted(PLAIN_PAIR_CASES))
def test_the_plain_flash_pair_matches_the_reference_and_its_vjp(name):
    b, t, t_pad, h, g, d, dv, causal, window = PLAIN_PAIR_CASES[name]
    rng = np.random.RandomState(sorted(PLAIN_PAIR_CASES).index(name))
    q, k, v, do = (jnp.asarray(rng.randn(*shape), jnp.float32)
                   for shape in ((b, t, h, d), (b, t, g, d), (b, t, g, dv),
                                 (b, t, h, dv)))
    scale = d ** -0.5
    want, vjp = jax.vjp(
        lambda q, k, v: reference_attention(
            q, k, v, causal=causal, scale=scale, window=window), q, k, v)
    want_grads = vjp(do)

    def padded(x):
        # [B, T, H, D] -> [B H, T_pad, D], as ``flash_attention`` hands
        # its operands to the pair
        x = x.transpose(0, 2, 1, 3).reshape(-1, t, x.shape[3])
        return jnp.pad(x, ((0, 0), (0, t_pad - t), (0, 0)))

    def unpadded(x3, heads):
        return x3[:, :t].reshape(b, heads, t, -1).transpose(0, 2, 1, 3)

    call = dict(t_real=t, scale=scale, causal=causal, window=window,
                block_q=t_pad, block_k=t_pad)
    q3, k3, v3, do3 = (padded(x) for x in (q, k, v, do))
    out, lse = pk.flash.plain_fwd(q3, k3, v3, **call)
    assert out.shape == (b * h, t_pad, dv) and lse.shape == (b * h, t_pad, 1)
    assert out.dtype == q.dtype and lse.dtype == jnp.float32
    assert _rel(unpadded(out, h), want) < 1e-6
    # a row's lse is the log of its softmax's denominator: the row's
    # probabilities rebuilt from it sum to one
    s = scale * jnp.einsum("bqhd,bkhd->bhqk", q,
                           jnp.repeat(k, h // g, axis=2))
    p = jnp.exp(s - unpadded(lse, h).transpose(0, 2, 1, 3))
    if causal:
        i, j = np.indices((t, t))
        p = jnp.where((j <= i) & ((i - j < window) if window else True),
                      p, 0.0)
    np.testing.assert_allclose(np.asarray(p.sum(-1)), 1.0, rtol=1e-5)
    delta = jnp.sum(do3 * out, axis=-1, keepdims=True)
    grads = pk.flash.plain_bwd(q3, k3, v3, do3, lse, delta, **call)
    for which, got, w, heads in zip(("dq", "dk", "dv"), grads, want_grads,
                                    (h, g, g)):
        assert got.dtype == q.dtype and got.shape[1] == t_pad
        assert not np.asarray(got[:, t:]).any(), which
        assert _rel(unpadded(got, heads), w) < 1e-5, which
