"""Pallas flash-attention kernel tests (interpret mode on CPU — the same
kernel code that compiles via Mosaic on TPU; the backend-equivalence trick
mirrors the reference's cpu-vs-gpu check_consistency harness,
tests/python/gpu/test_operator_gpu.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.ops.pallas_kernels import (
    flash_attention, flash_tiles, reference_attention)


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
    # force the model's flash branch off-TPU (Pallas interpreter) and
    # check it agrees with the reference-attention branch — this executes
    # the actual flash_attention call site in the transformer, so a
    # swapped q/k/v argument or wrong keyword there fails here, not on
    # hardware
    from mxnet_tpu.models.transformer import transformer_lm

    init_fn, apply_fn = transformer_lm(
        vocab=50, d_model=32, n_layers=1, n_heads=2, dtype=jnp.float32,
    )
    params = init_fn(seed=0)
    toks = jnp.asarray(np.random.RandomState(1).randint(0, 50, (2, 16)))
    ref_logits = apply_fn(params, toks)
    monkeypatch.setenv("MXNET_TPU_FORCE_FLASH", "1")
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


def _kernel_dots(dtype):
    """(operand dtypes, result dtype) of every dot_general inside the
    three kernels of a causal flash call on ``dtype`` inputs."""
    q = jnp.zeros((1, 256, 1, 64), dtype)
    fn = lambda q, k, v: flash_attention(q, k, v, causal=True)
    jaxpr = jax.make_jaxpr(jax.grad(_loss(fn), argnums=(0, 1, 2)))(q, q, q)
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


def test_flash_kernels_feed_mxu_operands_as_given():
    bf16 = _kernel_dots(jnp.bfloat16)
    # the Mosaic and the interpreter branch carry the same three kernels
    assert sorted(bf16) == ["flash_dkv_bf16_q256_k256",
                            "flash_dq_bf16_q256_k256",
                            "flash_fwd_bf16_q256_k256"]
    # 2 / 3 / 4 products a tile pair, in the masked and the unmasked
    # body, in both branches
    assert {k: len(v) for k, v in bf16.items()} == {
        "flash_fwd_bf16_q256_k256": 8, "flash_dq_bf16_q256_k256": 12,
        "flash_dkv_bf16_q256_k256": 16}
    for dots in bf16.values():
        for operands, result in dots:
            assert operands == (jnp.bfloat16, jnp.bfloat16)
            assert result == jnp.float32
    f32 = _kernel_dots(jnp.float32)
    assert sorted(f32) == ["flash_dkv_f32_q256_k256",
                           "flash_dq_f32_q256_k256",
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
    assert pk._flash_vmem_bytes(
        bq, bk, d, jnp.dtype(dtype).itemsize) <= pk._FLASH_VMEM_BUDGET


def test_flash_tiles_at_the_cell_shape_are_large():
    # OLMoE's attention: 16,384 grid steps a call at 128 x 128
    bq, bk = flash_tiles(4096, 128, jnp.bfloat16)
    assert bq >= 256 and bk >= 512
    assert (4096 // bq) * (4096 // bk) <= 1024


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
        assert telemetry.total("attention.flash_lowerings") == 2
    finally:
        telemetry.disable()
        telemetry.reset()
