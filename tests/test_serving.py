"""Serving-path tests: shared bucketing, the continuous-batching engine
(bitwise batching correctness, drain semantics), the AOT predict pool
(reshape LRU, bundle CRCs, int8 parity), KV-cached decode equivalence,
mid-flight slot admission/eviction, and the zero-steady-state-recompile
guarantee. Subprocess SIGTERM-drain and server self-tests are marked
slow (nightly)."""
import importlib
import json
import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu.ndarray as nd
from mxnet_tpu import predict, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.serving import buckets
from mxnet_tpu.serving.engine import ServeClosed, ServingEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_TFM_DIMS = dict(vocab=32, d_model=16, n_heads=2, n_layers=2, d_ff=32)


# ---------------------------------------------------------------------------
# serving/buckets.py — the one bucket-selection implementation
# ---------------------------------------------------------------------------

def test_bucket_ladder():
    assert buckets.bucket_ladder(8) == [1, 2, 4, 8]
    assert buckets.bucket_ladder(6) == [1, 2, 4, 6]
    assert buckets.bucket_ladder(1) == [1]
    assert buckets.bucket_ladder(32, base=8) == [8, 16, 32]
    with pytest.raises(ValueError):
        buckets.bucket_ladder(0)


def test_smallest_covering_and_value():
    ladder = [1, 2, 4, 8]
    assert buckets.covering_value(ladder, 1) == 1
    assert buckets.covering_value(ladder, 3) == 4
    assert buckets.covering_value(ladder, 8) == 8
    assert buckets.covering_value(ladder, 9) is None
    assert buckets.smallest_covering([10, 20, 30], 15) == 1


def test_pad_batch_and_scatter_roundtrip():
    rows = [np.full((3,), i, np.float32) for i in range(3)]
    batched = buckets.pad_batch(rows, 4, fill=-1)
    assert batched.shape == (4, 3)
    assert (batched[3] == -1).all()
    row = buckets.pad_to_width(np.arange(3, dtype=np.float32), 5, 9)
    assert row.tolist() == [0, 1, 2, 9, 9]
    outs = buckets.scatter_rows([batched, batched * 2], 3)
    assert len(outs) == 3
    for i, per_req in enumerate(outs):
        assert per_req[0].tolist() == rows[i].tolist()
        assert per_req[1].tolist() == (rows[i] * 2).tolist()


# ---------------------------------------------------------------------------
# engine batching correctness
# ---------------------------------------------------------------------------

def _mlp_predictor(in_dim=16, quant=""):
    mlp = importlib.import_module("mxnet_tpu.models.mlp")
    sym = mlp.get_symbol(num_classes=10, hidden=(32,))
    rng = np.random.RandomState(0)
    arg_shapes, _, _ = sym.infer_shape(data=(1, in_dim))
    params = {
        ("arg:%s" % n): nd.array((rng.randn(*s) * 0.2).astype(np.float32))
        for n, s in zip(sym.list_arguments(), arg_shapes)
        if n not in ("data", "softmax_label")
    }
    return predict.Predictor(sym.tojson(), params, {"data": (1, in_dim)},
                             quant=quant)


def test_engine_coalesces_and_rows_are_bitwise():
    """Co-batched rows must be BITWISE what the same row produces alone
    at the same position in the same bucket (padding/coalescing adds no
    numerics), and allclose to the truly-unbatched batch-1 dispatch
    (whose different shape may tile the gemm differently)."""
    from mxnet_tpu.serving import engine as _se

    p = _mlp_predictor()
    eng = ServingEngine(p, max_batch=4, batch_timeout_ms=200.0)
    eng.start()
    batches0 = _se._C_BATCHES.value()
    rng = np.random.RandomState(1)
    xs = rng.randn(3, 16).astype(np.float32)
    futs = [eng.submit(data=xs[i]) for i in range(3)]
    outs = [f.result(30.0) for f in futs]
    eng.drain()
    if telemetry.registry.enabled():
        assert _se._C_BATCHES.value() - batches0 == 1  # one coalesced call

    for i in range(3):
        solo = np.zeros((4, 16), np.float32)
        solo[i] = xs[i]
        same_bucket = p.predict_batch(data=solo)[0][i]
        assert np.array_equal(outs[i][0], same_bucket)
        unbatched = p.predict_batch(data=xs[i][None])[0][0]
        assert np.allclose(outs[i][0], unbatched, rtol=1e-6, atol=1e-6)


def test_engine_drain_finishes_inflight_and_rejects_new():
    p = _mlp_predictor()
    eng = ServingEngine(p, max_batch=4, batch_timeout_ms=1.0)
    eng.start()
    futs = [eng.submit(data=np.zeros(16, np.float32)) for _ in range(6)]
    eng.drain()
    for f in futs:  # everything accepted before drain completes
        assert len(f.result(1.0)) == 1
    with pytest.raises(ServeClosed):
        eng.submit(data=np.zeros(16, np.float32))
    eng.drain()  # idempotent


def test_engine_missing_input_rejected():
    p = _mlp_predictor()
    eng = ServingEngine(p, max_batch=2, batch_timeout_ms=1.0)
    with pytest.raises(MXNetError):
        eng.submit(wrong_name=np.zeros(16, np.float32))


# ---------------------------------------------------------------------------
# predictor pool: reshape LRU, bundle CRCs, quantization
# ---------------------------------------------------------------------------

def test_reshape_reuses_lru_executor():
    p = _mlp_predictor()
    first = p._exec
    p.reshape({"data": (4, 16)})
    second = p._exec
    assert second is not first
    p.reshape({"data": (1, 16)})
    assert p._exec is first  # LRU hit: no rebind, same executor object
    assert len(p.cached_shape_keys) == 2


def test_exec_cache_eviction(monkeypatch):
    monkeypatch.setenv("MXTPU_SERVE_EXEC_CACHE", "2")
    p = _mlp_predictor()
    for b in (2, 3, 4):
        p.reshape({"data": (b, 16)})
    assert len(p.cached_shape_keys) == 2  # capped, oldest evicted


def test_bundle_roundtrip_and_crc_failures(tmp_path):
    mlp = importlib.import_module("mxnet_tpu.models.mlp")
    sym = mlp.get_symbol(num_classes=10, hidden=(32,))
    rng = np.random.RandomState(0)
    arg_shapes, _, _ = sym.infer_shape(data=(1, 16))
    arg_params = {
        n: nd.array((rng.randn(*s) * 0.2).astype(np.float32))
        for n, s in zip(sym.list_arguments(), arg_shapes)
        if n not in ("data", "softmax_label")
    }
    path = str(tmp_path / "model.pred")
    predict.export_bundle(path, sym, arg_params)

    loaded = predict.load_bundle(path, {"data": (1, 16)})
    x = rng.randn(1, 16).astype(np.float32)
    ref = _mlp_predictor().predict_batch(data=x)[0]
    assert np.array_equal(loaded.predict_batch(data=x)[0], ref)

    # flip one byte INSIDE a known tensor: the error must name it
    blob = bytearray(open(path, "rb").read())
    needle = np.ascontiguousarray(
        arg_params["fc1_weight"].asnumpy()).tobytes()
    off = bytes(blob).find(needle)
    assert off > 0
    corrupt = bytearray(blob)
    corrupt[off + 8] ^= 0xFF
    bad = str(tmp_path / "bad.pred")
    open(bad, "wb").write(bytes(corrupt))
    with pytest.raises(MXNetError) as e:
        predict.load_bundle(bad, {"data": (1, 16)})
    assert "arg:fc1_weight" in str(e.value) and "bad.pred" in str(e.value)

    # flip a byte in the symbol JSON: section-level CRC catches it
    sym_off = bytes(blob).find(b'"nodes"')
    corrupt2 = bytearray(blob)
    corrupt2[sym_off] ^= 0xFF
    bad2 = str(tmp_path / "bad2.pred")
    open(bad2, "wb").write(bytes(corrupt2))
    with pytest.raises(MXNetError) as e2:
        predict.load_bundle(bad2, {"data": (1, 16)})
    assert "symbol section" in str(e2.value)


def test_int8_quant_parity():
    from mxnet_tpu.serving import quant

    f32 = _mlp_predictor()
    i8 = _mlp_predictor(quant="int8")
    xs = np.random.RandomState(2).randn(32, 16).astype(np.float32)
    a = f32.predict_batch(data=xs)[0]
    b = i8.predict_batch(data=xs)[0]
    assert quant.top1_agreement(a, b) >= 0.99


def test_quantized_tensor_roundtrip():
    from mxnet_tpu.serving.quant import QuantizedTensor

    w = np.random.RandomState(3).randn(8, 64).astype(np.float32)
    qt = QuantizedTensor.quantize(w)
    assert qt.q.dtype == np.int8
    back = qt.dequantize()
    assert back.shape == w.shape
    # symmetric per-channel int8: worst-case error is scale/2 per entry
    assert np.abs(back - w).max() <= (np.abs(w).max(axis=1) / 127).max()


# ---------------------------------------------------------------------------
# KV-cached decode
# ---------------------------------------------------------------------------

def _ref_greedy(apply_fn, params, prompt, n_steps):
    """Reference: full recompute over the growing sequence each step."""
    import jax.numpy as jnp

    toks = list(prompt)
    out = []
    for _ in range(n_steps):
        logits = apply_fn(params, jnp.asarray([toks], jnp.int32))
        nxt = int(np.argmax(np.asarray(logits)[0, -1]))
        out.append(nxt)
        toks.append(nxt)
    return out


def test_kv_decode_matches_full_recompute():
    """Prefill + ring-buffer decode over mixed-length slots must match
    the full-forward recompute: prefill last-logits to 1e-5, every
    decode step's logits to 1e-5, greedy tokens exactly."""
    import jax.numpy as jnp

    from mxnet_tpu.models import transformer as tfm

    init_fn, apply_fn = tfm.transformer_lm(**_TFM_DIMS)
    params = init_fn(0)
    init_cache, prefill, decode_step = tfm.transformer_lm_serving(
        max_len=16, **_TFM_DIMS)

    prompts = [[1, 2, 3], [4, 5, 6, 7, 8], [9, 10]]
    lengths = np.array([len(p) for p in prompts], np.int32)
    toks = np.zeros((3, 8), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    cache = init_cache(3)
    cache, last = prefill(params, cache, jnp.asarray(toks),
                          jnp.arange(3, dtype=jnp.int32),
                          jnp.asarray(lengths))
    last = np.asarray(last)
    seqs = [list(p) for p in prompts]
    for i, p in enumerate(prompts):
        ref = np.asarray(apply_fn(params, jnp.asarray([p], jnp.int32)))
        assert np.allclose(last[i], ref[0, -1], rtol=1e-5, atol=1e-5)

    step_toks = np.array([int(np.argmax(last[i])) for i in range(3)],
                         np.int32)
    for _ in range(4):
        for i in range(3):
            seqs[i].append(int(step_toks[i]))
        cache, logits = decode_step(params, cache, jnp.asarray(step_toks))
        logits = np.asarray(logits)
        for i in range(3):
            ref = np.asarray(apply_fn(
                params, jnp.asarray([seqs[i]], jnp.int32)))[0, -1]
            assert np.allclose(logits[i], ref, rtol=1e-5, atol=1e-5)
            assert int(np.argmax(logits[i])) == int(np.argmax(ref))
        step_toks = np.argmax(logits, axis=-1).astype(np.int32)


def test_generation_engine_midflight_admission():
    """3 requests on 2 slots: the third is admitted mid-flight into the
    slot the first frees, without disturbing the second's decode. Every
    continuation must equal the full-recompute greedy reference."""
    from mxnet_tpu.models import transformer as tfm
    from mxnet_tpu.serving.decode import GenerationEngine

    init_fn, apply_fn = tfm.transformer_lm(**_TFM_DIMS)
    params = init_fn(0)
    model = tfm.transformer_lm_serving(max_len=16, **_TFM_DIMS)
    gen = GenerationEngine(params, model, slots=2, max_len=16)
    gen.compile()

    prompts = {"a": [1, 2, 3], "b": [4, 5, 6, 7], "c": [8, 9]}
    budget = {"a": 3, "b": 6, "c": 2}
    reqs = {k: gen.submit(prompts[k], max_new=budget[k]) for k in prompts}
    # only 2 slots: c cannot be admitted until a or b finishes
    assert gen.step()
    assert gen.active == 2 and reqs["c"].t_admit is None
    for _ in range(40):
        if all(r.done.is_set() for r in reqs.values()):
            break
        gen.step()
    for k in prompts:
        got = reqs[k].result(0)
        assert got == _ref_greedy(apply_fn, params, prompts[k], budget[k])
    assert reqs["c"].t_admit is not None
    assert gen.active == 0 and sorted(gen._free) == [0, 1]


def test_generation_engine_drain_rejects_and_prompt_cap():
    from mxnet_tpu.models import transformer as tfm
    from mxnet_tpu.serving.decode import GenerationEngine

    init_fn, _ = tfm.transformer_lm(**_TFM_DIMS)
    model = tfm.transformer_lm_serving(max_len=16, **_TFM_DIMS)
    gen = GenerationEngine(init_fn(0), model, slots=2, max_len=16)
    with pytest.raises(MXNetError):
        gen.submit(list(range(1, 20)))  # prompt longer than the window
    gen.start()
    fut = gen.submit([1, 2, 3], max_new=2)
    gen.drain()
    assert len(fut.result(0)) == 2  # in-flight finished during drain
    with pytest.raises(ServeClosed):
        gen.submit([1, 2], max_new=1)


# ---------------------------------------------------------------------------
# the AOT guarantee: zero steady-state recompiles
# ---------------------------------------------------------------------------

def test_zero_steady_state_recompiles_mixed_shapes():
    """After warmup, a mixed-shape request stream (every batch bucket,
    every prompt-length bucket) must never retrace: the anatomy
    recompile counter stays exactly flat."""
    from mxnet_tpu.models import transformer as tfm
    from mxnet_tpu.serving.decode import GenerationEngine
    from mxnet_tpu.telemetry import anatomy

    was_enabled = telemetry.registry.enabled()
    telemetry.enable()
    try:
        p = _mlp_predictor()
        p.compile([{"data": (b, 16)} for b in buckets.bucket_ladder(4)])
        init_fn, _ = tfm.transformer_lm(**_TFM_DIMS)
        model = tfm.transformer_lm_serving(max_len=16, **_TFM_DIMS)
        gen = GenerationEngine(init_fn(0), model, slots=2, max_len=16)
        gen.compile()  # warmup: every (count x length) bucket

        r0 = anatomy._C_RECOMPILES.value()
        rng = np.random.RandomState(4)
        for b in (1, 3, 2, 4, 1, 4, 2, 3):  # mixed batch buckets
            xs = rng.randn(b, 16).astype(np.float32)
            bucket = buckets.covering_value(buckets.bucket_ladder(4), b)
            p.predict_batch(data=buckets.pad_batch(list(xs), bucket))
        for n in (3, 9, 2, 14):  # mixed prompt lengths
            gen.submit(rng.randint(1, 32, size=n), max_new=2)
        for _ in range(30):
            if not gen.step() and not gen._pending:
                break
        assert anatomy._C_RECOMPILES.value() - r0 == 0
    finally:
        if not was_enabled:
            telemetry.disable()


# ---------------------------------------------------------------------------
# server process: SIGTERM drain + self-test (slow / nightly)
# ---------------------------------------------------------------------------

def _serve_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("MXTPU_SERVE_QUANT", None)
    return env


@pytest.mark.slow
@pytest.mark.timeout(300)
def test_sigterm_drains_and_exits_zero(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import serve as serve_tool

    bundle = str(tmp_path / "lenet.pred")
    serve_tool._build_toy_bundle(bundle)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "tools", "serve.py"),
         "--ctx", "cpu", "--bundle", bundle, "--input", "data=1x28x28",
         "--port", "0"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=_serve_env(), cwd=REPO)
    try:
        line = proc.stdout.readline()
        assert "serving on" in line, line
        port = int(line.split(":")[-1].split(" ")[0].strip("()"))
        with socket.create_connection(("127.0.0.1", port), 30) as s:
            f = s.makefile("rwb")
            x = np.zeros((1, 28, 28), np.float32)
            f.write((json.dumps({"inputs": {"data": x.tolist()}})
                     + "\n").encode())
            f.flush()
            reply = json.loads(f.readline().decode())
            assert len(reply["outputs"][0]) == 10, reply
            # in-flight request already answered; now ask for drain
            proc.terminate()  # SIGTERM
            rc = proc.wait(timeout=120)
        assert rc == 0
        rest = proc.stdout.read()
        assert "draining" in rest and "drained, bye" in rest
    finally:
        if proc.poll() is None:
            proc.kill()


@pytest.mark.timeout(300)
def test_serve_self_test_subprocess():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "serve.py"),
         "--ctx", "cpu", "--self-test"],
        capture_output=True, text=True, timeout=280, env=_serve_env(),
        cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "serve self-test PASSED" in r.stdout
