#!/usr/bin/env python3
"""chip_smoke.py — the standing proof that the system starts on the chip.

One process drives the two paths users pay for, once, through their
normal entry points, at the full width of ResNet-50 (depth and weights
as shipped, weights random from a seed):

  device    platform / device_kind / count, the peak-table entry, the
            native library, the compile-cache directory
  kernels   flash attention forward+backward, compiled by Mosaic and
            compared with its jnp reference
  train     ``Module(sym, context=mx.tpu(0), mesh=make_mesh(dp=1))
            .fit(..., kvstore='device')`` — the fused ShardedTrainStep,
            bf16 ResNet-50 b256 on a repeated synthetic batch
  serve     ``export_bundle`` -> ``load_bundle(ctx=mx.tpu(0))`` ->
            ``ServingEngine``: concurrent requests, every row compared
            with solo ``Predictor`` dispatch
  generate  ``GenerationEngine`` on ``transformer_lm_serving``: prefill
            and decode, every token checked against a full forward
  train_dpN only with more than one chip: the same ResNet-50 under
            ``MXTPU_AMP=bf16`` over all N chips (flat sharded update,
            fp32 masters, the slab rule inside shard_map)

It exits non-zero if the platform is not ``tpu``, if a leg raises, if a
request goes unanswered, or if a mechanism a leg asked for did not
engage. There is no CPU branch. ``--rehearse-cpu`` is a debugging aid
for a host without the chip: tiny sizes, every line says
``platform=cpu``, and no result object is printed. The timings printed
here are bring-up observations (compile seconds, first/later step wall),
never benchmark numbers.

Last line of stdout on success:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""
from __future__ import annotations

import argparse
import collections
import functools
import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

MOSAIC_CALL = "tpu_custom_call"  # how a Mosaic kernel shows in lowered HLO

Sizes = collections.namedtuple(
    "Sizes", "layers image classes batch steps serve_batch requests "
             "lm lm_max_len prompt_long prompt_short new_tokens")

# full width: ResNet-50 / 1000 classes / 224x224 at batch 256, and the
# transformer at its shipped defaults (d_model 512, 8 heads, 4 layers)
REAL = Sizes(layers=50, image=(3, 224, 224), classes=1000, batch=256,
             steps=8, serve_batch=4, requests=12,
             lm=dict(vocab=32000, d_model=512, n_heads=8, n_layers=4,
                     d_ff=2048),
             lm_max_len=256, prompt_long=150, prompt_short=20,
             new_tokens=8)
REHEARSAL = Sizes(layers=18, image=(3, 32, 32), classes=10, batch=8,
                  steps=6, serve_batch=4, requests=6,
                  lm=dict(vocab=128, d_model=64, n_heads=2, n_layers=2,
                          d_ff=128),
                  lm_max_len=256, prompt_long=130, prompt_short=12,
                  new_tokens=4)


class SmokeFailure(AssertionError):
    """A check of the smoke did not hold."""


def check(cond, what):
    if not cond:
        raise SmokeFailure(what)


class Smoke:
    """Shared state of one run: the device, the sizes, the printer and
    the compile accounting (jax.monitoring events)."""

    def __init__(self, rehearsal):
        import jax

        self.devices = jax.devices()
        self.platform = self.devices[0].platform
        self.kind = self.devices[0].device_kind
        self.rehearsal = rehearsal
        self.size = REHEARSAL if rehearsal else REAL
        self._events = collections.Counter()
        self._seconds = collections.Counter()
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    # -- printing ------------------------------------------------------
    def say(self, leg, **fields):
        parts = ["platform=%s" % self.platform, "leg=%s" % leg]
        parts += ["%s=%s" % (k, v) for k, v in fields.items()]
        print(" ".join(parts), flush=True)

    # -- compile accounting ---------------------------------------------
    def _on_event(self, event, **_):
        self._events[event] += 1

    def _on_duration(self, event, seconds, **_):
        self._seconds[event] += seconds

    def compile_stats(self):
        """Cumulative (backend-compile seconds incl. cache retrieval,
        persistent-cache hits, misses)."""
        return (self._seconds["/jax/core/compile/backend_compile_duration"],
                self._events["/jax/compilation_cache/cache_hits"],
                self._events["/jax/compilation_cache/cache_misses"])

    def peak_bytes(self):
        out = []
        for d in self.devices:
            stats = d.memory_stats() or {}
            out.append(int(stats.get("peak_bytes_in_use", 0)))
        return out

    # -- contexts --------------------------------------------------------
    def ctx(self, i=0):
        import mxnet_tpu as mx

        # the rehearsal is the only place a host context is ever named
        return mx.cpu(i) if self.rehearsal else mx.tpu(i)

    def expect_mosaic(self, text, what):
        """On the chip a Pallas kernel must be a Mosaic custom call in the
        lowered step; the rehearsal runs the interpreter or the kernel's
        plain form and has none."""
        n = text.count(MOSAIC_CALL)
        if self.rehearsal:
            check(n == 0, "%s: Mosaic call in a CPU rehearsal" % what)
        else:
            check(n > 0, "%s: no Mosaic custom call in the lowered "
                         "program (the reference or the interpreter ran)"
                         % what)
        return n


def run_leg(smoke, name, fn, *args):
    """Run one leg; a leg that raises ends the smoke. Returns what the
    leg hands to a later one."""
    c0, h0, m0 = smoke.compile_stats()
    t0 = time.perf_counter()
    out = fn(smoke, *args)
    c1, h1, m1 = smoke.compile_stats()
    smoke.say(name, status="passed",
              wall_s="%.1f" % (time.perf_counter() - t0),
              compile_s="%.1f" % (c1 - c0), cache_hits=h1 - h0,
              cache_misses=m1 - m0,
              peak_hbm_bytes="/".join(str(b) for b in smoke.peak_bytes()))
    return out


# ---------------------------------------------------------------------------
# legs
# ---------------------------------------------------------------------------

def leg_device(smoke):
    import mxnet_tpu as mx
    from mxnet_tpu import native
    from mxnet_tpu.telemetry import costmodel

    peak = costmodel.peak_flops_for_kind(smoke.kind)
    smoke.say("device", kind=repr(smoke.kind), count=len(smoke.devices),
              peak_bf16_flops=peak,
              native="libmxtpu" if native.available() else "python-fallback",
              compile_cache=mx.base.compile_cache_dir())
    if not smoke.rehearsal:
        check(peak is not None,
              "device_kind %r is not in telemetry/costmodel's peak table "
              "(MFU would silently turn off)" % smoke.kind)


def leg_kernels(smoke):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import kernels as pk

    rng = np.random.RandomState(0)
    # in the rehearsal the interpreter stands in for Mosaic
    flash = functools.partial(pk.flash_attention,
                              interpret=smoke.rehearsal)
    # the shapes examples/train_transformer_lm.py uses by default
    B, T, H, D = 8, 256, 8, 32
    # the kernels feed the MXU at default precision, so f32 inputs see
    # bf16-pass rounding too (measured ~1e-2 on grads of scale ~4)
    for dtype, tol in ((jnp.bfloat16, 5e-2), (jnp.float32, 5e-2)):
        q, k, v = (jnp.asarray(rng.randn(B, T, H, D), dtype)
                   for _ in range(3))
        w = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)

        def loss(attn):
            return lambda q, k, v: jnp.sum(
                attn(q, k, v, causal=True).astype(jnp.float32) * w)

        fwd = jax.jit(lambda q, k, v: flash(q, k, v, causal=True))
        bwd = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))
        n_fwd = smoke.expect_mosaic(fwd.lower(q, k, v).as_text(),
                                    "flash forward")
        n_bwd = smoke.expect_mosaic(bwd.lower(q, k, v).as_text(),
                                    "flash backward")
        out = fwd(q, k, v)
        grads = bwd(q, k, v)
        q32, k32, v32 = (a.astype(jnp.float32) for a in (q, k, v))
        with jax.default_matmul_precision("highest"):
            ref = pk.reference_attention(q32, k32, v32, causal=True)
            ref_grads = jax.grad(loss(pk.reference_attention),
                                 argnums=(0, 1, 2))(q32, k32, v32)
        errs = [float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))]
        errs += [float(jnp.max(jnp.abs(g.astype(jnp.float32) - r)))
                 for g, r in zip(grads, ref_grads)]
        smoke.say("kernels", kernel="flash_attention",
                  dtype=jnp.dtype(dtype).name, shape="%dx%dx%dx%d" % (
                      B, T, H, D),
                  mosaic_calls="%d+%d" % (n_fwd, n_bwd),
                  max_abs_err="out=%.2e,dq=%.2e,dk=%.2e,dv=%.2e" % tuple(
                      errs), tol=tol)
        check(all(np.isfinite(e) and e <= tol for e in errs),
              "flash attention %s disagrees with the reference: %s"
              % (jnp.dtype(dtype).name, errs))


def _resnet(smoke, dtype):
    from mxnet_tpu.models.resnet import get_symbol

    s = smoke.size
    return get_symbol(
        num_classes=s.classes, num_layers=s.layers,
        image_shape=",".join(str(d) for d in s.image), dtype=dtype)


def _fit(smoke, mod, sym_dtype, tag):
    """A few fit() steps on one repeated synthetic batch; checks the
    per-step train cross-entropy. Each host-clock stamp is closed by
    the metric's host fetch of that step's outputs."""
    import mxnet_tpu as mx

    s = smoke.size
    rng = np.random.RandomState(0)
    X = rng.rand(s.batch, *s.image).astype(np.float32)
    y = rng.randint(0, s.classes, s.batch).astype(np.float32)
    it = mx.io.ResizeIter(
        mx.io.NDArrayIter(X, y, batch_size=s.batch), s.steps)
    losses, stamps = [], [time.perf_counter()]

    def on_batch(param):
        losses.append(param.eval_metric.get()[1])
        param.eval_metric.reset()
        stamps.append(time.perf_counter())

    mx.random.seed(0)
    np.random.seed(0)
    mod.fit(it, eval_metric="ce", optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
            kvstore="device", num_epoch=1,
            initializer=mx.init.Xavier(rnd_type="gaussian",
                                       factor_type="in", magnitude=2),
            batch_end_callback=on_batch)
    walls = np.diff(stamps)
    smoke.say(tag, model="resnet-%d" % s.layers, batch=s.batch,
              image="x".join(str(d) for d in s.image),
              sym_dtype=sym_dtype, steps=len(losses),
              loss=",".join("%.4f" % v for v in losses),
              first_step_s="%.1f" % walls[0],
              later_step_s_median="%.3f" % float(np.median(walls[1:])))
    check(len(losses) == s.steps, "fit ran %d of %d steps"
          % (len(losses), s.steps))
    check(all(np.isfinite(losses)), "non-finite loss: %s" % losses)
    check(losses[-1] < losses[0],
          "loss did not fall on a repeated batch: %s" % losses)


def _check_placement(smoke, mod, n_dev, tag):
    """Every parameter / aux / optimizer-state leaf lives on the mesh's
    devices, all of the expected platform; nothing sits on device 0
    alone when there are several."""
    import jax

    leaves = jax.tree_util.tree_leaves(
        (mod._fused_params, mod._fused_aux, mod._fused_opt))
    platforms = {d.platform for a in leaves for d in a.sharding.device_set}
    set_sizes = {len(a.sharding.device_set) for a in leaves}
    smoke.say(tag, leaves=len(leaves), leaf_platforms=sorted(platforms),
              leaf_device_set_sizes=sorted(set_sizes))
    check(platforms == {smoke.platform},
          "fused state on %s, expected %s" % (platforms, smoke.platform))
    check(set_sizes == {n_dev},
          "some leaf lives on %s devices, expected %d" % (set_sizes, n_dev))


def _inspect_step(smoke, mod, tag):
    """Lower the module's fused step again against its live state and
    abstract batch feeds (nothing runs): the lowered text shows which
    kernels the step contains, and compiling it — a cache hit — gives
    XLA's own account of the step's memory, which the allocator's
    peak_bytes_in_use may or may not include."""
    import jax
    import jax.numpy as jnp

    s = smoke.size
    trainer = mod._fused_trainer
    batch = {
        name: jax.ShapeDtypeStruct(shape, np.float32,
                                   sharding=trainer.batch_sharding())
        for name, shape in (("data", (s.batch,) + s.image),
                            ("softmax_label", (s.batch,)))}
    scalar = jnp.zeros((), jnp.float32)
    lowered = trainer._step.lower(
        mod._fused_params, mod._fused_aux, mod._fused_opt, batch,
        jnp.zeros((2,), jnp.uint32), scalar, scalar, scalar)
    mem = lowered.compile().memory_analysis()
    smoke.say(tag, step_argument_bytes=mem.argument_size_in_bytes,
              step_output_bytes=mem.output_size_in_bytes,
              step_alias_bytes=mem.alias_size_in_bytes,
              step_temp_bytes=mem.temp_size_in_bytes)
    return lowered.as_text()


def leg_train(smoke):
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import make_mesh

    sym = _resnet(smoke, "bfloat16")
    mod = mx.mod.Module(sym, context=smoke.ctx(0),
                        mesh=make_mesh(dp=1))
    _fit(smoke, mod, "bfloat16", "train")
    trainer = mod._fused_trainer
    check(trainer is not None, "dp=1 mesh + kvstore='device' did not "
                               "reach the fused ShardedTrainStep")
    param_dtypes = sorted({str(a.dtype) for a in mod._fused_params.values()})
    smoke.say("train", fused=True, flat_mode=trainer.flat_mode,
              amp=trainer.amp, param_dtypes=param_dtypes,
              update_kernel="reference (flat update needs dp>1)")
    check("bfloat16" in param_dtypes, "the bf16 symbol has no bf16 weight")
    _check_placement(smoke, mod, 1, "train")
    text = _inspect_step(smoke, mod, "train")
    check(MOSAIC_CALL not in text, "an opt-in Pallas kernel is in the "
                                   "default dp=1 step")
    return (sym,) + tuple(mod.get_params())


def leg_serve(smoke, sym, arg_params, aux_params):
    from mxnet_tpu import predict
    from mxnet_tpu.serving.engine import ServingEngine

    s = smoke.size
    tmp = tempfile.mkdtemp(prefix="chip_smoke_")
    bundle = os.path.join(tmp, "resnet.pred")
    predict.export_bundle(bundle, sym, arg_params, aux_params)
    pred = predict.load_bundle(bundle, {"data": (1,) + s.image},
                               ctx=smoke.ctx(0))
    os.remove(bundle)
    os.rmdir(tmp)
    engine = ServingEngine(pred, max_batch=s.serve_batch,
                           batch_timeout_ms=20.0).start()
    serve_devices = sorted({
        str(d) for fn in pred._serve_cache.values()
        for sh in fn._compiled.input_shardings[0] for d in sh.device_set})
    check(serve_devices == [str(smoke.ctx(0).jax_device)],
          "serving executables live on %s, not the context's device"
          % serve_devices)
    rng = np.random.RandomState(1)
    inputs = rng.rand(s.requests, *s.image).astype(np.float32)
    futures = [None] * s.requests

    def client(ids):
        for i in ids:
            futures[i] = engine.submit(data=inputs[i])

    n_clients = 3
    threads = [threading.Thread(target=client,
                                args=(range(k, s.requests, n_clients),))
               for k in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    check(all(f is not None for f in futures), "a client thread hung")
    rows = [f.result(timeout=300)[0] for f in futures]
    engine.drain()
    worst, solos = 0.0, []
    for i, row in enumerate(rows):
        check(row.shape == (s.classes,), "row %d shape %s" % (i, row.shape))
        check(np.all(np.isfinite(row)), "row %d not finite" % i)
        check(abs(float(row.sum()) - 1.0) < 1e-2,
              "row %d is not a distribution (sum %s)" % (i, row.sum()))
        solos.append(pred.predict(data=inputs[i][None])[0][0])
        worst = max(worst, float(np.max(np.abs(row - solos[-1]))))
    # how far apart different requests' answers are: the scale against
    # which "equal to solo" means the right row came back
    spread = min(float(np.max(np.abs(a - b)))
                 for i, a in enumerate(solos) for b in solos[i + 1:])
    smoke.say("serve", requests=s.requests, answered=len(rows),
              buckets=engine.batch_buckets, executables_on=serve_devices,
              max_abs_diff_vs_solo="%.2e" % worst,
              min_diff_between_requests="%.2e" % spread)
    # batched rows ride a different batch size than the solo forward, so
    # bf16 conv tilings may differ in the last bits; probabilities agree
    check(worst <= 2e-2, "engine rows differ from solo dispatch by %g"
          % worst)


def leg_generate(smoke):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models.transformer import (
        transformer_lm, transformer_lm_serving)
    from mxnet_tpu.serving.decode import GenerationEngine

    s = smoke.size
    init_fn, apply_fn = transformer_lm(**s.lm)
    params = jax.device_put(init_fn(0), smoke.devices[0])
    model = transformer_lm_serving(max_len=s.lm_max_len, **s.lm)
    engine = GenerationEngine(params, model, slots=4, max_len=s.lm_max_len)
    engine.compile(prompt_lengths=[s.prompt_short, s.prompt_long])
    # which attention each prefill bucket holds: the long bucket is
    # T >= 128 and must be the flash kernel on the chip, the short one
    # stays the reference by design
    long_T = max(engine.len_buckets)
    toks = jnp.zeros((1, long_T), jnp.int32)
    one = jnp.zeros((1,), jnp.int32)
    n_long = smoke.expect_mosaic(
        engine._prefill_fn.lower(params, engine._cache, toks, one,
                                 one + 1).as_text(),
        "prefill attention at T=%d" % long_T)
    engine.start(precompile=False)
    rng = np.random.RandomState(2)
    prompts = [rng.randint(1, s.lm["vocab"], n)
               for n in (s.prompt_long, s.prompt_short) * 3]
    futures = [engine.submit(p, max_new=s.new_tokens) for p in prompts]
    outs = [f.result(timeout=300) for f in futures]
    engine.drain()
    cache_platforms = {d.platform for a in jax.tree_util.tree_leaves(
        engine._cache) for d in a.sharding.device_set}
    check(cache_platforms == {smoke.platform},
          "KV cache on %s" % cache_platforms)

    # reference: one teacher-forced full forward per request; every
    # token the engine chose must be (within bf16 noise of) the argmax
    full = jax.jit(apply_fn)
    worst_gap = 0.0
    for prompt, toks in zip(prompts, outs):
        check(len(toks) == s.new_tokens, "got %d of %d tokens"
              % (len(toks), s.new_tokens))
        check(all(0 <= t < s.lm["vocab"] for t in toks),
              "token out of range: %s" % toks)
        seq = np.concatenate([prompt, toks[:-1]]).astype(np.int32)
        logits = np.asarray(full(params, seq[None]))[0]
        check(np.all(np.isfinite(logits)), "non-finite reference logits")
        for i, tok in enumerate(toks):
            row = logits[len(prompt) - 1 + i]
            worst_gap = max(worst_gap, float(row.max() - row[tok]))
    smoke.say("generate", model="transformer_lm d%d h%d l%d v%d" % (
                  s.lm["d_model"], s.lm["n_heads"], s.lm["n_layers"],
                  s.lm["vocab"]),
              requests=len(prompts), tokens=sum(len(t) for t in outs),
              prefill_T=long_T,
              prefill_attention="pallas x%d" % n_long if n_long
              else "plain form (no Mosaic off the chip)",
              cache_on=sorted(cache_platforms),
              worst_logit_gap_vs_full_forward="%.2e" % worst_gap)
    check(worst_gap <= 0.05,
          "a decoded token trails the full-forward argmax by %g logits"
          % worst_gap)


def leg_train_dpn(smoke):
    """The same ResNet-50 over all N chips under MXTPU_AMP=bf16: the
    first time the flat sharded update, the fp32 masters and the slab
    rule inside shard_map meet real devices."""
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx

    n = len(smoke.devices)
    sym = _resnet(smoke, "float32")
    os.environ["MXTPU_AMP"] = "bf16"
    try:
        mod = mx.mod.Module(
            sym, context=[smoke.ctx(i) for i in range(n)])
        _fit(smoke, mod, "float32+amp", "train_dp%d" % n)
        trainer = mod._fused_trainer
        check(trainer is not None, "multi-device kvstore='device' did "
                                   "not reach the fused step")
        _inspect_step(smoke, mod, "train_dp%d" % n)
    finally:
        os.environ.pop("MXTPU_AMP", None)
    tag = "train_dp%d" % n
    total, resident = trainer.opt_state_shard_info(mod._fused_opt)
    # the invariant of the AMP path, checked on the devices in one
    # program: working params == bf16(fp32 masters), element for element
    mismatched = int(jax.jit(lambda params, opt: sum(
        jnp.sum(params[k] != m.astype(jnp.bfloat16))
        for k, m in trainer.master_params_named(opt).items()))(
            mod._fused_params, mod._fused_opt))
    in_use = [int((d.memory_stats() or {}).get("bytes_in_use", 0))
              for d in smoke.devices]
    smoke.say(tag, fused=True, flat_mode=trainer.flat_mode,
              amp=trainer.amp, update_calls=len(trainer._flat_plan.buckets),
              opt_state_elements=total, resident_on_device0=resident,
              params_ne_bf16_masters=mismatched,
              bytes_in_use="/".join(str(b) for b in in_use))
    check(trainer.flat_mode == "shard", "flat_mode %r" % trainer.flat_mode)
    check(trainer.amp, "MXTPU_AMP=bf16 did not engage")
    check(resident * n <= total * 1.01 + 8 * n,
          "optimizer state not sharded: %d of %d elements on device 0"
          % (resident, total))
    check(mismatched == 0, "params != bf16(masters) in %d elements"
          % mismatched)
    if not smoke.rehearsal:
        check(all(b > 0 for b in in_use),
              "a device holds nothing: %s" % in_use)
    _check_placement(smoke, mod, n, tag)


# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="debugging aid: tiny sizes on a host without "
                         "the chip; prints no result object")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.rehearse_cpu:
        print("chip_smoke: JAX found platform=%s (%d devices), not a TPU; "
              "this script has no CPU branch" % (platform, len(devices)),
              file=sys.stderr)
        return 2
    if args.rehearse_cpu and platform == "tpu":
        print("chip_smoke: --rehearse-cpu on a TPU host; run without the "
              "flag", file=sys.stderr)
        return 2

    smoke = Smoke(args.rehearse_cpu)
    run_leg(smoke, "device", leg_device)
    run_leg(smoke, "kernels", leg_kernels)
    trained = run_leg(smoke, "train", leg_train)
    run_leg(smoke, "serve", leg_serve, *trained)
    run_leg(smoke, "generate", leg_generate)
    legs = ["device", "kernels", "train", "serve", "generate"]
    if len(devices) > 1:
        legs.append("train_dp%d" % len(devices))
        run_leg(smoke, legs[-1], leg_train_dpn)
    total_s, hits, misses = smoke.compile_stats()
    smoke.say("summary", legs=",".join(legs),
              compile_s="%.1f" % total_s, cache_hits=hits,
              cache_misses=misses)
    if args.rehearse_cpu:
        print(json.dumps({"rehearsal": True, "platform": platform,
                          "legs": legs}))
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": smoke.kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
