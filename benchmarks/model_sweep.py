#!/usr/bin/env python
"""Per-model training throughput: fill the BASELINE.md single-GPU table.

The reference publishes per-model K80 img/s at batch 32
(/root/reference/example/image-classification/README.md:147-157), which
BASELINE.md calls the per-chip throughput *shape*. This sweep measures
the table with a fused-step + K-scan-dispatch technique and reports
per-model vs_baseline multiples.

MODEL_ONLY=name runs one model per process (one compile per chip
call); rows merge by model into the shared result file (same regime +
platform only, atomic replace).

Rows per model: f32 batch-32 scan-K device rate (reference dtype and
batch — comparable to the K80 column) and bf16 scan-K (the TPU-native
configuration). alexnet uses batch 512, its per-GPU batch in the
reference's scaling table (README.md:287-291).

Run: MODEL_ONLY=resnet-152 python benchmarks/model_sweep.py
Smoke: SWEEP_SMOKE=1 python benchmarks/model_sweep.py  (tiny, CPU)
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SMOKE = os.environ.get("SWEEP_SMOKE") == "1"
SCAN_K = int(os.environ.get("SWEEP_SCAN_K", "2" if SMOKE else "8"))
DISPATCHES = int(os.environ.get("SWEEP_DISPATCHES", "1" if SMOKE else "3"))

# name -> (builder kwargs, data hw, batch, reference K80 img/s)
# baselines: example/image-classification/README.md:147-157 (b32 rows)
# and :294 (alexnet 1-GPU row of the scaling table, batch 512).
MODELS = {
    "inception-bn": (("inception_bn", {}), 224, 32, 152.0),
    "resnet-18": (("resnet", {"num_layers": 18}), 224, 32, 185.0),
    "resnet-34": (("resnet", {"num_layers": 34}), 224, 32, 172.0),
    "resnet-101": (("resnet", {"num_layers": 101}), 224, 32, 78.0),
    "resnet-152": (("resnet", {"num_layers": 152}), 224, 32, 57.0),
    "inception-v3": (("inception_v3", {}), 299, 32, 30.4),
    "alexnet": (("alexnet", {}), 224, 512, 457.07),
}


def build_symbol(module, kwargs, hw):
    import importlib

    mod = importlib.import_module("mxnet_tpu.models." + module)
    if "image_shape" in mod.get_symbol.__code__.co_varnames:
        kwargs = dict(kwargs, image_shape="3,%d,%d" % (hw, hw))
    return mod.get_symbol(num_classes=1000, **kwargs)


def measure(jax, jnp, name, bf16):
    """One fused-train-step K-scan measurement; returns a result row."""
    from mxnet_tpu.executor import _GraphProgram

    (module, kwargs), hw, batch, base = MODELS[name]
    if SMOKE:
        # smallest spatial size each stem supports: inception-v3's
        # tower needs >=128, alexnet's stride-4 stem + fixed fc1
        # underflows below the real 224
        batch = 2
        hw = {"inception_v3": 128, "alexnet": 224}.get(module, 64)
    sym = build_symbol(module, kwargs, hw)
    program = _GraphProgram(sym)
    data_shape = (batch, 3, hw, hw)
    arg_shapes, _, aux_shapes = sym.infer_shape(
        data=data_shape, softmax_label=(batch,))
    rng = np.random.RandomState(0)
    params, aux = {}, {}
    for n, s in zip(sym.list_arguments(), arg_shapes):
        if n in ("data", "softmax_label"):
            continue
        if n.endswith("_gamma"):
            params[n] = np.ones(s, np.float32)
        elif n.endswith(("_beta", "_bias")):
            params[n] = np.zeros(s, np.float32)
        else:
            fan_in = int(np.prod(s[1:])) or 1
            params[n] = (rng.randn(*s) * np.sqrt(2.0 / fan_in)).astype(
                np.float32)
    aux = {n: (np.ones(s, np.float32) if n.endswith("var")
               else np.zeros(s, np.float32))
           for n, s in zip(sym.list_auxiliary_states(), aux_shapes)}

    lr, momentum, wd = 0.1, 0.9, 1e-4
    moms = {n: np.zeros_like(v) for n, v in params.items()}

    # models with Dropout (alexnet, vgg) need an rng at train time; a
    # fixed key is fine for throughput measurement
    rng_key = jax.random.PRNGKey(0)

    def train_step(ps, ms, ax, data, label):
        def loss_fn(p):
            if bf16:
                p = {n: v.astype(jnp.bfloat16) for n, v in p.items()}
            args = dict(p)
            args["data"] = data.astype(jnp.bfloat16) if bf16 else data
            args["softmax_label"] = label
            outs, new_ax = program(args, ax, rng_key, True)
            return jnp.sum(outs[0].astype(jnp.float32)), new_ax

        grads, new_ax = jax.grad(loss_fn, has_aux=True)(ps)
        new_ps, new_ms = {}, {}
        for n in ps:
            g = grads[n] / batch + wd * ps[n]
            m = momentum * ms[n] - lr * g
            new_ps[n] = ps[n] + m
            new_ms[n] = m
        return new_ps, new_ms, new_ax

    def k_steps(ps, ms, ax, data, label):
        def body(carry, _):
            p, m, a = carry
            return train_step(p, m, a, data, label), None
        (p, m, a), _ = jax.lax.scan(
            body, (ps, ms, ax), None, length=SCAN_K)
        return p, m, a

    step = jax.jit(k_steps, donate_argnums=(0, 1, 2))
    ps = {k: jnp.asarray(v) for k, v in params.items()}
    ms = {k: jnp.asarray(v) for k, v in moms.items()}
    ax = {k: jnp.asarray(v) for k, v in aux.items()}
    data = jnp.asarray(rng.rand(*data_shape), jnp.float32)
    label = jnp.asarray(rng.randint(0, 1000, batch), jnp.float32)

    t0 = time.perf_counter()
    ps, ms, ax = step(ps, ms, ax, data, label)  # compile + warm
    float(list(ps.values())[0].ravel()[0])
    compile_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    for _ in range(DISPATCHES):
        ps, ms, ax = step(ps, ms, ax, data, label)
    float(list(ps.values())[0].ravel()[0])
    dt = time.perf_counter() - t0

    n_steps = DISPATCHES * SCAN_K
    img_s = batch * n_steps / dt
    row = {
        "model": name, "batch": batch,
        "dtype": "bf16" if bf16 else "f32",
        "images_per_sec": round(img_s, 2),
        "step_ms": round(1000.0 * dt / n_steps, 2),
        "compile_s": round(compile_s, 1),
    }
    if not bf16 and not SMOKE:
        row["vs_baseline"] = round(img_s / base, 2)
        row["baseline_img_s"] = base
    return row


def main():
    import jax

    if SMOKE:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    dev = jax.devices()[0]
    names = list(MODELS)
    if os.environ.get("MODEL_ONLY"):
        names = [n.strip() for n in os.environ["MODEL_ONLY"].split(",")]
        unknown = set(names) - set(MODELS)
        if unknown:
            raise SystemExit("MODEL_ONLY unknown: %s" % sorted(unknown))
    if SMOKE:
        names = names[:1]

    rows = []
    for name in names:
        for bf16 in (False, True):
            try:
                rows.append(measure(jax, jnp, name, bf16))
            except Exception as e:  # noqa: BLE001 — record, keep sweeping
                rows.append({"model": name,
                             "dtype": "bf16" if bf16 else "f32",
                             "error": str(e)[:300]})
            print(json.dumps(rows[-1]), file=sys.stderr, flush=True)

    tag = os.environ.get("SWEEP_TAG", "smoke" if SMOKE else "v5e_r4")
    res_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "results")
    path = os.path.join(res_dir, "model_sweep_%s.json" % tag)
    # merge by (model, dtype): fresh wins; same regime + platform only
    try:
        with open(path) as f:
            prior = json.load(f)
        if (prior.get("scan_k"), prior.get("platform")) == (
                SCAN_K, dev.platform):
            fresh = {(r.get("model"), r.get("dtype")) for r in rows}
            rows = [r for r in prior.get("rows", [])
                    if (r.get("model"), r.get("dtype")) not in fresh] + rows
    except (FileNotFoundError, ValueError):
        pass
    order = {n: i for i, n in enumerate(MODELS)}
    rows.sort(key=lambda r: (order.get(r.get("model"), 99), r.get("dtype")))
    out = {"scan_k": SCAN_K, "platform": dev.platform,
           "device_kind": getattr(dev, "device_kind", "?"), "rows": rows}
    with open(path + ".tmp", "w") as f:
        json.dump(out, f, indent=1)
    os.replace(path + ".tmp", path)
    print(json.dumps({"written": path, "rows": len(rows)}))


if __name__ == "__main__":
    main()
