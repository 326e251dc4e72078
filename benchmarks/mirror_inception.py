"""Memory-mirror proof on real TPU: inception-v3 batch 128 (BASELINE.md
row 'inception-v3 w/ memory mirror (batch 32->128)': the reference fits
batch 128 on a 12 GB K80 only with MXNET_BACKWARD_DO_MIRROR=1 at a
30->27 img/s cost, example/image-classification/README.md:357-359).

Runs the fused train step with and without the mirror and prints ONE
JSON line: compiled temp memory (XLA memory_analysis) and step time for
both. Expected: mirror cuts activation temp memory materially, costing
some recompute throughput — mirroring (pun intended) the reference's
tradeoff. Usage: python benchmarks/mirror_inception.py [batch]
"""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_step(mirror, batch):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.executor import _GraphProgram
    from mxnet_tpu.models.inception_v3 import get_symbol

    if mirror:
        os.environ["MXNET_BACKWARD_DO_MIRROR"] = "1"
    else:
        os.environ.pop("MXNET_BACKWARD_DO_MIRROR", None)

    sym = get_symbol(num_classes=1000)
    program = _GraphProgram(sym)
    data_shape = (batch, 3, 299, 299)
    arg_shapes, _, aux_shapes = sym.infer_shape(
        data=data_shape, softmax_label=(batch,))
    arg_names = sym.list_arguments()
    aux_names = sym.list_auxiliary_states()
    rng = np.random.RandomState(0)
    params = {}
    for n, s in zip(arg_names, arg_shapes):
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
           for n, s in zip(aux_names, aux_shapes)}

    from mxnet_tpu.executor import _mirror_enabled, _mirror_policy

    do_mirror = _mirror_enabled()
    assert do_mirror == mirror

    def train_step(params, aux, data, label):
        def loss_fn(ps):
            args = dict(ps)
            args["data"] = data
            args["softmax_label"] = label
            outs, new_aux = program(args, aux, None, True)
            return jnp.sum(outs[0]), new_aux

        if do_mirror:
            loss_fn = jax.checkpoint(loss_fn, policy=_mirror_policy)
        grads, new_aux = jax.grad(loss_fn, has_aux=True)(params)
        new_params = {n: params[n] - 0.01 * grads[n] for n in params}
        return new_params, new_aux

    step = jax.jit(train_step, donate_argnums=(0, 1))
    data = jnp.asarray(rng.rand(*data_shape), jnp.float32)
    label = jnp.asarray(rng.randint(0, 1000, batch), jnp.float32)
    params = {k: jnp.asarray(v) for k, v in params.items()}
    aux = {k: jnp.asarray(v) for k, v in aux.items()}
    return step, params, aux, data, label


def measure(mirror, batch, steps=5, save=None):
    import jax

    if save is not None:
        os.environ["MXNET_MIRROR_SAVE"] = save
    else:
        os.environ.pop("MXNET_MIRROR_SAVE", None)
    step, params, aux, data, label = build_step(mirror, batch)
    t0 = time.perf_counter()
    compiled = step.lower(params, aux, data, label).compile()
    compile_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    params, aux = compiled(params, aux, data, label)  # warm
    # force completion via a scalar host fetch
    float(list(params.values())[0].ravel()[0])
    t0 = time.perf_counter()
    for _ in range(steps):
        params, aux = compiled(params, aux, data, label)
    float(list(params.values())[0].ravel()[0])
    dt = (time.perf_counter() - t0) / steps
    return {
        "temp_bytes": int(mem.temp_size_in_bytes),
        "step_ms": round(1000 * dt, 1),
        "img_s": round(batch / dt, 1),
        "compile_s": round(compile_s, 1),
    }


# Policy sweep (VERDICT r3 weak #4: 19% throughput cost vs the
# reference's 10% — the remat set is the knob). Each variant saves
# MORE residual classes, trading memory back for recompute time:
#   +pool:   pin pooling outputs (reduce_window) — cheap memory,
#            cuts the pool->conv recompute chains
#   +concat: also pin Concat outputs (the reference's need_mirror
#            keeps Concat, graph_executor.cc)
#   +div:    also pin the BN custom_vjp reduces (mul/add chains stay
#            rematerialized)
_BASE_SAVE = "dot_general,conv_general_dilated"
VARIANTS = {
    "plain": (False, None),
    "mirror": (True, None),
    "mirror_pool": (True, _BASE_SAVE + ",reduce_window_max,"
                    "reduce_window_sum,reduce_window"),
    "mirror_pool_concat": (True, _BASE_SAVE + ",reduce_window_max,"
                           "reduce_window_sum,reduce_window,concatenate"),
    "mirror_pool_concat_div": (True, _BASE_SAVE + ",reduce_window_max,"
                               "reduce_window_sum,reduce_window,"
                               "concatenate,div,rsqrt"),
}


def main():
    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 128
    # MIRROR_ONLY=v1,v2 runs a subset in THIS process and merges into
    # the shared result file (5 inception compiles are slow; a subset
    # fits a short chip call).
    names = list(VARIANTS)
    if os.environ.get("MIRROR_ONLY"):
        names = [n.strip() for n in os.environ["MIRROR_ONLY"].split(",")]
        unknown = set(names) - set(VARIANTS)
        if unknown:
            raise SystemExit("MIRROR_ONLY unknown: %s" % sorted(unknown))
    out = {"model": "inception_v3", "batch": batch}
    path = None
    if os.environ.get("MIRROR_TAG"):
        path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "results",
            "mirror_sweep_%s.json" % os.environ["MIRROR_TAG"])
        try:
            with open(path) as f:
                prior = json.load(f)
            if prior.get("batch") == batch:
                out.update({k: v for k, v in prior.items()
                            if k in VARIANTS})
        except (FileNotFoundError, ValueError):
            pass
    for tag in names:
        mirror, save = VARIANTS[tag]
        try:
            out[tag] = measure(mirror, batch, save=save)
            if save:
                out[tag]["save_set"] = save
        except Exception as e:  # noqa: BLE001 — record, keep sweeping
            out[tag] = {"error": str(e)[:200]}
    plain_temp = out.get("plain", {}).get("temp_bytes")
    if plain_temp:
        for tag in VARIANTS:
            if tag != "plain" and "temp_bytes" in out.get(tag, {}):
                out[tag]["temp_ratio"] = round(
                    out[tag]["temp_bytes"] / max(plain_temp, 1), 3)
        if "temp_ratio" in out.get("mirror", {}):
            out["temp_ratio"] = out["mirror"]["temp_ratio"]
    if path:
        with open(path + ".tmp", "w") as f:
            json.dump(out, f, indent=1)
        os.replace(path + ".tmp", path)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
