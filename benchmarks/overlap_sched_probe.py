#!/usr/bin/env python
"""Measure what overlap XLA actually SCHEDULES for the gradient
all-reduces of the 8-device ShardedTrainStep.

The scaling model's >=90% weak-scaling claim assumed XLA hides most of
the allreduce behind backward compute. This probe replaces
that assumption with evidence from the compiled program itself: the
optimized HLO of jit(step) is SCHEDULED (`is_scheduled=true` — the
text order of the entry computation IS the execution order), so we can
read off, for every collective:

  * whether it was converted to an async start/done pair (overlap is
    only possible at all for async collectives);
  * how many substantive compute instructions (fusions, convolutions,
    dots) are scheduled inside each start->done window;
  * the fraction of collective BYTES whose start is scheduled before
    the last backward compute instruction (the "overlap opportunity"
    coefficient: bytes that CAN ride behind remaining compute).

Modes:
  OSP_MODE=cpu (default)  8-device virtual CPU mesh. This is the same
      backend the dryrun gate uses; note the CPU pipeline has no
      latency-hiding scheduler, so its result is the floor, not the
      TPU expectation.
  OSP_MODE=tpu_aot        AOT-compile the same program for a v5e 2x4
      topology (no 8-chip hardware needed — compile only). This is the
      pipeline whose scheduler the claim is about.

Output: benchmarks/results/overlap_sched_<mode>_<tag>.json
"""
from __future__ import annotations

import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MODE = os.environ.get("OSP_MODE", "cpu")
LAYERS = int(os.environ.get("OSP_LAYERS", "50"))
BATCH = int(os.environ.get("OSP_BATCH", "32"))  # per chip
TAG = os.environ.get("OSP_TAG", "r5")
DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
               "pred": 1, "u8": 1, "s8": 1}

COLLECTIVE_RE = re.compile(
    r"^\s*(%?\S+)\s*=\s*(\(.*?\)|\S+)\s+"
    r"(all-reduce-start|all-reduce-done|all-reduce|"
    r"reduce-scatter|all-gather-start|all-gather-done|all-gather|"
    r"collective-permute-start|collective-permute-done|collective-permute)"
    r"\(")
COMPUTE_RE = re.compile(
    r"^\s*%?\S+\s*=\s*\S+\s+(fusion|convolution|dot|custom-call)\(")
SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def shape_bytes(blob):
    total = 0
    for m in SHAPE_RE.finditer(blob):
        dt, dims = m.group(1), m.group(2)
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def entry_body_lines(hlo_text):
    """Lines of the ENTRY computation in schedule order."""
    m = re.search(r"^ENTRY [^{]*\{$(.*?)^\}", hlo_text,
                  re.M | re.S)
    if m is None:
        # fall back: largest computation block
        blocks = re.findall(r"^\S?ENTRY?[^{]*\{$(.*?)^\}", hlo_text,
                            re.M | re.S)
        if not blocks:
            raise ValueError("no ENTRY computation found")
        m = max(blocks, key=len)
        return m.splitlines()
    return m.group(1).splitlines()


def analyze(hlo_text):
    assert "is_scheduled=true" in hlo_text.splitlines()[0], \
        "HLO is not scheduled; text order would be meaningless"
    lines = entry_body_lines(hlo_text)
    events = []  # (idx, kind, name, bytes)
    for i, ln in enumerate(lines):
        cm = COLLECTIVE_RE.match(ln)
        if cm:
            events.append((i, cm.group(3), cm.group(1),
                           shape_bytes(cm.group(2))))
            continue
        if COMPUTE_RE.match(ln):
            events.append((i, "compute", None, 0))

    compute_idx = [i for i, k, _, _ in events if k == "compute"]
    last_compute = compute_idx[-1] if compute_idx else -1
    colls = [(i, k, n, b) for i, k, n, b in events if k != "compute"]

    sync_kinds = {"all-reduce", "reduce-scatter", "all-gather",
                  "collective-permute"}
    total_bytes = 0
    overlappable_bytes = 0
    async_pairs = 0
    sync_colls = 0
    windows = []
    for i, k, n, b in colls:
        if k.endswith("-done"):
            continue
        if k.endswith("-start"):
            async_pairs += 1
            # matching done = the -done whose operand list references
            # THIS start's name (overlapping same-kind starts make
            # "next done of the same kind" pair wrongly: start A,
            # start B, done A, done B would give B the window [B, doneA])
            name = n.lstrip("%")
            # (?![\w.]) = full-name match: %all-reduce-start must not
            # pair with a done consuming %all-reduce-start.1
            done_i = next(
                (j for j, kk, _, _ in colls
                 if kk == k.replace("-start", "-done")
                 and re.search(r"\(\s*%?" + re.escape(name) + r"(?![\w.])",
                               lines[j])),
                None)
            # payload bytes: the DONE's result shape (a start's printed
            # shape is a tuple carrying operand aliases — counting it
            # double-counts the transfer)
            if done_i is not None:
                dm = COLLECTIVE_RE.match(lines[done_i])
                b = shape_bytes(dm.group(2)) if dm else b
            total_bytes += b
            inside = sum(1 for ci in compute_idx
                         if done_i is not None and i < ci < done_i)
            windows.append({"start_line": i, "done_line": done_i,
                            "bytes": b, "compute_ops_inside": inside})
            if inside > 0 or (i < last_compute):
                overlappable_bytes += b
        elif k in sync_kinds:
            sync_colls += 1
            total_bytes += b
            # a sync collective can still be followed by compute it
            # does NOT depend on only if the scheduler put compute
            # after it; count bytes as overlappable only in that case
            if i < last_compute:
                overlappable_bytes += b

    return {
        "scheduled": True,
        "entry_instructions": len(lines),
        "compute_instructions": len(compute_idx),
        "collectives_sync": sync_colls,
        "collectives_async_pairs": async_pairs,
        "collective_bytes_total": total_bytes,
        "collective_bytes_with_compute_after_start": overlappable_bytes,
        "overlap_opportunity_coeff": (
            round(overlappable_bytes / total_bytes, 4)
            if total_bytes else None),
        "async_windows": windows[:12],
        "last_compute_line": last_compute,
        "first_collective_line": colls[0][0] if colls else None,
    }


def build_step(jax, mesh):
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.parallel.train_step import ShardedTrainStep
    from mxnet_tpu.models.resnet import get_symbol

    sym = get_symbol(num_classes=1000, num_layers=LAYERS)
    n_dev = mesh.devices.size
    st = ShardedTrainStep(
        sym, mesh,
        optimizer=mx.optimizer.create("sgd", learning_rate=0.1,
                                      momentum=0.9)).compile()
    data_shape = (BATCH * n_dev, 3, 224, 224)
    arg_shapes, _, aux_shapes = sym.infer_shape(
        data=data_shape, softmax_label=(BATCH * n_dev,))
    rng = np.random.RandomState(0)
    args = {}
    for name, shp in zip(sym.list_arguments(), arg_shapes):
        if name in ("data", "softmax_label"):
            continue
        args[name] = (rng.randn(*shp) * 0.01).astype("f")
    auxs = {name: np.zeros(shp, "f") if "var" not in name
            else np.ones(shp, "f")
            for name, shp in zip(sym.list_auxiliary_states(), aux_shapes)}
    params, aux = st.place_params(args, auxs)
    opt = st.make_state(params)
    import jax.numpy as jnp

    batch = {
        "data": jax.device_put(
            rng.rand(*data_shape).astype("f"), st.batch_sharding()),
        "softmax_label": jax.device_put(
            rng.randint(0, 1000, data_shape[0]).astype("f"),
            st.batch_sharding()),
    }
    lowered = st._step.lower(
        params, aux, opt, batch, jnp.zeros((2,), jnp.uint32),
        jnp.asarray(0.1, jnp.float32), jnp.asarray(1.0, jnp.float32),
        jnp.asarray(jnp.inf, jnp.float32))  # guard gate open
    return lowered


def main():
    out = {"mode": MODE, "model": "resnet-%d b%d/chip dp8" % (LAYERS, BATCH)}
    if MODE == "cpu":
        from __graft_entry__ import _force_cpu_mesh_platform

        _force_cpu_mesh_platform(8)
        import numpy as np

        import jax
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices()[:8]), ("dp",))
        lowered = build_step(jax, mesh)
        txt = lowered.compile().as_text()
        out["backend"] = "cpu (8 virtual devices; no latency-hiding "
        out["backend"] += "scheduler in this pipeline — floor, not "
        out["backend"] += "TPU expectation)"
        out.update(analyze(txt))
    elif MODE == "tpu_aot":
        import jax
        from jax.experimental import topologies

        topo = None
        errors = {}
        for name, kw in (
                ("v5e:2x4", {}),
                ("v5litepod-8", {}),
                ("", {"platform": "tpu", "topology": "2x4x1"}),
        ):
            try:
                topo = topologies.get_topology_desc(name, **kw)
                out["topology"] = name or str(kw)
                break
            except Exception as e:  # noqa: BLE001 — try the next spelling
                errors[name or str(kw)] = str(e)[:200]
        if topo is None:
            out["error"] = "no topology description available"
            out["attempts"] = errors
        else:
            from jax.sharding import Mesh
            import numpy as np

            mesh = Mesh(np.array(topo.devices).reshape(-1)[:8], ("dp",))
            lowered = build_step(jax, mesh)
            txt = lowered.compile().as_text()
            out["backend"] = "tpu v5e AOT (2x4 topology, compile only)"
            out.update(analyze(txt))
    else:
        raise SystemExit("unknown OSP_MODE %r" % MODE)

    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "results",
        "overlap_sched_%s_%s.json" % (MODE, TAG))
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: v for k, v in out.items()
                      if k != "async_windows"}))
    return 1 if "error" in out else 0


if __name__ == "__main__":
    sys.exit(main())
