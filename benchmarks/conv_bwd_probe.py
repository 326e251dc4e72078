#!/usr/bin/env python
"""Per-shape conv fwd / input-grad / filter-grad timing for ResNet-50.

Conv backward is the suspected sink of the ResNet-50 device step (not
measured; see PERF.md). This probe answers *which* backward — the input
gradient (dgrad) or the filter gradient (wgrad) — of *which* layer
shapes, and whether an explicit NHWC layout fixes it, without guessing
from whole-graph numbers.

Method: every distinct Convolution configuration is pulled from the
real `models/resnet.get_symbol(50)` graph (with multiplicity), then
each of fwd / dgrad / wgrad is timed as its own K-iteration
`lax.scan` program (one dispatch per measurement, so the wall rate
approaches the device rate).
A tiny data-dependent perturbation of the carry defeats CSE/DCE
without changing the measured op.

Output: one JSON (benchmarks/results/conv_bwd_probe_<tag>.json) with
per-shape ms and TFLOP/s for every (pass, layout, dtype) and the
multiplicity-weighted totals that should reproduce the step trace's
conv time.

Run on the chip:  python benchmarks/conv_bwd_probe.py
Smoke (CPU):      PROBE_SMOKE=1 python benchmarks/conv_bwd_probe.py
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SMOKE = os.environ.get("PROBE_SMOKE") == "1"
BATCH = int(os.environ.get("PROBE_BATCH", "4" if SMOKE else "256"))
SCAN_K = int(os.environ.get("PROBE_SCAN_K", "2" if SMOKE else "8"))
REPS = int(os.environ.get("PROBE_REPS", "1" if SMOKE else "3"))
PEAK_TFLOPS = 197.0  # v5e bf16 spec; only used for the %-of-peak column


def collect_conv_configs(batch):
    """(data_shape, w_shape, stride, pad, groups) -> multiplicity, from
    the flagship ResNet-50 graph at the bench batch size."""
    from mxnet_tpu.models.resnet import get_symbol

    sym = get_symbol(num_classes=1000, num_layers=50)
    env = sym._infer_shape_env(data=(batch, 3, 224, 224),
                               softmax_label=(batch,))
    from mxnet_tpu.symbol import _topo_order

    configs = {}
    for node in _topo_order([n for n, _ in sym._outputs]):
        if node.is_variable or node.op.name != "Convolution":
            continue
        attrs = node.canon_attrs()
        dshape = env[(id(node.inputs[0][0]), node.inputs[0][1])]
        wshape = env[(id(node.inputs[1][0]), node.inputs[1][1])]
        from mxnet_tpu.ops.utils import as_tuple

        kernel = as_tuple(attrs["kernel"])
        nd = len(kernel)
        stride = as_tuple(attrs.get("stride") or (1,) * nd, nd, "stride")
        pad = as_tuple(attrs.get("pad") or (0,) * nd, nd, "pad")
        groups = int(attrs.get("num_group", 1))
        key = (tuple(dshape), tuple(wshape), stride, pad, groups)
        configs[key] = configs.get(key, 0) + 1
    return configs


def conv_flops(dshape, wshape, stride, pad):
    n, c, h, w = dshape
    o, cg, kh, kw = wshape
    oh = (h + 2 * pad[0] - kh) // stride[0] + 1
    ow = (w + 2 * pad[1] - kw) // stride[1] + 1
    return 2.0 * n * o * oh * ow * cg * kh * kw


def _dn(layout):
    import jax

    if layout == "NCHW":
        spec = ("NCHW", "OIHW", "NCHW")
    else:
        spec = ("NHWC", "HWIO", "NHWC")
    return jax.lax.conv_dimension_numbers((1, 1, 1, 1), (1, 1, 1, 1), spec)


def build_pass(jax, jnp, pass_name, layout, dtype,
               dshape, wshape, stride, pad, groups):
    """Return (jitted K-scan fn, init args) for one measured pass."""
    dn = _dn(layout)
    n, c, h, w = dshape
    o, cg, kh, kw = wshape
    # pad entries: int (symmetric) or (lo, hi) — the s2d stem needs
    # asymmetric padding to stay mathematically equivalent
    pads = [p if isinstance(p, tuple) else (p, p) for p in pad]
    oh = (h + sum(pads[0]) - kh) // stride[0] + 1
    ow = (w + sum(pads[1]) - kw) // stride[1] + 1
    if layout == "NCHW":
        x_shape, w_shape2, y_shape = dshape, wshape, (n, o, oh, ow)
    else:
        x_shape, w_shape2, y_shape = (
            (n, h, w, c), (kh, kw, cg, o), (n, oh, ow, o))

    def conv(x, wt):
        return jax.lax.conv_general_dilated(
            x, wt, window_strides=stride,
            padding=pads,
            dimension_numbers=dn, feature_group_count=groups)

    rng = np.random.RandomState(0)
    x0 = jnp.asarray(rng.randn(*x_shape) * 0.1, dtype)
    w0 = jnp.asarray(rng.randn(*w_shape2) * 0.1, dtype)
    ct0 = jnp.asarray(rng.randn(*y_shape) * 0.1, dtype)

    eps = jnp.asarray(1e-6, dtype)  # keeps the scan body live, value ~0

    if pass_name == "fwd":
        def body(x, _):
            y = conv(x, w0)
            return x + eps * y.mean().astype(dtype), None
    elif pass_name == "dgrad":
        def body(ct, _):
            _, vjp = jax.vjp(lambda xx: conv(xx, w0), x0)
            (gx,) = vjp(ct)
            return ct + eps * gx.mean().astype(dtype), None
    elif pass_name in ("wgrad_patches", "wgrad_taps"):
        # the wgrad LEVERS (ops/nn.py), per shape: vjp w.r.t. the
        # weight routes through each lever's custom filter gradient.
        # NCHW / symmetric pads / groups==1 only (the levers' own gate).
        from mxnet_tpu.ops import nn as _nn

        lever = (_nn._conv2d_wgrad_patches
                 if pass_name == "wgrad_patches"
                 else _nn._conv2d_wgrad_taps)
        pad_ints = tuple(p[0] for p in pads)

        def body(ct, _):
            _, vjp = jax.vjp(
                lambda ww: lever(x0, ww, stride, pad_ints, (1, 1)), w0)
            (gw,) = vjp(ct)
            return ct + eps * gw.mean().astype(dtype), None
    else:  # wgrad
        def body(ct, _):
            _, vjp = jax.vjp(lambda ww: conv(x0, ww), w0)
            (gw,) = vjp(ct)
            return ct + eps * gw.mean().astype(dtype), None

    def k_scan(carry):
        out, _ = jax.lax.scan(body, carry, None, length=SCAN_K)
        return out

    init = x0 if pass_name == "fwd" else ct0
    return jax.jit(k_scan), init


def time_pass(jax, jnp, fn, init):
    out = fn(init)
    float(out.ravel()[0].astype(jnp.float32))  # compile + warm
    t0 = time.perf_counter()
    for _ in range(REPS):
        out = fn(out)
    float(out.ravel()[0].astype(jnp.float32))
    dt = time.perf_counter() - t0
    return 1000.0 * dt / (REPS * SCAN_K)  # ms per single pass


def _sweep_items(jax, jnp, items, dtypes, layouts, passes, rows, totals,
                 flush=None):
    """Measure every (config, dtype, layout, pass); appends to rows/
    totals in place; `flush` (if given) persists the rows after EVERY
    measurement, so a call killed at its time limit keeps what landed."""
    for (dshape, wshape, stride, pad, groups), mult in items:
        flops = conv_flops(dshape, wshape, stride, pad)
        for dt_name, dt in dtypes:
            for layout in layouts:
                row_passes = passes
                if (layout == "NCHW" and groups == 1
                        and not any(isinstance(p, tuple) for p in pad)
                        and os.environ.get("PROBE_WGRAD_LEVERS") == "1"):
                    # per-shape lever comparison (one extra compile per
                    # lever per shape — opt-in to keep the default
                    # sweep's compile count unchanged)
                    row_passes = passes + ("wgrad_patches", "wgrad_taps")
                for p in row_passes:
                    fn, init = build_pass(
                        jax, jnp, p, layout, dt,
                        dshape, wshape, stride, pad, groups)
                    try:
                        ms = time_pass(jax, jnp, fn, init)
                    except Exception as e:  # noqa: BLE001 — record, keep going
                        rows.append({"dshape": dshape, "wshape": wshape,
                                     "pass": p, "layout": layout,
                                     "dtype": dt_name, "error": str(e)[:200]})
                        continue
                    tf = flops / (ms / 1000.0) / 1e12
                    rows.append({
                        "dshape": list(dshape), "wshape": list(wshape),
                        "stride": list(stride), "pad": list(pad),
                        "mult": mult, "pass": p, "layout": layout,
                        "dtype": dt_name, "ms": round(ms, 3),
                        "tflops": round(tf, 1),
                        "pct_peak": round(100 * tf / PEAK_TFLOPS, 1),
                    })
                    key = (dt_name, layout, p)
                    totals[key] = totals.get(key, 0.0) + ms * mult
                    print("%-28s %-5s %-5s %-4s %8.3f ms  %6.1f TF/s "
                          "(%4.1f%%) x%d"
                          % (str(dshape), dt_name, layout, p, ms, tf,
                             100 * tf / PEAK_TFLOPS, mult),
                          file=sys.stderr)
                    if flush is not None:
                        flush()


def main():
    import jax
    import jax.numpy as jnp

    if SMOKE:
        jax.config.update("jax_platforms", "cpu")
    dev = jax.devices()[0]
    tag = os.environ.get("PROBE_TAG", "smoke" if SMOKE else "v5e_r4")
    configs = collect_conv_configs(BATCH)
    print("distinct conv configs: %d (batch %d)" % (len(configs), BATCH),
          file=sys.stderr)

    dtypes = [("bf16", jnp.bfloat16)] if not SMOKE else [("f32", jnp.float32)]
    if os.environ.get("PROBE_F32") == "1":
        dtypes.append(("f32", jnp.float32))
    layouts = ("NCHW", "NHWC")
    passes = ("fwd", "dgrad", "wgrad")

    rows = []
    totals = {}
    items = sorted(configs.items(), key=lambda kv: -conv_flops(*kv[0][:4]))
    if SMOKE:
        items = items[:2]
    # PROBE_TOP bounds the compile count (each (config, pass, layout,
    # dtype) is its own compile — the full 23-config sweep is ~138).
    # Dropped configs are logged so the sweep never silently reads as
    # exhaustive.
    top = int(os.environ.get("PROBE_TOP", "0"))
    if top and len(items) > top:
        dropped = items[top:]
        print("PROBE_TOP=%d: dropping %d configs (%.1f%% of weighted "
              "flops)" % (top, len(dropped),
                          100 * sum(conv_flops(*k[:4]) * m
                                    for k, m in dropped)
                          / sum(conv_flops(*k[:4]) * m
                                for k, m in items)),
              file=sys.stderr)
        items = items[:top]
    from mxnet_tpu.resilience.checkpoint import atomic_file as _atomic

    result_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "results",
        "conv_bwd_probe_%s.json" % tag)

    def _flush_rows():
        snap = {
            "batch": BATCH, "scan_k": SCAN_K,
            "platform": dev.platform,
            "configs_total": len(configs),
            "configs_measured": len(items),
            "rows": rows,
            "partial_reason": "in progress (incremental flush; a "
                              "complete run overwrites this)",
        }
        with _atomic(result_path, mode="w") as f:
            json.dump(snap, f, indent=1)

    _sweep_items(jax, jnp, items, dtypes, layouts, passes, rows,
                 totals, flush=_flush_rows)

    # Stem space-to-depth experiment (MLPerf resnet-on-TPU trick): the
    # 7x7/s2 conv on C=3 wastes the MXU's 128 lanes; reshaping input
    # 224x224x3 -> 112x112x12 (2x2 space-to-depth) and zero-padding the
    # kernel 7x7 -> 8x8 gives the mathematically equivalent 4x4/s1 conv
    # on C=12. Time both stems in every pass to see what the swap buys.
    s2d_rows = []
    for p in passes:
        for label, dshape, wshape, stride, pad in (
            ("stem_std", (BATCH, 3, 224, 224), (64, 3, 7, 7),
             (2, 2), (3, 3)),
            # 7x7/s2 pad 3 == (in s2d space) 4x4/s1 with the front-
            # zero-padded kernel and ASYMMETRIC pad (2,1): 112 outputs
            # either way; tap mapping proven exact in
            # tests/test_resnet_s2d.py (models/resnet.convert_stem_to_s2d)
            ("stem_s2d", (BATCH, 12, 112, 112), (64, 12, 4, 4),
             (1, 1), ((2, 1), (2, 1))),
        ):
            try:
                fn, init = build_pass(
                    jax, jnp, p, "NHWC", dtypes[0][1],
                    dshape, wshape, stride, pad, 1)
                ms = time_pass(jax, jnp, fn, init)
                s2d_rows.append({"exp": label, "pass": p,
                                 "ms": round(ms, 3)})
                print("%-9s %-5s %8.3f ms" % (label, p, ms),
                      file=sys.stderr)
            except Exception as e:  # noqa: BLE001
                s2d_rows.append({"exp": label, "pass": p,
                                 "error": str(e)[:160]})

    summary = {
        "%s_%s_%s_total_ms" % k: round(v, 2) for k, v in totals.items()
    }
    out = {
        "batch": BATCH, "scan_k": SCAN_K, "reps": REPS,
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", "?"),
        # coverage stamps: without these a PROBE_TOP-truncated sweep's
        # summary_weighted_ms silently reads as exhaustive
        "configs_total": len(configs),
        "configs_measured": len(items),
        "probe_top": top or None,
        "wgrad_lever_passes":
            os.environ.get("PROBE_WGRAD_LEVERS") == "1",
        "summary_weighted_ms": summary,
        "stem_space_to_depth": s2d_rows,
        "rows": rows,
    }
    with _atomic(result_path, mode="w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"written": result_path, **summary}))


if __name__ == "__main__":
    main()
