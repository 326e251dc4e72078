#!/usr/bin/env python3
"""bench/run.py — one process, one cell, one run.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell, warms up only its shapes, measures for ``--seconds``,
prints its result as the last line of stdout and exits. ``--trace 0``
gives the cell's end-to-end metrics with telemetry and profiler off;
``--trace 1`` turns ``mxnet_tpu.telemetry`` on, profiles a short steady
slice and gives the per-layer metrics and a breakdown instead.

There is no CPU branch: without a TPU of a kind that bench/peaks.json
knows, or with fewer chips than the cell asks for, the exit code is 2
and no result is printed. ``--rehearse-cpu`` is a debugging aid for a
host without the chip: the tiny sizes of bench/tests/rehearsal/, every
line says ``platform=cpu``, and the result object it prints holds no
metric value.

Everything that belongs to one configuration, cell, traffic mix, traffic
kind or per-layer metric is a file found by its name (bench/README.md).
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up counts from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.dirname(BENCH))

import lib  # noqa: E402
import reduce_trace  # noqa: E402


def load_cell(manifest, workload, rehearsal):
    """The cell's file with its traffic mix laid in, and its
    configuration — with the rehearsal's tiny sizes on top where asked."""
    entries = [w for w in manifest["workloads"] if w["name"] == workload]
    if not entries:
        raise lib.BenchError("BENCHMARK.json has no workload %r" % workload)
    entry = entries[0]
    cell = lib.load_json(lib.find("cells", workload, ".json"))
    for key in ("config", "traffic", "chips"):
        if cell[key] != entry[key]:
            raise lib.BenchError(
                "cell file and manifest disagree on %s: %r, %r"
                % (key, cell[key], entry[key]))
    mix = lib.load_json(lib.find("traffic", cell["traffic"], ".json"))
    cell = dict(cell, traffic=dict(mix, name=cell["traffic"]), name=workload)
    cfg = lib.load_json(lib.find("configs", cell["config"], ".json"))
    if rehearsal:
        tiny = lib.load_json(os.path.join(
            lib.BENCH, "tests", "rehearsal", workload + ".json"))
        cfg = lib.merge(cfg, tiny.get("config", {}))
        cell = lib.merge(cell, tiny.get("cell", {}))
        cfg["rehearsal"] = True
    return cell, cfg


def metrics_of(manifest, section, workload, reported=None):
    """The manifest's metrics of ``section`` that this cell reports: those
    with no ``workloads`` key or with the cell in it and, for a per-layer
    metric, only where the end-to-end metric it moves is reported."""
    out = []
    for m in manifest[section]:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        if reported is not None and m["moves"] not in reported:
            continue
        out.append(m)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="debugging aid: tiny sizes on a host without the "
                         "chip; the result object holds no metric value")
    ap.add_argument("--out", default=None,
                    help="directory for the trace and the run's record "
                         "(default <checkout>/.bench_out/<workload>)")
    args = ap.parse_args(argv)

    manifest = lib.load_json(lib.MANIFEST)
    cell, cfg = load_cell(manifest, args.workload, args.rehearse_cpu)
    seconds = (args.seconds if args.seconds is not None
               else manifest["run_seconds"])
    chips = cell["chips"]
    out_dir = args.out or os.path.join(lib.ROOT, ".bench_out", args.workload)
    os.makedirs(out_dir, exist_ok=True)

    # the only variables of the program the benchmark sets are those the
    # configuration lists, each with its reason: a user's defaults are
    # what is measured
    for name, spec in cfg.get("env", {}).items():
        os.environ[name] = spec["value"]
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=%d" % max(chips, 1))

    import jax

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    say = lib.Printer(platform, args.workload)
    peaks = lib.load_json(os.path.join(lib.BENCH, "peaks.json"))
    if args.rehearse_cpu:
        if platform != "cpu":
            print("bench: --rehearse-cpu found platform=%s" % platform,
                  file=sys.stderr)
            return 2
        peak = None
    else:
        if platform != "tpu" or len(devices) < chips or kind not in peaks:
            print("bench: need %d TPU chip(s) of a kind in bench/peaks.json; "
                  "JAX found platform=%s kind=%r count=%d. There is no CPU "
                  "branch." % (chips, platform, kind, len(devices)),
                  file=sys.stderr)
            return 2
        peak = peaks[kind]

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry

    compile_log = lib.CompileLog()
    say("device", kind=repr(kind), count=len(devices), chips=chips,
        seed=args.seed, seconds=seconds, trace=args.trace,
        compile_cache=mx.base.compile_cache_dir(),
        env=json.dumps({k: v["value"] for k, v in cfg.get("env", {}).items()}))
    if args.trace:
        telemetry.enable()
    session = lib.Session(bool(args.trace), out_dir, compile_log)

    traffic = lib.load_module("traffic", cell["traffic"]["kind"])
    t_imported = time.perf_counter()
    state = traffic.setup(cfg, cell, args.seed)
    t_ready = time.perf_counter()
    run = traffic.run(state, seconds, session)

    setup_s = run["open_t"] - T_PROCESS
    # where set-up went: imports and the device client, the traffic
    # kind's own set-up, then the program's (bind, init, first steps)
    say("setup", total_s="%.2f" % setup_s,
        imports_s="%.2f" % (t_imported - T_PROCESS),
        traffic_setup_s="%.2f" % (t_ready - t_imported),
        to_first_step_s="%.2f" % (run.get("first_step_t", t_ready) - t_ready),
        warmup_s="%.2f" % (run["open_t"] - run.get("first_step_t", t_ready)))
    c_setup, c_window = compile_log.of("setup"), compile_log.of("window")
    say("compile", setup_s="%.2f" % c_setup["seconds"],
        setup_compiles=c_setup["compiles"], cache_hits=c_setup["hits"],
        cache_misses=c_setup["misses"],
        window_compiles=c_window["compiles"],
        window_s="%.2f" % c_window["seconds"])
    checks = list(run["checks"])
    checks.append(("no_compile_in_window", c_window["compiles"] == 0,
                   "%d compiles, %.2f s" % (c_window["compiles"],
                                            c_window["seconds"])))

    flops_fn = lib.load_module("flops", cfg["flops"]).forward_flops_per_sample
    run.update(
        cfg=cfg, cell=cell, chips=chips, peak=peak, setup_s=setup_s,
        forward_flops_per_sample=flops_fn(cfg),
        memory_peak_bytes=lib.memory_peak_bytes(devices[:chips]))
    counters = {
        "compile": {"setup": c_setup, "window": c_window},
        "telemetry": (lib.telemetry_delta(session.snap_open,
                                          session.snap_close)
                      if args.trace else {}),
    }
    if counters["telemetry"]:
        say("telemetry", **{
            name: ("%.6g" % d["value"] if "value" in d
                   else "%.6g/%d" % (d["sum"], d["count"]))
            for name, d in sorted(counters["telemetry"].items())
            if d.get("value") or d.get("count")})
    trace = None
    if session.xplane:
        trace = reduce_trace.reduce(reduce_trace.load(session.xplane))

    end_to_end = dict(run["metrics"], setup_s=setup_s)
    reported = [m for m in metrics_of(manifest, "end_to_end", args.workload)
                if m["name"] in end_to_end]
    metrics = {}
    if args.trace:
        names = {m["name"] for m in reported}
        for m in metrics_of(manifest, "per_layer", args.workload, names):
            reader = lib.load_module("layer_metrics", m["name"])
            value = reader.compute(trace, counters, run)
            if value is None:
                continue  # nothing to read: the metric is left out
            if isinstance(value, tuple):  # a reader may fail the run
                value, ok, why = value
                checks.append((m["name"], ok, why))
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in reported:
            metrics[m["name"]] = {"value": end_to_end[m["name"]],
                                  "unit": m["unit"]}

    for name, ok, why in checks:
        say("check", name=name, ok=ok, detail=json.dumps(why))
    correct = all(ok for _, ok, _ in checks)
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": run["memory_peak_bytes"]}
    result = {"correct": correct, "attempted": run["attempted"],
              "failed": run["failed"], "metrics": metrics, "device": device}
    if trace and trace["devices"]:
        used = [trace["devices"][i] for i in sorted(trace["devices"])][:chips]
        device["busy_s"] = sum(d["busy_s"] for d in used) / len(used)
        device["window_s"] = trace["window_s"]
        for i, d in sorted(trace["devices"].items()):
            say("device_trace", device=i, busy_s="%.6f" % d["busy_s"],
                idle_share="%.4f" % d["idle_share"], ops=d["ops"],
                by_class_s=json.dumps(d["by_class_s"]),
                collective_s="%.6f" % d["collective_s"],
                collective_exposed_s="%.6f" % d["collective_exposed_s"],
                modules=json.dumps(d["modules"]))
        result["breakdown"] = {"device_ops": used[0]["top_ops"],
                               "idle_gaps": used[0]["idle_gaps"]}

    say("run", **{k: run[k] for k in run.get("report", ())})
    if peak and run.get("samples_s"):
        say("model_mfu", percent=lib.load_module(
            "layer_metrics", "model_mfu").compute(None, counters, run),
            forward_gflop_per_sample=run["forward_flops_per_sample"] / 1e9)
    if args.rehearse_cpu:
        # a CPU number is never written under a device metric's name
        result = dict(result, rehearsal=True, metrics={
            k: {"unit": v["unit"]} for k, v in metrics.items()})
    else:
        record = dict(result, workload=args.workload, seed=args.seed,
                      seconds=seconds, trace=args.trace,
                      compile=counters["compile"], end_to_end=end_to_end,
                      run={k: run[k] for k in run.get("report", ())},
                      series=run.get("series", {}))
        with open(os.path.join(out_dir, "run_seed%d_trace%d.json"
                               % (args.seed, args.trace)), "w") as f:
            json.dump(record, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except lib.BenchError as e:
        print("bench: %s" % e, file=sys.stderr)
        sys.exit(2)
