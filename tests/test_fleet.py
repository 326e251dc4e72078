"""Fleet observability plane (docs/observability.md "Fleet view").

Covers the full PR-16 contract:

* record tagging + clock handshake — every JSONL record carries
  rank/pid/host, and ``clock_<rank>.json`` lets the aggregator place
  drifting per-rank clocks on one filesystem timeline;
* snapshot merging — ``Registry.merge_snapshot`` is idempotent per
  (rank, seq), replaces (not adds) a rank's cumulative streams, and
  unions histogram bucket-edge generations;
* skew decomposition — the e2e straggler test runs three 8-virtual-
  device fits into one run dir with ``delay_collective_ms`` injected
  into one rank, and the aggregator must name that rank, attribute its
  slowness to the collective phase, keep phases + unattributed summing
  to wall exactly, and feed the same evidence into the watchdog's
  decision record;
* the /metrics endpoint — Prometheus text exposition (0.0.4,
  format-checked with tools/fleet_top.check_prometheus_text) plus the
  /healthz JSON liveness view, bound to 127.0.0.1.
"""
import json
import os
import subprocess
import sys
import textwrap
import urllib.error
import urllib.request

import pytest

import mxnet_tpu as mx  # noqa: F401  (ensures the package import path)
from mxnet_tpu import telemetry as tm
from mxnet_tpu.parallel import heartbeat as hb
from mxnet_tpu.resilience import fault
from mxnet_tpu.telemetry import export as texport
from mxnet_tpu.telemetry import fleet
from tools import fleet_top

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _isolate():
    """Zero metric values and detach sinks around every test (handles
    held by instrument sites stay registered)."""
    tm.reset()
    tm.disable()
    yield
    tm.reset()
    tm.disable()


# ---------------------------------------------------------------------------
# record tagging + clock handshake
# ---------------------------------------------------------------------------

def test_records_tagged_and_default_sink_adopted(tmp_path, monkeypatch):
    run_dir = str(tmp_path)
    monkeypatch.setenv("MXTPU_RUN_DIR", run_dir)
    monkeypatch.setenv("DMLC_RANK", "3")
    monkeypatch.delenv("MXTPU_TELEMETRY_FILE", raising=False)
    monkeypatch.setattr(texport, "_handshake_done", False)
    tm.enable()
    try:
        assert tm.jsonl_path() == os.path.join(run_dir, "telemetry_r3.jsonl")
        texport.emit_record({"type": "anatomy", "t": 1.0})
        tm.flush()
    finally:
        tm.reset()
    with open(os.path.join(run_dir, "telemetry_r3.jsonl")) as f:
        records = [json.loads(line) for line in f if line.strip()]
    for rec in records:
        assert rec["rank"] == 3
        assert rec["pid"] == os.getpid()
        assert rec["host"]
    # metrics snapshots carry the merge-idempotence sequence number
    assert any(r["type"] == "metrics" and r["seq"] >= 1 for r in records)
    # the handshake landed alongside the stream
    offsets = fleet.read_clock_offsets(run_dir)
    assert 3 in offsets
    assert abs(offsets[3]["offset"]) < 60.0  # same machine: near zero


def test_rank_tags_opt_out(monkeypatch):
    monkeypatch.setenv("MXTPU_RANK_TAGS", "0")
    assert texport.tag_record({"type": "x"}) == {"type": "x"}
    monkeypatch.setenv("MXTPU_RANK_TAGS", "1")
    assert texport.tag_record({"type": "x"})["rank"] == texport.fleet_rank()


def test_clock_offset_aligns_drifting_ranks(tmp_path):
    """A rank whose wall clock runs 5s behind the filesystem's gets its
    anatomy timestamps shifted forward by exactly that offset."""
    run_dir = str(tmp_path)
    now = 1700000000.0
    for rank, wall in ((0, now), (1, now - 5.0)):
        with open(os.path.join(run_dir, "clock_%d.json" % rank), "w") as f:
            json.dump({"rank": rank, "pid": 1, "host": "h", "wall": wall,
                       "mono": 0.0}, f)
        os.utime(os.path.join(run_dir, "clock_%d.json" % rank), (now, now))
        rec = {"type": "anatomy", "t": 100.0 if rank == 0 else 95.0,
               "interval": 0, "step_end": 4, "steps": 4,
               "wall_seconds": 0.1, "step_ms": 25.0,
               "phases": {"collective": 0.01}, "unattributed_seconds": 0.09}
        with open(os.path.join(run_dir, "telemetry_r%d.jsonl" % rank),
                  "w") as f:
            f.write(json.dumps(rec) + "\n")
    agg = fleet.FleetAggregator(run_dir).refresh()
    assert abs(agg.offsets[0]["offset"] - 0.0) < 0.01
    assert abs(agg.offsets[1]["offset"] - 5.0) < 0.01
    t0 = agg.ranks[0]["anatomy"][0]["t_aligned"]
    t1 = agg.ranks[1]["anatomy"][0]["t_aligned"]
    # same true moment after alignment, despite 5s of recorded skew
    assert abs(t0 - t1) < 0.01


# ---------------------------------------------------------------------------
# snapshot merging
# ---------------------------------------------------------------------------

def test_merge_snapshot_idempotent_per_rank_seq():
    reg = fleet.Registry()
    snap = {"c": {"kind": "counter",
                  "streams": [{"labels": {}, "value": 5}]}}
    assert reg.merge_snapshot(snap, rank=0, seq=1) is True
    # a replayed (or reordered) JSONL tail cannot double-count
    assert reg.merge_snapshot(snap, rank=0, seq=1) is False
    assert reg.total("c") == 5.0
    # snapshots are cumulative: a newer one REPLACES the rank's streams
    snap2 = {"c": {"kind": "counter",
                   "streams": [{"labels": {}, "value": 9}]}}
    assert reg.merge_snapshot(snap2, rank=0, seq=2) is True
    assert reg.total("c") == 9.0
    # another rank is a separate stream, summed by total()
    assert reg.merge_snapshot(snap, rank=1, seq=1) is True
    assert reg.total("c") == 14.0
    text = reg.render_prometheus()
    assert 'rank="0"' in text and 'rank="1"' in text
    assert fleet_top.check_prometheus_text(text) == []


def test_merge_snapshot_unions_histogram_edges():
    """Ranks running different bucket-edge generations merge by edge-set
    union; cumulative counts stay exact at source edges (documented
    percentile_from_counts semantics) and the render stays valid."""
    reg = fleet.Registry()
    reg.merge_snapshot({"lat": {"kind": "histogram", "streams": [
        {"labels": {}, "sum": 3.0, "count": 3,
         "counts": [1, 2, 0], "buckets": [1.0, 2.0]}]}}, rank=0, seq=1)
    reg.merge_snapshot({"lat": {"kind": "histogram", "streams": [
        {"labels": {}, "sum": 9.0, "count": 4,
         "counts": [1, 3], "buckets": [5.0]}]}}, rank=1, seq=1)
    m = reg.get("lat")
    assert m.buckets == (1.0, 2.0, 5.0)
    # rank 0's mass sits at its own source edges, exactly
    assert m.count(rank="0") == 3 and m.count(rank="1") == 4
    text = reg.render_prometheus()
    assert fleet_top.check_prometheus_text(text) == []
    # percentiles on merged state: exact at source edges — rank 1 put
    # 1 of 4 samples at or below 5.0, so p25 interpolates inside (0, 5]
    p = tm.percentile_from_counts((1.0, 2.0, 5.0), [0, 0, 1, 3], 4, 9.0, 25)
    assert 0.0 < p <= 5.0


def test_rebucket_counts_preserves_cumulative_at_source_edges():
    counts = fleet._registry.rebucket_counts([2, 3, 1], (1.0, 4.0),
                                             (1.0, 2.0, 4.0))
    # all mass in (1, 4] is attributed to the top of the source bucket
    assert counts == [2, 0, 3, 1]
    assert sum(counts) == 6


# ---------------------------------------------------------------------------
# skew decomposition (unit level)
# ---------------------------------------------------------------------------

def _anatomy(wall, collective, step_end=4, **phases):
    phases = dict(phases, collective=collective)
    return {"type": "anatomy", "t": 0.0, "interval": 0,
            "step_end": step_end, "steps": 4, "wall_seconds": wall,
            "step_ms": 250.0 * wall, "phases": phases,
            "unattributed_seconds": wall - sum(phases.values())}


def test_decompose_imputes_wait_and_keeps_invariants():
    per = {0: _anatomy(1.0, 0.8, input_wait=0.1),
           1: _anatomy(0.4, 0.3, input_wait=0.05)}
    d = fleet.FleetAggregator.decompose(per)
    # rank 0 does 0.2s of own work vs rank 1's 0.1s -> rank 1 spends up
    # to 0.1s of its collective waiting on rank 0
    assert d["straggler"] == 0
    assert abs(d["ranks"][1]["wait_seconds"] - 0.1) < 1e-9
    assert abs(d["ranks"][0]["wait_seconds"] - 0.0) < 1e-9
    assert fleet.FleetAggregator.check_interval(per, d) == []
    # scores: rank 0 keeps its full wall, rank 1 sheds the imputed wait
    assert abs(d["ranks"][0]["score_seconds"] - 1.0) < 1e-9
    assert abs(d["ranks"][1]["score_seconds"] - 0.3) < 1e-9
    assert abs(d["skew_seconds"] - 0.7) < 1e-9


def test_bottleneck_names_the_excess_phase():
    per = {0: _anatomy(0.5, 0.05, input_wait=0.35),
           1: _anatomy(0.15, 0.05, input_wait=0.02),
           2: _anatomy(0.15, 0.05, input_wait=0.02)}
    d = fleet.FleetAggregator.decompose(per)
    assert d["straggler"] == 0
    assert d["bottleneck"] == "input"


# ---------------------------------------------------------------------------
# liveness signals in the fleet view
# ---------------------------------------------------------------------------

def test_heartbeat_stall_surfaces_in_liveness(tmp_path, monkeypatch):
    d = str(tmp_path)
    monkeypatch.setenv(hb.RUN_DIR_ENV, d)
    # the spec string must differ from test_elastic's (fault one-shots
    # are deduped per-process by the raw env string)
    monkeypatch.setenv(fault.ENV,
                       "heartbeat_stall=1@2,uniq=fleet%d" % os.getpid())
    w0 = hb.HeartbeatWriter(d, 0, interval=0.05).start()
    w1 = hb.HeartbeatWriter(d, 1, interval=0.05).start()
    try:
        fault.fire("step", step=1)
        fault.fire("step", step=2)
    finally:
        w0.stop()
        w1.stop()
    live = fleet.read_liveness(d)
    assert live[1]["stalled"] is True and not live[1]["lost"]
    assert live[0]["stalled"] is False
    # progress was back-dated by the stall tombstone: visibly ancient
    assert live[1]["prog_age"] > live[0]["prog_age"] + 60.0
    # and the watchdog-facing evidence carries it even with no telemetry
    ev = fleet.FleetAggregator(d).refresh().evidence()
    assert ev["telemetry_ranks"] == 0
    assert ev["liveness"]["1"]["stalled"] is True
    assert "stalled" not in ev["liveness"].get("0", {})


def test_heartbeat_writer_drops_clock_handshake(tmp_path):
    w = hb.HeartbeatWriter(str(tmp_path), 2, interval=60.0).start()
    try:
        assert 2 in fleet.read_clock_offsets(str(tmp_path))
    finally:
        w.stop()


# ---------------------------------------------------------------------------
# the /metrics + /healthz endpoint
# ---------------------------------------------------------------------------

def _get(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.headers.get("Content-Type"), r.read().decode("utf-8")


def test_metrics_endpoint_scrape_format(tmp_path):
    reg = fleet.Registry()
    reg.merge_snapshot({
        "fit.steps": {"kind": "counter",
                      "streams": [{"labels": {}, "value": 12}]},
        "lat": {"kind": "histogram", "streams": [
            {"labels": {"op": "push"}, "sum": 2.0, "count": 3,
             "counts": [1, 2, 0], "buckets": [1.0, 2.0]}]},
    }, rank=0, seq=1)
    hb.HeartbeatWriter(str(tmp_path), 0, interval=60.0)._beat()
    srv = fleet.MetricsServer(0, registry=reg,
                              run_dir=str(tmp_path)).start()
    try:
        assert srv.addr == "127.0.0.1"  # never exposed beyond the host
        base = "http://127.0.0.1:%d" % srv.port
        ctype, body = _get(base + "/metrics")
        assert ctype == fleet.PROM_CONTENT_TYPE
        assert fleet_top.check_prometheus_text(body) == [], body
        assert 'rank="0"' in body and "mxtpu_fit_steps" in body
        ctype, body = _get(base + "/healthz")
        assert ctype == "application/json"
        health = json.loads(body)
        assert health["status"] == "ok"
        assert health["pid"] == os.getpid()
        assert health["liveness"]["0"]["hb_age"] is not None
        with pytest.raises(urllib.error.HTTPError) as exc:
            _get(base + "/nope")
        assert exc.value.code == 404
    finally:
        srv.stop()


def test_enable_starts_singleton_endpoint(tmp_path):
    tm.enable(metrics_port=0)
    try:
        srv = fleet._server
        assert srv is not None
        # idempotent: a second enable reuses the running server
        tm.enable(metrics_port=0)
        assert fleet._server is srv
        _, body = _get("http://127.0.0.1:%d/metrics" % srv.port)
        assert fleet_top.check_prometheus_text(body) == []
    finally:
        tm.reset()  # stops the endpoint
    assert fleet._server is None


# ---------------------------------------------------------------------------
# e2e: injected straggler named with the right bottleneck phase
# ---------------------------------------------------------------------------

FLEET_SCRIPT = textwrap.dedent("""\
    import os, sys
    sys.path.insert(0, %(repo)r)
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import mxnet_tpu as mx

    np.random.seed(0)
    mx.random.seed(0)
    rng = np.random.RandomState(42)
    X = rng.randn(256, 8).astype(np.float32)
    y = rng.randint(0, 4, 256).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=16)  # 16 steps/epoch

    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")

    mod = mx.mod.Module(net, context=[mx.cpu(i) for i in range(8)])
    mod.fit(it, eval_metric=mx.metric.create("acc"), kvstore="local",
            optimizer="sgd", optimizer_params={"learning_rate": 0.1},
            initializer=mx.init.Uniform(0.1), num_epoch=1)
    print("FLEET-RANK-DONE rank=%%s" %% os.environ.get("DMLC_RANK"),
          flush=True)
""") % {"repo": REPO}


def _run_rank(script_dir, run_dir, rank, extra_env=None, timeout=300):
    script = os.path.join(script_dir, "train_fleet.py")
    if not os.path.exists(script):
        with open(script, "w") as f:
            f.write(FLEET_SCRIPT)
    env = os.environ.copy()
    for var in ("XLA_FLAGS", fault.ENV, "MXTPU_TELEMETRY_FILE",
                "MXTPU_WORLD_SIZE", "MXTPU_ELASTIC", "MXTPU_METRICS_PORT"):
        env.pop(var, None)
    env.update({
        "MXTPU_RUN_DIR": run_dir,
        "DMLC_RANK": str(rank),
        "MXTPU_TELEMETRY": "1",
        "MXTPU_ANATOMY_INTERVAL": "4",
    })
    env.update(extra_env or {})
    return subprocess.run([sys.executable, script], capture_output=True,
                          text=True, timeout=timeout, env=env)


def test_straggler_attribution_e2e(tmp_path):
    """Three 8-virtual-device fits share one run dir; rank 1's
    collectives each sleep an injected 50 ms (200 ms/step over 4 keys),
    so the aggregator must name rank 1 collective-bound, the skew
    decomposition must stay exactly consistent with each rank's wall
    time, and a watchdog pass over the same run dir must attach that
    evidence to its decision record."""
    run_dir = str(tmp_path / "run")
    os.makedirs(run_dir)
    for rank in (0, 1, 2):
        extra = {fault.ENV: "delay_collective_ms=50"} if rank == 1 else {}
        proc = _run_rank(str(tmp_path), run_dir, rank, extra)
        assert proc.returncode == 0, proc.stderr
        assert "FLEET-RANK-DONE rank=%d" % rank in proc.stdout

    agg = fleet.FleetAggregator(run_dir).refresh()
    assert sorted(agg.ranks) == [0, 1, 2]
    s = agg.summary()
    # the injected rank is the straggler, and for the right reason
    assert s["straggler"] == 1, s
    assert s["bottleneck"] == "collective", s
    # 4 steps/interval x ~200ms injected -> skew far above noise
    assert s["max_skew_ms"] > 400.0, s["max_skew_ms"]
    # per-rank identity + progress in the rollup
    for rank in (0, 1, 2):
        pr = s["per_rank"][rank]
        assert pr["steps"] == 16
        assert pr["pid"] and pr["host"]
        assert pr["clock_offset"] is not None
        assert pr["hb_age"] is not None  # fit started a liveness writer
    # every aligned interval satisfies the accounting invariants:
    # phases + unattributed == wall, collective split re-sums
    intervals = agg.intervals()
    assert len(intervals) >= 3
    for _key, per in intervals:
        decomp = fleet.FleetAggregator.decompose(per)
        assert fleet.FleetAggregator.check_interval(per, decomp) == []
    # the merged registry (fed by each rank's metrics snapshots) renders
    # valid Prometheus text with per-rank streams
    text = agg.registry.render_prometheus()
    assert fleet_top.check_prometheus_text(text) == [], text[:2000]
    assert 'rank="1"' in text
    # the injected delay is visible in the merged collective histogram
    coll = agg.registry.get("parallel.collective_seconds")
    assert coll is not None and coll.kind == "histogram"

    # fleet_top renders the same story
    table = fleet_top.render_table(s)
    assert "STRAGGLER" in table
    assert "rank 1 (collective-bound)" in table

    # watchdog: a supervision pass over this run dir cites the evidence
    from tools import watchdog

    rc = watchdog.supervise([sys.executable, "-c", "pass"],
                            max_restarts=0, run_dir=run_dir,
                            poll_interval=0.05, log=lambda *_: None)
    assert rc == 0
    with open(os.path.join(run_dir, "decisions.jsonl")) as f:
        decisions = [json.loads(line) for line in f if line.strip()]
    assert decisions and decisions[-1]["action"] == "done"
    ev = decisions[-1]["evidence"]
    assert ev["telemetry_ranks"] == 3
    assert ev["straggler"] == 1
    assert ev["bottleneck"] == "collective"
    assert ev["max_skew_ms"] > 400.0
    assert ev["last_intervals"], ev
    # raw per-rank wall/wait milliseconds ride along as the evidence
    last = ev["last_intervals"][-1]
    assert last["ranks"]["1"]["wall_ms"] > last["ranks"]["0"]["wall_ms"]

    # perf_doctor's fleet section reads the same run dir
    from tools import perf_doctor

    text, _summary = perf_doctor.fleet_section(run_dir)
    assert "== fleet (3 ranks) ==" in text
    assert "rank 1 is collective-bound" in text
