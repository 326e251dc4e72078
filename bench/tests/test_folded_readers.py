"""PR 68 folded the per-layer manifest to one entry a mechanism. Each
reader of the tree before it that went or was widened
(``parent_readers/``, with the kept entries that stand for it in
``parent_readers/folded.json``) and the kept ones read the runs the
``test_*_cell.py`` files build, and find equal numbers: sums and parts
where the table says so."""
import pytest

import lib
import setup_phases
import test_dots3_cell
import test_kanana_cell
import test_kimi_linear_cell
import test_lfm2_cell
import test_mimo_cell
import test_nemotron_cell
import test_setup_phases
import test_solar_open2_cell
import test_trinity_mini_cell
from helpers import folded, parent_reader

FOLDED = folded()
RUNS = {m.CELL: m._run for m in (
    test_dots3_cell, test_kanana_cell, test_kimi_linear_cell, test_lfm2_cell,
    test_mimo_cell, test_nemotron_cell, test_solar_open2_cell,
    test_trinity_mini_cell)}
TRACE = {"devices": {}}


def _number(value):
    return value[0] if isinstance(value, tuple) else value


def _kept(names, run):
    return [_number(lib.load_module("layer_metrics", n).compute(
        TRACE, {"telemetry": {}}, run)) for n in names]


@pytest.mark.parametrize("old,cell", [
    (old, cell) for old, spec in sorted(FOLDED.items())
    for cell in spec["cells"]])
def test_the_kept_entries_read_what_the_parents_reader_read(old, cell):
    spec, run = FOLDED[old], RUNS[cell]()
    was = _number(parent_reader(old).compute(TRACE, {"telemetry": {}}, run))
    now = _kept(spec["kept"], run)
    assert was is not None and None not in now
    manifest = {m["name"]: m for m in lib.load_json(lib.MANIFEST)["per_layer"]}
    assert all(cell in manifest[k]["workloads"] for k in spec["kept"])
    if spec["how"] in ("equal", "sum"):
        assert (len(now) > 1) is (spec["how"] == "sum")
        assert sum(now) == pytest.approx(was, rel=1e-12)
    elif spec["how"] == "part":
        # Kimi Linear's latent node without its three projections, which
        # no entry reads now (PERF.md section 7)
        import kda_scopes
        assert sum(now) == pytest.approx(
            was - kda_scopes.ms(TRACE, run, "mla_proj"), rel=1e-12)
    else:
        # MiMo's rows: the busiest layer's before, the layers' mean now
        assert spec["how"] == "redefined"
        rows = [sum(layer[:8]) for layer in run["expert_counts"]]
        assert was == pytest.approx(max(rows) / 1024.0)
        assert sum(now) == pytest.approx(sum(rows) / 1024.0 / len(rows))


def test_a_parents_moe_reader_guards_on_its_model_and_the_kept_one_does_not():
    """What the copies were: one reader behind a guard on the operations
    module. The kept entry reads every share's run."""
    run = RUNS["kimi_linear_fit_share_8k"]()
    assert parent_reader("trinity_moe_device_ms").compute(
        TRACE, {"telemetry": {}}, run) is None
    assert _kept(["moe_share_device_ms"], run) == [pytest.approx(14.0)]


def test_the_remainder_holds_the_two_setup_terms_that_went(monkeypatch):
    snap, run = test_setup_phases._snap(), dict(test_setup_phases.RUN)
    parent = parent_reader("setup_unattributed_s")     # loads the module
    import parent_setup_phases
    for module in (setup_phases, parent_setup_phases):
        monkeypatch.setattr(module, "registry_at_open", lambda r: snap)
    was, was_ok, _ = parent.compute(None, {}, run)
    went = [parent_reader(n).compute(None, {}, run)
            for n in ("setup_bind_s", "setup_telemetry_s")]
    now, ok, why = lib.load_module(
        "layer_metrics", "setup_unattributed_s").compute(None, {}, run)
    assert went == [5.0, 2.0] and now == pytest.approx(was + sum(went))
    assert ok is was_ok           # judged on the same seconds as before
    # every term that keeps an entry reads what it read
    for term in setup_phases.TERMS[:-1]:
        assert setup_phases.term(run, term) == parent_setup_phases.term(
            run, term)
    assert sum(v for v in setup_phases.terms(run).values()) \
        + setup_phases.harness_s(run) == pytest.approx(run["setup_s"])


@pytest.mark.parametrize("old", sorted(
    o for o, spec in FOLDED.items() if spec["how"] == "gone"))
def test_what_went_with_no_successor_is_in_no_list(old):
    manifest = lib.load_json(lib.MANIFEST)
    assert old not in [m["name"] for m in manifest["per_layer"]]
    with pytest.raises(lib.BenchError):
        lib.find("layer_metrics", old, ".py")
