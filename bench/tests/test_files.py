"""Every file the manifest names exists and loads, every data file
names files that exist, and names and units keep to the characters the
driver allows."""
import glob
import os
import re

import pytest

import lib

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]+$")
MANIFEST = lib.load_json(lib.MANIFEST)
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_manifest_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["bench"]
    assert MANIFEST["command"] == ["python3", "bench/run.py"]
    assert isinstance(MANIFEST["run_seconds"], int)
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert os.path.getsize(lib.MANIFEST) <= 64 * 1024
    cells = MANIFEST["workloads"]
    assert 2 <= len(cells) <= 24
    assert sum(c["chips"] == 4 for c in cells) <= max(1, len(cells) // 4)
    pairs = [(c["config"], c["traffic"]) for c in cells]
    assert len(set(pairs)) == len(pairs)


def test_names_units_and_lines():
    names = []
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in MANIFEST[section]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((section[:3] == "end" or section[:3] == "per",
                          entry["name"]))
    metric_names = [n for is_metric, n in names if is_metric]
    assert len(set(metric_names)) == len(metric_names)
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in MANIFEST["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    e2e = {m["name"] for m in MANIFEST["end_to_end"]}
    assert "setup_s" in e2e
    for m in MANIFEST["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and _line(m["layer"])
    for c in MANIFEST["workloads"]:
        assert set(c) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(c["config"]) and NAME.match(c["traffic"])
        assert c["chips"] in (1, 4) and _line(c["why"])
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _line(c["source"]) and _line(c["why"])
        assert all(NAME.match(k) for k in c["reduced"])


def test_every_file_under_paths_is_named_from_allowed_characters():
    for root, dirs, files in os.walk(lib.BENCH):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            rel = os.path.relpath(os.path.join(root, f), lib.ROOT)
            assert PATH.match(rel), rel


@pytest.mark.parametrize("entry", MANIFEST["configs"],
                         ids=lambda e: e["name"])
def test_manifest_configuration(entry):
    path = os.path.join(lib.ROOT, entry["file"])
    assert path == lib.find("configs", entry["name"], ".json")
    cfg = lib.load_json(path)
    assert cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    used = {c["config"] for c in MANIFEST["workloads"]}
    assert entry["name"] in used
    files = [c["file"] for c in MANIFEST["configs"]]
    assert files.count(entry["file"]) == 1


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(
    lib.BENCH, "configs", "*.json"))), ids=os.path.basename)
def test_configuration_file(path):
    cfg = lib.load_json(path)
    assert _line(cfg["source"])
    assert callable(lib.resolve(cfg["factory"]))
    assert isinstance(cfg["kwargs"], dict) and len(cfg["input_shape"]) == 3
    assert hasattr(lib.load_module("flops", cfg["flops"]),
                   "forward_flops_per_sample")
    for name, spec in cfg["env"].items():
        assert spec["value"] and spec["why"], name
    assert isinstance(cfg["reduced"], list) and "assumed" in cfg


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(
    lib.BENCH, "cells", "*.json"))), ids=os.path.basename)
def test_cell_file(path):
    cell = lib.load_json(path)
    name = os.path.basename(path)[:-len(".json")]
    entry = [w for w in MANIFEST["workloads"] if w["name"] == name]
    assert entry, "cell file without a manifest entry: %s" % name
    for key in ("config", "traffic", "chips", "why"):
        assert cell[key] == entry[0][key], key
    assert cell["who"] and cell["expect"]
    lib.find("configs", cell["config"], ".json")
    mix = lib.load_json(lib.find("traffic", cell["traffic"], ".json"))
    kind = lib.load_module("traffic", mix["kind"])
    assert callable(kind.setup) and callable(kind.run)
    assert os.path.isfile(os.path.join(
        lib.BENCH, "tests", "rehearsal", name + ".json"))


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(
    lib.BENCH, "traffic", "*.json"))), ids=os.path.basename)
def test_traffic_mix_file(path):
    mix = lib.load_json(path)
    lib.find("traffic", mix["kind"], ".py")
    if "initializer" in mix:
        assert callable(lib.resolve(mix["initializer"]))


@pytest.mark.parametrize("metric", MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader(metric):
    reader = lib.load_module("layer_metrics", metric["name"])
    assert callable(reader.compute) and reader.__doc__
    cells = {w["name"] for w in MANIFEST["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells


def test_every_reader_is_in_the_manifest():
    listed = {m["name"] for m in MANIFEST["per_layer"]}
    found = {os.path.basename(p)[:-3] for p in glob.glob(os.path.join(
        lib.BENCH, "layer_metrics", "*.py"))}
    assert found == listed


PER_LAYER_LIMIT = 128       # what a manifest may hold


def test_the_per_layer_manifest_has_room():
    used = len(MANIFEST["per_layer"])
    print("per_layer: %d entries of %d, %d free"
          % (used, PER_LAYER_LIMIT, PER_LAYER_LIMIT - used))
    assert used <= PER_LAYER_LIMIT


def _model_prefixes():
    """What an entry named for a model would start with: a
    configuration's or a cell's name, its first word or its first two,
    and the short forms in use (``tests/named_for_a_model/``)."""
    names = [c["name"] for c in MANIFEST["configs"]] + [
        w["name"].split("_fit")[0] for w in MANIFEST["workloads"]]
    out = set()
    for name in names:
        words = name.split("_")
        out |= {name, words[0], "_".join(words[:2])}
    # ``olmo`` is two models' first word and no entry's; a bare class
    # name (``conv``, ``attn``) is a mechanism
    return {p + "_" for p in out} | {
        f["prefix"] for f in _named_for_a_model()}


def _named_for_a_model():
    return [lib.load_json(p) for p in sorted(glob.glob(os.path.join(
        lib.BENCH, "tests", "named_for_a_model", "*.json")))]


def test_an_entry_is_named_for_its_mechanism_not_for_a_model():
    """One entry a mechanism, listing every cell that runs it (PR 68). An
    entry may carry a model's name only where its reader reads what only
    that model traces; each such prefix has a file under
    ``tests/named_for_a_model/`` with its reason and its entries, and a
    later PR that must add one adds a file."""
    allowed = {}
    for f in _named_for_a_model():
        assert f["reason"] and f["model"] in [
            c["name"] for c in MANIFEST["configs"]], f
        for name in f["entries"]:
            assert name.startswith(f["prefix"]) and name not in allowed
            allowed[name] = f["prefix"]
    prefixes = _model_prefixes()
    named = [m["name"] for m in MANIFEST["per_layer"]
             if any(m["name"].startswith(p) for p in prefixes)]
    assert sorted(named) == sorted(allowed)
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in allowed:        # a model's own entry lists its own cells
        assert len(by_name[name]["workloads"]) == 1, name


FLOPS_CALL = re.compile(r"\bflops\.([a-z_0-9]+)\(")
# entry -> what its reader asks of the configuration's operations module
# beyond what it calls as ``flops.<name>(`` (it finds these by getattr,
# or through a ``*_scopes`` helper that answers None without them)
OPERATIONS = {
    "moe_share_rows_over_expected": ("expected_share_rows",),
    "moe_share_roofline_share": ("moe_share_flops",),
    "mla_kernel_roofline_share": ("mla_kernel_flops",),
    "kda_device_ms": ("kda_core_flops",),
    "kda_core_device_ms": ("kda_core_flops",),
    "kda_core_roofline_share": ("kda_core_flops",),
    "moe_roofline_share": ("moe_flops",),
    "attn_roofline_share": ("attn_kernel_flops",),
}


@pytest.mark.parametrize("metric", [
    m for m in MANIFEST["per_layer"] if "workloads" in m],
    ids=lambda m: m["name"])
def test_every_listed_cell_has_the_readers_operations_functions(metric):
    """A list may name a cell only where the reader finds what it counts
    with: an entry opened to a cell whose operations module lacks the
    function reads nothing there, and the traced run is refused."""
    source = open(lib.find("layer_metrics", metric["name"], ".py")).read()
    wanted = set(FLOPS_CALL.findall(source)) | set(
        OPERATIONS.get(metric["name"], ()))
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    for cell in metric["workloads"]:
        cfg = lib.load_json(lib.find("configs", cells[cell]["config"],
                                     ".json"))
        module = lib.load_module("flops", cfg["flops"])
        missing = [f for f in wanted if not hasattr(module, f)]
        assert not missing, (cell, cfg["flops"], missing)


def test_peaks_name_their_source():
    peaks = lib.load_json(os.path.join(lib.BENCH, "peaks.json"))
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_s"] == 819e9
    assert all(p["source"] for p in peaks.values())


def test_readers_with_nothing_to_read_return_nothing():
    run = {"steps": 0, "window_s": 1.0, "chips": 1, "peak": None,
           "cfg": {}, "samples_s": 0}
    counters = {"telemetry": {}, "compile": {"setup": {"seconds": 0.0}}}
    for m in MANIFEST["per_layer"]:
        if m["name"] == "compile_s":
            continue
        reader = lib.load_module("layer_metrics", m["name"])
        assert reader.compute(None, counters, run) is None, m["name"]


def test_harness_reads_no_underscore_attribute_of_the_program():
    """The yardstick must survive the refactors it is there to judge."""
    private = re.compile(r"\b(mx|mod|engine|pred|exact|trainer|telemetry"
                         r"|predict|module)\._[a-z]")
    sets_env = re.compile(r"environ\[[\"'](MXTPU|MXNET)_")
    for path in glob.glob(os.path.join(lib.BENCH, "**", "*.py"),
                          recursive=True):
        if os.sep + "tests" + os.sep in path:
            continue
        text = open(path).read()
        assert not private.search(text), path
        assert not sets_env.search(text), path
