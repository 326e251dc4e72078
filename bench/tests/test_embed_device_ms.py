"""``embed_device_ms``: the ``embed`` class of ``lm_scopes``' reduction
per step, over a reduction handed in as ``run["lm_scopes"]``."""
import pytest

import lib

NAME = "embed_device_ms"


def _run(**over):
    run = {"trace_steps": 5,
           "lm_scopes": {"class_s": {"attn": 0.04, "moe": 0.3, "norm": 0.01,
                                     "embed": 0.29},
                         "head_loss_s": 0.1, "moe_part_s": {}}}
    run.update(over)
    return run


def _read(run, trace=True):
    return lib.load_module("layer_metrics", NAME).compute(
        {"devices": {}} if trace else None, {"telemetry": {}}, run)


def test_it_reads_the_embed_class_per_step():
    assert _read(_run()) == pytest.approx(290 / 5)


@pytest.mark.parametrize("run", [
    _run(lm_scopes=None),                      # a conv net's trace
    _run(trace_steps=0),
], ids=["no_scopes", "no_steps"])
def test_it_finds_nothing_where_there_is_nothing(run):
    """None, never zero, never a raise: the benchmark's files are laid
    over older checkouts too."""
    assert _read(run) is None
    assert _read(_run(), trace=False) is None


def test_its_entry_lists_the_twelve_cells_with_an_embedding():
    manifest = lib.load_json(lib.MANIFEST)
    entry = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert entry == [{
        "name": NAME, "unit": "ms/step", "better": "lower",
        "source": "device_trace", "layer": "ops and kernels",
        "moves": "train_samples_s",
        "workloads": [
            "olmoe_fit_resident_4k", "mimo_v2_flash_fit_share_4k",
            "kanana2_fit_share_8k", "nemotron3_nano_fit_share_8k",
            "olmo_hybrid_fit_stage_4k", "lfm2_fit_share_8k",
            "falcon_h1_fit_share_4k", "kimi_linear_fit_share_8k",
            "trinity_mini_fit_share_8k", "dots3_note_fit_share_4k",
            "solar_open2_fit_share_4k", "ouro_fit_loop_4k"]}]
    # every cell whose configuration is a token model (PR 68 opened the
    # list to the five newest)
    conv = ("resnet50", "resnet50_amp", "inception_v3")
    assert entry[0]["workloads"] == [
        c["name"] for c in manifest["workloads"] if c["config"] not in conv]
    kinds = {c["name"]: c for c in manifest["workloads"]}
    assert all(cell in kinds for cell in entry[0]["workloads"])
