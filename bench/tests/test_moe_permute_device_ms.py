"""``moe_permute_device_ms``: the expert layer's row moves (the
``dispatch`` and ``combine`` stages of ``lm_scopes``' split) per step,
over a reduction handed in as ``run["lm_scopes"]``."""
import pytest

import lib

NAME = "moe_permute_device_ms"


def _run(**over):
    run = {"trace_steps": 5,
           "lm_scopes": {"class_s": {"attn": 0.04, "moe": 0.3, "norm": 0,
                                     "embed": 0},
                         "head_loss_s": 0.1,
                         "moe_part_s": {"router": 0.001, "dispatch": 0.068,
                                        "experts": 0.16, "combine": 0.0745}}}
    run.update(over)
    return run


def _read(run, trace=True):
    return lib.load_module("layer_metrics", NAME).compute(
        {"devices": {}} if trace else None, {"telemetry": {}}, run)


def test_it_reads_dispatch_and_combine_per_step():
    assert _read(_run()) == pytest.approx((68 + 74.5) / 5)
    # one of the two stages alone is still a reading
    only = _run()
    del only["lm_scopes"]["moe_part_s"]["combine"]
    assert _read(only) == pytest.approx(68 / 5)


@pytest.mark.parametrize("run", [
    _run(lm_scopes=None),                      # a conv net's trace
    _run(trace_steps=0),
    _run(lm_scopes={"class_s": {"attn": 0.04, "moe": 0.3, "norm": 0,
                                "embed": 0},
                    "head_loss_s": 0, "moe_part_s": {"other": 0.3}}),
    _run(lm_scopes={"class_s": {}, "head_loss_s": 0.1}),
], ids=["no_scopes", "no_steps", "stages_unscoped", "no_split"])
def test_it_finds_nothing_where_there_is_nothing(run):
    """None, never zero, never a raise: the benchmark's files are laid
    over older checkouts too."""
    assert _read(run) is None
    assert _read(_run(), trace=False) is None


def test_its_entry_lists_the_cells_with_an_expert_layer():
    """Found by name, wherever it stands: OLMoE's cell and, since PR 68,
    every cell that holds a share of its experts (``topk_moe`` scopes the
    same two stages in all of them)."""
    manifest = lib.load_json(lib.MANIFEST)
    entry = [m for m in manifest["per_layer"] if m["name"] == NAME]
    share = [m for m in manifest["per_layer"]
             if m["name"] == "moe_share_device_ms"][0]["workloads"]
    assert len(share) == 8
    assert entry == [{
        "name": NAME, "unit": "ms/step", "better": "lower",
        "source": "device_trace", "layer": "ops and kernels",
        "moves": "train_samples_s",
        "workloads": ["olmoe_fit_resident_4k"] + share}]
