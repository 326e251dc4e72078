"""The reader of ``fit.lookahead_steps``: a share of the window's steps
where the program has the counter, nothing where it has not."""
import pytest

import lib

RUN = {"steps": 80, "window_s": 10.0, "chips": 1, "cfg": {}}


def read(telemetry, run=RUN):
    return lib.load_module("layer_metrics", "fit_lookahead_share").compute(
        None, {"telemetry": telemetry}, run)


@pytest.mark.parametrize("value,share", [(80, 100.0), (60, 75.0), (0, 0.0)])
def test_counter_present_gives_the_share(value, share):
    assert read({"fit.lookahead_steps": {"value": value}}) == share


def test_counter_absent_gives_nothing():
    # the parent commit: other counters, not this one
    assert read({"train_step.steps": {"value": 80}}) is None


def test_no_steps_gives_nothing():
    assert read({"fit.lookahead_steps": {"value": 0}},
                dict(RUN, steps=0)) is None
