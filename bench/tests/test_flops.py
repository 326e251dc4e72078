"""The operations functions against the published counts, and against
the program's own hand count on the day of the copy (PR 22)."""
import pytest

import lib

PUBLISHED = {
    # He et al. (arXiv:1512.03385): 4.1 G multiply-adds at 224x224
    "resnet50": 8.1e9,
    # Szegedy et al. (arXiv:1512.00567): 5.7 G multiply-adds at 299x299
    "inception_v3": 11.5e9,
}


@pytest.mark.parametrize("config", sorted(PUBLISHED))
def test_forward_flops_per_sample(config):
    from mxnet_tpu.telemetry import costmodel

    cfg = lib.load_json(lib.find("configs", config, ".json"))
    fn = lib.load_module("flops", cfg["flops"])
    got = fn.forward_flops_per_sample(cfg)
    assert got == pytest.approx(PUBLISHED[config], rel=0.05)
    symbol = lib.resolve(cfg["factory"])(**cfg["kwargs"])
    theirs = costmodel.analytic_forward_flops(
        symbol, data=(1,) + tuple(cfg["input_shape"]))
    assert got == pytest.approx(theirs, rel=1e-3)
    assert fn.TRAIN_MULTIPLIER == 3


def test_amp_configuration_shares_the_count():
    a = lib.load_json(lib.find("configs", "resnet50", ".json"))
    b = lib.load_json(lib.find("configs", "resnet50_amp", ".json"))
    fn = lib.load_module("flops", a["flops"])
    assert a["flops"] == b["flops"]
    assert fn.forward_flops_per_sample(a) == fn.forward_flops_per_sample(b)
