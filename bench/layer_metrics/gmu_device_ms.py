"""Busy milliseconds of device 0 per step in the gated memory units
(``fc/layer<l>_gmu_{in,out}_proj``, ``act/layer<l>_gmu_gate`` and
``act/layer<l>_gmu``: ``W_2 (m * silu(W_1 a))`` with ``m`` ONE earlier
Mamba layer's scan output), forward and backward together."""
import sscan_scopes


def compute(trace, counters, run):
    return sscan_scopes.ms(trace, run, "gmu")
