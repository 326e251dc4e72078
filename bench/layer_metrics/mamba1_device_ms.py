"""Busy milliseconds of device 0 per step in the whole Mamba-1 mixer: every
op under a ``Mamba1`` node (``ssm/<node>``: the causal taps, ``x_proj``,
``dt_proj`` and the softplus, the selective scan, the gate) and its two
projections (``fc/layer<l>_mamba_{in,out}_proj``), forward and backward
together."""
import sscan_scopes


def compute(trace, counters, run):
    return sscan_scopes.ms(trace, run, "mamba")
