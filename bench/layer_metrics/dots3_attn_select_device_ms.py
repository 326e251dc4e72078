"""Busy milliseconds of device 0 per step in the full-attention layers'
kernels (scope ``select``: the latent flash pair's selected variant,
``flash2sel_fwd_*`` and ``flash2sel_bwd_*``, every live tile masked by
the keep-mask's tile), forward and backward together."""
import dots3_scopes


def compute(trace, counters, run):
    return dots3_scopes.ms(trace, run, "select")
