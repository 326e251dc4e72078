"""Busy milliseconds of device 0 per step in the attention kernels of a
Trinity model's sliding-window layers (scope ``attn/<node>/window``: the
flash pair over a band of 2,048 keys, 8 query heads on one key/value
head), forward and backward together. None for a configuration whose
operations module counts no gated attention layer."""
import afmoe_scopes
import share_scopes


def compute(trace, counters, run):
    if not afmoe_scopes.afmoe_flops(run):
        return None
    return share_scopes.attn_ms(trace, run, "window")
