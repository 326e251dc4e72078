"""Busy milliseconds of device 0 per step in the attention kernels of
the full-attention layers (scope ``attn/<node>/full``: the flash
kernel's three calls over the causal triangle, 8 query heads on one
key/value head), forward and backward together."""
import share_scopes


def compute(trace, counters, run):
    return share_scopes.attn_ms(trace, run, "full")
