"""Busy milliseconds of device 0 per step in the attention kernels of
the window layers (scope ``attn/<node>/window``: the flash kernel's
three calls over the band and the sink's arithmetic), forward and
backward together."""
import share_scopes


def compute(trace, counters, run):
    return share_scopes.attn_ms(trace, run, "window")
