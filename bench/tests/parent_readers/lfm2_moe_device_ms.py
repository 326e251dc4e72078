"""Busy milliseconds of device 0 per step in ops whose scope's class is
``moe`` in a short-convolution model's share (the router over all 64
experts with its ``+ 1e-6`` renormalisation, the compaction of the rows
routed to the 8 held experts, three grouped products a pass at 2048 /
1536 over the share's buffer, the scatter back), forward and backward
together. None for a configuration of another operations module."""
import lm_scopes
import sconv_scopes


def compute(trace, counters, run):
    if not sconv_scopes.lfm2_flops(run):
        return None
    return lm_scopes.class_ms(trace, run, "moe")
