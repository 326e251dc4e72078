"""Busy milliseconds of device 0 per step in the flash calls of the
differential CROSS-attention layers (``attn/<node>/diff/cross``: a layer's
own queries against ONE earlier layer's keys and values), forward and
backward together: where the shared keys' and values' gradients are made,
one part a reader, that the step sums."""
import sscan_scopes


def compute(trace, counters, run):
    return sscan_scopes.ms(trace, run, "diff_cross")
