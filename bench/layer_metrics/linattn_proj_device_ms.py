"""Busy milliseconds of device 0 per step in the linear-attention layers'
five wide projections (the ``FullyConnected`` nodes named
``layer<i>_linattn_{q,k,v,g,o}_proj``: queries, keys, values, the output
gate and the output), forward and backward together: the part of a linear
layer that is plain matrix products, beside which its core is cheap."""
import linblock_scopes


def compute(trace, counters, run):
    return linblock_scopes.ms(trace, run, "linattn_proj")
