"""Busy milliseconds of device 0 per step in the grouped-attention
layer's five projections of a Solar Open 2 share (the ``FullyConnected``
nodes ``layer<i>_{q,k,v,o}_proj`` and ``layer<i>_attn_gate_proj``: 4096
-> 4096, 512, 512, 4096 and back from 4096 at 32 query heads on 4),
forward and backward together: the part of the layer that is plain matrix
products."""
import afmoe_scopes
import solar2_scopes


def compute(trace, counters, run):
    if not solar2_scopes.solar2_flops(run):
        return None
    return afmoe_scopes.ms(trace, run, "attn_proj")
