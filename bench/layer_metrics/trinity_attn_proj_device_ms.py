"""Busy milliseconds of device 0 per step in the attention layers' five
projections (the ``FullyConnected`` nodes ``layer<i>_{q,k,v,o}_proj``
and ``layer<i>_attn_gate_proj``: 2048 -> 4096, 512, 512, 4096 and back
from 4096), forward and backward together: the part of an attention
layer that is plain matrix products, the largest part of the step's
required operations."""
import afmoe_scopes


def compute(trace, counters, run):
    return afmoe_scopes.ms(trace, run, "attn_proj")
