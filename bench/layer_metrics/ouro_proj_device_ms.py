"""Busy milliseconds of device 0 per step in the looped stack's attention
projections (the ``FullyConnected`` nodes
``loop<t>_layer<i>_{q,k,v,o}_proj``: four 2,048 x 2,048 products a visit,
24 visits a step in the cell), forward and backward together: every pass
reads the same four matrices and the weight gradient is summed over the
passes."""
import ouro_scopes


def compute(trace, counters, run):
    return ouro_scopes.ms(trace, run, "proj")
