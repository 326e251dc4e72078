"""Busy milliseconds of device 0 per step in the looped stack's dense
SwiGLU (the ``FullyConnected`` nodes
``loop<t>_layer<i>_{gate,up,down}_proj``: three 2,048 x 5,632 products a
visit, 24 visits a step in the cell, 58% of the step's required
operations), forward and backward together."""
import ouro_scopes


def compute(trace, counters, run):
    return ouro_scopes.ms(trace, run, "mlp")
