"""Busy milliseconds of device 0 per step in the nodes of the looped
stack's layers (every ``loop<t>_layer<i>_*`` node: 24 layer visits a
step in the cell, each its four projections, the rotation, the attention
kernels, the SwiGLU's three products and four norms), forward and
backward together. The auto-named nodes between them (the residual adds,
the SwiGLU's activation and product) count where XLA fuses them into a
named node's op."""
import ouro_scopes


def compute(trace, counters, run):
    return ouro_scopes.ms(trace, run, "layers")
