"""Busy milliseconds of device 0 per step in the looped stack's norms
(the op class ``norm``: four ``RMSNorm`` nodes a layer visit and the
final norm after every pass, 4 x 24 + 4 = 100 nodes a step in the cell),
forward and backward together. Each is a pass over the stream's 2,048
columns bound by bandwidth; a norm on a sub-layer's output reads what a
product just wrote."""
import ouro_scopes


def compute(trace, counters, run):
    return ouro_scopes.class_ms(trace, run, "norm")
