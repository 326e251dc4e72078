"""Busy milliseconds of device 0 per step under the scope ``hc_coeff`` of
the ``HyperCoeff`` nodes: the one pass over the residual streams that
gives a sub-layer's n (n + 2) coefficient products and the stream's mean
square, the sigmoid, clamp and exp on them, and the backward (the
products' two transposes: onto the stream and onto ``phi``)."""
import hc_scopes


def compute(trace, counters, run):
    return hc_scopes.ms(trace, run, "hc_coeff")
