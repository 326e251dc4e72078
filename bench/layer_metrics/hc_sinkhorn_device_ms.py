"""Busy milliseconds of device 0 per step under the scope ``hc_sinkhorn``
of the ``HyperCoeff`` nodes: the iterations that make a token's carry
matrix doubly stochastic (columns then rows, ``hc_sinkhorn_iters`` times),
the check of what they left, and their backward, which recomputes them.
Elementwise over [n, n, tokens] float32: no operation the count of
required operations holds, all of it overhead to keep small."""
import hc_scopes


def compute(trace, counters, run):
    return hc_scopes.ms(trace, run, "hc_sinkhorn")
