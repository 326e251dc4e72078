"""Busy milliseconds of device 0 per step under the scope ``hc_mix`` of the
``HyperMix`` nodes (``hc/<node>_read`` and ``hc/<node>_write``): a
sub-layer's input read off the residual streams through the learned row,
its output written back beside the streams carried through the doubly
stochastic matrix, and the backward of both — every sub-layer of every
block, the prediction module's too."""
import hc_scopes


def compute(trace, counters, run):
    return hc_scopes.ms(trace, run, "hc_mix")
