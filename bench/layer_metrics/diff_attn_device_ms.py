"""Busy milliseconds of device 0 per step under the ``DiffAttention``
nodes' four scopes (``attn/<node>/diff/{window,full,cross}``: the two
flash calls a layer, value width twice the query's; ``diff/combine``:
lambda, the difference of the two maps, its norm and the factor), forward
and backward together. The projections round the node are
``FullyConnected`` nodes of their own and not in it."""
import sscan_scopes


def compute(trace, counters, run):
    return sscan_scopes.ms(trace, run, "diff")
