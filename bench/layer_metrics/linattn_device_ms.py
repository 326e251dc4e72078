"""Busy milliseconds of device 0 per step under the ``LinearAttention``
nodes (``linattn/<node>``: the recurrence with a fixed decay a head on the
state-space scan's kernel pair, its decay tables and scale, the RMSNorm a
head and the sigmoid gate behind it), forward and backward together, every
layer that has one. The projections round the node are
``linattn_proj_device_ms``."""
import linblock_scopes


def compute(trace, counters, run):
    return linblock_scopes.ms(trace, run, "linattn")
