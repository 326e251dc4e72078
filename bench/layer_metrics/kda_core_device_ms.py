"""Busy milliseconds of device 0 per step in the ``delta_rule`` scope of
the Kimi Delta Attention nodes (``gdn/<node>/.../delta_rule``): the
write strengths and the ``kda_fwd_`` / ``kda_bwd_`` pair (unit keys and
queries, the log decays a channel and their running sums, a chunk's two
decayed tables, its triangular system, the products with the state and
the recurrence over the chunks), every KDA layer, forward and backward
together (the backward kernel computes the forward's tables again
first). None for a configuration whose operations module counts no KDA
core."""
import gdn_scopes
import kda_scopes


def compute(trace, counters, run):
    if not kda_scopes.kimi_flops(run):
        return None
    return gdn_scopes.ms(trace, run, "delta_rule")
