"""Busy milliseconds of device 0 per step in the ``delta_rule`` scope of
a Solar Open 2 share's KDA nodes (``gdn/<node>/.../delta_rule``): the
write strengths ``2 sigmoid(b)`` and the ``kda_fwd_`` / ``kda_bwd_`` pair
(unit keys and queries, the log decays a channel and their running sums,
a chunk's two decayed tables, its triangular system with entries up to 2,
the products with the state and the recurrence over the chunks), three
layers of 32 heads, forward and backward together (the backward kernel
computes the forward's tables again first)."""
import gdn_scopes
import solar2_scopes


def compute(trace, counters, run):
    if not solar2_scopes.solar2_flops(run):
        return None
    return gdn_scopes.ms(trace, run, "delta_rule")
