"""Busy milliseconds of device 0 per step in the ``delta_rule`` scope of
the gated delta-rule nodes (``gdn/<node>/.../delta_rule``): unit keys
and queries, write strengths, log decays and their running sums, a
chunk's triangular system, the products inside a chunk and with the
state, the recurrence over the chunks, forward and backward together
(the backward computes the forward again first)."""
import gdn_scopes


def compute(trace, counters, run):
    return gdn_scopes.ms(trace, run, "delta_rule")
