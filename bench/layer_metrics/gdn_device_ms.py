"""Busy milliseconds of device 0 per step under the gated delta-rule
nodes (scope ``gdn/<node>`` of a ``GatedDeltaNet`` op: the three causal
convolutions, unit keys and queries, write strengths and decays, the
chunk form of the delta rule, the per-head norm and its gate), forward
and backward together, what the backward recomputes of the forward
included. The projections round it are ``FullyConnected`` nodes of their
own and not in it (``gdn_proj_device_ms``)."""
import gdn_scopes


def compute(trace, counters, run):
    return gdn_scopes.ms(trace, run, "gdn")
