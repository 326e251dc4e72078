"""Busy milliseconds of device 0 per step under the Kimi Delta Attention
nodes (scope ``gdn/<node>`` of a ``GatedDeltaNet`` op in its channel
form, at all of its heads or at the share held: the three causal
convolutions, the write strengths, the ``kda_`` kernel pair with the
unit keys and queries and the decays a channel it makes in VMEM, the
per-head norm and its sigmoid gate), forward and backward together, what
the backward recomputes of the forward included. The nine projections
round it are ``FullyConnected`` nodes of their own and not in it
(``kda_proj_device_ms``). None for a configuration whose operations
module counts no KDA core."""
import gdn_scopes
import kda_scopes


def compute(trace, counters, run):
    if not kda_scopes.kimi_flops(run):
        return None
    return gdn_scopes.ms(trace, run, "gdn")
