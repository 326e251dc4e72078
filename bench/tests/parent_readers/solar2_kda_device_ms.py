"""Busy milliseconds of device 0 per step under the Kimi Delta Attention
nodes of a Solar Open 2 share (scope ``gdn/<node>`` of a
``GatedDeltaNet`` op in its channel form at the 32 heads held: the three
causal convolutions, write strengths ``2 sigmoid``, the ``kda_`` kernel
pair with the unit norms and decays it makes in VMEM, the per-head norm
and its sigmoid gate), three layers, forward and backward together, what
the backward recomputes of the forward included. The nine projections
round it are ``FullyConnected`` nodes of their own and not in it
(``solar2_kda_proj_device_ms``). None for a configuration whose
operations module counts no KDA core beside a gated grouped attention."""
import gdn_scopes
import solar2_scopes


def compute(trace, counters, run):
    if not solar2_scopes.solar2_flops(run):
        return None
    return gdn_scopes.ms(trace, run, "gdn")
