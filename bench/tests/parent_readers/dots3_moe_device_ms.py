"""Busy milliseconds of device 0 per step in the expert layers of a
dots3 model's share: ops whose scope's class is ``moe`` (the router over
all 256 experts, the compaction of the rows routed to the held experts,
three grouped products a pass at 5120 / 1536 over the share's buffer, the
row moves back) and the shared expert's three ``FullyConnected`` nodes
(``layer<i>_shared_{gate,up,down}_proj``), forward and backward together.
None for a configuration whose operations module counts no selection."""
import dots3_scopes
import lm_scopes
import mla_scopes


def compute(trace, counters, run):
    if not dots3_scopes.dots3_flops(run):
        return None
    routed = lm_scopes.class_ms(trace, run, "moe")
    shared = mla_scopes.ms(trace, run, "shared")
    if routed is None or shared is None:
        return None
    return routed + shared
