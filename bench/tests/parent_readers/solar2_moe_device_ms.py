"""Busy milliseconds of device 0 per step in the expert layers of a Solar
Open 2 share, all four: ops whose scope's class is ``moe`` (the router
over all 320 experts, the compaction of the rows routed to the 10 held,
three grouped products a pass at 4096 / 1280 over the share's buffer, the
row moves back) and the shared expert's three ``FullyConnected`` nodes
(``layer<i>_shared_{gate,up,down}_proj``), forward and backward
together."""
import lm_scopes
import mla_scopes
import solar2_scopes


def compute(trace, counters, run):
    if not solar2_scopes.solar2_flops(run):
        return None
    routed = lm_scopes.class_ms(trace, run, "moe")
    shared = mla_scopes.ms(trace, run, "shared")
    if routed is None or shared is None:
        return None
    return routed + shared
