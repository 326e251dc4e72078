"""Busy milliseconds of device 0 per step in the shared experts' three
projections (the ``FullyConnected`` nodes named
``layer<i>_shared_{gate,up,down}_proj``: one SwiGLU that every token
passes, whatever the router says), every layer that has one, forward and
backward together. The activation and the product between them are
elementwise nodes under names of their own and not in it; the routed
experts are ``moe_share_device_ms``'s."""
import mla_scopes


def compute(trace, counters, run):
    return mla_scopes.ms(trace, run, "shared")
