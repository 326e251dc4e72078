"""The least time the chip could take for the expert layers of a step —
required operations of the router and of the ``top_k`` experts of every
token (``flops/olmoe_symbol.moe_flops``), forward and backward, over the
bf16 peak; compute-bound at about 512 rows an expert — as a share of the
``moe`` class's device time. Sort, gather and combine are in the time
and need no operation, so they can only lower it."""
import lm_scopes


def compute(trace, counters, run):
    return lm_scopes.roofline_share(trace, run, "moe", "moe_flops")
