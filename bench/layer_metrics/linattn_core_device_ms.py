"""Busy milliseconds of device 0 per step in the ``core`` scope of the
``LinearAttention`` nodes (``linattn/<node>/core``: ``S_t = lambda S_{t-1}
+ k_t v_t^T``, ``o_t = S_t^T q_t`` in the chunked form, the kernel pair
``ssd_fwd_*`` / ``ssd_bwd_*`` where the shapes take it, every head its own
group; the decay tables XLA makes for it and the scale), forward and
backward together."""
import linblock_scopes


def compute(trace, counters, run):
    return linblock_scopes.ms(trace, run, "linattn_core")
