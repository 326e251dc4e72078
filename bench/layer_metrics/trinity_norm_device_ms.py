"""Busy milliseconds of device 0 per step in the six norms of every
layer (the ``RMSNorm`` nodes ``layer<i>_{attn_norm, attn_post_norm,
ffn_norm, ffn_post_norm}`` over the stream's 2,048 columns and
``layer<i>_{q_norm, k_norm}`` over a head's own 128), forward and
backward together; ``final_norm`` is not counted. Each is a pass over
HBM by the count."""
import afmoe_scopes


def compute(trace, counters, run):
    return afmoe_scopes.ms(trace, run, "norm")
