"""Busy milliseconds of device 0 per step in the attention kernels of a
parallel-mixer model (scope ``attn/layer<i>_attn/full``: the flash pair
``flash_fwd_`` / ``flash_bwd_bf16_q1024_k1024`` over the causal triangle,
10 query heads on 2 key/value heads of 128, five to one), forward and
backward together. RoPE and the key's multiplier are nodes of their own
and not in it."""
import h1_scopes


def compute(trace, counters, run):
    return h1_scopes.ms(trace, run, "attn_full")
