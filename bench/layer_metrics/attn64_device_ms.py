"""Busy milliseconds of device 0 per step in the attention kernels of a
model whose heads are 64 wide (scope ``attn/<node>/full``: the flash
kernel's forward and its one-pass backward over the causal triangle, 32
query heads on 8 key/value heads, half a lane row a head), forward and
backward together. The per-head norms and RoPE are nodes of their own
and not in it. None for a configuration of another operations module."""
import sconv_scopes
import share_scopes


def compute(trace, counters, run):
    if not sconv_scopes.lfm2_flops(run):
        return None
    return share_scopes.attn_ms(trace, run, "full")
