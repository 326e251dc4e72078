"""Busy milliseconds of device 0 per step in the gated grouped-attention
node of a Solar Open 2 share: the attention kernels (scope
``attn/<node>/full``: the ``flash_`` pair over the causal triangle,
nothing rotated, 32 query heads on 4 key/value heads of 128) and the
sigmoid gate on their output (``attn/<node>/gate``: ``out *
sigmoid(gate)`` an element each in float32, and its backward), forward
and backward together. The five projections are
``solar2_gqa_proj_device_ms``."""
import afmoe_scopes
import share_scopes
import solar2_scopes


def compute(trace, counters, run):
    if not solar2_scopes.solar2_flops(run):
        return None
    kernels = share_scopes.attn_ms(trace, run, "full")
    gate = afmoe_scopes.ms(trace, run, "gate")
    if kernels is None or gate is None:
        return None
    return kernels + gate
