"""The least time the chip could take for the sliding-window layers'
attention kernels of a step — required operations of the scores and
values over the band (``flops/dots3_symbol.attn_window_flops``: ``min(i +
1, 513)`` keys for query i, 256 multiply-adds a score and 128 a value,
the held heads), forward and backward (three forwards), every window
layer, over the bf16 peak — as a share of
``dots3_attn_window_device_ms``. Bound by operations by the count; a
band of 513 keys on tiles of 1,024 computes two tiles of 1,024 x 1,024
scores for every 1,024 x 513 it needs, so a quarter is the most this
tiling can read."""
import dots3_scopes
import share_scopes


def compute(trace, counters, run):
    flops = dots3_scopes.dots3_flops(run)
    if not flops:
        return None
    cfg = run["cfg"]
    return share_scopes.roofline_share(
        run, flops.attn_window_flops(cfg) * flops.window_layers(cfg),
        dots3_scopes.ms(trace, run, "window"))
