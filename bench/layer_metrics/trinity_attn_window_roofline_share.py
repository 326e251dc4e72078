"""The least time the chip could take for the sliding-window layers'
attention kernels of a step — required operations of the scores and
values over the band (``flops/afmoe_symbol.attn_window_flops``: ``min(i +
1, 2048)`` keys for query i, 128 multiply-adds a score and 128 a value),
forward and backward (three forwards: the scores the backward recomputes
do not count), every sliding layer, over the bf16 peak — as a share of
``trinity_attn_window_device_ms``. Bound by operations by the count (a
q tile of 1,024 rows reads its three k tiles once and computes 2 x 256
operations a pair); what the edge tiles compute outside the band, the
mask, the softmax's own arithmetic and the per-step cost can only lower
it."""
import afmoe_scopes
import share_scopes


def compute(trace, counters, run):
    flops = afmoe_scopes.afmoe_flops(run)
    if not flops:
        return None
    cfg = run["cfg"]
    return share_scopes.roofline_share(
        run, flops.attn_window_flops(cfg) * flops.window_layers(cfg),
        share_scopes.attn_ms(trace, run, "window"))
