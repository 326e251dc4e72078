"""The least time the chip could take for the window layers' attention
kernels of a step — required operations of the scores and values over
the band (``flops/mimo_v2_symbol.attn_window_flops``: ``min(i + 1,
window)`` keys for query i), forward and backward (three forwards: the
scores the backward recomputes do not count), every window layer, over
the bf16 peak — as a share of ``attn_window_device_ms``. Compute-bound
by the count (192 + 128 operations a pair a byte pair read once a
tile); what the tiles compute outside the band, the mask and the
per-step cost can only lower it."""
import share_scopes


def compute(trace, counters, run):
    flops = share_scopes.flops_of(run)
    count = flops and getattr(flops, "attn_window_flops", None)
    if not count:
        return None
    return share_scopes.roofline_share(
        run, count(run["cfg"]) * flops.window_layers(run["cfg"]),
        share_scopes.attn_ms(trace, run, "window"))
