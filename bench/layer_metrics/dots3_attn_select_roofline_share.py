"""The least time the chip could take for the full-attention layers'
kernels of a step — required operations of the scores and values over
the SELECTED pairs alone (``flops/dots3_symbol.attn_select_flops``:
``sum_t min(t + 1, 2048)`` pairs a head, 192 multiply-adds a score and
128 a value, the held heads), forward and backward (three forwards: the
scores the backward recomputes do not count), every full layer, over the
bf16 peak — as a share of ``dots3_attn_select_device_ms``. Bound by
operations by the count. The pair computes every pair of every live
causal tile and masks the dropped ones: at 4,096 tokens 8.39 M pairs for
6.29 M kept, so 75% is the most this form can read, before the mask's
own arithmetic."""
import dots3_scopes
import share_scopes


def compute(trace, counters, run):
    flops = dots3_scopes.dots3_flops(run)
    if not flops:
        return None
    cfg = run["cfg"]
    return share_scopes.roofline_share(
        run, flops.attn_select_flops(cfg) * flops.full_layers(cfg),
        dots3_scopes.ms(trace, run, "select"))
