"""The least time the chip could take for the selected attention kernels
of a step — the larger of the required operations of the scores and values
over the KEPT pairs alone (the operations module's ``select_flops``:
``sum_t min(t + 1, topk)`` pairs a query head, ``head_dim`` multiply-adds
a score and a value), forward and backward (three forwards: the scores the
backward recomputes do not count), over the bf16 peak, and of the bytes
the pair must move (``select_bytes``: q, k, v, o, dO and the gradients
once, the mask's causal half once a key/value group and pass) over the
HBM's rate, every layer — as a share of ``attn_select_device_ms``. Bound
by operations by the count. The pair computes every pair of every live
causal tile and masks the dropped ones: at 8,192 tokens and 2,048 kept
keys 33.56 M pairs for 14.68 M kept, so 43.7% is the most this form can
read, before the mask's own arithmetic, until a selection skips tiles."""
import select_scopes
import share_scopes


def compute(trace, counters, run):
    flops = share_scopes.flops_of(run)
    busy_ms = select_scopes.ms(trace, run, ("select",), plain_only=True)
    peak = run.get("peak")
    if (not busy_ms or not peak or not getattr(flops, "select_flops", None)
            or not getattr(flops, "select_bytes", None)):
        return None
    cfg = run["cfg"]
    each = run["batch"] * flops.layers(cfg) / float(run["chips"])
    required_s = max(
        run["flops_multiplier"] * flops.select_flops(cfg)
        / peak["bf16_flops"],
        flops.select_bytes(cfg) / peak["hbm_bytes_s"]) * each
    return 100.0 * 1e3 * required_s / busy_ms
