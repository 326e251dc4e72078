"""The least time the chip could take for the grouped-attention layer's
kernels of a step — the larger of the causal pairs' required operations
over the bf16 peak (``flops/solar_open2_symbol.gqa_kernel_flops``: 128
multiply-adds a score and 128 a value a pair of the triangle and held
query head) and their required bytes over the HBM peak
(``gqa_kernel_bytes``: queries, keys and values in, the output out,
once), forward and backward (three forwards: the scores the backward
recomputes do not count) — as a share of the scope ``attn/<node>/full``'s
device time. Bound by operations by the count (0.70 ms forward against
0.09 ms of bytes at 32 heads and T 4,096); what the diagonal's tiles
compute past the diagonal, the mask, the softmax's own arithmetic and the
per-step cost can only lower it. It FAILS THE RUN where the ops named
``flash_`` hold under half of the scope's time."""
import share_scopes
import solar2_scopes


def compute(trace, counters, run):
    flops = solar2_scopes.solar2_flops(run)
    if not flops or not run.get("peak"):
        return None
    busy_ms = share_scopes.attn_ms(trace, run, "full")
    if not busy_ms:
        return None
    cfg = run["cfg"]
    return solar2_scopes.roofline_share(
        trace, run, flops.gqa_layers(cfg), flops.gqa_kernel_flops(cfg),
        flops.gqa_kernel_bytes(cfg), busy_ms, ("flash_fwd", "flash_bwd"))
