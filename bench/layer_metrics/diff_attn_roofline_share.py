"""The least time the chip could take for the differential-attention
layers' flash calls of a step — the larger of their required operations
over the bf16 peak (``flops/phi4_flash_symbol.diff_attn_flops``: two maps a
pair, a score row of the head's width and a read of the pair's value of
twice that, over the causal triangle or the window's band) and their
required bytes over the HBM peak (``diff_attn_bytes``), forward and
backward (three forwards: the scores the backward recomputes do not
count), every layer by its kind — as a share of the device time under
``diff/window``, ``diff/full`` and ``diff/cross`` (the combination is not
in it). Compute-bound by the count at 4,096 tokens."""
import share_scopes
import sscan_scopes

KINDS = ("window", "full", "cross")


def compute(trace, counters, run):
    flops = share_scopes.flops_of(run)
    busy_ms = sscan_scopes.ms(trace, run, "flash")
    if (not busy_ms or not run.get("peak")
            or not getattr(flops, "diff_attn_flops", None)):
        return None
    cfg, peak = run["cfg"], run["peak"]
    least_s = sum(
        flops.layers(cfg, kind) * max(
            flops.diff_attn_flops(cfg, kind) / peak["bf16_flops"],
            flops.diff_attn_bytes(cfg, kind) / peak["hbm_bytes_s"])
        for kind in KINDS)
    least_s *= run["flops_multiplier"] * run["batch"] / run["chips"]
    return 100.0 * 1e3 * least_s / busy_ms
