"""The least time the chip could take for the state-space layers' scans
of a step — the larger of their required operations over the bf16 peak
and their required bytes over the HBM peak (``flops/nemotron_h_symbol``:
``scan_flops``, the chunked form's four products with the causal
triangle counted once, and ``scan_bytes``, operands in and result out
once), forward and backward (three forwards of each), every Mamba-2
block — as a share of the ``scan`` scope's device time. Bound by bytes
by the count (0.62 ms a block against 0.34 ms of operations at T 8,192):
the scan as ``jax.numpy`` writes its decays and partial results to HBM
many times over, which is what this share is low by."""
import share_scopes
import ssm_scopes


def compute(trace, counters, run):
    flops = share_scopes.flops_of(run)
    busy_ms = ssm_scopes.ms(trace, run, "scan")
    if (not busy_ms or not run.get("peak")
            or not getattr(flops, "scan_flops", None)):
        return None
    cfg, peak = run["cfg"], run["peak"]
    per_step = (run["flops_multiplier"] * flops.blocks(cfg, "M")
                * run["batch"] / run["chips"])
    least_s = per_step * max(flops.scan_flops(cfg) / peak["bf16_flops"],
                             flops.scan_bytes(cfg) / peak["hbm_bytes_s"])
    return 100.0 * 1e3 * least_s / busy_ms
