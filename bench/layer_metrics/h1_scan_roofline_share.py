"""The least time the chip could take for the state-space scans of a
step of a parallel-mixer model — the larger of their required operations
over the bf16 peak and their required bytes over the HBM peak
(``flops/falcon_h1_symbol``: ``scan_flops``, the chunked form's four
products with the causal triangle counted once, and ``scan_bytes``,
operands in and result out once), forward and backward (three forwards of
each), every layer — as a share of the ``scan`` scope's device time. At
state 256 on heads of 128 the two bounds are near each other (0.050 ms
of operations against 0.046 ms of bytes a layer forward at T 4,096); the
Nemotron cell's pair, at half the state, is bound by bytes."""
import h1_scopes
import share_scopes


def compute(trace, counters, run):
    flops = share_scopes.flops_of(run)
    busy_ms = h1_scopes.ms(trace, run, "scan")
    if (not busy_ms or not run.get("peak")
            or not getattr(flops, "scan_flops", None)
            or not getattr(flops, "layers", None)):
        return None
    cfg, peak = run["cfg"], run["peak"]
    per_step = (run["flops_multiplier"] * flops.layers(cfg) * run["batch"]
                / run["chips"])
    least_s = per_step * max(flops.scan_flops(cfg) / peak["bf16_flops"],
                             flops.scan_bytes(cfg) / peak["hbm_bytes_s"])
    return 100.0 * 1e3 * least_s / busy_ms
