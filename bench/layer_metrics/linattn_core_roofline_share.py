"""The least time the chip could take for the linear layers' cores of a
step, as a share of ``linattn_core_device_ms``: the larger of their
required operations over the bf16 peak (the operations module's
``scan_flops``: the chunked form's four products, the causal triangle of a
chunk counted once) and their required bytes over the HBM peak
(``scan_bytes``: q, k and v in and o out once), forward and backward (three
forwards of each), every layer that runs one (``scan_layers``). Bound by
bytes by the count at heads of 128 / 128 (0.33 ms a layer forward against
0.13 ms of operations at T 16,384)."""
import linblock_scopes
import share_scopes


def compute(trace, counters, run):
    flops = share_scopes.flops_of(run)
    busy_ms = linblock_scopes.ms(trace, run, "linattn_core")
    least = linblock_scopes.least_ms(
        run, *(getattr(flops, name, None) for name in (
            "scan_flops", "scan_bytes", "scan_layers")))
    if not busy_ms or not least:
        return None
    return 100.0 * run["flops_multiplier"] * least / busy_ms
