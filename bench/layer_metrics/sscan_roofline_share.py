"""The least time the chip could take for the selective scans of a step —
their required bytes over the HBM peak (``flops/phi4_flash_symbol``:
``sscan_bytes``, ``x``, ``dt``, ``B`` and ``C`` in and ``m`` out once),
forward and backward (three forwards), every Mamba-1 layer — as a share
of the ``sscan`` scope's device time. The scan is elementwise: a decay a
channel and a state index leaves no matrix product, ``bench/peaks.json``
has no vector peak, so the bound is the bytes alone (``sscan_flops``
counts the elementwise operations for whoever adds one). A scan that
keeps its state in VMEM is bound by its vector work long before its
bytes, so this share reads how far the vector work is from free, not how
near the kernel is to its own ceiling."""
import share_scopes
import sscan_scopes


def compute(trace, counters, run):
    flops = share_scopes.flops_of(run)
    busy_ms = sscan_scopes.ms(trace, run, "sscan")
    if (not busy_ms or not run.get("peak")
            or not getattr(flops, "sscan_bytes", None)
            or not getattr(flops, "mamba_layers", None)):
        return None
    cfg = run["cfg"]
    least_s = (run["flops_multiplier"] * flops.mamba_layers(cfg)
               * run["batch"] / run["chips"] * flops.sscan_bytes(cfg)
               / run["peak"]["hbm_bytes_s"])
    return 100.0 * 1e3 * least_s / busy_ms
