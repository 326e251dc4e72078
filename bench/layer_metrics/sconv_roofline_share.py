"""The least time the chip could take for the gated short convolutions
of a step — their required bytes over the HBM peak
(``flops/lfm2_symbol.sconv_bytes``: the op's input and output once
forward; its input, its output's cotangent and its input's cotangent
once backward), every ``conv`` layer — as a share of the ``sconv``
nodes' device time. The op has no matrix product: bytes are its only
bound (0.16 ms a layer forward and 0.29 backward at T 8,192). What the
float32 tables cost between the bf16 ends, the shifted reads and the
recomputation under the checkpoint is what this share is low by."""
import sconv_scopes


def compute(trace, counters, run):
    flops = sconv_scopes.lfm2_flops(run)
    busy_ms = sconv_scopes.ms(trace, run, "sconv")
    if not flops or not busy_ms or not run.get("peak"):
        return None
    cfg = run["cfg"]
    per_step = (flops.layers(cfg, flops.CONV) * run["batch"] / run["chips"]
                * (flops.sconv_bytes(cfg)
                   + flops.sconv_bytes(cfg, backward=True)))
    return 100.0 * 1e3 * per_step / run["peak"]["hbm_bytes_s"] / busy_ms
