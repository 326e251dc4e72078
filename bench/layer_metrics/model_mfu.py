"""Model FLOP/s utilization of the traced run's window: required
operations per sample (training: three forwards) times the samples per
second the traffic kind reports, over chips times the bf16 peak.
Recomputed operations never count. It is ``train_samples_s`` times a
constant of the cell."""


def compute(trace, counters, run):
    if not run.get("peak") or not run.get("samples_s"):
        return None
    flops_s = (run["flops_multiplier"] * run["forward_flops_per_sample"]
               * run["samples_s"])
    return 100.0 * flops_s / (run["chips"] * run["peak"]["bf16_flops"])
