"""Busy milliseconds of device 0 per step in ops whose scope's class is
``moe`` where the layer holds a share of its experts (TopKMoE: the
router over all experts, the compaction of the rows routed here, the
grouped expert matmuls over the share's buffer, the scatter back),
forward and backward together. None for a configuration without a
share."""
import lm_scopes


def compute(trace, counters, run):
    if not run.get("cfg", {}).get("share"):
        return None
    return lm_scopes.class_ms(trace, run, "moe")
