"""Busy milliseconds of device 0 per step in ops whose scope's class is
``moe`` where the layer holds a share of its experts (TopKMoE: the
router over all experts, the compaction of the rows routed here, the
grouped expert matmuls over the share's buffer — three a pass through
gated experts, two through un-gated ones — and the row moves back),
every expert layer, forward and backward together. A shared expert's
projections are ``FullyConnected`` nodes of their own and not in it
(``shared_expert_device_ms``). None for a configuration without a
share."""
import lm_scopes


def compute(trace, counters, run):
    if not run.get("cfg", {}).get("share"):
        return None
    return lm_scopes.class_ms(trace, run, "moe")
