"""Busy milliseconds of device 0 per step in ops whose scope's class is
``moe`` where the experts are un-gated relu² ones (``mlp_hidden_act``
``relu2``: the router over all experts, the compaction of the rows
routed here, two grouped products a pass at the expert width, the
scatter back), forward and backward together. None for a configuration
whose experts are gated."""
import lm_scopes


def compute(trace, counters, run):
    if run.get("cfg", {}).get("mlp_hidden_act") != "relu2":
        return None
    return lm_scopes.class_ms(trace, run, "moe")
