"""Busy milliseconds of device 0 per step in ops whose scope's class is
``moe`` (TopKMoE: router, sort and gather, grouped expert matmuls,
combine), forward and backward together."""
import lm_scopes


def compute(trace, counters, run):
    return lm_scopes.class_ms(trace, run, "moe")
