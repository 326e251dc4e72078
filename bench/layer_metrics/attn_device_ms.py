"""Busy milliseconds of device 0 per step in ops whose scope's class is
``attn`` (the Attention op — the flash kernel on the TPU — and RoPE),
forward and backward together."""
import lm_scopes


def compute(trace, counters, run):
    return lm_scopes.class_ms(trace, run, "attn")
