"""Busy milliseconds of device 0 per step in the looped stack's attention
(the op class ``attn``: the flash pair's three calls a visit,
``attn/loop<t>_layer<i>_attn/full``, and the two ``RoPE`` nodes a visit),
forward and backward together, 24 visits a step in the cell."""
import ouro_scopes


def compute(trace, counters, run):
    return ouro_scopes.class_ms(trace, run, "attn")
