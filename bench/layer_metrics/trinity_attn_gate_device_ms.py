"""Busy milliseconds of device 0 per step in the sigmoid gate on the
attention kernels' output (scope ``attn/<node>/gate``: ``out *
sigmoid(gate)`` an element each in float32, and its backward: two
products and the sigmoid's derivative), every layer, forward and
backward together. A pass over HBM by the count (three [T, 4096] bf16
arrays forward); a later PR that moves it into the kernel's epilogue is
read here."""
import afmoe_scopes


def compute(trace, counters, run):
    return afmoe_scopes.ms(trace, run, "gate")
