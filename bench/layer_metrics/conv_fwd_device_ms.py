"""Busy milliseconds of device 0 a WHOLE step in ops whose scope's class
is ``conv`` and whose pass is ``fwd``: the forward convolutions of every
``Convolution`` node, with what XLA fused into them. Summed over the
step programs that lie whole inside the slice and divided by their
number, not by ``trace_steps`` (``conv_scopes.whole_steps``)."""
import conv_scopes


def compute(trace, counters, run):
    return conv_scopes.pass_ms(trace, run, "fwd")
