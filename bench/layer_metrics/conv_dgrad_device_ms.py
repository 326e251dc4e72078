"""Busy milliseconds of device 0 a WHOLE step in ops whose scope's class
is ``conv`` and whose pass is ``dgrad``: the data-gradient convolutions
(scope ``dgrad`` inside the node's, ``ops/nn.py::_conv_named_grads``) of
every ``Convolution`` node, with what XLA fused into them. Whole steps as
``conv_fwd_device_ms``; None where the program names no gradient (an
older commit), never 0."""
import conv_scopes


def compute(trace, counters, run):
    return conv_scopes.pass_ms(trace, run, "dgrad")
