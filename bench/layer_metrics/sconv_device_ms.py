"""Busy milliseconds of device 0 per step under the gated
short-convolution nodes (scope ``sconv/<node>`` of a ``ShortConv`` op:
the gate ``B * x``, the three taps' shifted multiply-adds, the gate ``C *
.``, all in float32 between a bf16 input and output), forward and
backward together, what the backward recomputes of the forward included.
The projections round it are ``FullyConnected`` nodes of their own and
not in it (``sconv_proj_device_ms``)."""
import sconv_scopes


def compute(trace, counters, run):
    return sconv_scopes.ms(trace, run, "sconv")
