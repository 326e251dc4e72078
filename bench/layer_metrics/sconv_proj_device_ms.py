"""Busy milliseconds of device 0 per step in the short-convolution
layers' two projections (the ``FullyConnected`` nodes named
``layer<i>_conv_in_proj``, 2048 -> 6144, and ``layer<i>_conv_out_proj``,
2048 -> 2048), forward and backward together: the part of a ``conv``
layer's mixer that is plain matrix products. None for a program without
a ``ShortConv`` node."""
import sconv_scopes


def compute(trace, counters, run):
    return sconv_scopes.ms(trace, run, "proj")
