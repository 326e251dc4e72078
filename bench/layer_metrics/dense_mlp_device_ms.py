"""Busy milliseconds of device 0 per step in the dense SwiGLUs' three
projections of a model with gated delta-rule layers (the
``FullyConnected`` nodes named ``layer<i>_{gate,up,down}_proj``, 3840 ->
11008 twice and back, every layer), forward and backward together. The
activation and the product between them are elementwise nodes under
names of their own and not in it."""
import gdn_scopes


def compute(trace, counters, run):
    return gdn_scopes.ms(trace, run, "mlp")
