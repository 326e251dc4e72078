"""The least time the chip could take for the ``wgrad`` pass of a step's
convolutions — for each ``Convolution`` node the LARGER of its operations
over the bf16 peak and its bytes over the HBM peak (``conv_scopes.bounds``,
from the cell's symbol alone), summed over the nodes — as a share of
``conv_wgrad_device_ms``. What XLA fuses into a convolution (BatchNorm's
reductions, an activation) is in the milliseconds and not in the bound:
the share reads low by it, never high."""
import conv_scopes


def compute(trace, counters, run):
    return conv_scopes.pass_roofline_share(trace, run, "wgrad")
