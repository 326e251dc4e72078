"""Busy milliseconds of device 0 per step in the dense SwiGLUs of a
parallel-mixer model: the ``FullyConnected`` nodes
``layer<i>_{gate,up,down}_proj`` (5120 -> 10752 twice and back, the
widest dense products of the benchmark) and the two multipliers' nodes
(``layer<i>_gate_proj_scale`` on the gate's pre-activation,
``layer<i>_down_proj_scale`` on the result), forward and backward
together. The activation and the product between the projections are
elementwise nodes under names of their own and not in it."""
import h1_scopes


def compute(trace, counters, run):
    return h1_scopes.ms(trace, run, "mlp")
