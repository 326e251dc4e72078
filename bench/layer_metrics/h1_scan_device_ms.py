"""Busy milliseconds of device 0 per step in the ``scan`` scope of the
``Mamba2`` nodes of a parallel-mixer model: step sizes and decays, the
tables XLA makes from them and the kernel pair ``ssd_fwd_`` /
``ssd_bwd_bf16_q128_p128_n256`` (16 heads of 128 in one group, state 256:
a chunk's step is sixteen lane tiles and a [256, 2048] float32 state in
VMEM), the skip inside them, forward and backward together."""
import h1_scopes


def compute(trace, counters, run):
    return h1_scopes.ms(trace, run, "scan")
