"""Busy milliseconds of device 0 per step under the state-space nodes
(scope ``ssm/<node>`` of a ``Mamba2`` op: the causal convolution, the
step sizes and decays, the chunked scan, the skip, the gate and the
grouped norm), forward and backward together, what the backward
recomputes of the forward included. The in and out projections round it
are ``FullyConnected`` nodes of their own and not in it
(``ssm_proj_device_ms``)."""
import ssm_scopes


def compute(trace, counters, run):
    return ssm_scopes.ms(trace, run, "ssm")
