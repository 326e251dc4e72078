"""Busy milliseconds of device 0 per step under the ``Mamba2`` nodes of a
parallel-mixer model (scope ``ssm/layer<i>_ssm``: the causal taps with
the multipliers of ``x | B | C`` on their weights, step sizes and decays,
the chunked scan, the skip, the gate under ``z``'s multiplier and the
grouped norm), forward and backward together. The in and out projections
round it are ``FullyConnected`` nodes of their own and not in it
(``h1_mixer_device_ms`` holds them)."""
import h1_scopes


def compute(trace, counters, run):
    return h1_scopes.ms(trace, run, "ssm")
