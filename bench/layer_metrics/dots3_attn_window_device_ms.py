"""Busy milliseconds of device 0 per step in the sliding-window layers'
attention kernels (scope ``window`` of a ``LatentAttention`` node: the
single-key flash pair over the materialised 256-wide keys under a band
of 513 keys, on tiles of 1,024), forward and backward together."""
import dots3_scopes


def compute(trace, counters, run):
    return dots3_scopes.ms(trace, run, "window")
