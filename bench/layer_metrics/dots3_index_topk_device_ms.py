"""Busy milliseconds of device 0 per step in the indexers' choice of the
keys (scope ``index/topk``: the k-th largest score of every row found by
32 counting passes over the [T, T] scores' bits, the compare, the count):
the part of ``dots3_index_device_ms`` that is no matrix product."""
import dots3_scopes


def compute(trace, counters, run):
    return dots3_scopes.ms(trace, run, "index_topk")
