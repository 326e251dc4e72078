"""Busy milliseconds of device 0 per step under the nodes of the
multi-token-prediction module (every node named ``mtp<k>_*``: the second
read of the embedding and its norm, the hidden state's norm, the
projection, the module's own block — attention, experts and mixing — its
norm, the second read of the head and its loss), forward and backward."""
import hc_scopes


def compute(trace, counters, run):
    return hc_scopes.ms(trace, run, "mtp")
