"""Busy milliseconds of device 0 per step in a Solar Open 2 share's KDA
projections (the ``FullyConnected`` nodes ``layer<i>_kda_{q,k,v,o}_proj``:
4096 -> 4096 three times and back at the 32 heads held; the low-rank
pairs ``layer<i>_kda_{f,g}_{a,b}_proj``: 4096 -> 128 -> 4096 into the
decay and into the gate; ``layer<i>_kda_b_proj``: 4096 -> 32), three
layers, forward and backward together: the part of a KDA layer that is
plain matrix products."""
import kda_scopes
import solar2_scopes


def compute(trace, counters, run):
    if not solar2_scopes.solar2_flops(run):
        return None
    return kda_scopes.ms(trace, run, "kda_proj")
