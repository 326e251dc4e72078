"""Busy milliseconds of device 0 per step in the linear-attention
layers' five wide projections (the ``FullyConnected`` nodes named
``layer<i>_gdn_{q,k,v,g,o}_proj``: 3840 -> 2880 twice, 3840 -> 5760
twice, 5760 -> 3840), forward and backward together: the part of a
linear-attention layer that is plain matrix products. The two
projections of one column a head (``_a_proj``, ``_b_proj``) are not in
it."""
import gdn_scopes


def compute(trace, counters, run):
    return gdn_scopes.ms(trace, run, "proj")
