"""Busy milliseconds of device 0 per step in what stands round the
attention kernels of a dots3 layer: the ``FullyConnected`` nodes
``layer<i>_{q_a,q_b,kv_a,attn_gate,o}_proj``, the query latent's norm and
rescale (``layer<i>_q_a_norm``, ``layer<i>_q_a_scale``), the
``LatentAttention`` node's scope ``latent`` (the key/value latent's norm
and rescale, the up-projection, the rotations of the query and of the
shared key) and its scope ``gate`` (the sigmoid a head on the kernels'
output), forward and backward together."""
import dots3_scopes


def compute(trace, counters, run):
    return dots3_scopes.ms(trace, run, "proj", "latent", "gate")
