"""Busy milliseconds of device 0 per step under the latent-attention
nodes (scope ``attn/<node>`` of a ``LatentAttention`` op: the latent's
norm, the up-projection to every head's keys and values, the rotations
where the model rotates, the key's concatenation and the attention
kernel's three calls), every layer that has the node, forward and
backward together. The query, down- and output projections round it are
``FullyConnected`` nodes of their own and not in it."""
import mla_scopes


def compute(trace, counters, run):
    return mla_scopes.ms(trace, run, "mla")
