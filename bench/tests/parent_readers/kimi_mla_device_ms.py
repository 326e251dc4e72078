"""Busy milliseconds of device 0 per step in the latent-attention layer
of a Kimi Linear model: every op under the ``LatentAttention`` node
(``attn/<node>``: the latent's norm, the up-projection to every head's
keys and values, the query's lanes padded to a lane row — nothing is
rotated — and the flash pair of two key operands) and the three
``FullyConnected`` nodes round it (``layer<i>_{q,kv_a,o}_proj``),
forward and backward together. None for a program without a KDA
projection: Kanana's latent layers are ``mla_device_ms``'s."""
import kda_scopes
import mla_scopes


def compute(trace, counters, run):
    proj = kda_scopes.ms(trace, run, "mla_proj")
    node = mla_scopes.ms(trace, run, "mla")
    if proj is None or node is None:
        return None
    return node + proj
