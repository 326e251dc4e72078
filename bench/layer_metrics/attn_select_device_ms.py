"""Busy milliseconds of device 0 per step in the kernels of plain
``Attention`` nodes that a keep-mask feeds (scope ``select``: the selected
flash pair ``flashsel_fwd_*`` / ``flashsel_bwd_*``, a q tile the same rows
of every query head of a key/value head's group, every live causal tile
masked by the keep-mask's tile), forward and backward together, every
layer. A ``LatentAttention`` node's ``select`` (it traces a scope
``latent`` besides) is not read here."""
import select_scopes


def compute(trace, counters, run):
    return select_scopes.ms(trace, run, ("select",), plain_only=True)
