"""Busy milliseconds of device 0 per step in the ``latent`` scope of the
latent-attention nodes (``attn/<node>/latent``): the latent's RMSNorm,
the up-projection, interleaved RoPE on every head's rotary query part
and on the one rotary key a token, the broadcast of that key over the
heads and the key's concatenation, forward and backward together — what
the mechanism adds over plain attention before the kernel runs."""
import mla_scopes


def compute(trace, counters, run):
    return mla_scopes.ms(trace, run, "latent")
