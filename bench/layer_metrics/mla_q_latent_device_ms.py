"""Busy milliseconds of device 0 per step under the query latent's three
nodes (``*_q_latent_a_proj``, ``*_q_latent_norm``, ``*_q_latent_b_proj``:
the down-projection of the sub-layer's input to ``q_lora_rank``, its
RMSNorm, the up-projection to every head's query), every layer that has
them, forward and backward. They take the place of the one query
projection of a latent-attention model without a query latent."""
import hc_scopes


def compute(trace, counters, run):
    return hc_scopes.ms(trace, run, "q_latent")
