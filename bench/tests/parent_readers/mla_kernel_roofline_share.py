"""The least time the chip could take for the latent-attention layers'
attention kernels of a step — required operations of the causal scores
and values (``flops/kanana2_symbol.mla_kernel_flops``: every head, 192 a
score and 128 a value, (T + 1) / 2 keys a query), forward and backward
(three forwards: the scores the backward recomputes do not count), every
layer, over the bf16 peak — as a share of the ``full`` scope's device
time under those nodes. Compute-bound by the count; the tiles on the
diagonal, the mask and a grid step's fixed cost can only lower it."""
import mla_scopes
import share_scopes


def compute(trace, counters, run):
    flops = share_scopes.flops_of(run)
    count = flops and getattr(flops, "mla_kernel_flops", None)
    if not count:
        return None
    return share_scopes.roofline_share(
        run, count(run["cfg"]) * run["cfg"]["num_hidden_layers"],
        mla_scopes.ms(trace, run, "full"))
