"""The least time the chip could take for the attention kernel of a step
— required operations of the causal scores and values
(``flops/olmoe_symbol.attn_kernel_flops``), forward and backward (three
forwards: the scores the backward recomputes do not count), over the
bf16 peak — as a share of the ``attn`` class's device time (the kernel's
three calls and RoPE)."""
import lm_scopes


def compute(trace, counters, run):
    return lm_scopes.roofline_share(trace, run, "attn", "attn_kernel_flops")
