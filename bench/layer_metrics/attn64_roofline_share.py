"""The least time the chip could take for the attention kernels of a
step on heads of 64 — required operations of the causal scores and
values (``flops/lfm2_symbol.attn64_kernel_flops``: 32 heads, 64 + 64 a
pair, (T + 1) / 2 keys a query), forward and backward (three forwards:
the scores the backward recomputes do not count), every
``full_attention`` layer, over the bf16 peak — as a share of
``attn64_device_ms``. A head of 64 fills half the 128 lanes of a score
product's contraction and of a value product's result, so the kernel
cannot reach what heads of 128 reach; the mask, the tiles above the
diagonal's edge and the per-step cost lower it further."""
import sconv_scopes
import share_scopes


def compute(trace, counters, run):
    flops = sconv_scopes.lfm2_flops(run)
    if not flops:
        return None
    cfg = run["cfg"]
    return share_scopes.roofline_share(
        run, flops.attn64_kernel_flops(cfg) * flops.layers(cfg, flops.FULL),
        share_scopes.attn_ms(trace, run, "full"))
