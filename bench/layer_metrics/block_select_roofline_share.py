"""The least time the chip could take for the choice of blocks of a step,
as a share of ``block_select_device_ms``: the larger of the scorer's
required operations over the bf16 peak (the operations module's
``block_select_flops``: every query head against the pooled keys it may
see) and its required bytes over the HBM peak (``block_select_bytes``: the
queries and the keys in once, a byte a (query, block) out), once a step
(the choice has no backward), every layer that chooses (``layers``). Low by
what the ``jax.numpy`` scorer writes between its products and its choice
(the float32 window scores a row block, the block scores) and by the
keep-mask a KEY that ``Attention`` reads (268 MB at 16,384 tokens where the
choice itself is 4 MB)."""
import linblock_scopes
import share_scopes


def compute(trace, counters, run):
    flops = share_scopes.flops_of(run)
    busy_ms = linblock_scopes.ms(trace, run, "blocks")
    least = linblock_scopes.least_ms(
        run, *(getattr(flops, name, None) for name in (
            "block_select_flops", "block_select_bytes", "layers")))
    if not busy_ms or not least:
        return None
    return 100.0 * least / busy_ms
