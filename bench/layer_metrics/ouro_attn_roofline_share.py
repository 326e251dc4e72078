"""The least time the chip could take for the attention kernels of a
step of the looped stack — the larger of the causal triangle's required
operations over the bf16 peak (``flops/ouro_symbol.attention_flops``:
128 multiply-adds a score and 128 a value a pair of the triangle and
query head) and its required bytes over the HBM peak
(``attention_bytes``: ``q``, ``k`` and ``v`` in, the output out, once),
once a layer VISIT (``visits``: passes x layers, 24 in the cell), forward
and backward (three forwards: the scores the backward recomputes do not
count) — as a share of the scope ``attn/<node>/full``'s device time, the
same required work whatever computes it. Bound by operations by the
count (0.349 ms a visit forward against 0.082 ms of bytes at 16 heads of
128 and T 4,096); what the diagonal's tiles compute past the diagonal,
the mask, the softmax's own arithmetic and the per-call cost can only
lower it. It FAILS THE RUN where the ops named ``flash_`` hold under half
of the scope's time."""
import ouro_scopes
import share_scopes
import solar2_scopes


def compute(trace, counters, run):
    flops = ouro_scopes.ouro_flops(run)
    if not flops or not run.get("peak"):
        return None
    busy_ms = share_scopes.attn_ms(trace, run, "full")
    if not busy_ms:
        return None
    cfg = run["cfg"]
    return solar2_scopes.roofline_share(
        trace, run, flops.visits(cfg), flops.attention_flops(cfg),
        flops.attention_bytes(cfg), busy_ms, ("flash_fwd", "flash_bwd"))
