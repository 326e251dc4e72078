"""The least time the chip could take for the expert layers of a step
where each holds a share of its experts — required operations of the
router at its full width and of a SwiGLU expert for the rows the held
experts REALLY received in the last step (the model's count outputs;
``flops/mimo_v2_symbol.moe_share_flops``), forward and backward, over
the bf16 peak — as a share of the ``moe`` class's device time. Top-k,
compaction, sort and scatter are in the time and need no operation, so
they can only lower it."""
import lm_scopes
import share_scopes


def compute(trace, counters, run):
    flops, rows = share_scopes.flops_of(run), share_scopes.held_rows(run)
    count = flops and getattr(flops, "moe_share_flops", None)
    if not count or not rows:
        return None
    return share_scopes.roofline_share(
        run, sum(count(run["cfg"], r / float(run["batch"])) for r in rows),
        lm_scopes.class_ms(trace, run, "moe"))
