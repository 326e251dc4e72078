"""Rows the held experts of the busiest expert layer received in the
last step of the window, over what uniform routing sends a share
(tokens x experts-per-token x held / routed-over; 1,024 in the cell).
From the model's count outputs. 1.0 is a deployment's balanced routing;
the share's buffer (``share.share_rows_bound``) is twice that, and the
cell's own check fails a run past it. Like ``moe_load_max_over_mean`` it
describes the traffic and the seeded weights more than the code."""
import share_scopes


def compute(trace, counters, run):
    flops, rows = share_scopes.flops_of(run), share_scopes.held_rows(run)
    expected = flops and getattr(flops, "expected_share_rows", None)
    if not expected or not rows:
        return None
    return max(rows) / float(run["batch"] * expected(run["cfg"]))
