"""Rows the held experts of ALL expert layers received in the last step
of the window, summed, over what uniform routing sends them (layers x
tokens x experts-per-token x held / routed-over;
``expected_share_rows`` of the configuration's operations module a
layer). From the model's count outputs. The step's length follows this
sum (a row costs time in every grouped product), so it is the quantity a
share cell's run-to-run spread follows; 1.0 is a deployment's balanced
routing, the share's buffer (``share.share_rows_bound``) is a multiple
of it a layer, and the cell's own check fails a run whose busiest layer
is past that. Like ``moe_load_max_over_mean`` it describes the traffic
and the seeded weights more than the code."""
import share_scopes


def compute(trace, counters, run):
    flops, rows = share_scopes.flops_of(run), share_scopes.held_rows(run)
    expected = flops and getattr(flops, "expected_share_rows", None)
    if not expected or not rows:
        return None
    return sum(rows) / float(len(rows) * run["batch"] * expected(run["cfg"]))
