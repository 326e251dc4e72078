"""Rows the held experts of ALL expert layers received in the last step
of the window, summed, over what uniform routing sends them (layers x
tokens x experts-per-token x held / routed-over; 4 x 1,024 in the cell).
From the model's count outputs. The step's length follows this sum (a
row costs time in every grouped product); 1.0 is a deployment's balanced
routing. It describes the traffic and the seeded weights more than the
code."""
import dots3_scopes
import share_scopes


def compute(trace, counters, run):
    flops, held = dots3_scopes.dots3_flops(run), share_scopes.held_rows(run)
    if not flops or not held:
        return None
    return sum(held) / float(len(held) * run["batch"]
                             * flops.expected_share_rows(run["cfg"]))
