"""Rows the held experts of ALL sparse layers received in the last step
of the window, summed, over what uniform routing sends them (layers x
tokens x experts-per-token x held / routed-over; 8 x 4,096 in the cell).
From the model's count outputs. The step's length follows this sum (a
row costs time in every grouped product), so it is the quantity the
cell's run-to-run spread follows; 1.0 is a deployment's balanced routing.
It describes the traffic and the seeded weights more than the code."""
import sconv_scopes


def compute(trace, counters, run):
    flops, counts = sconv_scopes.lfm2_flops(run), run.get("expert_counts")
    cfg = run.get("cfg", {})
    if not flops or not counts or not cfg.get("share"):
        return None
    lo = cfg["share"].get("expert_offset", 0)
    held = sum(sum(layer[lo:lo + cfg["num_experts"]]) for layer in counts)
    return held / float(len(counts) * run["batch"]
                        * flops.expected_share_rows(cfg))
