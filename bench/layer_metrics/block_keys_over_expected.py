"""(query, key) pairs the ``BlockSelect`` nodes kept an execution over the
window, over what the rule's closed form predicts (the operations module's
``kept_pairs`` a sequence, times the batch): from the histogram
``attention.block_keys_kept``, which every execution of a node observes
from the device (sum / count: the mean pairs a node and execution, whatever
number of steps the window held). Exactly 1.0 or the run is not
``correct``: a choice that keeps more or fewer keys is another model."""
import share_scopes


def compute(trace, counters, run):
    flops = share_scopes.flops_of(run)
    seen = counters.get("telemetry", {}).get("attention.block_keys_kept")
    expected = getattr(flops, "kept_pairs", None)
    if not seen or not seen.get("count") or not expected:
        return None
    want = expected(run["cfg"]) * run["batch"]
    ratio = seen["sum"] / float(seen["count"]) / want
    return ratio, ratio == 1.0, (
        "kept %.0f over %d executions, want %d each"
        % (seen["sum"], seen["count"], want))
