"""(query, key) pairs the full-attention layers kept in the last step of
the window, summed over layers and sequences, over ``layers x sequences x
sum_t min(t + 1, index_topk)`` (2 x 6,292,480 in the cell). From the
model's selection-count outputs. Exactly 1.0 or the run is not
``correct``: a selection that keeps more or fewer keys is another
model."""
import dots3_scopes


def compute(trace, counters, run):
    flops, kept = dots3_scopes.dots3_flops(run), run.get("keys_selected")
    if not flops or not kept:
        return None
    want = flops.selected_pairs(run["cfg"]) * sum(len(l) for l in kept)
    ratio = sum(sum(layer) for layer in kept) / float(want)
    return ratio, ratio == 1.0, "kept %s, want %d each" % (
        kept, flops.selected_pairs(run["cfg"]))
