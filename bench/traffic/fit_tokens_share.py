"""Traffic kind ``fit_tokens_share``: ``fit_tokens`` for a model of which
this chip holds a share (``cfg.share``: ``n_routed_experts`` of the
router's ``experts_of`` experts from ``expert_offset`` on, a buffer of
``share_rows_bound`` rows; heads, dense columns and vocabulary rows held
are in the configuration's own keys). Set-up, the ``fit`` call, the
window, ``train_samples_s`` and the traced run's ``matches_reference``
are ``fit_tokens``' own (its ``setup`` and ``reference_check``, through
``lib.load_module``); the token ids are uniform over the HELD
vocabulary. What differs is what is asked of the model's count outputs,
one vector per EXPERT layer (``moe_layer_freq``), each over all
``experts_of`` experts, fetched once after ``fit`` returns (the window's
last step):

  experts_routed_over_all   every expert layer's counts are
                            ``experts_of`` long and sum to tokens x
                            experts-per-token: the router kept its width
                            and nothing was dropped from the routing
  held_rows_within_bound    the rows to the held experts fit the share's
                            buffer in every layer (past it the layer
                            computes no row, and says so only here)
  held_rows_near_expected   their ratio to the expected rows (tokens x
                            experts-per-token x held / experts_of) lies
                            within ``expect.held_rows_ratio`` in every
                            layer: the routing is a deployment's
  first_loss_near_expected  the first loss lies within
                            ``expect.first_loss_tol_expected`` of
                            ln(vocabulary) + ``expect.first_loss_excess``
                            (half the logits' variance at the stated
                            initialisation), tighter than ``fit``'s band
                            round ln(vocabulary) alone
"""
from __future__ import annotations

import math

import lib

fit_tokens = lib.load_module("traffic", "fit_tokens")
setup = fit_tokens.setup


def run(state, seconds, trace):
    out = fit_tokens.fit.run(state, seconds, trace)
    cfg, cell = state["cfg"], state["cell"]
    p, expect, share = cell["traffic"], cell["expect"], cfg["share"]
    counts = [[int(v) for v in o.asnumpy()]
              for o in state["mod"].get_outputs()[1:]]
    layers = sum(cfg["moe_layer_freq"][:cfg["num_hidden_layers"]])
    held, of = cfg["n_routed_experts"], share["experts_of"]
    lo = share.get("expert_offset", 0)
    rows = p["batch"] * cfg["kwargs"]["seq_len"] * cfg["num_experts_per_tok"]
    expected = rows * held / float(of)
    here = [sum(layer[lo:lo + held]) for layer in counts]
    ratios = [h / expected for h in here]
    low, high = expect["held_rows_ratio"]
    first = out["series"]["losses"][0]
    want_first = math.log(state["classes"]) + expect["first_loss_excess"]
    out["checks"] += [
        ("experts_routed_over_all",
         len(counts) == layers and all(
             len(layer) == of and sum(layer) == rows for layer in counts),
         "%d expert layers of %d, widths %s, rows per layer %s, want %d "
         "over %d" % (len(counts), layers, [len(c) for c in counts],
                      [sum(c) for c in counts], rows, of)),
        ("held_rows_within_bound",
         all(h <= share["share_rows_bound"] for h in here),
         "rows to experts %d..%d per layer %s, bound %d" % (
             lo, lo + held - 1, here, share["share_rows_bound"])),
        ("held_rows_near_expected",
         all(low <= r <= high for r in ratios),
         "ratio to the expected %.0f per layer %s, want %s..%s" % (
             expected, ["%.3f" % r for r in ratios], low, high)),
        ("first_loss_near_expected",
         abs(first - want_first) <= expect["first_loss_tol_expected"],
         "first %.4f, ln(%d) + %s = %.4f, tol %s" % (
             first, state["classes"], expect["first_loss_excess"],
             want_first, expect["first_loss_tol_expected"])),
    ]
    out["expert_counts"] = counts
    out["held_rows_max"], out["held_rows_min"] = max(here), min(here)
    out["held_rows_ratio_max"] = max(ratios)
    out["held_rows_ratio_min"] = min(ratios)
    out["series"]["expert_counts"] = counts
    out["series"]["held_rows"] = here
    out["report"] += ("held_rows_max", "held_rows_min",
                      "held_rows_ratio_max", "held_rows_ratio_min")
    if trace.tracing:
        check = fit_tokens.reference_check(state)
        out["checks"].append(check)
        out["series"]["reference_check"] = check[2]
    return out
