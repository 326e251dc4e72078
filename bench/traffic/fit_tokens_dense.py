"""Traffic kind ``fit_tokens_dense``: ``fit_tokens`` for a language model
without experts. Set-up, the ``fit`` call, the window, ``train_samples_s``
and the traced run's ``matches_reference`` are ``fit_tokens``' own (its
``setup`` and ``reference_check``, through ``lib.load_module``; the
configuration's reference returns a ``router_gap`` that marks no token a
near-tie, so every checked position is compared); the token ids are
uniform over the HELD vocabulary (``cfg.vocab_size``). No operation of
the step depends on the data. What differs is what is asked of the
model's outputs: there are no count outputs to read, and the checks are

  loss_is_the_only_output   the symbol hands back its loss and nothing
                            else (a count output would say an expert
                            layer had been built)
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
    expect = state["cell"]["expect"]
    outputs = len(state["mod"].get_outputs())
    first = out["series"]["losses"][0]
    want_first = math.log(state["classes"]) + expect["first_loss_excess"]
    out["checks"] += [
        ("loss_is_the_only_output", outputs == 1, "%d outputs" % outputs),
        ("first_loss_near_expected",
         abs(first - want_first) <= expect["first_loss_tol_expected"],
         "first %.4f, ln(%d) + %s = %.4f, tol %s" % (
             first, state["classes"], expect["first_loss_excess"],
             want_first, expect["first_loss_tol_expected"])),
    ]
    if trace.tracing:
        check = fit_tokens.reference_check(state)
        out["checks"].append(check)
        out["series"]["reference_check"] = check[2]
    return out
