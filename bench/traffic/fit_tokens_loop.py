"""Traffic kind ``fit_tokens_loop``: ``fit_tokens`` for a looped language
model (``models/ouro.py``: a stack run ``total_ut_steps`` times over one
set of weights, an exit after every pass, one exit-weighted loss).
Set-up, the ``fit`` call, the window and ``train_samples_s`` are
``fit_tokens``' own (its ``setup`` and ``fit.run``, through
``lib.load_module``); the token ids are uniform over the HELD vocabulary
(``cfg.vocab_size``). No operation of the step depends on the data. The
model's outputs are its loss and ``exit_mass`` (the mean of every exit's
probability over the batch's tokens, ``total_ut_steps`` long), fetched
once after ``fit`` returns (the last step's). The checks:

  loss_and_exit_mass_are_the_outputs
                            the symbol hands back those two and nothing
                            else; ``exit_mass`` is ``total_ut_steps``
                            long, each entry in [0, 1], and sums to 1
                            within 1e-3 (the ends belong: a gate that
                            training has saturated reads 0 or 1 in
                            float32, and that is the model's doing, not
                            a fault of the program)
  first_loss_near_expected  the first loss lies within
                            ``expect.first_loss_tol_expected`` of
                            ln(vocabulary) + ``expect.first_loss_excess``
                            (half the logits' variance at the stated
                            initialisation) - beta H(1/2, 1/4, ...,
                            2^-(T-1), 2^-(T-1)): at a gate drawn
                            Normal(0.02) every lambda is one half to a
                            few parts in a hundred (beta is
                            ``cfg.assumed.exit_beta``)

In the ``--trace 1`` run only, after the window has closed and outside
``setup_s``, ``matches_reference``: the trained parameters are fetched,
the training state is dropped from the device, and one fresh seeded
sequence is run through (a) the symbol bound for inference — its loss,
``exit_mass`` and EVERY exit's float32 logits
(``loop<t>_lm_head_f32``) over the last ``check_last_positions``
positions — and (b) the benchmark's copy of the plain float32 reference
(``reference/<cfg.reference>.py``) fed the same parameters.
``expect.reference`` holds the limits: ``loss_abs_max``,
``exit_mass_abs_max`` (the largest entry's distance) and, by
``fit_tokens.logits_error``'s measure an exit, ``logits_p90_first_max``
(the FIRST exit's 90th percentile: the distance grows pass by pass and
so does its spread over seeds, so the first exit, read after one pass
as a plain stack's head is, is where a limit can stand between the
system and one precision below) and, with the WORST exit deciding,
``logits_p90_max`` and ``logits_max_max``. The same reference
computed one precision below (bf16 throughout) is held to the same
limits on the same parameters and sequence, and its distances are
printed under ``one_precision_below``: the second of the two readings
the limits were set between. It decides nothing.
"""
from __future__ import annotations

import gc
import math

import numpy as np

import lib

fit_tokens = lib.load_module("traffic", "fit_tokens")
setup = fit_tokens.setup


def halving_entropy(passes):
    """H(1/2, 1/4, ..., 2^-(T-1), 2^-(T-1)): the exit distribution of a
    gate that reads one half everywhere."""
    p = [0.5 ** (t + 1) for t in range(passes - 1)] + [0.5 ** (passes - 1)]
    return -sum(x * math.log(x) for x in p)


def run(state, seconds, trace):
    out = fit_tokens.fit.run(state, seconds, trace)
    cfg, expect = state["cfg"], state["cell"]["expect"]
    passes = cfg["total_ut_steps"]
    outputs = state["mod"].get_outputs()
    mass = ([float(v) for v in outputs[1].asnumpy()]
            if len(outputs) > 1 else [])
    first = out["series"]["losses"][0]
    beta = cfg["assumed"]["exit_beta"]
    want_first = (math.log(state["classes"]) + expect["first_loss_excess"]
                  - beta * halving_entropy(passes))
    out["checks"] += [
        ("loss_and_exit_mass_are_the_outputs",
         len(outputs) == 2 and len(mass) == passes
         and all(0.0 <= m <= 1.0 for m in mass)
         and abs(sum(mass) - 1.0) <= 1e-3,
         "%d outputs, exit_mass %s (sum %.6f), want %d entries in [0, 1] "
         "that sum to 1" % (len(outputs), ["%.4f" % m for m in mass],
                            sum(mass), passes)),
        ("first_loss_near_expected",
         abs(first - want_first) <= expect["first_loss_tol_expected"],
         "first %.4f, ln(%d) + %s - %s x %.4f = %.4f, tol %s" % (
             first, state["classes"], expect["first_loss_excess"], beta,
             halving_entropy(passes), want_first,
             expect["first_loss_tol_expected"])),
    ]
    out["exit_mass"] = mass
    out["series"]["exit_mass"] = mass
    out["report"] += ("exit_mass",)
    if trace.tracing:
        check = reference_check(state)
        out["checks"].append(check)
        out["series"]["reference_check"] = check[2]
    return out


def reference_check(state):
    """The check ``matches_reference``: the trained model against the
    plain reference on one fresh sequence, over every exit. Drops the
    training module from ``state``."""
    mx, cfg, sym = state["mx"], state["cfg"], state["sym"]
    limits = state["cell"]["expect"]["reference"]
    last = state["cell"]["traffic"]["check_last_positions"]
    t, passes = cfg["kwargs"]["seq_len"], cfg["total_ut_steps"]
    arg_params, _ = state["mod"].get_params()
    state["mod"] = None  # the training state leaves the device
    gc.collect()

    tokens = np.random.default_rng([state["seed"], 1]).integers(
        0, cfg["vocab_size"], (1, t + 1))
    data = tokens[:, :-1].astype(np.float32)
    label = tokens[:, 1:].astype(np.float32)

    internals = sym.get_internals()
    tails = [mx.sym.slice_axis(
        internals["loop%d_lm_head_f32_output" % (i + 1)], axis=0,
        begin=t - last, end=t) for i in range(passes)]
    bound = mx.mod.Module(
        mx.sym.Group([internals["loss_output"],
                      internals["exit_mass_output"]] + tails),
        context=state["ctx"])
    bound.bind(data_shapes=[("data", data.shape)],
               label_shapes=[("softmax_label", label.shape)],
               for_training=False)
    bound.set_params(arg_params, {})
    bound.forward(mx.io.DataBatch(data=[mx.nd.array(data)],
                                  label=[mx.nd.array(label)]),
                  is_train=False)
    outs = bound.get_outputs()
    loss = float(outs[0].asnumpy().mean())
    mass = outs[1].asnumpy().astype(np.float32)
    logits = [o.asnumpy().astype(np.float32) for o in outs[2:]]
    del bound, outs
    gc.collect()

    ref = lib.load_module("reference", cfg["reference"])
    host = {k: v.asnumpy() for k, v in arg_params.items()}
    want = ref.forward(host, data, cfg, labels=label, last=last)
    want_logits = np.asarray(want["logits"], np.float32)[:, 0]
    want_mass = np.asarray(want["exit_mass"], np.float32)
    clear = np.ones(last, bool)  # no router: every position is compared

    def distance(got_loss, got_mass, got_logits):
        by_exit = [fit_tokens.logits_error(got, ref_logits, clear)
                   for got, ref_logits in zip(got_logits, want_logits)]
        found = {"loss_abs_diff": abs(got_loss - float(want["loss"])),
                 "exit_mass_abs_diff": float(
                     np.abs(got_mass - want_mass).max()),
                 "logits_p90_first": by_exit[0][0],
                 "logits_p90": max(p90 for p90, _ in by_exit),
                 "logits_max": max(worst for _, worst in by_exit),
                 "logits_p90_by_exit": [p90 for p90, _ in by_exit]}
        return found, (
            found["loss_abs_diff"] <= limits["loss_abs_max"]
            and found["exit_mass_abs_diff"] <= limits["exit_mass_abs_max"]
            and found["logits_p90_first"] <= limits["logits_p90_first_max"]
            and found["logits_p90"] <= limits["logits_p90_max"]
            and found["logits_max"] <= limits["logits_max_max"])

    detail, ok = distance(loss, mass, logits)
    below = ref.forward(host, data, cfg, labels=label, last=last,
                        dtype="bfloat16")
    below, below_ok = distance(
        float(below["loss"]), np.asarray(below["exit_mass"], np.float32),
        np.asarray(below["logits"], np.float32)[:, 0])
    below["within_limits"] = bool(below_ok)
    detail.update({
        "loss": loss, "reference_loss": float(want["loss"]),
        "exit_mass": [float(m) for m in mass],
        "reference_exit_mass": [float(m) for m in want_mass],
        "reference_exit_nll": [float(v) for v in np.asarray(
            want["exit_nll"], np.float32)],
        "positions": last, "exits": passes,
        "one_precision_below": below, "limits": limits})
    return "matches_reference", bool(ok), detail
