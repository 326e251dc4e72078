"""Traffic kind ``fit_tokens_share_mtp``: ``fit_tokens_share_layers`` for a
model with a multi-token-prediction module (``models/xing4.py``: ONE loss,
main + weighted module, behind ``MakeLoss``; after the expert layers'
counts three outputs behind ``BlockGrad``: the main loss, the module's,
and ``hc_res_sum_err``). Set-up, the ``fit`` call, the window,
``train_samples_s`` and the counts' four checks are that kind's own,
through ``lib.load_module``: it is handed the module behind a front that
shows it the loss and the counts alone, and a configuration in which the
module's block counts as one more layer (so that its router and its held
rows are held to the same checks). What this kind adds:

  two_losses_make_the_one   the last step's ``loss`` is main +
                            ``mtp_loss_weight`` x module within 1e-3, both
                            parts finite, the module's at least
                            ``expect.mtp_over_main_min`` of the main one
                            (the token after next is never the easier of
                            the two) and at most ``expect.mtp_loss_max``
                            (it has not risen from where it began)
  carry_is_doubly_stochastic
                            ``hc_res_sum_err`` is at most
                            ``expect.hc_res_sum_err_max``

and, in the ``--trace 1`` run, its own ``matches_reference`` in place of
``fit_tokens``' (same protocol: the trained parameters, the training
state dropped, one fresh seeded sequence through the symbol bound for
inference and through ``reference/<cfg.reference>.py``, near-ties of the
router left out and bounded): BOTH heads' float32 logits over the last
``check_last_positions`` positions and BOTH losses apart.
``expect.reference`` holds ``loss_abs_max`` (each loss),
``logits_p90_max`` and ``logits_max_max`` (each head, the worse
deciding). The same reference computed one precision below (bf16
throughout) is held to the same limits and printed under
``one_precision_below``: it decides nothing.
"""
from __future__ import annotations

import gc
import math

import numpy as np

import lib

layers = lib.load_module("traffic", "fit_tokens_share_layers")
fit_tokens = layers.share.fit_tokens
setup = layers.setup
EXTRAS = 3  # main loss, module's loss, hc_res_sum_err


class _Front:
    """The training module as ``fit_tokens_share`` reads it: everything
    is the module's own but ``get_outputs``, which keeps this kind's
    ``EXTRAS`` last outputs to itself."""

    def __init__(self, mod):
        self._mod = mod
        self.extras = None

    def __getattr__(self, name):
        return getattr(self._mod, name)

    def get_outputs(self):
        outs = self._mod.get_outputs()
        self.extras = [float(o.asnumpy().mean()) for o in outs[-EXTRAS:]]
        return outs[:-EXTRAS]

    def release(self):
        self._mod = None


def run(state, seconds, trace):
    cfg, expect = state["cfg"], state["cell"]["expect"]
    front = state["mod"] = _Front(state["mod"])
    state["cfg"] = dict(cfg, num_hidden_layers=cfg["num_hidden_layers"]
                        + cfg["num_nextn_predict_layers"])
    theirs = fit_tokens.reference_check
    fit_tokens.reference_check = lambda s: reference_check(
        dict(s, cfg=cfg), front)
    try:
        out = layers.run(state, seconds, trace)
    finally:
        fit_tokens.reference_check = theirs
        state["cfg"], state["mod"] = cfg, None
    main, module, err = front.extras
    weight = cfg.get("mtp_loss_weight", 0.3)
    last = out["series"]["losses"][-1]
    low, high = expect["mtp_over_main_min"], expect["mtp_loss_max"]
    out["checks"] += [
        ("two_losses_make_the_one",
         math.isfinite(main) and math.isfinite(module)
         and abs(main + weight * module - last) <= 1e-3
         and module >= low * main and module <= high,
         "main %.4f + %s x module %.4f = %.4f, the step's loss %.4f; "
         "module over main %.4f, want at least %s; module at most %s" % (
             main, weight, module, main + weight * module, last,
             module / main, low, high)),
        ("carry_is_doubly_stochastic", err <= expect["hc_res_sum_err_max"],
         "hc_res_sum_err %.3e, limit %s" % (
             err, expect["hc_res_sum_err_max"])),
    ]
    out["loss_parts"] = [main, module]
    out["hc_res_sum_err"] = err
    out["report"] += ("loss_parts", "hc_res_sum_err")
    return out


def reference_check(state, front):
    """The check ``matches_reference`` over both heads and both losses.
    Drops the training module."""
    mx, cfg, sym = state["mx"], state["cfg"], state["sym"]
    limits = state["cell"]["expect"]["reference"]
    last = state["cell"]["traffic"]["check_last_positions"]
    t = cfg["kwargs"]["seq_len"]
    arg_params, _ = front.get_params()
    front.release()  # the training state leaves the device
    gc.collect()

    tokens = np.random.default_rng([state["seed"], 1]).integers(
        0, cfg["vocab_size"], (1, t + 1))
    data = tokens[:, :-1].astype(np.float32)
    label = tokens[:, 1:].astype(np.float32)

    internals = sym.get_internals()
    heads = ("lm_head_f32_output", "mtp0_lm_head_f32_output")
    tails = [mx.sym.slice_axis(internals[name], axis=0, begin=t - last,
                               end=t) for name in heads]
    bound = mx.mod.Module(
        mx.sym.Group([internals["loss_part_output"],
                      internals["mtp0_loss_part_output"]] + tails),
        context=state["ctx"])
    bound.bind(data_shapes=[("data", data.shape)],
               label_shapes=[("softmax_label", label.shape)],
               for_training=False)
    bound.set_params(arg_params, {})
    bound.forward(mx.io.DataBatch(data=[mx.nd.array(data)],
                                  label=[mx.nd.array(label)]),
                  is_train=False)
    outs = bound.get_outputs()
    losses = [float(o.asnumpy().mean()) for o in outs[:2]]
    logits = [o.asnumpy().astype(np.float32) for o in outs[2:]]
    del bound, outs
    gc.collect()

    ref = lib.load_module("reference", cfg["reference"])
    host = {k: v.asnumpy() for k, v in arg_params.items()}
    want = ref.forward(host, data, cfg, labels=label, last=last)
    keys = ("logits", "mtp_logits")
    want_logits = [np.asarray(want[k], np.float32)[0] for k in keys]
    want_losses = [float(want["loss_main"]), float(want["loss_mtp"])]
    gap = np.asarray(want["router_gap"], np.float32).min(axis=0)
    near_tie = gap < limits["near_tie_eps"]
    clear = ~near_tie[t - last:]

    def distance(got_losses, got_logits):
        by_head = [fit_tokens.logits_error(got, ref_logits, clear)
                   for got, ref_logits in zip(got_logits, want_logits)]
        found = {"loss_abs_diff": [abs(g - w) for g, w in zip(
                     got_losses, want_losses)],
                 "logits_p90": [p90 for p90, _ in by_head],
                 "logits_max": [worst for _, worst in by_head]}
        return found, (
            max(found["loss_abs_diff"]) <= limits["loss_abs_max"]
            and max(found["logits_p90"]) <= limits["logits_p90_max"]
            and max(found["logits_max"]) <= limits["logits_max_max"])

    detail, ok = distance(losses, logits)
    below = ref.forward(host, data, cfg, labels=label, last=last,
                        dtype="bfloat16")
    below, below_ok = distance(
        [float(below["loss_main"]), float(below["loss_mtp"])],
        [np.asarray(below[k], np.float32)[0] for k in keys])
    below["within_limits"] = bool(below_ok)
    detail.update({
        "losses": losses, "reference_losses": want_losses,
        "near_tie_share": float(near_tie.mean()), "positions": last,
        "one_precision_below": below, "limits": limits})
    ok = ok and detail["near_tie_share"] <= limits["near_tie_share_max"]
    return "matches_reference", bool(ok), detail
