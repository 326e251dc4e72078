"""Traffic kind ``fit_tokens``: ``fit`` for a language model. One
``mx.mod.Module(...).fit(...)`` call holds warm-up, the traced slice and
the measured window exactly as ``traffic/fit.py`` runs them (this kind
calls its ``run``: same window, median-step ``train_samples_s`` with one
sample = one sequence, ``stall_share``, the loss checks against
ln(vocabulary)); what differs is the batch and what follows the window.

Parameters (the cell's ``traffic`` object), besides those of ``fit``:
  batch                  sequences a step; each is ``kwargs.seq_len``
                         token ids, uniform over the vocabulary, made on
                         the device from the seed; the label of a
                         position is the next token (one more token is
                         drawn than is fed)
  check_last_positions   how many of the last positions' logits the
                         traced run compares with the reference

The configuration's ``factory`` takes the configuration itself (its
published keys are the model) and ``kwargs``. The model's outputs are
its loss and, per expert layer, the rows each expert received; the last
step's counts are fetched once after ``fit`` returns (``expert_counts``)
and must sum to tokens x experts-per-token: dropless.

In the ``--trace 1`` run only, after the window has closed and outside
``setup_s``: the trained parameters are fetched, the training state is
dropped from the device, and one fresh seeded sequence is run through
(a) the symbol bound for inference — its loss, and the float32 logits
of the last ``check_last_positions`` positions — and (b) the
benchmark's copy of the plain float32 reference
(``reference/<cfg.reference>.py``) fed the same parameters, one layer's
weights on the device at a time. ``expect.reference`` holds the limits:
tokens whose routing is a near-tie in the reference (margin between the
last chosen and the first rejected expert's probability under
``near_tie_eps`` in any layer; bf16 activations may flip such a call
either way) are left out of the logits comparison, and their share is
printed and bounded. The same reference computed one precision below
(bf16 throughout, its router too) is held to the same limits on the
same parameters and sequence, and its distances are printed under
``one_precision_below``: the second of the two readings the limits
were set between. It decides nothing.
"""
from __future__ import annotations

import gc

import numpy as np

import lib

fit = lib.load_module("traffic", "fit")


def setup(cfg, cell, seed):
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.mesh import dp_sharding

    p = cell["traffic"]
    if p["feed"] != "resident":
        raise lib.BenchError("fit_tokens: unknown feed %r" % p["feed"])
    chips = cell["chips"]
    shape = (p["batch"], cfg["kwargs"]["seq_len"])
    vocab = cfg["vocab_size"]
    sym = lib.resolve(cfg["factory"])(cfg, **cfg["kwargs"])
    mesh = make_mesh(dp=chips, devices=jax.devices()[:chips])
    # the rehearsal is the only place a host context is ever named
    ctx = mx.cpu if cfg.get("rehearsal") else mx.tpu
    context = [ctx(i) for i in range(chips)]
    mod = mx.mod.Module(sym, context=context if chips > 1 else context[0],
                        mesh=mesh)
    sharding = dp_sharding(mesh)

    def make(key):
        tokens = jax.random.randint(
            key, (shape[0], shape[1] + 1), 0, vocab, jnp.int32)
        return tokens[:, :-1], tokens[:, 1:]

    data, label = jax.jit(make, out_shardings=(sharding, sharding))(
        jax.random.PRNGKey(seed))
    jax.block_until_ready((data, label))
    pair = (mx.nd.NDArray(data), mx.nd.NDArray(label))
    return {"mx": mx, "mod": mod, "sym": sym, "ctx": context[0],
            "next_batch": lambda: pair, "cfg": cfg, "cell": cell,
            "seed": seed, "data_shape": shape, "label_shape": shape,
            "classes": vocab}


def run(state, seconds, trace):
    out = fit.run(state, seconds, trace)
    cfg, p = state["cfg"], state["cell"]["traffic"]
    counts = [[int(v) for v in o.asnumpy()]
              for o in state["mod"].get_outputs()[1:]]
    rows = p["batch"] * cfg["kwargs"]["seq_len"] * cfg["num_experts_per_tok"]
    out["checks"].append((
        "experts_dropless",
        len(counts) == cfg["num_hidden_layers"]
        and all(sum(layer) == rows for layer in counts),
        "%d layers, rows per layer %s, want %d" % (
            len(counts), [sum(layer) for layer in counts], rows)))
    out["expert_counts"] = counts
    out["expert_rows_max"] = max(max(layer) for layer in counts)
    out["expert_rows_min"] = min(min(layer) for layer in counts)
    out["series"]["expert_counts"] = counts
    out["report"] += ("expert_rows_max", "expert_rows_min")
    if trace.tracing:
        check = reference_check(state)
        out["checks"].append(check)
        out["series"]["reference_check"] = check[2]
    return out


def logits_error(got, want, clear):
    """Per-token largest |logit difference| in standard deviations of the
    reference's logits: (90th percentile, largest) over ``clear``."""
    per_token = (np.abs(got - want).max(axis=1) / want.std())[clear]
    if not per_token.size:
        return float("inf"), float("inf")  # nothing left to compare
    return float(np.percentile(per_token, 90)), float(per_token.max())


def reference_check(state):
    """The check ``matches_reference``: the trained model against the
    plain reference on one fresh sequence. Drops the training module
    from ``state``."""
    mx, cfg, sym = state["mx"], state["cfg"], state["sym"]
    limits = state["cell"]["expect"]["reference"]
    last = state["cell"]["traffic"]["check_last_positions"]
    t = cfg["kwargs"]["seq_len"]
    arg_params, _ = state["mod"].get_params()
    state["mod"] = None  # the training state leaves the device
    gc.collect()

    tokens = np.random.default_rng([state["seed"], 1]).integers(
        0, cfg["vocab_size"], (1, t + 1))
    data = tokens[:, :-1].astype(np.float32)
    label = tokens[:, 1:].astype(np.float32)

    internals = sym.get_internals()
    tail = mx.sym.slice_axis(internals["lm_head_f32_output"], axis=0,
                             begin=t - last, end=t)
    bound = mx.mod.Module(mx.sym.Group([internals["loss_output"], tail]),
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
    logits = outs[1].asnumpy().astype(np.float32)
    del bound, outs
    gc.collect()

    ref = lib.load_module("reference", cfg["reference"])
    host = {k: v.asnumpy() for k, v in arg_params.items()}
    want = ref.forward(host, data, cfg, labels=label, last=last)
    want_logits = np.asarray(want["logits"], np.float32)[0]
    gap = np.asarray(want["router_gap"], np.float32).min(axis=0)
    near_tie = gap < limits["near_tie_eps"]
    clear = ~near_tie[t - last:]

    def distance(got_loss, got_logits):
        p90, worst = logits_error(got_logits, want_logits, clear)
        found = {"loss_abs_diff": abs(got_loss - float(want["loss"])),
                 "logits_p90": p90, "logits_max": worst}
        return found, (found["loss_abs_diff"] <= limits["loss_abs_max"]
                       and p90 <= limits["logits_p90_max"]
                       and worst <= limits["logits_max_max"])

    detail, ok = distance(loss, logits)
    below = ref.forward(host, data, cfg, labels=label, last=last,
                        dtype="bfloat16")
    below, below_ok = distance(
        float(below["loss"]), np.asarray(below["logits"], np.float32)[0])
    below["within_limits"] = bool(below_ok)
    detail.update({
        "loss": loss, "reference_loss": float(want["loss"]),
        "near_tie_share": float(near_tie.mean()), "positions": last,
        "one_precision_below": below, "limits": limits})
    ok = ok and detail["near_tie_share"] <= limits["near_tie_share_max"]
    return "matches_reference", bool(ok), detail
