"""Traffic kind ``fit_tokens_share_select``: ``fit_tokens_share_layers``
for a model whose full-attention layers choose their keys
(``models/dots3.py``: ``KeyIndexer`` keeps ``min(t + 1, index_topk)`` keys
of query t). Its outputs are the loss, one count vector an expert layer
and then ONE selection count a full layer (pairs kept a sequence);
``fit_tokens_share`` reads every output past the loss as expert counts,
so ``run`` hands it the module with the selection counts split off
``get_outputs`` and keeps them. Set-up, the ``fit`` call, the window,
``train_samples_s`` and every check of ``fit_tokens_share`` are that
kind's own. Added:

  keys_selected_exact       every full layer's selection count at the
                            window's last step is exactly ``sum_t min(t +
                            1, index_topk)`` a sequence: each query kept
                            what it must, no more and no fewer
  selection_ties_bounded    (traced run) in the float32 reference on the
                            trained weights, the share of (query, key)
                            pairs of the rows that choose whose index
                            score lies within
                            ``expect.reference.select_near_tie_eps`` of
                            the row's ``index_topk``-th (a call bf16
                            operands may make either way) is at most
                            ``select_near_tie_share_max`` in every full
                            layer, and the reference keeps the same
                            count; the reference computed in bf16
                            throughout is printed beside it

The reference reaches this kind through ``cfg["select_report"]``
(``reference/<cfg.reference>.py::forward`` fills it): ``fit_tokens``'
``reference_check`` keeps only what it compares.
"""
from __future__ import annotations

import lib

layers = lib.load_module("traffic", "fit_tokens_share_layers")
setup = layers.setup


class _WithoutSelection:
    """The module, its last ``n`` outputs (the selection counts) split
    off ``get_outputs`` and kept under ``selected``."""

    def __init__(self, mod, n):
        self.mod, self.n, self.selected = mod, n, None

    def __getattr__(self, name):
        return getattr(self.mod, name)

    def get_outputs(self, *args, **kwargs):
        outs = self.mod.get_outputs(*args, **kwargs)
        self.selected = outs[len(outs) - self.n:]
        return outs[:len(outs) - self.n]


def run(state, seconds, trace):
    cfg, limits = state["cfg"], state["cell"]["expect"]["reference"]
    n = cfg["num_hidden_layers"]
    full = sum(1 for kind in cfg["layer_types"][:n]
               if kind == "full_attention")
    mod = state["mod"] = _WithoutSelection(state["mod"], full)
    report = {"eps": limits["select_near_tie_eps"]}
    state["cfg"] = dict(cfg, select_report=report)
    out = layers.run(state, seconds, trace)
    t, k = cfg["kwargs"]["seq_len"], cfg["index_topk"]
    want = sum(min(i + 1, k) for i in range(t))
    selected = [[int(v) for v in o.asnumpy()] for o in mod.selected or ()]
    out["checks"].append((
        "keys_selected_exact",
        len(selected) == full and all(
            v == want for layer in selected for v in layer),
        "%d full layers of %d, pairs kept a sequence %s, want %d" % (
            len(selected), full, selected, want)))
    out["keys_selected"] = selected
    out["keys_selected_expected"] = want
    out["series"]["keys_selected"] = selected
    if trace.tracing:
        exact = report.get("float32", {})
        shares = exact.get("near_tie_share", [])
        out["checks"].append((
            "selection_ties_bounded",
            len(shares) == full
            and all(s <= limits["select_near_tie_share_max"]
                    for s in shares)
            and all(v == want for layer in exact["keys_selected"]
                    for v in layer),
            dict(report, limit=limits["select_near_tie_share_max"])))
        out["series"]["select_report"] = report
    return out
