"""Traffic kind ``fit_tokens_share_layers``: ``fit_tokens_share`` for a
configuration that says which layers have experts by
``first_k_dense_replace`` and an integer ``moe_layer_freq`` (the
``deepseek_v3`` keys) where ``fit_tokens_share`` reads one entry a
layer. Set-up is that kind's; ``run`` hands it the same configuration
with the list its reference expands the two keys to (``expert_layers``)
and adds nothing else."""
from __future__ import annotations

import lib

share = lib.load_module("traffic", "fit_tokens_share")
setup = share.setup


def run(state, seconds, trace):
    cfg = state["cfg"]
    layers = lib.load_module("reference", cfg["reference"]).expert_layers(cfg)
    state["cfg"] = dict(cfg, moe_layer_freq=[int(e) for e in layers])
    return share.run(state, seconds, trace)
