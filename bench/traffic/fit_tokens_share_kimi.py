"""Traffic kind ``fit_tokens_share_kimi``: ``fit_tokens_share`` for a
configuration that counts its experts under ``num_experts``, its experts
a token under ``num_experts_per_token`` and says which layers have them
by ``first_k_dense_replace`` and an integer ``moe_layer_freq`` (the
``kimi_linear`` keys) where ``fit_tokens_share`` reads
``n_routed_experts``, ``num_experts_per_tok`` and one ``moe_layer_freq``
entry a layer. Set-up is that kind's; ``run`` hands it the same
configuration with the three names it reads (the list is what the
configuration's reference expands the keys to, ``expert_layers``) and
adds nothing else: no check, no window, no set-up."""
from __future__ import annotations

import lib

share = lib.load_module("traffic", "fit_tokens_share")
setup = share.setup


def run(state, seconds, trace):
    cfg = state["cfg"]
    layers = lib.load_module("reference", cfg["reference"]).expert_layers(cfg)
    state["cfg"] = dict(cfg, n_routed_experts=cfg["num_experts"],
                        num_experts_per_tok=cfg["num_experts_per_token"],
                        moe_layer_freq=[int(e) for e in layers])
    return share.run(state, seconds, trace)
