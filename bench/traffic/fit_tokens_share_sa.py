"""Traffic kind ``fit_tokens_share_sa``: ``fit_tokens_share_select`` for a
configuration in which EVERY layer has experts and an indexer and that
spells its sizes with the ``qwen3_moe`` keys and a ``sa_config`` group
(``models/keye_vl2.py``): it counts its experts under ``num_experts``,
names no layer kinds and keeps the selection's size under
``sa_config.topk``, where ``fit_tokens_share_select`` reads
``n_routed_experts``, one ``layer_types`` entry a layer and
``index_topk`` (``fit_tokens_share_layers`` under it takes the expert
layers from the configuration's reference, ``expert_layers``: all of
them here). Set-up is that kind's; ``run`` hands it the same
configuration with the three names it reads and adds nothing else: no
check, no window, no set-up."""
from __future__ import annotations

import lib

select = lib.load_module("traffic", "fit_tokens_share_select")
setup = select.setup


def run(state, seconds, trace):
    cfg = state["cfg"]
    state["cfg"] = dict(
        cfg, n_routed_experts=cfg["num_experts"],
        index_topk=cfg["sa_config"]["topk"],
        layer_types=["full_attention"] * cfg["num_hidden_layers"])
    return select.run(state, seconds, trace)
