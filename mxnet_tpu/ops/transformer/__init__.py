"""Transformer-block operators for the Symbol API, a module an op family:
what a decoder-only language model needs to be an ``mx.sym`` graph that
``Module.fit`` trains through the fused step. A family's module holds its
plain functions, its ``OpDef``s (``_contrib_<Name>`` with the alias ``<Name>``:
that exports them as ``mx.contrib.sym`` / ``mx.contrib.nd`` functions), their
inference and counters, and names its kernel family:

  norm       ``RMSNorm``, ``LayerNorm``; ``rms_norm``, ``layer_norm``
  rotary     ``RoPE``; ``rope`` with its tables and its one-pass form
  taps       ``ShortConv``; the causal taps, what ``ssm`` and ``delta`` share
  attention  ``Attention``, ``DiffAttention``; ``gate_output``
  blocks     ``BlockSelect``: the key blocks a query reads, chosen from
             pooled keys with the attention's own queries and keys
  latent     ``LatentAttention`` (both forms, the query pass), ``KeyIndexer``
  ssm        ``Mamba2``, ``Mamba1``, ``LinearAttention`` (a fixed decay a
             head, on the same scan); ``ssd_scan``, ``selective_scan``
  delta      ``GatedDeltaNet``; the scalar and the channel delta rule
  moe        ``TopKMoE``
  hyper      ``HyperCoeff``, ``HyperMix``; the Sinkhorn mixings
  sums       ``ScaledSum``, ``ExitMix``

Arrows point one way: the first four import no sibling and are the only
siblings a family imports; nothing here imports ``executor``, ``module`` or
``models``. A Mosaic call's cache key holds the source lines of its call
path (``ROADMAP.md`` Design 16): an edit in one family's module recompiles
the cells that run that family, no others. Activations are ``[batch, time,
heads * head_dim]`` between ops, ``[tokens, width]`` for experts and streams.
"""
from . import moe  # noqa: F401  (registers TopKMoE)
from .attention import diff_attention, gate_output
from .blocks import block_select
from .delta import (
    KDA_SUB_BLOCK, channel_delta_rule, gated_delta_net, gated_delta_rule)
from .hyper import hyper_coeff, hyper_coeff_read, hyper_mix, sinkhorn
from .latent import keep_top_k, key_indexer, latent_attention
from .norm import rms_norm
from .rotary import rope
from .ssm import (
    lightning_slopes, linear_attention, mamba1, mamba2, selective_scan,
    ssd_scan)
from .sums import exit_mix, scaled_sum
from .taps import causal_taps, gated_taps, short_conv

__all__ = [
    "KDA_SUB_BLOCK", "block_select", "causal_taps", "channel_delta_rule",
    "exit_mix",
    "gate_output", "gated_delta_net", "gated_delta_rule", "gated_taps",
    "hyper_coeff", "hyper_coeff_read", "hyper_mix", "keep_top_k",
    "diff_attention", "key_indexer", "latent_attention", "lightning_slopes",
    "linear_attention", "mamba1", "mamba2",
    "rms_norm", "rope", "scaled_sum", "selective_scan", "short_conv",
    "sinkhorn", "ssd_scan",
]
