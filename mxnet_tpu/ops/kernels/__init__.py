"""Pallas TPU kernels for the hot ops, one module a kernel family.

The reference reaches for hand-written CUDA / cuDNN where the stock ops
are too slow (SURVEY.md §2 N6 cudnn_*-inl.h, N18 mshadow). The TPU-native
equivalent is Pallas: kernels that XLA cannot produce from jnp alone
because they need explicit on-chip (VMEM) accumulation patterns.

  flash        single-key flash attention (forward, dq / dkv, the
               one-pass backward), its selected pair under a keep-mask
               (``flash_select``: the mask's tile one bias under a group's
               heads, the diagonal tile by the column blocks its rows
               see), ``attention`` (the ops' one dispatch)
               and ``reference_attention`` / ``kept_attention`` (the
               oracles)
  latent       latent attention's two-key flash pair and the pass over
               its query
  gmm          the expert layer's grouped matmul, and the sorted
               segment sum that is its wgrad at another shape
  ssd          the chunked state-space scan (Mamba-2)
  sscan        the selective scan (Mamba-1): a decay a channel and a state
               index, the [state, channels-tile] state carried in VMEM
  gdn          the gated delta rule's chunk core, its decay a head's
               (gdn_) or a key channel's (kda_, Kimi Delta Attention)
  taps         the causal taps: the short depthwise convolution over time
               of Mamba2, GatedDeltaNet and ShortConv, with its epilogue
  gate_norm    the gate and the grouped RMSNorm behind the scan (Mamba2)
               and the delta rule (GatedDeltaNet), one pass each way
  rope         the rotary embedding of whole heads of whole lane rows,
               one pass each way that is Attention's transposition too
  hyper        the residual streams' passes (hyper-connections): the
               coefficient products, the mean square and the read in one
               pass over a token block each way, the stream's cotangents
               summed in the backward's; the write of the next stream,
               whole rows, one pass each way
  topk         the choice of a row's k largest scores (KeyIndexer's
               keep-mask): the 32 counting passes, the tie count and the
               mask on a row block held in VMEM, one read of the scores
  conv         the conv-backward pair
  common       what they share

An op calls an entry exported here; the entry asks its family's
``*_takes`` / plan whether it has tiles for the shapes and
``common.on_tpu`` for the branch: the Mosaic kernels where the enclosing
computation is LOWERED for the TPU (never the process default backend),
the plain ``jax.lax`` / ``jax.numpy`` form of the same signature on every
other platform, and the Pallas interpreter only where the caller says
``interpret=True`` (the kernels' tests; ``common.INTERPRET`` is what the
op-level callers pass, for the tests that reach a kernel through a
model). Whatever else a test or a benchmark needs of a family (its
``pallas_call`` wrappers, its VMEM count) it imports from the family's
module.

Layout convention matches ``parallel/ring_attention``: [B, T, H, D].
"""
from . import common
from .conv import (
    conv_bwd_filter, conv_bwd_input, conv_bwd_plan, conv_kernel_enabled)
from .flash import (
    attention, flash_attention, flash_select, flash_select_takes,
    flash_tiles, kept_attention, reference_attention)
from .gate_norm import gate_norm_takes, gated_rms_norm
from .gdn import channel_delta_net, gated_delta_rule, gdn_takes
from .gmm import (
    gmm_metadata, gmm_row_tile, gmm_runs_kernel, gmm_tiles, grouped_matmul,
    held_transposed, sorted_segment_sum)
from .hyper import (
    hyper_takes, stream_mix, stream_products, stream_read, stream_write)
from .latent import (
    latent_flash, latent_flash_takes, latent_query, latent_query_takes)
from .rope import rope_rows, rotate_heads
from .ssd import ssd_scan, ssd_takes
from .sscan import selective_scan, sscan_takes
from .taps import causal_conv, taps_takes
from .topk import top_k_mask, top_k_rows

__all__ = [
    "attention", "causal_conv", "channel_delta_net", "common",
    "conv_bwd_filter", "conv_bwd_input",
    "conv_bwd_plan", "conv_kernel_enabled", "flash_attention",
    "flash_select", "flash_select_takes", "flash_tiles", "gate_norm_takes",
    "gated_delta_rule", "gated_rms_norm", "gdn_takes",
    "gmm_metadata", "gmm_row_tile", "gmm_runs_kernel", "gmm_tiles",
    "grouped_matmul", "held_transposed", "hyper_takes", "kept_attention",
    "latent_flash",
    "latent_flash_takes", "latent_query",
    "latent_query_takes", "reference_attention", "rope_rows", "rotate_heads",
    "selective_scan", "sorted_segment_sum", "sscan_takes",
    "ssd_scan", "ssd_takes", "stream_mix", "stream_products", "stream_read",
    "stream_write",
    "taps_takes", "top_k_mask", "top_k_rows",
]
