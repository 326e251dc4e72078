"""Busy milliseconds of device 0 per step in every op of the parallel
mixers of a model whose blocks run ``Mamba2`` and ``Attention`` side by
side off one norm (``h1_scopes``' ``mixer``): both nodes (scopes
``ssm/layer<i>_ssm`` and ``attn/layer<i>_attn``, the two RoPE nodes with
the second), their four-plus-two ``FullyConnected`` projections, the
multipliers' nodes (``layer<i>_k_proj_scale``, ``layer<i>_attn_in_scale``)
and the scaled sum with its residual add (``layer<i>_mixer_sum``,
``layer<i>_mixer_add``), forward and backward together. The shared norm,
the SwiGLU and the head are not in it."""
import h1_scopes


def compute(trace, counters, run):
    return h1_scopes.ms(trace, run, "mixer")
