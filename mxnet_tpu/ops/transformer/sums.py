"""Two small ops that weigh and sum what other nodes made: ``ScaledSum``
(sub-layer outputs under fixed scalars, Falcon-H1's multipliers) and
``ExitMix`` (a looped language model's exit distribution over its passes
and the loss it weights: Zhu et al., "Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741, stage I). Plain ``jax.numpy``."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ... import telemetry as _tm
from ...base import MXNetError
from ..registry import OpDef, register
from ..utils import same_shape_infer


_M_PARALLEL_BLOCKS = _tm.counter(
    "lm.parallel_blocks", "Traces of a ScaledSum node that sums the "
    "mixers of one parallel block (one per node and lowering, nothing per "
    "step); labels: mixers (their kinds, '+'-joined), count")


def scaled_sum(xs, scales):
    """``sum_i scales[i] * xs[i]`` of arrays of one shape and dtype under
    fixed Python scalars: each product and the sum float32, one rounding
    to the inputs' dtype (a scalar rounded to bf16 first would be off by
    up to 0.4%, and 0.0375 is by 0.26%)."""
    acc = None
    for x, scale in zip(xs, scales):
        term = x.astype(jnp.float32) * float(scale)
        acc = term if acc is None else acc + term
    return acc.astype(xs[0].dtype)


def _scaled_sum(attrs, ins, is_train):
    scales = tuple(attrs["scales"])
    if len(scales) != len(ins):
        raise ValueError("ScaledSum: %d inputs under scales=%r"
                         % (len(ins), scales))
    kinds = attrs.get("kinds")
    if kinds:
        _M_PARALLEL_BLOCKS.inc(mixers=str(kinds), count=len(ins))
    return [scaled_sum(ins, scales)]


register(
    OpDef(
        "_contrib_ScaledSum",
        _scaled_sum,
        arguments=("args",),
        key_var_num_args="num_args",
        defaults={"scales": (1.0,), "kinds": None},
        infer_shape=lambda attrs, in_shapes: same_shape_infer(
            len(in_shapes))(attrs, in_shapes),
        aliases=("ScaledSum",),
        op_class="act",
    )
)


_M_LOOP_VISITS = _tm.counter(
    "lm.loop_layer_visits", "Layer visits a step of a looped stack (passes "
    "x layers over ONE set of weights), counted where its ExitMix node is "
    "traced (one per node and lowering, nothing per step); labels: passes")


def exit_mix(gates, nll, beta):
    """The exit distribution of ``gates`` [N, T] (a token's gate
    pre-activation after each of T passes) and the loss it gives ``nll``
    [N, T] (the token's cross-entropy at each exit): ``lambda_t =
    sigmoid(gates_t)``; ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` for
    ``t < T`` and ``p_T = prod_{j<T} (1 - lambda_j)``, so the last column
    of ``gates`` is read by nothing; a token's loss ``sum_t p_t nll_t -
    beta H(p)``, ``H(p) = -sum_t p_t log p_t``. Returns (loss [N], p [N,
    T]), float32 both. ``log p`` is a sum of ``log_sigmoid``s (of the
    gate where the token exits, of its negative where it stays), so a
    saturated gate gives ``p log p`` = 0 and no ``log 0``."""
    gates, nll = gates.astype(jnp.float32), nll.astype(jnp.float32)
    stayed = jnp.zeros_like(gates[:, 0])  # log prod_{j<t} (1 - lambda_j)
    log_p = []
    for t in range(gates.shape[1] - 1):
        log_p.append(stayed + jax.nn.log_sigmoid(gates[:, t]))
        stayed = stayed + jax.nn.log_sigmoid(-gates[:, t])
    log_p = jnp.stack(log_p + [stayed], axis=1)
    p = jnp.exp(log_p)
    return jnp.sum(p * (nll + beta * log_p), axis=1), p


def _exit_mix(attrs, ins, is_train):
    gates, nll = ins
    visits = int(attrs.get("visits", 0))
    if visits:
        _M_LOOP_VISITS.inc(visits, passes=gates.shape[1])
    return list(exit_mix(gates, nll, float(attrs.get("beta", 0.0))))


def _exit_mix_infer(attrs, in_shapes):
    known = [tuple(s) for s in in_shapes if s is not None]
    if not known:
        raise MXNetError("ExitMix: data shape required")
    if len(known[0]) != 2 or any(s != known[0] for s in known):
        raise ValueError("ExitMix: gates and nll must share one [tokens, "
                         "passes] shape, got %s" % (in_shapes,))
    return [known[0]] * 2, [(known[0][0],), known[0]], []


register(
    OpDef(
        "_contrib_ExitMix",
        _exit_mix,
        arguments=("gates", "nll"),
        outputs=("loss", "prob"),
        defaults={"beta": 0.0, "visits": 0},
        infer_shape=_exit_mix_infer,
        infer_type=lambda attrs, in_types: (
            [np.float32] * 2, [np.float32] * 2, []),
        aliases=("ExitMix",),
        op_class="loss",
    )
)
