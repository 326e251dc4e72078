"""``HyperCoeff`` / ``HyperMix``: manifold-constrained hyper-connections
(Xie et al., mHC, arXiv:2512.24880, on Zhu et al., Hyper-Connections,
arXiv:2409.19606): n residual streams a token, read through a learned
row, written through a learned column and carried through a learned,
doubly stochastic matrix, all three a function of the token. The passes
over the stream are the kernel family ``ops/kernels/hyper.py`` where
``_takes_one_stream_pass`` says so, ``jax.numpy`` like the mixings elsewhere.

Layout, which decides the cost on the chip: the stream is [tokens, n C],
stream j the lane-aligned columns j C .. (j + 1) C - 1, so that no array
has the n streams as a minor dimension (a bf16 [.., 4, C] pads 4 to a
16-row tile); every coefficient array has the TOKENS on its last axis
([n, tokens], [n, n, tokens]: a [tokens, 4] pads 4 lanes to 128)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ... import telemetry as _tm
from ..registry import OpDef, register
from ..utils import first_type, required_shape


_M_HC_SUBLAYERS = _tm.counter(
    "lm.hc_sublayers", "Traces of a HyperCoeff node: one sub-layer wrapped "
    "in hyper-connections (one per node and lowering, nothing per step); "
    "labels: streams, iters (the Sinkhorn iterations)")


def sinkhorn(m, iters, eps):
    """``m`` [n, n, tokens] positive -> doubly stochastic a token: ``iters``
    times the columns (axis 0 summed) then the rows (axis 1 summed) divided
    by their sum + ``eps``; the rows are exact at exit. The sums are
    written out stream by stream, so an iteration is elementwise over the
    tokens and nothing is reduced; the iterations are ONE loop body (a
    program of six blocks would else carry 12 x 20 copies of it, three
    times over with the backward)."""
    def total(parts):
        return functools.reduce(jnp.add, parts)

    n = m.shape[0]

    def iteration(_, m):
        m = m / (total([m[i] for i in range(n)])[None] + eps)
        return m / (total([m[:, j] for j in range(n)])[:, None] + eps)

    return jax.lax.fori_loop(0, iters, iteration, m)


def _coefficients(raw, mean_sq, bias, alpha, n, iters, eps, clamp, norm_eps):
    """``hyper_coeff``'s three mixings and ``err`` from the products ``raw``
    [n (n + 2), tokens] and the mean square [tokens]: float32, elementwise
    over the tokens, the iterations recomputed in the backward
    (``jax.checkpoint``: the residuals are the products and the mean
    square, not 2 x iters small arrays)."""
    f32 = jnp.float32

    @jax.checkpoint
    def coefficients(raw, mean_sq, bias, alpha):
        with jax.named_scope("hc_coeff"):
            bias, alpha = bias.astype(f32), alpha.astype(f32)
            z = raw * jax.lax.rsqrt(mean_sq + norm_eps)[None]
            z = (z * jnp.repeat(alpha, np.array([n, n, n * n]),
                                total_repeat_length=n * (n + 2))[:, None]
                 + bias[:, None])
            pre = jax.nn.sigmoid(z[:n])[None]
            post = 2.0 * jax.nn.sigmoid(z[n:2 * n])
            m = jnp.exp(jnp.clip(z[2 * n:], clamp[0], clamp[1])).reshape(
                n, n, -1)
        with jax.named_scope("hc_sinkhorn"):
            res = sinkhorn(m, iters, eps)
            err = jnp.maximum(
                jnp.max(jnp.abs(jnp.sum(res, axis=1) - 1.0)),
                jnp.max(jnp.abs(jnp.sum(res, axis=0) - 1.0)))
        return pre, post, res, err.reshape(1)

    return coefficients(raw, mean_sq, bias, alpha)


def hyper_coeff(x, phi, bias, alpha, streams, iters, eps, clamp,
                norm_eps=1e-6):
    """The three mixings of one sub-layer from its stream ``x`` [tokens, n
    C]: ``xbar = x / sqrt(mean(x^2) + norm_eps)`` over all n C lanes (no
    learned scale), and with ``phi`` [n (n + 2), n C] (rows: n of the read,
    n of the write, n n of the carry, row-major), ``bias`` [n (n + 2)] and
    ``alpha`` [3],

        pre  = sigmoid(alpha_0 (phi_pre xbar) + b_pre)            [1, n, tokens]
        post = 2 sigmoid(alpha_1 (phi_post xbar) + b_post)        [n, tokens]
        res  = sinkhorn(exp(clip(alpha_2 (phi_res xbar) + b_res)))  [n, n, tokens]

    and ``err`` [1], the largest ``|rowsum - 1|``, ``|colsum - 1|`` of any
    token's ``res``: what the iterations left. ONE pass over the stream
    gives the n (n + 2) products and the mean square (``phi xbar = (phi x)
    / rms``); everything is float32; the iterations are recomputed in the
    backward (``_coefficients``)."""
    from .. import kernels

    with jax.named_scope("hc_coeff"):
        raw, mean_sq = kernels.stream_products(x, phi)    # [n(n+2), N], [N]
    return _coefficients(raw, mean_sq, bias, alpha, streams, iters, eps,
                         clamp, norm_eps)


_M_HC_LOWERINGS = _tm.counter(
    "lm.hc_lowerings", "Traces of a HyperCoeff node, or of a HyperMix node "
    "that writes all n streams back, by the form its pass over the stream "
    "takes (one per node and lowering, nothing per step); labels: node "
    "(coeff: HyperCoeff; write: HyperMix with an addend and a square mix), "
    "form (one_pass: ops/kernels/hyper.py, one kernel pass over a token "
    "block each way: the products, the mean square and the read with the "
    "stream's cotangents summed in the backward's, or the write; plain: "
    "the jax.numpy forms)")


def _takes_one_stream_pass(node, x, streams):
    """Whether a node's pass over the stream ``x`` is a kernel pair's:
    ``kernels.hyper_takes`` has a token block for the stream, and the
    program is not one the partitioner splits (the kernels have no
    partitioning rule; ``rope``'s rule); counts the node."""
    from .. import kernels

    one_pass = bool(
        x.ndim == 2 and x.shape[1] % streams == 0
        and kernels.hyper_takes(x.shape[0], streams, x.shape[1] // streams,
                                x.dtype) is not None
        and not kernels.common.trace_is_partitioned())
    _M_HC_LOWERINGS.inc(node=node, form="one_pass" if one_pass else "plain")
    return one_pass


def hyper_coeff_read(x, phi, bias, alpha, streams, iters, eps, clamp,
                     norm_eps=1e-6):
    """``hyper_coeff`` on the kernel pair: its four results, the read
    ``hyper_mix(x, pre)`` [tokens, C] and the stream (the same array: the
    write reads it off this node, so that its cotangent arrives IN the
    backward's one pass), for the shapes ``kernels.hyper_takes`` admits.
    The products, the mean square and the read are one pass over a token
    block; the mixings stay ``_coefficients``. Results within the float32
    rounding of the ``jax.numpy`` forms' sums."""
    from .. import kernels

    with jax.named_scope("hc_coeff"):
        raw, mean_sq, read, stream = kernels.stream_read(
            x, phi, bias, alpha, streams, norm_eps,
            interpret=kernels.common.INTERPRET)
    return _coefficients(raw, mean_sq, bias, alpha, streams, iters, eps,
                         clamp, norm_eps) + (read, stream)


def hyper_mix(x, mix, add=None, add_mix=None):
    """``out[t, i] = sum_j mix[i, j, t] x[t, j] (+ add_mix[i, t] add[t])``:
    x [tokens, n C], mix [m, n, tokens] float32, add [tokens, C], add_mix
    [m, tokens] -> [tokens, m C] in ``x``'s dtype. m = 1 reads a sub-layer's
    input off the streams, m = n writes its output back beside the carried
    streams. Products and sums float32, one rounding."""
    from .. import kernels

    with jax.named_scope("hc_mix"):
        return kernels.stream_mix(x, mix, add, add_mix)


def _hyper_coeff(attrs, ins, is_train):
    """The mixings, the read ``HyperMix(data, pre)`` and the stream for the
    write to read; one pass over the stream where
    ``_takes_one_stream_pass`` says so, the ``jax.numpy`` forms
    elsewhere."""
    n, iters = int(attrs["streams"]), int(attrs.get("iters", 20))
    _M_HC_SUBLAYERS.inc(streams=n, iters=iters)
    options = dict(
        streams=n, iters=iters, eps=float(attrs.get("eps", 1e-6)),
        clamp=tuple(float(v) for v in attrs.get("clamp", (-30.0, 30.0))),
        norm_eps=float(attrs.get("norm_eps", 1e-6)))
    if _takes_one_stream_pass("coeff", ins[0], n):
        return list(hyper_coeff_read(*ins, **options))
    outs = hyper_coeff(*ins, **options)
    return list(outs) + [hyper_mix(ins[0], outs[0]), ins[0]]


def _hyper_coeff_infer(attrs, in_shapes):
    n = int(attrs["streams"])
    data = required_shape(in_shapes[0], "HyperCoeff")
    if len(data) != 2 or data[1] % n:
        raise ValueError("HyperCoeff: data %s must be [tokens, streams=%d x "
                         "hidden]" % (data, n))
    rows = n * (n + 2)
    return ([data, (rows, data[1]), (rows,), (3,)],
            [(1, n, data[0]), (n, data[0]), (n, n, data[0]), (1,),
             (data[0], data[1] // n), data],
            [])


def _hyper_coeff_infer_type(attrs, in_types):
    """The coefficients are float32 whatever the stream is; ``phi``, the
    read and the stream handed on are the stream's type (a matrix product's
    operand), bias and alpha float32."""
    t = first_type("HyperCoeff", in_types[:2])
    return ([t, t] + [np.float32 if x is None else x for x in in_types[2:]],
            [np.float32] * 4 + [t] * 2, [])


register(
    OpDef(
        "_contrib_HyperCoeff",
        _hyper_coeff,
        arguments=("data", "phi", "bias", "alpha"),
        outputs=("pre", "post", "res", "err", "read", "stream"),
        defaults={"streams": 4, "iters": 20, "eps": 1e-6,
                  "clamp": (-30.0, 30.0), "norm_eps": 1e-6},
        infer_shape=_hyper_coeff_infer,
        infer_type=_hyper_coeff_infer_type,
        aliases=("HyperCoeff",),
        op_class="hc",
    )
)


def _hyper_mix(attrs, ins, is_train):
    """The write of all n streams is one kernel pass each way where
    ``_takes_one_stream_pass`` says so; every other mixing ``hyper_mix``."""
    from .. import kernels

    if (len(ins) == 4 and ins[1].shape[0] == ins[1].shape[1]
            and _takes_one_stream_pass("write", ins[0], ins[1].shape[1])):
        x, res, y, post = ins
        with jax.named_scope("hc_mix"):
            return [kernels.stream_write(
                x, res, y, post, interpret=kernels.common.INTERPRET)]
    return [hyper_mix(*ins)]


def _hyper_mix_infer(attrs, in_shapes):
    data = required_shape(in_shapes[0], "HyperMix")
    mix = required_shape(in_shapes[1], "HyperMix")
    if (len(data) != 2 or len(mix) != 3 or mix[2] != data[0]
            or data[1] % mix[1]):
        raise ValueError("HyperMix: data %s [tokens, n x hidden] under mix "
                         "%s [m, n, tokens]" % (data, mix))
    c = data[1] // mix[1]
    added = [(data[0], c), (mix[0], data[0])] if len(in_shapes) > 2 else []
    return [data, mix] + added, [(data[0], mix[0] * c)], []


def _hyper_mix_infer_type(attrs, in_types):
    t = first_type("HyperMix", (in_types[0],) + tuple(in_types[2:3]))
    return ([t, np.float32] + ([t, np.float32] if len(in_types) > 2
                               else []), [t], [])


_hyper_mix_op = OpDef(
    "_contrib_HyperMix",
    _hyper_mix,
    arguments=("data", "mix", "add", "add_mix"),
    defaults={"with_add": False},
    infer_shape=_hyper_mix_infer,
    infer_type=_hyper_mix_infer_type,
    aliases=("HyperMix",),
    op_class="hc",
)
_hyper_mix_op.list_arguments = lambda attrs=None: (
    ["data", "mix"] + (["add", "add_mix"]
                       if attrs and attrs.get("with_add") else []))
register(_hyper_mix_op)
