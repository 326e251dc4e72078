"""Transformer-block operators for the Symbol API: RMSNorm, RoPE,
Attention, LatentAttention, KeyIndexer, Mamba2, TopKMoE, GatedDeltaNet,
ShortConv and ScaledSum.

Beyond-reference capability (the 2017 operator set has no attention and
no sparse-expert layer): what a decoder-only LM with sparse experts
(``models/olmoe.py``) needs to be an ``mx.sym`` graph that
``Module.fit`` trains through the fused step. Each op is a thin
``OpDef`` over one function kept elsewhere: ``Attention`` over the one
attention dispatch ``ops/kernels.attention`` (flash kernel on the
TPU at T >= 128, the materialised reference elsewhere;
``LatentAttention`` projects its keys and values up from a latent first,
for its own flash pair or the same dispatch, over every causal key, a
window of them or the keys ``KeyIndexer``'s mask keeps), ``TopKMoE`` over
``parallel/moe.topk_moe``; ``Mamba2`` (a state-space mixer's core: the
convolution, the chunked scan and the gated norm) is ``jax.numpy`` here,
with no kernel behind it. Exported as ``mx.contrib.sym`` /
``mx.contrib.nd`` functions through ``contrib.ops.CONTRIB_OP_EXPORTS``.

Layout: activations are ``[batch, time, heads * head_dim]`` between ops
(what ``FullyConnected(flatten=False)`` produces); the expert layer
takes ``[tokens, d_model]``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry as _tm
from ..base import MXNetError
from .registry import OpDef, register
from .utils import same_shape_infer


def _known(shape, what):
    if shape is None:
        raise MXNetError("%s: data shape required" % what)  # resolvable later
    return tuple(shape)


# --------------------------------------------------------------------------
# RMSNorm
# --------------------------------------------------------------------------
def rms_norm(x, gamma, eps):
    """``gamma * x / sqrt(mean(x^2) + eps)`` over the last axis: the
    statistics in float32, the normalised value cast back to ``x``'s
    dtype before the scale (the order of the published OLMoE code)."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    normed = (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype)
    return gamma.astype(x.dtype) * normed


def _rms_norm(attrs, ins, is_train):
    data, gamma = ins
    return [rms_norm(data, gamma, float(attrs.get("eps", 1e-5)))]


def _rms_norm_infer(attrs, in_shapes):
    data = _known(in_shapes[0], "RMSNorm")
    return [data, (data[-1],)], [data], []


register(
    OpDef(
        "_contrib_RMSNorm",
        _rms_norm,
        arguments=("data", "gamma"),
        defaults={"eps": 1e-5},
        infer_shape=_rms_norm_infer,
        aliases=("RMSNorm",),
    )
)


# --------------------------------------------------------------------------
# RoPE — rotary position embedding, half-rotation or interleaved pairs
# --------------------------------------------------------------------------
def rope(x, num_heads, theta, rotary_dim=0, offset=0, interleave=False):
    """Rotate ``x`` [B, T, H*D] by its positions 0..T-1, in place: the
    R = ``rotary_dim`` dimensions of a head from ``offset`` on (0: the
    whole head); the dimensions before and past them pass through.
    The pairs are (i, i + R/2) — the ``rotate_half`` convention — or,
    with ``interleave``, (2i, 2i + 1); pair i turns by ``pos *
    theta^(-2i/R)`` either way. Angles, sines and the rotation itself
    are float32; the result is ``x``'s dtype. Whole heads of whole lane
    rows under ``rotate_half`` take ONE pass each way and no half is an
    array (``_takes_one_pass``, at this file's end): results are EQUAL."""
    b, t, hd = x.shape
    d = hd // num_heads
    r = rotary_dim or d - offset
    if _takes_one_pass(x, num_heads, r, offset, interleave):
        return _rotate_whole_heads(x, num_heads, theta)
    cos, sin = (jnp.asarray(table, jnp.float32)[None, :, None, :]
                for table in _rope_tables(t, r, theta, interleave))
    x4 = x.astype(jnp.float32).reshape(b, t, num_heads, d)
    rot = x4[..., offset: offset + r]
    if interleave:
        # the pair's other lane by two lane rotations and a select on
        # the lane's parity (no [.., R/2, 2] reshape: a minor dimension
        # of 2 is a padded layout on the chip): -x[2i+1] at 2i, x[2i]
        # at 2i+1
        even = (np.arange(r) % 2 == 0)[None, None, None, :]
        other = jnp.where(even, -jnp.roll(rot, -1, axis=-1),
                          jnp.roll(rot, 1, axis=-1))
        rotated = [rot * cos + other * sin]
    else:
        x1, x2 = rot[..., : r // 2], rot[..., r // 2:]
        rotated = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
    before = [x4[..., :offset]] if offset else []
    out = jnp.concatenate(before + rotated + [x4[..., offset + r:]],
                          axis=-1)
    return out.reshape(b, t, hd).astype(x.dtype)


def _rope(attrs, ins, is_train):
    return [rope(ins[0], int(attrs["num_heads"]),
                 float(attrs.get("theta", 10000.0)),
                 int(attrs.get("rotary_dim", 0)),
                 int(attrs.get("rotary_offset", 0)),
                 bool(attrs.get("interleave", False)))]


def _split_heads(what, name, shape, heads):
    """``shape`` [batch, time, heads * head_dim] -> head_dim. A
    ValueError: a known-but-wrong shape must survive the infer fixpoint
    loop (see SwitchMoE)."""
    if len(shape) != 3 or heads <= 0 or shape[2] % heads:
        raise ValueError(
            "%s: %s must be [batch, time, %d heads * head_dim], got %s"
            % (what, name, heads, shape))
    return shape[2] // heads


def _check_rotation(what, d, r, offset):
    if r % 2 or r <= 0 or offset < 0 or offset + r > d:
        raise ValueError(
            "%s: the rotated dimensions must be an even count inside the "
            "head_dim %d, got %d from %d on" % (what, d, r, offset))


def _rope_infer(attrs, in_shapes):
    data = _known(in_shapes[0], "RoPE")
    d = _split_heads("RoPE", "data", data, int(attrs["num_heads"]))
    offset = int(attrs.get("rotary_offset", 0))
    _check_rotation("RoPE", d, int(attrs.get("rotary_dim", 0)) or d - offset,
                    offset)
    return [data], [data], []


register(
    OpDef(
        "_contrib_RoPE",
        _rope,
        arguments=("data",),
        defaults={"num_heads": 1, "theta": 10000.0, "rotary_dim": 0,
                  "rotary_offset": 0, "interleave": False},
        infer_shape=_rope_infer,
        aliases=("RoPE",),
    )
)


# --------------------------------------------------------------------------
# Attention — multi-head scaled-dot-product attention
# --------------------------------------------------------------------------
def _kv_heads(attrs):
    return int(attrs.get("num_kv_heads", 0)) or int(attrs["num_heads"])


_M_GATED_LOWERINGS = _tm.counter(
    "attention.gated_lowerings", "Traces of an Attention call site whose "
    "output is gated (with_gate: one per lowering, nothing per step); "
    "labels: heads, dv (the value width a head)")


def gate_output(out, gate):
    """``out * sigmoid(gate)``, an element each (a gate per head and
    channel) or broadcast (``LatentAttention``'s one gate a head over the
    head's columns): the sigmoid and the product float32, one rounding
    to ``out``'s dtype."""
    return (out.astype(jnp.float32)
            * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(out.dtype)


def _optional_inputs(attrs, names=("sink", "gate")):
    return [name for name in names
            if bool((attrs or {}).get("with_" + name, False))]


def _attention(attrs, ins, is_train):
    from .kernels import attention

    q, k, v = ins[:3]
    optional = dict(zip(_optional_inputs(attrs), ins[3:]))
    heads, kv_heads = int(attrs["num_heads"]), _kv_heads(attrs)
    window = int(attrs.get("window", 0))
    b, t, _ = q.shape

    def split(x, n):
        return x.reshape(b, t, n, x.shape[2] // n)

    with jax.named_scope("window" if window else "full"):
        out = attention(split(q, heads), split(k, kv_heads),
                        split(v, kv_heads),
                        causal=bool(attrs.get("causal", True)),
                        window=window, sink=optional.get("sink"))
    out = out.reshape(b, t, -1)
    if "gate" in optional:
        _M_GATED_LOWERINGS.inc(heads=heads, dv=out.shape[2] // heads)
        with jax.named_scope("gate"):
            out = gate_output(out, optional["gate"])
    return [out]


def _attention_infer(attrs, in_shapes):
    heads, kv_heads = int(attrs["num_heads"]), _kv_heads(attrs)
    if kv_heads <= 0 or heads % kv_heads:
        raise ValueError("Attention: num_kv_heads=%d must divide "
                         "num_heads=%d" % (kv_heads, heads))
    if int(attrs.get("window", 0)) and not bool(attrs.get("causal", True)):
        raise ValueError("Attention: a window needs causal=True")
    q, k, v = (_known(shape, "Attention") for shape in in_shapes[:3])
    d = _split_heads("Attention", "query", q, heads)
    dk = _split_heads("Attention", "key", k, kv_heads)
    dv = _split_heads("Attention", "value", v, kv_heads)
    for name, shape in (("key", k), ("value", v)):
        if shape[:2] != q[:2]:
            raise ValueError(
                "Attention: %s %s does not share query's batch and time "
                "%s" % (name, shape, q[:2]))
    if dk != d:
        raise ValueError(
            "Attention: key %s has head_dim %d over %d heads, query %s "
            "has %d over %d" % (k, dk, kv_heads, q, d, heads))
    out = q[:2] + (heads * dv,)
    optional = {"sink": (heads,), "gate": out}
    ins = [q, k, v] + [optional[name] for name in _optional_inputs(attrs)]
    return ins, [out], []


_attn = OpDef(
    "_contrib_Attention",
    _attention,
    arguments=("query", "key", "value", "sink", "gate"),
    defaults={"num_heads": 1, "num_kv_heads": 0, "causal": True,
              "window": 0, "with_sink": False, "with_gate": False},
    infer_shape=_attention_infer,
    aliases=("Attention",),
)
_attn.list_arguments = lambda attrs=None: (
    ["query", "key", "value"] + _optional_inputs(attrs))
register(_attn)


# --------------------------------------------------------------------------
# LatentAttention — causal attention whose keys and values are projected
# up from one normalised latent a token (MLA, DeepSeek-V2/V3)
# --------------------------------------------------------------------------
_M_LATENT_LOWERINGS = _tm.counter(
    "attention.latent_lowerings", "Traces of a LatentAttention call site "
    "(one per lowering, nothing per step); labels: heads, latent (the "
    "width keys and values are projected up from), rope (the rotary key "
    "every head shares), nope (a head's own key), dv, impl (see below) "
    "and, where set, rotary=0, window (the band's keys), select=1 (a "
    "keep-mask chooses the keys), gate=headwise, query_latent (the width "
    "the caller projected the query up from), rope_factor (YaRN's, where "
    "the frequencies are blended), score_scale (where the scores' scale "
    "is not 1 / sqrt(nope + rope))")


def latent_attention(query, latent, gamma, up_weight, num_heads, rope_dim,
                     v_head_dim, theta, eps, interleave=True, rotary=True,
                     window=0, latent_scale=1.0, gate=None, keep=None,
                     query_latent=0, score_scale=0.0, rope_scaling=()):
    """query [B, T, H * (N + R)] (a head's N un-rotated dimensions, then
    its R rotary ones), latent [B, T, L + R] (the compressed key/value
    latent, then the one rotary key a token), gamma [L], up_weight
    [H * (N + Dv), L] (a head's N key rows, then its Dv value rows) ->
    [B, T, H * Dv].

    ``c = RMSNorm(latent[:L])``; ``(k_nope_h, v_h) = up_weight c``;
    RoPE on each head's ``q_rope`` and on the shared ``k_rope``; head
    h's key is ``[k_nope_h, k_rope]``; causal softmax attention scaled
    by ``1 / sqrt(N + R)``. Norm statistics, rotation and softmax are
    float32; the up-projection takes operands of ``latent``'s dtype and
    accumulates in float32. The attention itself has two forms, chosen
    by the shapes alone and counted under ``impl``: ``kernel`` where
    ``kernels.latent_flash_takes`` admits them
    (``_latent_kernel_path`` below: the flash pair of two key operands
    where the step is lowered for the TPU), ``composed`` everywhere else
    (``_latent_composed_path``: the concatenated key through the one
    attention dispatch). ``rotary=False`` (NoPE latent attention): neither
    the query's R last dimensions nor the shared key is rotated, ``theta``
    and ``interleave`` are read by nothing; the same two forms on the same
    shapes (the kernels never rotated), counted with ``rotary=0``.

    ``latent_scale``: a fixed scalar on the normed latent (the product
    float32, one rounding), the rotary key unscaled. ``window`` > 0: row t
    sees the keys t - window + 1 .. t (``Attention``'s convention), through
    the composed form whatever the shapes (the pair has no band), under the
    scope ``window``. ``keep`` [B, T, T] (0 drops the pair; what
    ``KeyIndexer`` gives): row t's softmax runs over its kept keys s <= t
    only, the mask every head's and without a gradient; ``kernel`` is then
    the pair's selected variant (``flash2sel_*``, ``latent_flash(keep=)``),
    ``composed`` the materialised ``kernels.latent.kept_attention``, both
    under the scope ``select``. ``gate`` [B, T, H]: head h's output times
    ``sigmoid(gate[.., h])``, float32, one rounding (scope ``gate``).

    ``score_scale`` > 0 takes the place of ``1 / sqrt(N + R)`` on the
    scores (a scaled RoPE's ``mscale^2`` folded in by the model), through
    both forms. ``rope_scaling`` = ``(factor, beta_fast, beta_slow,
    original_max_position)``: both rotations turn by YaRN's blended
    frequencies (``kernels.common.rope_inv_freq``), a static table that
    takes ``theta``'s place wherever it goes; cos and sin are not scaled.
    At the defaults neither is read and the program is what it was."""
    from .kernels import latent_flash_takes

    if rope_scaling:
        theta = (theta,) + tuple(float(v) for v in rope_scaling)

    width = latent.shape[2] - rope_dim
    nope = query.shape[2] // num_heads - rope_dim
    kernel = not window and latent_flash_takes(
        query.shape[1], nope, rope_dim, v_head_dim, query.dtype)
    _M_LATENT_LOWERINGS.inc(
        heads=num_heads, latent=width, rope=rope_dim, nope=nope,
        dv=v_head_dim, impl="kernel" if kernel else "composed",
        **({} if rotary else {"rotary": 0}),
        **({"window": window} if window else {}),
        **({} if keep is None else {"select": 1}),
        **({} if gate is None else {"gate": "headwise"}),
        **({"query_latent": query_latent} if query_latent else {}),
        **({"rope_factor": rope_scaling[0]} if rope_scaling else {}),
        **({"score_scale": "%.6g" % score_scale} if score_scale else {}))
    with jax.named_scope("latent"):
        c = rms_norm(latent[..., :width], gamma, eps)
        if latent_scale != 1:
            c = (c.astype(jnp.float32)
                 * np.float32(latent_scale)).astype(c.dtype)
        kv = jax.lax.dot_general(
            c, up_weight.astype(c.dtype), (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).astype(c.dtype)
        k_rope = latent[..., width:]
        if rotary:
            k_rope = rope(k_rope, 1, theta, rope_dim, 0, interleave)
    if keep is not None:
        keep = jax.lax.stop_gradient(keep)
    if kernel:
        out = _latent_kernel_path(query, kv, k_rope, num_heads, v_head_dim,
                                  theta, interleave, rotary, keep=keep,
                                  scale=score_scale)
    else:
        out = _latent_composed_path(query, kv, k_rope, num_heads, v_head_dim,
                                    theta, interleave, rotary, window=window,
                                    keep=keep, scale=score_scale)
    if gate is None:
        return out
    with jax.named_scope("gate"):
        b, t, _ = out.shape
        return gate_output(out.reshape(b, t, num_heads, v_head_dim),
                           gate[..., None]).reshape(b, t, -1)


_LATENT_OPTIONAL = ("gate", "keep")


def _latent_attention(attrs, ins, is_train):
    optional = dict(zip(_optional_inputs(attrs, _LATENT_OPTIONAL),
                        ins[4:]))
    return [latent_attention(
        *ins[:4], num_heads=int(attrs["num_heads"]),
        rope_dim=int(attrs["rope_dim"]),
        v_head_dim=int(attrs["v_head_dim"]),
        theta=float(attrs.get("theta", 10000.0)),
        eps=float(attrs.get("eps", 1e-6)),
        interleave=bool(attrs.get("interleave", True)),
        rotary=bool(attrs.get("rotary", True)),
        window=int(attrs.get("window", 0)),
        latent_scale=float(attrs.get("latent_scale", 1.0)),
        query_latent=int(attrs.get("query_latent", 0)),
        score_scale=float(attrs.get("score_scale", 0.0)),
        rope_scaling=tuple(attrs.get("rope_scaling") or ()), **optional)]


def _latent_attention_infer(attrs, in_shapes):
    heads, r = int(attrs["num_heads"]), int(attrs["rope_dim"])
    dv = int(attrs["v_head_dim"])
    q = _known(in_shapes[0], "LatentAttention")
    latent = _known(in_shapes[1], "LatentAttention")
    d = _split_heads("LatentAttention", "query", q, heads)
    _check_rotation("LatentAttention", d, r, d - r)
    if dv <= 0:
        raise ValueError("LatentAttention: v_head_dim must be set (> 0)")
    if len(latent) != 3 or latent[:2] != q[:2] or latent[2] <= r:
        raise ValueError(
            "LatentAttention: latent %s must be query's [batch, time] %s "
            "by the latent width + rope_dim=%d" % (latent, q[:2], r))
    width = latent[2] - r
    window = int(attrs.get("window", 0))
    if window < 0 or (window and bool(attrs.get("with_keep", False))):
        raise ValueError(
            "LatentAttention: window=%d must be >= 0, and a window beside "
            "a keep-mask is not implemented" % window)
    optional = {"gate": q[:2] + (heads,), "keep": q[:2] + (q[1],)}
    return ([q, latent, (width,), (heads * (d - r + dv), width)]
            + [optional[name]
               for name in _optional_inputs(attrs, _LATENT_OPTIONAL)],
            [q[:2] + (heads * dv,)], [])


def _latent_attention_infer_type(attrs, in_types):
    """The keep-mask has a type of its own (int8, ``KeyIndexer``'s);
    every other input and the output share the query's."""
    names = ["query", "latent", "latent_gamma", "up_weight"
             ] + _optional_inputs(attrs, _LATENT_OPTIONAL)
    known = [t for name, t in zip(names, in_types)
             if t is not None and name != "keep"]
    if not known:
        raise MXNetError("LatentAttention: cannot infer type")
    t = known[0]
    return ([np.int8 if name == "keep" and x is None
             else t if x is None else x
             for name, x in zip(names, in_types)], [t], [])


_latent_op = OpDef(
    "_contrib_LatentAttention",
    _latent_attention,
    arguments=("query", "latent", "latent_gamma", "up_weight", "gate",
               "keep"),
    defaults={"num_heads": 1, "rope_dim": 0, "v_head_dim": 0,
              "theta": 10000.0, "eps": 1e-6, "interleave": True,
              "rotary": True, "window": 0, "latent_scale": 1.0,
              "query_latent": 0, "score_scale": 0.0, "rope_scaling": (),
              "with_gate": False, "with_keep": False},
    infer_shape=_latent_attention_infer,
    infer_type=_latent_attention_infer_type,
    aliases=("LatentAttention",),
)
_latent_op.list_arguments = lambda attrs=None: (
    ["query", "latent", "latent_gamma", "up_weight"]
    + _optional_inputs(attrs, _LATENT_OPTIONAL))
register(_latent_op)


# --------------------------------------------------------------------------
# Mamba2 — the state-space mixer's core between its two projections
# (Mamba-2 / SSD, Dao & Gu, arXiv:2405.21060)
# --------------------------------------------------------------------------
_M_SCAN_LOWERINGS = _tm.counter(
    "ssm.scan_lowerings", "Traces of a Mamba2 call site (one per "
    "lowering, nothing per step); labels: heads, head_dim, state, groups, "
    "chunk, conv (the convolution's taps), impl (kernel / einsum) and, "
    "where the projection's five segments are scaled, scaled=1")


def ssd_scan(x, bmat, cmat, dt, a, chunk):
    """The state-space recurrence ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t
    B_t^T``, ``y_t = S_t C_t`` (``S`` [H, P, N], zero before the first
    token) in its chunked (SSD) form. x [B, T, H, P], bmat and cmat
    [B, T, G, N] (head h reads group ``h // (H / G)``), dt [B, T, H]
    float32 and positive, a [H] float32 and negative -> y [B, T, H, P]
    float32.

    Inside a chunk of ``chunk`` tokens the masked ``(C B^T) * decay``
    product against ``dt x``; a chunk's end state; the recurrence over
    the chunks (a ``lax.scan``, the state entering each chunk kept); and
    the carried state read through ``C``. Log decays, their running sums
    and the carried state are float32; the four products take operands of
    ``x``'s dtype and accumulate in float32. T is padded to whole chunks
    with ``dt`` 0 (no decay, no input) and the padding cut off. The form
    for the shapes ``kernels.ssd_scan`` has no tiles for, and what
    its tests hold it to."""
    f32 = jnp.float32
    b, t, h, p = x.shape
    g, n = bmat.shape[2:]
    e = h // g                                    # heads a group
    pad = -t % chunk
    if pad:
        x, bmat, cmat, dt = (
            jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
            for v in (x, bmat, cmat, dt))
    nc = (t + pad) // chunk
    x = x.reshape(b, nc, chunk, g, e, p)
    bmat = bmat.reshape(b, nc, chunk, g, n)
    cmat = cmat.reshape(b, nc, chunk, g, n)
    dt = dt.reshape(b, nc, chunk, g, e)
    cum = jnp.cumsum(dt * a.reshape(g, e), axis=2)    # log decay to here
    total = cum[:, :, -1]                             # [B, nc, G, E]
    x32 = x.astype(f32)

    def dot(spec, lhs, rhs):
        return jnp.einsum(spec, lhs, rhs, preferred_element_type=f32)

    # inside a chunk: token i reads token j <= i through C_i . B_j,
    # decayed by exp(cum_i - cum_j)
    cb = dot("bcign,bcjgn->bcgij", cmat, bmat)
    at = jnp.moveaxis(cum, 2, -1)                     # [B, nc, G, E, Q]
    causal = np.tril(np.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(causal, at[..., :, None] - at[..., None, :],
                              -jnp.inf))
    mixed = (cb[:, :, :, None] * decay).astype(x.dtype)
    y = dot("bcgeij,bcjgep->bcigep", mixed,
            (x32 * dt[..., None]).astype(x.dtype))
    # a chunk's end state, had it started from zero
    to_end = jnp.exp(total[:, :, None] - cum) * dt
    states = dot("bcjgep,bcjgn->cbgepn",
                 (x32 * to_end[..., None]).astype(x.dtype), bmat)

    def carry(state, chunk_in):
        ended, decayed = chunk_in
        return state * jnp.exp(decayed)[..., None, None] + ended, state

    _, entering = jax.lax.scan(
        carry, jnp.zeros(states.shape[1:], f32),
        (states, jnp.moveaxis(total, 1, 0)))
    # the state a chunk entered with, read through C and decayed to here
    y = y + dot("bcign,cbgepn->bcigep", cmat,
                entering.astype(x.dtype)) * jnp.exp(cum)[..., None]
    return y.reshape(b, t + pad, h, p)[:, :t]


def mamba2(proj, conv_weight, conv_bias, dt_bias, a_log, d_skip, norm_gamma,
           num_heads, head_dim, state_size, num_groups, chunk_size, eps,
           remat=False, multipliers=None):
    """proj [B, T, 2 H P + 2 G N + H] (``in_proj``'s output: the gate
    ``z``, then ``x | B | C``, then a step size a head), conv_weight
    [taps, H P + 2 G N] (tap ``taps - 1`` meets the current token),
    conv_bias [H P + 2 G N], dt_bias, a_log and d_skip [H], norm_gamma
    [H P] -> [B, T, H P] (``out_proj``'s input).

    ``x | B | C = silu(conv(.))``, a causal depthwise convolution over
    time (scope ``conv1d``: ``conv`` is the class of the ``Convolution``
    nodes in a trace): one Pallas kernel each way over that window of
    ``proj``'s columns where the taps' family has tiles for the shapes
    and the step is lowered for the TPU (``kernels.taps_takes`` /
    ``causal_conv``), ``causal_taps``' shifted multiply-adds elsewhere;
    ``dt =
    softplus(dt + dt_bias)``, ``a = -exp(a_log)``, ``y = ssd_scan(...) +
    d_skip x`` (scope ``scan``); ``RMSNorm(y * silu(z))`` with the
    statistics over each of the G groups of columns, times ``norm_gamma``
    (scope ``gate_norm``: the gate first, then the norm). The
    convolution's sum, step sizes, decays, the carried state, the gate
    and the norm's statistics are float32 whatever ``proj``'s dtype. The
    scan is the Pallas kernel pair where the shapes have tiles for it and
    the step is lowered for the TPU (``kernels.ssd_takes`` /
    ``ssd_scan``; the skip inside it), the ``jnp.einsum`` form elsewhere.
    The gate and norm are one Pallas kernel each way where
    ``kernels.gate_norm_takes`` has tiles (``gated_rms_norm``,
    ``gate_first``), the ``gate_norm`` closure below elsewhere.
    ``remat`` (training): the float32 tables of the ``jax.numpy`` forms
    are computed again in the backward pass, not kept (``jax.checkpoint``
    round each; plain autodiff kept 9.6 GB of them at the Nemotron
    cell's shape); the scan's kernel pair keeps its own residuals (its
    output and the states), the taps' and the gate and norm's their
    inputs (the backward kernel computes the sums again in VMEM), and
    each runs once each way.

    ``multipliers`` (Falcon-H1's ``ssm_multipliers``, with whatever
    scalar the projection's input carried folded in): five fixed scalars
    over ``proj``'s segments ``z | x | B | C | dt``, ``proj * m`` a
    segment in the mathematics. No scaled copy of ``proj`` is made (the
    taps' kernel reads its window where ``in_proj`` left it): ``x``'s,
    ``B``'s and ``C``'s scale the taps' float32 weights a column (a
    depthwise tap is linear in its column; the bias is not scaled),
    ``z``'s sits inside the gate's ``silu`` and ``dt``'s in front of
    ``dt_bias``, each float32.

    The call site counts itself here (``ssm.scan_lowerings``,
    ``causal_taps.lowerings`` and ``gate_norm.lowerings``); the block
    itself is ``_mamba2_block``, one ``jax.jit`` for every node of one
    signature: a model's layers trace, differentiate and lower it once
    (XLA inlines the calls, each under its own node's scope)."""
    from . import kernels

    kernel = bool(kernels.ssd_takes(
        num_heads, head_dim, state_size, num_groups, chunk_size, proj.dtype))
    d_in = num_heads * head_dim
    taps_kernel = _taps_site("mamba2", proj, conv_weight, "bias_silu",
                             offset=d_in)
    norm_kernel = _gate_norm_site("mamba2", "gate_first", num_groups,
                                  d_in // num_groups, proj)
    if multipliers is not None:
        multipliers = tuple(float(m) for m in multipliers)
        if len(multipliers) != 5:
            raise ValueError("Mamba2: multipliers=%r must be five scalars, "
                             "one a segment of z | x | B | C | dt"
                             % (multipliers,))
    _M_SCAN_LOWERINGS.inc(heads=num_heads, head_dim=head_dim,
                          state=state_size, groups=num_groups,
                          chunk=chunk_size, conv=conv_weight.shape[0],
                          impl="kernel" if kernel else "einsum",
                          **({} if multipliers is None else {"scaled": 1}))
    return _mamba2_block(
        proj, conv_weight, conv_bias, dt_bias, a_log, d_skip, norm_gamma,
        sizes=(num_heads, head_dim, state_size, num_groups, chunk_size),
        eps=float(eps), remat=bool(remat), kernel=kernel,
        taps_kernel=taps_kernel, interpret=kernels.common.INTERPRET,
        multipliers=multipliers, norm_kernel=norm_kernel)


@functools.partial(jax.jit, static_argnames=(
    "sizes", "eps", "remat", "kernel", "taps_kernel", "interpret",
    "multipliers", "norm_kernel"))
def _mamba2_block(proj, conv_weight, conv_bias, dt_bias, a_log, d_skip,
                  norm_gamma, *, sizes, eps, remat, kernel, taps_kernel,
                  interpret, multipliers=None, norm_kernel=False):
    """``mamba2`` for one signature (``sizes``: heads, head width, state,
    groups, chunk)."""
    from . import kernels

    f32 = jnp.float32
    b, t, _ = proj.shape
    h, p, n, g, chunk = sizes
    d_in = h * p
    conv_dim = d_in + 2 * g * n
    m_z = m_dt = None
    if multipliers is not None:
        m_z, m_x, m_b, m_c, m_dt = multipliers
        conv_weight = conv_weight.astype(f32) * np.repeat(
            np.asarray([m_x, m_b, m_c], np.float32), (d_in, g * n, g * n))

    def again(f, **policy):
        return jax.checkpoint(f, **policy) if remat else f

    def conv1d(proj, conv_weight, conv_bias):
        acc = causal_taps(proj[..., d_in:d_in + conv_dim], conv_weight,
                          conv_bias)
        return jax.nn.silu(acc).astype(proj.dtype)

    def gate_norm(y, proj, norm_gamma):
        z = proj[..., :d_in].astype(f32)
        gated = y.reshape(b, t, d_in) * jax.nn.silu(
            z if m_z is None else z * m_z)
        groups = gated.reshape(b, t, g, d_in // g)
        var = jnp.mean(jnp.square(groups), axis=-1, keepdims=True)
        normed = (groups * jax.lax.rsqrt(var + eps)).reshape(b, t, d_in)
        return norm_gamma.astype(proj.dtype) * normed.astype(proj.dtype)

    with jax.named_scope("conv1d"):
        if taps_kernel:
            xbc = kernels.causal_conv(
                proj, conv_weight, conv_bias, form="bias_silu", offset=d_in,
                channels=conv_dim, interpret=interpret)
        else:
            xbc = again(conv1d)(proj, conv_weight, conv_bias)
    with jax.named_scope("scan"):
        x = xbc[..., :d_in].reshape(b, t, h, p)
        bc = (xbc[..., d_in:d_in + g * n].reshape(b, t, g, n),
              xbc[..., d_in + g * n:].reshape(b, t, g, n))
        dt = proj[..., d_in + conv_dim:].astype(f32)
        dt = jax.nn.softplus((dt if m_dt is None else dt * m_dt)
                             + dt_bias.astype(f32))
        a = -jnp.exp(a_log.astype(f32))
        if kernel:
            y = kernels.ssd_scan(x, *bc, dt, a, d_skip, chunk,
                                 interpret=interpret)
        else:
            y = again(functools.partial(ssd_scan, chunk=chunk),
                      policy=jax.checkpoint_policies.dots_saveable)(
                          x, *bc, dt, a)
            y = y + d_skip.astype(f32)[:, None] * x.astype(f32)
    with jax.named_scope("gate_norm"):
        if norm_kernel:
            return kernels.gated_rms_norm(
                y.reshape(b, t, d_in), proj, norm_gamma, form="gate_first",
                groups=g, eps=eps, scale=m_z, interpret=interpret)
        return again(gate_norm)(y, proj, norm_gamma)


def _mamba2_sizes(attrs):
    return tuple(int(attrs[k]) for k in (
        "num_heads", "head_dim", "state_size", "num_groups"))


def _mamba2(attrs, ins, is_train):
    h, p, n, g = _mamba2_sizes(attrs)
    return [mamba2(*ins, num_heads=h, head_dim=p, state_size=n, num_groups=g,
                   chunk_size=int(attrs["chunk_size"]),
                   eps=float(attrs.get("eps", 1e-5)), remat=is_train,
                   multipliers=attrs.get("multipliers"))]


def _mamba2_infer(attrs, in_shapes):
    h, p, n, g = _mamba2_sizes(attrs)
    taps, chunk = int(attrs["conv_kernel"]), int(attrs["chunk_size"])
    if min(h, p, n, g, taps, chunk) <= 0 or h % g:
        raise ValueError(
            "Mamba2: num_heads=%d, head_dim=%d, state_size=%d, "
            "num_groups=%d, conv_kernel=%d and chunk_size=%d must be "
            "positive and the groups divide the heads"
            % (h, p, n, g, taps, chunk))
    data = _known(in_shapes[0], "Mamba2")
    d_in, conv_dim = h * p, h * p + 2 * g * n
    if len(data) != 3 or data[2] != d_in + conv_dim + h:
        raise ValueError(
            "Mamba2: data must be [batch, time, %d] (z %d | x B C %d | "
            "dt %d), got %s" % (d_in + conv_dim + h, d_in, conv_dim, h,
                                data))
    return ([data, (taps, conv_dim), (conv_dim,), (h,), (h,), (h,),
             (d_in,)], [data[:2] + (d_in,)], [])


register(
    OpDef(
        "_contrib_Mamba2",
        _mamba2,
        arguments=("data", "conv_weight", "conv_bias", "dt_bias", "a_log",
                   "d", "norm_gamma"),
        defaults={"num_heads": 1, "head_dim": 0, "state_size": 0,
                  "num_groups": 1, "conv_kernel": 4, "chunk_size": 128,
                  "eps": 1e-5, "multipliers": None},
        infer_shape=_mamba2_infer,
        aliases=("Mamba2",),
    )
)


# --------------------------------------------------------------------------
# TopKMoE — dropless top-k sparse-expert FFN
# --------------------------------------------------------------------------
def _topk_moe(attrs, ins, is_train):
    """``parallel/moe.topk_moe`` as a Symbol op. Two outputs: the routed
    FFN result and how many (token, expert) rows each of the
    ``num_experts`` experts received — float32 so that it can ride out
    of a training step beside the loss (behind ``BlockGrad``; it has no
    gradient)."""
    from ..parallel.moe import topk_moe

    data, gate_w, w_gate_up, w_down = ins[:4]
    params = {"gate_w": gate_w, "w_gate_up": w_gate_up, "w_down": w_down}
    if bool(attrs.get("with_select_bias", False)):
        params["select_bias"] = ins[4]
    y, counts = topk_moe(
        params, data, top_k=int(attrs["top_k"]),
        norm_topk_prob=bool(attrs.get("norm_topk_prob", False)),
        scoring=str(attrs.get("scoring", "softmax")),
        routed_scale=float(attrs.get("routed_scale", 1.0)),
        activation=str(attrs.get("activation", "swiglu")),
        expert_offset=int(attrs.get("expert_offset", 0)),
        share_rows_bound=int(attrs.get("share_rows_bound", 0)),
        renorm_eps=float(attrs.get("renorm_eps", 0.0)))
    return [y, counts.astype(jnp.float32)]


def _topk_moe_infer(attrs, in_shapes):
    data = _known(in_shapes[0], "TopKMoE")
    if len(data) != 2:
        raise ValueError("TopKMoE: data must be [tokens, d_model] "
                         "(Reshape (B,T,D) inputs to (B*T, D))")
    d_model = data[1]
    num_experts = int(attrs["num_experts"])
    held = int(attrs.get("experts_held", 0)) or num_experts
    offset = int(attrs.get("expert_offset", 0))
    hidden = int(attrs["num_hidden"])
    top_k = int(attrs["top_k"])
    if hidden <= 0:
        raise ValueError("TopKMoE: num_hidden must be set (> 0)")
    if not 1 <= top_k <= num_experts:
        raise ValueError("TopKMoE: top_k must lie in 1..num_experts, got "
                         "%d of %d" % (top_k, num_experts))
    if str(attrs.get("scoring", "softmax")) not in ("softmax", "sigmoid"):
        raise ValueError("TopKMoE: scoring must be softmax or sigmoid, "
                         "got %r" % (attrs["scoring"],))
    activation = str(attrs.get("activation", "swiglu"))
    if activation not in ("swiglu", "relu2"):
        raise ValueError("TopKMoE: activation must be swiglu or relu2, "
                         "got %r" % (activation,))
    if not (0 < held <= num_experts and 0 <= offset <= num_experts - held):
        raise ValueError(
            "TopKMoE: experts_held=%d from expert_offset=%d are not among "
            "num_experts=%d" % (held, offset, num_experts))
    if held < num_experts and not (
            0 < int(attrs.get("share_rows_bound", 0)) <= data[0] * top_k):
        raise ValueError(
            "TopKMoE: a share (experts_held=%d of %d) needs "
            "share_rows_bound in 1..tokens * top_k (%d), got %s"
            % (held, num_experts, data[0] * top_k,
               attrs.get("share_rows_bound", 0)))
    # un-gated, ``gate_up_weight`` is the up projection alone
    up = hidden if activation == "relu2" else 2 * hidden
    ins = [data, (d_model, num_experts), (held, d_model, up),
           (held, hidden, d_model)]
    return (ins + [(num_experts,)] * (len(in_shapes) - 4),
            [data, (num_experts,)], [])


def _topk_moe_infer_type(attrs, in_types):
    known = [t for t in in_types if t is not None]
    if not known:
        raise MXNetError("TopKMoE: cannot infer type")
    t = known[0]
    return ([t if x is None else x for x in in_types],
            [t, np.float32], [])


_moe = OpDef(
    "_contrib_TopKMoE",
    _topk_moe,
    arguments=("data", "gate_weight", "gate_up_weight", "down_weight",
               "select_bias"),
    outputs=("output", "expert_count"),
    defaults={"num_experts": 8, "num_hidden": 0, "top_k": 2,
              "norm_topk_prob": False, "scoring": "softmax",
              "routed_scale": 1.0, "activation": "swiglu",
              "renorm_eps": 0.0,
              "with_select_bias": False,
              "experts_held": 0,
              "expert_offset": 0,
              "share_rows_bound": 0},
    infer_shape=_topk_moe_infer,
    infer_type=_topk_moe_infer_type,
    aliases=("TopKMoE",),
)
_moe.list_arguments = lambda attrs=None: (
    ["data", "gate_weight", "gate_up_weight", "down_weight"]
    + (["select_bias"] if (attrs or {}).get("with_select_bias") else []))
register(_moe)


# --------------------------------------------------------------------------
# GatedDeltaNet — a linear-attention mixer's core between its projections:
# the gated delta rule (Yang, Kautz & Hatamizadeh, arXiv:2412.06464; write
# strengths up to 2, Grazzi et al., arXiv:2411.12537)
# --------------------------------------------------------------------------
_M_LINEAR_ATTN_LOWERINGS = _tm.counter(
    "linear_attn.lowerings", "Traces of a GatedDeltaNet call site (one per "
    "lowering, nothing per step); labels: heads, key_dim, value_dim (a "
    "head's widths), chunk (tokens a chunk of the delta rule), conv (the "
    "convolution's taps), impl (kernel: the Pallas pair where the step is "
    "lowered for the TPU, the chunk form elsewhere; chunked: the jax.numpy "
    "chunk form everywhere), decay=channel (gdn.py's kda_ pair), gate, "
    "beta_scale=2 (a channel call whose write strengths are 2 sigmoid)")


def gated_delta_rule(q, k, v, g, beta, chunk):
    """The gated delta rule ``S_t = a_t S_{t-1} + k_t u_t^T`` with ``u_t =
    beta_t (v_t - a_t S_{t-1}^T k_t)``, ``a_t = exp(g_t)``, ``o_t = S_t^T
    q_t`` (``S`` [H, K, V] float32, zero before the first token) in its
    chunk form. q and k [B, T, H, K], v [B, T, H, V], g (log decay, <= 0)
    and beta (write strength) [B, T, H] float32 -> o [B, T, H, V]
    float32.

    With ``b_i`` the running sum of ``g`` inside a chunk, ``c_i =
    exp(b_i)`` and ``D_ij = exp(b_i - b_j)`` (the exponential of a masked
    non-positive difference): ``L_ij = beta_i D_ij (k_i . k_j)`` below the
    diagonal; one unit-triangular system a chunk and head, ``(I + L) [W |
    Y] = [beta v | beta c k]`` (forward substitution: ``L`` is nilpotent,
    but the powers of a product form cancel badly once keys repeat); ``M
    = tril((q k^T) * D)``. All of that for every chunk at once; then, a
    ``lax.scan`` over the chunks whose carry is the state, three
    products with the state ``S`` a chunk enters with: ``o = M W + (c q -
    M Y) S``, ``u = W - Y S`` and ``S' = c_C S + (k c_C / c)^T u``.
    Decays, ``D``, the triangular solve and the state are float32; the
    products take operands of ``v``'s dtype and accumulate in float32. T
    is padded to whole chunks with ``k`` 0, ``beta`` 0 and ``g`` 0 (no
    write, no decay) and the padding cut off. The form for the shapes
    ``kernels.gated_delta_rule`` has no tiles for and for every
    platform but the TPU, and what its tests hold it to."""
    f32 = jnp.float32
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    pad = -t % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    nc = (t + pad) // chunk
    dtype = v.dtype

    def chunks(x):  # [B, T, H, ...] -> [B, nc, H, C, ...]
        return jnp.moveaxis(x.reshape((b, nc, chunk) + x.shape[2:]), 3, 2)

    def dot(spec, lhs, rhs):
        return jnp.einsum(spec, lhs.astype(dtype), rhs.astype(dtype),
                          preferred_element_type=f32)

    q, k, v = chunks(q), chunks(k), chunks(v)
    beta = chunks(beta.astype(f32))[..., None]        # [B, nc, H, C, 1]
    cum = jnp.cumsum(chunks(g.astype(f32)), axis=-1)  # b_i
    c = jnp.exp(cum)[..., None]                       # decay from the
    to_end = jnp.exp(cum[..., -1:] - cum)[..., None]  # start; to the end
    lower = np.tril(np.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(lower, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))              # D, 0 above the diag
    system = beta * decay * dot("bchid,bchjd->bchij", k, k)
    solved = jax.scipy.linalg.solve_triangular(
        system, jnp.concatenate([beta * v.astype(f32),
                                 beta * c * k.astype(f32)], axis=-1),
        lower=True, unit_diagonal=True)               # the diagonal unread
    w, y = solved[..., :dv], solved[..., dv:]
    m = decay * dot("bchid,bchjd->bchij", q, k)
    out0 = dot("bchij,bchjv->bchiv", m, w)
    q_in = c * q.astype(f32) - dot("bchij,bchjd->bchid", m, y)
    k_out = to_end * k.astype(f32)

    def step(state, at):                              # [B, H, K, V]
        out0, q_in, w, y, k_out, kept = at
        u = w - dot("bhid,bhdv->bhiv", y, state)
        out = out0 + dot("bhid,bhdv->bhiv", q_in, state)
        state = kept[..., None, None] * state + dot("bhid,bhiv->bhdv",
                                                    k_out, u)
        return state, out

    _, out = jax.lax.scan(
        step, jnp.zeros((b, h, dk, dv), f32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (
            out0, q_in.astype(dtype), w.astype(dtype), y.astype(dtype),
            k_out.astype(dtype), jnp.exp(cum[..., -1]))))
    out = jnp.moveaxis(out, 0, 1)                     # [B, nc, H, C, V]
    return jnp.moveaxis(out, 2, 3).reshape(b, t + pad, h, dv)[:, :t]


def gated_delta_net(query, key, value, gate, a, b, conv_weight, a_log,
                    dt_bias, norm_gamma, num_heads, chunk_size, eps,
                    allow_neg_eigval=True, remat=False, gate_act="silu"):
    """query and key [B, T, H K], value and gate [B, T, H V], a and b [B,
    T, H] (the six projections of the block's input), conv_weight [taps,
    2 H K + H V] (the taps of ``query | key | value``; tap ``taps - 1``
    meets the current token), a_log and dt_bias [H], norm_gamma [V] ->
    [B, T, H V] (``o_proj``'s input).

    ``q, k, v = silu(conv(.))``, a causal depthwise convolution over time
    without bias (scope ``conv1d``; each of the three arrays the taps'
    Pallas kernel pair where ``kernels.taps_takes`` has tiles for it and
    the step is lowered for the TPU, ``causal_taps`` elsewhere); ``q = q /
    |q| / sqrt(K)`` and ``k = k / |k|`` a head (``|x|`` = sqrt(sum x^2 +
    1e-6)), ``beta = 2 sigmoid(b)`` (``allow_neg_eigval``; without it the 2
    goes), ``g = -exp(a_log) softplus(a + dt_bias)``, ``o =
    gated_delta_rule(...)`` (scope ``delta_rule``); ``RMSNorm(o) norm_gamma
    silu(gate)`` with the statistics over each head's V columns (scope
    ``gate_norm``: the norm first, then the gate). The convolution's sum,
    the two norms, write strengths, decays, the triangular solve, the
    state and the gate are float32 whatever the inputs' dtype. The rule is
    the Pallas kernel pair where the shapes have tiles for it and the step
    is lowered for the TPU (``kernels.gdn_takes``), the ``jax.numpy``
    chunk form elsewhere. ``remat`` (training): each of the three scopes
    is computed again in the backward pass from its inputs
    (``jax.checkpoint``); of ``delta_rule`` on the kernel path that is the
    unit norms, ``beta`` and ``g`` only: the kernel pair keeps its own
    residuals and runs once each way. Where ``kernels.gate_norm_takes``
    has tiles the gate and norm are ``gated_rms_norm`` (``norm_first``) on
    ``o`` head-major as the rule's kernel wrote it (a ``silu`` gate).

    **A decay a channel** (Kimi Delta Attention, arXiv:2510.26692), taken
    by the shape of ``a``: [B, T, H K] with ``dt_bias`` [H K] (``a_log``
    stays [H]) gives ``g = -exp(a_log_h) softplus(a + dt_bias)`` a key
    channel and the rule ``channel_delta_rule`` below. Where
    ``kernels.gdn_takes(..., "channel")`` has tiles (a head whole lane
    rows) the block is ``_channel_delta_block``: the pair ``kda_fwd_`` /
    ``kda_bwd_`` from the taps' outputs on, the unit norms and decays made
    in VMEM, and the gate and norm ``gated_rms_norm`` (``token_major``)
    on ``o`` as that pair wrote it. ``gate_act="sigmoid"``: the gate behind
    the norm is a sigmoid. Both are counted where they are not the default.

    The call site counts itself here (``linear_attn.lowerings``,
    ``gate_norm.lowerings``, ``causal_taps.lowerings`` once a convolved
    array); the block is ONE ``jax.jit`` for every node of a signature."""
    from . import kernels

    key_dim, value_dim = (x.shape[2] // num_heads for x in (query, value))
    channel = a.shape[2] != num_heads
    if gate_act not in ("silu", "sigmoid"):
        raise ValueError("GatedDeltaNet: gate_act=%r (silu or sigmoid)"
                         % (gate_act,))
    kernel = kernels.gdn_takes(
        num_heads, key_dim, value_dim, chunk_size, value.dtype,
        "channel" if channel else "scalar")
    gated = {} if gate_act == "silu" else {"gate": gate_act}
    labels = dict(gated, decay="channel") if channel else gated
    if channel and allow_neg_eigval:  # not the channel form's accepted 1
        labels["beta_scale"] = 2
    _M_LINEAR_ATTN_LOWERINGS.inc(
        heads=num_heads, key_dim=key_dim, value_dim=value_dim,
        chunk=chunk_size, conv=conv_weight.shape[0],
        impl="kernel" if kernel else "chunked", **labels)
    taps_kernel = tuple(
        _taps_site("gated_delta_net", x, conv_weight, "silu",
                   channels=x.shape[2]) for x in (query, key, value))
    # the norm's kernel reads o where the rule's kernel left it: head-major
    # from the scalar pair (a silu gate), token-major from the channel pair
    norm_kernel = _gate_norm_site(
        "gated_delta_net", "token_major" if channel else "norm_first",
        num_heads, value_dim, gate, core=kernel and (channel or (
            query.shape[1] % chunk_size == 0 and gate_act == "silu")), **gated)
    if channel and kernel:  # the rule's pair from the taps' outputs on
        return _channel_delta_block(
            query, key, value, gate, a, b, conv_weight, a_log, dt_bias,
            norm_gamma, heads=int(num_heads), chunk=int(chunk_size),
            eps=float(eps), beta_scale=2.0 if allow_neg_eigval else 1.0,
            remat=bool(remat), taps_kernel=taps_kernel, gate_act=gate_act,
            interpret=kernels.common.INTERPRET, norm_kernel=norm_kernel)
    return _gated_delta_block(
        query, key, value, gate, a, b, conv_weight, a_log, dt_bias,
        norm_gamma, heads=int(num_heads), chunk=int(chunk_size),
        eps=float(eps), beta_scale=2.0 if allow_neg_eigval else 1.0,
        remat=bool(remat), kernel=kernel, taps_kernel=taps_kernel,
        interpret=kernels.common.INTERPRET, norm_kernel=norm_kernel,
        gate_act=gate_act)


@functools.partial(jax.jit, static_argnames=(
    "heads", "chunk", "eps", "beta_scale", "remat", "kernel", "taps_kernel",
    "interpret", "norm_kernel", "gate_act"))
def _gated_delta_block(query, key, value, gate, a, b, conv_weight, a_log,
                       dt_bias, norm_gamma, *, heads, chunk, eps,
                       beta_scale, remat, kernel, taps_kernel, interpret,
                       norm_kernel=False, gate_act="silu"):
    """``gated_delta_net`` for one signature (``a`` [B, T, H K]: the
    channel form)."""
    from . import kernels

    f32 = jnp.float32
    bsz, t, _ = query.shape
    dk, dv = query.shape[2] // heads, value.shape[2] // heads
    channel = a.shape[2] != heads

    def again(f):
        return jax.checkpoint(f) if remat else f

    def conv1d(x, w, takes):
        if takes:
            return kernels.causal_conv(x, w, form="silu",
                                       interpret=interpret)
        return again(lambda x, w: jax.nn.silu(
            causal_taps(x, w)).astype(x.dtype))(x, w)

    def unit(x):  # each head's vector over its length, float32
        x = x.astype(f32).reshape(bsz, t, heads, -1)
        return x * jax.lax.rsqrt(
            jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)

    def unit_and_strengths(q, k, a, b, a_log, dt_bias):
        beta = beta_scale * jax.nn.sigmoid(b.astype(f32))
        if channel:  # a rate a head, a step size a key channel
            a_log = a_log[:, None]
            a = a.reshape(bsz, t, heads, dk)
            dt_bias = dt_bias.reshape(heads, dk)
        g = -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(
            a.astype(f32) + dt_bias.astype(f32))
        return ((unit(q) * dk ** -0.5).astype(value.dtype),
                unit(k).astype(value.dtype), g, beta)

    def delta_rule(q, k, v, a, b, a_log, dt_bias):
        q, k, g, beta = unit_and_strengths(q, k, a, b, a_log, dt_bias)
        rule = channel_delta_rule if channel else gated_delta_rule
        return rule(q, k, v.reshape(bsz, t, heads, dv), g, beta, chunk)

    def gate_norm(o, gate, norm_gamma):
        var = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
        normed = o * jax.lax.rsqrt(var + eps) * norm_gamma.astype(f32)
        gated = normed.reshape(bsz, t, heads * dv) * getattr(
            jax.nn, gate_act)(gate.astype(f32))
        return gated.astype(gate.dtype)

    with jax.named_scope("conv1d"):
        edges = (0, heads * dk, 2 * heads * dk, 2 * heads * dk + heads * dv)
        q, k, v = (conv1d(x, conv_weight[:, lo:hi], takes)
                   for x, lo, hi, takes in zip((query, key, value), edges,
                                               edges[1:], taps_kernel))
    with jax.named_scope("delta_rule"):
        if kernel:
            q, k, g, beta = again(unit_and_strengths)(q, k, a, b, a_log,
                                                      dt_bias)
            o = kernels.gated_delta_rule(
                q, k, v.reshape(bsz, t, heads, dv), g, beta, chunk,
                interpret=interpret, head_major=norm_kernel)
        else:
            o = again(delta_rule)(q, k, v, a, b, a_log, dt_bias)
    with jax.named_scope("gate_norm"):
        if norm_kernel:
            return kernels.gated_rms_norm(o, gate, norm_gamma, eps=eps,
                                          form="norm_first",
                                          interpret=interpret)
        return again(gate_norm)(o, gate, norm_gamma)


def _gated_delta_net(attrs, ins, is_train):
    return [gated_delta_net(
        *ins, num_heads=int(attrs["num_heads"]),
        chunk_size=int(attrs.get("chunk_size", 64)),
        eps=float(attrs.get("eps", 1e-6)),
        allow_neg_eigval=bool(attrs.get("allow_neg_eigval", True)),
        remat=is_train, gate_act=str(attrs.get("gate_act", "silu")))]


def _gated_delta_net_infer(attrs, in_shapes):
    heads, taps = int(attrs["num_heads"]), int(attrs.get("conv_kernel", 4))
    chunk = int(attrs.get("chunk_size", 64))
    if min(heads, taps, chunk) <= 0:
        raise ValueError(
            "GatedDeltaNet: num_heads=%d, conv_kernel=%d and chunk_size=%d "
            "must be positive" % (heads, taps, chunk))
    q = _known(in_shapes[0], "GatedDeltaNet")
    v = _known(in_shapes[2], "GatedDeltaNet")
    dk = _split_heads("GatedDeltaNet", "query", q, heads)
    dv = _split_heads("GatedDeltaNet", "value", v, heads)
    if v[:2] != q[:2]:
        raise ValueError("GatedDeltaNet: value %s does not share query's "
                         "batch and time %s" % (v, q[:2]))
    scalars = q[:2] + (heads,)
    # a decay a channel where ``a`` is as wide as the keys (KDA)
    channel = dk > 1 and in_shapes[4] is not None and tuple(
        in_shapes[4]) == tuple(q)
    return ([q, q, v, v, q if channel else scalars, scalars,
             (taps, 2 * heads * dk + heads * dv), (heads,),
             (heads * dk,) if channel else (heads,), (dv,)], [v], [])


register(
    OpDef(
        "_contrib_GatedDeltaNet",
        _gated_delta_net,
        arguments=("query", "key", "value", "gate", "a", "b", "conv_weight",
                   "a_log", "dt_bias", "norm_gamma"),
        defaults={"num_heads": 1, "conv_kernel": 4, "chunk_size": 64,
                  "eps": 1e-6, "allow_neg_eigval": True, "gate_act": "silu"},
        infer_shape=_gated_delta_net_infer,
        aliases=("GatedDeltaNet",),
    )
)


# --------------------------------------------------------------------------
# ShortConv — a double-gated short causal convolution between its two
# projections (the ``conv`` mixer of the LFM2 family), and the taps that
# ``Mamba2`` and ``GatedDeltaNet`` share with it
# --------------------------------------------------------------------------
_M_SCONV_LOWERINGS = _tm.counter(
    "sconv.lowerings", "Traces of a ShortConv call site (one per lowering, "
    "nothing per step); labels: channels, taps, impl (kernel: the Pallas "
    "pair of ops/kernels/taps.py where the step is lowered for the TPU; "
    "jnp: shifted multiply-adds that XLA fuses)")


def causal_taps(x, weight, bias=None):
    """A causal depthwise convolution over time as shifted multiply-adds:
    x [B, T, C], weight [taps, C] (tap ``taps - 1`` meets the current
    token, ``x`` is zero before the sequence), bias [C] or None -> float32
    [B, T, C], ``bias + sum_j weight[j] * x[t - (taps - 1) + j]`` summed
    in float32 in that order. The plain form: what ``mamba2``,
    ``gated_delta_net`` and ``short_conv`` run on every platform but the
    TPU and for the shapes ``kernels.taps_takes`` refuses, and the oracle
    of ``kernels.causal_conv``, which sums the same terms in the same
    order in VMEM."""
    taps, t = weight.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0))).astype(jnp.float32)
    w = weight.astype(jnp.float32)
    acc = None if bias is None else bias.astype(jnp.float32)
    for j in range(taps):
        term = padded[:, j:j + t] * w[j]
        acc = term if acc is None else acc + term
    return acc


def gated_taps(proj, conv_weight):
    """``C * causal_taps(B * x)`` of proj [B, T, 3 H] = ``B | C | x``,
    conv_weight [taps, H] -> [B, T, H] in proj's dtype, the gates and the
    sum float32: ``short_conv`` in ``jax.numpy``."""
    h = conv_weight.shape[1]
    f32 = jnp.float32
    with jax.named_scope("gate_in"):
        z = proj[..., :h].astype(f32) * proj[..., 2 * h:].astype(f32)
    with jax.named_scope("conv1d"):
        c = causal_taps(z, conv_weight)
    with jax.named_scope("gate_out"):
        return (proj[..., h:2 * h].astype(f32) * c).astype(proj.dtype)


def short_conv(proj, conv_weight, remat=False):
    """proj [B, T, 3 H] (``in_proj``'s output, ``B | C | x`` in that
    order), conv_weight [taps, H] -> [B, T, H] (``out_proj``'s input):
    ``C * causal_taps(B * x)``, no bias and no activation anywhere. The
    two gates and the taps' sum are float32 whatever ``proj``'s dtype,
    the result ``proj``'s. One Pallas kernel each way where the family
    has tiles for the shapes and the step is lowered for the TPU
    (``kernels.taps_takes`` / ``causal_conv``, scope ``conv1d``: the
    three thirds read where ``proj`` holds them, all of ``dproj`` written
    by the backward), ``gated_taps`` elsewhere (scopes ``gate_in``,
    ``conv1d``, ``gate_out``). ``remat`` (training): the backward pass
    keeps the op's two inputs and computes the float32 tables again, the
    kernel in VMEM, ``gated_taps`` under one ``jax.checkpoint``."""
    from . import kernels

    kernel = _taps_site("short_conv", proj, conv_weight, "gates")
    _M_SCONV_LOWERINGS.inc(channels=conv_weight.shape[1],
                           taps=conv_weight.shape[0],
                           impl="kernel" if kernel else "jnp")
    if kernel:
        with jax.named_scope("conv1d"):
            return kernels.causal_conv(proj, conv_weight, form="gates",
                                       interpret=kernels.common.INTERPRET)
    return (jax.checkpoint(gated_taps) if remat else gated_taps)(
        proj, conv_weight)


def _short_conv(attrs, ins, is_train):
    return [short_conv(*ins, remat=is_train)]


def _short_conv_infer(attrs, in_shapes):
    taps = int(attrs.get("conv_kernel", 3))
    data = _known(in_shapes[0], "ShortConv")
    if taps <= 0 or len(data) != 3 or data[2] % 3:
        raise ValueError(
            "ShortConv: conv_kernel=%d must be positive and data [batch, "
            "time, 3 * channels] (B | C | x), got %s" % (taps, data))
    h = data[2] // 3
    return [data, (taps, h)], [data[:2] + (h,)], []


register(
    OpDef(
        "_contrib_ShortConv",
        _short_conv,
        arguments=("data", "conv_weight"),
        defaults={"conv_kernel": 3},
        infer_shape=_short_conv_infer,
        aliases=("ShortConv",),
    )
)


# --------------------------------------------------------------------------
# LatentAttention's two forms of the attention itself (``latent_attention``
# chooses; down here so that no line above moves: see GatedDeltaNet's note)
# --------------------------------------------------------------------------
def _latent_composed_path(query, kv, k_rope, num_heads, v_head_dim, theta,
                          interleave, rotary=True, window=0, keep=None,
                          scale=0.0):
    """Every head's key materialised: the rotation over the whole query,
    the shared rotary key broadcast and concatenated behind each head's
    slice of ``kv`` [B, T, H (N + Dv)], the values sliced out of it, and
    ``kernels.attention`` (the flash kernel on the TPU at T >= 128,
    the materialised reference elsewhere; its band under ``window``), or
    under ``keep`` the materialised ``kernels.latent.kept_attention``."""
    from .kernels import attention
    from .kernels.latent import kept_attention

    b, t, _ = query.shape
    rope_dim = k_rope.shape[2]
    nope = query.shape[2] // num_heads - rope_dim
    with jax.named_scope("latent"):
        kv = kv.reshape(b, t, num_heads, nope + v_head_dim)
        q = rope(query, num_heads, theta, rope_dim, nope,
                 interleave) if rotary else query
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(
                k_rope[:, :, None, :], (b, t, num_heads, rope_dim))],
            axis=-1)
    q = q.reshape(b, t, num_heads, nope + rope_dim)
    if keep is not None:
        with jax.named_scope("select"):
            out = kept_attention(q, k, kv[..., nope:], keep,
                                 scale or (nope + rope_dim) ** -0.5)
    else:
        with jax.named_scope("window" if window else "full"):
            out = attention(q, k, kv[..., nope:], causal=True,
                            window=window, scale=scale or None)
    return out.reshape(b, t, num_heads * v_head_dim)


def _latent_kernel_path(query, kv, k_rope, num_heads, v_head_dim, theta,
                        interleave, rotary=True, keep=None, scale=0.0):
    """Nothing of [T, H, N + R] built for the keys: one pass over the
    query (``_kernel_query``) and ``kernels.latent_flash`` on
    ``kv`` and ``k_rope`` where the up-projection and the rotation left
    them; its output is the output projection's input as it stands."""
    from .kernels import common, latent_flash

    rope_dim = k_rope.shape[2]
    width = query.shape[2] // num_heads
    interpret = common.INTERPRET
    with jax.named_scope("latent"):
        if rotary:
            q = _kernel_query(query, num_heads, rope_dim, theta, interleave,
                              interpret)
        else:  # a head's R lanes as they are, zeros to a whole lane row
            q = jnp.pad(query.reshape(query.shape[:2] + (num_heads, width)),
                        ((0, 0),) * 3 + ((0, -rope_dim % 128),)).reshape(
                            query.shape[:2] + (-1,))
        k_rope = jnp.pad(k_rope, ((0, 0), (0, 0), (0, -rope_dim % 128)))
    with jax.named_scope("full" if keep is None else "select"):
        return latent_flash(q, kv, k_rope, num_heads, width - rope_dim,
                            scale=scale or width ** -0.5,
                            interpret=interpret,
                            keep=keep)


def _rotated_lanes(x, rope_dim, theta, interleave):
    """x [B, T, H, N + R] -> its R last lanes a head rotated, [B, T, H, R]
    in x's type: ``rope`` on those lanes alone, the only ones lifted to
    float32."""
    b, t, h, d = x.shape
    rot = rope(x[..., d - rope_dim:].reshape(b, t, h * rope_dim), h, theta,
               interleave=interleave)
    return rot.reshape(b, t, h, rope_dim)


@functools.partial(jax.jit, static_argnames=(
    "num_heads", "rope_dim", "theta", "interleave", "inverse", "interpret"))
def _query_pass(x, *, num_heads, rope_dim, theta, interleave, inverse,
                interpret):
    """x [B, T, H (N + R)] -> [B, T, H (N + Rp)]: each head's R last lanes
    rotated by their positions and zero lanes behind them to a whole lane
    row, Rp; under ``inverse`` the transpose, on the cotangent (the
    rotation's transpose on its R lanes, the N others through, the zero
    lanes' unread). ``kernels.latent_query`` where it has blocks
    for the shapes and the step is lowered for the TPU, ``jax.numpy``
    everywhere else; one ``jax.jit`` a signature."""
    from . import kernels

    b, t, _ = x.shape
    pad = -rope_dim % 128
    d = x.shape[2] // num_heads - (pad if inverse else 0)   # N + R

    def composed(x):
        x = x.reshape(b, t, num_heads, -1)
        if inverse:
            turned, = jax.linear_transpose(
                lambda r: _rotated_lanes(r, rope_dim, theta, interleave),
                jax.ShapeDtypeStruct((b, t, num_heads, rope_dim), x.dtype))(
                    x[..., d - rope_dim:d])
            parts = [x[..., :d - rope_dim], turned]
        else:
            parts = [x[..., :d - rope_dim],
                     _rotated_lanes(x, rope_dim, theta, interleave),
                     jnp.zeros((b, t, num_heads, pad), x.dtype)]
        return jnp.concatenate(parts, axis=-1).reshape(b, t, -1)

    if not kernels.latent_query_takes(t, num_heads, d - rope_dim, rope_dim):
        return composed(x)
    return kernels.common.on_tpu(
        functools.partial(
            kernels.latent_query, heads=num_heads, nope=d - rope_dim,
            rope=rope_dim, theta=theta, interleave=interleave,
            inverse=inverse),
        composed, interpret, x)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def _kernel_query(query, num_heads, rope_dim, theta, interleave, interpret):
    """The query as ``latent_flash`` reads it (``_query_pass``): one pass
    that reads it once; the backward is the same pass backwards, no pad
    and no sum of two."""
    return _query_pass(query, num_heads=num_heads, rope_dim=rope_dim,
                       theta=theta, interleave=interleave, inverse=False,
                       interpret=interpret)


def _kernel_query_fwd(query, *static):
    return _kernel_query(query, *static), None


def _kernel_query_bwd(num_heads, rope_dim, theta, interleave, interpret, _,
                      g):
    return (_query_pass(g, num_heads=num_heads, rope_dim=rope_dim,
                        theta=theta, interleave=interleave, inverse=True,
                        interpret=interpret),)


_kernel_query.defvjp(_kernel_query_fwd, _kernel_query_bwd)


# --------------------------------------------------------------------------
# The causal taps' call sites (``mamba2``, ``gated_delta_net``,
# ``short_conv``): which form runs, counted
# --------------------------------------------------------------------------
_M_TAPS_LOWERINGS = _tm.counter(
    "causal_taps.lowerings", "Traces of a causal depthwise convolution's "
    "call site (one per convolved array, node and lowering, nothing per "
    "step); labels: site (mamba2 / gated_delta_net / short_conv), "
    "channels, taps, impl (kernel: the Pallas pair of ops/kernels/taps.py "
    "where the step is lowered for the TPU, the jax.numpy form of the "
    "same signature elsewhere; jnp: causal_taps' shifted multiply-adds "
    "everywhere)")


def _taps_site(site, src, conv_weight, form, offset=0, channels=None):
    """Whether ``kernels.causal_conv`` takes ``channels`` columns of src
    from ``offset`` (``conv_weight``'s by default); the call site counts
    itself here, outside its block's ``jax.jit``."""
    from . import kernels

    taps = conv_weight.shape[0]
    channels = conv_weight.shape[1] if channels is None else channels
    kernel = kernels.taps_takes(channels, src.shape[1], taps, src.dtype,
                                form, offset, src.shape[2])
    _M_TAPS_LOWERINGS.inc(site=site, channels=channels, taps=taps,
                          impl="kernel" if kernel else "jnp")
    return kernel


_M_GATE_NORM_LOWERINGS = _tm.counter(
    "gate_norm.lowerings", "Traces of the gate and grouped RMSNorm of a "
    "Mamba2 or GatedDeltaNet call site (one per node and lowering, nothing "
    "per step); labels: site, groups, width, gate (where not silu), impl "
    "(kernel: the pair of ops/kernels/gate_norm.py where lowered for the TPU, "
    "jax.numpy form of the same signature elsewhere; jnp: the block's "
    "gate_norm closure everywhere)")


def _gate_norm_site(site, form, groups, width, src, core=True, **gated):
    """Whether ``kernels.gated_rms_norm`` takes ``groups`` groups of
    ``width`` columns gated by the first of src's (``core``: whether what
    feeds it is laid out as the kernels read it); the call site counts
    itself here (``gated``: more labels), outside its block's ``jax.jit``."""
    from . import kernels

    kernel = bool(core) and kernels.gate_norm_takes(
        form, groups, width, src.shape[1], src.dtype, 0, src.shape[2])
    _M_GATE_NORM_LOWERINGS.inc(site=site, groups=groups, width=width,
                               impl="kernel" if kernel else "jnp", **gated)
    return kernel


# --------------------------------------------------------------------------
# ScaledSum — sub-layer outputs under fixed scalars (Falcon-H1's
# multipliers): one scaled, or several scaled and summed off one input
# --------------------------------------------------------------------------
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
    )
)


# --------------------------------------------------------------------------
# The delta rule with a decay a CHANNEL (Kimi Delta Attention, Kimi Linear,
# arXiv:2510.26692): ``GatedDeltaNet``'s rule where ``a`` is as wide as
# the keys (down here so that no line above moves: see GatedDeltaNet's note)
# --------------------------------------------------------------------------
KDA_SUB_BLOCK = 16  # tokens a sub-block of a chunk (fla's chunk_kda)


def channel_delta_rule(q, k, v, g, beta, chunk):
    """The delta rule ``S_t = Diag(a_t) S_{t-1} + k_t u_t^T`` with ``u_t =
    beta_t (v_t - (Diag(a_t) S_{t-1})^T k_t)``, ``a_t = exp(g_t)`` a vector
    over the K key channels, ``o_t = S_t^T q_t`` (``S`` [H, K, V] float32,
    zero before the first token) in its chunk form. q and k [B, T, H, K],
    v [B, T, H, V], g [B, T, H, K] (log decay, <= 0) and beta [B, T, H]
    float32 -> o [B, T, H, V] float32.

    With ``b_i`` in R^K the running sum of ``g`` inside a chunk, ``A(x)_ij
    = sum_d x_id k_jd exp(b_id - b_jd)``: the decay sits INSIDE the
    contraction, and the factored ``(x_i exp(b_i)) . (k_j exp(-b_j))``
    raises e to a positive power that overflows float32 after a few
    strongly decayed tokens. So a chunk is cut into sub-blocks of
    ``KDA_SUB_BLOCK`` tokens (one sub-block where that does not divide it). A pair in different sub-blocks factors through the first token
    ``n`` of the later one, ``exp(b_i - b_n)`` and ``exp(b_n - b_j)`` both
    at most 1, and is a product on the MXU (``_decayed_products``); a pair
    in one sub-block is summed from ``exp(b_i - b_j)`` itself, masked
    before the exponential. No exponential of a positive number anywhere.
    Then ``gated_delta_rule``'s steps with a vector where it has a scalar:
    ``L = beta * strict_lower(A(k))``; one unit-triangular system a chunk
    and head, ``(I + L) [W | Y] = [beta v | beta (exp(b) * k)]``; ``M =
    lower(A(q))``; a ``lax.scan`` over the chunks whose carry is the
    state: ``o = M W + (exp(b) * q - M Y) S``, ``u = W - Y S``, ``S' =
    Diag(exp(b_C)) S + (exp(b_C - b) * k)^T u``. Decays, their sums, the
    tables, the solve and the state are float32; the products take
    operands of ``v``'s dtype and accumulate in float32. T is padded to
    whole chunks as ``gated_delta_rule`` pads it. The form for every
    platform but the TPU and for the shapes ``kernels.gdn_takes(...,
    "channel")`` has no tiles for (a head that is not whole lane rows, a
    chunk no sub-block divides); the others are the pair ``kda_fwd_`` /
    ``kda_bwd_`` of ``ops/kernels/gdn.py`` where the step is lowered for
    the TPU (``_channel_delta_block``), held to this form by
    ``tests/test_gated_delta_kernel.py``."""
    f32 = jnp.float32
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    # one sub-block where 16 does not divide the chunk: every pair from the
    # difference itself
    sub = chunk if chunk % KDA_SUB_BLOCK else KDA_SUB_BLOCK
    pad = -t % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    nc = (t + pad) // chunk
    dtype = v.dtype

    def chunks(x):  # [B, T, H, ...] -> [B, nc, H, C, ...]
        return jnp.moveaxis(x.reshape((b, nc, chunk) + x.shape[2:]), 3, 2)

    def dot(spec, lhs, rhs):
        return jnp.einsum(spec, lhs.astype(dtype), rhs.astype(dtype),
                          preferred_element_type=f32)

    q, k, v = chunks(q), chunks(k), chunks(v)
    beta = chunks(beta.astype(f32))[..., None]        # [B, nc, H, C, 1]
    cum = jnp.cumsum(chunks(g.astype(f32)), axis=3)   # b_i [B, nc, H, C, K]
    c = jnp.exp(cum)                                  # decay from the
    to_end = jnp.exp(cum[..., -1:, :] - cum)          # start; to the end
    kk, qk = _decayed_products(q, k, cum, sub, dot)
    solved = jax.scipy.linalg.solve_triangular(
        beta * kk, jnp.concatenate(
            [beta * v.astype(f32), beta * c * k.astype(f32)], axis=-1),
        lower=True, unit_diagonal=True)               # the diagonal unread
    w, y = solved[..., :dv], solved[..., dv:]
    out0 = dot("bchij,bchjv->bchiv", qk, w)
    q_in = c * q.astype(f32) - dot("bchij,bchjd->bchid", qk, y)
    k_out = to_end * k.astype(f32)

    def step(state, at):                              # [B, H, K, V]
        out0, q_in, w, y, k_out, kept = at
        u = w - dot("bhid,bhdv->bhiv", y, state)
        out = out0 + dot("bhid,bhdv->bhiv", q_in, state)
        state = kept[..., None] * state + dot("bhid,bhiv->bhdv", k_out, u)
        return state, out

    _, out = jax.lax.scan(
        step, jnp.zeros((b, h, dk, dv), f32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (
            out0, q_in.astype(dtype), w.astype(dtype), y.astype(dtype),
            k_out.astype(dtype), c[..., -1, :])))
    out = jnp.moveaxis(out, 0, 1)                     # [B, nc, H, C, V]
    return jnp.moveaxis(out, 2, 3).reshape(b, t + pad, h, dv)[:, :t]


def _decayed_products(q, k, cum, sub, dot):
    """``A(k)`` and ``A(q)`` of ``channel_delta_rule``, [B, nc, H, C, C]
    float32, zero above the diagonal: q, k [B, nc, H, C, K], ``cum`` their
    running log decays (float32, falling along C). Sub-block ``I``'s rows
    against the columns of the sub-blocks before it: ``(x_i exp(b_i -
    b_n)) . (k_j exp(b_n - b_j))`` with ``n`` the sub-block's first token,
    one batched product a kind of row over every sub-block but the first;
    the ``sub x sub`` blocks on the diagonal: the sum over K of ``x_id k_jd
    exp(b_id - b_jd)``, the difference masked to ``j <= i`` before the
    exponential (a reduction XLA fuses: nothing [.., sub, sub, K] is
    written)."""
    f32 = jnp.float32
    lead, (chunk, dk) = q.shape[:3], q.shape[3:]
    ns = chunk // sub

    def subs(x):  # [.., C, K] -> [.., ns, sub, K]
        return x.reshape(lead + (ns, sub, dk))

    kf = k.astype(f32)
    qs, ks, cs = subs(q.astype(f32)), subs(kf), subs(cum)
    lower = np.tril(np.ones((sub, sub), bool))[..., None]
    within = jnp.exp(jnp.where(
        lower, cs[..., :, None, :] - cs[..., None, :, :], -jnp.inf))
    cols = ks[..., None, :, :] * within               # k_j exp(b_i - b_j)
    diag = [jnp.sum(x[..., :, None, :] * cols, axis=-1) for x in (ks, qs)]
    if ns == 1:
        return tuple(d.reshape(lead + (chunk, chunk)) for d in diag)
    before = (ns - 1) * sub                           # columns with a later
    first = cs[..., 1:, :1, :]                        # sub-block; b_n
    earlier = (np.arange(before)[None, :]
               < sub * np.arange(1, ns)[:, None])[..., None]
    k_to = kf[..., None, :before, :] * jnp.exp(jnp.where(
        earlier, first - cum[..., None, :before, :], -jnp.inf))
    from_n = jnp.exp(cs[..., 1:, :, :] - first)       # [.., ns - 1, sub, K]
    own = np.eye(ns, dtype=np.float32)[:, None, :, None]
    out = []
    for x, d in zip((ks, qs), diag):
        off = dot("bchnid,bchnjd->bchnij", x[..., 1:, :, :] * from_n, k_to)
        full = jnp.pad(off.reshape(lead + (before, before)),
                       ((0, 0),) * 3 + ((sub, 0), (0, sub)))
        blocks = d[..., :, :, None, :] * own          # block-diagonal
        out.append(full + blocks.reshape(lead + (chunk, chunk)))
    return tuple(out)


@functools.partial(jax.jit, static_argnames=(
    "heads", "chunk", "eps", "beta_scale", "remat", "taps_kernel",
    "interpret", "gate_act", "norm_kernel"))
def _channel_delta_block(query, key, value, gate, a, b, conv_weight, a_log,
                         dt_bias, norm_gamma, *, heads, chunk, eps,
                         beta_scale, remat, taps_kernel, interpret,
                         gate_act, norm_kernel):
    """``gated_delta_net`` with a decay a channel where the rule's kernel
    pair has tiles (``kernels.gdn_takes(..., "channel")``), one signature:
    ``_gated_delta_block``'s three scopes with ``delta_rule`` ONE call,
    ``kernels.channel_delta_net``, from the convolution's outputs to ``o``
    [B, T, H V]. The unit norms, the decays and their running sums are
    made in VMEM, a chunk at a time, and again in the backward kernel:
    under ``remat`` nothing of the scope is computed twice but the write
    strengths, and no array [B, T, H, K] exists between the taps' pair and
    the norm (as a reshape of [B, T, H K] it is a move on the TPU: a tile
    is eight heads of a token there and eight tokens of a head here).
    ``gate_norm`` reads ``o`` where the pair wrote it, token-major, a head
    a lane row: ``kernels.gated_rms_norm`` (``token_major``, the gate's
    activation ``gate_act``) where ``norm_kernel`` (``gate_norm_takes``
    has tiles), with no checkpoint round it (its backward kernel is the
    recomputation), the closure for the shapes the pair refuses.
    Off the TPU the calls are the ``jax.numpy`` forms on the same values.
    (Down here so that no line of ``_gated_delta_block`` moves: the scalar
    pair's call sits in it.)"""
    from . import kernels

    f32 = jnp.float32
    bsz, t, _ = query.shape
    dk, dv = query.shape[2] // heads, value.shape[2] // heads

    def again(f):
        return jax.checkpoint(f) if remat else f

    def conv1d(x, w, takes):
        if takes:
            return kernels.causal_conv(x, w, form="silu",
                                       interpret=interpret)
        return again(lambda x, w: jax.nn.silu(
            causal_taps(x, w)).astype(x.dtype))(x, w)

    def gate_norm(o, gate, norm_gamma):
        o = o.reshape(bsz, t, heads, dv)
        var = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
        normed = o * jax.lax.rsqrt(var + eps) * norm_gamma.astype(f32)
        gated = normed.reshape(bsz, t, heads * dv) * getattr(
            jax.nn, gate_act)(gate.astype(f32))
        return gated.astype(gate.dtype)

    with jax.named_scope("conv1d"):
        edges = (0, heads * dk, 2 * heads * dk, 2 * heads * dk + heads * dv)
        q, k, v = (conv1d(x, conv_weight[:, lo:hi], takes)
                   for x, lo, hi, takes in zip((query, key, value), edges,
                                               edges[1:], taps_kernel))
    with jax.named_scope("delta_rule"):
        beta = again(lambda b: beta_scale * jax.nn.sigmoid(b.astype(f32)))(b)
        o = kernels.channel_delta_net(q, k, v, a, beta, a_log, dt_bias,
                                      chunk, interpret=interpret)
    with jax.named_scope("gate_norm"):
        if norm_kernel:
            return kernels.gated_rms_norm(o, gate, norm_gamma, eps=eps,
                                          form="token_major", groups=heads,
                                          act=gate_act, interpret=interpret)
        return again(gate_norm)(o, gate, norm_gamma)


# --------------------------------------------------------------------------
# KeyIndexer — attention that chooses its keys: a light many-head scorer
# over one key a token and an exact top-k a query row, as a keep-mask for
# ``LatentAttention(with_keep=True)`` (DeepSeek-V3.2-Exp's lightning indexer;
# down here so that no line above moves)
# --------------------------------------------------------------------------
_M_INDEX_LOWERINGS = _tm.counter(
    "attention.index_lowerings", "Traces of a KeyIndexer call site (one per "
    "lowering, nothing per step); labels: heads, width (a head's and the "
    "one key's), topk, rows (query rows a block of the scores), impl (jnp: "
    "blocked jax.numpy scores and a bisection on the scores' bits, on "
    "every platform)")

INDEX_BLOCK_ROWS = 256   # query rows a block of [heads, rows, keys] scores


def _sortable_bits(x):
    """float32 -> uint32 of the same order (negative values reversed
    under the others, -0.0 under +0.0)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >> np.uint32(31) == 0, bits | np.uint32(1 << 31),
                     ~bits)


def keep_top_k(scores, k):
    """scores [..., T, S] (read as float32) -> bool of the same shape: in
    each row its ``k`` largest entries (all of them where S <= k), ties to the
    lower index: ``jax.lax.top_k``'s choice without its sort. The k-th
    largest value of a row is found bit by bit (32 counting passes over
    the scores' order-preserving bits); only a row that holds its k-th
    value more than once pays the running count that breaks the tie."""
    if scores.shape[-1] <= k:
        return jnp.ones(scores.shape, bool)
    u = _sortable_bits(scores.astype(jnp.float32))

    def count(mask):
        return jnp.sum(mask, axis=-1, keepdims=True, dtype=jnp.int32)

    def step(i, cur):
        cand = cur | (np.uint32(1 << 31) >> i.astype(jnp.uint32))
        return jnp.where(count(u >= cand) >= k, cand, cur)

    kth = jax.lax.fori_loop(0, 32, step,
                            jnp.zeros(u.shape[:-1] + (1,), jnp.uint32))
    above = u > kth
    # entries of the k-th value a row may still take, lowest index first
    room = k - count(above)
    tied = u == kth

    def by_index():
        return above | (tied & (jnp.cumsum(tied, axis=-1, dtype=jnp.int32)
                                <= room))

    return jax.lax.cond(jnp.any(count(tied) > room), by_index,
                        lambda: above | tied)


def index_scores(q, k, w, rows=INDEX_BLOCK_ROWS):
    """``I[b, t, s] = sum_j w[b, t, j] relu(q[b, t, j] . k[b, s])`` for s
    <= t, -inf past the diagonal: q [B, T, H, D] and k [B, T, D] in the
    operands' type (the products accumulate in float32), w [B, T, H]
    float32 -> [B, T, T] float32. A block of ``rows`` query rows at a time
    against the keys up to its last row: no [H, T, T] array exists."""
    b, t, h, d = q.shape
    out = []
    for lo in range(0, t, rows):
        hi = min(lo + rows, t)
        s = jnp.einsum("bthd,bsd->bths", q[:, lo:hi], k[:, :hi],
                       preferred_element_type=jnp.float32)
        s = jnp.einsum("bths,bth->bts", jax.nn.relu(s), w[:, lo:hi])
        live = (np.arange(hi)[None, :] <= np.arange(lo, hi)[:, None])
        s = jnp.where(live[None], s, -jnp.inf)
        out.append(jnp.pad(s, ((0, 0), (0, 0), (0, t - hi)),
                           constant_values=-np.inf))
    return jnp.concatenate(out, axis=1) if len(out) > 1 else out[0]


def layer_norm(x, gamma, beta, eps):
    """LayerNorm over the last axis: statistics float32, the normalised
    value cast to ``x``'s dtype before scale and shift (``rms_norm``'s
    order)."""
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    normed = ((x32 - mean) * jax.lax.rsqrt(var + eps)).astype(x.dtype)
    return gamma.astype(x.dtype) * normed + beta.astype(x.dtype)


def key_indexer(query_latent, data, q_weight, k_weight, k_gamma, k_beta,
                head_weight, num_heads, rope_dim, topk, theta, eps=1e-6):
    """query_latent [B, T, Lq] (the normed query latent), data [B, T, D]
    (the block's normed input), q_weight [H W, Lq], k_weight [W, D],
    k_gamma and k_beta [W], head_weight [H, D] -> (keep [B, T, T] int8,
    count [B] float32).

    ``qI_j = q_weight_j query_latent`` for H heads of W; ``kI =
    LayerNorm(k_weight data)``, ONE key a token; RoPE (rotate-half pairs)
    on the first ``rope_dim`` dimensions of both; ``w = (H^-0.5 W^-0.5)
    head_weight data``; ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``
    for s <= t; row t keeps its ``min(t + 1, topk)`` keys of largest I,
    ties to the lower index. ``count`` is the pairs kept a batch row
    (``sum_t min(t + 1, topk)``: exact). Nothing here has a gradient:
    every input is read behind ``stop_gradient`` (the published model
    trains the scorer by a loss of its own). The two products take
    operands of ``data``'s type and accumulate in float32; ReLU, the
    weights, the sum over the heads and the compare are float32."""
    (query_latent, data, q_weight, k_weight, k_gamma, k_beta,
     head_weight) = (jax.lax.stop_gradient(x) for x in (
         query_latent, data, q_weight, k_weight, k_gamma, k_beta,
         head_weight))
    b, t, _ = data.shape
    width = k_weight.shape[0]
    dtype = data.dtype
    rows = min(INDEX_BLOCK_ROWS, t)
    _M_INDEX_LOWERINGS.inc(heads=num_heads, width=width, topk=topk,
                           rows=rows, impl="jnp")

    def project(x, weight):
        return jax.lax.dot_general(
            x, weight.astype(x.dtype), (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    with jax.named_scope("index"):
        q = rope(project(query_latent, q_weight).astype(dtype), num_heads,
                 theta, rope_dim)
        k = rope(layer_norm(project(data, k_weight).astype(dtype), k_gamma,
                            k_beta, eps), 1, theta, rope_dim)
        w = project(data, head_weight) * np.float32(
            num_heads ** -0.5 * width ** -0.5)
        scores = index_scores(q.reshape(b, t, num_heads, width), k, w, rows)
        with jax.named_scope("topk"):
            keep = keep_top_k(scores, topk) & (scores > -jnp.inf)
            count = jnp.sum(keep, axis=(1, 2), dtype=jnp.int32)
    return keep.astype(jnp.int8), count.astype(jnp.float32)


def _key_indexer(attrs, ins, is_train):
    return list(key_indexer(
        *ins, num_heads=int(attrs["num_heads"]),
        rope_dim=int(attrs["rope_dim"]), topk=int(attrs["topk"]),
        theta=float(attrs.get("theta", 10000.0)),
        eps=float(attrs.get("eps", 1e-6))))


def _key_indexer_infer(attrs, in_shapes):
    heads, width = int(attrs["num_heads"]), int(attrs["head_dim"])
    latent = _known(in_shapes[0], "KeyIndexer")
    data = _known(in_shapes[1], "KeyIndexer")
    if len(data) != 3 or len(latent) != 3 or latent[:2] != data[:2]:
        raise ValueError(
            "KeyIndexer: query_latent %s and data %s must be [batch, time, "
            "width] over the same positions" % (latent, data))
    if heads <= 0 or width <= 0 or int(attrs["topk"]) <= 0:
        raise ValueError("KeyIndexer: num_heads, head_dim and topk must be "
                         "set (> 0)")
    _check_rotation("KeyIndexer", width, int(attrs["rope_dim"]), 0)
    b, t, d = data
    return ([latent, data, (heads * width, latent[2]), (width, d), (width,),
             (width,), (heads, d)], [(b, t, t), (b,)], [])


def _key_indexer_infer_type(attrs, in_types):
    known = [t for t in in_types if t is not None]
    if not known:
        raise MXNetError("KeyIndexer: cannot infer type")
    return ([known[0] if x is None else x for x in in_types],
            [np.int8, np.float32], [])


register(
    OpDef(
        "_contrib_KeyIndexer",
        _key_indexer,
        arguments=("query_latent", "data", "q_weight", "k_weight", "k_gamma",
                   "k_beta", "head_weight"),
        outputs=("keep", "count"),
        defaults={"num_heads": 1, "head_dim": 0, "rope_dim": 0, "topk": 0,
                  "theta": 10000.0, "eps": 1e-6},
        infer_shape=_key_indexer_infer,
        infer_type=_key_indexer_infer_type,
        aliases=("KeyIndexer",),
    )
)


# --------------------------------------------------------------------------
# ExitMix — a looped language model's exit distribution over its passes and
# the loss it weights (Zhu et al., "Scaling Latent Reasoning via Looped
# Language Models", arXiv:2510.25741, stage I): down here so that no line
# above moves
# --------------------------------------------------------------------------
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
    )
)


# --------------------------------------------------------------------------
# RoPE's one-pass form (``rope`` chooses; down here so that no line above
# moves)
# --------------------------------------------------------------------------
_M_ROPE_LOWERINGS = _tm.counter(
    "rope.lowerings", "Traces of a rope call site (one per lowering, "
    "nothing per step); labels: heads, head_dim, form (one_pass: a whole "
    "head of whole lane rows rotated in one pass each way, "
    "ops/kernels/rope.py; halves: the two halves computed apart and "
    "concatenated)")


def _rope_tables(t, r, theta, interleave=False):
    """cos and sin of positions 0..t-1 times pair i's frequency
    ``theta^(-2i/r)`` (a tuple ``theta``: YaRN's blended frequencies,
    ``kernels.common.rope_inv_freq``), float64 [t, r/2]; under
    ``interleave`` [t, r], a pair's two lanes sharing its angle."""
    from .kernels.common import rope_inv_freq

    angles = (np.arange(t, dtype=np.float64)[:, None]
              * rope_inv_freq(theta, r)[None, :])
    if interleave:
        angles = np.repeat(angles, 2, axis=-1)
    return np.cos(angles), np.sin(angles)


def _takes_one_pass(x, num_heads, r, offset, interleave):
    """Whether a ``rope`` call is a whole head's rotation over whole lane
    rows that ``kernels.rope_rows`` has a tile for, read off the call's
    own arguments (and, as ``Embedding``'s rule, not in a program the
    partitioner splits: the kernels have no partitioning rule); counts
    the call site."""
    from . import kernels

    d = x.shape[2] // num_heads
    one_pass = (not interleave and offset == 0 and r == d
                and kernels.rope_rows(num_heads, d, x.shape[1],
                                      x.dtype) is not None
                and not kernels.common.trace_is_partitioned())
    _M_ROPE_LOWERINGS.inc(heads=num_heads, head_dim=d,
                          form="one_pass" if one_pass else "halves")
    return one_pass


@functools.lru_cache(maxsize=None)
def _whole_head_tables(t, d, theta):
    """``[cos | cos]`` and ``[-sin | sin]``, float32 [t, d]: one pair of
    arrays a shape, so that a program's call sites share two constants."""
    cos, sin = _rope_tables(t, d, theta)
    return (np.concatenate([cos, cos], axis=-1).astype(np.float32),
            np.concatenate([-sin, sin], axis=-1).astype(np.float32))


def _rotate_whole_heads(x, num_heads, theta):
    """``rope``'s one-pass form: ``kernels.rotate_heads`` on the tables."""
    from . import kernels

    c, s = _whole_head_tables(x.shape[1], x.shape[2] // num_heads, theta)
    return kernels.rotate_heads(x, c, s, num_heads,
                                interpret=kernels.common.INTERPRET)


# --------------------------------------------------------------------------
# HyperCoeff / HyperMix — manifold-constrained hyper-connections (Xie et
# al., mHC, arXiv:2512.24880, on Zhu et al., Hyper-Connections,
# arXiv:2409.19606): n residual streams a token, read through a learned
# row, written through a learned column and carried through a learned,
# doubly stochastic matrix, all three a function of the token (down here so
# that no line above moves)
#
# Layout, which is what decides the cost on the chip: the stream is
# [tokens, n C], stream j the lane-aligned columns j C .. (j + 1) C - 1, so
# that no array has the n streams as a minor dimension (a bf16 [.., 4, C]
# pads 4 to a 16-row tile); every coefficient array has the TOKENS on its
# last axis ([n, tokens], [n, n, tokens]: a [tokens, 4] pads 4 lanes to
# 128).
# --------------------------------------------------------------------------
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
    from . import kernels

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
    from . import kernels

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
    from . import kernels

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
    from . import kernels

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
    data = _known(in_shapes[0], "HyperCoeff")
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
    known = [t for t in in_types[:2] if t is not None]
    if not known:
        raise MXNetError("HyperCoeff: cannot infer type")
    return ([known[0], known[0]] + [
        np.float32 if t is None else t for t in in_types[2:]],
        [np.float32] * 4 + [known[0]] * 2, [])


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
    )
)


def _hyper_mix(attrs, ins, is_train):
    """The write of all n streams is one kernel pass each way where
    ``_takes_one_stream_pass`` says so; every other mixing ``hyper_mix``."""
    from . import kernels

    if (len(ins) == 4 and ins[1].shape[0] == ins[1].shape[1]
            and _takes_one_stream_pass("write", ins[0], ins[1].shape[1])):
        x, res, y, post = ins
        with jax.named_scope("hc_mix"):
            return [kernels.stream_write(
                x, res, y, post, interpret=kernels.common.INTERPRET)]
    return [hyper_mix(*ins)]


def _hyper_mix_infer(attrs, in_shapes):
    data = _known(in_shapes[0], "HyperMix")
    mix = _known(in_shapes[1], "HyperMix")
    if (len(data) != 2 or len(mix) != 3 or mix[2] != data[0]
            or data[1] % mix[1]):
        raise ValueError("HyperMix: data %s [tokens, n x hidden] under mix "
                         "%s [m, n, tokens]" % (data, mix))
    c = data[1] // mix[1]
    added = [(data[0], c), (mix[0], data[0])] if len(in_shapes) > 2 else []
    return [data, mix] + added, [(data[0], mix[0] * c)], []


def _hyper_mix_infer_type(attrs, in_types):
    known = [t for t in (in_types[0],) + tuple(in_types[2:3])
             if t is not None]
    if not known:
        raise MXNetError("HyperMix: cannot infer type")
    t = known[0]
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
)
_hyper_mix_op.list_arguments = lambda attrs=None: (
    ["data", "mix"] + (["add", "add_mix"]
                       if attrs and attrs.get("with_add") else []))
register(_hyper_mix_op)
