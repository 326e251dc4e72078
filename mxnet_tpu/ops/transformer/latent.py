"""``LatentAttention`` (MLA, DeepSeek-V2/V3): causal attention whose
keys and values are projected up from one normalised latent a token, over
every causal key, a window of them or the keys a keep-mask chooses; and
``KeyIndexer`` (DeepSeek-V3.2-Exp's lightning indexer), the light
many-head scorer whose exact top-k a query row is that mask (the scores
``jax.numpy`` on every platform, the choice ``kernels.top_k_mask``). The
attention is the kernel family
``ops/kernels/latent.py`` (a flash pair of two key operands, a pass over the
query) where ``kernels.latent_flash_takes`` admits the shapes, the
concatenated key through ``kernels.attention`` elsewhere."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ... import telemetry as _tm
from ..registry import OpDef, register
from ..utils import (
    check_rotation, first_type, head_width, optional_inputs, required_shape,
    types_beside_keep)
from .attention import gate_output
from .norm import layer_norm, rms_norm
from .rotary import rope


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
    from .. import kernels

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


def _latent_composed_path(query, kv, k_rope, num_heads, v_head_dim, theta,
                          interleave, rotary=True, window=0, keep=None,
                          scale=0.0):
    """Every head's key materialised: the rotation over the whole query,
    the shared rotary key broadcast and concatenated behind each head's
    slice of ``kv`` [B, T, H (N + Dv)], the values sliced out of it, and
    ``kernels.attention`` (the flash kernel on the TPU at T >= 128,
    the materialised reference elsewhere; its band under ``window``), or
    under ``keep`` the materialised ``kernels.kept_attention``."""
    from ..kernels import attention, kept_attention

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
    from ..kernels import common, latent_flash

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
    (``_latent_kernel_path``: the flash pair of two key operands
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
    ``composed`` the materialised ``kernels.kept_attention``, both
    under the scope ``select``. ``gate`` [B, T, H]: head h's output times
    ``sigmoid(gate[.., h])``, float32, one rounding (scope ``gate``).

    ``score_scale`` > 0 takes the place of ``1 / sqrt(N + R)`` on the
    scores (a scaled RoPE's ``mscale^2`` folded in by the model), through
    both forms. ``rope_scaling`` = ``(factor, beta_fast, beta_slow,
    original_max_position)``: both rotations turn by YaRN's blended
    frequencies (``kernels.common.rope_inv_freq``), a static table that
    takes ``theta``'s place wherever it goes; cos and sin are not scaled.
    At the defaults neither is read and the program is what it was."""
    from ..kernels import latent_flash_takes

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
    optional = dict(zip(optional_inputs(attrs, _LATENT_OPTIONAL),
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
    q = required_shape(in_shapes[0], "LatentAttention")
    latent = required_shape(in_shapes[1], "LatentAttention")
    d = head_width("LatentAttention", "query", q, heads)
    check_rotation("LatentAttention", d, r, d - r)
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
               for name in optional_inputs(attrs, _LATENT_OPTIONAL)],
            [q[:2] + (heads * dv,)], [])


def _latent_attention_infer_type(attrs, in_types):
    """The keep-mask has a type of its own (int8, ``KeyIndexer``'s);
    every other input and the output share the query's."""
    types, t = types_beside_keep(
        "LatentAttention",
        ["query", "latent", "latent_gamma", "up_weight"]
        + optional_inputs(attrs, _LATENT_OPTIONAL), in_types)
    return types, [t], []


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
    op_class="attn",
)
_latent_op.list_arguments = lambda attrs=None: (
    ["query", "latent", "latent_gamma", "up_weight"]
    + optional_inputs(attrs, _LATENT_OPTIONAL))
register(_latent_op)


_M_INDEX_LOWERINGS = _tm.counter(
    "attention.index_lowerings", "Traces of a KeyIndexer call site (one per "
    "lowering, nothing per step); labels: heads, width (a head's and the "
    "one key's), topk, rows (query rows a block of the scores), impl (how "
    "the keys are chosen among the blocked jax.numpy scores; pallas: the "
    "shapes have a row block in kernels.topk, the kernel where the step is "
    "lowered for the TPU; jnp: the bisection on the scores' bits in "
    "jax.numpy on every platform)")

INDEX_BLOCK_ROWS = 256   # query rows a block of [heads, rows, keys] scores


def keep_top_k(scores, k, live=False, causal=False):
    """scores [..., T, S] (read as float32) -> bool of the same shape: in
    each row its ``k`` largest entries (all of them where S <= k), ties to the
    lower index: ``jax.lax.top_k``'s choice without its sort. The k-th
    largest value of a row is found bit by bit (32 counting passes over
    the scores' order-preserving bits); only a row that holds its k-th
    value more than once pays the running count that breaks the tie.
    Under ``live`` an -inf is never kept (a row keeps at most its entries
    over -inf); ``causal`` is the caller's word that row t holds -inf past
    column t, which lets the kernel stop a row block's passes there.
    ``kernels.top_k_mask`` makes the choice: on a row block held in VMEM
    where it has one for the shape and the step is lowered for the TPU."""
    from .. import kernels

    keep, _ = kernels.top_k_mask(scores.astype(jnp.float32), k, live, causal,
                                 interpret=kernels.common.INTERPRET)
    return keep.astype(bool)


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
    from .. import kernels

    (query_latent, data, q_weight, k_weight, k_gamma, k_beta,
     head_weight) = (jax.lax.stop_gradient(x) for x in (
         query_latent, data, q_weight, k_weight, k_gamma, k_beta,
         head_weight))
    b, t, _ = data.shape
    width = k_weight.shape[0]
    dtype = data.dtype
    rows = min(INDEX_BLOCK_ROWS, t)
    _M_INDEX_LOWERINGS.inc(
        heads=num_heads, width=width, topk=topk, rows=rows,
        impl="pallas" if kernels.top_k_rows((b, t, t), topk) else "jnp")

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
            keep, kept = kernels.top_k_mask(
                scores, topk, live=True, causal=True,
                interpret=kernels.common.INTERPRET)
            count = jnp.sum(kept, axis=(1, 2), dtype=jnp.int32)
    return keep, count.astype(jnp.float32)


def _key_indexer(attrs, ins, is_train):
    return list(key_indexer(
        *ins, num_heads=int(attrs["num_heads"]),
        rope_dim=int(attrs["rope_dim"]), topk=int(attrs["topk"]),
        theta=float(attrs.get("theta", 10000.0)),
        eps=float(attrs.get("eps", 1e-6))))


def _key_indexer_infer(attrs, in_shapes):
    heads, width = int(attrs["num_heads"]), int(attrs["head_dim"])
    latent = required_shape(in_shapes[0], "KeyIndexer")
    data = required_shape(in_shapes[1], "KeyIndexer")
    if len(data) != 3 or len(latent) != 3 or latent[:2] != data[:2]:
        raise ValueError(
            "KeyIndexer: query_latent %s and data %s must be [batch, time, "
            "width] over the same positions" % (latent, data))
    if heads <= 0 or width <= 0 or int(attrs["topk"]) <= 0:
        raise ValueError("KeyIndexer: num_heads, head_dim and topk must be "
                         "set (> 0)")
    check_rotation("KeyIndexer", width, int(attrs["rope_dim"]), 0)
    b, t, d = data
    return ([latent, data, (heads * width, latent[2]), (width, d), (width,),
             (width,), (heads, d)], [(b, t, t), (b,)], [])


def _key_indexer_infer_type(attrs, in_types):
    t = first_type("KeyIndexer", in_types)
    return ([t if x is None else x for x in in_types],
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
        op_class="attn",
    )
)
