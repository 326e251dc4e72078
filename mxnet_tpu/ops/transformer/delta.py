"""``GatedDeltaNet``: a linear-attention mixer's core between its
projections: the causal taps, the gated delta rule (Yang, Kautz &
Hatamizadeh, arXiv:2412.06464; write strengths up to 2, Grazzi et al.,
arXiv:2411.12537) with a decay a head or a key CHANNEL (Kimi Delta
Attention, arXiv:2510.26692), and the norm behind a gate; one ``jax.jit`` a
signature. Kernel families, where lowered for the TPU and the shapes have
tiles: ``ops/kernels/taps.py``, ``ops/kernels/gdn.py`` (``gdn_`` and ``kda_``;
the rules here are their chunk forms) and ``ops/kernels/gate_norm.py``."""
from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np

from ... import telemetry as _tm
from ..registry import OpDef, register
from ..utils import head_width, required_shape
from .taps import _gate_norm_site, _taps_site, again, causal_taps


_M_LINEAR_ATTN_LOWERINGS = _tm.counter(
    "linear_attn.lowerings", "Traces of a GatedDeltaNet call site (one per "
    "lowering, nothing per step); labels: heads, key_dim, value_dim (a "
    "head's widths), chunk (tokens a chunk of the delta rule), conv (the "
    "convolution's taps), impl (kernel: the Pallas pair where the step is "
    "lowered for the TPU, the chunk form elsewhere; chunked: the jax.numpy "
    "chunk form everywhere), decay=channel (gdn.py's kda_ pair), gate, "
    "beta_scale=2 (a channel call whose write strengths are 2 sigmoid)")


def _in_chunks(q, k, v, g, beta, chunk):
    """What both rules' chunk forms start from: T padded to whole chunks, the
    five arrays [B, T, H, ...] -> [B, nc, H, C, ...] (``g`` and ``beta``
    float32, ``beta`` [.., C, 1]), ``dot`` (operands of ``v``'s dtype, float32
    sums) and ``finish`` (the scan's [nc, B, H, C, V] -> [B, T, H, V])."""
    f32 = jnp.float32
    b, t, h, _ = q.shape
    pad = -t % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    nc = (t + pad) // chunk
    dtype = v.dtype

    def chunks(x):
        return jnp.moveaxis(x.reshape((b, nc, chunk) + x.shape[2:]), 3, 2)

    def dot(spec, lhs, rhs):
        return jnp.einsum(spec, lhs.astype(dtype), rhs.astype(dtype),
                          preferred_element_type=f32)

    def finish(out):
        out = jnp.moveaxis(out, 0, 1)                 # [B, nc, H, C, V]
        return jnp.moveaxis(out, 2, 3).reshape(b, t + pad, h, -1)[:, :t]

    q, k, v = chunks(q), chunks(k), chunks(v)
    beta = chunks(beta.astype(f32))[..., None]
    return q, k, v, chunks(g.astype(f32)), beta, dot, finish


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
    f32, dtype, dk, dv = jnp.float32, v.dtype, q.shape[-1], v.shape[-1]
    q, k, v, g, beta, dot, finish = _in_chunks(q, k, v, g, beta, chunk)
    cum = jnp.cumsum(g, axis=-1)                      # b_i
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

    return finish(jax.lax.scan(
        step, jnp.zeros((k.shape[0], k.shape[2], dk, dv), f32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (
            out0, q_in.astype(dtype), w.astype(dtype), y.astype(dtype),
            k_out.astype(dtype), jnp.exp(cum[..., -1]))))[1])


KDA_SUB_BLOCK = 16  # tokens a sub-block of a chunk (fla's chunk_kda)


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
    ``KDA_SUB_BLOCK`` tokens (one where that does not divide it). A pair in
    different sub-blocks factors through the first token ``n`` of the later
    one, ``exp(b_i - b_n)`` and ``exp(b_n - b_j)`` both at most 1, and is a
    product on the MXU (``_decayed_products``); a pair in one sub-block is
    summed from ``exp(b_i - b_j)`` itself, masked before the exponential.
    No exponential of a positive number anywhere.
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
    # one sub-block where 16 does not divide the chunk: every pair from the
    # difference itself
    sub = chunk if chunk % KDA_SUB_BLOCK else KDA_SUB_BLOCK
    f32, dtype, dk, dv = jnp.float32, v.dtype, q.shape[-1], v.shape[-1]
    q, k, v, g, beta, dot, finish = _in_chunks(q, k, v, g, beta, chunk)
    cum = jnp.cumsum(g, axis=3)                       # b_i [B, nc, H, C, K]
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

    return finish(jax.lax.scan(
        step, jnp.zeros((k.shape[0], k.shape[2], dk, dv), f32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (
            out0, q_in.astype(dtype), w.astype(dtype), y.astype(dtype),
            k_out.astype(dtype), c[..., -1, :])))[1])


def _convolved(query, key, value, conv_weight, taps_kernel, remat,
               interpret):
    """Both blocks' scope ``conv1d``: ``silu(causal_taps(.))`` of each of the
    three arrays under its own columns of ``conv_weight`` (``query | key |
    value``), in its type: the taps' kernel pair where ``taps_kernel`` says
    so, the plain form (again in the backward under ``remat``) elsewhere."""
    from .. import kernels

    edges = list(itertools.accumulate(
        (x.shape[2] for x in (query, key, value)), initial=0))
    return tuple(
        kernels.causal_conv(x, conv_weight[:, lo:hi], form="silu",
                            interpret=interpret) if takes
        else again(lambda x, w: jax.nn.silu(causal_taps(x, w)).astype(
            x.dtype), remat)(x, conv_weight[:, lo:hi])
        for x, lo, hi, takes in zip((query, key, value), edges, edges[1:],
                                    taps_kernel))


def _norm_then_gate(o, gate, norm_gamma, *, heads, eps, gate_act):
    """Both blocks' ``gate_norm`` in ``jax.numpy``: ``RMSNorm(o) norm_gamma
    act(gate)`` with the statistics over each head's V columns; o float32
    [B, T, H, V] or [B, T, H V], the result ``gate``'s type."""
    f32 = jnp.float32
    bsz, t, _ = gate.shape
    o = o.reshape(bsz, t, heads, -1)
    var = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
    normed = o * jax.lax.rsqrt(var + eps) * norm_gamma.astype(f32)
    gated = normed.reshape(bsz, t, -1) * getattr(
        jax.nn, gate_act)(gate.astype(f32))
    return gated.astype(gate.dtype)


@functools.partial(jax.jit, static_argnames=(
    "heads", "chunk", "eps", "beta_scale", "remat", "kernel", "taps_kernel",
    "interpret", "norm_kernel", "gate_act"))
def _gated_delta_block(query, key, value, gate, a, b, conv_weight, a_log,
                       dt_bias, norm_gamma, *, heads, chunk, eps,
                       beta_scale, remat, kernel, taps_kernel, interpret,
                       norm_kernel=False, gate_act="silu"):
    """``gated_delta_net`` for one signature (``a`` [B, T, H K]: the
    channel form)."""
    from .. import kernels

    f32 = jnp.float32
    bsz, t, _ = query.shape
    dk, dv = query.shape[2] // heads, value.shape[2] // heads
    channel = a.shape[2] != heads

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

    with jax.named_scope("conv1d"):
        q, k, v = _convolved(query, key, value, conv_weight, taps_kernel,
                             remat, interpret)
    with jax.named_scope("delta_rule"):
        if kernel:
            q, k, g, beta = again(unit_and_strengths, remat)(
                q, k, a, b, a_log, dt_bias)
            o = kernels.gated_delta_rule(
                q, k, v.reshape(bsz, t, heads, dv), g, beta, chunk,
                interpret=interpret, head_major=norm_kernel)
        else:
            o = again(delta_rule, remat)(q, k, v, a, b, a_log, dt_bias)
    with jax.named_scope("gate_norm"):
        if norm_kernel:
            return kernels.gated_rms_norm(o, gate, norm_gamma, eps=eps,
                                          form="norm_first",
                                          interpret=interpret)
        return again(functools.partial(
            _norm_then_gate, heads=heads, eps=eps, gate_act=gate_act),
                     remat)(o, gate, norm_gamma)


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
    recomputation), ``_norm_then_gate`` for the shapes the pair refuses.
    Off the TPU the calls are the ``jax.numpy`` forms on the same values."""
    from .. import kernels

    f32 = jnp.float32

    with jax.named_scope("conv1d"):
        q, k, v = _convolved(query, key, value, conv_weight, taps_kernel,
                             remat, interpret)
    with jax.named_scope("delta_rule"):
        beta = again(lambda b: beta_scale * jax.nn.sigmoid(b.astype(f32)),
                     remat)(b)
        o = kernels.channel_delta_net(q, k, v, a, beta, a_log, dt_bias,
                                      chunk, interpret=interpret)
    with jax.named_scope("gate_norm"):
        if norm_kernel:
            return kernels.gated_rms_norm(o, gate, norm_gamma, eps=eps,
                                          form="token_major", groups=heads,
                                          act=gate_act, interpret=interpret)
        return again(functools.partial(
            _norm_then_gate, heads=heads, eps=eps, gate_act=gate_act),
                     remat)(o, gate, norm_gamma)


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
    channel and the rule ``channel_delta_rule``. Where
    ``kernels.gdn_takes(..., "channel")`` has tiles (a head whole lane
    rows) the block is ``_channel_delta_block``: the pair ``kda_fwd_`` /
    ``kda_bwd_`` from the taps' outputs on, the unit norms and decays made
    in VMEM, and the gate and norm ``gated_rms_norm`` (``token_major``)
    on ``o`` as that pair wrote it. ``gate_act="sigmoid"``: the gate behind
    the norm is a sigmoid. Both are counted where they are not the default.

    The call site counts itself here (``linear_attn.lowerings``,
    ``gate_norm.lowerings``, ``causal_taps.lowerings`` once a convolved
    array); the block is ONE ``jax.jit`` for every node of a signature."""
    from .. import kernels

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
    operands = (query, key, value, gate, a, b, conv_weight, a_log, dt_bias,
                norm_gamma)
    signature = dict(
        heads=int(num_heads), chunk=int(chunk_size), eps=float(eps),
        beta_scale=2.0 if allow_neg_eigval else 1.0, remat=bool(remat),
        taps_kernel=taps_kernel, interpret=kernels.common.INTERPRET,
        norm_kernel=norm_kernel, gate_act=gate_act)
    if channel and kernel:  # the rule's pair from the taps' outputs on
        return _channel_delta_block(*operands, **signature)
    return _gated_delta_block(*operands, kernel=kernel, **signature)


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
    q = required_shape(in_shapes[0], "GatedDeltaNet")
    v = required_shape(in_shapes[2], "GatedDeltaNet")
    dk = head_width("GatedDeltaNet", "query", q, heads)
    dv = head_width("GatedDeltaNet", "value", v, heads)
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
        op_class="gdn",
    )
)
