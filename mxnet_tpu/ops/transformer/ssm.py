"""``Mamba2``: the state-space mixer's core between its two projections
(Mamba-2 / SSD, Dao & Gu, arXiv:2405.21060): the causal taps, the chunked
scan and the gated grouped norm, one ``jax.jit`` a signature. Kernel
families, where lowered for the TPU and the shapes have tiles:
``ops/kernels/taps.py``, ``ops/kernels/ssd.py`` (``ssd_scan`` here is the
pair's einsum form and oracle) and ``ops/kernels/gate_norm.py``.
``Mamba1`` (Mamba, Gu & Dao, arXiv:2312.00752): the taps, the two small
projections, the selective scan (a decay a channel AND a state index:
``selective_scan`` here is the ``jax.numpy`` form and oracle of
``ops/kernels/sscan.py``) and the gate, with the scan's output before the
gate as a second result.
``LinearAttention`` (Lightning Attention, Qin et al., arXiv:2401.04658, as
MiniMax-01 runs it, arXiv:2501.08313): linear attention with a FIXED decay a
head, ``S_t = lambda S_{t-1} + k_t v_t^T``, ``o_t = scale S_t^T q_t``. That is
``Mamba2``'s scan read with other names (``x`` = values, ``B`` = keys, ``C`` =
queries, a step size of 1, ``a`` = ``-slope``, no skip, as many groups as
heads), so it runs ``ops/kernels/ssd.py`` and ``ssd_scan`` here as they
stand, with the norm a head and the sigmoid gate that follow it."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ... import telemetry as _tm
from ..registry import OpDef, register
from ..utils import head_width, required_shape
from .attention import gate_output
from .taps import _gate_norm_site, _taps_site, again, causal_taps


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
    ``gate_first``), the block's ``gate_norm`` closure elsewhere.
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
    from .. import kernels

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
    from .. import kernels

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
            xbc = again(conv1d, remat)(proj, conv_weight, conv_bias)
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
            y = again(functools.partial(ssd_scan, chunk=chunk), remat,
                      policy=jax.checkpoint_policies.dots_saveable)(
                          x, *bc, dt, a)
            y = y + d_skip.astype(f32)[:, None] * x.astype(f32)
    with jax.named_scope("gate_norm"):
        if norm_kernel:
            return kernels.gated_rms_norm(
                y.reshape(b, t, d_in), proj, norm_gamma, form="gate_first",
                groups=g, eps=eps, scale=m_z, interpret=interpret)
        return again(gate_norm, remat)(y, proj, norm_gamma)


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
    data = required_shape(in_shapes[0], "Mamba2")
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
        op_class="ssm",
    )
)


_M_SELECTIVE_LOWERINGS = _tm.counter(
    "ssm.selective_lowerings", "Traces of a Mamba1 call site (one per "
    "lowering, nothing per step); labels: channels, state, dt_rank, conv "
    "(the convolution's taps), impl (kernel: the pair of "
    "ops/kernels/sscan.py where the step is lowered for the TPU, the "
    "jax.numpy form of the same signature elsewhere; scan: the jax.numpy "
    "form everywhere)")

SSCAN_CHUNK = 64       # tokens a step of the jax.numpy form's carried scan


def selective_scan(x, dt, bmat, cmat, a, skip, chunk=SSCAN_CHUNK,
                   remat=False):
    """The selective recurrence ``S_t[c, n] = exp(dt_t[c] a[c, n])
    S_{t-1}[c, n] + dt_t[c] x_t[c] B_t[n]``, ``y_t[c] = sum_n C_t[n]
    S_t[c, n] + skip[c] x_t[c]`` (``S`` zero before the first token).
    x [B, T, D], dt [B, T, D] positive, bmat and cmat [B, T, N], a [D, N]
    negative, skip [D] -> y [B, T, D] float32, everything computed in
    float32.

    A ``lax.scan`` over chunks of ``chunk`` tokens carrying the state
    [B, D, N]; inside a chunk the recurrence is an associative scan over
    the pairs (decay, input), which holds the chunk's [chunk, D, N]
    states at once and no more (the whole sequence's would be 1.3 GB at
    4,096 tokens of 5,120 channels); under ``remat`` a chunk is computed
    again in the backward pass and only the carried states are kept. T is
    padded to whole chunks with ``dt`` 0 (no decay, no input). The form
    for every platform but the TPU and for the shapes
    ``kernels.sscan_takes`` refuses, and what the kernel pair's tests hold
    it to."""
    f32 = jnp.float32
    b, t, d = x.shape
    n = a.shape[1]
    pad = -t % chunk
    x32 = x.astype(f32)
    chunks = tuple(
        jnp.moveaxis(jnp.pad(v.astype(f32), ((0, 0), (0, pad), (0, 0)))
                     .reshape(b, (t + pad) // chunk, chunk, v.shape[2]), 1, 0)
        for v in (x32, dt, bmat, cmat))
    a = a.astype(f32)

    def join(left, right):
        return left[0] * right[0], right[0] * left[1] + right[1]

    def one(state, chunk_in):
        x_c, dt_c, b_c, c_c = chunk_in
        decay = jnp.exp(dt_c[..., None] * a)               # [B, Q, D, N]
        wrote = (dt_c * x_c)[..., None] * b_c[:, :, None, :]
        decayed, summed = jax.lax.associative_scan(
            join, (decay, wrote), axis=1)
        states = decayed * state[:, None] + summed
        return states[:, -1], jnp.sum(states * c_c[:, :, None, :], axis=-1)

    _, y = jax.lax.scan(again(one, remat), jnp.zeros((b, d, n), f32), chunks)
    y = jnp.moveaxis(y, 0, 1).reshape(b, t + pad, d)[:, :t]
    return y + skip.astype(f32) * x32


def mamba1(proj, conv_weight, conv_bias, x_proj_weight, dt_proj_weight,
           dt_bias, a_log, d_skip, remat=False):
    """proj [B, T, 2 D] (``in_proj``'s output, ``x | z``), conv_weight
    [taps, D] (tap ``taps - 1`` meets the current token), conv_bias [D],
    x_proj_weight [R + 2 N, D], dt_proj_weight [D, R], dt_bias [D], a_log
    [D, N], d_skip [D] -> (``m * silu(z)``, ``m``), both [B, T, D] in
    proj's type: ``out_proj``'s input, and the scan's output before the
    gate (the memory a later layer's gated unit reads).

    ``x = silu(conv(x))``, the causal depthwise convolution with bias
    (scope ``conv1d``; ``kernels.causal_conv`` where the taps' family has
    tiles and the step is lowered for the TPU, ``causal_taps``
    elsewhere); ``dt_low | B | C = x_proj_weight x`` (scope ``x_proj``);
    ``dt = softplus(dt_proj_weight dt_low + dt_bias)`` (scope
    ``dt_proj``); ``a = -exp(a_log)``; ``m = selective_scan(x, dt, B, C, a,
    d_skip)`` (scope ``sscan``: the Pallas pair of
    ``ops/kernels/sscan.py`` where ``kernels.sscan_takes`` has tiles and
    the step is lowered for the TPU, the ``jax.numpy`` form elsewhere);
    the gate (scope ``gate``). The two projections take operands of
    proj's type and accumulate in float32; ``dt``, the decays, the state,
    the sum over the state index and the gate are float32; ``m`` is
    rounded to proj's type once, before the gate and the second result
    read it. ``remat`` (training): the float32 tables of the ``jax.numpy``
    forms are computed again in the backward pass (``again``); the kernel
    pair keeps its operands and the states its chunks entered with.

    The call site counts itself here (``ssm.selective_lowerings``,
    ``causal_taps.lowerings``); the block is ``_mamba1_block``, one
    ``jax.jit`` for every node of one signature."""
    from .. import kernels

    d_in, state = a_log.shape
    kernel = bool(kernels.sscan_takes(d_in, state, proj.dtype))
    taps_kernel = _taps_site("mamba1", proj, conv_weight, "bias_silu",
                             channels=d_in)
    _M_SELECTIVE_LOWERINGS.inc(
        channels=d_in, state=state, dt_rank=dt_proj_weight.shape[1],
        conv=conv_weight.shape[0], impl="kernel" if kernel else "scan")
    return _mamba1_block(
        proj, conv_weight, conv_bias, x_proj_weight, dt_proj_weight, dt_bias,
        a_log, d_skip, remat=bool(remat), kernel=kernel,
        taps_kernel=taps_kernel, interpret=kernels.common.INTERPRET)


@functools.partial(jax.jit, static_argnames=(
    "remat", "kernel", "taps_kernel", "interpret"))
def _mamba1_block(proj, conv_weight, conv_bias, x_proj_weight,
                  dt_proj_weight, dt_bias, a_log, d_skip, *, remat, kernel,
                  taps_kernel, interpret):
    """``mamba1`` for one signature."""
    from .. import kernels

    f32 = jnp.float32
    d_in, n = a_log.shape
    rank = dt_proj_weight.shape[1]

    def conv1d(proj, conv_weight, conv_bias):
        acc = causal_taps(proj[..., :d_in], conv_weight, conv_bias)
        return jax.nn.silu(acc).astype(proj.dtype)

    def step_sizes(low, dt_proj_weight, dt_bias):
        dt = jnp.einsum("btr,dr->btd", low, dt_proj_weight,
                        preferred_element_type=f32)
        return jax.nn.softplus(dt + dt_bias.astype(f32))

    def gate(m, proj):
        return (m.astype(f32) * jax.nn.silu(proj[..., d_in:].astype(f32))
                ).astype(proj.dtype)

    with jax.named_scope("conv1d"):
        if taps_kernel:
            x = kernels.causal_conv(
                proj, conv_weight, conv_bias, form="bias_silu",
                channels=d_in, interpret=interpret)
        else:
            x = again(conv1d, remat)(proj, conv_weight, conv_bias)
    with jax.named_scope("x_proj"):
        low = jnp.einsum("btd,rd->btr", x, x_proj_weight,
                         preferred_element_type=f32).astype(proj.dtype)
    with jax.named_scope("dt_proj"):
        dt = step_sizes(low[..., :rank], dt_proj_weight, dt_bias)
    with jax.named_scope("sscan"):
        a = -jnp.exp(a_log.astype(f32))
        bc = low[..., rank:rank + n], low[..., rank + n:]
        if kernel:
            m = kernels.selective_scan(x, dt, *bc, a, d_skip,
                                       interpret=interpret)
        else:
            m = selective_scan(x, dt, *bc, a, d_skip,
                               remat=remat).astype(proj.dtype)
    with jax.named_scope("gate"):
        return again(gate, remat)(m, proj), m


def _mamba1_sizes(attrs):
    return tuple(int(attrs[k]) for k in (
        "channels", "state_size", "dt_rank", "conv_kernel"))


def _mamba1(attrs, ins, is_train):
    return list(mamba1(*ins, remat=is_train))


def _mamba1_infer(attrs, in_shapes):
    d_in, n, rank, taps = _mamba1_sizes(attrs)
    if min(d_in, n, rank, taps) <= 0:
        raise ValueError(
            "Mamba1: channels=%d, state_size=%d, dt_rank=%d and "
            "conv_kernel=%d must be positive" % (d_in, n, rank, taps))
    data = required_shape(in_shapes[0], "Mamba1")
    if len(data) != 3 or data[2] != 2 * d_in:
        raise ValueError(
            "Mamba1: data must be [batch, time, %d] (x %d | z %d), got %s"
            % (2 * d_in, d_in, d_in, data))
    out = data[:2] + (d_in,)
    return ([data, (taps, d_in), (d_in,), (rank + 2 * n, d_in),
             (d_in, rank), (d_in,), (d_in, n), (d_in,)], [out, out], [])


register(
    OpDef(
        "_contrib_Mamba1",
        _mamba1,
        arguments=("data", "conv_weight", "conv_bias", "x_proj_weight",
                   "dt_proj_weight", "dt_bias", "a_log", "d"),
        outputs=("output", "memory"),
        defaults={"channels": 0, "state_size": 16, "dt_rank": 0,
                  "conv_kernel": 4},
        infer_shape=_mamba1_infer,
        aliases=("Mamba1",),
        op_class="ssm",
    )
)


_M_LINATTN_LOWERINGS = _tm.counter(
    "linattn.lowerings", "Traces of a LinearAttention call site (one per "
    "lowering, nothing per step); labels: impl (kernel: the ssd pair where "
    "the step is lowered for the TPU; einsum: the chunked jax.numpy form), "
    "heads, chunk")


def lightning_slopes(heads_of, layer, layers_of, first=0, held=None):
    """``slope(h, l) = 2^(-8 (h + 1) / H) * (1 - l / (L - 1) + 1e-5)`` for
    the PUBLISHED head h of H = ``heads_of`` and the published layer l of L
    = ``layers_of`` (MiniMax-01's modelling code: ALiBi's geometric slopes
    under a factor that falls with depth): the ``held`` heads from ``first``
    on, as a tuple of floats."""
    held = heads_of - first if held is None else held
    factor = 1.0 - layer / max(layers_of - 1, 1) + 1e-5
    return tuple(2.0 ** (-8.0 * (h + 1) / heads_of) * factor
                 for h in range(first, first + held))


def linear_attention(query, key, value, slopes, num_heads, chunk_size=128,
                     norm_gamma=None, gate=None, eps=1e-6, remat=False):
    """query and key [B, T, H D], value [B, T, H P] (after their norms and
    rotation), ``slopes`` H positive floats -> [B, T, H P].

    Scope ``core``: the recurrence above, float32 state and result, the
    products' operands in ``value``'s type; ``D ** -0.5`` multiplies the
    float32 result. Scope ``norm`` (``norm_gamma`` [P], optional): an
    RMSNorm over each head's own P columns, one gamma shared by the heads,
    statistics float32, one rounding before the scale. Scope ``gate`` (``gate`` [B, T, H P], optional): ``o *
    sigmoid(gate)``, ``attention.gate_output``. ``remat`` (training): norm
    and gate are computed again in the backward pass from the core's
    float32 result, which the kernel pair keeps anyway."""
    from .. import kernels

    d = query.shape[2] // num_heads
    p = value.shape[2] // num_heads
    kernel = bool(kernels.ssd_takes(num_heads, p, d, num_heads, chunk_size,
                                    value.dtype))
    _M_LINATTN_LOWERINGS.inc(impl="kernel" if kernel else "einsum",
                             heads=num_heads, chunk=chunk_size)
    slopes = jnp.asarray(slopes, jnp.float32)
    if slopes.shape != (num_heads,):
        raise ValueError("LinearAttention: slopes=%r must be %d floats, one "
                         "a head" % (slopes.shape, num_heads))
    extra = {"norm_gamma": norm_gamma, "gate": gate}
    return _linattn_block(
        query, key, value, slopes,
        {name: x for name, x in extra.items() if x is not None},
        sizes=(num_heads, d, p, int(chunk_size)), scale=float(d) ** -0.5,
        eps=float(eps), remat=bool(remat), kernel=kernel,
        interpret=kernels.common.INTERPRET)


@functools.partial(jax.jit, static_argnames=(
    "sizes", "scale", "eps", "remat", "kernel", "interpret"))
def _linattn_block(query, key, value, slopes, extra, *, sizes, scale, eps,
                   remat, kernel, interpret):
    """``linear_attention`` for one signature (``sizes``: heads, key width,
    value width, chunk): a model's layers differ in their slopes alone,
    which are an operand, so they trace and lower this once."""
    from .. import kernels

    f32 = jnp.float32
    h, d, p, chunk = sizes
    b, t, _ = query.shape

    def heads(x, width):
        return x.reshape(b, t, h, width)

    with jax.named_scope("core"):
        q, k, v = heads(query, d), heads(key, d), heads(value, p)
        dt = jnp.ones((b, t, h), f32)
        a = -slopes
        if kernel:
            y = kernels.ssd_scan(v, k, q, dt, a, jnp.zeros((h,), f32), chunk,
                                 interpret=interpret)
        else:
            y = again(functools.partial(ssd_scan, chunk=chunk), remat,
                      policy=jax.checkpoint_policies.dots_saveable)(
                          v, k, q, dt, a)
        y = y * scale

    def finish(y, extra):
        out = y
        if "norm_gamma" in extra:
            with jax.named_scope("norm"):
                var = jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                out = (extra["norm_gamma"].astype(value.dtype)
                       * (y * jax.lax.rsqrt(var + eps)).astype(value.dtype))
        out = out.astype(value.dtype).reshape(b, t, h * p)
        if "gate" in extra:
            with jax.named_scope("gate"):
                out = gate_output(out, extra["gate"])
        return out

    return again(finish, remat)(y, extra)


def _linear_attention(attrs, ins, is_train):
    query, key, value, norm_gamma, gate = ins
    return [linear_attention(
        query, key, value, slopes=tuple(attrs["slopes"]),
        num_heads=int(attrs["num_heads"]), norm_gamma=norm_gamma, gate=gate,
        eps=float(attrs.get("eps", 1e-6)), remat=is_train)]


def _linear_attention_infer(attrs, in_shapes):
    heads = int(attrs["num_heads"])
    slopes = tuple(attrs.get("slopes") or ())
    if heads <= 0 or len(slopes) != heads or min(slopes) <= 0:
        raise ValueError(
            "LinearAttention: slopes=%r must be num_heads=%d positive "
            "floats, a head's fixed log decay a token" % (slopes, heads))
    q, k, v = (required_shape(shape, "LinearAttention")
               for shape in in_shapes[:3])
    head_width("LinearAttention", "query", q, heads)
    p = head_width("LinearAttention", "value", v, heads)
    if k != q or v[:2] != q[:2]:
        raise ValueError(
            "LinearAttention: key %s must be query's shape %s and value %s "
            "share its batch and time" % (k, q, v))
    return [q, k, v, (p,), v], [v], []


register(
    OpDef(
        "_contrib_LinearAttention",
        _linear_attention,
        arguments=("query", "key", "value", "norm", "gate"),
        defaults={"num_heads": 1, "slopes": (), "eps": 1e-6},
        infer_shape=_linear_attention_infer,
        aliases=("LinearAttention",),
        op_class="linattn",
    )
)
