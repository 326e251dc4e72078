"""Mixture-of-Experts FFN with expert parallelism (ep mesh axis).

Beyond-reference capability (SURVEY.md §2.3 lists expert parallel as
absent in the reference): the TPU-native MoE recipe in the
Mesh-TensorFlow / GShard / Switch-Transformer lineage, written the XLA
way — routing, dispatch and combine are einsums over dense one-hot
dispatch tensors, and expert parallelism is nothing but a sharding
annotation: expert-major tensors carry ``PartitionSpec("ep", ...)``,
tokens stay dp-sharded, and GSPMD inserts the all-to-alls between the
token and expert layouts. No hand-written collectives, so the same
function runs single-device (tests) and on a dp x ep mesh (dryrun)
with identical numerics.

Routing is Switch-style top-1 with a capacity limit: tokens that
overflow an expert's capacity are dropped (contribute zero), matching
the published behavior; an auxiliary load-balance loss (Switch
Transformer eq. 4) keeps the router from collapsing onto one expert.
"""
import functools

import jax
import jax.numpy as jnp

from .. import telemetry as _tm


def init_moe_params(rng, d_model, d_hidden, num_experts, dtype=jnp.float32):
    """Router + expert weights. Expert-major tensors lead with the E axis
    so ``PartitionSpec("ep", ...)`` shards whole experts."""
    import numpy as np

    r = np.random.RandomState(rng)
    scale = 1.0 / np.sqrt(d_model)
    return {
        "gate_w": jnp.asarray(
            r.randn(d_model, num_experts) * scale, dtype),
        "w_up": jnp.asarray(
            r.randn(num_experts, d_model, d_hidden) * scale, dtype),
        "w_down": jnp.asarray(
            r.randn(num_experts, d_hidden, d_model) / np.sqrt(d_hidden),
            dtype),
    }


def moe_partition_specs():
    """PartitionSpecs for init_moe_params output on a (dp, ..., ep) mesh."""
    from jax.sharding import PartitionSpec as P

    return {
        "gate_w": P(),                 # router replicated
        "w_up": P("ep", None, None),   # whole experts per ep shard
        "w_down": P("ep", None, None),
    }


def switch_moe(params, x, capacity_factor=1.25):
    """Top-1 MoE FFN. x: [tokens, d_model] -> ([tokens, d_model], aux_loss).

    Dense-dispatch formulation: dispatch/combine are [tokens, E, C]
    one-hots, expert compute is a batched einsum over [E, C, d] — the
    shape GSPMD splits cleanly along E (ep axis) with all-to-alls at the
    einsum boundaries.
    """
    tokens, d_model = x.shape
    num_experts = params["gate_w"].shape[1]
    capacity = int(max(1, tokens * capacity_factor / num_experts))

    logits = x.astype(jnp.float32) @ params["gate_w"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)              # [T, E]
    expert_idx = jnp.argmax(probs, axis=-1)              # [T]
    expert_prob = jnp.take_along_axis(
        probs, expert_idx[:, None], axis=-1)[:, 0]       # [T]
    assign = jax.nn.one_hot(expert_idx, num_experts, dtype=jnp.float32)

    # position of each token within its expert's queue; >= capacity drops
    pos_in_expert = (jnp.cumsum(assign, axis=0) - assign) * assign  # [T, E]
    keep = (pos_in_expert < capacity) * assign                      # [T, E]
    pos = pos_in_expert.sum(-1).astype(jnp.int32)                   # [T]
    pos_hot = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)      # [T, C]

    dispatch = keep[:, :, None] * pos_hot[:, None, :]    # [T, E, C]
    combine = dispatch * expert_prob[:, None, None]      # [T, E, C]

    # Routing above stays f32; the expert FFN itself runs in the caller's
    # compute dtype (bf16 on the MXU) like the dense FFN it replaces.
    cdtype = x.dtype
    # token layout -> expert layout (GSPMD: all-to-all over ep here)
    expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(cdtype), x)
    h = jax.nn.relu(jnp.einsum(
        "ecd,edh->ech", expert_in, params["w_up"].astype(cdtype)))
    expert_out = jnp.einsum(
        "ech,ehd->ecd", h, params["w_down"].astype(cdtype))
    # expert layout -> token layout (all-to-all back)
    y = jnp.einsum("tec,ecd->td", combine.astype(cdtype), expert_out)

    # Switch load-balance loss: E * sum_e fraction_tokens_e * mean_prob_e
    frac_tokens = assign.mean(0)
    mean_prob = probs.mean(0)
    aux_loss = num_experts * jnp.sum(frac_tokens * mean_prob)
    return y.astype(x.dtype), aux_loss




def _expert_dot(counts, rows, dtype):
    """``dot(lhs, rhs)`` over ``rows`` rows sorted into ``counts``
    groups: ``ops.kernels.grouped_matmul``, whose Pallas kernels
    run where the step is lowered for the TPU and ``jax.lax.ragged_dot``
    on every other platform (and at shapes the kernels do not take). The
    kernels' group metadata is made once here and shared by every product
    (and its two transposes) that the returned function is used for. A
    weight that the chip holds transposed (``kernels.held_transposed``,
    from its shape: Nemotron-H's un-gated ``[E, 2688, 1856]``) reaches the
    kernels as the swap of its last two axes, a bitcast of what is held,
    where the declared order costs a copy of the weight, of its gradient
    and of the optimizer's state each step."""
    from ..ops import kernels

    groups = counts.shape[0]
    if not kernels.gmm_runs_kernel(rows, dtype):
        return lambda lhs, rhs: jax.lax.ragged_dot(lhs, rhs, counts)
    metadata = kernels.gmm_metadata(
        counts, rows, kernels.gmm_row_tile(rows, groups))
    product = functools.partial(kernels.grouped_matmul, group_sizes=counts,
                                metadata=metadata,
                                interpret=kernels.common.INTERPRET)

    def dot(lhs, rhs):
        if kernels.held_transposed(rhs.shape):
            return product(lhs, jnp.swapaxes(rhs, 1, 2),
                           rhs_transposed=True)
        return product(lhs, rhs)

    return dot


def _experts(params, rows, counts, activation, scale=None):
    """The experts' feed-forward over ``rows`` sorted into ``counts``
    groups. ``swiglu``: ``down(silu(gate) * up)``, gate and up one
    product over ``w_gate_up`` [E, d, 2h]; ``relu2``: ``down(relu(up)^2)``
    with ``w_gate_up`` [E, d, h] the up projection alone, two grouped
    products a pass where SwiGLU does three. ``scale`` [rows] (float32; a
    share's routing weights): the activation is computed in float32,
    multiplied by its row's scale there and rounded once, so the rows
    leave the down projection already weighted (``down`` is linear)
    (``_scaled_activation``)."""
    dtype = rows.dtype
    hidden = params["w_down"].shape[1]
    if activation not in ("swiglu", "relu2"):
        raise ValueError("topk_moe: activation must be swiglu or relu2, "
                         "got %r" % (activation,))
    dot = _expert_dot(counts, rows.shape[0], dtype)
    up = dot(rows, params["w_gate_up"].astype(dtype))
    if scale is not None:
        act = _scaled_activation(up, scale, activation)
    elif activation == "relu2":
        act = jnp.square(jax.nn.relu(up.astype(jnp.float32))).astype(dtype)
    else:
        act = jax.nn.silu(up[:, :hidden]) * up[:, hidden:]
    return dot(act, params["w_down"].astype(dtype))


def _activation_parts(up, activation):
    """float32 (activation of ``up``, its derivatives by ``up``'s column
    blocks): ``relu2``: ``relu(up)^2`` and (``2 relu(up)``,); ``swiglu``:
    ``silu(gate) * lin`` over the two halves of ``up``'s columns (sliced
    before they are widened: XLA writes a widened ``up`` to HBM whole
    where the slices follow it) and (``silu'(gate) * lin``, ``silu(gate)``)."""
    if activation == "relu2":
        positive = jax.nn.relu(up.astype(jnp.float32))
        return jnp.square(positive), (2 * positive,)
    hidden = up.shape[1] // 2
    gate = up[:, :hidden].astype(jnp.float32)
    lin = up[:, hidden:].astype(jnp.float32)
    sig = jax.nn.sigmoid(gate)
    silu = gate * sig
    return silu * lin, ((sig + silu * (1 - sig)) * lin, silu)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
@functools.partial(jax.jit, static_argnums=(2,))
def _scaled_activation(up, scale, activation):
    """``_experts``' activation of ``up`` in float32 times ``scale`` a
    row, rounded once. The backward keeps ``up`` and ``scale`` and
    computes the activation again, in two passes over them (the rows'
    cotangent; the scale's, a sum a row): no float32 table of the
    activation's shape reaches HBM either way. One ``jax.jit`` a
    signature each way for a model's layers."""
    act, _ = _activation_parts(up, activation)
    return (act * scale[:, None]).astype(up.dtype)


def _scaled_activation_fwd(up, scale, activation):
    return _scaled_activation(up, scale, activation), (up, scale)


@functools.partial(jax.jit, static_argnums=(0,))
def _scaled_activation_bwd(activation, res, d_out):
    up, scale = res
    act, slopes = _activation_parts(up, activation)
    d_out = d_out.astype(jnp.float32)
    d_act = d_out * scale[:, None]
    d_up = jnp.concatenate([d_act * slope for slope in slopes], axis=1)
    return d_up.astype(up.dtype), jnp.sum(d_out * act, axis=1)


_scaled_activation.defvjp(_scaled_activation_fwd, _scaled_activation_bwd)


_M_PERMUTE_LOWERINGS = _tm.counter(
    "moe.permute_lowerings", "Traces of a topk_moe call site that moves "
    "its rows into expert order and back by the permutation pair (one "
    "per lowering, nothing per step); labels: rows (tokens * top_k), "
    "top_k, width (d_model)")


def _take_rows(a, index):
    """``a[index]`` along the first axis, every index in range: one
    ``lax.gather``, without the clamp or fill of ``jnp.take``."""
    numbers = jax.lax.GatherDimensionNumbers(
        offset_dims=tuple(range(1, a.ndim)), collapsed_slice_dims=(0,),
        start_index_map=(0,))
    return jax.lax.gather(
        a, index[:, None], numbers, (1,) + a.shape[1:],
        mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)


# The row moves round the experts. ``order`` [tokens * top_k] sorts the
# (token, expert) pairs by expert and ``inverse`` [tokens, top_k] is its
# inverse permutation (the row of a token's j-th expert), which autodiff
# cannot know: it transposes each gather to a scatter-add over the rows.
# Here the transpose of a gather by one is a gather by the other, so rows
# move by gathers only, forward and backward; the indices carry no
# gradient.

@jax.custom_vjp
def _dispatch(x, order, inverse):
    """Token rows into expert order: ``rows[r] = x[order[r] // top_k]``."""
    return _dispatch_fwd(x, order, inverse)[0]


def _dispatch_fwd(x, order, inverse):
    top_k = inverse.shape[1]
    _M_PERMUTE_LOWERINGS.inc(rows=order.shape[0], top_k=top_k,
                             width=x.shape[1])
    return _take_rows(x, jax.lax.div(order, jnp.int32(top_k))), inverse


def _dispatch_bwd(inverse, d_rows):
    # a token's top_k cotangent rows, summed in float32 and rounded once
    per_token = _take_rows(d_rows, inverse.reshape(-1)).reshape(
        inverse.shape + d_rows.shape[1:])
    dx = jnp.sum(per_token.astype(jnp.float32), axis=1)
    return dx.astype(d_rows.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(out_rows, weights, order, inverse):
    """Expert rows back to their tokens and a token's ``top_k`` rows
    summed under its routing weights in float32: ``y[t] = sum_j
    weights[t, j] * out_rows[inverse[t, j]]``."""
    return _combine_fwd(out_rows, weights, order, inverse)[0]


def _combine_fwd(out_rows, weights, order, inverse):
    per_token = _take_rows(out_rows, inverse.reshape(-1)).reshape(
        inverse.shape + out_rows.shape[1:])
    y = jnp.einsum("tkd,tk->td", per_token.astype(jnp.float32), weights)
    return y.astype(out_rows.dtype), (per_token, weights, order)


def _combine_bwd(res, dy):
    per_token, weights, order = res
    top_k = weights.shape[1]
    d_weights = jnp.einsum("td,tkd->tk", dy.astype(jnp.float32),
                           per_token.astype(jnp.float32))
    # the [tokens, d] cotangent gathered into expert order and scaled a
    # row, rounded once: the [tokens, top_k, d] float32 cotangent of
    # ``per_token`` is never written
    scale = _take_rows(weights.reshape(-1), order)
    d_rows = _take_rows(dy, jax.lax.div(order, jnp.int32(top_k)))
    d_rows = d_rows.astype(jnp.float32) * scale[:, None]
    return d_rows.astype(per_token.dtype), d_weights, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


_M_SHARE_LOWERINGS = _tm.counter(
    "moe.share_lowerings", "Traces of a topk_moe call site that holds a "
    "share of its experts (one per lowering, nothing per step); labels: "
    "held (experts computed here), of (experts routed over), bound (rows "
    "of the share's buffer) and, where it is not 1, scale (the factor on "
    "the routing weights); where they are not the defaults, act and "
    "renorm_eps")


def _route(params, x, top_k, norm_topk_prob, scoring, routed_scale=1.0,
           renorm_eps=0.0):
    """Float32 routing: (weights [T, k], experts [T, k]). ``softmax``:
    top-k of the softmax. ``sigmoid``: scores ``sigmoid(logits)``, the
    choice by score plus ``select_bias`` (which carries no gradient and
    never reaches the weights), the weights the chosen scores. The
    weights, renormalised or not (over their sum plus ``renorm_eps``),
    times ``routed_scale``."""
    logits = jnp.dot(
        x.astype(jnp.float32), params["gate_w"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST)
    if scoring == "softmax":
        weights, experts = jax.lax.top_k(
            jax.nn.softmax(logits, axis=-1), top_k)
    elif scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        select = scores
        if params.get("select_bias") is not None:
            select = scores + jax.lax.stop_gradient(
                params["select_bias"].astype(jnp.float32))
        _, experts = jax.lax.top_k(select, top_k)
        # the chosen scores by a mask over the experts, not a gather:
        # [T, k, E] compares fuse into one pass, forward and backward,
        # where take_along_axis is a gather and then a scatter-add
        chosen = experts[:, :, None] == jax.lax.broadcasted_iota(
            experts.dtype, (1, 1, scores.shape[1]), 2)
        weights = jnp.sum(jnp.where(chosen, scores[:, None, :], 0), axis=-1)
    else:
        raise ValueError("topk_moe: scoring must be softmax or sigmoid, "
                         "got %r" % (scoring,))
    if norm_topk_prob:
        total = jnp.sum(weights, axis=-1, keepdims=True)
        weights = weights / (total + renorm_eps if renorm_eps else total)
    if routed_scale != 1.0:
        weights = weights * routed_scale
    return weights, experts


def topk_moe(params, x, top_k, norm_topk_prob=False, scoring="softmax",
             expert_offset=0, share_rows_bound=0, routed_scale=1.0,
             activation="swiglu", renorm_eps=0.0):
    """Dropless top-k MoE FFN with SwiGLU experts (the OLMoE / Mixtral
    block) or, ``activation="relu2"``, un-gated ``relu(.)^2`` ones
    (Nemotron-H). x: [tokens, d_model] -> ([tokens, d_model], counts
    [E]).

    ``params``: ``gate_w`` [d, E] (router), ``w_gate_up`` [E, d, 2h]
    (each expert's gate projection in the first ``h`` columns, its up
    projection in the last; un-gated, [E, d, h]: the up projection
    alone), ``w_down`` [E, h, d]. Expert tensors lead with E, so
    ``moe_partition_specs`` applies to them too.

    No capacity: every (token, expert) pair of the routing is computed,
    whatever the load. The ``tokens * top_k`` rows are sorted by expert
    and the three expert matmuls run as two grouped matmuls over the
    sorted rows, so the work is that of the routing and not
    ``O(T * E * C * d)`` as in ``switch_moe``'s dense dispatch. Where
    the layer holds every expert the rows move into expert order and
    back by gathers only, forward and backward (``_dispatch``,
    ``_combine``: a permutation and its inverse over the ``tokens *
    top_k`` pairs), and nothing on that path is a scatter but the
    transpose of the router's ``top_k`` (a share's moves are below). Who
    computes them (``_expert_dot``): the Pallas kernels of
    ``ops.kernels.grouped_matmul`` where the step is lowered for
    the TPU, ``jax.lax.ragged_dot`` everywhere else (by
    ``kernels.common.on_tpu``, inside that function); both take
    operands of ``x.dtype``, accumulate in float32 and round once. The
    router (matmul at full float32 precision, scores, top-k) stays in
    float32 whatever the activations' dtype (``_route``: ``scoring``
    ``softmax`` or ``sigmoid``, the latter with the optional
    ``select_bias`` [E]); routing weights are not renormalised unless
    ``norm_topk_prob`` (then over their sum plus ``renorm_eps``: the
    ``+ 1e-6`` of the ``lfm2_moe`` code), and are multiplied by
    ``routed_scale`` after that (DeepSeek-V3's
    ``routed_scaling_factor``). ``counts`` is the
    number of rows each of the E experts received (int32, no gradient).

    **A share of the experts.** Where ``w_gate_up`` holds H < E experts,
    they are the router's experts ``expert_offset`` .. ``expert_offset
    + H - 1``: the layer routes over all E, and computes the part of
    the result that the held experts give (what an expert-parallel
    member computes before the exchange; nothing stands in for the
    others). The rows routed here are compacted into a buffer of
    ``share_rows_bound`` rows, and gather, expert products and combine
    run over that buffer, not over ``tokens * top_k``. Rows past the
    bound are not computed; ``counts`` still counts every row over all
    E, so a caller sees that they existed. A share has no inverse
    permutation (a token holds 0 to ``top_k`` rows of the buffer), but
    its buffer is made in token order, so a token's rows are a
    contiguous segment of it: rows reach the buffer by a gather
    (``_share_dispatch``) and leave it by a gather into token order and
    a sorted segment sum (``_share_combine``;
    ``ops.kernels.sorted_segment_sum``: the grouped matmul's wgrad
    kernel over an exact 0 / 1 table where the step is lowered for the
    TPU, ``jax.ops.segment_sum`` elsewhere), each the other's transpose,
    so neither direction scatters; float32 accumulation, one rounding.
    The routing weight multiplies the experts' activation in float32
    before that activation's one rounding (``_experts(scale=)``), not
    the rounded output rows: the down projection is linear, and what
    the segment sum adds is then the rows themselves. The weights'
    cotangents come back through the same sum (``_share_weights``).
    """
    tokens = x.shape[0]
    num_experts = params["gate_w"].shape[1]
    held = params["w_down"].shape[0]

    with jax.named_scope("router"):
        weights, experts = _route(params, x, top_k, norm_topk_prob,
                                  scoring, routed_scale, renorm_eps)

    if held < num_experts:
        labels = {} if routed_scale == 1.0 else {"scale": routed_scale}
        if activation != "swiglu":
            labels["act"] = activation
        if renorm_eps:
            labels["renorm_eps"] = renorm_eps
        from ..ops import kernels

        _M_SHARE_LOWERINGS.inc(
            held=held, of=num_experts, bound=share_rows_bound,
            sum="segment_product" if kernels.gmm_runs_kernel(
                share_rows_bound, x.dtype) else "segment_sum", **labels)
        return _topk_moe_share(params, x, weights, experts, expert_offset,
                               share_rows_bound, activation)

    with jax.named_scope("dispatch"):
        flat_expert = experts.reshape(-1)                     # [T*k]
        # int32 whatever jax_enable_x64 says: the chip sorts and
        # gathers by 64-bit indices as pairs of words
        pairs = jax.lax.iota(jnp.int32, tokens * top_k)
        _, order = jax.lax.sort((flat_expert, pairs), num_keys=1,
                                is_stable=True)               # by expert
        _, inverse = jax.lax.sort((order, pairs), num_keys=1)
        inverse = inverse.reshape(tokens, top_k)
        counts = jnp.sum(jax.nn.one_hot(
            flat_expert, num_experts, dtype=jnp.int32), axis=0,
            dtype=jnp.int32)
        rows = _dispatch(x, order, inverse)                   # [T*k, d]

    with jax.named_scope("experts"):
        out_rows = _experts(params, rows, counts, activation)  # [T*k, d]

    with jax.named_scope("combine"):
        y = _combine(out_rows, weights, order, inverse)
    return y, jax.lax.stop_gradient(counts)


# A share's row moves. The compaction makes the buffer in TOKEN order (a
# token's held rows are consecutive there, the free rows last) and one
# small sort puts it in expert order; ``inverse`` [bound] is the row of
# the expert-ordered buffer that token-ordered row r holds and
# ``segment`` [bound] that row's token (``tokens`` for a free row).
# Autodiff would transpose ``x[token]`` to a scatter-add of the buffer
# into the tokens; here the sum of a token's rows is a sorted segment
# sum (``ops.kernels.sorted_segment_sum``: a product where the step is
# lowered for the TPU), so the two moves are each other's transposes and
# rows move by gathers over the buffer only, forward and backward. A free
# row belongs to no segment and to no expert's group: whatever it holds,
# nothing reads it. What a layer traces of all this beyond the gathers
# sits behind module-level ``jax.jit``s: one trace a signature.

@functools.partial(jax.jit, static_argnames=("offset", "held", "bound"))
def _share_plan(experts, *, offset, held, bound):
    """Where the rows of a share's buffer come from, from the routing
    ``experts`` [tokens, top_k] alone: ``sizes`` [held] (rows an expert
    held), and a row each of the expert-ordered buffer ``token`` (0 for a
    free row), ``pairs`` (its (token, k) pair; ``tokens * top_k`` for a
    free row) and of the token-ordered one ``inverse``, ``segment``,
    ``slot`` (the k of its pair)."""
    tokens, top_k = experts.shape
    local = experts.reshape(-1).astype(jnp.int32) - jnp.int32(offset)
    here = (local >= 0) & (local < held)
    # the first ``bound`` pairs routed here, in token order: row r of
    # the buffer takes the first pair whose running count of held pairs
    # reaches r + 1 (a fused compare-and-count; what
    # ``jnp.nonzero(size=)`` does by a scatter-add over every pair); one
    # past the last pair marks a free row
    running = jnp.cumsum(here.astype(jnp.int32))
    row = jax.lax.iota(jnp.int32, bound)
    pairs = jnp.searchsorted(running, row + 1, side="left",
                             method="compare_all").astype(jnp.int32)
    free = pairs >= tokens * top_k
    group = jnp.where(free, held, _take_rows(
        local, jnp.minimum(pairs, tokens * top_k - 1)))
    segment = jnp.where(free, tokens, pairs // top_k)
    slot = pairs % top_k
    # into expert order (int32 sorts: ``jnp.argsort`` sorts and gathers
    # by int64 under jax_enable_x64) and back
    group, pairs, order = jax.lax.sort(
        (group, pairs, row), num_keys=1, is_stable=True)
    _, inverse = jax.lax.sort((order, row), num_keys=1)
    sizes = jnp.sum(jax.nn.one_hot(group, held, dtype=jnp.int32), axis=0)
    token = jnp.where(group < held, pairs // top_k, 0)
    return sizes, token, pairs, inverse, segment, slot


def _sum_rows(rows, inverse, segment, tokens):
    """The expert-ordered ``rows`` of each token summed in float32 and
    rounded once -> [tokens, d]."""
    from ..ops import kernels

    return kernels.sorted_segment_sum(
        _take_rows(rows, inverse), segment, tokens,
        interpret=kernels.common.INTERPRET)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _share_dispatch(x, token, inverse, segment, tokens):
    """Token rows into the expert-ordered buffer: ``rows[r] =
    x[token[r]]``."""
    return _take_rows(x, token)


def _share_dispatch_fwd(x, token, inverse, segment, tokens):
    return _take_rows(x, token), (inverse, segment)


def _share_dispatch_bwd(tokens, res, d_rows):
    return (_sum_rows(d_rows, *res, tokens), None, None, None)


_share_dispatch.defvjp(_share_dispatch_fwd, _share_dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _share_combine(out_rows, token, inverse, segment, tokens):
    """The buffer's rows back to their tokens: ``y[t]`` the sum of the
    rows whose token is t."""
    return _sum_rows(out_rows, inverse, segment, tokens)


def _share_combine_fwd(out_rows, token, inverse, segment, tokens):
    return _sum_rows(out_rows, inverse, segment, tokens), token


def _share_combine_bwd(tokens, token, dy):
    return _take_rows(dy, token), None, None, None


_share_combine.defvjp(_share_combine_fwd, _share_combine_bwd)


@jax.jit
def _pick_weights(weights, pairs):
    """``weights`` [tokens, top_k] at ``pairs`` [bound], 0 where a pair is
    one past the last (a free row)."""
    flat = weights.reshape(-1)
    last = flat.shape[0] - 1
    return jnp.where(pairs <= last,
                     _take_rows(flat, jnp.minimum(pairs, last)), 0)


@functools.partial(jax.jit, static_argnames=("tokens", "top_k"))
def _weights_table(d_scale, inverse, segment, slot, *, tokens, top_k):
    """[bound, top_k] in token order: a row's weight cotangent at the k of
    its pair, zeros beside it and in a free row."""
    placed = jnp.where(segment < tokens, _take_rows(d_scale, inverse), 0)
    return jnp.where(
        slot[:, None] == jax.lax.iota(jnp.int32, top_k)[None, :],
        placed[:, None], 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _share_weights(weights, pairs, inverse, segment, slot, shape):
    """The routing weight [bound] of every row of the expert-ordered
    buffer, 0 for a free row. Its transpose places a row's cotangent at
    its pair of [tokens, top_k] as the moves place rows: the [bound,
    top_k] table that holds it at the pair's k, summed by token (each
    sum has one addend at most), where the gather's own transpose is a
    scatter-add. ``shape`` is ``weights.shape``."""
    return _pick_weights(weights, pairs)


def _share_weights_fwd(weights, pairs, inverse, segment, slot, shape):
    return _pick_weights(weights, pairs), (inverse, segment, slot)


def _share_weights_bwd(shape, res, d_scale):
    from ..ops import kernels

    inverse, segment, slot = res
    table = _weights_table(d_scale, inverse, segment, slot,
                           tokens=shape[0], top_k=shape[1])
    return (kernels.sorted_segment_sum(
        table, segment, shape[0], interpret=kernels.common.INTERPRET),
        None, None, None, None)


_share_weights.defvjp(_share_weights_fwd, _share_weights_bwd)


def _topk_moe_share(params, x, weights, experts, offset, bound, activation):
    """``topk_moe`` where the layer holds experts ``offset`` ..
    ``offset + H - 1`` of the E it routes over: their part of the
    result, over a buffer of ``bound`` rows: the first ``bound`` pairs
    routed here, in token order. The rows move by gathers over the buffer
    and a token's rows are summed as a sorted segment sum
    (``_share_dispatch``, ``_share_combine``: each the other's
    transpose); the routing weight is applied in float32 where the
    experts' activation is rounded (``_experts``), so what is summed is
    the rows themselves, in float32, rounded once."""
    tokens = x.shape[0]
    top_k = experts.shape[1]
    num_experts = params["gate_w"].shape[1]
    held = params["w_down"].shape[0]
    if not 0 < bound <= tokens * top_k:
        raise ValueError(
            "topk_moe: a share needs share_rows_bound in 1..tokens * "
            "top_k (%d), got %d" % (tokens * top_k, bound))
    if not 0 <= offset <= num_experts - held:
        raise ValueError(
            "topk_moe: experts %d..%d are not among the router's %d"
            % (offset, offset + held - 1, num_experts))

    with jax.named_scope("dispatch"):
        counts = jnp.sum(jax.nn.one_hot(
            experts.reshape(-1), num_experts, dtype=jnp.int32), axis=0)
        sizes, token, pairs, inverse, segment, slot = _share_plan(
            experts, offset=offset, held=held, bound=bound)
        rows = _share_dispatch(x, token, inverse, segment, tokens)
        scale = _share_weights(weights, pairs, inverse, segment, slot,
                               weights.shape)

    with jax.named_scope("experts"):
        out_rows = _experts(params, rows, sizes, activation, scale)

    with jax.named_scope("combine"):
        y = _share_combine(out_rows, token, inverse, segment, tokens)
    return y, jax.lax.stop_gradient(counts)
