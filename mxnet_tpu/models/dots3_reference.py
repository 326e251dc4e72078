"""Plain reference for ``models/dots3.py``: dots3-note-prev's forward
pass, loss and gradients in straightforward ``jax.numpy``.

No kernel, no sort of the program's, no grouped matmul, no cache, no
absorbed projection, no keep-mask tiles: keys and values of every head
are projected up from the normalised latent and materialised, the scores
are a ``[block, T]`` matrix a head with an explicit mask (``block``
queries at a time, so that 4k positions fit a chip: a block's rows are
whole softmax rows, nothing is computed online), the indexer's scores
are materialised a block of rows at a time for all of its heads and the
selection is ``jax.lax.top_k`` on the masked index scores, the expert
layer is a loop over the experts held with a mask. Everything is
computed in ``dtype`` — float32 by default, under
``jax.default_matmul_precision("highest")`` so that a TPU does not
quietly run float32 matmuls in bf16 passes. ``dtype=jnp.bfloat16`` is
the same mathematics one precision below what any configuration of the
system states (router, norms, RoPE's tables, the indexer's scores and
compare, softmaxes, gates and the loss in bf16 too): a comparison's
tolerance has to fail it.

It follows the published ``config.json`` (``model_type`` dots3_note) key
by key (``cfg`` below). Every key but one is a ``deepseek_v3`` key, a
DeepSeek-V3.2 indexer key, or the same key with ``swa_`` in front for
the ``sliding_attention`` layers. ``x = RMSNorm(h)`` (``rms_norm_eps``);
``c_q = r_q RMSNorm(W_dq x)`` (``q_lora_rank``); ``q = W_uq c_q``, a
head's ``qk_nope_head_dim`` un-rotated dimensions then its
``qk_rope_head_dim`` rotary ones; ``(c, k_rope) = W_dkv x``
(``kv_lora_rank`` + ``qk_rope_head_dim``); ``(k_nope_h, v_h) = W_ukv
(r_kv RMSNorm(c))``. RoPE (``rope_theta`` / ``swa_rope_theta``, no
scaling, the pairs ``(2i, 2i + 1)`` de-interleaved and rotated by halves
as ``deepseek_v3``'s code does to queries and keys alike) on ``q_rope``
of every head and on the one ``k_rope`` a token; scores ``q . k /
sqrt(qk_nope_head_dim + qk_rope_head_dim)``.

``layer_types[i]`` ``full_attention``: **the indexer** (DeepSeek-V3.2-Exp's
published inference code, whose keys ``index_n_heads``,
``index_head_dim``, ``index_topk`` are): ``qI_j = W_qI,j c_q``; ``kI =
LayerNorm(W_kI x)`` (weight and bias, eps 1e-6), one key a token; RoPE on
the first ``qk_rope_head_dim`` dimensions of both (rotate-half pairs, not
interleaved, as that code has it); ``w = index_n_heads^-0.5
index_head_dim^-0.5 W_w x``; ``I[t, s] = sum_j w[t, j] relu(qI[t, j] .
kI[s])`` for ``s <= t``; ``S_t`` the ``min(t + 1, index_topk)`` keys of
largest ``I[t, .]`` (``top_k``: ties to the lower index); head h's
softmax runs over ``S_t`` alone. The indexer's inputs are detached: no
gradient reaches it or passes through it. ``sliding_attention``: no
indexer; query t sees keys ``t - sliding_window_size + 1 .. t``.
``attention_gate_type`` headwise: head h's output times
``sigmoid(W_g x)_h`` before ``W_o``. Then the feed-forward as
``models/kanana2_reference.py`` has it: dense SwiGLU in the first
``first_k_dense_replace`` layers, shared SwiGLU + routed experts
(sigmoid scores, ``num_experts_per_tok`` largest of score + bias, weights
renormalised over ``sum + 1e-20``, times ``routed_scaling_factor``) in
the others. Final RMSNorm, untied head, mean next-token cross-entropy.

Departures from the published description, in the program alike:
``apply_mla_qkv_lora_rescale`` is read as LongCat-Flash's
``mla_scale_q_lora`` / ``mla_scale_kv_lora`` (``r = sqrt(hidden_size /
rank)`` on the normed latents, the rotary key unscaled); the indexer's
Hadamard rotation (applied to both sides, every product as it was) and
its FP8 cast are left out; the indexer is not trained (DeepSeek-V3.2's
KL term is not in ``config.json``); the balancing rule that moves the
selection bias and any auxiliary loss are left out; the vision and audio
towers and the multi-token-prediction layer are not in ``config.json``
and are left out.

**A share.** The counts are read from the parameters, not from ``cfg``:
heads from ``attn_gate_proj_weight``, the router's width from
``moe_gate_weight``, the experts held from ``moe_down_weight`` (experts
``share.expert_offset`` .. onwards), the dense columns and the vocabulary
rows from their matrices. What the absent heads, experts and columns
would add is left out, as it is in the program. Both down-projections,
the indexer, the router and the shared expert are whole in every share.

Parameters are a dict under the names of the symbol's arguments
(``embed_weight``, ``layer0_q_a_proj_weight``, ``layer0_q_a_norm_gamma``,
``layer0_q_b_proj_weight``, ``layer0_kv_a_proj_weight``,
``layer0_attn_latent_gamma``, ``layer0_attn_up_weight``,
``layer0_attn_gate_proj_weight``, ``layer0_index_q_weight``,
``layer0_index_k_weight``, ``layer0_index_k_gamma``,
``layer0_index_k_beta``, ``layer0_index_head_weight``,
``layer0_o_proj_weight`` ..., ``lm_head_weight``; ``FullyConnected``
weights are ``[out, in]``). Host arrays are fine: a layer's parameters
are placed when the layer runs.
"""
import jax
import jax.numpy as jnp
import numpy as np

FULL, SLIDING = "full_attention", "sliding_attention"


def rms_norm(x, gamma, eps):
    return gamma * (x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps))


def layer_norm(x, gamma, beta, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return gamma * ((x - mean) * jax.lax.rsqrt(var + eps)) + beta


def rope(x, theta, interleave):
    """x [B, T, H, R], positions 0..T-1, every dimension rotated: ``x *
    cos + rotate_half(x) * sin``, after the de-interleave where
    ``interleave`` (the result stays in the de-interleaved order, as in
    the published code: done to queries and keys alike, the scores are
    those of rotating in place)."""
    t, r = x.shape[1], x.shape[3]
    if interleave:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    inv_freq = 1.0 / (theta ** (np.arange(0, r, 2, dtype=np.float64) / r))
    freqs = np.arange(t, dtype=np.float64)[:, None] * inv_freq[None, :]
    emb = np.concatenate([freqs, freqs], axis=-1)
    cos = jnp.asarray(np.cos(emb), x.dtype)[None, :, None, :]
    sin = jnp.asarray(np.sin(emb), x.dtype)[None, :, None, :]
    rotated = jnp.concatenate([-x[..., r // 2:], x[..., : r // 2]], axis=-1)
    return x * cos + rotated * sin


def index_scores(c_q, x, w_q, w_k, k_gamma, k_beta, w_heads, theta,
                 rope_dim, block=256):
    """The indexer's masked scores ``I`` [B, T, T] (-inf past the
    diagonal), a block of query rows at a time for all its heads."""
    b, t, _ = x.shape
    width, heads = w_k.shape[0], w_heads.shape[0]
    q = (c_q @ w_q.T).reshape(b, t, heads, width)
    k = layer_norm(x @ w_k.T, k_gamma, k_beta, 1e-6).reshape(b, t, 1, width)
    q = jnp.concatenate([rope(q[..., :rope_dim], theta, False),
                         q[..., rope_dim:]], axis=-1)
    k = jnp.concatenate([rope(k[..., :rope_dim], theta, False),
                         k[..., rope_dim:]], axis=-1)[:, :, 0]
    w = (x @ w_heads.T) * (heads ** -0.5 * width ** -0.5)     # [B, T, H]
    pos = np.arange(t)
    out = []
    for s in range(0, t, block):
        per_head = jax.nn.relu(
            jnp.einsum("bqhd,bkd->bqhk", q[:, s:s + block], k))
        scores = jnp.einsum("bqhk,bqh->bqk", per_head, w[:, s:s + block])
        mask = pos[s:s + block, None] >= pos[None, :]
        out.append(jnp.where(mask[None], scores, -jnp.inf))
    return jnp.concatenate(out, axis=1)


def select(scores, topk, near_tie_eps=None):
    """keep [B, T, T] bool: row t's ``min(t + 1, topk)`` keys of largest
    masked score, by ``jax.lax.top_k`` (ties to the lower index). With
    ``near_tie_eps`` also the share of (row, key) pairs, among the rows
    that choose (t >= topk), whose score lies within that distance of the
    row's ``topk``-th (that key itself not counted): the calls a lower
    precision may make either way."""
    t = scores.shape[-1]
    causal = jnp.asarray(np.tril(np.ones((t, t), bool)))[None]
    if t <= topk:
        keep = jnp.broadcast_to(causal, scores.shape)
        return (keep, 0.0) if near_tie_eps is not None else keep
    values, idx = jax.lax.top_k(scores, topk)
    rows = jnp.arange(t)[None, :, None]
    keep = jnp.zeros(scores.shape, bool).at[
        jnp.arange(scores.shape[0])[:, None, None], rows, idx].set(True)
    keep = keep & causal
    if near_tie_eps is None:
        return keep
    kth = values[..., -1:]
    chooses = (np.arange(t) >= topk)[None, :, None]
    near = (jnp.abs(scores.astype(jnp.float32) - kth.astype(jnp.float32))
            < near_tie_eps) & causal & chooses
    # the ``topk``-th key itself is at distance 0 in every such row
    rows = scores.shape[0] * (t - topk)
    share = (jnp.sum(near) - rows) / jnp.maximum(
        jnp.sum(causal & chooses) * scores.shape[0] - rows, 1)
    return keep, share


def attention(q, k, v, keep=None, window=0, block=256):
    """Causal softmax attention, q and k [B, T, H, D], v [B, T, H, Dv],
    scores materialised for ``block`` queries at a time; under ``keep``
    [B, T, T] over the kept keys alone, under ``window`` over the last
    ``window`` keys."""
    t, d = q.shape[1], q.shape[3]
    pos = np.arange(t)
    out = []
    for s in range(0, t, block):
        # a Python float: a numpy scalar would promote to float64
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, s:s + block], k) \
            * d ** -0.5
        rows = pos[s:s + block, None]
        mask = rows >= pos[None, :]
        if window:
            mask = mask & (rows - pos[None, :] < window)
        mask = jnp.asarray(mask)[None, None]
        if keep is not None:
            mask = mask & keep[:, None, s:s + block]
        scores = jnp.where(mask, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", probs, v))
    return jnp.concatenate(out, axis=1)


def geometry(cfg, kind):
    """(N, R, Dv, q latent, kv latent, theta) of a layer kind."""
    pre = "" if kind == FULL else "swa_"
    return tuple(cfg[pre + key] for key in (
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "q_lora_rank",
        "kv_lora_rank", "rope_theta"))


def latent_attention(x, p, n, cfg, kind, near_tie_eps=None):
    """The attention sub-layer of layer ``n`` (a name prefix) on the
    normed input x [B, T, d] -> ([B, T, d], selection stats or None)."""
    b, t, hidden = x.shape
    nope, r, dv, q_rank, kv_rank, theta = geometry(cfg, kind)
    eps = cfg["rms_norm_eps"]
    interleave = cfg.get("rope_interleave", True)
    rescale = cfg.get("apply_mla_qkv_lora_rescale", False)
    r_q = (hidden / q_rank) ** 0.5 if rescale else 1.0
    r_kv = (hidden / kv_rank) ** 0.5 if rescale else 1.0
    c_q = r_q * rms_norm(x @ p(n + "q_a_proj_weight").T,
                         p(n + "q_a_norm_gamma"), eps)
    q = (c_q @ p(n + "q_b_proj_weight").T).reshape(b, t, -1, nope + r)
    heads = q.shape[2]
    latent = x @ p(n + "kv_a_proj_weight").T
    c = r_kv * rms_norm(latent[..., :kv_rank],
                        p(n + "attn_latent_gamma"), eps)
    kv = (c @ p(n + "attn_up_weight").T).reshape(b, t, heads, nope + dv)
    k_rope = rope(latent[..., kv_rank:].reshape(b, t, 1, r), theta,
                  interleave)
    q = jnp.concatenate(
        [q[..., :nope], rope(q[..., nope:], theta, interleave)], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (b, t, heads, r))],
        axis=-1)
    keep = stats = None
    if kind == FULL:
        scores = index_scores(
            *(jax.lax.stop_gradient(a) for a in (
                c_q, x, p(n + "index_q_weight"), p(n + "index_k_weight"),
                p(n + "index_k_gamma"), p(n + "index_k_beta"),
                p(n + "index_head_weight"))), theta, r)
        if near_tie_eps is None:
            keep = select(scores, cfg["index_topk"])
        else:
            keep, share = select(scores, cfg["index_topk"], near_tie_eps)
            stats = {"near_tie_share": share}
        keep = jax.lax.stop_gradient(keep)
        if stats is not None:
            stats["keys_selected"] = jnp.sum(keep, axis=(1, 2))
    a = attention(q, k, kv[..., nope:], keep=keep,
                  window=0 if kind == FULL else cfg["sliding_window_size"])
    if cfg.get("attention_gate_type") == "headwise":
        gate = jax.nn.sigmoid(x @ p(n + "attn_gate_proj_weight").T)
        a = a * gate[..., None]
    return a.reshape(b, t, heads * dv) @ p(n + "o_proj_weight").T, stats


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate.T) * (x @ w_up.T)) @ w_down.T


def moe(x, gate_w, w_gate_up, w_down, select_bias, top_k, norm_topk_prob,
        scoring="sigmoid", offset=0, routed_scale=1.0):
    """x [N, d]; the router is ``gate_w`` [d, E], the experts held are
    E's ``offset`` .. ``offset + H - 1`` (``w_down`` [H, h, d]).
    Returns the held experts' part of the layer's output, the row count
    of each of the E experts, and each token's margin between its last
    chosen and its first rejected expert (selection scores) where one of
    the two is held here — +inf where neither is: that call cannot
    change this share's result."""
    num_experts = gate_w.shape[1]
    held, hidden = w_down.shape[0], w_down.shape[1]
    logits = x @ gate_w
    scores = (jax.nn.sigmoid(logits) if scoring == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    choose = scores if select_bias is None else scores + select_bias
    top_s, top_i = jax.lax.top_k(choose, min(top_k + 1, num_experts))
    if top_k < num_experts:
        edge = top_i[:, top_k - 1:] - offset                  # [N, 2]
        here = jnp.any((edge >= 0) & (edge < held), axis=-1)
        gap = jnp.where(here, top_s[:, top_k - 1] - top_s[:, top_k],
                        jnp.inf)
    else:
        gap = jnp.full(x.shape[:1], jnp.inf)
    top_i = top_i[:, :top_k]
    top_w = jnp.take_along_axis(scores, top_i, axis=-1)
    if norm_topk_prob:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
    top_w = top_w * routed_scale
    out = jnp.zeros_like(x)
    for e in range(held):
        chosen = top_i == offset + e                          # [N, k]
        weight = jnp.sum(jnp.where(chosen, top_w, 0), axis=-1)
        gate_up = x @ w_gate_up[e]
        y = (jax.nn.silu(gate_up[:, :hidden]) * gate_up[:, hidden:]) \
            @ w_down[e]
        out = out + y * weight[:, None]
    counts = jnp.sum(jax.nn.one_hot(top_i, num_experts, dtype=jnp.int32),
                     axis=(0, 1))
    return out, counts, gap


def expert_layers(cfg):
    """[expert layer?] per layer: the first ``first_k_dense_replace``
    are dense, then every ``moe_layer_freq``-th has experts (a list, one
    entry a layer, is taken as it stands)."""
    n, freq = cfg["num_hidden_layers"], cfg["moe_layer_freq"]
    if isinstance(freq, (list, tuple)):
        return [bool(e) for e in freq[:n]]
    return [i >= cfg["first_k_dense_replace"] and i % freq == 0
            for i in range(n)]


def forward(params, tokens, cfg, labels=None, dtype=jnp.float32,
            last=None, block=512):
    """tokens [B, T] int. Returns a dict: ``logits`` [B, last or T, V],
    ``expert_counts`` [expert layers, E], ``router_gap`` [expert layers,
    B*T] and, with ``labels`` [B, T], ``loss`` (mean token
    cross-entropy) and ``per_sequence`` [B]. Where ``cfg`` holds a dict
    ``select_report``, its ``eps`` is the selection's near-tie distance
    and the full layers' ``near_tie_share`` and ``keys_selected`` (lists,
    one entry a full layer) are written into it under the name of
    ``dtype``: what a caller that cannot reach this function's result
    reads. One layer at a time, and the head over ``block`` positions at
    a time, so the whole ``[T, V]`` table is never held."""
    def p(name):
        value = params[name]
        if hasattr(value, "asnumpy"):
            value = value.asnumpy()
        return jnp.asarray(value).astype(dtype)

    eps = cfg["rms_norm_eps"]
    offset = cfg.get("share", {}).get("expert_offset", 0)
    report = cfg.get("select_report")
    near_tie_eps = None if report is None else report["eps"]
    b, t = tokens.shape
    with jax.default_matmul_precision("highest"):
        h = p("embed_weight")[jnp.asarray(tokens, jnp.int32)]  # [B, T, d]
        counts, gaps, selections = [], [], []
        for i, (kind, experts) in enumerate(zip(cfg["layer_types"],
                                                expert_layers(cfg))):
            n = "layer%d_" % i
            x = rms_norm(h, p(n + "attn_norm_gamma"), eps)
            a, stats = latent_attention(x, p, n, cfg, kind, near_tie_eps)
            if stats is not None:
                selections.append(stats)
            h = h + a
            x = rms_norm(h, p(n + "ffn_norm_gamma"), eps)
            if not experts:
                h = h + swiglu(x, p(n + "gate_proj_weight"),
                               p(n + "up_proj_weight"),
                               p(n + "down_proj_weight"))
                continue
            y, count, gap = moe(
                x.reshape(b * t, -1), p(n + "moe_gate_weight"),
                p(n + "moe_gate_up_weight"), p(n + "moe_down_weight"),
                p(n + "moe_select_bias"), cfg["num_experts_per_tok"],
                cfg["norm_topk_prob"], cfg.get("scoring_func", "sigmoid"),
                offset, cfg.get("routed_scaling_factor") or 1.0)
            y = y.reshape(b, t, -1)
            if cfg.get("n_shared_experts"):
                y = y + swiglu(x, p(n + "shared_gate_proj_weight"),
                               p(n + "shared_up_proj_weight"),
                               p(n + "shared_down_proj_weight"))
            h = h + y
            counts.append(count)
            gaps.append(gap)
        if report is not None:
            report[jnp.dtype(dtype).name] = {
                "near_tie_share": [float(s["near_tie_share"])
                                   for s in selections],
                "keys_selected": [[int(v) for v in s["keys_selected"]]
                                  for s in selections]}
        h = rms_norm(h, p("final_norm_gamma"), eps)
        head = p("lm_head_weight")
        out = {"expert_counts": jnp.stack(counts),
               "router_gap": jnp.stack(gaps)}
        keep = t if last is None else last
        if labels is None:
            out["logits"] = h[:, t - keep:] @ head.T
            return out
        labels = jnp.asarray(labels, jnp.int32)
        nll, logits = [], []
        for s in range(0, t, block):
            z = h[:, s:s + block] @ head.T                    # [B, blk, V]
            logp = jax.nn.log_softmax(z, axis=-1)
            nll.append(-jnp.take_along_axis(
                logp, labels[:, s:s + block, None], axis=-1)[..., 0])
            lo = max(s, t - keep)
            if lo < s + block:
                logits.append(z[:, lo - s:])
        nll = jnp.concatenate(nll, axis=1)                    # [B, T]
        out["logits"] = jnp.concatenate(logits, axis=1)
        out["per_sequence"] = jnp.mean(nll, axis=1)
        out["loss"] = jnp.mean(nll)
        return out


def loss_and_grads(params, tokens, labels, cfg):
    """(mean token loss, {name: gradient}) in float32."""
    params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}

    def loss_fn(ps):
        return forward(ps, tokens, cfg, labels=labels)["loss"]

    return jax.value_and_grad(loss_fn)(params)


def sgd_momentum_step(params, momenta, grads, lr, momentum):
    """The reference's own update, the rule of ``sgd_mom_update`` without
    weight decay: ``m = momentum * m - lr * g``; ``w = w + m``."""
    momenta = {k: momentum * momenta[k] - lr * grads[k] for k in params}
    return {k: params[k] + momenta[k] for k in params}, momenta
