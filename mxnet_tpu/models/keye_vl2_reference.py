"""Plain reference for ``models/keye_vl2.py``: the forward pass, loss and
gradients of Keye-VL-2.0-30B-A3B's language model in straightforward
``jax.numpy``.

No kernel, no sort of the program's, no grouped matmul, no cache, no
keep-mask tiles: the key/value heads are repeated over their groups and
the scores are a ``[block, T]`` matrix a head with an explicit mask
(``block`` queries at a time, so that 8k positions fit a chip: a block's
rows are whole softmax rows, nothing is computed online), the indexer's
scores are materialised a block of rows at a time for all of its heads
and the selection is ``jax.lax.top_k`` on that block's masked index
scores, the expert layer is a loop over the experts held with a mask.
Everything is computed in ``dtype`` — float32 by default, under
``jax.default_matmul_precision("highest")`` so that a TPU does not
quietly run float32 matmuls in bf16 passes. ``dtype=jnp.bfloat16`` is the
same mathematics one precision below what any configuration of the
system states (router, norms, RoPE's tables, the indexer's scores and
compare, softmaxes and the loss in bf16 too): a comparison's tolerance
has to fail it.

It follows the published ``config.json`` (``model_type`` KeyeVL2) key by
key (``cfg`` below): the backbone's keys are those of a softmax-routed
expert model (``qwen3_moe``'s spelling), ``sa_config`` holds the sizes
of a DeepSeek-V3.2-Exp indexer. Per layer, ``x = RMSNorm(h)``
(``rms_norm_eps``):

    qI_j = W_qI,j x                      # indexer_num_heads of indexer_head_dim
    kI   = LayerNorm(W_kI x)             # ONE key a token, eps 1e-6
    qI, kI = RoPE(qI), RoPE(kI)          # the whole 64, rotate-half pairs
    w    = H_I^-0.5 D_I^-0.5 W_w x
    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])          for s <= t
    S_t  = the min(t + 1, topk) keys of largest I[t, .], ties to the lower s
    q, k, v = W_q x, W_k x, W_v x        # 32 heads on 4 of head_dim 128
    q, k = RMSNorm_head(q), RMSNorm_head(k);  q, k = RoPE(q), RoPE(k)
    o_i[t] = softmax_{s in S_t}(q_i[t] . k_g[s] / sqrt(head_dim)) v_g
    h = h + W_o o
    n = RMSNorm(h)
    h = h + sum_{e in top8(softmax(W_r n))} p_e / sum p . SwiGLU_e(n)

ONE selection a token serves all the heads. The indexer's inputs are
detached: no gradient reaches it or passes through it. Then the final
RMSNorm, the untied head, mean next-token cross-entropy.

Departures from the published description, in the program alike
(``bench/configs/keye_vl2_30b_a3b.json`` lists each under ``assumed``
with its source): the indexer is DeepSeek-V3.2-Exp's published
``Indexer`` with its query taken from the block's normed input (this
model has no query latent) and the rotation over the whole 64-wide head;
its Hadamard rotation (applied to both sides, every product as it was)
and its FP8 cast are left out; the indexer is not trained (no KL term is
in ``config.json``); ``q_chunk_size`` / ``kv_chunk_size`` are read as the
blocks the published implementation evaluates in and change no
arithmetic; ``mrope_section`` on text tokens, whose three position ids
are equal, is plain RoPE over the whole head; the vision tower is not in
``config.json``'s language-model keys and is left out.

**A share.** The counts are read from the parameters, not from ``cfg``:
the router's width from ``moe_gate_weight``, the experts held from
``moe_down_weight`` (experts ``share.expert_offset`` .. onwards), the
vocabulary rows from their matrices. What the absent experts would add is
left out, as it is in the program. Attention, the indexer and the router
are whole in every share.

Parameters are a dict under the names of the symbol's arguments
(``embed_weight``, ``layer0_attn_norm_gamma``, ``layer0_q_proj_weight``,
``layer0_q_norm_gamma``, ``layer0_k_proj_weight``,
``layer0_k_norm_gamma``, ``layer0_v_proj_weight``,
``layer0_index_q_weight``, ``layer0_index_k_weight``,
``layer0_index_k_gamma``, ``layer0_index_k_beta``,
``layer0_index_head_weight``, ``layer0_o_proj_weight``,
``layer0_ffn_norm_gamma``, ``layer0_moe_gate_weight``,
``layer0_moe_gate_up_weight``, ``layer0_moe_down_weight`` ...,
``final_norm_gamma``, ``lm_head_weight``; ``FullyConnected`` weights are
``[out, in]``). Host arrays are fine: a layer's parameters are placed
when the layer runs.
"""
import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x, gamma, eps):
    return gamma * (x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps))


def layer_norm(x, gamma, beta, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return gamma * ((x - mean) * jax.lax.rsqrt(var + eps)) + beta


def rope(x, theta):
    """x [B, T, H, R], positions 0..T-1, every dimension rotated by the
    rotate-half pairs (i, i + R / 2): ``x * cos + rotate_half(x) * sin``."""
    t, r = x.shape[1], x.shape[3]
    inv_freq = 1.0 / (theta ** (np.arange(0, r, 2, dtype=np.float64) / r))
    freqs = np.arange(t, dtype=np.float64)[:, None] * inv_freq[None, :]
    emb = np.concatenate([freqs, freqs], axis=-1)
    cos = jnp.asarray(np.cos(emb), x.dtype)[None, :, None, :]
    sin = jnp.asarray(np.sin(emb), x.dtype)[None, :, None, :]
    rotated = jnp.concatenate([-x[..., r // 2:], x[..., : r // 2]], axis=-1)
    return x * cos + rotated * sin


def select(x, w_q, w_k, k_gamma, k_beta, w_heads, theta, topk,
           near_tie_eps=None, block=256):
    """The indexer on the normed input x [B, T, d] -> (keep [B, T, T]
    bool, the near-tie share or None): row t's ``min(t + 1, topk)`` keys
    of largest index score by ``jax.lax.top_k`` (ties to the lower
    index), a block of query rows at a time for all the indexer's heads.
    With ``near_tie_eps`` also the share of (row, key) pairs, among the
    rows that choose (t >= topk), whose score lies within that distance
    of the row's ``topk``-th (that key itself not counted): the calls a
    lower precision may make either way."""
    b, t, _ = x.shape
    width, heads = w_k.shape[0], w_heads.shape[0]
    q = rope((x @ w_q.T).reshape(b, t, heads, width), theta)
    k = rope(layer_norm(x @ w_k.T, k_gamma, k_beta, 1e-6).reshape(
        b, t, 1, width), theta)[:, :, 0]
    w = (x @ w_heads.T) * (heads ** -0.5 * width ** -0.5)     # [B, T, H]
    pos = np.arange(t)
    keep, near, pairs = [], 0.0, 0
    for s in range(0, t, block):
        rows = pos[s:s + block]
        per_head = jax.nn.relu(
            jnp.einsum("bqhd,bkd->bqhk", q[:, s:s + block], k))
        scores = jnp.einsum("bqhk,bqh->bqk", per_head, w[:, s:s + block])
        causal = jnp.asarray(rows[:, None] >= pos[None, :])[None]
        scores = jnp.where(causal, scores, -jnp.inf)
        if t <= topk:
            keep.append(jnp.broadcast_to(causal, scores.shape))
            continue
        values, idx = jax.lax.top_k(scores, topk)
        chosen = jnp.zeros(scores.shape, bool).at[
            jnp.arange(b)[:, None, None], jnp.arange(len(rows))[None, :, None],
            idx].set(True)
        keep.append(chosen & causal)
        if near_tie_eps is None:
            continue
        chooses = jnp.asarray(rows >= topk)[None, :, None]
        close = (jnp.abs(scores.astype(jnp.float32)
                         - values[..., -1:].astype(jnp.float32))
                 < near_tie_eps) & causal & chooses
        # the ``topk``-th key itself is at distance 0 in every such row
        chosen_rows = b * int(np.sum(rows >= topk))
        near = near + jnp.sum(close) - chosen_rows
        pairs += b * int(np.sum((rows[:, None] >= pos[None, :])
                                & (rows >= topk)[:, None])) - chosen_rows
    keep = jnp.concatenate(keep, axis=1)
    if near_tie_eps is None:
        return keep, None
    return keep, near / max(pairs, 1)


def attention(q, k, v, keep, block=256):
    """Causal softmax attention over the kept keys alone: q [B, T, H, D],
    k and v [B, T, G, D] (query head i reads key/value head ``i // (H /
    G)``), keep [B, T, T]; scores materialised for ``block`` queries at a
    time."""
    t, heads, d = q.shape[1], q.shape[2], q.shape[3]
    group = heads // k.shape[2]
    k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
    pos = np.arange(t)
    out = []
    for s in range(0, t, block):
        # a Python float: a numpy scalar would promote to float64
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, s:s + block], k) \
            * d ** -0.5
        mask = jnp.asarray(pos[s:s + block, None] >= pos[None, :])[None, None]
        mask = mask & keep[:, None, s:s + block]
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", probs, v))
    return jnp.concatenate(out, axis=1)


def sparse_attention(x, p, n, cfg, near_tie_eps=None):
    """The attention sub-layer of layer ``n`` (a name prefix) on the
    normed input x [B, T, d] -> ([B, T, d], selection stats or None)."""
    b, t, _ = x.shape
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, eps, theta = cfg["head_dim"], cfg["rms_norm_eps"], cfg["rope_theta"]

    def head_normed(name, count):
        y = (x @ p(n + name + "_proj_weight").T).reshape(b, t, count, d)
        return rope(rms_norm(y, p(n + name + "_norm_gamma"), eps), theta)

    keep, share = select(
        *(jax.lax.stop_gradient(a) for a in (
            x, p(n + "index_q_weight"), p(n + "index_k_weight"),
            p(n + "index_k_gamma"), p(n + "index_k_beta"),
            p(n + "index_head_weight"))), theta, cfg["sa_config"]["topk"],
        near_tie_eps)
    keep = jax.lax.stop_gradient(keep)
    stats = None if near_tie_eps is None else {
        "near_tie_share": share, "keys_selected": jnp.sum(keep, axis=(1, 2))}
    a = attention(head_normed("q", heads), head_normed("k", kv_heads),
                  (x @ p(n + "v_proj_weight").T).reshape(b, t, kv_heads, d),
                  keep)
    return a.reshape(b, t, heads * d) @ p(n + "o_proj_weight").T, stats


def moe(x, gate_w, w_gate_up, w_down, top_k, norm_topk_prob, offset=0):
    """x [N, d]; the router is ``gate_w`` [d, E], the experts held are
    E's ``offset`` .. ``offset + H - 1`` (``w_down`` [H, h, d]). Softmax
    over all E, the ``top_k`` largest chosen, their probabilities
    renormalised under ``norm_topk_prob``. Returns the held experts' part
    of the layer's output, the row count of each of the E experts, and
    each token's margin between its last chosen and its first rejected
    expert's probability where one of the two is held here — +inf where
    neither is: that call cannot change this share's result."""
    num_experts = gate_w.shape[1]
    held, hidden = w_down.shape[0], w_down.shape[1]
    probs = jax.nn.softmax(x @ gate_w, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, min(top_k + 1, num_experts))
    if top_k < num_experts:
        edge = top_i[:, top_k - 1:] - offset                  # [N, 2]
        here = jnp.any((edge >= 0) & (edge < held), axis=-1)
        gap = jnp.where(here, top_p[:, top_k - 1] - top_p[:, top_k],
                        jnp.inf)
    else:
        gap = jnp.full(x.shape[:1], jnp.inf)
    top_p, top_i = top_p[:, :top_k], top_i[:, :top_k]
    if norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    out = jnp.zeros_like(x)
    for e in range(held):
        chosen = top_i == offset + e                          # [N, k]
        weight = jnp.sum(jnp.where(chosen, top_p, 0), axis=-1)
        gate_up = x @ w_gate_up[e]
        y = (jax.nn.silu(gate_up[:, :hidden]) * gate_up[:, hidden:]) \
            @ w_down[e]
        out = out + y * weight[:, None]
    counts = jnp.sum(jax.nn.one_hot(top_i, num_experts, dtype=jnp.int32),
                     axis=(0, 1))
    return out, counts, gap


def expert_layers(cfg):
    """[expert layer?] per layer: every ``decoder_sparse_step``-th layer
    that ``mlp_only_layers`` does not name (``qwen3_moe``'s rule; the
    published values make every layer one)."""
    step = cfg.get("decoder_sparse_step", 1)
    dense = set(cfg.get("mlp_only_layers") or ())
    return [i not in dense and (i + 1) % step == 0
            for i in range(cfg["num_hidden_layers"])]


def forward(params, tokens, cfg, labels=None, dtype=jnp.float32,
            last=None, block=512):
    """tokens [B, T] int. Returns a dict: ``logits`` [B, last or T, V],
    ``expert_counts`` [layers, E], ``router_gap`` [layers, B*T] and, with
    ``labels`` [B, T], ``loss`` (mean token cross-entropy) and
    ``per_sequence`` [B]. Where ``cfg`` holds a dict ``select_report``,
    its ``eps`` is the selection's near-tie distance and the layers'
    ``near_tie_share`` and ``keys_selected`` (lists, one entry a layer)
    are written into it under the name of ``dtype``: what a caller that
    cannot reach this function's result reads. One layer at a time, and
    the head over ``block`` positions at a time, so the whole ``[T, V]``
    table is never held."""
    def p(name):
        value = params[name]
        if hasattr(value, "asnumpy"):
            value = value.asnumpy()
        return jnp.asarray(value).astype(dtype)

    if not all(expert_layers(cfg)):
        raise ValueError("keye_vl2_reference: a layer without experts is "
                         "not built (decoder_sparse_step, mlp_only_layers)")
    eps = cfg["rms_norm_eps"]
    offset = cfg.get("share", {}).get("expert_offset", 0)
    report = cfg.get("select_report")
    near_tie_eps = None if report is None else report["eps"]
    b, t = tokens.shape
    with jax.default_matmul_precision("highest"):
        h = p("embed_weight")[jnp.asarray(tokens, jnp.int32)]  # [B, T, d]
        counts, gaps, selections = [], [], []
        for i in range(cfg["num_hidden_layers"]):
            n = "layer%d_" % i
            a, stats = sparse_attention(
                rms_norm(h, p(n + "attn_norm_gamma"), eps), p, n, cfg,
                near_tie_eps)
            if stats is not None:
                selections.append(stats)
            h = h + a
            x = rms_norm(h, p(n + "ffn_norm_gamma"), eps)
            y, count, gap = moe(
                x.reshape(b * t, -1), p(n + "moe_gate_weight"),
                p(n + "moe_gate_up_weight"), p(n + "moe_down_weight"),
                cfg["num_experts_per_tok"], cfg["norm_topk_prob"], offset)
            h = h + y.reshape(b, t, -1)
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
