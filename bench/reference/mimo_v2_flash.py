"""Plain reference for ``models/mimo_v2.py``: MiMo-V2-Flash's forward
pass, loss and gradients in straightforward ``jax.numpy``.

No kernel, no sort, no grouped matmul, no cache: attention over a
materialised ``[T, T]`` score matrix with an explicit mask (causal, and
in window layers ``i - j < sliding_window``), the sink as one more
column of the softmax, grouped heads by repeating the key/value heads,
the expert layer as a loop over the experts held with a mask.
Everything is computed in ``dtype`` — float32 by default, under
``jax.default_matmul_precision("highest")`` so that a TPU does not
quietly run float32 matmuls in bf16 passes. ``dtype=jnp.bfloat16`` is
the same mathematics one precision below what any configuration of the
system states (router, norms, softmaxes, sink and the loss in bf16 too):
a comparison's tolerance has to fail it.

It follows the published ``config.json`` key by key (``cfg`` below).
Layer ``l`` is a window layer where ``hybrid_layer_pattern[l]`` is 1
(``swa_*`` head counts and widths, ``swa_rope_theta``, the window, the
sink where ``add_swa_attention_sink_bias``) and a full layer where it is
0; it has experts where ``moe_layer_freq[l]`` is 1 and a dense SwiGLU
otherwise. ``x = RMSNorm(h)`` (``layernorm_epsilon``); ``q = W_q x``,
``k = W_k x``, ``v = attention_value_scale * W_v x``; RoPE on the first
``int(head_dim * partial_rotary_factor)`` dimensions of each query and
key head (rounded down to even); scores ``q . k / sqrt(head_dim)``;
``h += W_o attn``. Then ``x = RMSNorm(h)`` and ``h += W_d (silu(W_g x)
* W_u x)`` or the expert layer: ``z = sigmoid(W_r x)``
(``scoring_func``), the ``num_experts_per_tok`` largest of ``z + b``
chosen (``topk_method`` noaux_tc, ``n_group`` 1: ``b`` moves the choice
and nothing else), weights ``z`` over the chosen, renormalised
(``norm_topk_prob``). Final RMSNorm, untied head, mean next-token
cross-entropy.

**A share.** The counts are read from the parameters, not from ``cfg``:
heads from the projections' rows, dense columns from ``gate_proj``, the
router's width from ``moe_gate_weight`` and the experts held from
``moe_down_weight``. Where a layer holds H of the router's E experts
they are experts ``share.expert_offset`` .. ``+ H - 1`` (0 without the
key), and the layer's result is their part alone: what the absent
experts would add is left out, as it is in the program, and so is what
absent heads and dense columns would add to their projections' sums.

Left out, in the program alike: the multi-token-prediction layers (not
in ``config``), the balancing rule that moves ``b`` (it stays where it
is given: zeros), and any auxiliary loss.

Parameters are a dict under the names of the symbol's arguments
(``embed_weight``, ``layer0_q_proj_weight`` ..., ``layer1_attn_sink``,
``layer1_moe_select_bias``, ``lm_head_weight``; ``FullyConnected``
weights are ``[out, in]``). Host arrays are fine: a layer's parameters
are placed when the layer runs, so an un-jitted call holds one layer's
float32 weights at a time.
"""
import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x, gamma, eps):
    return gamma * (x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps))


def rope(x, theta, rotary_dim):
    """x [B, T, H, D], positions 0..T-1: the published ``rotate_half``
    form, ``x * cos + rotate_half(x) * sin``, on the first
    ``rotary_dim`` dimensions of each head; the rest pass through."""
    t, r = x.shape[1], rotary_dim
    inv_freq = 1.0 / (theta ** (np.arange(0, r, 2, dtype=np.float64) / r))
    freqs = np.arange(t, dtype=np.float64)[:, None] * inv_freq[None, :]
    emb = np.concatenate([freqs, freqs], axis=-1)
    cos = jnp.asarray(np.cos(emb), x.dtype)[None, :, None, :]
    sin = jnp.asarray(np.sin(emb), x.dtype)[None, :, None, :]
    rot, rest = x[..., :r], x[..., r:]
    rotated = jnp.concatenate([-rot[..., r // 2:], rot[..., : r // 2]],
                              axis=-1)
    return jnp.concatenate([rot * cos + rotated * sin, rest], axis=-1)


def attention(q, k, v, window=0, sink=None):
    """Causal softmax attention, q [B, T, H, D], k [B, T, G, D],
    v [B, T, G, Dv], scores materialised. ``window`` w > 0: query i sees
    keys i-w+1 .. i. ``sink`` [H]: a logit that joins each row's
    softmax and carries no value."""
    t, h, d = q.shape[1], q.shape[2], q.shape[3]
    group = h // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    # a Python float: a numpy scalar would promote to float64 under x64
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
    pos = np.arange(t)
    mask = pos[:, None] >= pos[None, :]
    if window:
        mask &= pos[:, None] - pos[None, :] < window
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    if sink is not None:
        scores = jnp.concatenate([scores, jnp.broadcast_to(
            sink[None, :, None, None], scores.shape[:3] + (1,))], axis=-1)
    probs = jax.nn.softmax(scores, axis=-1)[..., :t]
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def moe(x, gate_w, w_gate_up, w_down, select_bias, top_k, norm_topk_prob,
        scoring="sigmoid", offset=0):
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
    select = scores if select_bias is None else scores + select_bias
    top_s, top_i = jax.lax.top_k(select, min(top_k + 1, num_experts))
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
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
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


def rotary_dim(cfg, head_dim):
    """``int(head_dim * partial_rotary_factor)``, down to even."""
    r = int(head_dim * cfg.get("partial_rotary_factor", 1.0))
    return r - r % 2


def layer_kinds(cfg):
    """[(window layer?, expert layer?)] per layer."""
    n = cfg["num_hidden_layers"]
    return list(zip(map(bool, cfg["hybrid_layer_pattern"][:n]),
                    map(bool, cfg["moe_layer_freq"][:n])))


def forward(params, tokens, cfg, labels=None, dtype=jnp.float32,
            last=None, block=512):
    """tokens [B, T] int. Returns a dict: ``logits`` [B, last or T, V],
    ``expert_counts`` [expert layers, E], ``router_gap`` [expert layers,
    B*T] and, with ``labels`` [B, T], ``loss`` (mean token
    cross-entropy) and ``per_sequence`` [B]. One layer at a time, and
    the head over ``block`` positions at a time, so the whole ``[T,
    V]`` table is never held."""
    def p(name):
        value = params[name]
        if hasattr(value, "asnumpy"):
            value = value.asnumpy()
        return jnp.asarray(value).astype(dtype)

    eps = cfg["layernorm_epsilon"]
    offset = cfg.get("share", {}).get("expert_offset", 0)
    b, t = tokens.shape
    with jax.default_matmul_precision("highest"):
        h = p("embed_weight")[jnp.asarray(tokens, jnp.int32)]  # [B, T, d]
        counts, gaps = [], []
        for i, (windowed, experts) in enumerate(layer_kinds(cfg)):
            n = "layer%d_" % i
            pre = "swa_" if windowed else ""
            d, dv = cfg[pre + "head_dim"], cfg[pre + "v_head_dim"]
            theta = cfg["swa_rope_theta" if windowed else "rope_theta"]
            sink = cfg["add_swa_attention_sink_bias" if windowed
                       else "add_full_attention_sink_bias"]
            x = rms_norm(h, p(n + "attn_norm_gamma"), eps)
            q = (x @ p(n + "q_proj_weight").T).reshape(b, t, -1, d)
            k = (x @ p(n + "k_proj_weight").T).reshape(b, t, -1, d)
            v = (x @ p(n + "v_proj_weight").T).reshape(b, t, -1, dv)
            v = v * cfg.get("attention_value_scale", 1.0)
            r = rotary_dim(cfg, d)
            a = attention(
                rope(q, theta, r), rope(k, theta, r), v,
                window=cfg["sliding_window"] if windowed else 0,
                sink=p(n + "attn_sink") if sink else None)
            h = h + a.reshape(b, t, -1) @ p(n + "o_proj_weight").T
            x = rms_norm(h, p(n + "ffn_norm_gamma"), eps)
            if experts:
                y, count, gap = moe(
                    x.reshape(b * t, -1), p(n + "moe_gate_weight"),
                    p(n + "moe_gate_up_weight"), p(n + "moe_down_weight"),
                    p(n + "moe_select_bias"), cfg["num_experts_per_tok"],
                    cfg["norm_topk_prob"], cfg["scoring_func"], offset)
                h = h + y.reshape(b, t, -1)
                counts.append(count)
                gaps.append(gap)
            else:
                gate = jax.nn.silu(x @ p(n + "gate_proj_weight").T)
                h = h + (gate * (x @ p(n + "up_proj_weight").T)) \
                    @ p(n + "down_proj_weight").T
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
