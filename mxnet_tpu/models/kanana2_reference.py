"""Plain reference for ``models/kanana2.py``: Kanana-2-30B-A3B's forward
pass, loss and gradients in straightforward ``jax.numpy``.

No kernel, no sort, no grouped matmul, no cache, no absorbed
projection: keys and values of every head are projected up from the
normalised latent and materialised, the scores are a ``[block, T]``
matrix a head with an explicit causal mask (``block`` queries at a time,
so that 8k positions fit a chip: a block's rows are whole softmax rows,
nothing is computed online), the expert layer is a loop over the experts
held with a mask. Everything is computed in ``dtype`` — float32 by
default, under ``jax.default_matmul_precision("highest")`` so that a TPU
does not quietly run float32 matmuls in bf16 passes.
``dtype=jnp.bfloat16`` is the same mathematics one precision below what
any configuration of the system states (router, norms, the latent's
norm, RoPE's tables, softmaxes and the loss in bf16 too): a comparison's
tolerance has to fail it.

It follows the published ``config.json`` (``model_type`` deepseek_v3)
key by key (``cfg`` below) and the published code's order. ``x =
RMSNorm(h)`` (``rms_norm_eps``); ``q = W_q x`` (``q_lora_rank`` null: no
query latent), a head's ``qk_nope_head_dim`` un-rotated dimensions then
its ``qk_rope_head_dim`` rotary ones; ``(c, k_rope) = W_dkv x``
(``kv_lora_rank`` + ``qk_rope_head_dim``); ``(k_nope_h, v_h) = W_ukv
RMSNorm(c)``, a head's ``qk_nope_head_dim + v_head_dim`` outputs split
in that order. RoPE (``rope_theta``, no scaling) on ``q_rope`` of every
head and on the one ``k_rope`` a token: with ``rope_interleave`` the
pairs are ``(2i, 2i + 1)``, which the published code de-interleaves
(``[x0, x2, ..., x1, x3, ...]``) before it applies ``x * cos +
rotate_half(x) * sin``; done to queries and keys alike, so the scores
are those of rotating in place. ``k_h = [k_nope_h, k_rope]``; scores
``q . k / sqrt(qk_nope_head_dim + qk_rope_head_dim)``, causal; ``h +=
W_o attn``. Then ``x = RMSNorm(h)`` and, in the first
``first_k_dense_replace`` layers, ``h += W_d (silu(W_g x) * W_u x)``; in
the others ``z = sigmoid(W_r x)`` (``scoring_func``), the
``num_experts_per_tok`` largest of ``z + b`` chosen (``topk_method``
noaux_tc, ``n_group`` 1: ``b`` moves the choice and nothing else),
weights ``z`` over the chosen, renormalised over ``sum + 1e-20``
(``norm_topk_prob``) and times ``routed_scaling_factor``; ``h +=
shared(x) + routed(x)``, the shared experts one SwiGLU of
``n_shared_experts * moe_intermediate_size``. Final RMSNorm, untied
head, mean next-token cross-entropy.

**A share.** The counts are read from the parameters, not from ``cfg``:
the router's width from ``moe_gate_weight`` and the experts held from
``moe_down_weight``. Where a layer holds H of the router's E experts
they are experts ``share.expert_offset`` .. ``+ H - 1`` (0 without the
key), and the routed part of the layer's result is theirs alone: what
the absent experts would add is left out, as it is in the program.
Attention, the shared experts and the dense layer are whole in every
share.

Left out, in the program alike: the balancing rule that moves ``b`` (it
stays where it is given: zeros) and any auxiliary loss.

Parameters are a dict under the names of the symbol's arguments
(``embed_weight``, ``layer0_q_proj_weight``, ``layer0_kv_a_proj_weight``,
``layer0_attn_latent_gamma``, ``layer0_attn_up_weight``,
``layer1_shared_gate_proj_weight`` ..., ``layer1_moe_select_bias``,
``lm_head_weight``; ``FullyConnected`` weights are ``[out, in]``). Host
arrays are fine: a layer's parameters are placed when the layer runs, so
an un-jitted call holds one layer's float32 weights at a time.
"""
import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x, gamma, eps):
    return gamma * (x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps))


def rope(x, theta, interleave):
    """x [B, T, H, R], positions 0..T-1, every dimension rotated: the
    published form, ``x * cos + rotate_half(x) * sin``, after the
    de-interleave where ``interleave`` (the result stays in the
    de-interleaved order, as in the published code)."""
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


def attention(q, k, v, block=256):
    """Causal softmax attention, q and k [B, T, H, D], v [B, T, H, Dv],
    scores materialised for ``block`` queries at a time."""
    t, d = q.shape[1], q.shape[3]
    pos = np.arange(t)
    out = []
    for s in range(0, t, block):
        # a Python float: a numpy scalar would promote to float64
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, s:s + block], k) \
            * d ** -0.5
        mask = pos[s:s + block, None] >= pos[None, :]
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", probs, v))
    return jnp.concatenate(out, axis=1)


def latent_attention(q, latent, gamma, w_up, cfg):
    """q [B, T, H * (N + R)], latent [B, T, L + R] -> [B, T, H * Dv]."""
    b, t, _ = q.shape
    n, r = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, width = cfg["v_head_dim"], cfg["kv_lora_rank"]
    theta = cfg["rope_theta"]
    interleave = cfg.get("rope_interleave", False)
    q = q.reshape(b, t, -1, n + r)
    heads = q.shape[2]
    c = rms_norm(latent[..., :width], gamma, cfg["rms_norm_eps"])
    kv = (c @ w_up.T).reshape(b, t, heads, n + dv)
    k_rope = rope(latent[..., width:].reshape(b, t, 1, r), theta, interleave)
    q = jnp.concatenate(
        [q[..., :n], rope(q[..., n:], theta, interleave)], axis=-1)
    k = jnp.concatenate(
        [kv[..., :n], jnp.broadcast_to(k_rope, (b, t, heads, r))], axis=-1)
    return attention(q, k, kv[..., n:]).reshape(b, t, heads * dv)


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
    cross-entropy) and ``per_sequence`` [B]. One layer at a time, and
    the head over ``block`` positions at a time, so the whole ``[T,
    V]`` table is never held."""
    def p(name):
        value = params[name]
        if hasattr(value, "asnumpy"):
            value = value.asnumpy()
        return jnp.asarray(value).astype(dtype)

    eps = cfg["rms_norm_eps"]
    offset = cfg.get("share", {}).get("expert_offset", 0)
    b, t = tokens.shape
    with jax.default_matmul_precision("highest"):
        h = p("embed_weight")[jnp.asarray(tokens, jnp.int32)]  # [B, T, d]
        counts, gaps = [], []
        for i, experts in enumerate(expert_layers(cfg)):
            n = "layer%d_" % i
            x = rms_norm(h, p(n + "attn_norm_gamma"), eps)
            a = latent_attention(
                x @ p(n + "q_proj_weight").T, x @ p(n + "kv_a_proj_weight").T,
                p(n + "attn_latent_gamma"), p(n + "attn_up_weight"), cfg)
            h = h + a @ p(n + "o_proj_weight").T
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
                cfg["norm_topk_prob"], cfg["scoring_func"], offset,
                cfg.get("routed_scaling_factor") or 1.0)
            y = y.reshape(b, t, -1)
            if cfg.get("n_shared_experts"):
                y = y + swiglu(x, p(n + "shared_gate_proj_weight"),
                               p(n + "shared_up_proj_weight"),
                               p(n + "shared_down_proj_weight"))
            h = h + y
            counts.append(count)
            gaps.append(gap)
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
