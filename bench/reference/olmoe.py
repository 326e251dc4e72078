"""Plain reference for ``models/olmoe.py``: OLMoE's forward pass, loss
and gradients in straightforward ``jax.numpy``.

No kernel, no sort, no grouped matmul, no cache: causal attention over
a materialised ``[T, T]`` score matrix, the expert layer as a loop over
all experts with a mask. Everything is computed in ``dtype`` — float32
by default, under ``jax.default_matmul_precision("highest")`` so that a
TPU does not quietly run float32 matmuls in bf16 passes.
``dtype=jnp.bfloat16`` is the same mathematics one precision below
what any configuration of the system states (router, norms, softmaxes
and the loss in bf16 too): a comparison's tolerance has to fail it.

It follows the published ``config.json`` key by key (``cfg`` below):
``hidden_size``, ``num_attention_heads`` (= ``num_key_value_heads``:
plain multi-head attention), ``num_hidden_layers``, ``num_experts``,
``num_experts_per_tok``, ``intermediate_size`` (one expert's width),
``norm_topk_prob``, ``rms_norm_eps``, ``rope_theta``, ``vocab_size``;
``hidden_act`` silu, no biases, no ``clip_qkv``, untied head, RMSNorm
on the projected queries and keys over the full hidden width.
Departures from the published training job, shared with the system:
the router's load-balancing and z losses are left out of the
objective; the router is float32 when ``dtype`` is (it is not forced
above ``dtype``).

Parameters are a dict under the names of the symbol's arguments
(``embed_weight``, ``layer0_q_proj_weight`` ..., ``lm_head_weight``;
``FullyConnected`` weights are ``[out, in]``). Host arrays are fine: a
layer's parameters are placed when the layer runs, so an un-jitted call
holds one layer's float32 weights at a time.
"""
import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x, gamma, eps):
    return gamma * (x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps))


def rope(x, theta):
    """x [B, T, H, D], positions 0..T-1, the published ``rotate_half``
    form: ``x * cos + rotate_half(x) * sin``."""
    t, d = x.shape[1], x.shape[3]
    inv_freq = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    freqs = np.arange(t, dtype=np.float64)[:, None] * inv_freq[None, :]
    emb = np.concatenate([freqs, freqs], axis=-1)
    cos = jnp.asarray(np.cos(emb), x.dtype)[None, :, None, :]
    sin = jnp.asarray(np.sin(emb), x.dtype)[None, :, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], axis=-1)
    return x * cos + rotated * sin


def attention(q, k, v):
    """Causal softmax attention, [B, T, H, D], scores materialised."""
    t, d = q.shape[1], q.shape[3]
    # a Python float: a numpy scalar would promote to float64 under x64
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
    mask = np.tril(np.ones((t, t), bool))
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)


def moe(x, gate_w, w_gate_up, w_down, top_k, norm_topk_prob):
    """x [N, d]. Returns the layer's output, each expert's row count and
    each token's margin between its last chosen and first rejected
    expert (router probabilities; +inf when every expert is chosen)."""
    num_experts, hidden = w_down.shape[0], w_down.shape[1]
    probs = jax.nn.softmax(x @ gate_w, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, min(top_k + 1, num_experts))
    gap = (top_p[:, top_k - 1] - top_p[:, top_k]
           if top_k < num_experts else jnp.full(x.shape[:1], jnp.inf))
    top_p, top_i = top_p[:, :top_k], top_i[:, :top_k]
    if norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    out = jnp.zeros_like(x)
    counts = []
    for e in range(num_experts):
        chosen = top_i == e                                   # [N, k]
        weight = jnp.sum(jnp.where(chosen, top_p, 0), axis=-1)
        gate_up = x @ w_gate_up[e]
        y = (jax.nn.silu(gate_up[:, :hidden]) * gate_up[:, hidden:]) \
            @ w_down[e]
        out = out + y * weight[:, None]
        counts.append(jnp.sum(chosen))
    return out, jnp.stack(counts), gap


def forward(params, tokens, cfg, labels=None, dtype=jnp.float32,
            last=None, block=512):
    """tokens [B, T] int. Returns a dict: ``logits`` [B, last or T, V],
    ``expert_counts`` [L, E], ``router_gap`` [L, B*T] and, with
    ``labels`` [B, T], ``loss`` (mean token cross-entropy) and
    ``per_sequence`` [B]. The head runs over ``block`` positions at a
    time, so the whole ``[T, V]`` table is never held."""
    def p(name):
        value = params[name]
        if hasattr(value, "asnumpy"):
            value = value.asnumpy()
        return jnp.asarray(value).astype(dtype)

    hidden, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    b, t = tokens.shape
    with jax.default_matmul_precision("highest"):
        h = p("embed_weight")[jnp.asarray(tokens, jnp.int32)]  # [B, T, d]
        counts, gaps = [], []
        for i in range(cfg["num_hidden_layers"]):
            n = "layer%d_" % i
            x = rms_norm(h, p(n + "attn_norm_gamma"), eps)
            q = rms_norm(x @ p(n + "q_proj_weight").T,
                         p(n + "q_norm_gamma"), eps)
            k = rms_norm(x @ p(n + "k_proj_weight").T,
                         p(n + "k_norm_gamma"), eps)
            v = x @ p(n + "v_proj_weight").T
            split = (b, t, heads, hidden // heads)
            a = attention(rope(q.reshape(split), theta),
                          rope(k.reshape(split), theta), v.reshape(split))
            h = h + a.reshape(b, t, hidden) @ p(n + "o_proj_weight").T
            x = rms_norm(h, p(n + "ffn_norm_gamma"), eps)
            y, count, gap = moe(
                x.reshape(b * t, hidden), p(n + "moe_gate_weight"),
                p(n + "moe_gate_up_weight"), p(n + "moe_down_weight"),
                cfg["num_experts_per_tok"], cfg["norm_topk_prob"])
            h = h + y.reshape(b, t, hidden)
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
