"""Plain reference for ``models/ouro.py``: Ouro's forward pass, loss and
gradients in straightforward ``jax.numpy``.

No kernel, no cache, no unrolled graph: a Python loop over the passes and
over the layers that reads ONE dict of weights, attention scores a
``[block, T]`` matrix a head with an explicit causal mask (``block``
queries at a time, so that 4k positions fit a chip: a block's rows are
whole softmax rows), the head over ``block`` positions at a time.
Everything is computed in ``dtype`` — float32 by default, under
``jax.default_matmul_precision("highest")`` so that a TPU does not
quietly run float32 matmuls in bf16 passes. ``dtype=jnp.bfloat16`` is
the same mathematics one precision below what any configuration of the
system states (norms, the rotation, softmaxes, the gate, the exit
distribution, its entropy and the loss in bf16 too): a comparison's
tolerance has to fail it.

It follows the published ``config.json`` (``model_type`` ouro) key by
key (``cfg`` below) and Zhu et al., "Scaling Latent Reasoning via Looped
Language Models" (arXiv:2510.25741). A layer, RMSNorms of
``rms_norm_eps`` with gammas of their own:

    a = input_layernorm(h);  q, k, v = W_q a, W_k a, W_v a
    q, k = RoPE(q), RoPE(k)      # rotate-half pairs (i, i + D/2) over the
                                 # whole head, ``rope_theta``
    h = h + input_layernorm_2(W_o Attention(q, k, v))
    m = post_attention_layernorm(h)
    h = h + post_attention_layernorm_2(W_down(silu(W_gate m) * W_up m))

``num_attention_heads`` query heads on ``num_key_value_heads`` key/value
heads of ``head_dim``, causal, scale ``1 / sqrt(head_dim)``, no bias.
The model, for ``t = 1..total_ut_steps`` over the SAME weights:

    h = layers(h);  n_t = norm(h);  h = n_t      # the normed state goes on
    z_t = W_head n_t;  lambda_t = sigmoid(w_g . n_t + b_g)

``p_t = lambda_t prod_{j<t}(1 - lambda_j)`` for ``t < T``, ``p_T =
prod_{j<T}(1 - lambda_j)``; a token's loss ``sum_t p_t l_t - beta H(p)``
with ``l_t = -log softmax(z_t)[label]`` and ``H(p) = -sum_t p_t log
p_t``; the loss is the mean over tokens.

Departures from the published description, shared with the symbol:
``beta`` is no key of ``config.json`` (``cfg["assumed"]["exit_beta"]``
where given, else 0.05: the paper's later-stage value as recalled);
stage II (the gate trained against detached losses) and the early exit
at inference (``early_exit_threshold``) are left out; ``lambda_T`` is
not computed (nothing reads it).

Parameters are a dict under the names of the symbol's arguments
(``embed_weight``; ``layer0_q_proj_weight`` ... ``_k_``, ``_v_``,
``_o_``, ``_gate_``, ``_up_``, ``_down_proj_weight``;
``layer0_input_layernorm_gamma``, ``_input_layernorm_2_gamma``,
``_post_attention_layernorm_gamma``, ``_post_attention_layernorm_2_gamma``;
``final_norm_gamma``, ``lm_head_weight``, ``exit_gate_weight`` [1,
hidden], ``exit_gate_bias`` [1]; ``FullyConnected`` weights are ``[out,
in]``), each held ONCE whatever the number of passes. Host arrays are
fine: a parameter is placed when it is read.

There are no experts: ``router_gap`` is returned as one row of +inf (no
token's result hangs on a near-tie) and ``expert_counts`` empty, so that
the benchmark's comparison of the sparse-expert models reads this one
unchanged.
"""
import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x, gamma, eps):
    return gamma * (x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps))


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate.T) * (x @ w_up.T)) @ w_down.T


def rope(x, theta):
    """x [B, T, H, D]: the pairs (i, i + D/2) of every head turned by
    ``pos * theta^(-2i/D)``."""
    t, d = x.shape[1], x.shape[3]
    inv_freq = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    angles = np.arange(t, dtype=np.float64)[:, None] * inv_freq[None, :]
    cos = jnp.asarray(np.cos(angles), x.dtype)[None, :, None, :]
    sin = jnp.asarray(np.sin(angles), x.dtype)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def attention(q, k, v, cfg, block=256):
    """Causal softmax attention under the rotation, q [B, T, H * D], k
    and v [B, T, KV * D], scores materialised for ``block`` queries at a
    time."""
    b, t, _ = q.shape
    heads = cfg["num_attention_heads"]
    kv = cfg.get("num_key_value_heads") or heads
    d = cfg.get("head_dim") or cfg["hidden_size"] // heads
    theta = float(cfg["rope_theta"])
    q = rope(q.reshape(b, t, heads, d), theta)
    k = jnp.repeat(rope(k.reshape(b, t, kv, d), theta), heads // kv, axis=2)
    v = jnp.repeat(v.reshape(b, t, kv, d), heads // kv, axis=2)
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
    return jnp.concatenate(out, axis=1).reshape(b, t, heads * d)


def exit_distribution(gates):
    """gates: T arrays of pre-activations (the last is not read) -> the T
    exit probabilities, each shaped as a gate."""
    stay = jnp.ones_like(gates[0])
    p = []
    for g in gates[:-1]:
        lam = jax.nn.sigmoid(g)
        p.append(lam * stay)
        stay = stay * (1 - lam)
    return p + [stay]


def forward(params, tokens, cfg, labels=None, dtype=jnp.float32,
            last=None, block=512):
    """tokens [B, T] int. Returns a dict: ``logits`` [P, B, last or T, V]
    (every exit's, P = ``total_ut_steps``), ``exit_prob`` [P, B, last or
    T] (every exit's ``p_t`` there), ``exit_mass`` [P] (the mean of
    ``p_t`` over all tokens), ``expert_counts`` [0, 0], ``router_gap``
    [1, B*T] of +inf and, with ``labels`` [B, T], ``loss`` (the mean
    token loss), ``per_sequence`` [B] and ``exit_nll`` [P] (each exit's
    mean cross-entropy). One parameter at a time, and the head over
    ``block`` positions at a time, so the whole ``[T, V]`` table is
    never held."""
    def p(name):
        value = params[name]
        if hasattr(value, "asnumpy"):
            value = value.asnumpy()
        return jnp.asarray(value).astype(dtype)

    eps = cfg["rms_norm_eps"]
    passes = cfg["total_ut_steps"]
    beta = (cfg.get("assumed") or {}).get("exit_beta", 0.05)
    b, t = tokens.shape
    keep = t if last is None else last
    if labels is not None:
        labels = jnp.asarray(labels, jnp.int32)
    with jax.default_matmul_precision("highest"):
        h = p("embed_weight")[jnp.asarray(tokens, jnp.int32)]  # [B, T, d]
        gates, nll, logits = [], [], []
        for _ in range(passes):
            for i in range(cfg["num_hidden_layers"]):
                n = "layer%d_" % i
                a = rms_norm(h, p(n + "input_layernorm_gamma"), eps)
                y = attention(
                    a @ p(n + "q_proj_weight").T,
                    a @ p(n + "k_proj_weight").T,
                    a @ p(n + "v_proj_weight").T, cfg) \
                    @ p(n + "o_proj_weight").T
                h = h + rms_norm(y, p(n + "input_layernorm_2_gamma"), eps)
                m = rms_norm(h, p(n + "post_attention_layernorm_gamma"), eps)
                y = swiglu(m, p(n + "gate_proj_weight"),
                           p(n + "up_proj_weight"),
                           p(n + "down_proj_weight"))
                h = h + rms_norm(
                    y, p(n + "post_attention_layernorm_2_gamma"), eps)
            h = rms_norm(h, p("final_norm_gamma"), eps)  # and carried on
            gates.append(h @ p("exit_gate_weight")[0] + p("exit_gate_bias"))
            head = p("lm_head_weight")
            if labels is None:
                logits.append(h[:, t - keep:] @ head.T)
                continue
            rows, tail = [], []
            for s in range(0, t, block):
                z = h[:, s:s + block] @ head.T                # [B, blk, V]
                logp = jax.nn.log_softmax(z, axis=-1)
                rows.append(-jnp.take_along_axis(
                    logp, labels[:, s:s + block, None], axis=-1)[..., 0])
                lo = max(s, t - keep)
                if lo < s + block:
                    tail.append(z[:, lo - s:])
            nll.append(jnp.concatenate(rows, axis=1))          # [B, T]
            logits.append(jnp.concatenate(tail, axis=1))
        prob = jnp.stack(exit_distribution(gates))             # [P, B, T]
        out = {"logits": jnp.stack(logits),
               "exit_prob": prob[:, :, t - keep:],
               "exit_mass": jnp.mean(prob, axis=(1, 2)),
               "expert_counts": jnp.zeros((0, 0), jnp.int32),
               "router_gap": jnp.full((1, b * t), jnp.inf, jnp.float32)}
        if labels is None:
            return out
        nll = jnp.stack(nll)                                   # [P, B, T]
        entropy = -jnp.sum(prob * jnp.log(prob), axis=0)
        token = jnp.sum(prob * nll, axis=0) - beta * entropy   # [B, T]
        out["exit_nll"] = jnp.mean(nll, axis=(1, 2))
        out["per_sequence"] = jnp.mean(token, axis=1)
        out["loss"] = jnp.mean(token)
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
