"""Plain reference for ``models/olmo_hybrid.py``: Olmo-Hybrid's forward
pass, loss and gradients in straightforward ``jax.numpy``.

No kernel, no chunk, no triangular system, no cache: the
linear-attention layer is the gated delta rule itself, one token after
another (a ``lax.scan`` over time whose carry is the state ``[H, K,
V]``), the attention scores are a ``[block, T]`` matrix a head with an
explicit causal mask (``block`` queries at a time, so that 4k positions
fit a chip: a block's rows are whole softmax rows). Everything is
computed in ``dtype`` — float32 by default, under
``jax.default_matmul_precision("highest")`` so that a TPU does not
quietly run float32 matmuls in bf16 passes. ``dtype=jnp.bfloat16`` is
the same mathematics one precision below what any configuration of the
system states (norms, write strengths, decays, the carried state,
softmaxes and the loss in bf16 too): a comparison's tolerance has to
fail it.

It follows the published ``config.json`` (``model_type`` olmo_hybrid)
key by key (``cfg`` below). Every block norms the OUTPUT of its two
sub-layers (the Olmo 2 / Olmo 3 order; ``rms_norm_eps``):

    h = h + RMSNorm(mixer(h));  h = h + RMSNorm(SwiGLU(h))

its mixer the entry of ``layer_types``:

``linear_attention`` (gated delta rule; H ``linear_num_key_heads`` =
``linear_num_value_heads``, K ``linear_key_head_dim``, V
``linear_value_head_dim``): ``q, k, v = silu(conv(W_q x)), silu(conv(W_k
x)), silu(conv(W_v x))``, causal depthwise convolutions of
``linear_conv_kernel_dim`` taps over time without bias; a head's ``q =
q / |q| / sqrt(K)``, ``k = k / |k|`` with ``|x| = sqrt(sum x^2 +
1e-6)``; ``beta = 2 sigmoid(W_b x)`` (``linear_allow_neg_eigval``;
without it the 2 goes); ``g = -exp(A_log) softplus(W_a x + dt_bias)``,
``alpha = exp(g)``; ``S_t = alpha_t S_{t-1} + k_t u_t^T`` with ``u_t =
beta_t (v_t - alpha_t S_{t-1}^T k_t)`` (zero before the first token);
``o_t = S_t^T q_t``; ``y = W_o (RMSNorm_V(o) gamma * silu(W_g x))``, the
norm over each head's V columns and before the gate.

``full_attention``: ``q = RMSNorm(W_q x)``, ``k = RMSNorm(W_k x)`` over
the whole projected width, ``v = W_v x``, no bias,
``num_attention_heads`` query heads on ``num_key_value_heads`` key/value
heads of ``hidden_size / num_attention_heads``, causal, scale ``1 /
sqrt(head_dim)``, NO rotary embedding (``rope_parameters.rope_theta`` is
null); ``W_o``.

Final RMSNorm, untied head, mean next-token cross-entropy.

Parameters are a dict under the names of the symbol's arguments
(``embed_weight``; a linear layer's ``layer0_gdn_q_proj_weight`` ...
``_k_``, ``_v_``, ``_g_``, ``_a_``, ``_b_``, ``_o_proj_weight``,
``layer0_gdn_conv_weight`` [taps, 2 H K + H V] (the taps of ``q | k |
v``), ``layer0_gdn_a_log``, ``layer0_gdn_dt_bias``,
``layer0_gdn_norm_gamma`` [V]; a full layer's ``layer3_q_proj_weight``
..., ``layer3_q_norm_gamma``, ``layer3_k_norm_gamma``; every layer's
``layer0_attn_norm_gamma``, ``layer0_gate_proj_weight``,
``_up_proj_weight``, ``_down_proj_weight``, ``layer0_ffn_norm_gamma``;
``final_norm_gamma``, ``lm_head_weight``; ``FullyConnected`` weights are
``[out, in]``). Host arrays are fine: a layer's parameters are placed
when the layer runs, so an un-jitted call holds one layer's float32
weights at a time.

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


def causal_conv(x, taps):
    """x [B, T, C], taps [n, C]: tap ``n - 1`` meets the current token,
    tap 0 the oldest; no bias."""
    n, t = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (n - 1, 0), (0, 0)))
    return sum(padded[:, j:j + t] * taps[j] for j in range(n))


def gated_delta_net(q, k, v, gate, a, b, conv_w, a_log, dt_bias, gamma,
                    cfg):
    """The six projections of a block's input ([B, T, H K] twice, [B, T,
    H V] twice, [B, T, H] twice) -> [B, T, H V], one token after
    another."""
    bsz, t, _ = q.shape
    h = cfg["linear_num_key_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    wq, wk, wv = jnp.split(conv_w, [h * dk, 2 * h * dk], axis=1)
    q = jax.nn.silu(causal_conv(q, wq)).reshape(bsz, t, h, dk)
    k = jax.nn.silu(causal_conv(k, wk)).reshape(bsz, t, h, dk)
    v = jax.nn.silu(causal_conv(v, wv)).reshape(bsz, t, h, dv)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) \
        * dk ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    beta = jax.nn.sigmoid(b) * (2 if cfg["linear_allow_neg_eigval"] else 1)
    alpha = jnp.exp(-jnp.exp(a_log) * jax.nn.softplus(a + dt_bias))

    def token(state, at):                                     # [B, H, K, V]
        q_t, k_t, v_t, alpha_t, beta_t = at
        state = alpha_t[..., None, None] * state
        u_t = beta_t[..., None] * (
            v_t - jnp.sum(state * k_t[..., None], axis=2))
        state = state + k_t[..., None] * u_t[:, :, None, :]
        return state, jnp.sum(state * q_t[..., None], axis=2)

    _, o = jax.lax.scan(
        token, jnp.zeros((bsz, h, dk, dv), q.dtype),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, alpha, beta)))
    o = rms_norm(jnp.moveaxis(o, 0, 1), gamma, cfg["rms_norm_eps"])
    return o.reshape(bsz, t, h * dv) * jax.nn.silu(gate)


def attention(q, k, v, cfg, block=256):
    """Causal softmax attention without positions, q [B, T, H * D], k
    and v [B, T, KV * D], scores materialised for ``block`` queries at a
    time."""
    b, t, _ = q.shape
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // heads
    q = q.reshape(b, t, heads, d)
    k = jnp.repeat(k.reshape(b, t, kv, d), heads // kv, axis=2)
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


def forward(params, tokens, cfg, labels=None, dtype=jnp.float32,
            last=None, block=512):
    """tokens [B, T] int. Returns a dict: ``logits`` [B, last or T, V],
    ``expert_counts`` [0, 0], ``router_gap`` [1, B*T] of +inf and, with
    ``labels`` [B, T], ``loss`` (mean token cross-entropy) and
    ``per_sequence`` [B]. One layer at a time, and the head over
    ``block`` positions at a time, so the whole ``[T, V]`` table is
    never held."""
    def p(name):
        value = params[name]
        if hasattr(value, "asnumpy"):
            value = value.asnumpy()
        return jnp.asarray(value).astype(dtype)

    eps = cfg["rms_norm_eps"]
    b, t = tokens.shape
    with jax.default_matmul_precision("highest"):
        h = p("embed_weight")[jnp.asarray(tokens, jnp.int32)]  # [B, T, d]
        for i, kind in enumerate(cfg["layer_types"]):
            n = "layer%d_" % i
            if kind == "linear_attention":
                g = n + "gdn_"
                y = gated_delta_net(
                    *(h @ p(g + name + "_proj_weight").T
                      for name in ("q", "k", "v", "g", "a", "b")),
                    p(g + "conv_weight"), p(g + "a_log"), p(g + "dt_bias"),
                    p(g + "norm_gamma"), cfg) @ p(g + "o_proj_weight").T
            elif kind == "full_attention":
                y = attention(
                    rms_norm(h @ p(n + "q_proj_weight").T,
                             p(n + "q_norm_gamma"), eps),
                    rms_norm(h @ p(n + "k_proj_weight").T,
                             p(n + "k_norm_gamma"), eps),
                    h @ p(n + "v_proj_weight").T, cfg) \
                    @ p(n + "o_proj_weight").T
            else:
                raise ValueError("layer %d is %r" % (i, kind))
            h = h + rms_norm(y, p(n + "attn_norm_gamma"), eps)
            y = swiglu(h, p(n + "gate_proj_weight"), p(n + "up_proj_weight"),
                       p(n + "down_proj_weight"))
            h = h + rms_norm(y, p(n + "ffn_norm_gamma"), eps)
        h = rms_norm(h, p("final_norm_gamma"), eps)
        head = p("lm_head_weight")
        out = {"expert_counts": jnp.zeros((0, 0), jnp.int32),
               "router_gap": jnp.full((1, b * t), jnp.inf, jnp.float32)}
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
