"""Plain reference for ``models/falcon_h1.py``: Falcon-H1's forward pass,
loss and gradients in straightforward ``jax.numpy``.

No kernel, no chunk, no cache: the state-space layer is the recurrence
itself, one token after another (a ``lax.scan`` over time whose carry is
the float32 state ``[H, P, N]``), the convolution a loop over its taps,
the attention scores a ``[block, T]`` matrix a head with an explicit
causal mask (``block`` queries at a time, so that 4,096 positions fit a
chip: a block's rows are whole softmax rows). Everything is computed in
``dtype`` — float32 by default, under
``jax.default_matmul_precision("highest")`` so that a TPU does not
quietly run float32 matmuls in bf16 passes. ``dtype=jnp.bfloat16`` is
the same mathematics one precision below what any configuration of the
system states (norms, step sizes, decays, the carried state, softmaxes
and the loss in bf16 too): a comparison's tolerance has to fail it.

It follows the published ``config.json`` (``model_type`` falcon_h1) key
by key (``cfg`` below) and the published modelling code for where each
of the fourteen fixed multipliers lands. Every layer, with ``n =
RMSNorm(h)`` (``rms_norm_eps``), ONE norm for both mixers:

    h = h + ssm_out_multiplier * Mamba2(ssm_in_multiplier * n)
          + attention_out_multiplier * Attn(attention_in_multiplier * n)
    h = h + MLP(RMSNorm(h))

``Mamba2(u)`` (H ``mamba_n_heads``, P ``mamba_d_head``, G
``mamba_n_groups``, N ``mamba_d_state``): ``p = W_in u`` of widths ``H P
| H P | G N | G N | H`` = ``z | x | B | C | dt``; **``p`` times a fixed
vector holding ``ssm_multipliers[0..4]`` over those five segments**;
``x | B | C = silu(conv(.))``, a causal depthwise convolution of
``mamba_d_conv`` taps over time with bias; head h reads group ``h // (H
/ G)``; ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)``; ``S_t =
exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`` (zero before the first token);
``y_t = S_t C_t + D x_t``; ``y = RMSNorm_groups(y * silu(z)) * gamma``,
the statistics over each of the G groups of columns (the gate first:
``mamba_norm_before_gate`` false); ``W_out y``. ``mamba_chunk_size`` is
read by nothing here, and ``mamba_d_ssm`` by nothing either: the columns
held are ``H P``.

``Attn(u)``: ``q, k, v`` projections without bias, ``num_attention_heads``
query heads on ``num_key_value_heads`` key/value heads of ``head_dim``;
``k = (W_k u) * key_multiplier``; RoPE over the whole head, theta
``rope_theta``, the halves rotated (pair i is (i, i + D / 2)); causal,
scale ``1 / sqrt(head_dim)``; ``W_o``.

``MLP(v) = (W_down (W_up v * silu(W_gate v * mlp_multipliers[0]))) *
mlp_multipliers[1]``.

The embedding's rows times ``embedding_multiplier``; final RMSNorm;
untied head, logits times ``lm_head_multiplier``; mean next-token
cross-entropy.

**A share** (one of the chips that divide each layer by tensor
parallelism) is the same mathematics on the heads, groups and columns
held: the widths are read from the parameters' shapes and the counts
from ``cfg`` as it is given (``mamba_n_heads``, ``mamba_n_groups``,
``num_attention_heads``, ``num_key_value_heads`` the counts HELD), so a
share's result is its partial sum and nothing stands in for the other
chips or their all-reduce.

Parameters are a dict under the names of the symbol's arguments
(``embed_weight``, ``layer0_norm_gamma``, ``layer0_in_proj_weight``,
``layer0_ssm_conv_weight`` [taps, channels], ``layer0_ssm_conv_bias``,
``layer0_ssm_dt_bias``, ``layer0_ssm_a_log``, ``layer0_ssm_d``,
``layer0_ssm_norm_gamma``, ``layer0_out_proj_weight``,
``layer0_q_proj_weight`` ..., ``layer0_ffn_norm_gamma``,
``layer0_gate_proj_weight`` ..., ``final_norm_gamma``,
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


def mamba2(proj, conv_w, conv_b, dt_bias, a_log, d_skip, gamma, cfg):
    """proj [B, T, 2 H P + 2 G N + H], already under its five
    multipliers -> [B, T, H P], one token after another; the carried
    state [B, H, P, N] is ``proj``'s dtype (float32 by default)."""
    b, t, _ = proj.shape
    h, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    g, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    d_in, taps = h * p, conv_w.shape[0]
    z, xbc, dt = jnp.split(proj, [d_in, proj.shape[2] - h], axis=-1)
    # tap ``taps - 1`` meets the current token, tap 0 the oldest
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    acc = conv_b
    for j in range(taps):
        acc = acc + padded[:, j:j + t] * conv_w[j]
    xbc = jax.nn.silu(acc)
    x = xbc[..., :d_in].reshape(b, t, h, p)
    bmat = xbc[..., d_in:d_in + g * n].reshape(b, t, g, n)
    cmat = xbc[..., d_in + g * n:].reshape(b, t, g, n)
    dt = jax.nn.softplus(dt + dt_bias)                        # [B, T, H]
    a = -jnp.exp(a_log)                                       # [H]

    def token(state, at):                                     # [B, H, P, N]
        x_t, b_t, c_t, dt_t = at
        b_t = jnp.repeat(b_t, h // g, axis=1)                 # [B, H, N]
        c_t = jnp.repeat(c_t, h // g, axis=1)
        state = (jnp.exp(dt_t * a)[:, :, None, None] * state
                 + (dt_t[:, :, None] * x_t)[..., None] * b_t[:, :, None, :])
        y_t = jnp.sum(state * c_t[:, :, None, :], axis=-1)
        return state, y_t + d_skip[:, None] * x_t

    _, y = jax.lax.scan(
        token, jnp.zeros((b, h, p, n), proj.dtype),
        tuple(jnp.moveaxis(v, 1, 0) for v in (x, bmat, cmat, dt)))
    y = jnp.moveaxis(y, 0, 1).reshape(b, t, d_in) * jax.nn.silu(z)
    groups = y.reshape(b, t, g, d_in // g)
    groups = groups * jax.lax.rsqrt(
        jnp.mean(groups * groups, axis=-1, keepdims=True)
        + cfg["rms_norm_eps"])
    return gamma * groups.reshape(b, t, d_in)


def ssm_vector(cfg, dtype):
    """The fixed vector over ``in_proj``'s columns: ``ssm_multipliers[i]``
    over segment i of ``z | x | B | C | dt``."""
    h, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return jnp.asarray(np.repeat(
        np.asarray(cfg["ssm_multipliers"], np.float64),
        (h * p, h * p, gn, gn, h)), dtype)


def rope(x, heads, theta):
    """x [B, T, heads * D] rotated by its positions over the whole head,
    pair i = (i, i + D / 2) turning by ``pos * theta^(-2 i / D)``."""
    b, t, width = x.shape
    d = width // heads
    inv_freq = 1.0 / (float(theta) ** (np.arange(0, d, 2, dtype=np.float64)
                                       / d))
    angles = np.arange(t, dtype=np.float64)[:, None] * inv_freq[None, :]
    cos = jnp.asarray(np.cos(angles), x.dtype)[None, :, None, :]
    sin = jnp.asarray(np.sin(angles), x.dtype)[None, :, None, :]
    x = x.reshape(b, t, heads, d)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).reshape(b, t, width)


def attention(q, k, v, cfg, block=256):
    """Causal softmax attention, q [B, T, H * D], k and v [B, T, KV *
    D] (rotated already), scores materialised for ``block`` queries at a
    time."""
    b, t, _ = q.shape
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["head_dim"]
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


def mixers(n, p, prefix, cfg):
    """The two scaled mixer outputs of one layer on its normed input
    ``n`` [B, T, d]: (``ssm_out_multiplier * Mamba2(.)``,
    ``attention_out_multiplier * Attn(.)``). ``p`` fetches a parameter
    by name."""
    u = n * cfg["ssm_in_multiplier"]
    proj = (u @ p(prefix + "in_proj_weight").T) * ssm_vector(cfg, n.dtype)
    ssm = mamba2(
        proj, p(prefix + "ssm_conv_weight"), p(prefix + "ssm_conv_bias"),
        p(prefix + "ssm_dt_bias"), p(prefix + "ssm_a_log"),
        p(prefix + "ssm_d"), p(prefix + "ssm_norm_gamma"), cfg) \
        @ p(prefix + "out_proj_weight").T
    u = n * cfg["attention_in_multiplier"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    theta = cfg["rope_theta"]
    attn = attention(
        rope(u @ p(prefix + "q_proj_weight").T, heads, theta),
        rope((u @ p(prefix + "k_proj_weight").T) * cfg["key_multiplier"],
             kv, theta),
        u @ p(prefix + "v_proj_weight").T, cfg) \
        @ p(prefix + "o_proj_weight").T
    return (ssm * cfg["ssm_out_multiplier"],
            attn * cfg["attention_out_multiplier"])


def mlp(v, p, prefix, cfg):
    """``(W_down (W_up v * silu(W_gate v * m0))) * m1``."""
    m_gate, m_down = cfg["mlp_multipliers"]
    gate = jax.nn.silu((v @ p(prefix + "gate_proj_weight").T) * m_gate)
    return ((v @ p(prefix + "up_proj_weight").T * gate)
            @ p(prefix + "down_proj_weight").T) * m_down


def forward(params, tokens, cfg, labels=None, dtype=jnp.float32,
            last=None, block=512, parts=None):
    """tokens [B, T] int. Returns a dict: ``logits`` [B, last or T, V],
    ``expert_counts`` [0, 0], ``router_gap`` [1, B*T] of +inf and, with
    ``labels`` [B, T], ``loss`` (mean token cross-entropy) and
    ``per_sequence`` [B]. One layer at a time, and the head over
    ``block`` positions at a time, so the whole ``[T, V]`` table is
    never held. ``parts``, a list, receives a dict a layer: the stream
    the mixers are added to (``h``), their two scaled outputs (``ssm``,
    ``attn``), the stream the MLP is added to (``h_mid``) and its output
    (``mlp``)."""
    def p(name):
        value = params[name]
        if hasattr(value, "asnumpy"):
            value = value.asnumpy()
        return jnp.asarray(value).astype(dtype)

    eps = cfg["rms_norm_eps"]
    b, t = tokens.shape
    with jax.default_matmul_precision("highest"):
        h = p("embed_weight")[jnp.asarray(tokens, jnp.int32)] \
            * cfg["embedding_multiplier"]                      # [B, T, d]
        for i in range(cfg["num_hidden_layers"]):
            n = "layer%d_" % i
            ssm, attn = mixers(rms_norm(h, p(n + "norm_gamma"), eps), p, n,
                               cfg)
            read = h
            h = h + (ssm + attn)
            y = mlp(rms_norm(h, p(n + "ffn_norm_gamma"), eps), p, n, cfg)
            if parts is not None:
                parts.append({"h": read, "ssm": ssm, "attn": attn, "mlp": y,
                              "h_mid": h})
            h = h + y
        h = rms_norm(h, p("final_norm_gamma"), eps)
        head = p("lm_head_weight")
        scale = cfg["lm_head_multiplier"]
        out = {"expert_counts": jnp.zeros((0, 0), jnp.int32),
               "router_gap": jnp.full((1, b * t), jnp.inf, jnp.float32)}
        keep = t if last is None else last
        if labels is None:
            out["logits"] = (h[:, t - keep:] @ head.T) * scale
            return out
        labels = jnp.asarray(labels, jnp.int32)
        nll, logits = [], []
        for s in range(0, t, block):
            z = (h[:, s:s + block] @ head.T) * scale          # [B, blk, V]
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
