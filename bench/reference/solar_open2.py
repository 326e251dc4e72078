"""Plain reference for ``models/solar_open2.py``: Solar-Open2-250B's
forward pass, loss and gradients in straightforward ``jax.numpy``.

No kernel, no chunk, no triangular system, no sort, no grouped matmul,
no cache: a Kimi Delta Attention layer is the delta rule itself, one
token after another (a ``lax.scan`` over time whose carry is the state
``[H, K, V]``, decayed a key CHANNEL at a time), a grouped-attention
layer's scores are a ``[block, T]`` matrix a head with an explicit causal
mask (``block`` queries at a time, so that 4k positions fit a chip: a
block's rows are whole softmax rows), the expert layer is a loop over the
experts held with a mask. Everything is computed in ``dtype`` — float32
by default, under ``jax.default_matmul_precision("highest")`` so that a
TPU does not quietly run float32 matmuls in bf16 passes.
``dtype=jnp.bfloat16`` is the same mathematics one precision below what
any configuration of the system states (router, norms, write strengths,
decays, the carried state, gates, softmaxes and the loss in bf16 too): a
comparison's tolerance has to fail it.

It follows the published ``config.json`` (``model_type`` solar_open2)
key by key (``cfg`` below). Layers are numbered from 0, as
``gqa_layers`` counts them: layer ``i`` is a gated grouped-attention
layer where ``gqa_layers`` names it and a KDA layer otherwise; ``h +=
mixer(RMSNorm(h))``, ``h += ffn(RMSNorm(h))`` (``rms_norm_eps``).

KDA (H ``linear_attn_config.num_heads``, K = V
``linear_attn_config.head_dim``; fla's ``KimiDeltaAttention`` order):
``q, k, v = silu(conv(W_q x)), silu(conv(W_k x)), silu(conv(W_v x))``,
causal depthwise convolutions of ``short_conv_kernel_size`` taps over
time without bias; a head's ``q = q / |q| / sqrt(K)``, ``k = k / |k|``
with ``|x| = sqrt(sum x^2 + 1e-6)``; ``beta = 2 sigmoid(W_b x)`` a head,
in (0, 2) (``kda_allow_neg_eigval``: the transition ``I - beta k k^T`` has
the eigenvalue ``1 - beta`` in (-1, 1); ``sigmoid`` alone where the key is
false); the log decay a CHANNEL, ``g = -exp(A_log_h) softplus(W_f2 (W_f1
x) + dt_bias)`` through a low-rank pair (``kda_use_full_proj`` false),
``alpha = exp(g)``; ``S_t = Diag(alpha_t) S_{t-1} + k_t u_t^T`` with ``u_t
= beta_t (v_t - (Diag(alpha_t) S_{t-1})^T k_t)`` (zero before the first
token), which is ``S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} +
beta_t k_t v_t^T``; ``o_t = S_t^T q_t``; ``y = W_o (RMSNorm_V(o) gamma *
sigmoid(W_g2 (W_g1 x)))``, the norm over each head's V columns and BEFORE
the gate, the gate a sigmoid.

Gated grouped attention (``num_attention_heads`` query heads on
``num_key_value_heads`` key/value heads of ``head_dim``): ``q, k, v = W_q
x, W_k x, W_v x`` without bias or norm, NOTHING rotated (``use_rope``
false: ``rope_theta`` and ``partial_rotary_factor`` are read by
nothing); scores ``q . k / sqrt(head_dim)``, causal; ``a = softmax(.) v``;
``a * sigmoid(W_g x)`` a head and channel (``use_gqa_gate``; without the
key's weight in the parameters there is no gate); ``W_o``.

The feed-forward of EVERY layer (``first_k_dense_replace`` 0): ``z =
sigmoid(W_r x)`` in float32, the ``num_experts_per_tok`` largest of ``z
+ b`` chosen, weights ``z`` over the chosen, renormalised over ``sum +
1e-20`` (``norm_topk_prob``) and times ``routed_scaling_factor``; ``h +=
shared(x) + routed(x)``, the shared expert one SwiGLU of
``n_shared_experts * moe_intermediate_size``. Final RMSNorm, untied head,
mean next-token cross-entropy.

**A share.** The counts are what ``cfg`` and the parameters hold: the
heads of both mixers are ``cfg``'s (``linear_attn_config.num_heads``,
``num_attention_heads``, ``num_key_value_heads``: the heads held, each a
whole head: its columns of every projection, its ``A_log``, its
``dt_bias`` channels, its taps), the router's width is
``moe_gate_weight``'s and the experts held ``moe_down_weight``'s. Where
a layer holds H of the router's E experts they are experts
``share.expert_offset`` .. ``+ H - 1`` (0 without the key), and the
routed part of the layer's result is theirs alone. Each mixer's ``W_o``
gives the held heads' part of the layer's result: what the absent heads
and experts would add is left out, as it is in the program, and the
partial result goes on to the next layer. The router, the shared expert,
the first halves of the low-rank pairs and the norms are whole in every
share.

Departures from the published code, in the program alike: the balancing
rule that moves ``b`` is left out (it stays where it is given: zeros), so
is any auxiliary loss.

Parameters are a dict under the names of the symbol's arguments
(``embed_weight``; a KDA layer's ``layer1_kda_q_proj_weight`` ...
``_k_``, ``_v_``, ``_f_a_``, ``_f_b_``, ``_g_a_``, ``_g_b_``, ``_b_``,
``_o_proj_weight``, ``layer1_kda_conv_weight`` [taps, 3 H K] (the taps
of ``q | k | v``), ``layer1_kda_a_log`` [H], ``layer1_kda_dt_bias`` [H
K], ``layer1_kda_norm_gamma`` [V]; a grouped-attention layer's
``layer0_q_proj_weight``, ``_k_``, ``_v_``, ``_attn_gate_``,
``_o_proj_weight``; ``layer0_attn_norm_gamma``,
``layer0_ffn_norm_gamma``; ``layer0_moe_gate_weight``,
``layer0_moe_gate_up_weight``, ``layer0_moe_down_weight``,
``layer0_moe_select_bias``, ``layer0_shared_gate_proj_weight`` ...;
``final_norm_gamma``, ``lm_head_weight``; ``FullyConnected`` weights are
``[out, in]``). Host arrays are fine: a layer's parameters are placed
when the layer runs, so an un-jitted call holds one layer's float32
weights at a time.
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


def delta_attention(q, k, v, gate, a, b, conv_w, a_log, dt_bias, gamma,
                    cfg):
    """The projections of a block's input (q, k, v, the gate's and the
    decay's pre-activations [B, T, H K], the write strength's [B, T, H])
    -> [B, T, H V], one token after another; H is ``a_log``'s length (the
    heads held)."""
    bsz, t, _ = q.shape
    h = a_log.shape[0]
    d = cfg["linear_attn_config"]["head_dim"]
    wq, wk, wv = jnp.split(conv_w, 3, axis=1)
    q = jax.nn.silu(causal_conv(q, wq)).reshape(bsz, t, h, d)
    k = jax.nn.silu(causal_conv(k, wk)).reshape(bsz, t, h, d)
    v = jax.nn.silu(causal_conv(v, wv)).reshape(bsz, t, h, d)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) \
        * d ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    beta = jax.nn.sigmoid(b)                                  # [B, T, H]
    if cfg.get("kda_allow_neg_eigval", True):
        beta = 2 * beta
    alpha = jnp.exp(-jnp.exp(a_log)[:, None] * jax.nn.softplus(
        (a + dt_bias).reshape(bsz, t, h, d)))                 # [B, T, H, K]

    def token(state, at):                                     # [B, H, K, V]
        q_t, k_t, v_t, alpha_t, beta_t = at
        state = alpha_t[..., None] * state
        u_t = beta_t[..., None] * (
            v_t - jnp.sum(state * k_t[..., None], axis=2))
        state = state + k_t[..., None] * u_t[:, :, None, :]
        return state, jnp.sum(state * q_t[..., None], axis=2)

    _, o = jax.lax.scan(
        token, jnp.zeros((bsz, h, d, d), q.dtype),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, alpha, beta)))
    o = rms_norm(jnp.moveaxis(o, 0, 1), gamma, cfg["rms_norm_eps"])
    return o.reshape(bsz, t, h * d) * jax.nn.sigmoid(gate)


def gated_attention(q, k, v, gate, cfg, block=256):
    """Causal softmax attention times ``sigmoid(gate)`` (no gate where it
    is None): q and gate [B, T, H * D], k and v [B, T, KV * D], H and KV
    the heads held; nothing normed, nothing rotated; scores materialised
    for ``block`` queries at a time."""
    b, t, _ = q.shape
    d = cfg["head_dim"]
    heads, kv = q.shape[2] // d, k.shape[2] // d
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
    out = jnp.concatenate(out, axis=1).reshape(b, t, heads * d)
    return out if gate is None else out * jax.nn.sigmoid(gate)


def moe(x, gate_w, w_gate_up, w_down, select_bias, top_k, norm_topk_prob,
        offset=0, routed_scale=1.0):
    """x [N, d]; the router is ``gate_w`` [d, E] with sigmoid scores, the
    experts held are E's ``offset`` .. ``offset + H - 1`` (``w_down`` [H,
    h, d]). Returns the held experts' part of the layer's output, the row
    count of each of the E experts, and each token's margin between its
    last chosen and its first rejected expert (selection scores) where
    one of the two is held here — +inf where neither is: that call
    cannot change this share's result."""
    num_experts = gate_w.shape[1]
    held, hidden = w_down.shape[0], w_down.shape[1]
    scores = jax.nn.sigmoid(x @ gate_w)
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
    """[expert layer?] per layer: all but the first
    ``first_k_dense_replace`` (0 as published: every layer)."""
    return [i >= cfg.get("first_k_dense_replace", 0)
            for i in range(cfg["num_hidden_layers"])]


def kda_layer(x, p, prefix, cfg):
    """One KDA mixer on the normed input ``x`` [B, T, d]: the held heads'
    part of the layer's result. ``p(name)`` gives a parameter."""
    def proj(y, name):
        return y @ p(prefix + "kda_" + name + "_proj_weight").T

    k = prefix + "kda_"
    y = delta_attention(
        proj(x, "q"), proj(x, "k"), proj(x, "v"),
        proj(proj(x, "g_a"), "g_b"), proj(proj(x, "f_a"), "f_b"),
        proj(x, "b"), p(k + "conv_weight"), p(k + "a_log"),
        p(k + "dt_bias"), p(k + "norm_gamma"), cfg)
    return proj(y, "o")


def gqa_layer(x, p, prefix, cfg):
    """One gated grouped-attention mixer on the normed input ``x``: the
    held heads' part of the layer's result."""
    def proj(y, name):
        return y @ p(prefix + name + "_proj_weight").T

    gate = proj(x, "attn_gate") if cfg.get("use_gqa_gate", True) else None
    return proj(gated_attention(
        proj(x, "q"), proj(x, "k"), proj(x, "v"), gate, cfg), "o")


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
    gqa = set(cfg["gqa_layers"])                              # 0-based
    b, t = tokens.shape
    if not all(expert_layers(cfg)):
        raise ValueError("solar_open2_reference: first_k_dense_replace=%r "
                         "(only 0)" % (cfg["first_k_dense_replace"],))
    with jax.default_matmul_precision("highest"):
        h = p("embed_weight")[jnp.asarray(tokens, jnp.int32)]  # [B, T, d]
        counts, gaps = [], []
        for i in range(cfg["num_hidden_layers"]):
            n = "layer%d_" % i
            x = rms_norm(h, p(n + "attn_norm_gamma"), eps)
            h = h + (gqa_layer if i in gqa else kda_layer)(x, p, n, cfg)
            x = rms_norm(h, p(n + "ffn_norm_gamma"), eps)
            y, count, gap = moe(
                x.reshape(b * t, -1), p(n + "moe_gate_weight"),
                p(n + "moe_gate_up_weight"), p(n + "moe_down_weight"),
                p(n + "moe_select_bias"), cfg["num_experts_per_tok"],
                cfg["norm_topk_prob"], offset,
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
