"""Plain reference for ``models/xing4.py``: Xing4.0-29B-A4B's forward
pass, its two losses and their gradient in straightforward ``jax.numpy``.

No kernel, no sort, no grouped matmul, no cache, no absorbed projection, no
fused pass: the stream is ``X`` [B, T, n, C] and the recurrences are
written as the papers write them, a Python loop over layers, over the
Sinkhorn iterations and over the experts held; keys and values of every
head are projected up and materialised; the scores are a ``[block, T]``
matrix a head under an explicit causal mask (whole softmax rows, nothing
online). Everything is computed in ``dtype`` — float32 by default, under
``jax.default_matmul_precision("highest")``. ``dtype=jnp.bfloat16`` is the
same mathematics one precision below what any configuration of the system
states (router, norms, RoPE's tables, the mixing's coefficients and
iterations, softmaxes and losses in bf16 too): a comparison's tolerance
has to fail it.

It follows the published ``config.json`` (``model_type`` xing4_0:
``deepseek_v3``'s keys plus ``hc_mult``, ``hc_sinkhorn_iters``, ``hc_eps``,
``mhc_h_res_clamp_min/max``) key by key (``cfg`` below).

**The stream** (manifold-constrained hyper-connections, arXiv:2512.24880
on arXiv:2409.19606). ``X_0[t, j] = Embed(id_t)`` for every j. Each of a
block's two sub-layers, attention then feed-forward, is wrapped alike with
its own ``phi`` [n (n + 2), n C] (rows: n of the read, n of the write, n n
of the carry, row-major), ``bias`` [n (n + 2)] and ``alpha`` [3]. A token
at a time:

    xbar  = vec(X[t]) / sqrt(mean(vec(X[t])^2) + rms_norm_eps)
    Hpre  = sigmoid(alpha_0 (phi_pre xbar) + b_pre)              [n]
    Hpost = 2 sigmoid(alpha_1 (phi_post xbar) + b_post)          [n]
    M     = exp(clip(alpha_2 mat(phi_res xbar) + b_res, min, max))   [n, n]
    hc_sinkhorn_iters times: M = M / (colsum(M) + hc_eps);
                             M = M / (rowsum(M) + hc_eps)
    u     = sum_j Hpre[j] X[t, j]
    y     = F(RMSNorm(u))
    X'[t, i] = sum_j M[i, j] X[t, j] + Hpost[i] y

After the last block ``h = sum_j X[t, j]``, final RMSNorm, untied head.

**F** is ``deepseek_v3``'s. Attention: ``cq = RMSNorm(W_dq x)``
(``q_lora_rank``), ``q = W_uq cq``, a head's ``qk_nope_head_dim``
un-rotated dimensions then its ``qk_rope_head_dim`` rotary ones; ``(c,
k_rope) = W_dkv x``; ``(k_nope_h, v_h) = W_ukv RMSNorm(c)``. RoPE under
``rope_scaling`` (YaRN, arXiv:2309.00071, as the published code computes
it): pair i's frequency is ``theta^(-2i/R)`` blended with that over
``factor`` by the linear ramp between the pairs that turn ``beta_fast`` and
``beta_slow`` times over ``original_max_position_embeddings``; cos and sin
are scaled by ``mscale / mscale_all_dim`` (1 here); the scores by
``1/sqrt(qk_head_dim) * m^2``, ``m = 0.1 mscale_all_dim ln(factor) + 1``.
With ``rope_interleave`` (absent from the file: the family's default,
true) the pairs are ``(2i, 2i + 1)``, de-interleaved before
``rotate_half`` for queries and keys alike. Feed-forward: the first
``first_k_dense_replace`` layers ``W_d (silu(W_g x) * W_u x)``; the others
``z = sigmoid(W_r x)``, the ``num_experts_per_tok`` largest of ``z + b``
chosen (``noaux_tc``, ``n_group`` 1), weights ``z`` over the chosen,
renormalised over ``sum + 1e-20`` and times ``routed_scaling_factor``;
shared SwiGLU of ``n_shared_experts * moe_intermediate_size`` added.

**Multi-token prediction** (``num_nextn_predict_layers`` 1,
arXiv:2412.19437 section 2.2): at position i ``h' = W_p [RMSNorm(Embed(
id_{i+1})) ; RMSNorm(h_i)]`` (``h_i`` the summed streams before the final
norm), replicated to n streams, one expert block of the same class with
its own weights (``mtp0_*``), the streams' sum, the module's own norm, the
model's one head; the target is ``id_{i+2}`` and the last position has
none. ``labels`` holds ``id_{i+1}``. Loss = main + ``mtp_loss_weight``
(0.3) x the module's, each a mean over its targets.

**A share** as ``models/kanana2_reference.py``: the counts are read from
the parameters; where a layer holds H of the router's E experts they are
``share.expert_offset`` .. ``+ H - 1`` and the routed part is theirs alone.

Left out, in the program alike: the rule that moves the selection bias,
any auxiliary loss.

Parameters are a dict under the symbol's argument names (``embed_weight``,
``layer0_attn_hc_phi`` / ``_bias`` / ``_alpha``, ``layer0_attn_norm_gamma``,
``layer0_q_latent_a_proj_weight``, ``layer0_q_latent_norm_gamma``,
``layer0_q_latent_b_proj_weight``, ``layer0_kv_a_proj_weight``,
``layer0_attn_latent_gamma``, ``layer0_attn_up_weight``,
``layer0_o_proj_weight``, ``layer0_ffn_hc_*``, ``layer0_ffn_norm_gamma``,
..., ``mtp0_embed_norm_gamma``, ``mtp0_hidden_norm_gamma``,
``mtp0_proj_weight``, ``mtp0_final_norm_gamma``, ``lm_head_weight``;
``FullyConnected`` weights are ``[out, in]``). Host arrays are fine: a
layer's parameters are placed when the layer runs.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

MTP_LOSS_WEIGHT = 0.3


def rms_norm(x, gamma, eps):
    return gamma * (x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps))


def yarn_inv_freq(r, theta, scaling):
    """Pair i's frequency, float64 [r / 2]: ``theta^(-2i/r)`` without
    ``scaling``; under it the published ``_compute_yarn_parameters``."""
    plain = 1.0 / (theta ** (np.arange(0, r, 2, dtype=np.float64) / r))
    if not scaling:
        return plain
    factor = scaling["factor"]
    original = scaling["original_max_position_embeddings"]

    def correction_dim(rotations):
        return (r * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(scaling.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction_dim(scaling.get("beta_slow", 1))), r - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(r // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    extrapolation = 1.0 - ramp
    return plain / factor * (1 - extrapolation) + plain * extrapolation


def yarn_mscale(scale, mscale):
    return 0.1 * mscale * math.log(scale) + 1.0 if scale > 1 else 1.0


def rope(x, theta, interleave, scaling=None):
    """x [B, T, H, R], positions 0..T-1, every dimension rotated: ``x *
    cos + rotate_half(x) * sin`` after the de-interleave where
    ``interleave``; cos and sin times ``mscale / mscale_all_dim``'s
    ratio under ``scaling``."""
    t, r = x.shape[1], x.shape[3]
    if interleave:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    freqs = (np.arange(t, dtype=np.float64)[:, None]
             * yarn_inv_freq(r, theta, scaling)[None, :])
    emb = np.concatenate([freqs, freqs], axis=-1)
    attention_factor = 1.0
    if scaling:
        attention_factor = (
            yarn_mscale(scaling["factor"], scaling.get("mscale", 1))
            / yarn_mscale(scaling["factor"],
                          scaling.get("mscale_all_dim", 0) or 1))
    cos = jnp.asarray(np.cos(emb) * attention_factor,
                      x.dtype)[None, :, None, :]
    sin = jnp.asarray(np.sin(emb) * attention_factor,
                      x.dtype)[None, :, None, :]
    rotated = jnp.concatenate([-x[..., r // 2:], x[..., : r // 2]], axis=-1)
    return x * cos + rotated * sin


def attention(q, k, v, scale, block=256):
    """Causal softmax attention, q and k [B, T, H, D], v [B, T, H, Dv],
    scores materialised for ``block`` queries at a time."""
    t = q.shape[1]
    pos = np.arange(t)
    out = []
    for s in range(0, t, block):
        # a Python float: a numpy scalar would promote to float64
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, s:s + block], k) \
            * float(scale)
        mask = pos[s:s + block, None] >= pos[None, :]
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", probs, v))
    return jnp.concatenate(out, axis=1)


def score_scale(cfg):
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    scaling = cfg.get("rope_scaling")
    if scaling and scaling.get("mscale_all_dim", 0):
        m = yarn_mscale(scaling["factor"], scaling["mscale_all_dim"])
        scale = scale * m * m
    return scale


def latent_attention(q, latent, gamma, w_up, cfg):
    """q [B, T, H * (N + R)], latent [B, T, L + R] -> [B, T, H * Dv]."""
    b, t, _ = q.shape
    n, r = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, width = cfg["v_head_dim"], cfg["kv_lora_rank"]
    theta, scaling = cfg["rope_theta"], cfg.get("rope_scaling")
    interleave = cfg.get("rope_interleave", True)
    q = q.reshape(b, t, -1, n + r)
    heads = q.shape[2]
    c = rms_norm(latent[..., :width], gamma, cfg["rms_norm_eps"])
    kv = (c @ w_up.T).reshape(b, t, heads, n + dv)
    k_rope = rope(latent[..., width:].reshape(b, t, 1, r), theta, interleave,
                  scaling)
    q = jnp.concatenate(
        [q[..., :n], rope(q[..., n:], theta, interleave, scaling)], axis=-1)
    k = jnp.concatenate(
        [kv[..., :n], jnp.broadcast_to(k_rope, (b, t, heads, r))], axis=-1)
    return attention(q, k, kv[..., n:], score_scale(cfg)).reshape(
        b, t, heads * dv)


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate.T) * (x @ w_up.T)) @ w_down.T


def moe(x, gate_w, w_gate_up, w_down, select_bias, top_k, norm_topk_prob,
        offset=0, routed_scale=1.0):
    """x [N, d]; the router is ``gate_w`` [d, E], the experts held are
    E's ``offset`` .. ``offset + H - 1`` (``w_down`` [H, h, d]). Returns
    the held experts' part of the layer's output, the row count of each
    of the E experts, and each token's margin between its last chosen and
    its first rejected expert (selection scores) where one of the two is
    held here — +inf where neither is."""
    num_experts = gate_w.shape[1]
    held, hidden = w_down.shape[0], w_down.shape[1]
    scores = jax.nn.sigmoid(x @ gate_w)
    select = scores + select_bias
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
    """[expert layer?] per block: the first ``first_k_dense_replace`` of
    the ``num_hidden_layers`` are dense, the rest have experts."""
    return [i >= cfg["first_k_dense_replace"]
            for i in range(cfg["num_hidden_layers"])]


def hyper_coefficients(x, phi, bias, alpha, cfg):
    """x [B, T, n, C] -> (Hpre [B, T, n], Hpost [B, T, n], Hres [B, T, n,
    n]) by the recurrences of this file's head, in ``x``'s dtype."""
    b, t, n, c = x.shape
    flat = x.reshape(b, t, n * c)
    xbar = flat * jax.lax.rsqrt(
        jnp.mean(flat * flat, axis=-1, keepdims=True) + cfg["rms_norm_eps"])
    z = xbar @ phi.T                                          # [B, T, n(n+2)]
    pre = jax.nn.sigmoid(alpha[0] * z[..., :n] + bias[:n])
    post = 2 * jax.nn.sigmoid(alpha[1] * z[..., n:2 * n] + bias[n:2 * n])
    m = jnp.exp(jnp.clip(
        alpha[2] * z[..., 2 * n:] + bias[2 * n:],
        cfg.get("mhc_h_res_clamp_min", -30),
        cfg.get("mhc_h_res_clamp_max", 30))).reshape(b, t, n, n)
    eps = cfg["hc_eps"]
    for _ in range(cfg["hc_sinkhorn_iters"]):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)   # columns
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)   # rows
    return pre, post, m


def forward(params, tokens, cfg, labels=None, dtype=jnp.float32,
            last=None, block=512):
    """tokens [B, T] int. Returns a dict: ``logits`` [B, last or T, V] and,
    with a prediction module, ``mtp_logits`` alike; ``expert_counts``
    [expert layers, E] (the module's block last); ``router_gap`` [expert
    layers, B*T]; ``hc_res_sum_err``; and, with ``labels`` [B, T] (the
    next token at each position), ``loss`` (main + weighted module),
    ``loss_main``, ``loss_mtp`` and ``per_sequence`` [B]. One layer at a
    time, and the head over ``block`` positions at a time."""
    def p(name):
        value = params[name]
        if hasattr(value, "asnumpy"):
            value = value.asnumpy()
        return jnp.asarray(value).astype(dtype)

    eps = cfg["rms_norm_eps"]
    n = cfg["hc_mult"]
    offset = cfg.get("share", {}).get("expert_offset", 0)
    modules = cfg.get("num_nextn_predict_layers", 0)
    weight = cfg.get("mtp_loss_weight", MTP_LOSS_WEIGHT)
    b, t = tokens.shape
    counts, gaps, errs = [], [], []

    def wrapped(x, pre, fn):
        """One sub-layer under its hyper-connections, x [B, T, n, C]."""
        h_pre, h_post, h_res = hyper_coefficients(
            x, p(pre + "hc_phi"), p(pre + "hc_bias"), p(pre + "hc_alpha"),
            cfg)
        errs.append(jnp.maximum(
            jnp.max(jnp.abs(jnp.sum(h_res, axis=-1) - 1)),
            jnp.max(jnp.abs(jnp.sum(h_res, axis=-2) - 1))))
        u = jnp.einsum("btj,btjc->btc", h_pre, x)
        y = fn(rms_norm(u, p(pre + "norm_gamma"), eps))
        return (jnp.einsum("btij,btjc->btic", h_res, x)
                + h_post[..., None] * y[:, :, None, :])

    def block_of(x, name, experts):
        def attn(u):
            cq = rms_norm(u @ p(name + "q_latent_a_proj_weight").T,
                          p(name + "q_latent_norm_gamma"), eps)
            a = latent_attention(
                cq @ p(name + "q_latent_b_proj_weight").T,
                u @ p(name + "kv_a_proj_weight").T,
                p(name + "attn_latent_gamma"), p(name + "attn_up_weight"),
                cfg)
            return a @ p(name + "o_proj_weight").T

        def ffn(u):
            if not experts:
                return swiglu(u, p(name + "gate_proj_weight"),
                              p(name + "up_proj_weight"),
                              p(name + "down_proj_weight"))
            y, count, gap = moe(
                u.reshape(b * t, -1), p(name + "moe_gate_weight"),
                p(name + "moe_gate_up_weight"), p(name + "moe_down_weight"),
                p(name + "moe_select_bias"), cfg["num_experts_per_tok"],
                cfg["norm_topk_prob"], offset,
                cfg.get("routed_scaling_factor") or 1.0)
            counts.append(count)
            gaps.append(gap)
            y = y.reshape(b, t, -1)
            if cfg.get("n_shared_experts"):
                y = y + swiglu(u, p(name + "shared_gate_proj_weight"),
                               p(name + "shared_up_proj_weight"),
                               p(name + "shared_down_proj_weight"))
            return y

        x = wrapped(x, name + "attn_", attn)
        return wrapped(x, name + "ffn_", ffn)

    def replicated(h):
        return jnp.broadcast_to(h[:, :, None, :], h.shape[:2] + (n,)
                                + h.shape[2:])

    with jax.default_matmul_precision("highest"):
        embed = p("embed_weight")
        x = replicated(embed[jnp.asarray(tokens, jnp.int32)])
        for i, experts in enumerate(expert_layers(cfg)):
            x = block_of(x, "layer%d_" % i, experts)
        h = jnp.sum(x, axis=2)
        streams = [("", rms_norm(h, p("final_norm_gamma"), eps), 0)]
        if labels is not None:
            labels = jnp.asarray(labels, jnp.int32)
        if modules and labels is not None:
            joined = jnp.concatenate(
                [rms_norm(embed[labels], p("mtp0_embed_norm_gamma"), eps),
                 rms_norm(h, p("mtp0_hidden_norm_gamma"), eps)], axis=-1)
            x = block_of(replicated(joined @ p("mtp0_proj_weight").T),
                         "mtp0_", True)
            streams.append(("mtp_", rms_norm(
                jnp.sum(x, axis=2), p("mtp0_final_norm_gamma"), eps), 1))
        head = p("lm_head_weight")
        out = {"expert_counts": jnp.stack(counts),
               "router_gap": jnp.stack(gaps),
               "hc_res_sum_err": functools.reduce(jnp.maximum, errs)}
        keep = t if last is None else last
        if labels is None:
            out["logits"] = streams[0][1][:, t - keep:] @ head.T
            return out
        for prefix, hidden, ahead in streams:
            # the stream's target at position i is labels[i + ahead]
            target = jnp.concatenate(
                [labels[:, ahead:], labels[:, :ahead]], axis=1)
            nll, logits = [], []
            for s in range(0, t, block):
                z = hidden[:, s:s + block] @ head.T           # [B, blk, V]
                logp = jax.nn.log_softmax(z, axis=-1)
                nll.append(-jnp.take_along_axis(
                    logp, target[:, s:s + block, None], axis=-1)[..., 0])
                lo = max(s, t - keep)
                if lo < s + block:
                    logits.append(z[:, lo - s:])
            nll = jnp.concatenate(nll, axis=1)[:, :t - ahead]  # [B, T - a]
            out[prefix + "logits"] = jnp.concatenate(logits, axis=1)
            out[prefix + "per_sequence"] = jnp.mean(nll, axis=1)
        out["loss_main"] = jnp.mean(out["per_sequence"])
        out["loss"] = out["loss_main"]
        if len(streams) > 1:
            out["loss_mtp"] = jnp.mean(out["mtp_per_sequence"])
            out["per_sequence"] = (out["per_sequence"]
                                   + weight * out["mtp_per_sequence"])
            out["loss"] = out["loss_main"] + weight * out["loss_mtp"]
        return out


def loss_and_grads(params, tokens, labels, cfg):
    """(main + weighted module loss, {name: gradient}) in float32."""
    params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}

    def loss_fn(ps):
        return forward(ps, tokens, cfg, labels=labels)["loss"]

    return jax.value_and_grad(loss_fn)(params)


def sgd_momentum_step(params, momenta, grads, lr, momentum):
    """The reference's own update, the rule of ``sgd_mom_update`` without
    weight decay: ``m = momentum * m - lr * g``; ``w = w + m``."""
    momenta = {k: momentum * momenta[k] - lr * grads[k] for k in params}
    return {k: params[k] + momenta[k] for k in params}, momenta
