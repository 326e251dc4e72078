"""Plain reference for ``models/lfm2.py``: LFM2-24B-A2B's forward pass,
loss and gradients in straightforward ``jax.numpy``.

No kernel, no sort, no grouped matmul, no cache: the convolution is a
loop over its taps, the attention scores are a ``[block, T]`` matrix a
head with an explicit causal mask (``block`` queries at a time, so that
8k positions fit a chip: a block's rows are whole softmax rows), the
expert layer is a loop over the experts held with a mask. Everything is
computed in ``dtype`` — float32 by default, under
``jax.default_matmul_precision("highest")`` so that a TPU does not
quietly run float32 matmuls in bf16 passes. ``dtype=jnp.bfloat16`` is
the same mathematics one precision below what any configuration of the
system states (router, norms, the convolution's gates and sum, softmaxes
and the loss in bf16 too): a comparison's tolerance has to fail it.

It follows the published ``config.json`` (``model_type`` lfm2_moe) key
by key (``cfg`` below). ``h_0 = E[ids]``; every layer is ``h +=
mixer(RMSNorm(h))`` then ``h += ffn(RMSNorm(h))`` (``norm_eps``), the
mixer an entry of ``layer_types``:

``conv``: ``[B | C | x] = W_in u`` (three times ``hidden_size``, split
in that order); ``z = B * x``; ``c_t = sum_j w_j * z_{t - (L - 1) + j}``
over the ``conv_L_cache`` taps (depthwise, causal, ``z`` zero before the
sequence, the last tap meets the current token, no bias); ``W_out (C *
c)``. No activation anywhere in the mixer.

``full_attention``: ``q, k, v`` projections without bias; RMSNorm over
each head's own ``head_dim`` columns of ``q`` and of ``k`` (one gamma a
projection, shared by the heads), THEN the rotation of the whole head
(half-rotation pairs ``(i, i + D/2)``, ``rope_parameters.rope_theta``);
``num_attention_heads`` query heads on ``num_key_value_heads`` key/value
heads, causal, scale ``1 / sqrt(head_dim)``; ``W_o``.

The feed-forward is ``W_2 (silu(W_1 x) * W_3 x)`` of width
``intermediate_size`` in the first ``num_dense_layers`` layers and
sparse after: ``s = sigmoid(W_r x)``; the ``num_experts_per_tok``
largest of ``s + b`` chosen; weights ``s`` over the chosen, renormalised
over ``sum + 1e-6`` (``norm_topk_prob``) and times
``routed_scaling_factor``; an expert is a SwiGLU of width
``moe_intermediate_size``; no shared expert.

Final RMSNorm, a head that is the embedding's matrix, mean next-token
cross-entropy.

**A share.** As ``kanana2_reference``: the router's width is read from
``moe_gate_weight`` and the experts held from ``moe_down_weight``; where
a layer holds H of the router's E experts they are experts
``share.expert_offset`` .. ``+ H - 1`` and the layer's result is theirs
alone. The mixers and the dense feed-forward are whole in every share.

Left out, in the program alike: the balancing rule that moves ``b`` (it
stays where it is given: zeros) and any auxiliary loss.

Parameters are a dict under the names of the symbol's arguments
(``embed_weight`` [V, d], the head too; ``layer0_operator_norm_gamma``,
``layer0_conv_in_proj_weight`` [3 d, d], ``layer0_conv_weight`` [taps,
d], ``layer0_conv_out_proj_weight``, ``layer0_ffn_norm_gamma``,
``layer0_gate_proj_weight`` ..., ``layer1_q_proj_weight`` ...,
``layer1_q_norm_gamma`` [head_dim], ``layer1_k_norm_gamma``,
``layer1_moe_gate_weight`` [d, E], ``layer1_moe_gate_up_weight`` [H, d,
2 width] (an expert's gate columns, then its up columns),
``layer1_moe_down_weight`` [H, width, d], ``layer1_moe_select_bias``,
``final_norm_gamma``; ``FullyConnected`` weights are ``[out, in]``).
Host arrays are fine: a layer's parameters are placed when the layer
runs, so an un-jitted call holds one layer's float32 weights at a time.
"""
import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x, gamma, eps):
    return gamma * (x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps))


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate.T) * (x @ w_up.T)) @ w_down.T


def short_conv(proj, conv_w):
    """proj [B, T, 3 d] (``B | C | x``), conv_w [taps, d] -> [B, T, d]:
    a loop over the taps."""
    t, taps = proj.shape[1], conv_w.shape[0]
    gate_b, gate_c, x = jnp.split(proj, 3, axis=-1)
    # tap ``taps - 1`` meets the current token, tap 0 the oldest
    padded = jnp.pad(gate_b * x, ((0, 0), (taps - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for j in range(taps):
        out = out + conv_w[j] * padded[:, j:j + t]
    return gate_c * out


def rope(x, theta):
    """x [B, T, heads, D] rotated by its positions, pairs (i, i + D/2)."""
    t, d = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    angles = np.arange(t, dtype=np.float64)[:, None] * inv_freq[None, :]
    cos = jnp.asarray(np.cos(angles), x.dtype)[None, :, None, :]
    sin = jnp.asarray(np.sin(angles), x.dtype)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def head_dim_of(cfg):
    return (cfg.get("head_dim")
            or cfg["hidden_size"] // cfg["num_attention_heads"])


def attention(q, k, v, q_gamma, k_gamma, cfg, block=256):
    """Causal softmax attention, q [B, T, H * D], k and v [B, T, KV * D]:
    each head's query and key normed over its own D columns, then
    rotated; scores materialised for ``block`` queries at a time."""
    b, t, _ = q.shape
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, eps = head_dim_of(cfg), cfg["norm_eps"]
    theta = float(cfg["rope_parameters"]["rope_theta"])
    q = rope(rms_norm(q.reshape(b, t, heads, d), q_gamma, eps), theta)
    k = rope(rms_norm(k.reshape(b, t, kv, d), k_gamma, eps), theta)
    k = jnp.repeat(k, heads // kv, axis=2)
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


def moe(x, gate_w, w_gate_up, w_down, select_bias, top_k, offset=0,
        routed_scale=1.0):
    """x [N, d]; the router is ``gate_w`` [d, E], the experts held are
    E's ``offset`` .. ``offset + H - 1`` (``w_down`` [H, width, d]).
    Returns the held experts' part of the layer's output, the row count
    of each of the E experts, and each token's margin between its last
    chosen and its first rejected expert (selection scores) where one of
    the two is held here — +inf where neither is: that call cannot
    change this share's result."""
    num_experts = gate_w.shape[1]
    held, width = w_down.shape[:2]
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
    top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-6)
    top_w = top_w * routed_scale
    out = jnp.zeros_like(x)
    for e in range(held):
        chosen = top_i == offset + e                          # [N, k]
        weight = jnp.sum(jnp.where(chosen, top_w, 0), axis=-1)
        up = x @ w_gate_up[e]
        y = (jax.nn.silu(up[:, :width]) * up[:, width:]) @ w_down[e]
        out = out + y * weight[:, None]
    counts = jnp.sum(jax.nn.one_hot(top_i, num_experts, dtype=jnp.int32),
                     axis=(0, 1))
    return out, counts, gap


def expert_layers(cfg):
    """[expert layer?] per layer: those after the leading dense ones."""
    return [i >= cfg["num_dense_layers"]
            for i in range(cfg["num_hidden_layers"])]


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

    eps = cfg["norm_eps"]
    offset = cfg.get("share", {}).get("expert_offset", 0)
    sparse = expert_layers(cfg)
    b, t = tokens.shape
    with jax.default_matmul_precision("highest"):
        head = p("embed_weight")
        h = head[jnp.asarray(tokens, jnp.int32)]              # [B, T, d]
        counts, gaps = [], []
        for i, kind in enumerate(cfg["layer_types"]):
            n = "layer%d_" % i
            x = rms_norm(h, p(n + "operator_norm_gamma"), eps)
            if kind == "conv":
                y = short_conv(x @ p(n + "conv_in_proj_weight").T,
                               p(n + "conv_weight")) \
                    @ p(n + "conv_out_proj_weight").T
            elif kind == "full_attention":
                y = attention(
                    x @ p(n + "q_proj_weight").T, x @ p(n + "k_proj_weight").T,
                    x @ p(n + "v_proj_weight").T, p(n + "q_norm_gamma"),
                    p(n + "k_norm_gamma"), cfg) @ p(n + "o_proj_weight").T
            else:
                raise ValueError("layer %d of layer_types is %r" % (i, kind))
            h = h + y
            x = rms_norm(h, p(n + "ffn_norm_gamma"), eps)
            if not sparse[i]:
                h = h + swiglu(x, p(n + "gate_proj_weight"),
                               p(n + "up_proj_weight"),
                               p(n + "down_proj_weight"))
                continue
            y, count, gap = moe(
                x.reshape(b * t, -1), p(n + "moe_gate_weight"),
                p(n + "moe_gate_up_weight"), p(n + "moe_down_weight"),
                p(n + "moe_select_bias"), cfg["num_experts_per_tok"],
                offset, float(cfg.get("routed_scaling_factor") or 1.0))
            h = h + y.reshape(b, t, -1)
            counts.append(count)
            gaps.append(gap)
        h = rms_norm(h, p("final_norm_gamma"), eps)
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
