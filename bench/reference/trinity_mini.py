"""Plain reference for ``models/afmoe.py``: Trinity-Mini's forward pass,
loss and gradients in straightforward ``jax.numpy``.

No kernel, no sort, no grouped matmul, no cache: the attention scores are
a ``[block, T]`` matrix a head under an explicit causal / window mask
(``block`` queries at a time, so that 8k positions fit a chip: a block's
rows are whole softmax rows), the expert layer is a loop over the experts
held with a mask. Everything is computed in ``dtype`` — float32 by
default, under ``jax.default_matmul_precision("highest")`` so that a TPU
does not quietly run float32 matmuls in bf16 passes.
``dtype=jnp.bfloat16`` is the same mathematics one precision below what
any configuration of the system states (router, norms, rotation,
softmaxes, the gate and the loss in bf16 too): a comparison's tolerance
has to fail it.

It follows the published ``config.json`` (``model_type`` afmoe) key by
key (``cfg`` below) and, for what no key states, the published modelling
code of that model type. ``h_0 = sqrt(hidden_size) E[ids]``
(``mup_enabled``); every layer is

    h += RMSNorm(attention(RMSNorm(h)));  h += RMSNorm(ffn(RMSNorm(h)))

four norms a block (``rms_norm_eps``). Attention: ``q, k, v, g``
projections without bias (``g`` as wide as ``q``); RMSNorm over each
head's own ``head_dim`` columns of ``q`` and of ``k`` (one gamma a
projection, shared by the heads); in a ``sliding_attention`` layer THEN
the rotation of the whole head (half-rotation pairs ``(i, i + D/2)``,
``rope_theta``) and in a ``full_attention`` layer nothing;
``num_attention_heads`` query heads on ``num_key_value_heads`` key/value
heads, causal, in a sliding layer query i sees keys ``i - j <
sliding_window``, scale ``1 / sqrt(head_dim)``; the result times
``sigmoid(g)``, an element each; ``W_o``.

The feed-forward is ``W_2 (silu(W_1 x) * W_3 x)`` of width
``intermediate_size`` in the first ``num_dense_layers`` layers and sparse
after: ``s = sigmoid(W_r x)``; the ``num_experts_per_tok`` largest of ``s
+ b`` chosen; weights ``s`` over the chosen, renormalised over ``sum +
1e-20`` (``route_norm``) and times ``route_scale``; an expert is a SwiGLU
of width ``moe_intermediate_size``; beside them one shared SwiGLU of
width ``moe_intermediate_size * num_shared_experts`` that every token
passes.

Final RMSNorm, an untied head, mean next-token cross-entropy.

**A share.** As ``kanana2_reference``: the router's width is read from
``moe_gate_weight`` and the experts held from ``moe_down_weight``; where
a layer holds H of the router's E experts they are experts
``share.expert_offset`` .. ``+ H - 1`` and the routed part of the layer's
result is theirs alone. Attention, the shared expert and the dense
feed-forward are whole in every share.

Left out, in the program alike: the balancing rule that moves ``b`` (it
stays where it is given: zeros), the auxiliary loss
(``load_balance_coeff``).

Parameters are a dict under the names of the symbol's arguments
(``embed_weight`` [V, d], ``layer0_attn_norm_gamma``,
``layer0_{q,k,v}_proj_weight``, ``layer0_attn_gate_proj_weight`` [H D,
d], ``layer0_{q,k}_norm_gamma`` [head_dim], ``layer0_o_proj_weight``,
``layer0_attn_post_norm_gamma``, ``layer0_ffn_norm_gamma``,
``layer0_{gate,up,down}_proj_weight``, ``layer0_ffn_post_norm_gamma``,
``layer2_moe_gate_weight`` [d, E], ``layer2_moe_gate_up_weight`` [H, d,
2 width] (an expert's gate columns, then its up columns),
``layer2_moe_down_weight`` [H, width, d], ``layer2_moe_select_bias``,
``layer2_shared_{gate,up,down}_proj_weight``, ``final_norm_gamma``,
``lm_head_weight`` [V, d]; ``FullyConnected`` weights are ``[out,
in]``). Host arrays are fine: a layer's parameters are placed when the
layer runs, so an un-jitted call holds one layer's float32 weights at a
time.
"""
import jax
import jax.numpy as jnp
import numpy as np

SLIDING, FULL = "sliding_attention", "full_attention"


def rms_norm(x, gamma, eps):
    return gamma * (x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps))


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate.T) * (x @ w_up.T)) @ w_down.T


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


def gated_attention(q, k, v, gate, q_gamma, k_gamma, cfg, kind, block=256):
    """Causal softmax attention times ``sigmoid(gate)``: q and gate [B, T,
    H * D], k and v [B, T, KV * D]. Each head's query and key normed
    over its own D columns; rotated in a sliding layer, which also sees
    ``sliding_window`` keys at the most; scores materialised for
    ``block`` queries at a time."""
    b, t, _ = q.shape
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, eps = head_dim_of(cfg), cfg["rms_norm_eps"]
    q = rms_norm(q.reshape(b, t, heads, d), q_gamma, eps)
    k = rms_norm(k.reshape(b, t, kv, d), k_gamma, eps)
    if kind == SLIDING:
        theta = float(cfg["rope_theta"])
        q, k = rope(q, theta), rope(k, theta)
    elif kind != FULL:
        raise ValueError("layer_types has %r" % (kind,))
    k = jnp.repeat(k, heads // kv, axis=2)
    v = jnp.repeat(v.reshape(b, t, kv, d), heads // kv, axis=2)
    pos = np.arange(t)
    out = []
    for s in range(0, t, block):
        # a Python float: a numpy scalar would promote to float64
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, s:s + block], k) \
            * d ** -0.5
        ahead = pos[s:s + block, None] - pos[None, :]       # i - j
        mask = ahead >= 0
        if kind == SLIDING:
            mask &= ahead < cfg["sliding_window"]
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", probs, v))
    out = jnp.concatenate(out, axis=1).reshape(b, t, heads * d)
    return out * jax.nn.sigmoid(gate)


def moe(x, gate_w, w_gate_up, w_down, select_bias, top_k, offset=0,
        routed_scale=1.0, renorm=True, scoring="sigmoid"):
    """x [N, d]; the router is ``gate_w`` [d, E], the experts held are
    E's ``offset`` .. ``offset + H - 1`` (``w_down`` [H, width, d]).
    Returns the held experts' part of the layer's output, the row count
    of each of the E experts, and each token's margin between its last
    chosen and its first rejected expert (selection scores) where one of
    the two is held here — +inf where neither is: that call cannot
    change this share's result."""
    num_experts = gate_w.shape[1]
    held, width = w_down.shape[:2]
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
    if renorm:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
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

    def layer_norm(x, name):
        return rms_norm(x, p(name + "_gamma"), cfg["rms_norm_eps"])

    offset = cfg.get("share", {}).get("expert_offset", 0)
    sparse = expert_layers(cfg)
    shared = cfg.get("num_shared_experts") or 0
    b, t = tokens.shape
    with jax.default_matmul_precision("highest"):
        h = p("embed_weight")[jnp.asarray(tokens, jnp.int32)]  # [B, T, d]
        if cfg.get("mup_enabled", False):
            # a Python float: a numpy scalar would promote to float64
            h = h * float(cfg["hidden_size"] ** 0.5)
        counts, gaps = [], []
        for i, kind in enumerate(cfg["layer_types"]):
            n = "layer%d_" % i
            x = layer_norm(h, n + "attn_norm")
            y = gated_attention(
                x @ p(n + "q_proj_weight").T, x @ p(n + "k_proj_weight").T,
                x @ p(n + "v_proj_weight").T,
                x @ p(n + "attn_gate_proj_weight").T,
                p(n + "q_norm_gamma"), p(n + "k_norm_gamma"), cfg, kind)
            h = h + layer_norm(y @ p(n + "o_proj_weight").T,
                               n + "attn_post_norm")
            x = layer_norm(h, n + "ffn_norm")
            if not sparse[i]:
                y = swiglu(x, p(n + "gate_proj_weight"),
                           p(n + "up_proj_weight"),
                           p(n + "down_proj_weight"))
            else:
                y, count, gap = moe(
                    x.reshape(b * t, -1), p(n + "moe_gate_weight"),
                    p(n + "moe_gate_up_weight"), p(n + "moe_down_weight"),
                    p(n + "moe_select_bias"), cfg["num_experts_per_tok"],
                    offset, float(cfg.get("route_scale") or 1.0),
                    bool(cfg.get("route_norm", True)),
                    cfg.get("score_func", "sigmoid"))
                y = y.reshape(b, t, -1)
                if shared:
                    y = y + swiglu(x, p(n + "shared_gate_proj_weight"),
                                   p(n + "shared_up_proj_weight"),
                                   p(n + "shared_down_proj_weight"))
                counts.append(count)
                gaps.append(gap)
            h = h + layer_norm(y, n + "ffn_post_norm")
        h = layer_norm(h, "final_norm")
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
