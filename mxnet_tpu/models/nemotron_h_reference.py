"""Plain reference for ``models/nemotron_h.py``: Nemotron-3-Nano's
forward pass, loss and gradients in straightforward ``jax.numpy``.

No kernel, no chunk, no sort, no grouped matmul, no cache: the
state-space layer is the recurrence itself, one token after another (a
``lax.scan`` over time whose carry is the state ``[H, P, N]``), the
attention scores are a ``[block, T]`` matrix a head with an explicit
causal mask (``block`` queries at a time, so that 8k positions fit a
chip: a block's rows are whole softmax rows), the expert layer is a loop
over the experts held with a mask. Everything is computed in ``dtype`` —
float32 by default, under ``jax.default_matmul_precision("highest")`` so
that a TPU does not quietly run float32 matmuls in bf16 passes.
``dtype=jnp.bfloat16`` is the same mathematics one precision below what
any configuration of the system states (router, norms, step sizes,
decays, the carried state, softmaxes and the loss in bf16 too): a
comparison's tolerance has to fail it.

It follows the published ``config.json`` (``model_type`` nemotron_h)
key by key (``cfg`` below). Every block is ``h += mixer(RMSNorm(h))``
(``layer_norm_epsilon``), its kind a character of
``hybrid_override_pattern``:

``M`` (Mamba-2; H ``mamba_num_heads``, P ``mamba_head_dim``, G
``n_groups``, N ``ssm_state_size``): ``[z | xBC | dt] = W_in u`` of
widths ``H P | H P + 2 G N | H``; ``xBC = silu(conv(xBC))``, a causal
depthwise convolution of ``conv_kernel`` taps over time with bias;
``[x | B | C] = xBC``, head h reads group ``h // (H / G)``; ``dt =
softplus(dt + dt_bias)``; ``A = -exp(A_log)``; ``S_t = exp(dt_t A)
S_{t-1} + dt_t x_t B_t^T`` (zero before the first token); ``y_t = S_t
C_t + D x_t``; ``y = RMSNorm_groups(y * silu(z)) * gamma``, the
statistics over each of the G groups of columns (the gate first:
``norm_before_gate`` false); ``W_out y``. ``chunk_size`` is read by
nothing here.

``*`` (attention): ``q, k, v`` projections without bias,
``num_attention_heads`` query heads on ``num_key_value_heads`` key/value
heads of ``head_dim``, causal, scale ``1 / sqrt(head_dim)``, no rotary
embedding (the published ``nemotron_h`` attention applies none).

``E`` (experts): ``s = sigmoid(W_r x)``; the ``num_experts_per_tok``
largest of ``s + b`` chosen (``n_group`` 1); weights ``s`` over the
chosen, renormalised over ``sum + 1e-20`` (``norm_topk_prob``) and times
``routed_scaling_factor``; an expert is ``W_d relu(W_u x)^2``, no gate;
one shared expert of the same form and width
``moe_shared_expert_intermediate_size`` on the same input, added.

Final RMSNorm, untied head, mean next-token cross-entropy.

**A share.** As ``kanana2_reference``: the router's width is read from
``moe_gate_weight`` and the experts held from ``moe_down_weight``; where
a layer holds H of the router's E experts they are experts
``share.expert_offset`` .. ``+ H - 1`` and the routed part of the
layer's result is theirs alone. The state-space and attention layers
and the shared expert are whole in every share.

Left out, in the program alike: the balancing rule that moves ``b`` (it
stays where it is given: zeros) and any auxiliary loss.

Parameters are a dict under the names of the symbol's arguments
(``embed_weight``, ``layer0_norm_gamma``, ``layer0_in_proj_weight``,
``layer0_ssm_conv_weight`` [taps, channels], ``layer0_ssm_conv_bias``,
``layer0_ssm_dt_bias``, ``layer0_ssm_a_log``, ``layer0_ssm_d``,
``layer0_ssm_norm_gamma``, ``layer0_out_proj_weight``,
``layer1_moe_gate_weight`` [d, E], ``layer1_moe_gate_up_weight`` [H, d,
width] (the up projection alone), ``layer1_moe_down_weight``,
``layer1_moe_select_bias``, ``layer1_shared_up_proj_weight`` ...,
``layer5_q_proj_weight`` ..., ``final_norm_gamma``, ``lm_head_weight``;
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


def relu2(x, w_up, w_down):
    return jnp.square(jax.nn.relu(x @ w_up.T)) @ w_down.T


def mamba2(proj, conv_w, conv_b, dt_bias, a_log, d_skip, gamma, cfg):
    """proj [B, T, 2 H P + 2 G N + H] -> [B, T, H P], one token after
    another."""
    b, t, _ = proj.shape
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    d_in, taps = h * p, conv_w.shape[0]
    z, xbc, dt = jnp.split(proj, [d_in, proj.shape[2] - h], axis=-1)
    # tap ``taps - 1`` meets the current token, tap 0 the oldest
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    windows = jnp.stack([padded[:, j:j + t] for j in range(taps)], axis=0)
    xbc = jax.nn.silu(jnp.sum(windows * conv_w[:, None, None, :], axis=0)
                      + conv_b)
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
        + cfg["layer_norm_epsilon"])
    return gamma * groups.reshape(b, t, d_in)


def attention(q, k, v, cfg, block=256):
    """Causal softmax attention without positions, q [B, T, H * D], k
    and v [B, T, KV * D], scores materialised for ``block`` queries at a
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


def moe(x, gate_w, w_up, w_down, select_bias, top_k, norm_topk_prob,
        offset=0, routed_scale=1.0):
    """x [N, d]; the router is ``gate_w`` [d, E], the experts held are
    E's ``offset`` .. ``offset + H - 1`` (``w_down`` [H, h, d]).
    Returns the held experts' part of the layer's output, the row count
    of each of the E experts, and each token's margin between its last
    chosen and its first rejected expert (selection scores) where one of
    the two is held here — +inf where neither is: that call cannot
    change this share's result."""
    num_experts = gate_w.shape[1]
    held = w_down.shape[0]
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
        y = jnp.square(jax.nn.relu(x @ w_up[e])) @ w_down[e]
        out = out + y * weight[:, None]
    counts = jnp.sum(jax.nn.one_hot(top_i, num_experts, dtype=jnp.int32),
                     axis=(0, 1))
    return out, counts, gap


def expert_layers(cfg):
    """[expert layer?] per block of the pattern."""
    return [kind == "E" for kind in cfg["hybrid_override_pattern"]]


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

    eps = cfg["layer_norm_epsilon"]
    offset = cfg.get("share", {}).get("expert_offset", 0)
    b, t = tokens.shape
    with jax.default_matmul_precision("highest"):
        h = p("embed_weight")[jnp.asarray(tokens, jnp.int32)]  # [B, T, d]
        counts, gaps = [], []
        for i, kind in enumerate(cfg["hybrid_override_pattern"]):
            n = "layer%d_" % i
            x = rms_norm(h, p(n + "norm_gamma"), eps)
            if kind == "M":
                y = mamba2(
                    x @ p(n + "in_proj_weight").T, p(n + "ssm_conv_weight"),
                    p(n + "ssm_conv_bias"), p(n + "ssm_dt_bias"),
                    p(n + "ssm_a_log"), p(n + "ssm_d"),
                    p(n + "ssm_norm_gamma"), cfg) @ p(n + "out_proj_weight").T
            elif kind == "*":
                y = attention(
                    x @ p(n + "q_proj_weight").T, x @ p(n + "k_proj_weight").T,
                    x @ p(n + "v_proj_weight").T, cfg) \
                    @ p(n + "o_proj_weight").T
            elif kind == "E":
                y, count, gap = moe(
                    x.reshape(b * t, -1), p(n + "moe_gate_weight"),
                    p(n + "moe_gate_up_weight"), p(n + "moe_down_weight"),
                    p(n + "moe_select_bias"), cfg["num_experts_per_tok"],
                    cfg["norm_topk_prob"], offset,
                    cfg.get("routed_scaling_factor") or 1.0)
                y = y.reshape(b, t, -1)
                if cfg.get("n_shared_experts"):
                    y = y + relu2(x, p(n + "shared_up_proj_weight"),
                                  p(n + "shared_down_proj_weight"))
                counts.append(count)
                gaps.append(gap)
            else:
                raise ValueError("block %d of the pattern is %r" % (i, kind))
            h = h + y
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
