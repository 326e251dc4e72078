"""Plain reference for ``models/minicpm_sala.py``: MiniCPM-SALA's forward
pass, loss and gradients in straightforward ``jax.numpy``.

No kernel, no chunk, no cache: the linear layers are the recurrence itself,
one token after another (a ``lax.scan`` over time whose carry is the float32
state ``[H, D, D]``); the sparse layers choose their blocks with
``jnp.argsort`` and run a masked softmax over ``[block of queries, T]``
scores a head, so that 16,384 positions fit a chip. Everything is computed in
``dtype``: float32 by default, under
``jax.default_matmul_precision("highest")`` so that a TPU does not quietly
run float32 matmuls in bf16 passes. ``dtype="bfloat16"`` is the same
mathematics one precision below what any configuration of the system states
(norms, the carried state, softmaxes, the block scores and the loss in bf16
too): a comparison's tolerance has to fail it.

It follows the published ``config.json`` (``model_type`` minicpm_sala) key by
key (``cfg`` below). With ``s = scale_depth / sqrt(L)``, L the PUBLISHED
depth (``share.layers_of`` where fewer layers are held; MiniCPM's scaling,
arXiv:2404.06395):

    h = scale_emb * E[token]
    h = h + s * mixer_l(RMSNorm(h))          # mixer_types[l]
    h = h + s * SwiGLU(RMSNorm(h))
    logits = W_head (RMSNorm(h) / (hidden_size / dim_model_base))

``lightning-attn`` (Lightning Attention, arXiv:2401.04658, as MiniMax-01
runs it, arXiv:2501.08313), ``lightning_nh`` heads of ``lightning_head_dim``
for queries, keys and values: ``q, k, v = W_q x, W_k x, W_v x``; an RMSNorm
over each head's own columns on q and on k, one gamma a projection shared by
its heads (``qk_norm``); RoPE over the whole head on q and k, the halves
rotated (``lightning_use_rope``); ``S_t = lambda S_{t-1} + k_t v_t^T``, ``o_t
= S_t^T q_t / sqrt(D)`` (``lightning_scale``), ``lambda_h = exp(-slope_h)``,
``slope(h, l) = 2^(-8 (h + 1) / H) (1 - l / (L - 1) + 1e-5)`` for the
published head h of H and the published layer l of L; an RMSNorm over each
head's own columns of o (``use_output_norm``); ``o * sigmoid(W_g x)``
(``use_output_gate``); ``W_o``.

``minicpm4`` (InfLLM-V2, arXiv:2506.07900, arXiv:2509.24663):
``num_attention_heads`` query heads on ``num_key_value_heads`` key/value
heads of ``head_dim``, no rotation (``attn_use_rope`` false), the same norm a
head on q and k, the same sigmoid gate before ``W_o``
(``attn_use_output_gate``). Where the sequence is longer than
``sparse_config.dense_len``, per key/value head and the query heads that
read it: pooled keys ``Kc_j = mean(k[stride j : stride j + kernel])``;
``p[t, h, :] = softmax over {j : stride j + kernel <= t + 1} of q . Kc_j /
sqrt(D)``, summed over the group's heads; a block of ``block_size`` keys
scores the largest ``p`` of the windows that overlap it; query t keeps its
first ``init_blocks`` blocks, every block that holds one of its last
``window_size`` keys and the ``topk`` best-scored of the blocks between
(ties to the lower index); its softmax runs over the keys s <= t of the
kept blocks. The choice carries no gradient. At ``dense_len`` or fewer
positions every key is read.

**Departures from the published descriptions**, shared with the symbol: the
``topk`` blocks are counted beside the initial and the local ones (the row
says only "block top-64"; MiniCPM4's kernel counts its forced blocks inside
its top-k, a detail the configuration's ``assumed.sparse_config`` states);
the local window is whole blocks (every block that holds one of the last
``window_size`` keys, up to ``window_size + block_size - 1`` keys), where a
token-exact window would cut the oldest block; no auxiliary loss of any
kind.

**A share** (one of the chips that divide each layer by tensor parallelism)
is the same mathematics on the heads and columns held: widths are read from
the parameters' shapes and counts from ``cfg`` as given (``lightning_nh``,
``num_attention_heads``, ``num_key_value_heads`` the counts HELD;
``share.first_lightning_head`` and ``share.first_layer`` place the held
heads and layers among the published ones for the slopes), so a share's
result is its partial sum and nothing stands in for the other chip or its
all-reduce.

Parameters are a dict under the names of the symbol's arguments
(``embed_weight``, ``layer0_attn_norm_gamma``, ``layer0_q_proj_weight`` ...
``layer0_attn_gate_proj_weight``, ``layer0_q_norm_gamma``,
``layer1_linattn_q_proj_weight`` ... ``layer1_linattn_o_norm_gamma``,
``layer0_ffn_norm_gamma``, ``layer0_gate_proj_weight`` ...,
``final_norm_gamma``, ``lm_head_weight``; ``FullyConnected`` weights are
``[out, in]``). Host arrays are fine: a layer's parameters are placed when
the layer runs, so an un-jitted call holds one layer's weights at a time.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

SPARSE_DEFAULTS = {"kernel_size": 32, "kernel_stride": 16, "block_size": 64,
                   "topk": 64, "init_blocks": 1, "window_size": 2048,
                   "dense_len": 8192}


def rms_norm(x, gamma, eps):
    return gamma * (x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps))


def head_norm(x, gamma, heads, eps):
    """RMSNorm over each head's own columns of x [B, T, heads * D], one
    gamma [D] shared by the heads."""
    b, t, width = x.shape
    return rms_norm(x.reshape(b, t, heads, width // heads), gamma,
                    eps).reshape(b, t, width)


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


def published(cfg):
    """(layers, lightning heads) of the uncut model and (first layer, first
    lightning head) held: what the slopes and the depth scaling read."""
    share = cfg.get("share", {})
    return (share.get("layers_of", cfg["num_hidden_layers"]),
            share.get("lightning_heads_of", cfg["lightning_nh"]),
            share.get("first_layer", 0),
            share.get("first_lightning_head", 0))


def slopes(cfg, layer):
    """The held lightning heads' slopes in held layer ``layer``."""
    layers_of, heads_of, first_layer, first_head = published(cfg)
    factor = 1.0 - (first_layer + layer) / max(layers_of - 1, 1) + 1e-5
    return [2.0 ** (-8.0 * (h + 1) / heads_of) * factor
            for h in range(first_head, first_head + cfg["lightning_nh"])]


def lightning(x, p, prefix, cfg, layer):
    """One ``lightning-attn`` mixer on its normed input x [B, T, d]: the
    recurrence token by token."""
    heads, d = cfg["lightning_nh"], cfg["lightning_head_dim"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    b, t, _ = x.shape
    n = prefix + "linattn_"
    q, k, v, g = (x @ p(n + name + "_proj_weight").T
                  for name in ("q", "k", "v", "g"))
    q = rope(head_norm(q, p(n + "q_norm_gamma"), heads, eps), heads, theta)
    k = rope(head_norm(k, p(n + "k_norm_gamma"), heads, eps), heads, theta)
    decay = jnp.exp(-jnp.asarray(slopes(cfg, layer), jnp.float32)).astype(
        x.dtype)[None, :, None, None]
    q, k, v = (y.reshape(b, t, heads, d).transpose(1, 0, 2, 3)
               for y in (q, k, v))

    def token(state, at):                                    # [B, H, D, D]
        q_t, k_t, v_t = at
        state = decay * state + k_t[..., :, None] * v_t[..., None, :]
        return state, jnp.einsum("bhdp,bhd->bhp", state, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((b, heads, d, d), x.dtype),
                        (q, k, v))
    o = o.transpose(1, 0, 2, 3) * d ** -0.5                  # [B, T, H, D]
    o = rms_norm(o, p(n + "o_norm_gamma"), eps).reshape(b, t, heads * d)
    return (o * jax.nn.sigmoid(g)) @ p(n + "o_proj_weight").T


def choose_blocks(q, k, sparse):
    """q [T, H, D] (one key/value head's query heads), k [T, D] -> (kept
    [T, blocks] bool, gap [T]): the rule of the module docstring; ``gap``
    is the distance between the last chosen block's score and the best
    rejected one's (+inf where every candidate is taken)."""
    t, _, d = q.shape
    pool, stride = sparse["kernel_size"], sparse["kernel_stride"]
    block, topk = sparse["block_size"], sparse["topk"]
    blocks = -(-t // block)
    windows = max((t - pool) // stride + 1, 0)
    inside = stride * np.arange(windows)[:, None] + np.arange(pool)[None, :]
    pooled = jnp.mean(k[inside], axis=1)                     # [windows, D]
    at = np.arange(t)
    ends = stride * np.arange(windows) + pool                # a window's end
    rows = []
    for lo in range(0, t, 1024):    # [H, 1024, windows] scores at a time
        live = ends[None, :] <= at[lo:lo + 1024, None] + 1
        s = jnp.einsum("thd,jd->htj", q[lo:lo + 1024], pooled) * d ** -0.5
        s = jnp.where(live[None], s, -jnp.inf)
        top = jnp.max(s, axis=-1, keepdims=True, initial=-jnp.inf)
        e = jnp.where(live[None],
                      jnp.exp(s - jnp.where(top > -jnp.inf, top, 0)), 0)
        total = jnp.sum(e, axis=-1, keepdims=True)
        rows.append(jnp.sum(e / jnp.where(total > 0, total, 1), axis=0))
    p = jnp.concatenate(rows, axis=0)                        # [T, windows]
    # window j overlaps block i where stride j < block (i + 1) and
    # stride j + pool > block i: a run of windows a block
    runs = [(max((block * i - pool) // stride + 1, 0),
             min(-(-block * (i + 1) // stride), windows))
            for i in range(blocks)]
    score = jnp.stack([jnp.max(p[:, lo:hi], axis=1, initial=0)
                       for lo, hi in runs], axis=1)          # [T, blocks]
    index = np.arange(blocks)[None, :]
    first = (np.maximum(at - sparse["window_size"] + 1, 0) // block)[:, None]
    init = np.minimum(first, sparse["init_blocks"])
    fixed = ((index >= first) & (index <= at[:, None] // block)) \
        | (index < init)
    others = (index >= init) & (index < first)
    ranked = jnp.where(others, score.astype(jnp.float32), -jnp.inf)
    order = jnp.argsort(-ranked, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    chosen = others & (rank < topk)
    best = -jnp.sort(-ranked, axis=-1)
    gap = (best[:, topk - 1] - best[:, topk]) if blocks > topk else \
        jnp.full((t,), jnp.inf)
    gap = jnp.where(np.sum(others, axis=1) > topk, gap, jnp.inf)
    return fixed | chosen, gap


def sparse_attention(x, p, prefix, cfg, rows=256, stats=None):
    """One ``minicpm4`` mixer on its normed input x [B, T, d]; ``stats``, a
    dict, receives ``gap`` [B * T] (the smallest of the key/value heads'),
    ``kept`` (the (query, key) pairs kept a batch row, summed over the
    key/value heads) and ``blocks`` (the choice, bool [B, key/value heads, T,
    blocks]); the last two None where every key is read."""
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    sparse = dict(SPARSE_DEFAULTS, **cfg.get("sparse_config", {}))
    b, t, _ = x.shape
    group = heads // kv
    q, k, v, g = (x @ p(prefix + name + "_proj_weight").T
                  for name in ("q", "k", "v", "attn_gate"))
    q = head_norm(q, p(prefix + "q_norm_gamma"), heads, eps).reshape(
        b, t, kv, group, d)
    k = head_norm(k, p(prefix + "k_norm_gamma"), kv, eps).reshape(b, t, kv, d)
    v = v.reshape(b, t, kv, d)
    select = t > sparse["dense_len"]
    block = sparse["block_size"]
    at = np.arange(t)
    out = []
    gaps, chosen, kept_pairs = [], [], np.zeros((b,), np.int64)
    for i in range(b):
        per_kv = []
        for j in range(kv):
            kept = None
            if select:
                kept, gap = choose_blocks(q[i, :, j], k[i, :, j], sparse)
                gaps.append(gap)
                chosen.append(kept)
            parts = []
            for s in range(0, t, rows):
                mask = at[s:s + rows, None] >= at[None, :]
                if kept is not None:
                    mask = mask & jnp.repeat(kept[s:s + rows], block,
                                             axis=1)[:, :t]
                    kept_pairs[i] += int(jnp.sum(mask))
                scores = jnp.einsum("qhd,kd->hqk", q[i, s:s + rows, j],
                                    k[i, :, j]) * d ** -0.5
                scores = jnp.where(mask[None], scores, -jnp.inf)
                parts.append(jnp.einsum("hqk,kd->qhd",
                                        jax.nn.softmax(scores, axis=-1),
                                        v[i, :, j]))
            per_kv.append(jnp.concatenate(parts, axis=0))    # [T, group, D]
        out.append(jnp.stack(per_kv, axis=1).reshape(t, heads * d))
    if stats is not None:
        stats["kept"] = kept_pairs if select else None
        stats["blocks"] = (jnp.stack(chosen).reshape((b, kv)
                                                     + chosen[0].shape)
                           if select else None)
        stats["gap"] = (jnp.min(jnp.stack(gaps).reshape(b, kv, t), axis=1)
                        .reshape(b * t) if select
                        else jnp.full((b * t,), jnp.inf))
    return (jnp.stack(out) * jax.nn.sigmoid(g)) @ p(prefix + "o_proj_weight").T


def mlp(x, p, prefix, block=4096):
    """``W_down (W_up x * silu(W_gate x))``, ``block`` positions at a time
    (the two wide activations of 16,384 positions are 1 GB in float32)."""
    gate_w, up_w, down_w = (p(prefix + name + "_proj_weight").T
                            for name in ("gate", "up", "down"))
    return jnp.concatenate(
        [((x[:, s:s + block] @ up_w) * jax.nn.silu(x[:, s:s + block]
                                                   @ gate_w)) @ down_w
         for s in range(0, x.shape[1], block)], axis=1)


def forward(params, tokens, cfg, labels=None, dtype=jnp.float32,
            last=None, block=512, parts=None):
    """tokens [B, T] int. Returns a dict: ``logits`` [B, last or T, V],
    ``expert_counts`` [0, 0], ``router_gap`` [sparse layers or 1, B*T] (a
    sparse layer's ``choose_blocks`` gap where it chooses, +inf elsewhere),
    ``block_keys_kept`` (a list, one entry a sparse layer: the pairs kept a
    batch row, or None where every key is read) and, with ``labels`` [B,
    T], ``loss`` (mean token cross-entropy) and ``per_sequence`` [B]. One
    layer at a time, and the head over ``block`` positions at a time, so
    the whole ``[T, V]`` table is never held. ``parts``, a list, receives a
    dict a layer: the stream the mixer is added to (``h``), the mixer's
    scaled output (``mixer``), the stream the MLP is added to (``h_mid``)
    and its scaled output (``mlp``)."""
    def p(name):
        value = params[name]
        if hasattr(value, "asnumpy"):
            value = value.asnumpy()
        return jnp.asarray(value).astype(dtype)

    eps = cfg["rms_norm_eps"]
    b, t = tokens.shape
    depth = cfg["scale_depth"] / math.sqrt(published(cfg)[0])
    with jax.default_matmul_precision("highest"):
        h = p("embed_weight")[jnp.asarray(tokens, jnp.int32)] \
            * cfg["scale_emb"]                                 # [B, T, d]
        gaps, kept = [], []
        for i, kind in enumerate(cfg["mixer_types"]):
            n = "layer%d_" % i
            x = rms_norm(h, p(n + "attn_norm_gamma"), eps)
            if kind == "lightning-attn":
                y = lightning(x, p, n, cfg, i)
            else:
                stats = {}
                y = sparse_attention(x, p, n, cfg, stats=stats)
                gaps.append(stats["gap"])
                kept.append(stats["kept"])
            read, y = h, y * depth
            h = h + y
            z = mlp(rms_norm(h, p(n + "ffn_norm_gamma"), eps), p, n) * depth
            if parts is not None:
                parts.append({"h": read, "mixer": y, "h_mid": h, "mlp": z})
            h = h + z
        h = rms_norm(h, p("final_norm_gamma"), eps) \
            / (cfg["hidden_size"] / cfg["dim_model_base"])
        head = p("lm_head_weight")
        out = {"expert_counts": jnp.zeros((0, 0), jnp.int32),
               "block_keys_kept": kept,
               "router_gap": (jnp.stack(gaps).astype(jnp.float32) if gaps
                              else jnp.full((1, b * t), jnp.inf,
                                            jnp.float32))}
        keep = t if last is None else last
        if labels is None:
            out["logits"] = h[:, t - keep:] @ head.T
            return out
        labels = jnp.asarray(labels, jnp.int32)
        nll, logits = [], []
        for s in range(0, t, block):
            z = h[:, s:s + block] @ head.T                     # [B, blk, V]
            logp = jax.nn.log_softmax(z, axis=-1)
            nll.append(-jnp.take_along_axis(
                logp, labels[:, s:s + block, None], axis=-1)[..., 0])
            lo = max(s, t - keep)
            if lo < s + block:
                logits.append(z[:, lo - s:])
        nll = jnp.concatenate(nll, axis=1)                     # [B, T]
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
