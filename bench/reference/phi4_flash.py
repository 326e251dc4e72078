"""Plain reference for ``models/phi4_flash.py``: Phi-4-mini-flash-reasoning's
(SambaY's) forward pass, loss and gradients in straightforward
``jax.numpy``.

No kernel, no chunk, no cache: the Mamba layer is the selective recurrence
itself, one token after another (a ``lax.scan`` over time whose carry is
the state ``[D, N]``), the attention scores are a ``[block, T]`` matrix a
head with an explicit causal (and window) mask (``block`` queries at a
time, so that 4k positions fit a chip: a block's rows are whole softmax
rows). Everything is computed in ``dtype`` — float32 by default, under
``jax.default_matmul_precision("highest")`` so that a TPU does not quietly
run float32 matmuls in bf16 passes. ``dtype=jnp.bfloat16`` is the same
mathematics one precision below what any configuration of the system
states (norms, step sizes, decays, the carried state, lambda, softmaxes and
the loss in bf16 too): a comparison's tolerance has to fail it.

It follows the published ``config.json`` (``model_type`` phi4flash; Ren et
al., arXiv:2507.06607) key by key (``cfg`` below). Every layer ``l`` of
``N`` (published numbering; ``cfg["layers_held"]`` lists the numbers held,
all by default, ``cfg["published"]["num_hidden_layers"]`` is ``N`` where
the dict is a stage's):

    h = h + Mixer_l(LayerNorm(h));  h = h + SwiGLU(LayerNorm(h))

LayerNorm with gamma and beta (``layer_norm_eps``), the feed-forward
``W_down(silu(W_gate a) * W_up a)`` without bias. The mixer:

``l < N/2`` even, and ``l = N/2``: **Mamba-1** (Gu & Dao, arXiv:2312.00752).
``x | z = W_in a``; ``x = silu(conv(x) + b)``, a causal depthwise
convolution of 4 taps; ``dt_low | B | C = W_x x``; ``dt = softplus(W_dt
dt_low + b_dt)``; ``A = -exp(A_log)``; ``S_t[c, n] = exp(dt_t[c] A[c, n])
S_{t-1}[c, n] + dt_t[c] B_t[n] x_t[c]`` (zero before the first token);
``m_t[c] = sum_n C_t[n] S_t[c, n] + D[c] x_t[c]``; output ``W_out (m *
silu(z))``. Layer ``N/2``'s ``m`` is the memory.

``l < N/2`` odd (under ``sliding_window`` keys), ``l = N/2 + 1`` (full; its
keys and values are kept), ``l > N/2 + 1`` odd (cross: its own query and
output projections, layer ``N/2 + 1``'s keys and values, full):
**differential attention** (Ye et al., arXiv:2410.05258). ``q = W_q a +
b_q`` etc.; query heads 2p, 2p + 1 are pair p's ``q1``, ``q2``, key heads
2g, 2g + 1 group g's ``k1``, ``k2``, ``V_g = [v_2g | v_2g+1]``; pair p reads
group ``p // (pairs / groups)``; ``O_i = softmax(q_i k_i^T / sqrt(64) +
mask) V``; ``lambda_init = 0.8 - 0.6 exp(-0.3 l)``, ``lambda = exp(lq1 .
lk1) - exp(lq2 . lk2) + lambda_init``; ``O = (1 - lambda_init)
RMSNorm_128(O1 - lambda O2) gamma``; output ``W_o [O_0 | ..] + b_o``.

``l > N/2 + 1`` even: **GMU**, ``W_2 (m * silu(W_1 a))``.

Final LayerNorm, logits through the transposed embedding, mean next-token
cross-entropy.

**What the published file has no key for, and where each comes from.**
State 16, 4 taps, expansion 2 and ``dt_rank`` = hidden / 16 are the Mamba
family's convention (read here off the parameters' shapes);
``mb_per_layer`` 2 puts a Mamba mixer in every even layer; the order of
the layers and the GMU are arXiv:2507.06607's (section 2, figure 1: the
self-decoder is Samba, one full-attention layer makes the cache, the
cross-decoder alternates GMU and cross-attention); the differential form
is Ye et al.'s with pairs of NEIGHBOURING heads (a checkpoint that pairs
them otherwise differs by a fixed permutation of projection columns, which
random weights do not see); biases on the attention projections, none
elsewhere.

Parameters are a dict under the names of the symbol's arguments
(``models/phi4_flash.py`` lists them; ``FullyConnected`` weights are
``[out, in]``). Host arrays are fine: a layer's parameters are placed when
the layer runs.

There are no experts: ``router_gap`` is returned as one row of +inf and
``expert_counts`` empty, so that the benchmark's comparison of the
sparse-expert models reads this one unchanged.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np


def layer_norm(x, gamma, beta, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return gamma * ((x - mean) * jax.lax.rsqrt(var + eps)) + beta


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate.T) * (x @ w_up.T)) @ w_down.T


def causal_conv(x, taps, bias):
    """x [B, T, C], taps [n, C]: tap ``n - 1`` meets the current token."""
    n, t = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (n - 1, 0), (0, 0)))
    return bias + sum(padded[:, j:j + t] * taps[j] for j in range(n))


def mamba(a, w_in, taps, conv_bias, w_x, w_dt, dt_bias, a_log, d_skip,
          w_out):
    """A block's normed input [B, T, hidden] -> (the mixer's output, the
    scan's output ``m`` [B, T, D] before the gate), one token after
    another."""
    d_in, n = a_log.shape
    rank = w_dt.shape[1]
    proj = a @ w_in.T
    x, z = proj[..., :d_in], proj[..., d_in:]
    x = jax.nn.silu(causal_conv(x, taps, conv_bias))
    low = x @ w_x.T
    dt = jax.nn.softplus(low[..., :rank] @ w_dt.T + dt_bias)
    bmat, cmat = low[..., rank:rank + n], low[..., rank + n:]
    decay_rate = -jnp.exp(a_log)

    def token(state, at):                                     # [B, D, N]
        x_t, dt_t, b_t, c_t = at
        state = (jnp.exp(dt_t[..., None] * decay_rate) * state
                 + (dt_t * x_t)[..., None] * b_t[:, None, :])
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(
        token, jnp.zeros((a.shape[0], d_in, n), a.dtype),
        tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, bmat, cmat)))
    m = jnp.moveaxis(y, 0, 1) + d_skip * x
    return (m * jax.nn.silu(z)) @ w_out.T, m


def diff_attention(q, k, v, lambdas, gamma, cfg, number, window, block=256):
    """q [B, T, H * D], k and v [B, T, KV * D] -> [B, T, H * D]: the two
    maps of every pair against the pair's value of width 2 D, scores
    materialised for ``block`` queries at a time."""
    b, t, _ = q.shape
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // heads
    pairs, groups = heads // 2, kv // 2
    q = q.reshape(b, t, pairs, 2, d)
    k = jnp.repeat(k.reshape(b, t, groups, 2, d), pairs // groups, axis=2)
    v = jnp.repeat(v.reshape(b, t, groups, 2 * d), pairs // groups, axis=2)
    lq1, lk1, lq2, lk2 = lambdas
    # Python floats: a numpy scalar would promote to float64
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * number)
    lam = (jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2))
           + lam_init).astype(q.dtype)
    pos = np.arange(t)
    out = []
    for s in range(0, t, block):
        delta = pos[s:s + block, None] - pos[None, :]
        mask = (delta >= 0) & ((delta < window) if window else True)
        maps = []
        for i in (0, 1):
            scores = jnp.einsum("bqpd,bkpd->bpqk", q[:, s:s + block, :, i],
                                k[:, :, :, i]) * d ** -0.5
            scores = jnp.where(mask[None, None], scores, -jnp.inf)
            maps.append(jnp.einsum("bpqk,bkpe->bqpe",
                                   jax.nn.softmax(scores, axis=-1), v))
        o = maps[0] - lam * maps[1]
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + cfg["layer_norm_eps"])
        out.append(gamma * o * (1.0 - lam_init))
    return jnp.concatenate(out, axis=1).reshape(b, t, heads * d)


def layer_kinds(cfg):
    """[(published number, kind)] of the layers held: ``mamba``, ``window``,
    ``memory``, ``full``, ``gmu`` or ``cross``."""
    depth = cfg.get("published", {}).get("num_hidden_layers",
                                         cfg["num_hidden_layers"])
    half = depth // 2
    held = cfg.get("layers_held")
    held = range(cfg["num_hidden_layers"]) if held is None else held

    def kind(number):
        if number < half:
            return "mamba" if number % 2 == 0 else "window"
        if number in (half, half + 1):
            return "memory" if number == half else "full"
        return "gmu" if number % 2 == 0 else "cross"

    return [(number, kind(number)) for number in held]


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

    eps = cfg["layer_norm_eps"]
    b, t = tokens.shape
    memory = keys = values = None
    with jax.default_matmul_precision("highest"):
        embed = p("embed_weight")
        h = embed[jnp.asarray(tokens, jnp.int32)]             # [B, T, d]
        for number, kind in layer_kinds(cfg):
            n = "layer%d_" % number
            a = layer_norm(h, p(n + "norm_gamma"), p(n + "norm_beta"), eps)
            if kind in ("mamba", "memory"):
                m = n + "mamba_"
                y, scanned = mamba(
                    a, p(m + "in_proj_weight"), p(m + "conv_weight"),
                    p(m + "conv_bias"), p(m + "x_proj_weight"),
                    p(m + "dt_proj_weight"), p(m + "dt_bias"),
                    p(m + "a_log"), p(m + "d"), p(m + "out_proj_weight"))
                if kind == "memory":
                    memory = scanned
            elif kind == "gmu":
                y = (memory * jax.nn.silu(a @ p(n + "gmu_in_proj_weight").T)
                     ) @ p(n + "gmu_out_proj_weight").T
            else:
                def proj(name):
                    return (a @ p(n + name + "_proj_weight").T
                            + p(n + name + "_proj_bias"))

                if kind == "cross":
                    k, v = keys, values
                else:
                    k, v = proj("k"), proj("v")
                if kind == "full":
                    keys, values = k, v
                y = diff_attention(
                    proj("q"), k, v,
                    [p(n + "attn_lambda_" + v_) for v_ in
                     ("q1", "k1", "q2", "k2")], p(n + "attn_subln_gamma"),
                    cfg, number,
                    cfg["sliding_window"] if kind == "window" else 0)
                y = y @ p(n + "o_proj_weight").T + p(n + "o_proj_bias")
            h = h + y
            a = layer_norm(h, p(n + "ffn_norm_gamma"),
                           p(n + "ffn_norm_beta"), eps)
            h = h + swiglu(a, p(n + "gate_proj_weight"),
                           p(n + "up_proj_weight"), p(n + "down_proj_weight"))
        h = layer_norm(h, p("final_norm_gamma"), p("final_norm_beta"), eps)
        out = {"expert_counts": jnp.zeros((0, 0), jnp.int32),
               "router_gap": jnp.full((1, b * t), jnp.inf, jnp.float32)}
        keep = t if last is None else last
        if labels is None:
            out["logits"] = h[:, t - keep:] @ embed.T
            return out
        labels = jnp.asarray(labels, jnp.int32)
        nll, logits = [], []
        for s in range(0, t, block):
            z = h[:, s:s + block] @ embed.T                   # [B, blk, V]
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
