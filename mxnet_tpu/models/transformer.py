"""Transformer LM with ring attention — the TPU-native long-context model.

The reference's long-sequence story is bucketing + model-parallel LSTM
(SURVEY.md §5.7); the idiomatic TPU equivalent is a transformer whose
sequence axis shards over the mesh 'sp' axis with ring attention
(mxnet_tpu.parallel.ring_attention) and whose FFN/attention projections
shard over 'tp'. This is a pure-JAX model (not the Symbol API): it is the
flagship for the multi-chip dryrun and the long-context benchmark.
"""
from __future__ import annotations

import numpy as np


def transformer_lm(vocab=32000, d_model=512, n_heads=8, n_layers=4,
                   d_ff=2048, dtype=None, moe_experts=0, moe_every=2):
    """Returns (init_fn(rng, seq_len, batch) -> params,
                apply_fn(params, tokens, mesh=None) -> logits).

    ``moe_experts > 0`` replaces every ``moe_every``-th layer's FFN with
    a Switch-MoE block (parallel/moe.py): expert weights lead with the E
    axis so a dp x ep mesh shards them with ``moe_partition_specs`` and
    GSPMD inserts the dispatch all-to-alls. MoE apply returns
    ``(logits, aux_loss)`` — the load-balance term to add to the LM loss."""
    import jax
    import jax.numpy as jnp

    if dtype is None:
        dtype = jnp.bfloat16
    head_dim = d_model // n_heads

    def _is_moe_layer(i):
        return moe_experts > 0 and i % moe_every == moe_every - 1

    def init_fn(seed=0):
        rng = np.random.RandomState(seed)

        def w(*shape, scale=None):
            scale = scale or (1.0 / np.sqrt(shape[0]))
            return (rng.randn(*shape) * scale).astype(np.float32)

        params = {"embed": w(vocab, d_model, scale=0.02)}
        for i in range(n_layers):
            layer = {
                "ln1": np.ones((d_model,), np.float32),
                "ln2": np.ones((d_model,), np.float32),
                "wq": w(d_model, n_heads * head_dim),
                "wk": w(d_model, n_heads * head_dim),
                "wv": w(d_model, n_heads * head_dim),
                "wo": w(n_heads * head_dim, d_model),
            }
            if _is_moe_layer(i):
                # one source of truth for the MoE param layout
                from ..parallel.moe import init_moe_params

                layer["moe"] = {
                    k: np.asarray(v) for k, v in init_moe_params(
                        rng.randint(1 << 30), d_model, d_ff,
                        moe_experts).items()
                }
            else:
                layer["w1"] = w(d_model, d_ff)
                layer["w2"] = w(d_ff, d_model)
            params["l%d" % i] = layer
        params["ln_f"] = np.ones((d_model,), np.float32)
        return params

    def rmsnorm(x, g):
        x32 = x.astype(jnp.float32)
        n = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + 1e-6)
        return (n * g).astype(x.dtype)

    def attention(x, p, mesh=None):
        B, T, D = x.shape
        q = (x @ p["wq"].astype(dtype)).reshape(B, T, n_heads, head_dim)
        k = (x @ p["wk"].astype(dtype)).reshape(B, T, n_heads, head_dim)
        v = (x @ p["wv"].astype(dtype)).reshape(B, T, n_heads, head_dim)
        # ring (sp>1 mesh) / Pallas flash / reference selection lives in
        # one place now — ops.kernels.attention — shared with the
        # LSTM attention readout (models/lstm.py)
        from ..ops.kernels import attention as attn_dispatch

        o = attn_dispatch(q, k, v, causal=True, mesh=mesh)
        return o.reshape(B, T, D) @ p["wo"].astype(dtype)

    def apply_fn(params, tokens, mesh=None):
        x = jnp.take(params["embed"], tokens, axis=0).astype(dtype)
        # simple learned-free positional encoding (rotary-lite: sinusoidal)
        T = tokens.shape[1]
        pos = np.arange(100000)[:, None] / (
            10000 ** (np.arange(0, d_model, 2) / d_model)
        )
        pe = jnp.asarray(
            np.concatenate([np.sin(pos), np.cos(pos)], axis=-1)[:T], dtype
        )
        x = x + pe[None]
        aux_total = jnp.zeros((), jnp.float32)
        for i in range(n_layers):
            p = params["l%d" % i]
            x = x + attention(rmsnorm(x, p["ln1"].astype(dtype)), p, mesh)
            h = rmsnorm(x, p["ln2"].astype(dtype))
            if _is_moe_layer(i):
                from ..parallel.moe import switch_moe

                B = h.shape[0]
                y, aux = switch_moe(
                    p["moe"], h.reshape(B * T, d_model))
                x = x + y.reshape(B, T, d_model)
                aux_total = aux_total + aux
            else:
                h = jax.nn.gelu(h @ p["w1"].astype(dtype))
                x = x + h @ p["w2"].astype(dtype)
        x = rmsnorm(x, params["ln_f"].astype(dtype))
        logits = x.astype(jnp.float32) @ params["embed"].T
        if moe_experts > 0:
            return logits, aux_total
        return logits

    return init_fn, apply_fn


def _sinusoid_pe(n_rows, d_model):
    pos = np.arange(n_rows)[:, None] / (
        10000 ** (np.arange(0, d_model, 2) / d_model)
    )
    return np.concatenate([np.sin(pos), np.cos(pos)], axis=-1)


def transformer_lm_serving(vocab=32000, d_model=512, n_heads=8, n_layers=4,
                           d_ff=2048, dtype=None, max_len=256):
    """KV-cached serving twin of :func:`transformer_lm`: consumes the
    SAME param tree (``transformer_lm(...)[0]()``), adds a preallocated
    ring-buffer KV cache so autoregressive decode is one shape-stable
    step per token (no per-token recompiles) and prefill is one padded
    forward per (count, length) bucket.

    Returns ``(init_cache, prefill, decode_step)``:

    - ``init_cache(slots)`` → cache dict; ``slots`` is the fixed decode
      batch. ``k``/``v`` are ``[L, slots, max_len, H, Dh]`` rings; the
      in-graph ``length`` counter and ``pos_map`` (absolute position
      per ring cell, -1 = empty) keep every step's shapes static while
      handling per-slot lengths, ring wraparound, and slot reuse.
    - ``prefill(params, cache, tokens[n, T], slots[n], lengths[n],
      mesh=None)`` → ``(cache, last_logits[n, vocab])``: a normal
      causal forward (ops.kernels.attention dispatch, so an
      'sp' mesh routes long prompts through parallel/ring_attention)
      whose per-layer K/V scatter into the cache rows of ``slots`` —
      new sequences join a running batch mid-flight without touching
      other slots.
    - ``decode_step(params, cache, tokens[slots])`` →
      ``(cache, logits[slots, vocab])``: one token for EVERY slot
      against the cache (inactive slots compute garbage and are simply
      ignored by the caller — the price of a static shape).

    MoE layers are not supported on the decode path (dense FFN only).
    """
    import jax
    import jax.numpy as jnp

    if dtype is None:
        dtype = jnp.bfloat16
    head_dim = d_model // n_heads
    scale = 1.0 / float(np.sqrt(head_dim))
    # absolute positions live past the ring window; size the PE table
    # for the longest total sequence the engine may reach
    pe_rows = max(4 * max_len, 1024)
    pe_np = _sinusoid_pe(pe_rows, d_model)

    def rmsnorm(x, g):
        x32 = x.astype(jnp.float32)
        n = x32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(x32), -1, keepdims=True) + 1e-6)
        return (n * g).astype(x.dtype)

    def init_cache(slots):
        return {
            "k": jnp.zeros((n_layers, slots, max_len, n_heads, head_dim),
                           dtype),
            "v": jnp.zeros((n_layers, slots, max_len, n_heads, head_dim),
                           dtype),
            "pos_map": jnp.full((slots, max_len), -1, jnp.int32),
            "length": jnp.zeros((slots,), jnp.int32),
        }

    def prefill(params, cache, tokens, slots, lengths, mesh=None):
        n, T = tokens.shape
        if T > max_len:
            raise ValueError(
                "prefill bucket %d exceeds KV window %d" % (T, max_len))
        pe = jnp.asarray(pe_np[:T], dtype)
        x = jnp.take(params["embed"], tokens, axis=0).astype(dtype) + pe[None]
        from ..ops.kernels import attention as attn_dispatch

        ck, cv = cache["k"], cache["v"]
        for i in range(n_layers):
            p = params["l%d" % i]
            h = rmsnorm(x, p["ln1"].astype(dtype))
            q = (h @ p["wq"].astype(dtype)).reshape(n, T, n_heads, head_dim)
            k = (h @ p["wk"].astype(dtype)).reshape(n, T, n_heads, head_dim)
            v = (h @ p["wv"].astype(dtype)).reshape(n, T, n_heads, head_dim)
            o = attn_dispatch(q, k, v, causal=True, mesh=mesh)
            x = x + o.reshape(n, T, d_model) @ p["wo"].astype(dtype)
            h = rmsnorm(x, p["ln2"].astype(dtype))
            h = jax.nn.gelu(h @ p["w1"].astype(dtype))
            x = x + h @ p["w2"].astype(dtype)
            ck = ck.at[i, slots, :T].set(k.astype(dtype))
            cv = cv.at[i, slots, :T].set(v.astype(dtype))
        # reset the WHOLE ring row for each admitted slot: cells past
        # the prompt stay -1 (empty), so a previous occupant's stale
        # K/V can never leak into the new sequence's attention
        cell = jnp.arange(max_len)[None, :]
        row = jnp.where(cell < lengths[:, None], cell, -1).astype(jnp.int32)
        pos_map = cache["pos_map"].at[slots].set(row)
        length = cache["length"].at[slots].set(lengths.astype(jnp.int32))
        xf = rmsnorm(x, params["ln_f"].astype(dtype))
        logits = xf.astype(jnp.float32) @ params["embed"].T
        last = logits[jnp.arange(n), lengths - 1]
        return {"k": ck, "v": cv, "pos_map": pos_map, "length": length}, last

    def decode_step(params, cache, tokens):
        S = tokens.shape[0]
        pos = cache["length"]  # [S] absolute position of the new token
        idx = pos % max_len  # ring cell it lands in
        rows = jnp.arange(S)
        # same rounding as prefill: embed and PE each cast to the
        # compute dtype BEFORE the add (adding in f32 and casting after
        # drifts ~1e-3 from the full-forward reference in bf16)
        pe = jnp.asarray(pe_np, dtype)
        x = (jnp.take(params["embed"], tokens, axis=0).astype(dtype)
             + pe[jnp.clip(pos, 0, pe_rows - 1)])
        pos_map = cache["pos_map"].at[rows, idx].set(pos)
        mask = (pos_map >= 0) & (pos_map <= pos[:, None])  # [S, M]
        ck, cv = cache["k"], cache["v"]
        for i in range(n_layers):
            p = params["l%d" % i]
            h = rmsnorm(x, p["ln1"].astype(dtype))
            q = (h @ p["wq"].astype(dtype)).reshape(S, n_heads, head_dim)
            k = (h @ p["wk"].astype(dtype)).reshape(S, n_heads, head_dim)
            v = (h @ p["wv"].astype(dtype)).reshape(S, n_heads, head_dim)
            ck = ck.at[i, rows, idx].set(k)
            cv = cv.at[i, rows, idx].set(v)
            # same numerics as reference_attention: f32 scores/softmax
            s = jnp.einsum("shd,smhd->shm", q, ck[i]).astype(
                jnp.float32) * scale
            s = jnp.where(mask[:, None, :], s, -1e30)
            prob = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("shm,smhd->shd", prob,
                           cv[i].astype(jnp.float32)).astype(dtype)
            x = x + o.reshape(S, d_model) @ p["wo"].astype(dtype)
            h = rmsnorm(x, p["ln2"].astype(dtype))
            h = jax.nn.gelu(h @ p["w1"].astype(dtype))
            x = x + h @ p["w2"].astype(dtype)
        xf = rmsnorm(x, params["ln_f"].astype(dtype))
        logits = xf.astype(jnp.float32) @ params["embed"].T
        new_cache = {"k": ck, "v": cv, "pos_map": pos_map,
                     "length": pos + 1}
        return new_cache, logits

    return init_cache, prefill, decode_step
