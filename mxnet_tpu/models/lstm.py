"""LSTM language model (PTB) — bucketing workload.

Capability parity: reference example/rnn/lstm_bucketing.py +
cudnn_lstm_bucketing.py (SURVEY.md §7 workload 3). Two paths, matching the
reference:
- ``lstm_unroll``: explicitly unrolled LSTMCell stack (the nnvm-graph path)
- ``fused_lstm_sym``: FusedRNNCell → the ``RNN`` op (lax.scan kernel)

Plus a TPU-native variant, ``lstm_attention_lm``: a pure-JAX
recurrence (lax.scan) with a causal self-attention readout over the
hidden-state sequence, routed through the same attention dispatcher the
transformer uses (ops.kernels.attention — reference / Pallas
flash / ring by mesh+length).
"""
import numpy as np

from .. import symbol as sym
from ..rnn.rnn_cell import FusedRNNCell, LSTMCell, SequentialRNNCell


def lstm_unroll(num_layers, seq_len, input_size, num_hidden, num_embed,
                num_label, dropout=0.0):
    """Unrolled symbol for one bucket length (sym_gen inner)."""
    stack = SequentialRNNCell()
    for i in range(num_layers):
        stack.add(LSTMCell(num_hidden=num_hidden, prefix="lstm_l%d_" % i))
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    embed = sym.Embedding(data, input_dim=input_size, output_dim=num_embed,
                          name="embed")
    stack.reset()
    outputs, states = stack.unroll(seq_len, inputs=embed, merge_outputs=True)
    pred = sym.Reshape(outputs, shape=(-1, num_hidden))
    pred = sym.FullyConnected(pred, num_hidden=num_label, name="pred")
    label_flat = sym.Reshape(label, shape=(-1,))
    return sym.SoftmaxOutput(pred, label_flat, name="softmax")


def fused_lstm_sym(num_layers, seq_len, input_size, num_hidden, num_embed,
                   num_label, dropout=0.0):
    """FusedRNNCell path (parity cudnn_lstm_bucketing.py)."""
    cell = FusedRNNCell(num_hidden, num_layers=num_layers, mode="lstm",
                        dropout=dropout)
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    embed = sym.Embedding(data, input_dim=input_size, output_dim=num_embed,
                          name="embed")
    outputs, _ = cell.unroll(seq_len, inputs=embed, merge_outputs=True,
                             layout="NTC")
    pred = sym.Reshape(outputs, shape=(-1, num_hidden))
    pred = sym.FullyConnected(pred, num_hidden=num_label, name="pred")
    label_flat = sym.Reshape(label, shape=(-1,))
    return sym.SoftmaxOutput(pred, label_flat, name="softmax"), cell


def lstm_attention_lm(vocab=10000, num_hidden=256, num_embed=256,
                      n_heads=4, dtype=None):
    """Pure-JAX LSTM LM with an attention readout.

    Returns (init_fn(seed) -> params, apply_fn(params, tokens,
    mesh=None) -> logits[B, T, vocab]). The recurrence is one
    ``lax.scan`` LSTM layer; instead of predicting from h_t alone, each
    position attends causally over the full hidden sequence (the
    "attentive language model" readout), which is where the flash /
    ring attention kernels slot into the RNN path.
    """
    import jax
    import jax.numpy as jnp

    if dtype is None:
        dtype = jnp.float32
    assert num_hidden % n_heads == 0
    head_dim = num_hidden // n_heads

    def init_fn(seed=0):
        rng = np.random.RandomState(seed)

        def w(*shape, scale=None):
            scale = scale or (1.0 / np.sqrt(shape[0]))
            return (rng.randn(*shape) * scale).astype(np.float32)

        return {
            "embed": w(vocab, num_embed, scale=0.02),
            # gate order i, f, g, o — matches rnn_cell.LSTMCell
            "wx": w(num_embed, 4 * num_hidden),
            "wh": w(num_hidden, 4 * num_hidden),
            "b": np.zeros((4 * num_hidden,), np.float32),
            "wq": w(num_hidden, num_hidden),
            "wk": w(num_hidden, num_hidden),
            "wv": w(num_hidden, num_hidden),
            "wo": w(num_hidden, num_hidden),
            "pred": w(num_hidden, vocab),
        }

    def apply_fn(params, tokens, mesh=None):
        B, T = tokens.shape
        x = jnp.take(params["embed"], tokens, axis=0).astype(dtype)
        wx, wh = params["wx"].astype(dtype), params["wh"].astype(dtype)
        b = params["b"].astype(dtype)

        def step(carry, xt):
            h, c = carry
            gates = xt @ wx + h @ wh + b
            i, f, g, o = jnp.split(gates, 4, axis=-1)
            c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
            h = jax.nn.sigmoid(o) * jnp.tanh(c)
            return (h, c), h

        h0 = jnp.zeros((B, num_hidden), dtype)
        _, hs = jax.lax.scan(step, (h0, h0), jnp.swapaxes(x, 0, 1))
        hs = jnp.swapaxes(hs, 0, 1)  # [B, T, H]

        q = (hs @ params["wq"].astype(dtype)).reshape(B, T, n_heads,
                                                      head_dim)
        k = (hs @ params["wk"].astype(dtype)).reshape(B, T, n_heads,
                                                      head_dim)
        v = (hs @ params["wv"].astype(dtype)).reshape(B, T, n_heads,
                                                      head_dim)
        from ..ops.kernels import attention as attn_dispatch

        o = attn_dispatch(q, k, v, causal=True, mesh=mesh)
        ctx = o.reshape(B, T, num_hidden) @ params["wo"].astype(dtype)
        return (hs + ctx).astype(jnp.float32) @ params["pred"]

    return init_fn, apply_fn


class BucketingLSTMModel:
    """sym_gen factory for BucketingModule (parity lstm_bucketing.py:69)."""

    def __init__(self, num_layers, input_size, num_hidden, num_embed,
                 num_label, dropout=0.0, fused=False):
        self.num_layers = num_layers
        self.input_size = input_size
        self.num_hidden = num_hidden
        self.num_embed = num_embed
        self.num_label = num_label
        self.dropout = dropout
        self.fused = fused

    def __call__(self, bucket_key):
        builder = fused_lstm_sym if self.fused else lstm_unroll
        out = builder(
            self.num_layers, bucket_key, self.input_size, self.num_hidden,
            self.num_embed, self.num_label, self.dropout
        )
        symf = out[0] if isinstance(out, tuple) else out
        return symf, ("data",), ("softmax_label",)
