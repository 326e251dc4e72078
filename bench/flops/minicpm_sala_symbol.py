"""Required forward operations per sample (one sequence) of the MiniCPM-SALA
symbol AS HELD HERE, from the configuration's keys alone: two operations per
multiply-add of every matrix product the mathematics needs.

A ``lightning-attn`` layer: its five projections over the heads held (q, k,
v, gate, o) and the linear core in the chunked form the state-space scan's
kernels run, chunks of 128 (``scan_flops``: the names the scan's readers
ask every operations module for; here every head is its own group and the
state is the key's width). A ``minicpm4`` layer: its five projections (q,
gate and o over the query heads, k and v over the key/value heads); the
block scorer (``block_select_flops``: the group's queries against the pooled
keys a query may see, ``head_dim`` a score; it must score every window to
choose among them, has no backward and counts once); the scores and values
of the KEPT pairs alone (``select_flops``: ``kept_pairs`` a query head,
``head_dim`` a score and ``head_dim`` a value), not the causal square. Every
layer the dense SwiGLU's three products over the columns held; then the head
over the held vocabulary. Norms, rotations, decays, softmaxes, the pooling's
means, the choice of blocks and the embedding lookup are not matrix products
and count nothing. What the other chip of the tensor-parallel pair computes
is not counted. Training is three times this for everything that is
differentiated; recomputed operations never count (the flash pair's backward
recomputes its scores, the mirrored SwiGLU its activation).

``scan_bytes`` and ``select_bytes`` are what the two kernel pairs have to
move whatever their form; ``block_select_bytes`` one read of the queries and
the pooled keys and one write of the choice, a byte a (query, block).
"""
from __future__ import annotations

TRAIN_MULTIPLIER = 3
CHUNK = 128      # the scan's chunk (ops/transformer/ssm.py::linear_attention)
SPARSE = {"kernel_size": 32, "kernel_stride": 16, "block_size": 64,
          "topk": 64, "init_blocks": 1, "window_size": 2048,
          "dense_len": 8192}
LINEAR, SPARSE_KIND = "lightning-attn", "minicpm4"


def _t(cfg):
    return cfg["kwargs"]["seq_len"]


def _sparse(cfg):
    return dict(SPARSE, **cfg.get("sparse_config", {}))


def kind_layers(cfg, kind):
    return sum(1 for m in cfg["mixer_types"] if m == kind)


def scan_layers(cfg):
    """The layers that run the linear core."""
    return kind_layers(cfg, LINEAR)


def layers(cfg):
    """The layers whose attention reads a keep-mask: the ``minicpm4``
    layers of a sequence longer than ``dense_len`` (``attn_select_*``'s
    count)."""
    selects = _t(cfg) > _sparse(cfg)["dense_len"]
    return kind_layers(cfg, SPARSE_KIND) if selects else 0


def full_layers(cfg):
    """The ``minicpm4`` layers that read every key (a sequence of
    ``dense_len`` positions or fewer): none in the cell."""
    return kind_layers(cfg, SPARSE_KIND) - layers(cfg)


def causal_pairs(cfg):
    t = _t(cfg)
    return t * (t + 1) // 2


def kept_pairs(cfg):
    """(query, key) pairs s <= t one selecting layer keeps of one sequence,
    a head: query t keeps the keys from its first local block (the block of
    key ``t - window_size + 1``) up to its own, ``init_blocks`` whole blocks
    where they lie before that, and ``topk`` whole blocks of those between
    (all of them where they are fewer). The closed form of
    ``ops/transformer/blocks.py::kept_pairs``, written again here: the
    benchmark counts with its own arithmetic."""
    s = _sparse(cfg)
    block, total = s["block_size"], 0
    for t in range(_t(cfg)):
        first = max(t - s["window_size"] + 1, 0) // block
        init = min(first, s["init_blocks"])
        total += (t - block * first + 1
                  + block * (init + min(first - init, s["topk"])))
    return total


def scan_flops(cfg):
    """Forward operations of ONE layer's linear core for one sequence,
    chunks of Q tokens, H heads of D keys and D values, every head its own
    group: ``q k^T`` over the causal triangle of a chunk ((Q + 1) / 2 tokens
    a token, D a score), the masked product against ``v`` over the same
    triangle (D a head), a chunk's end state (D D a token and head) and the
    carried state read through ``q`` (D D a token and head)."""
    h, d = cfg["lightning_nh"], cfg["lightning_head_dim"]
    triangle = (CHUNK + 1) / 2.0
    return 2.0 * _t(cfg) * (triangle * 2 * h * d + 2 * h * d * d)


def scan_bytes(cfg, itemsize=2):
    """Bytes ONE layer's linear core has to move forward for one sequence:
    q, k and v in and o out, ``itemsize`` each."""
    h, d = cfg["lightning_nh"], cfg["lightning_head_dim"]
    return float(itemsize) * _t(cfg) * 4 * h * d


def linattn_projection_flops(cfg):
    """Forward operations of ONE lightning layer's q, k, v, gate and o
    projections over the heads held."""
    width = cfg["lightning_nh"] * cfg["lightning_head_dim"]
    return 2.0 * _t(cfg) * cfg["hidden_size"] * 5 * width


def attention_projection_flops(cfg):
    """Forward operations of ONE sparse layer's q, gate and o projections
    (the query heads) and k and v (the key/value heads)."""
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return (2.0 * _t(cfg) * cfg["hidden_size"] * cfg["head_dim"]
            * (3 * heads + 2 * kv))


def scored_windows(cfg):
    """(query, pooled key) pairs ONE layer's scorer reads, a head: window j
    (keys ``stride j`` to ``stride j + kernel``) for every query at or past
    its end."""
    s = _sparse(cfg)
    pool, stride = s["kernel_size"], s["kernel_stride"]
    return sum(max((t + 1 - pool) // stride + 1, 0) for t in range(_t(cfg)))


def block_select_flops(cfg):
    """Operations of ONE layer's block scorer (forward; it has no
    backward): every query head against the pooled keys it may see."""
    return 2.0 * cfg["num_attention_heads"] * cfg["head_dim"] \
        * scored_windows(cfg)


def block_select_bytes(cfg, itemsize=2):
    """Bytes ONE layer's choice of blocks has to move: the queries and the
    keys in once, a byte a (query, block, key/value head) out."""
    s = _sparse(cfg)
    t, d = _t(cfg), cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    blocks = -(-t // s["block_size"])
    return float(itemsize) * t * d * (heads + kv) + kv * t * blocks


def select_flops(cfg):
    """Forward operations of ONE layer's attention over its kept pairs,
    every query head: a score and a value are ``head_dim`` multiply-adds
    each."""
    return (2.0 * cfg["num_attention_heads"] * 2 * cfg["head_dim"]
            * kept_pairs(cfg))


def select_bytes(cfg, itemsize=2):
    """Bytes ONE layer's selected pair has to move over a step, forward and
    backward together: q, o, dO and dq over the query heads, k, v, dk and
    dv over the key/value heads (``itemsize`` each: bf16), and the
    keep-mask's causal half (int8) once a key/value head's group and
    pass."""
    t, d = _t(cfg), cfg["head_dim"]
    heads, groups = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return (itemsize * t * d * (4 * heads + 4 * groups)
            + 2 * groups * causal_pairs(cfg))


def mlp_flops(cfg):
    """Forward operations of ONE layer's dense SwiGLU over the columns
    held."""
    width = cfg.get("share", {}).get("dense_columns_held",
                                     cfg["intermediate_size"])
    return 2.0 * _t(cfg) * 3 * cfg["hidden_size"] * width


def head_flops(cfg):
    return 2.0 * _t(cfg) * cfg["hidden_size"] * cfg["vocab_size"]


def parts(cfg):
    """The forward operations of one sequence by part, the scorer at its
    full forward count."""
    linear, sparse = scan_layers(cfg), kind_layers(cfg, SPARSE_KIND)
    attended = (layers(cfg) * select_flops(cfg)
                + full_layers(cfg) * 2.0 * cfg["num_attention_heads"] * 2
                * cfg["head_dim"] * causal_pairs(cfg))
    return {"linattn_projections": linear * linattn_projection_flops(cfg),
            "linattn_core": linear * scan_flops(cfg),
            "attention_projections": sparse * attention_projection_flops(cfg),
            "block_select": layers(cfg) * block_select_flops(cfg),
            "attended_pairs": attended,
            "mlp": cfg["num_hidden_layers"] * mlp_flops(cfg),
            "head": head_flops(cfg)}


def train_flops_per_sample(cfg):
    """What one training step needs: three times the differentiated parts,
    the scorer (forward only, no gradient) once."""
    p = parts(cfg)
    return (TRAIN_MULTIPLIER * (sum(p.values()) - p["block_select"])
            + p["block_select"])


def forward_flops_per_sample(cfg):
    """``train_flops_per_sample / TRAIN_MULTIPLIER``: the harness's training
    count is three times this, so the scorer, which has no backward, enters
    at a third."""
    return train_flops_per_sample(cfg) / TRAIN_MULTIPLIER
