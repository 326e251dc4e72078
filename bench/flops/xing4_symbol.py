"""Required forward operations per sample (one sequence) of the Xing4.0
symbol AS HELD HERE, from the configuration's keys alone: two operations
per multiply-add of every matrix product the mathematics needs. A block
(``blocks``: the layers held plus the prediction module's): the query
latent's two projections, the down-projection to the key/value latent and
the shared rotary key, the up-projection to every head's keys and values,
the causal scores and their values over the triangle ((T + 1) / 2 keys a
query), the output projection; the dense SwiGLU, or the shared expert, the
router at its full width and the held experts at the rows the share
expects; and the two sub-layers' coefficient products (n (n + 2) outputs
from n x hidden inputs each). Beside the blocks: the module's projection
(2 x hidden -> hidden) and BOTH reads of the head over the held
vocabulary. Not matrix products, so nothing: norms, rotary embedding,
softmaxes, the Sinkhorn iterations' elementwise work, the mixing itself
(4 + 4 multiply-adds a stream element: its cost is bytes, ``hc_mix_bytes``),
the compaction, both embedding lookups. Training is three times this;
recomputed operations never count.
"""
from __future__ import annotations

TRAIN_MULTIPLIER = 3


def _t(cfg):
    return cfg["kwargs"]["seq_len"]


def modules(cfg):
    return cfg.get("num_nextn_predict_layers", 0)


def blocks(cfg):
    """Blocks held: the layers and one a prediction module."""
    return cfg["num_hidden_layers"] + modules(cfg)


def expert_layers(cfg):
    """How many of the blocks have experts (the modules' all do)."""
    return blocks(cfg) - min(cfg["first_k_dense_replace"],
                             cfg["num_hidden_layers"])


def mla_layers(cfg):
    """Every block has the latent-attention node."""
    return blocks(cfg)


def mla_kernel_flops(cfg):
    """Forward operations of ONE block's attention kernel for one
    sequence: scores and values over the causal triangle, every head."""
    t = _t(cfg)
    width = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
             + cfg["v_head_dim"])
    return 2.0 * cfg["num_attention_heads"] * width * t * (t + 1) / 2.0


def mla_projection_flops(cfg):
    """Forward operations of ONE block's five projections round the
    kernel: down to the query latent and up from it, down to the
    key/value latent, up from it, output."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, latent, q_latent = (cfg["v_head_dim"], cfg["kv_lora_rank"],
                            cfg["q_lora_rank"])
    return 2.0 * _t(cfg) * (d * q_latent + q_latent * heads * (nope + rope)
                            + d * (latent + rope)
                            + latent * heads * (nope + dv)
                            + heads * dv * d)


def shared_expert_flops(cfg):
    """Forward operations of ONE expert block's shared expert."""
    width = (cfg.get("n_shared_experts") or 0) * cfg["moe_intermediate_size"]
    return 2.0 * _t(cfg) * 3 * cfg["hidden_size"] * width


def expected_share_rows(cfg):
    """Rows a block's held experts receive of one sequence under uniform
    routing."""
    of = cfg.get("share", {}).get("experts_of", cfg["n_routed_experts"])
    return (_t(cfg) * cfg["num_experts_per_tok"]
            * cfg["n_routed_experts"] / float(of))


def moe_share_flops(cfg, rows=None):
    """Forward operations of ONE expert block's routed part for one
    sequence: the router over all its experts and ``rows`` rows (default:
    the expected) through a SwiGLU expert."""
    d = cfg["hidden_size"]
    of = cfg.get("share", {}).get("experts_of", cfg["n_routed_experts"])
    rows = expected_share_rows(cfg) if rows is None else rows
    return 2.0 * (_t(cfg) * d * of
                  + rows * 3 * d * cfg["moe_intermediate_size"])


def hc_coeff_flops(cfg):
    """Forward operations of ONE block's coefficient products: two
    sub-layers, n (n + 2) outputs from the n x hidden stream each."""
    n = cfg["hc_mult"]
    return 2.0 * 2 * _t(cfg) * n * cfg["hidden_size"] * n * (n + 2)


def hc_mix_bytes(cfg, itemsize=2):
    """Bytes the mixing of one whole STEP (forward and backward, every
    sub-layer of every block) must move for one sequence, each array it
    needs read or written once at ``itemsize`` bytes an element (the
    model's dtype, bf16). With S = tokens x hidden elements and n streams,
    a sub-layer's

      read, forward    reads the stream (n S), writes the input (S)
      write, forward   reads the stream (n S) and the output (S), writes
                       the next stream (n S)
      write, backward  reads the next stream's cotangent (n S), the stream
                       (n S) and the output (S); writes the stream's
                       cotangent (n S) and the output's (S)
      read, backward   reads the input's cotangent (S) and the stream
                       (n S); adds into the stream's cotangent (n S read,
                       n S written: the sum of the two is the least that
                       two passes can do)

    = (n + 1) + (2 n + 1) + (3 n + 2) + (3 n + 1) = (9 n + 5) S elements.
    The coefficients' pass over the stream adds nothing to it: forward it
    can share the read's pass, backward its cotangent goes into the
    stream's in the read's pass. The coefficient arrays ([n, n, tokens]
    float32) are 1/224 of a stream at hidden 3584 and are left out."""
    n = cfg["hc_mult"]
    s = _t(cfg) * cfg["hidden_size"]
    return float(itemsize) * 2 * blocks(cfg) * (9 * n + 5) * s


def forward_flops_per_sample(cfg):
    d, t = cfg["hidden_size"], _t(cfg)
    experts = expert_layers(cfg)
    return ((1 + modules(cfg)) * 2.0 * t * d * cfg["vocab_size"]  # heads
            + modules(cfg) * 2.0 * t * 2 * d * d          # the projection
            + blocks(cfg) * (mla_projection_flops(cfg)
                             + mla_kernel_flops(cfg) + hc_coeff_flops(cfg))
            + (blocks(cfg) - experts) * 2.0 * t * 3 * d
            * cfg["intermediate_size"]
            + experts * (shared_expert_flops(cfg) + moe_share_flops(cfg)))
