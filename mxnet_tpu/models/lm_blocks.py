"""The pieces the decoder-only LM symbols share (``mimo_v2``,
``kanana2``, ``nemotron_h``, ``olmo_hybrid``, ``lfm2``, ``falcon_h1``,
``ouro``, ``phi4_flash``; the tail also ``olmoe``): a bias-free projection, the dense
SwiGLU and the un-gated relu² feed-forward, the one-mixer residual block,
the block that norms a sub-layer's output and the block whose mixers read
one normed input side by side, a fixed scalar on a node's output, the
routed expert layer's call, the Kimi Delta Attention mixer
(``kimi_linear``, ``solar_open2``), the sub-layer wrapped in hyper-connections
over several residual streams (``xing4``) and the head, untied or reading
the embedding's matrix, with its loss, one stream's or several's. Each takes the node-name prefix of its
layer, so a model's argument and scope names are its own; a model whose
weights are read at several depths (``ouro``) hands in the ``Variable``s
it made once."""
from .. import initializer as init
from .. import symbol as sym
from ..contrib import symbol as csym


def linear(x, name, num_hidden, init=None, weight=None):
    """``FullyConnected`` without a bias; ``init`` is the weight's own
    rule where the model states one. ``weight``: the matrix's
    ``Variable`` where the model made it itself, so that several nodes
    read ONE argument (``init`` is then the ``Variable``'s own)."""
    if weight is None and init is not None:
        weight = sym.Variable(name + "_weight", init=init)
    extra = {} if weight is None else {"weight": weight}
    return sym.FullyConnected(x, num_hidden=num_hidden, no_bias=True,
                              name=name, **extra)


def swiglu(x, prefix, width, hidden_size, gate_scale=1.0, out_scale=1.0,
           inits=(None, None, None), weights=(None, None, None)):
    """``<prefix>down_proj(silu(<prefix>gate_proj(x)) *
    <prefix>up_proj(x))`` at ``width`` columns; with the two fixed
    scalars, ``gate_scale`` on the gate's pre-activation and
    ``out_scale`` on the result (``scaled``). ``inits``: the rules of the
    gate's, the up and the down projection's weights; ``weights``: their
    ``Variable``s where the model made them itself (``linear``)."""
    gate = sym.Activation(
        scaled(linear(x, prefix + "gate_proj", width, inits[0], weights[0]),
               prefix + "gate_proj_scale", gate_scale), act_type="silu")
    return scaled(
        linear(gate * linear(x, prefix + "up_proj", width, inits[1],
                             weights[1]),
               prefix + "down_proj", hidden_size, inits[2], weights[2]),
        prefix + "down_proj_scale", out_scale)


def relu2_mlp(x, prefix, width, hidden_size):
    """``<prefix>down_proj(relu(<prefix>up_proj(x))^2)`` at ``width``
    columns: the un-gated feed-forward."""
    up = sym.Activation(linear(x, prefix + "up_proj", width),
                        act_type="relu")
    return linear(sym.square(up), prefix + "down_proj", hidden_size)


def mixer_block(h, prefix, rms_eps, mixer):
    """``h + mixer(RMSNorm(h), prefix)``, the norm named
    ``<prefix>norm``: a block of one norm and one mixer."""
    return h + mixer(csym.RMSNorm(h, eps=rms_eps, name=prefix + "norm"),
                     prefix)


def expert_layer(x, prefix, **attrs):
    """``TopKMoE`` named ``<prefix>moe`` with a zero selection bias that
    the model states itself: (its output, its row counts behind
    ``BlockGrad`` as ``<prefix>expert_count``)."""
    moe = csym.TopKMoE(
        x, with_select_bias=True, select_bias=sym.Variable(
            prefix + "moe_select_bias", init=init.Zero()),
        name=prefix + "moe", **attrs)
    return moe[0], sym.BlockGrad(moe[1], name=prefix + "expert_count")


def kda_mixer(x, prefix, hidden_size, seq_len, heads, head_dim, rank,
              conv_kernel, chunk_size, rms_eps, allow_neg_eigval):
    """A Kimi Delta Attention mixer on ``x`` [tokens, hidden] at ``heads``
    heads of ``head_dim`` keys and values (all of the model's, or the
    heads one chip holds: a held head is its columns of every projection
    below, its ``a_log``, its ``dt_bias`` channels and its taps): the four
    wide projections ``<prefix>kda_{q,k,v,o}_proj``, the two low-rank
    pairs into the gate and the decay (``_g_a`` / ``_g_b``, ``_f_a`` /
    ``_f_b``: hidden -> ``rank`` -> heads x head_dim, the first halves
    whole whatever the heads held, no bias) and the write strength's
    ``_b_proj`` round ``GatedDeltaNet`` in its channel form with a sigmoid
    gate (the node ``<prefix>kda``, which owns the taps, ``a_log``,
    ``dt_bias`` and the gated norm's gamma, each with the rule the model
    states). ``allow_neg_eigval``: write strengths ``2 sigmoid`` (up to
    2) where true, ``sigmoid`` where false."""
    p = prefix + "kda_"
    width = heads * head_dim

    def positions(y, columns):  # [B*T, w] -> [B, T, w]
        return sym.Reshape(y, shape=(-1, seq_len, columns))

    def wide(name):
        return positions(linear(x, p + name + "_proj", width), width)

    def low_rank(name):  # hidden -> rank -> H K, no bias on either
        return positions(linear(
            linear(x, p + name + "_a_proj", rank),
            p + name + "_b_proj", width), width)

    y = csym.GatedDeltaNet(
        wide("q"), wide("k"), wide("v"), low_rank("g"), low_rank("f"),
        positions(linear(x, p + "b_proj", heads), heads),
        conv_weight=sym.Variable(p + "conv_weight", init=init.Uniform(
            scale=conv_kernel ** -0.5)),
        a_log=sym.Variable(p + "a_log", init=init.LogOfUniform(
            low=1.0, high=16.0)),
        dt_bias=sym.Variable(p + "dt_bias", init=init.InverseSoftplus(
            low=0.001, high=0.1, floor=1e-4)),
        norm_gamma=sym.Variable(p + "norm_gamma", init=init.One()),
        num_heads=heads, conv_kernel=conv_kernel,
        chunk_size=chunk_size, eps=rms_eps,
        allow_neg_eigval=allow_neg_eigval, gate_act="sigmoid", name=p[:-1])
    return linear(sym.Reshape(y, shape=(-1, width)), p + "o_proj",
                  hidden_size)


def head_and_loss(h, label, counts, vocab_size, seq_len, rms_eps,
                  tied_to=None, logit_scale=1.0, init=None, norm=None):
    """``final_norm``, the ``lm_head``, float32 logits (``lm_head_f32``)
    and each sequence's mean next-token cross-entropy behind ``MakeLoss``
    (``loss``), grouped with the layers' counts. A token's log-probability
    is ONE node, ``pick_log_softmax`` (``lm_head_pick``), whose own
    backward rule keeps the logits, the labels and one number a row: no
    [tokens, vocab] table, no scatter. The head is untied, its own
    ``lm_head_weight`` (drawn by ``init``), unless ``tied_to`` is the
    embedding's ``Variable``: one matrix, table and weight alike, whose
    gradient sums both uses. ``logit_scale``: a fixed scalar on the float32
    logits (cast ``lm_head_cast`` then, scaled logits ``lm_head_f32``).
    ``norm``: the final norm where it is no ``RMSNorm`` (``head_loss``)."""
    per_sequence = head_loss(h, label, "", vocab_size, seq_len, rms_eps,
                             tied_to, logit_scale, init, norm=norm)
    loss = sym.MakeLoss(per_sequence, name="loss")
    return sym.Group([loss] + counts)


def head_loss(h, label, prefix, vocab_size, seq_len, rms_eps, weight=None,
              logit_scale=1.0, init=None, targets=None, norm=None):
    """One read of the head: ``<prefix>final_norm``, ``<prefix>lm_head``
    (its own ``<prefix>lm_head_weight`` drawn by ``init``, or ``weight``,
    a ``Variable`` the model made: the embedding's for a tied head, the
    ONE head's where several streams read it), float32 logits
    (``<prefix>lm_head_f32``), ``pick_log_softmax`` and each sequence's
    mean cross-entropy (``<prefix>lm_head_mean``) over its first
    ``targets`` positions (all of them by default: a stream that predicts
    further ahead has no label for its last ones). ``head_and_loss``'s
    nodes, which it builds through this. ``norm(h, name)`` builds the final
    norm where the model's is another (``phi4_flash``'s LayerNorm)."""
    if norm is None:
        normed = csym.RMSNorm(h, eps=rms_eps, name=prefix + "final_norm")
    else:
        normed = norm(h, prefix + "final_norm")
    if weight is None:
        logits = linear(normed, prefix + "lm_head", vocab_size, init)
    else:
        logits = sym.FullyConnected(normed, weight=weight,
                                    num_hidden=vocab_size, no_bias=True,
                                    name=prefix + "lm_head")
    logits = scaled(sym.Cast(
        logits, dtype="float32",
        name=prefix + ("lm_head_f32" if logit_scale == 1
                       else "lm_head_cast")),
        prefix + "lm_head_f32", logit_scale)
    ahead = {} if targets in (None, seq_len) else {
        "ahead": 1 + seq_len - targets}
    nll = 0 - sym.pick_log_softmax(logits,
                                   sym.Reshape(label, shape=(-1,)),
                                   name=prefix + "lm_head_pick", **ahead)
    nll = sym.Reshape(nll, shape=(-1, seq_len))
    if ahead:
        nll = sym.slice_axis(nll, axis=1, begin=0, end=targets)
    return sym.mean(nll, axis=1, name=prefix + "lm_head_mean")


def heads_and_loss(heads, counts, extras, vocab_size, seq_len, rms_eps,
                   weight):
    """Several streams through ONE head (``weight``, the model's
    ``Variable``) into ONE loss: ``heads`` is ``[(prefix, stream, labels,
    targets, loss weight)]``, each read as ``head_loss`` reads it; the
    weighted sum of their per-sequence means is behind ``MakeLoss``
    (``loss``), and each apart behind ``BlockGrad`` (``<prefix>loss_part``)
    after the layers' counts, then ``extras`` (nodes that carry no
    gradient already). The multi-token-prediction objective (DeepSeek-V3,
    arXiv:2412.19437 section 2.2)."""
    parts = [head_loss(h, label, prefix, vocab_size, seq_len, rms_eps,
                       weight, targets=targets)
             for prefix, h, label, targets, _ in heads]
    total = None
    for part, (_, _, _, _, scale) in zip(parts, heads):
        term = part if scale == 1 else part * float(scale)
        total = term if total is None else total + term
    return sym.Group(
        [sym.MakeLoss(total, name="loss")] + counts
        + [sym.BlockGrad(part, name=head[0] + "loss_part")
           for part, head in zip(parts, heads)] + extras)


def hyper_block(stream, prefix, part, rms_eps, sublayer, streams, iters, eps,
                clamp, sigma=0.02, alpha=0.01, carry_bias=4.0):
    """One sub-layer (``part``: ``attn`` or ``ffn``) of the block
    ``prefix`` wrapped in manifold-constrained hyper-connections
    (``ops/transformer.py``, ``HyperCoeff`` / ``HyperMix``): ``stream``
    [tokens, n hidden] -> (the stream after it, the largest distance of a
    carry matrix's row or column sum from 1 behind ``BlockGrad``). With
    ``<p>`` = ``<prefix><part>_``:

        pre, post, res, err, u, stream = HyperCoeff(stream)   # <p>hc
        y = sublayer(RMSNorm(u), prefix)               # <p>norm
        stream'[i] = sum_j res[i, j] stream[j] + post[i] y   # <p>hc_write

    (``u = sum_j pre[j] stream[j]``, the read, is the node's own result and
    the write reads the stream off it: ``ops/kernels/hyper.py``, PR 70.)
    The node owns ``<p>hc_phi`` [n (n + 2), n hidden] (Normal ``sigma``),
    ``<p>hc_bias`` (zeros for the read and the write, ``carry_bias`` on
    the carry's diagonal: the streams do not start as one average) and
    ``<p>hc_alpha`` (three scalars, ``alpha``), each stated through
    ``sym.Variable(init=)``."""
    n, p = streams, "%s%s_hc" % (prefix, part)
    bias = [0.0] * (2 * n) + [carry_bias * (i == j)
                              for i in range(n) for j in range(n)]
    coeff = csym.HyperCoeff(
        stream, phi=sym.Variable(p + "_phi", init=init.Normal(sigma=sigma)),
        bias=sym.Variable(p + "_bias", init=init.Constant(value=bias)),
        alpha=sym.Variable(p + "_alpha", init=init.Constant(value=alpha)),
        streams=n, iters=iters, eps=eps, clamp=tuple(clamp),
        norm_eps=rms_eps, name=p)
    y = sublayer(csym.RMSNorm(coeff[4], eps=rms_eps,
                              name="%s%s_norm" % (prefix, part)), prefix)
    out = csym.HyperMix(coeff[5], coeff[2], y, coeff[1], with_add=True,
                        name=p + "_write")
    return out, sym.BlockGrad(coeff[3], name=p + "_err")


def post_norm_block(h, prefix, norm, rms_eps, sublayer, gamma=None):
    """``h + RMSNorm(sublayer(h, prefix))``, the norm named
    ``<prefix><norm>``: the norm sits on the sub-layer's OUTPUT (the
    Olmo 2 / Olmo 3 order); ``mixer_block`` norms its input. ``gamma``:
    the norm's scale where the model made the ``Variable`` itself."""
    extra = {} if gamma is None else {"gamma": gamma}
    return h + csym.RMSNorm(sublayer(h, prefix), eps=rms_eps,
                            name=prefix + norm, **extra)


def scaled(x, name, scale):
    """``scale * x`` under a fixed Python scalar as the node ``name``
    (``ScaledSum`` of one input: the product float32, one rounding to
    ``x``'s dtype); ``x`` itself where the scalar is 1."""
    if scale == 1:
        return x
    return csym.ScaledSum(x, scales=(float(scale),), name=name)


def parallel_block(h, prefix, rms_eps, mixers):
    """``h + sum_i scale_i * mixer_i(RMSNorm(h), prefix)``: ONE norm
    (``<prefix>norm``) read by every mixer side by side, their outputs
    scaled and summed by one node (``<prefix>mixer_sum``, which counts
    the block in ``lm.parallel_blocks`` where it is traced) before the
    residual add (``<prefix>mixer_add``: named, so that a trace files the
    fusion the two end in with the block whichever is its root).
    ``mixers`` is ``[(kind, scale, mixer)]``."""
    normed = csym.RMSNorm(h, eps=rms_eps, name=prefix + "norm")
    return sym.elemwise_add(h, csym.ScaledSum(
        *[mixer(normed, prefix) for _, _, mixer in mixers],
        scales=tuple(float(scale) for _, scale, _ in mixers),
        kinds="+".join(kind for kind, _, _ in mixers),
        name=prefix + "mixer_sum"), name=prefix + "mixer_add")
