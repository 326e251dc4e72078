"""A benchmark cell's training step LOWERED for the TPU on this host (no
compile, no chip), in a form two checkouts can be compared by.

A refactor that must leave every program alone is checked here before it is
measured there: lower the cell's step from the parent's tree and from the
change's and compare. The module text is printed without debug information;
every ``tpu_custom_call``'s Mosaic body (serialized MLIR whose bytes hold the
source locations of the kernel's whole call path, ``ROADMAP.md`` Design 16)
is decoded and printed the same way, so that what is compared is what the
compilers read and not where the code lives.

    python3 tools/lowered_step.py <cell> [--tiny] [--root TREE] [--out DIR]

    <cell>   an entry of ``bench/cells/`` (one chip's program is lowered)
    --tiny   ``bench/tests/rehearsal/<cell>.json``'s sizes on top: no
             kernel's rule admits them, so the ``jax.numpy`` forms lower
    --root   the checkout to import ``mxnet_tpu`` and read ``bench/`` from
             (default: this file's)
    --out    write the normalised text there as ``<cell>.mlir.gz``

Prints one JSON line: the text's ``sha256``, its size, the kernel names with
their counts, and the names of the jitted blocks. Equal lines from two trees
mean equal programs; seconds a cell at the real size, a few hundred MB of
host memory for the largest.
"""
import argparse
import base64
import collections
import gzip
import hashlib
import json
import os
import re
import sys

BODY = re.compile(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22')


def lowered_text(root, cell_name, tiny):
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = [root, os.path.join(root, "bench")]
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_enable_compilation_cache", False)
    import lib
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.train_step import ShardedTrainStep

    def load(kind, name):
        return lib.load_json(os.path.join(root, "bench", kind, name + ".json"))

    cell = load("cells", cell_name)
    cfg, mix = load("configs", cell["config"]), load("traffic", cell["traffic"])
    if tiny:
        over = load(os.path.join("tests", "rehearsal"), cell_name)
        cfg = lib.merge(cfg, over.get("config", {}))
        mix = lib.merge(mix, over.get("cell", {}).get("traffic", {}))
    factory, kwargs = lib.resolve(cfg["factory"]), cfg["kwargs"]
    if "seq_len" in kwargs:  # a language model: tokens in, tokens out
        sym = factory(cfg, **kwargs)
        data = label = (mix["batch"], kwargs["seq_len"])
        feed = jnp.int32
    else:
        sym = factory(**kwargs)
        data, label = (mix["batch"],) + tuple(cfg["input_shape"]), (
            mix["batch"],)
        feed = jnp.float32
    names = [n for n in sym.list_arguments()
             if n not in ("data", "softmax_label")]
    optimizer = mx.optimizer.create(
        mix["optimizer"], sym=sym, param_idx2name=dict(enumerate(names)),
        rescale_grad=1.0, **mix["optimizer_params"])
    step = ShardedTrainStep(
        sym, make_mesh(dp=1, devices=jax.devices()[:1]), optimizer=optimizer)
    arg_shapes, _, aux_shapes = sym.infer_shape(data=data,
                                                softmax_label=label)
    shapes = dict(zip(sym.list_arguments(), arg_shapes))
    held = dict(zip(sym.list_arguments(), sym.infer_type(
        data=feed, softmax_label=feed)[0]))
    spec = jax.ShapeDtypeStruct
    scalar = spec((), jnp.float32)
    lowered = jax.jit(step._make_step_fn(), donate_argnums=(0, 1, 2)).trace(
        {n: spec(shapes[n], held[n]) for n in names},
        {n: spec(s, jnp.float32) for n, s in zip(
            sym.list_auxiliary_states(), aux_shapes)},
        {n: spec(shapes[n], jnp.float32) for n in names},
        {"data": spec(data, feed), "softmax_label": spec(label, feed)},
        spec((2,), jnp.uint32), scalar, scalar, scalar).lower(
            lowering_platforms=("tpu",))
    return lowered.as_text(debug_info=False)


def decode_bodies(text):
    """``text`` with every Mosaic body replaced by the sha256 of its own text
    printed without debug information; how many distinct bodies there were."""
    from jax._src.interpreters import mlir
    from jaxlib.mlir import ir

    seen = {}

    def digest(match):
        body = match.group(1)
        if body not in seen:
            context = mlir.make_ir_context()
            context.allow_unregistered_dialects = True
            with context:
                asm = ir.Module.parse(base64.b64decode(body)).operation.get_asm(
                    enable_debug_info=False)
            seen[body] = hashlib.sha256(asm.encode()).hexdigest()
        return "\\22body\\22: \\22mosaic:%s\\22" % seen[body]

    return BODY.sub(digest, text), len(seen)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("cell")
    parser.add_argument("--tiny", action="store_true")
    parser.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    parser.add_argument("--out")
    args = parser.parse_args(argv)
    root = os.path.abspath(args.root)
    text, bodies = decode_bodies(lowered_text(root, args.cell, args.tiny))
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        with gzip.open(os.path.join(
                args.out, args.cell + ".mlir.gz"), "wt") as f:
            f.write(text)
    functions = collections.Counter(re.findall(
        r"func\.func private @(_\w+?)(?:_\d+)?\(", text))
    print(json.dumps({
        "cell": args.cell + (".tiny" if args.tiny else ""),
        "sha256": hashlib.sha256(text.encode()).hexdigest(),
        "mb": round(len(text) / 1e6, 1), "mosaic_bodies": bodies,
        "kernels": dict(collections.Counter(
            re.findall(r'kernel_name = "([^"]+)"', text))),
        "blocks": sorted(n for n in functions if n.endswith("_block"))}))


if __name__ == "__main__":
    main()
