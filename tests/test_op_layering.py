"""The op layer's arrows (PR 71): ``mxnet_tpu/ops/`` imports nothing above
it but ``parallel`` in two named places, the transformer families import of
their siblings only the shared four, the executor names no op, and the
contrib namespaces export by the registry's rule."""
import ast
import os

import pytest

import mxnet_tpu as mx
from mxnet_tpu.contrib.ops import contrib_op_exports
from mxnet_tpu.ops import registry

ROOT = os.path.dirname(os.path.abspath(mx.__file__))
OPS = os.path.join(ROOT, "ops")
FAMILIES = os.path.join(OPS, "transformer")
# the families every other may import; they import no sibling themselves
SHARED = {"norm", "rotary", "taps", "attention"}
# the two arrows from ``ops/`` up (ROADMAP.md Design 7): named debts
UPWARD = {("transformer/moe.py", "mxnet_tpu.parallel.moe"),
          ("kernels/flash.py", "mxnet_tpu.parallel.ring_attention")}


def _modules(top):
    for folder, _, files in sorted(os.walk(top)):
        for name in sorted(files):
            if name.endswith(".py"):
                yield os.path.relpath(os.path.join(folder, name), top)


def _imports(path):
    """Every module a file imports, anywhere in it, as an absolute name."""
    package = os.path.relpath(os.path.dirname(path), os.path.dirname(
        ROOT)).replace(os.sep, ".").split(".")
    out = set()
    for node in ast.walk(ast.parse(open(path).read())):
        if isinstance(node, ast.Import):
            out.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = package[:len(package) - node.level + 1] if node.level \
                else []
            stem = ".".join(base + ([node.module] if node.module else []))
            out.add(stem)
            out.update("%s.%s" % (stem, alias.name) for alias in node.names)
    return out


@pytest.mark.parametrize("module", list(_modules(OPS)))
def test_an_op_module_imports_nothing_above_the_op_layer(module):
    above = {name for name in _imports(os.path.join(OPS, module))
             if name.startswith("mxnet_tpu.") and name.split(".")[1] in (
                 "executor", "module", "models", "parallel", "symbol",
                 "ndarray", "io", "kvstore", "serving")}
    allowed = {name for where, name in UPWARD if where == module}
    assert {n for n in above if not any(
        n.startswith(a) for a in allowed)} == set(), module


@pytest.mark.parametrize("module", list(_modules(FAMILIES)))
def test_a_transformer_family_imports_only_the_shared_siblings(module):
    stem = "mxnet_tpu.ops.transformer."
    siblings = {name[len(stem):].split(".")[0]
                for name in _imports(os.path.join(FAMILIES, module))
                if name.startswith(stem)}
    family = module[:-len(".py")]
    if family == "__init__":
        return  # the package names every family: that registers the ops
    siblings.discard(family)
    assert siblings <= (set() if family in SHARED else SHARED), siblings


@pytest.mark.parametrize("module", list(_modules(FAMILIES)))
def test_a_transformer_family_is_one_readable_module(module):
    text = open(os.path.join(FAMILIES, module)).read()
    assert text.count("\n") <= 700
    assert "down here" not in text.lower()
    assert ast.get_docstring(ast.parse(text)), "says what the family is"


def test_the_transformer_package_is_no_longer_than_the_file_was():
    assert not os.path.exists(os.path.join(OPS, "transformer.py"))
    total = sum(open(os.path.join(FAMILIES, m)).read().count("\n")
                for m in _modules(FAMILIES))
    # 2,300 when PR 71 cut the file up; PR 73's three ops (``Mamba1`` with
    # the selective scan's ``jax.numpy`` form, ``DiffAttention``,
    # ``LayerNorm``) are 354 lines in ``ssm``, ``attention`` and ``norm``;
    # PR 75's keep-mask on ``Attention`` (the input, its type rule, the
    # dispatch between the selected pair and ``kept_attention``, its
    # counter) is 50 in ``attention``; PR 79's two ops are 400:
    # ``LinearAttention`` in ``ssm`` (145: its slopes, the block on the
    # scan both ways, the norm a head and the gate) and the family
    # ``blocks`` (``BlockSelect``: pooled keys, window and block scores,
    # the choice, its closed form and its count a step)
    assert total <= 2300 + 360 + 50 + 400, total


def test_the_executor_names_no_op_above_it():
    text = open(os.path.join(ROOT, "executor.py")).read()
    assert "_contrib_" not in text and "_OP_CLASS" not in text


EXPORTED = {
    "MultiBoxPrior", "MultiBoxTarget", "MultiBoxDetection", "Proposal",
    "ROIPooling", "CTCLoss", "ctc_loss", "fft", "ifft", "quantize",
    "dequantize", "count_sketch", "SwitchMoE",
    "RMSNorm", "RoPE", "Attention", "LatentAttention", "Mamba2", "TopKMoE",
    "GatedDeltaNet", "ShortConv", "ScaledSum", "KeyIndexer", "ExitMix",
    "HyperCoeff", "HyperMix", "Mamba1", "DiffAttention", "LayerNorm",
    "LinearAttention", "BlockSelect",
}


@pytest.mark.parametrize("namespace", ["sym", "nd"])
def test_contrib_exports_exactly_the_31_names(namespace):
    """What ``CONTRIB_OP_EXPORTS`` listed by hand before PR 71, the three
    ops PR 73 registered and the two of PR 79."""
    assert set(contrib_op_exports()) == EXPORTED and len(EXPORTED) == 31
    space = getattr(mx.contrib, namespace)
    ops = {name for name in dir(space) if registry.exists(name)
           and callable(getattr(space, name))
           and not name.startswith("_")}
    assert EXPORTED <= ops
    # nothing else of the contrib corpus leaks into the namespace
    assert {name for name in ops if registry.exists("_contrib_" + name)
            } <= EXPORTED


@pytest.mark.parametrize("name", sorted(EXPORTED))
def test_a_contrib_export_is_the_registered_op(name):
    assert callable(getattr(mx.contrib.sym, name))
    assert getattr(mx.contrib.nd, name).__name__ == registry.get(name).name
