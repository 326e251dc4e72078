"""docs/env_vars.md against the tree: the table of ``MXTPU_*`` /
``MXNET_*`` variables and the code that reads them name the same set,
and no document advertises a variable the table does not have.

"Read" is decided from the text: a name that appears in a ``.py`` file
under ``mxnet_tpu/`` or ``tools/`` (a comment counts, so a stale mention
of a deleted variable fails too). A name ending in an underscore is a
prefix pattern (``MXNET_EXEC_BULK_EXEC_*``), not a variable.
"""
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_NAME = re.compile(r"\b(?:MXTPU|MXNET)_[A-Z0-9_]+\b")
_ROW = re.compile(r"^\|\s*`((?:MXTPU|MXNET)_[A-Z0-9_]+)`", re.M)
# names that are not this framework's runtime variables
_NOT_VARIABLES = {
    "MXNET_REGISTER_OP_PROPERTY",  # the reference's C++ macro (docstrings)
    "MXNET_TEST_TRAIN_FULL",       # the test suite's own tier switch
}


def _names(text):
    return {n for n in _NAME.findall(text)
            if not n.endswith("_")} - _NOT_VARIABLES


def _read(path):
    with open(os.path.join(ROOT, path), encoding="utf-8") as f:
        return f.read()


def _names_in_code():
    found = {}
    for top in ("mxnet_tpu", "tools"):
        for dirpath, _dirs, files in os.walk(os.path.join(ROOT, top)):
            for fname in files:
                if fname.endswith(".py"):
                    rel = os.path.relpath(os.path.join(dirpath, fname), ROOT)
                    for name in _names(_read(rel)):
                        found.setdefault(name, rel)
    return found


def _rows():
    return set(_ROW.findall(_read("docs/env_vars.md")))


def test_every_variable_read_has_a_row():
    rows = _rows()
    missing = {n: where for n, where in _names_in_code().items()
               if n not in rows}
    assert not missing, "read but not in docs/env_vars.md: %s" % missing


def test_every_row_is_read():
    stale = _rows() - set(_names_in_code())
    assert not stale, "rows of docs/env_vars.md nothing reads: %s" % stale


def test_every_documented_mention_has_a_row():
    rows = _rows()
    docs = ["README.md"] + sorted(
        os.path.join("docs", f) for f in os.listdir(os.path.join(ROOT, "docs"))
        if f.endswith(".md"))
    # perf_doctor's advice strings are what an operator is told to set
    from tools import perf_doctor

    texts = {d: _read(d) for d in docs}
    texts["tools/perf_doctor.py advice"] = "\n".join(
        perf_doctor._ADVICE.values())
    unknown = {where: sorted(_names(text) - rows)
               for where, text in texts.items()}
    unknown = {where: names for where, names in unknown.items() if names}
    assert not unknown, "mentioned without a row: %s" % unknown
