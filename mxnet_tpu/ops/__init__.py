"""Operator registry + all operator definitions.

Importing this package registers the full op corpus (parity with the
reference's ~150 NNVM tensor ops + ~50 legacy layer ops, SURVEY.md §2
N6/N7).
"""
from . import registry
from .registry import OpDef, get, exists, list_ops, primary_ops, register, register_op

# op definition modules — import order only matters for registration
from . import elemwise  # noqa: F401
from . import broadcast_reduce  # noqa: F401
from . import matrix  # noqa: F401
from . import indexing  # noqa: F401
from . import init_ops  # noqa: F401
from . import sample  # noqa: F401
from . import nn  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import rnn_op  # noqa: F401
from . import spatial  # noqa: F401
from . import transformer  # noqa: F401
