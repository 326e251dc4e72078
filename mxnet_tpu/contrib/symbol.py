"""mx.contrib.symbol — contrib ops as Symbol functions (parity: reference
mx.contrib.symbol, used by the SSD/RCNN example symbols)."""
from . import ops as _ops  # noqa: F401  (registers contrib ops)
from .ops import contrib_op_exports as _exports
from ..symbol import _make_symbol_function, _init_symbol_module as _reinit
from ..ops import registry as _registry
import sys as _sys

_mod = _sys.modules[__name__]
for _name in _exports():
    if _registry.exists(_name):
        setattr(_mod, _name, _make_symbol_function(_registry.get(_name)))
_reinit()
