"""mx.contrib.ndarray — contrib ops as NDArray functions (parity:
reference contrib op exposure under mx.contrib.nd)."""
from . import ops as _ops  # noqa: F401  (registers contrib ops)
from ..ndarray import _init_ndarray_module as _reinit
from ..ndarray import imperative_invoke
from ..ops import registry as _registry
import sys as _sys

_mod = _sys.modules[__name__]
from .ops import contrib_op_exports as _exports

for _name in _exports():
    if _registry.exists(_name):
        _opdef = _registry.get(_name)

        def _make(opdef):
            def fn(*args, **kwargs):
                out = kwargs.pop("out", None)
                kwargs.pop("name", None)
                return imperative_invoke(opdef, list(args), kwargs, out=out)

            fn.__name__ = opdef.name
            return fn

        setattr(_mod, _name, _make(_opdef))
# keep the base nd module in sync with newly registered contrib ops
_reinit()
