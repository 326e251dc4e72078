"""Contrib namespace (parity: reference mx.contrib — autograd + contrib
ops like MultiBoxPrior/Target/Detection used by the SSD example)."""
from .. import autograd
from . import autograd as _autograd_alias  # noqa: F401
from . import ndarray
from . import symbol

# the reference's short names
nd = ndarray
sym = symbol
