"""Functional op library; see functional.py for the registry."""
from . import functional  # noqa: F401  (populates OP_REGISTRY)
from . import detection  # noqa: F401
from . import control_flow  # noqa: F401
from . import attention  # noqa: F401
from . import moe  # noqa: F401
from . import retention  # noqa: F401
from . import ctc  # noqa: F401
from . import roi  # noqa: F401
from . import rcnn  # noqa: F401
from . import spatial  # noqa: F401
from . import extra  # noqa: F401
from . import legacy_ops  # noqa: F401
from . import contrib_extra  # noqa: F401
from .functional import *  # noqa: F401,F403

# Upstream exposes every CamelCase op under a snake_case name too
# (python/mxnet/ndarray/register.py generates both); mirror that by
# aliasing registry entries (same OpDef, two names) before the nd/sym
# namespaces generate their wrappers.
import re as _re
from ..base import OP_REGISTRY as _R


def _snake(name):
    s = _re.sub(r"([A-Z]+)([A-Z][a-z])", r"\1_\2", name)
    s = _re.sub(r"([a-z0-9])([A-Z])", r"\1_\2", s)
    return s.lower()


for _n in list(_R):
    if _n[:1].isupper():
        _s = _snake(_n)
        if _s not in _R:
            _R[_s] = _R[_n]
del _n
