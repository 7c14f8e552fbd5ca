"""``mx.libinfo`` (ref: python/mxnet/libinfo.py).

Upstream locates libmxnet.so and declares ``__version__``. Here the
"library" is the XLA/jax runtime plus the optional native helpers in
src/engine_cc; find_lib_path points at the latter."""
from __future__ import annotations

__version__ = "1.9.0.tpu"  # API-parity line: MXNet 1.9 surface, TPU backend


def find_lib_path():
    """Paths of the native helper libraries on this host, built from
    source on first use (ref: libinfo.py:find_lib_path)."""
    import os

    from .engine import _lib_location, native_lib_path

    native_lib_path()
    d, so = _lib_location()
    return [p for p in (so, os.path.join(d, "libmxtpu_im.so"))
            if os.path.exists(p)]


def find_include_path():
    """(ref: libinfo.py:find_include_path) — C sources double as headers."""
    import os

    from .engine import _lib_location

    return _lib_location()[0] if os.path.exists(_lib_location()[0]) else ""
