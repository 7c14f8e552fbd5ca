"""mxnet_tpu.cache — persistent cross-process compilation layer.

Two tiers (ISSUE: warm replicas in seconds, not compile-minutes):

* **Tier A — the executable store** (store.py): every jit funnel
  (``base.jitted``/``bulk_jitted``/``tape_jitted``, serve bucket and
  decode-step warmups, the hybrid compiled call) persists its compiled
  XLA executable to ``MXNET_COMP_CACHE_DIR``, content-addressed by the
  lowered HLO text + a jax/jaxlib/backend fingerprint. A fresh process
  re-traces (milliseconds) but never re-compiles (seconds-minutes) a
  program any previous process already built.
* **Tier B — AOT serving snapshots** (snapshot.py): ``serve.snapshot``
  bundles a served model's checkpoint, bucket/capacity config, input
  specs and the serialized executables of every warmed bucket into one
  artifact; ``serve.load(prefix, snapshot=True)`` rebuilds the server by
  **deserializing** those executables — no trace, no compile:
  ``engine.serve_compile_counter`` / ``decode_compile_counter`` stay 0
  from process start to the first served request.

The store is disabled by default; set ``MXNET_COMP_CACHE_DIR`` (cap via
``MXNET_COMP_CACHE_CAP`` bytes) or call :func:`configure`. Snapshots are
explicit artifacts and work regardless of the store.

Beneath both sits jax's own persistent compilation cache, which the entry
points (``chip_smoke.py``, ``bench.py``, ``serve.worker``) turn on through
:func:`enable_compile_cache`.
"""
from __future__ import annotations

import os

from .aot import AotFn  # noqa: F401  (re-export)
from .store import CompCacheStore, fingerprint  # noqa: F401

__all__ = ["AotFn", "CompCacheStore", "configure", "active_store",
           "enabled", "disable", "enable_compile_cache", "fingerprint",
           "stats", "traceable"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache():
    """Point jax's persistent compilation cache at a place that stays put,
    and return it. Where ``JAX_COMPILATION_CACHE_DIR`` is set jax already
    uses that directory and none is set in code; otherwise it is the fixed
    ``<checkout>/.jax_cache``. The path is part of the cache's key, so it is
    never a temporary name, a pid or a time."""
    import jax

    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not d:
        d = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", d)
    return d

_STORE = None
_ENV_CHECKED = False


def configure(directory, cap_bytes=None):
    """Enable the persistent executable store at ``directory`` (created on
    first write). Returns the store. Also seeds jax's persistent
    compilation cache fallback lazily if executable serialization turns
    out to be unsupported on the backend."""
    global _STORE, _ENV_CHECKED
    _STORE = CompCacheStore(directory, cap_bytes=cap_bytes)
    _ENV_CHECKED = True
    return _STORE


def disable():
    """Turn the store off (tests; also lets a long-lived process detach
    from a remounted cache dir). In-memory compiled programs stay live."""
    global _STORE, _ENV_CHECKED
    _STORE = None
    _ENV_CHECKED = True


def active_store():
    """The live CompCacheStore, auto-configured from
    ``MXNET_COMP_CACHE_DIR`` on first call; None when disabled."""
    global _ENV_CHECKED
    if not _ENV_CHECKED:
        _ENV_CHECKED = True
        d = os.environ.get("MXNET_COMP_CACHE_DIR")
        if d:
            configure(d)
    return _STORE


def enabled():
    return active_store() is not None


def traceable(fn):
    """The trace-safe form of a compiled callable: AotFn → its jit
    wrapper; anything else passes through (it's already a jit object)."""
    return fn.traceable if isinstance(fn, AotFn) else fn


def persistent_backed(fn, device=None, donate_argnums=None, tier="jit",
                      hint=""):
    """An ``AotFn`` over ``fn`` when the store is enabled, else None (the
    caller keeps its plain ``jax.jit`` — zero added overhead on the
    default path). The one hook ``base._jit_backed`` calls."""
    if active_store() is None:
        return None
    return AotFn(fn, donate_argnums=donate_argnums or (), device=device,
                 tier=tier, hint=hint)


def stats():
    """Store snapshot for tools/diagnose.py + the engine counters; reports
    disabled state explicitly so the section always prints."""
    from .. import engine

    st = active_store()
    out = {
        "enabled": st is not None,
        "hits": engine.comp_cache_hit_counter.count,
        "misses": engine.comp_cache_miss_counter.count,
        "deserializes": engine.comp_cache_deserialize_counter.count,
    }
    if st is not None:
        out.update(st.scan())
    return out
