"""AOT-compiled dispatch with a persistent disk tier (``AotFn``).

``jax.jit`` compiles lazily inside an opaque per-wrapper cache: the
executable can be neither exported (snapshot artifacts) nor seeded from
disk. ``AotFn`` makes the compile step explicit — ``lower()`` /
``compile()`` per input signature — so every program has a handle that can
be serialized, preloaded, and content-addressed in the cross-process store
(store.py), while the call path stays one dict lookup.

Two modes:

* multi-signature (default): the executor-pool / ``base.jitted`` shape —
  one wrapper serves many input signatures (buckets, op shapes); the sig
  is computed per call from leaf shapes/dtypes;
* ``single_signature=True``: the decode-loop shape — one wrapper is only
  ever called with ONE signature (fixed capacity/slots), so the hot path
  skips signature computation entirely: attribute read → call.

Robustness contract: a preloaded or deserialized executable whose avals
don't match the live call (wrong-key snapshot, reloaded params with new
shapes) raises ``TypeError`` from ``Compiled.__call__`` — the wrapper
catches exactly that, warns once, drops the bad executable and re-acquires
through lower/compile. Never a crash, one recompile.

Calls that arrive under an active trace (``jax.vjp`` over a hybrid block's
compiled fn while recording) cannot run a ``Compiled`` — they transparently
fall through to the equivalent ``jax.jit`` wrapper, which inlines under
the outer trace.
"""
from __future__ import annotations

import warnings

import numpy as np

import jax


def _arg_sig(args, kwargs):
    """Hashable signature of a call: pytree structure + per-leaf
    (shape, dtype, weak_type) for arrays, type name for Python scalars
    (value-independent: scalars are traced inputs, one program serves all
    values of a type)."""
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    sig = []
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            sig.append((tuple(shape), str(dtype),
                        bool(getattr(leaf, "weak_type", False))))
        else:
            sig.append((type(leaf).__name__,))
    return treedef, tuple(sig)


class AotFn:
    """Per-signature AOT compile + dispatch; the one funnel between this
    stack's program builders and XLA. See the module docstring."""

    __slots__ = ("_fn", "_jit", "_execs", "_only", "_single", "tier",
                 "hint", "_warned_mismatch")

    def __init__(self, fn, donate_argnums=(), device=None, tier="jit",
                 hint="", single_signature=False):
        self._fn = fn
        kw = {}
        donate = tuple(donate_argnums or ())
        if donate:
            kw["donate_argnums"] = donate
        if device is not None:
            kw["device"] = device
        self._jit = jax.jit(fn, **kw)
        self._execs = {}      # sig -> jax.stages.Compiled
        self._only = None     # single-signature fast slot
        self._single = bool(single_signature)
        self.tier = tier
        self.hint = hint
        self._warned_mismatch = False

    # ------------------------------------------------------------ dispatch
    def __call__(self, *args, **kwargs):
        if not jax.core.trace_ctx.is_top_level():
            # under an outer trace (vjp/grad over a compiled block): a
            # Compiled can't be inlined, the jit wrapper can
            return self._jit(*args, **kwargs)
        if self._single:
            compiled = self._only
            if compiled is None:
                compiled = self._acquire(args, kwargs, sig=None)
            try:
                return compiled(*args, **kwargs)
            except (TypeError, ValueError):
                self._mismatch()
                self._only = None
                return self._acquire(args, kwargs, sig=None)(*args, **kwargs)
        sig = _arg_sig(args, kwargs)
        compiled = self._execs.get(sig)
        if compiled is None:
            compiled = self._acquire(args, kwargs, sig)
        try:
            return compiled(*args, **kwargs)
        except (TypeError, ValueError):
            # aval/sharding drift at the same structural sig (params_fn
            # now returns different shapes, arrays moved device): the
            # signature is shape/dtype-level by design, so recompile once,
            # then let any genuine error surface from the fresh program
            self._mismatch()
            self._execs.pop(sig, None)
            return self._acquire(args, kwargs, sig)(*args, **kwargs)

    def _mismatch(self):
        if not self._warned_mismatch:
            self._warned_mismatch = True
            warnings.warn(
                "compiled executable for %s:%s does not match the live "
                "call signature — recompiling (stale snapshot/preload?)"
                % (self.tier, self.hint or "fn"), RuntimeWarning,
                stacklevel=3)

    # ------------------------------------------------------------ acquire
    def _acquire(self, args, kwargs, sig):
        """lower → (disk tier) → compile → (disk tier save) → cache.

        The whole acquire runs under an observability ``compile_context``
        (the serve/decode compile counters bump INSIDE the traced bodies,
        so this is where the retrace watchdog learns which program is
        being built) and its wall time feeds the compile-time gauges."""
        import time

        from . import active_store
        from ..observability import costs, note_compile, watchdog

        t0 = time.perf_counter()
        with watchdog.compile_context("%s:%s" % (self.tier,
                                                 self.hint or "fn")):
            lowered = self._jit.lower(*args, **kwargs)
            store = active_store()
            compiled = store.lookup(self.tier, lowered) if store is not None \
                else None
            if compiled is None:
                compiled = lowered.compile()
                if store is not None:
                    store.save(self.tier, lowered, compiled)
        note_compile(time.perf_counter() - t0)
        # eager cost attribution: the Compiled is in hand, profiling is
        # two XLA property reads (adopt() snapshot warm-starts have no
        # lowered handle and are skipped by design)
        costs.record_compiled(self.tier, self.hint, lowered, compiled)
        if self._single:
            self._only = compiled
        else:
            self._execs[sig if sig is not None
                        else _arg_sig(args, kwargs)] = compiled
        return compiled

    # ------------------------------------------------- snapshot interface
    @property
    def traceable(self):
        """The plain jit wrapper — for callers that need to trace through
        (``jax.vjp`` over the function while recording)."""
        return self._jit

    def sig_of(self, *args, **kwargs):
        """Public signature probe: accepts real arrays OR
        ``jax.ShapeDtypeStruct`` specs (only shape/dtype are read)."""
        return _arg_sig(args, kwargs)

    def compiled_for(self, sig=None):
        """The cached executable for ``sig`` (single-signature wrappers
        ignore it); None when not yet compiled."""
        if self._single or sig is None:
            return self._only
        return self._execs.get(sig)

    def adopt(self, compiled, sig=None):
        """Install a deserialized executable WITHOUT tracing — the
        snapshot warm-start path (zero compiles, zero traces). For
        multi-signature wrappers, ``sig`` comes from :meth:`sig_of` over
        spec structs."""
        if self._single or sig is None:
            self._only = compiled
        else:
            self._execs[sig] = compiled

    def signatures(self):
        return list(self._execs)

    def num_compiled(self):
        return (1 if self._only is not None else 0) + len(self._execs)
