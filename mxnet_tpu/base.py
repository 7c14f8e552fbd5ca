"""Shared utilities: dtype handling, jit cache, op registry.

The op registry is the TPU-native analogue of MXNet's operator registration
(ref: nnvm/src/core/op.cc, src/operator/*-inl.h NNVM_REGISTER_OP): every pure
functional op registers once and both front-ends (imperative ``nd`` and the
traced/hybridized path) are generated from it.
"""
from __future__ import annotations

import functools
import os
import threading
from typing import Callable, Dict, NamedTuple

import jax
import numpy as np

string_types = (str,)

_DTYPE_ALIASES = {
    "float16": np.float16,
    "bfloat16": jax.numpy.bfloat16,
    "float32": np.float32,
    "float64": np.float64,
    "int8": np.int8,
    "uint8": np.uint8,
    "int16": np.int16,
    "int32": np.int32,
    "int64": np.int64,
    "bool": np.bool_,
}

# fp8 storage dtypes (quantization.py fp8 modes) resolve by name where the
# jax build ships them — load_npz_exact's __dtype__ sidecars round-trip fp8
# checkpoints through resolve_dtype
for _fp8 in ("float8_e4m3fn", "float8_e5m2"):
    if hasattr(jax.numpy, _fp8):
        _DTYPE_ALIASES[_fp8] = getattr(jax.numpy, _fp8)


def is_tpu_backend():
    """True when the default backend is a TPU. Gates the pallas kernels."""
    return jax.default_backend() == "tpu"


def next_pow2(n):
    """Smallest power of two ≥ n — the shared bucket-rounding rule (serve
    batch buckets, decode cache capacities, prompt-length buckets): any
    request stream compiles at most log2(max) programs per knob instead of
    one per distinct size."""
    p = 1
    while p < n:
        p <<= 1
    return p


def resolve_dtype(dtype):
    if dtype is None:
        return None
    if isinstance(dtype, str):
        return _DTYPE_ALIASES.get(dtype, np.dtype(dtype).type)
    return dtype


def _freeze(v):
    if isinstance(v, (list, tuple)):
        return tuple(_freeze(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _freeze(x)) for k, x in v.items()))
    if isinstance(v, np.ndarray):
        return ("__np__", v.shape, str(v.dtype), v.tobytes())
    return v


def env_cap(name, default):
    """Integer cache cap from the environment (graphlint GL006 knobs)."""
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


class BoundedCache(dict):
    """Capped dict for module-level program/metadata caches (graphlint
    GL006: an unbounded module cache grows forever in long-running serving
    processes). Eviction is insertion-order (oldest first) and happens only
    on insert — hits stay plain-dict speed with zero LRU bookkeeping on the
    per-op hot path. Entries must be pure caches: evicting one may cost a
    recompute/recompile, never correctness. ``evictions`` counts the
    drops — observability.snapshot() surfaces it per cache, so cap churn
    in a long-running replica is visible instead of silent.

    Inserts are serialized by an internal lock: serve dispatcher threads
    populate these tables concurrently, and the unguarded
    len-check/evict/store sequence could evict twice or over-fill
    (racecheck GL011). Hits never take the lock — reads stay plain-dict
    speed. ``_insert_locked`` is a seam for analysis.concurrency's
    runtime race probe (placed *inside* the lock so correctly serialized
    writers never report)."""

    __slots__ = ("cap", "evictions", "_lk")

    def __init__(self, cap):
        super().__init__()
        self.cap = max(int(cap), 1)
        self.evictions = 0
        self._lk = threading.Lock()

    def __setitem__(self, key, value):
        with self._lk:
            self._insert_locked(key, value)

    def _insert_locked(self, key, value):
        if len(self) >= self.cap and key not in self:
            del self[next(iter(self))]
            self.evictions += 1
        dict.__setitem__(self, key, value)


# per-(op, static attrs, device) jitted callables. Keys include static-attr
# VALUES (reshape targets, axis lists), whose diversity is unbounded under
# adversarial serving traffic — hence the cap (MXNET_JIT_CACHE_CAP).
_JIT_CACHE: Dict = BoundedCache(env_cap("MXNET_JIT_CACHE_CAP", 4096))

# bulk-window FRONT memo (engine.bulk): window-structural-key →
# (program, arg selection) resolved through the canonical IR cache below.
# Steady-state epochs re-running an identical imperative chain hit this
# memo at hash-and-lookup cost — the imperative analogue of MXNet's
# CachedOp handle reuse; a miss builds the typed IR graph and resolves
# through _IR_CACHE (which is where identical math from the tape or a
# Symbol lands on the SAME compiled program).
# Capped (MXNET_BULK_CACHE_CAP): chain-topology diversity is unbounded.
_BULK_CACHE: Dict = BoundedCache(env_cap("MXNET_BULK_CACHE_CAP", 1024))

# the ONE canonical program cache (mxnet_tpu.ir.lower): content-addressed
# canonical-graph key → IREntry (optimized graph + every program lowered
# from it). The bulk/tape/symbol key schemes all collapse into this cache;
# the per-capture dicts above/below are thin front memos over it.
# MXNET_IR_CACHE_CAP bounds it; evictions are surfaced in
# observability.snapshot()["ir"].
_IR_CACHE: Dict = BoundedCache(env_cap("MXNET_IR_CACHE_CAP", 2048))


def _key_note(kind, key, limit=200):
    """Compact, truncated rendering of a program-cache key for watchdog
    attribution (observability): enough to identify the offending chain /
    tape topology in a structured warning, never the full key blob."""
    s = repr(key)
    if len(s) > limit:
        s = s[:limit - 3] + "..."
    return "%s:%s" % (kind, s)


def _jit_backed(fn, device=None, donate=None, tier="jit", hint=""):
    """The ONE funnel from this stack's program builders to jax.jit: a
    plain ``jax.jit`` when the persistent compilation store is off (the
    default — zero added overhead), a ``cache.AotFn`` when
    ``MXNET_COMP_CACHE_DIR`` is configured, so the compiled executable is
    persisted across processes (mxnet_tpu.cache Tier A). graphlint GL008
    flags direct ``jax.jit`` call sites that bypass this funnel.

    Because every capture path funnels through here, cost attribution
    (observability.costs) sees every program: the AotFn path records
    eagerly inside ``_acquire``; the plain-jit path is wrapped by
    ``costs.tracked`` (a per-call cache-size poll + lazy analysis).
    ``MXNET_COST_ATTRIBUTION=0`` restores the bare ``jax.jit`` return."""
    from .cache import persistent_backed
    from .observability import costs

    backed = persistent_backed(fn, device=device, donate_argnums=donate,
                               tier=tier, hint=hint)
    if backed is not None:
        return backed
    kw = {}
    if donate:
        kw["donate_argnums"] = tuple(donate)
    if device is not None:
        kw["device"] = device
    return costs.tracked(jax.jit(fn, **kw), tier, hint)


def bulk_jitted(key, builder):
    """LEGACY SHIM (pre-IR): cached jitted composed program for a flushed
    bulk window. The live flush path now builds a typed ``mxnet_tpu.ir``
    graph and lowers through ``ir.lower_forward`` (see
    ndarray._flush_window); this entry point remains for external callers
    that hand-compose a window program. ``key`` is the structural chain
    key; ``builder`` returns the pure replay function leaves→outputs,
    called only on a cache miss (engine.bulk_compile_counter bumps then —
    the no-recompile hook)."""
    f = _BULK_CACHE.get(key)
    if f is None:
        from .engine import bulk_compile_counter

        # note= carries the chain key to the retrace watchdog: a post-warmup
        # miss here warns with the offending topology (observability)
        bulk_compile_counter.bump(note=_key_note("bulk", key))
        f = _BULK_CACHE[key] = _jit_backed(builder(), tier="bulk",
                                           hint="bulk")
    return f


# compiled tape-replay FRONT memo (autograd.backward): structural key
# (tape topology, static attrs, leaf signatures, head set,
# grad_req/donation layout) → (program, arg selection) resolved through
# the canonical IR cache — the whole-program analogue of MXNet's nnvm
# backward graph executed via Imperative::Backward, now sharing the
# forward region's canonical form with the other captures.
# Capped like the others (MXNET_TAPE_CACHE_CAP).
_TAPE_CACHE: Dict = BoundedCache(env_cap("MXNET_TAPE_CACHE_CAP", 512))


def tape_jitted(key, builder):
    """LEGACY SHIM (pre-IR): cached jitted compiled-tape backward program.
    The live backward path now lowers the recorded region through
    ``mxnet_tpu.ir`` (autograd._compiled_backward); kept for external
    callers. ``builder`` (called only on a miss) returns
    ``(prog, donate_argnums)``; a steady-state record→backward loop must
    hit the cache every iteration — engine.tape_compile_counter (misses) /
    engine.tape_cache_hit_counter (hits) are the proof hooks tests and
    tools/diagnose.py read."""
    from .engine import tape_cache_hit_counter, tape_compile_counter

    f = _TAPE_CACHE.get(key)
    if f is None:
        tape_compile_counter.bump(note=_key_note("tape", key))
        prog, donate = builder()
        f = _TAPE_CACHE[key] = _jit_backed(prog, donate=donate or None,
                                           tier="tape", hint="tape")
    else:
        tape_cache_hit_counter.bump()
    return f


def jitted(fn: Callable, static_kwargs: dict, device=None):
    """Return a cached jitted callable of ``fn`` with the given static kwargs
    closed over. Equivalent role to MXNet's cached op handles for imperative
    invocation (ref: src/imperative/imperative.cc:InvokeOp)."""
    key = (fn, _freeze(static_kwargs), device)
    cached = _JIT_CACHE.get(key)
    if cached is None:
        f = functools.partial(fn, **static_kwargs) if static_kwargs else fn
        cached = _jit_backed(f, device=device, tier="jit",
                             hint=getattr(fn, "__name__", "op"))
        _JIT_CACHE[key] = cached
    return cached


class OpDef(NamedTuple):
    name: str
    fn: Callable
    # kwargs listed here are array-valued (traced); everything else static
    array_kwargs: tuple = ()
    # ops that need an rng key get one injected as kwarg `key`
    needs_rng: bool = False
    # ops that need the training flag get kwarg `training`
    needs_training: bool = False
    # number of outputs that are differentiable (None = all)
    nondiff: bool = False
    # tuple-returning ops declare their arity so the symbol builder can
    # mirror it with _item projections (MXNet: nnvm op num_outputs)
    n_outputs: int = 1
    # precomputed at registration: eligible for the imperative fast/lazy
    # path (single output, no rng/training-key injection) — one attr read
    # on the per-op hot loop instead of three
    fast_ok: bool = True


OP_REGISTRY: Dict[str, OpDef] = {}


def register_op(name=None, array_kwargs=(), needs_rng=False, needs_training=False, nondiff=False,
                n_outputs=1):
    def deco(fn):
        opname = name or fn.__name__
        OP_REGISTRY[opname] = OpDef(opname, fn, tuple(array_kwargs), needs_rng, needs_training,
                                    nondiff, n_outputs,
                                    n_outputs == 1 and not needs_rng and not needs_training)
        return fn

    return deco


class MXNetError(RuntimeError):
    pass


def check_call(ret):
    if ret != 0:
        raise MXNetError("native call failed with code %d" % ret)
