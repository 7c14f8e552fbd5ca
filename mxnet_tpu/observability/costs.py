"""Per-program cost attribution: flops / bytes / peak-HBM as telemetry.

XLA's compiled executables already answer "what does this program cost"
on every backend: ``Compiled.cost_analysis()`` reports flops and bytes
accessed, ``Compiled.memory_analysis()`` reports argument / output /
temp / aliased buffer sizes — all deterministic per (program, jax
version, backend), including on CPU. This module turns every program
built through the ``base._jit_backed`` funnel (imperative jit ops, bulk
windows, tape replays, hybrid blocks, Symbol executors, serve buckets,
decode steps, dist buckets, the fused optimizer step) into a recorded
:class:`CostProfile`, keyed ``(tier, key)`` where ``key`` follows the
persistent comp-cache's content-address discipline — a sha256 over the
lowered StableHLO text, so the same program gets the same key in every
process.

Two recording paths, matching the funnel's two shapes:

* ``cache.AotFn`` (serve/decode always; every tier when the persistent
  store is on): the executable is acquired explicitly in ``_acquire``,
  so :func:`record_compiled` profiles it on the spot — zero extra
  compiles, two XLA property reads.
* plain ``jax.jit`` (the store-off default): :func:`tracked` wraps the
  jit callable. After each call it polls the wrapper's executable-cache
  size (one cheap probe on the hot path); on growth it parks the
  *lowered* handle on a bounded pending list. The analysis needs a
  ``Compiled``, which jax's dispatch cache does not expose — pending
  entries are materialized LAZILY at snapshot time
  (:func:`materialize`), so a train/serve loop never pays the one extra
  explicit compile inline.

Surfaced as ``observability.snapshot()["costs"]`` (a registry
collector), in the Prometheus exposition (``profiles`` become
``program="tier:key"``-labelled samples), and ranked by
``tools/cost_report.py`` — whose ``--quick`` artifact pins the
flops/bytes/peak-HBM columns of the pinned bench programs as a CI gate
(tests/test_costs.py).

Kill switch: ``MXNET_COST_ATTRIBUTION=0`` (or :func:`set_enabled`) —
the funnel then returns bare ``jax.jit`` callables and every record
call is a no-op.
"""
from __future__ import annotations

import hashlib
import os
import sys
import threading

import jax


def _env_enabled():
    v = os.environ.get("MXNET_COST_ATTRIBUTION", "1").strip().lower()
    return v not in ("0", "false", "off", "no")


_enabled = _env_enabled()
_lock = threading.Lock()
# bounded like every other telemetry structure (the GL006 concern applied
# to telemetry itself): program diversity is unbounded under adversarial
# shapes, profiles and parked handles are not
_PROFILE_CAP = max(int(os.environ.get("MXNET_COST_PROFILE_CAP", "512")), 1)
_PENDING_CAP = max(int(os.environ.get("MXNET_COST_PENDING_CAP", "256")), 1)
_profiles = {}          # (tier, key) -> CostProfile, insertion-ordered
_pending = []           # (tier, hint, jax.stages.Lowered) awaiting analysis
_dropped = 0            # profiles/pending evicted past the caps
_errors = 0             # analysis failures swallowed (never break dispatch)

_FIELDS = ("flops", "bytes_accessed", "output_bytes", "argument_bytes",
           "alias_bytes", "temp_bytes", "generated_code_bytes",
           "peak_hbm_bytes")


class CostProfile:
    """One compiled program's deterministic cost columns.

    ``peak_hbm_bytes`` is the program's working set — arguments +
    outputs + XLA temp buffers, minus aliased (donated) bytes, which
    would otherwise be double-counted."""

    __slots__ = ("tier", "key", "hint", "builds") + _FIELDS

    def __init__(self, tier, key, hint, **cols):
        self.tier = tier
        self.key = key
        self.hint = hint
        self.builds = 1
        for f in _FIELDS:
            setattr(self, f, cols.get(f, 0))

    def as_dict(self):
        d = {"tier": self.tier, "key": self.key, "hint": self.hint,
             "builds": self.builds}
        for f in _FIELDS:
            d[f] = getattr(self, f)
        return d


def program_key(lowered_text):
    """Content address of a program: sha256 over its lowered StableHLO
    text — the same text the comp-cache's ``store.digest`` hashes, so the
    key is stable across processes for the same program + jax version.
    Truncated to 16 hex chars for label/report use."""
    h = hashlib.sha256()
    h.update(lowered_text.encode("utf-8")
             if isinstance(lowered_text, str) else lowered_text)
    return h.hexdigest()[:16]


def _analyze(compiled):
    """Cost columns from a ``jax.stages.Compiled``. Both XLA surfaces are
    best-effort per backend — missing properties degrade to zeros, never
    to an exception."""
    cols = {f: 0 for f in _FIELDS}
    try:
        ca = compiled.cost_analysis()
    except Exception:
        ca = None
    if isinstance(ca, dict):
        cols["flops"] = float(ca.get("flops", 0.0) or 0.0)
        cols["bytes_accessed"] = float(ca.get("bytes accessed", 0.0) or 0.0)
        cols["output_bytes"] = float(ca.get("bytes accessedout{}", 0.0)
                                     or 0.0)
    try:
        mem = compiled.memory_analysis()
    except Exception:
        mem = None
    if mem is not None:
        arg = int(getattr(mem, "argument_size_in_bytes", 0) or 0)
        out = int(getattr(mem, "output_size_in_bytes", 0) or 0)
        tmp = int(getattr(mem, "temp_size_in_bytes", 0) or 0)
        ali = int(getattr(mem, "alias_size_in_bytes", 0) or 0)
        cols["argument_bytes"] = arg
        cols["alias_bytes"] = ali
        cols["temp_bytes"] = tmp
        cols["generated_code_bytes"] = int(
            getattr(mem, "generated_code_size_in_bytes", 0) or 0)
        if out:
            cols["output_bytes"] = out
        cols["peak_hbm_bytes"] = arg + out + tmp - ali
    return cols


def _put(tier, key, hint, cols):
    global _dropped
    with _lock:
        prof = _profiles.get((tier, key))
        if prof is not None:
            prof.builds += 1
            return prof
        if len(_profiles) >= _PROFILE_CAP:
            _profiles.pop(next(iter(_profiles)))
            _dropped += 1
        prof = CostProfile(tier, key, hint, **cols)
        _profiles[(tier, key)] = prof
        return prof


def _hlolint_capture(tier, hint, key, lowered):
    """Hand the lowered program to the hlolint corpus (ISSUE 18): the
    same seam that records costs also captures the StableHLO text for
    program-level lint. Never raises into the record path; hlolint has
    its own kill switch + bounded corpus."""
    try:
        from mxnet_tpu.analysis import hlolint
        hlolint.capture(tier, hint, key, lowered)
    except Exception:
        pass


def record_compiled(tier, hint, lowered, compiled):
    """EAGER record (cache.AotFn._acquire): the ``Compiled`` is already
    in hand, so profiling costs two XLA property reads and one hash."""
    global _errors
    if not _enabled:
        return None
    try:
        key = program_key(lowered.as_text())
        _hlolint_capture(tier, hint, key, lowered)
        return _put(tier, key, hint, _analyze(compiled))
    except Exception:
        _errors += 1
        return None


class _TrackedJit:
    """Thin cost-attribution wrapper over a ``jax.jit`` callable (the
    store-off funnel shape). Forwards the call, polls the wrapper's
    executable-cache size, and on growth parks the lowered handle for
    lazy analysis. Attribute access delegates to the jit wrapper, so
    ``lower``/``eval_shape``/``__wrapped__`` users are unaffected;
    ``cache.traceable`` passes it through unchanged (it inlines under an
    outer trace exactly like the bare jit callable)."""

    __slots__ = ("_jit", "_tier", "_hint", "_seen")

    def __init__(self, jitfn, tier, hint):
        self._jit = jitfn
        self._tier = tier
        self._hint = hint
        self._seen = 0

    def __call__(self, *args, **kwargs):
        out = self._jit(*args, **kwargs)
        try:
            n = self._jit._cache_size()
        except Exception:
            return out
        if n != self._seen:
            self._seen = n
            self._note(args, kwargs)
        return out

    def _note(self, args, kwargs):
        global _dropped, _errors
        if not _enabled:
            return
        try:
            # lower() reads avals only — safe even when the call just
            # donated (and deleted) its input buffers
            lowered = self._jit.lower(*args, **kwargs)
        except Exception:
            _errors += 1
            return
        with _lock:
            if len(_pending) >= _PENDING_CAP:
                _pending.pop(0)
                _dropped += 1
            _pending.append((self._tier, self._hint, lowered))

    def __getattr__(self, name):
        return getattr(self._jit, name)


def tracked(jitfn, tier="jit", hint=""):
    """Wrap a jit callable for cost attribution; returns it unwrapped
    when compiles can't be observed (no ``_cache_size`` probe — e.g. a
    non-jit callable handed through the funnel by a test double)."""
    if not _enabled or not hasattr(jitfn, "_cache_size"):
        return jitfn
    return _TrackedJit(jitfn, tier, hint)


def materialize(limit=None):
    """Compile + analyze parked programs (snapshot time). Each unique
    program costs ONE explicit compile here — jax's dispatch cache and
    the AOT ``Lowered.compile()`` do not share executables — and repeats
    are deduplicated by content key before compiling. Returns the number
    of pending entries drained."""
    global _errors
    done = 0
    while limit is None or done < limit:
        with _lock:
            if not _pending:
                break
            tier, hint, lowered = _pending.pop(0)
        done += 1
        try:
            key = program_key(lowered.as_text())
            _hlolint_capture(tier, hint, key, lowered)
            with _lock:
                prof = _profiles.get((tier, key))
            if prof is not None:
                with _lock:
                    prof.builds += 1
                continue
            _put(tier, key, hint, _analyze(lowered.compile()))
        except Exception:
            _errors += 1
    return done


def profiles():
    """Recorded profiles as ``{"tier:key": dict}`` (copies)."""
    with _lock:
        return {"%s:%s" % (t, k): p.as_dict()
                for (t, k), p in _profiles.items()}


# ------------------------------------------------------------ HBM ledger
def _params_nbytes(block):
    total = 0
    for p in block.collect_params().values():
        try:
            total += int(p.data()._data.nbytes)
        except Exception:
            pass
    return total


def _server_ledger(s):
    row = {"params_bytes": _params_nbytes(s.model)}
    cache = getattr(s, "cache", None)
    if cache is not None and hasattr(cache, "nbytes"):
        row["kv_cache_bytes"] = int(cache.nbytes())
        row["kv_cache_bytes_unquantized"] = int(cache.nbytes_unquantized())
    with _lock:
        peaks = [p.peak_hbm_bytes for (t, _k), p in _profiles.items()
                 if t in ("serve", "decode")]
    row["program_peak_bytes"] = int(max(peaks)) if peaks else 0
    row["total_bytes"] = (row["params_bytes"] + row.get("kv_cache_bytes", 0)
                          + row["program_peak_bytes"])
    return row


def hbm_ledger():
    """Per-live-server HBM accounting: parameter bytes (live arrays),
    paged-KV bytes (``PagedKVCache.nbytes()`` — exact and
    quantization-aware, the int8 pages count their fp32 scale planes),
    and the peak serve/decode program working set from the recorded
    profiles. Only servers self-register (``serve._SERVERS``); trainer
    rows are built by callers via :func:`trainer_ledger`."""
    out = {"servers": {}}
    serve = sys.modules.get("mxnet_tpu.serve")
    if serve is None:
        out["subsystem"] = "not loaded"
        return out
    for s in list(getattr(serve, "_SERVERS", ())):
        try:
            out["servers"][s.name] = _server_ledger(s)
        except Exception as e:
            out["servers"][getattr(s, "name", "?")] = {
                "error": "%s: %s" % (type(e).__name__, e)}
    return out


def trainer_ledger(trainer):
    """HBM row for a ``gluon.Trainer``: parameter + gradient + optimizer
    state bytes (live arrays) plus the peak jit-tier program working set
    — the training-side counterpart of a server's ledger row."""
    import jax.tree_util as jtu

    params_b = grads_b = 0
    for p in getattr(trainer, "_params", ()):
        try:
            params_b += int(p.data()._data.nbytes)
        except Exception:
            pass
        try:
            g = p.grad()
            grads_b += int(getattr(g, "_data", g).nbytes)
        except Exception:
            pass
    states_b = 0
    for attr in ("_states", "_state", "_updaters"):
        st = getattr(trainer, attr, None)
        if st:
            for leaf in jtu.tree_leaves(st):
                states_b += int(getattr(leaf, "nbytes", 0) or 0)
            break
    with _lock:
        peaks = [p.peak_hbm_bytes for (t, _k), p in _profiles.items()
                 if t == "jit"]
    row = {"params_bytes": params_b, "grads_bytes": grads_b,
           "optimizer_state_bytes": states_b,
           "program_peak_bytes": int(max(peaks)) if peaks else 0}
    row["total_bytes"] = sum(row.values())
    return row


# -------------------------------------------------------------- snapshot
def snapshot_section():
    """The ``snapshot()["costs"]`` section (registry collector): bounded,
    JSON-able, never raises past the registry's collector guard.
    Materializes parked programs first so the section is complete at
    scrape time — the one place the lazy path pays its explicit
    compiles."""
    if _enabled:
        materialize()
    profs = profiles()
    with _lock:
        pend, dropped, errors = len(_pending), _dropped, _errors
    totals = {}
    for prof in profs.values():
        t = totals.setdefault(prof["tier"], {
            "programs": 0, "flops": 0.0, "bytes_accessed": 0.0,
            "peak_hbm_bytes": 0})
        t["programs"] += 1
        t["flops"] += prof["flops"]
        t["bytes_accessed"] += prof["bytes_accessed"]
        t["peak_hbm_bytes"] = max(t["peak_hbm_bytes"],
                                  prof["peak_hbm_bytes"])
    return {"enabled": _enabled, "profiles": profs, "totals": totals,
            "pending": pend, "dropped": dropped, "errors": errors,
            "ledger": hbm_ledger()}


# ------------------------------------------------------------- switches
def enabled():
    return _enabled


def set_enabled(on=True):
    """Runtime kill switch (also ``MXNET_COST_ATTRIBUTION=0`` at import).
    Returns the previous state. Programs built while disabled are never
    retroactively profiled — the funnel returned them unwrapped."""
    global _enabled
    prev = _enabled
    _enabled = bool(on)
    return prev


def reset():
    """Test hook: drop every recorded profile and parked handle."""
    global _dropped, _errors
    with _lock:
        _profiles.clear()
        del _pending[:]
        _dropped = _errors = 0
