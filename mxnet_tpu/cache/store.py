"""Disk-backed cross-process executable store (Tier A of mxnet_tpu.cache).

Every jit path in this stack (``base.jitted`` / ``bulk_jitted`` /
``tape_jitted``, the serve/decode warmup compiles, the hybrid-block
compiled call) builds its XLA program through one funnel; this store sits
under that funnel and persists the COMPILED executable across processes —
the TVM ``export_library`` idea (arXiv 1802.04799) applied to jit caches:
compile once, ship the artifact, load and serve.

Content-addressed keying: an entry's identity is the sha256 of the
**lowered StableHLO text** plus the backend/version fingerprint.  The
in-process caches key structurally (interned signatures, chain topology)
because they must be O(1) on the hot path; those keys are process-local
(intern ids are list indices).  The HLO text is what those keys *denote*,
is deterministic across processes for the same program, and makes wrong-key
collisions structurally impossible — two different programs cannot share a
digest.  Tracing still happens on a warm start (cheap, milliseconds); the
XLA compile (seconds-to-minutes on TPU) is what the store skips.

Discipline:

* single-writer atomic files — entries are written to a unique temp name
  and ``os.replace``d into place, so concurrent processes racing on the
  same key can never expose a torn read (last writer wins, both wrote the
  same bytes anyway);
* corruption / version mismatch is NEVER fatal: a truncated, stale-jaxlib
  or foreign entry logs one warning and falls back to a recompile;
* mtime+size GC: on insert, when the store exceeds ``MXNET_COMP_CACHE_CAP``
  bytes, oldest-mtime entries are evicted first (reads touch mtime, so the
  policy is LRU-ish without an index file);
* proof-hook counters mirror the ``*_compile_counter`` discipline:
  ``engine.comp_cache_hit_counter`` / ``comp_cache_miss_counter`` /
  ``comp_cache_deserialize_counter`` are what tests and tools/diagnose.py
  read.

The store is OFF unless ``MXNET_COMP_CACHE_DIR`` is set (or
:func:`configure` is called) — the default imperative/serving paths keep
their exact zero-overhead ``jax.jit`` dispatch.
"""
from __future__ import annotations

import hashlib
import os
import pickle
import tempfile
import threading
import warnings

SCHEMA = "mxc2"
ENTRY_MAGIC = "mxcexec1"
ENTRY_SUFFIX = ".mxc"

# tiers = subdirectories; one per jit funnel so diagnose.py can report
# per-tier entry counts and a GC sweep never mixes populations. The
# unified graph IR (mxnet_tpu.ir.lower) lowers every capture through
# base._jit_backed with the CAPTURE's tier name ("bulk"/"tape"/"symbol"),
# so cross-capture dedup upstream only ever SHRINKS a tier's population —
# one canonical program persists once, under the tier that built it first.
# "symbol" must be listed: its entries are written like any other tier's,
# and a tier missing here is invisible to scan()/gc() (unbounded growth).
TIERS = ("jit", "bulk", "tape", "hybrid", "symbol", "serve", "decode")


def _warn(msg):
    warnings.warn(msg, RuntimeWarning, stacklevel=3)


def fingerprint():
    """Backend/version fingerprint baked into every entry: a serialized
    executable is only valid for the exact jax/jaxlib pair and backend
    that produced it (PJRT gives no ABI stability across versions)."""
    import jax

    try:
        backend = jax.default_backend()
    except RuntimeError:  # backend init failed: still allow store writes
        backend = "unknown"
    import jaxlib

    return "|".join((SCHEMA, "jax=" + jax.__version__,
                     "jaxlib=" + jaxlib.__version__, backend))


def pack_entry(key, payload, in_tree, out_tree, devices, fp=None):
    """Serialize one executable entry to bytes. ``key`` is the entry's
    logical identity (HLO digest for store entries, the manifest key for
    snapshot entries) — verified on read BEFORE the fingerprint so a
    wrong-key file is reported as wrong-key, not as stale. ``devices`` are
    the ids of the devices the program was compiled for: the loader hands
    the executable back to exactly those (jax would otherwise load it over
    every local device)."""
    return pickle.dumps({
        "magic": ENTRY_MAGIC,
        "key": key,
        "fingerprint": fp if fp is not None else fingerprint(),
        "payload": payload,
        "in_tree": in_tree,
        "out_tree": out_tree,
        "devices": list(devices),
    }, protocol=pickle.HIGHEST_PROTOCOL)


def unpack_entry(data, expect_key, origin="compilation cache"):
    """Validate + unpickle one entry; returns the dict or None (with ONE
    warning) on any corruption, key mismatch, or version skew. The failure
    kind feeds the store counters: 'corrupt' (unreadable), 'wrong_key',
    'stale' (fingerprint skew)."""
    try:
        blob = pickle.loads(data)
        if not isinstance(blob, dict) or blob.get("magic") != ENTRY_MAGIC:
            raise ValueError("bad magic")
    except Exception as e:
        _warn("%s entry is corrupt (%s: %s) — recompiling"
              % (origin, type(e).__name__, e))
        return None, "corrupt"
    if expect_key is not None and blob.get("key") != expect_key:
        _warn("%s entry key mismatch (found %r, wanted %r) — recompiling"
              % (origin, blob.get("key"), expect_key))
        return None, "wrong_key"
    fp = fingerprint()
    if blob.get("fingerprint") != fp:
        _warn("%s entry was built by %r but this process is %r — "
              "recompiling" % (origin, blob.get("fingerprint"), fp))
        return None, "stale"
    return blob, None


def load_compiled_entry(path, expect_key, origin="compilation cache"):
    """Read + validate + deserialize an entry file into a callable
    ``jax.stages.Compiled``; None on ANY failure (one warning, never a
    crash). Returns (compiled_or_None, failure_kind_or_None)."""
    from .. import engine

    try:
        with open(path, "rb") as fh:
            data = fh.read()
    except OSError as e:
        _warn("%s entry unreadable (%s) — recompiling" % (origin, e))
        return None, "corrupt"
    blob, fail = unpack_entry(data, expect_key, origin=origin)
    if blob is None:
        return None, fail
    try:
        import jax
        from jax.experimental import serialize_executable as se

        by_id = {d.id: d for d in jax.local_devices()}
        compiled = se.deserialize_and_load(
            blob["payload"], blob["in_tree"], blob["out_tree"],
            execution_devices=[by_id[i] for i in blob["devices"]])
    except Exception as e:
        _warn("%s entry failed to deserialize (%s: %s) — recompiling"
              % (origin, type(e).__name__, e))
        return None, "corrupt"
    engine.comp_cache_deserialize_counter.bump()
    return compiled, None


def serialize_compiled(compiled):
    """(payload, in_tree, out_tree, device ids) for a
    ``jax.stages.Compiled``, or None when this backend's PJRT client does
    not support executable serialization (the caller then falls back to
    jax's own persistent compilation cache, which caches at the HLO level
    instead)."""
    try:
        from jax.experimental import serialize_executable as se

        devices = [d.id for d in
                   compiled.runtime_executable().local_devices()]
        return se.serialize(compiled) + (devices,)
    except Exception:
        return None


class CompCacheStore:
    """One directory of persisted executables, in tier subdirectories.

    Thread-safe for the write path (a lock guards GC bookkeeping); reads
    are lock-free. All sizes are bytes. See the module docstring for the
    on-disk discipline.
    """

    def __init__(self, directory, cap_bytes=None):
        self.directory = os.path.abspath(directory)
        if cap_bytes is None:
            try:
                cap_bytes = int(os.environ.get("MXNET_COMP_CACHE_CAP",
                                               2 << 30))
            except ValueError:
                cap_bytes = 2 << 30
        self.cap_bytes = int(cap_bytes)
        self._lock = threading.Lock()
        self._serialization_broken = False
        # store-side counters (process-local; the cross-path hit/miss/
        # deserialize counters live in engine with the other proof hooks)
        self.writes = 0
        self.evictions = 0
        self.stale = 0
        self.corrupt = 0
        self.wrong_key = 0

    # ------------------------------------------------------------ keying
    def digest(self, key_text):
        """Content digest of a program: fingerprint + lowered HLO text."""
        h = hashlib.sha256()
        h.update(fingerprint().encode())
        h.update(b"\0")
        h.update(key_text.encode() if isinstance(key_text, str)
                 else key_text)
        return h.hexdigest()

    def entry_path(self, tier, digest):
        return os.path.join(self.directory, tier, digest + ENTRY_SUFFIX)

    # ------------------------------------------------------------ lookup
    def lookup(self, tier, lowered):
        """Compiled executable for a ``jax.stages.Lowered``, or None.
        Bumps engine.comp_cache_hit_counter / comp_cache_miss_counter."""
        from .. import engine

        digest = self.digest(lowered.as_text())
        path = self.entry_path(tier, digest)
        if not os.path.exists(path):
            engine.comp_cache_miss_counter.bump()
            return None
        compiled, fail = load_compiled_entry(path, digest)
        if compiled is None:
            with self._lock:
                if fail == "stale":
                    self.stale += 1
                elif fail == "wrong_key":
                    self.wrong_key += 1
                else:
                    self.corrupt += 1
            # a bad entry will never become good; drop it so the next
            # process pays one compile, not one warning per lookup
            try:
                os.remove(path)
            except OSError:
                pass
            engine.comp_cache_miss_counter.bump()
            return None
        engine.comp_cache_hit_counter.bump()
        try:  # LRU-ish GC signal: reads refresh mtime
            os.utime(path, None)
        except OSError:
            pass
        return compiled

    # ------------------------------------------------------------ insert
    def save(self, tier, lowered, compiled):
        """Persist a freshly compiled executable; best-effort (a full disk
        or unsupported backend degrades to 'no persistence', never an
        error). Returns True when the entry landed."""
        if self._serialization_broken:
            return False
        packed = serialize_compiled(compiled)
        if packed is None:
            # executable serialization unsupported on this backend: fall
            # back to jax's persistent compilation cache (HLO-level — it
            # skips the XLA compile but not the executable load) once
            self._serialization_broken = True
            self._enable_xla_fallback()
            return False
        digest = self.digest(lowered.as_text())
        path = self.entry_path(tier, digest)
        try:
            data = pack_entry(digest, *packed)
            self.atomic_write(path, data)
        except Exception as e:
            _warn("compilation cache write failed (%s: %s) — continuing "
                  "without persistence for this entry"
                  % (type(e).__name__, e))
            return False
        with self._lock:
            self.writes += 1
        self.gc()
        return True

    @staticmethod
    def atomic_write(path, data):
        """Unique-temp + rename: a reader can never observe a torn entry,
        and two processes racing the same digest both write identical
        bytes — last replace wins harmlessly."""
        os.makedirs(os.path.dirname(path), exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path),
                                   suffix=".tmp")
        try:
            with os.fdopen(fd, "wb") as fh:
                fh.write(data)
            os.replace(tmp, path)
        except BaseException:
            try:
                os.remove(tmp)
            except OSError:
                pass
            raise

    def _enable_xla_fallback(self):
        from . import enable_compile_cache

        _warn("executable serialization unsupported on this backend — "
              "falling back to jax's persistent compilation cache under %s"
              % enable_compile_cache())

    # ---------------------------------------------------------------- GC
    def _entries(self):
        """[(path, mtime, size)] across all tiers."""
        out = []
        for tier in TIERS:
            d = os.path.join(self.directory, tier)
            if not os.path.isdir(d):
                continue
            for name in os.listdir(d):
                if not name.endswith(ENTRY_SUFFIX):
                    continue
                p = os.path.join(d, name)
                try:
                    st = os.stat(p)
                except OSError:
                    continue
                out.append((p, st.st_mtime, st.st_size))
        return out

    def gc(self):
        """Evict oldest-mtime entries until total bytes fit the cap.
        Eviction costs at most a recompile — entries are pure caches."""
        with self._lock:
            entries = self._entries()
            total = sum(s for _, _, s in entries)
            if total <= self.cap_bytes:
                return 0
            evicted = 0
            for p, _, s in sorted(entries, key=lambda e: e[1]):
                if total <= self.cap_bytes:
                    break
                try:
                    os.remove(p)
                except OSError:
                    continue
                total -= s
                evicted += 1
            self.evictions += evicted
            return evicted

    # ------------------------------------------------------------- stats
    def scan(self):
        """Per-tier {entries, bytes} + totals — the diagnose.py payload."""
        tiers = {}
        total_n = total_b = 0
        for tier in TIERS:
            d = os.path.join(self.directory, tier)
            n = b = 0
            if os.path.isdir(d):
                for name in os.listdir(d):
                    if name.endswith(ENTRY_SUFFIX):
                        try:
                            b += os.path.getsize(os.path.join(d, name))
                            n += 1
                        except OSError:
                            pass
            tiers[tier] = {"entries": n, "bytes": b}
            total_n += n
            total_b += b
        return {"dir": self.directory, "cap_bytes": self.cap_bytes,
                "entries": total_n, "bytes": total_b, "tiers": tiers,
                "writes": self.writes, "evictions": self.evictions,
                "stale": self.stale, "corrupt": self.corrupt,
                "wrong_key": self.wrong_key}
