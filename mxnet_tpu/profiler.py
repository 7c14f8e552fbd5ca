"""Profiler (ref: src/profiler/profiler.cc, python/mxnet/profiler.py).

Wraps jax.profiler (XLA/TPU traces viewable in TensorBoard/Perfetto) and adds
host-side named scopes with wall timers, mirroring MXNet's
profiler.set_config/start/stop/dump/dumps API.

Two outputs, like the reference:
* ``dump()`` → Chrome trace-event JSON (chrome://tracing / Perfetto), host
  scopes + imperative op dispatches as complete ('X') events;
* ``dumps(aggregate_stats=True)`` → the MXNet-style aggregate table
  (count/total/min/max/avg per name).
The XLA-side trace (device kernels) goes to ``<filename>_trace/`` via
jax.profiler and is viewable in TensorBoard — that covers what MXNet's
device-side CUPTI counters report.
"""
from __future__ import annotations

import contextlib
import gc
import json
import os
import threading
import time

import jax

_config = {"profile_all": False, "profile_imperative": True,
           "filename": "profile.json", "aggregate_stats": False}
_running = False
_records = []          # {"name", "ts_us", "dur_ms", "cat"}
# re-entrant: a collection that starts while this thread holds the lock (a
# dump allocates) records its own span from inside (``_gc_span``)
_lock = threading.RLock()
_epoch = time.perf_counter()

# the record buffer is BOUNDED (the GL006 unbounded-growth concern applied
# to the profiler itself: a long always-on run would otherwise grow host
# memory without limit). Past the cap, new records are counted as dropped
# and discarded — the retained prefix keeps a coherent trace; the dropped
# tally is surfaced in dump() metadata and observability.snapshot().
try:
    _RECORD_CAP = int(os.environ.get("MXNET_PROFILER_RECORD_CAP", "1000000"))
except ValueError:
    _RECORD_CAP = 1000000
_dropped = 0


def record_cap():
    return _RECORD_CAP


def num_records():
    return len(_records)


def records_dropped():
    """Records discarded because the bounded buffer was full — nonzero
    means the Chrome trace is truncated (raise MXNET_PROFILER_RECORD_CAP
    or dump/reset more often)."""
    return _dropped


def _sync_imperative():
    """Push the imperative-profiling flag (and this module object) into
    ndarray's hot loop: invoke() reads ONE precomputed boolean per op
    instead of two module-attr chains — that line runs per imperative op."""
    import sys

    from . import ndarray as _nd

    _nd._profiler_mod = sys.modules[__name__]
    _nd._prof_on = _running and _config["profile_imperative"]


def set_config(profile_all=False, profile_symbolic=True, profile_imperative=True,
               profile_memory=True, profile_api=True, filename="profile.json",
               aggregate_stats=False, **kwargs):
    _config.update(profile_all=profile_all, filename=filename,
                   profile_imperative=profile_imperative,
                   aggregate_stats=aggregate_stats)
    _sync_imperative()


def set_state(state="stop", profile_process="worker"):
    if state == "run":
        start()
    else:
        stop()


def is_running():
    return _running


def start(profile_process="worker"):
    global _running
    if _running:
        return
    _running = True
    _sync_imperative()
    logdir = _config["filename"].rsplit(".", 1)[0] + "_trace"
    try:
        jax.profiler.start_trace(logdir)
    except Exception:
        pass
    gc.callbacks.append(_gc_span)


def stop(profile_process="worker"):
    global _running, _gc_open
    if not _running:
        return
    _running = False
    _sync_imperative()
    if _gc_span in gc.callbacks:
        gc.callbacks.remove(_gc_span)
    _gc_open = None
    try:
        jax.profiler.stop_trace()
    except Exception:
        pass


# the collection under way while the profiler runs: (annotation, name,
# start); a collection never nests in another
_gc_open = None


def _gc_span(phase, info):
    """The ``gc.callbacks`` hook that ``start()`` installs and ``stop()``
    removes: every collection is a ``decode[gc<generation>]`` span on the
    thread that ran it (a collection halts the decode loop like every other
    thread, so the device's idle time under it gets a name), with
    ``collected`` and ``uncollectable`` in its record's ``args``."""
    global _gc_open
    if phase == "start":
        name = "decode[gc%d]" % info["generation"]
        ann = jax.profiler.TraceAnnotation(name)
        ann.__enter__()
        _gc_open = (ann, name, time.perf_counter())
        return
    opened, _gc_open = _gc_open, None
    if opened is None:          # installed while the collection ran
        return
    ann, name, t0 = opened
    ann.__exit__(None, None, None)
    t1 = time.perf_counter()
    _record(name, (t0 - _epoch) * 1e6, (t1 - t0) * 1e3, cat="gc",
            args={"collected": info["collected"],
                  "uncollectable": info["uncollectable"]})


def pause(profile_process="worker"):
    stop()


def resume(profile_process="worker"):
    start()


def _record(name, ts_us, dur_ms=None, cat="host", ph="X", **extra):
    global _dropped
    rec = {"name": name, "ts_us": ts_us, "cat": cat, "ph": ph, **extra}
    if dur_ms is not None:
        rec["dur_ms"] = dur_ms
    with _lock:
        if len(_records) >= _RECORD_CAP:
            _dropped += 1
            return
        _records.append(rec)


def aggregate():
    """MXNet-style aggregate stats: name → count/total/min/max/avg (ms)."""
    stats = {}
    with _lock:
        recs = list(_records)
    for r in recs:
        if r.get("ph", "X") != "X":
            continue  # counters/markers have no duration to aggregate
        s = stats.setdefault(r["name"], {"count": 0, "total_ms": 0.0,
                                         "min_ms": float("inf"), "max_ms": 0.0})
        s["count"] += 1
        s["total_ms"] += r["dur_ms"]
        s["min_ms"] = min(s["min_ms"], r["dur_ms"])
        s["max_ms"] = max(s["max_ms"], r["dur_ms"])
    for s in stats.values():
        s["avg_ms"] = s["total_ms"] / s["count"]
    return stats


def dumps(reset=False):
    """Aggregate table when configured (MXNet aggregate_stats=True), else the
    raw record list."""
    if _config["aggregate_stats"]:
        stats = aggregate()
        lines = ["%-40s %8s %12s %10s %10s %10s" %
                 ("Name", "Calls", "Total(ms)", "Min(ms)", "Max(ms)", "Avg(ms)")]
        for name, s in sorted(stats.items(), key=lambda kv: -kv[1]["total_ms"]):
            lines.append("%-40s %8d %12.3f %10.3f %10.3f %10.3f" %
                         (name, s["count"], s["total_ms"], s["min_ms"],
                          s["max_ms"], s["avg_ms"]))
        out = "\n".join(lines)
    else:
        with _lock:
            out = json.dumps(_records, indent=2)
    if reset:
        global _dropped
        with _lock:
            _records.clear()
            _dropped = 0
    return out


def dump(finished=True, profile_process="worker"):
    """Write Chrome trace-event JSON (the format MXNet's profiler.dump
    produces; open in chrome://tracing or Perfetto)."""
    events = []
    with _lock:
        for r in _records:
            ev = {"name": r["name"], "cat": r.get("cat", "host"),
                  "ph": r.get("ph", "X"), "ts": r["ts_us"],
                  "pid": os.getpid(), "tid": 0}
            if ev["ph"] == "X":
                ev["dur"] = r["dur_ms"] * 1e3
                if "args" in r:
                    ev["args"] = r["args"]  # bulk_scope op attribution
            elif ev["ph"] == "C":
                ev["args"] = {r["name"]: r["value"]}
            elif ev["ph"] == "i":
                ev["s"] = r.get("s", "g")
            events.append(ev)
    with open(_config["filename"], "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms",
                   "otherData": {"droppedRecords": _dropped}}, f)
    return _config["filename"]


@contextlib.contextmanager
def scope(name="<unk>"):
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(name):
        yield
    t1 = time.perf_counter()
    _record(name, (t0 - _epoch) * 1e6, (t1 - t0) * 1e3)


@contextlib.contextmanager
def op_scope(name):
    """Instruments one imperative op dispatch (called from ndarray.invoke when
    the profiler runs). Host-side cost only — device time is in the XLA trace;
    dispatch is async so dur ≈ Python+dispatch overhead, like MXNet's
    operator 'issue' events. Under lazy bulk execution (engine.bulk) the
    per-op event covers only the ~µs deferral; the real dispatch cost shows
    up as the flush's ``bulk[...]`` event (see bulk_scope)."""
    t0 = time.perf_counter()
    yield
    t1 = time.perf_counter()
    _record(name, (t0 - _epoch) * 1e6, (t1 - t0) * 1e3, cat="operator")


def _fused_label(op_names):
    """``mul x5,add x5,tanh x5``-style constituent label for a fused
    program event (shared by bulk_scope and backward_scope)."""
    counts = {}
    for n in op_names:
        counts[n] = counts.get(n, 0) + 1
    label = ",".join("%s x%d" % (n, c) if c > 1 else n
                     for n, c in counts.items())
    if len(label) > 120:
        label = label[:117] + "..."
    return label


@contextlib.contextmanager
def _fused_scope(kind, op_names):
    name = "%s[%s]" % (kind, _fused_label(op_names))
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(name):
        yield
    t1 = time.perf_counter()
    _record(name, (t0 - _epoch) * 1e6, (t1 - t0) * 1e3,
            cat="operator", args={"ops": list(op_names)})


def bulk_scope(op_names):
    """Instruments one flushed bulk-window dispatch (called from
    ndarray._flush_window): the composed program carries the cost of every
    deferred op it fuses, so the event is named after its constituents —
    ``bulk[mul x5,add x5,tanh x5]`` — keeping per-op attribution readable
    in the trace. The ``args.ops`` field holds the exact op sequence."""
    return _fused_scope("bulk", op_names)


@contextlib.contextmanager
def serve_scope(bucket, n_real):
    """Instruments one served-batch dispatch (called from
    serve.executor_pool when the profiler runs): the event is named
    ``serve[b32 fill=0.75]`` — compiled bucket size plus how much of it the
    coalesced requests actually filled — so batching efficiency reads
    directly off the trace next to the XLA kernels it feeds."""
    name = "serve[b%d fill=%.2f]" % (bucket, n_real / max(bucket, 1))
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(name):
        yield
    t1 = time.perf_counter()
    _record(name, (t0 - _epoch) * 1e6, (t1 - t0) * 1e3, cat="serve",
            args={"bucket": bucket, "rows": n_real})


@contextlib.contextmanager
def decode_scope(kind, slots, n_active, args=None, tag=None):
    """Instruments one stretch of the generative-decode scheduler's tick
    (called from serve.decoder when the profiler runs), named
    ``decode[<kind> fill=<active/slots> b<slots>]`` — batch-fill efficiency
    of the continuous-batching scheduler reads directly off the trace next
    to the XLA kernels, and between them the kinds cover the loop thread's
    time end to end, so every idle gap of the device lies under the span
    of what the host was doing:

    ``tick``         one scheduler tick that had something to do: the
                     parent of all the kinds below but ``idle``. Its self
                     time is the glue between them (the admission look, the
                     slot mask, the parameter list of the next dispatch)
    ``join<tp>``     the whole admission of one request at prompt bucket
                     ``tp`` (``args``: ``trace_id``, ``prompt_len``,
                     ``kind`` = prefill | inject); parent of the next two.
                     Its self time is key making, pad, prefix lookup, the
                     first-token read and bookkeeping.
    ``prefill<tp>``  the prefill (or prefix-inject) dispatch
    ``readout<tp>``  the page read-out into the prefix store (``mb``):
                     the wait for the prefill and the extract program, then
                     its one child
    ``copy<tp>``     the device-to-host copy of the page into the store
                     alone (``mb``): the outputs it copies are waited for
                     before it opens. The join's own read of the switch
                     decides, or where the join started untraced, one more
                     read at the copy's entry: a copy that follows a prefill
                     across the profiler's start is still named
    ``chunk<tc>``    one chunked-prefill dispatch
    ``ctl``          upload of the per-slot sampling controls after a
                     join or retire
    ``step``         one tick of the plain decode path, which keeps one
                     fused token step in flight ahead of the host: from the
                     dispatch of the step sent ahead to the host's read of
                     the step before it (the first tick of a stretch sends
                     two; one that sends none covers the read alone and
                     names the step read). ``ahead=1``: its dispatch went
                     out while another step was in flight, ``ahead=0``: not
                     (``verify<k>``: the speculative round, from its dispatch
                     to the read of its own tokens, carries no such field)
    ``wait``         inside ``step`` and ``verify<k>``: the host's read of
                     a step's tokens, where the loop waits for the device;
                     the host's own part of a tick is the tick less its
                     waits
    ``deliver``      bookkeeping after the read: the walk over the active
                     slots handing tokens to streams, retiring requests
    ``idle``         one span for a whole stretch in which no tick
                     progressed anything
    ``gc<gen>``      not written here: one collection of generation
                     ``gen`` on whatever thread ran it, ``decode[gc2]``
                     bare, from the ``gc.callbacks`` hook that ``start()``
                     installs and ``stop()`` removes (``_gc_span``)

    A tick reads the profiler's switch once, and once more for each request
    it joins (a join that starts after the profiler does is named even when
    its tick started before); ``step``, ``deliver``, ``ctl`` and ``chunk``
    keep the tick's reading.

    ``args`` goes to the TraceAnnotation (stats of the event in the
    ``.xplane.pb``) and to the Chrome record; the dict yielded is the
    record's ``args``, to which the caller may add what it learns inside.
    ``tag`` is more ``key=value`` fields of the name itself, which is what
    a reader of the trace sees: a routing model's ``step`` carries
    ``xmax=<the largest expert's load over the mean load>`` and
    ``xhit=<(layer, expert) pairs that got a pick>`` of the last step the
    host has read (a step's own load arrives with its tokens)
    (``decode[step fill=0.41 b32 xmax=2.50 xhit=38 kvread=0.066 ahead=1]``),
    and every ``step`` carries ``kvread=<the 128-position K/V blocks its
    live slots hold over the blocks the pool holds>``: what share of the
    pool the step's attention has to read (``serve.decoder._step_tag``);
    over a pool of recurrent state (``serve.kv_cache.StatePage``) it carries
    ``state=<MB its live slots hold>`` in that place; and last
    ``ahead=<0|1>`` (above). ``fill=`` and these fields are of the step the
    span sends."""
    name = "decode[%s fill=%.2f b%d%s]" % (
        kind, n_active / max(slots, 1), slots, " " + tag if tag else "")
    rec_args = {"slots": slots, "active": n_active}
    if args:
        rec_args.update(args)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(name, **(args or {})):
        yield rec_args
    t1 = time.perf_counter()
    _record(name, (t0 - _epoch) * 1e6, (t1 - t0) * 1e3, cat="serve",
            args=rec_args)


def backward_scope(op_names):
    """Instruments one compiled tape-replay dispatch (called from
    autograd._compiled_backward): the single program carries primal replay
    plus the vjp of every recorded op it fuses, named
    ``backward[mul x17,add x16,...]`` — the backward mirror of the
    ``bulk[...]`` events. The ``args.ops`` field holds the replayed op
    sequence in tape order."""
    return _fused_scope("backward", op_names)


class Domain:
    """Named grouping for profiler objects (ref: python/mxnet/profiler.py
    Domain). Maps to the trace-event ``cat`` field."""

    def __init__(self, name):
        self.name = name

    def new_task(self, name):
        return Task(self, name)

    def new_frame(self, name):
        return Frame(self, name)

    def new_event(self, name):
        return Event(self, name)

    def new_counter(self, name, value=None):
        return Counter(self, name, value)

    def new_marker(self, name):
        return Marker(self, name)


class Task:
    def __init__(self, domain=None, name="task"):
        self.name = name
        self._cat = domain.name if isinstance(domain, Domain) else "host"
        self._t0 = None

    def start(self):
        self._t0 = time.perf_counter()

    def stop(self):
        if self._t0 is not None:
            t1 = time.perf_counter()
            _record(self.name, (self._t0 - _epoch) * 1e6,
                    (t1 - self._t0) * 1e3, cat=self._cat)
            self._t0 = None


Frame = Task
Event = Task


class Counter:
    """Numeric counter emitted as Chrome trace 'C' events (ref: profiler.cc
    ProfileCounter). dump() renders these as a value-over-time track."""

    def __init__(self, domain=None, name="counter", value=None):
        self.name = name
        self._cat = domain.name if isinstance(domain, Domain) else "host"
        self._value = 0
        self._vlock = threading.Lock()
        if value is not None:
            self.set_value(value)

    def set_value(self, value):
        # record under the value lock: a preempted writer must not emit a
        # stale sample with a later timestamp (lock order _vlock→_lock only)
        with self._vlock:
            self._value = value
            _record(self.name, (time.perf_counter() - _epoch) * 1e6,
                    cat=self._cat, ph="C", value=value)

    def _add(self, delta):
        with self._vlock:
            self._value += delta
            _record(self.name, (time.perf_counter() - _epoch) * 1e6,
                    cat=self._cat, ph="C", value=self._value)

    def increment(self, delta=1):
        self._add(delta)

    def decrement(self, delta=1):
        self._add(-delta)

    def __iadd__(self, delta):
        self.increment(delta)
        return self

    def __isub__(self, delta):
        self.decrement(delta)
        return self


class Marker:
    """Instant event (ref: profiler.cc ProfileMarker)."""

    def __init__(self, domain=None, name="marker"):
        self.name = name
        self._cat = domain.name if isinstance(domain, Domain) else "host"

    def mark(self, scope="process"):
        _record(self.name, (time.perf_counter() - _epoch) * 1e6,
                cat=self._cat, ph="i",
                s={"process": "p", "thread": "t"}.get(scope, "g"))


# MXNET_PROFILER_AUTOSTART parity: begin tracing at import when requested
# (truthy values only — 'false'/'off'/'no' mean off, like upstream's int check).
if os.environ.get("MXNET_PROFILER_AUTOSTART", "0").lower() in ("1", "true", "yes", "on"):
    _config["profile_all"] = True
    start()


def device_memory_summary(device=None):
    """Live per-device memory stats (ref: MXNET_PROFILER memory counters /
    src/profiler/storage_profiler.h — there a storage-allocator hook; here
    the XLA client's own accounting, which is authoritative on TPU since
    jax owns the HBM pool).

    Returns {"bytes_in_use", "peak_bytes_in_use", "bytes_limit", ...} —
    whatever the backend reports (CPU backends may return {}).
    """
    import jax

    dev = device or jax.devices()[0]
    stats = getattr(dev, "memory_stats", lambda: None)()
    return dict(stats) if stats else {}


def dump_memory(path=None, device=None):
    """Return the device memory summary dict; with ``path``, also write it
    as JSON — the quick 'how much HBM is this model using' answer during
    bench/batch sweeps."""
    stats = device_memory_summary(device)
    if path:
        import json as _json

        with open(path, "w") as f:
            f.write(_json.dumps(stats, indent=1, sort_keys=True,
                                default=int) + "\n")
    return stats
