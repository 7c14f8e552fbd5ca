"""Reduction of a profiler trace (``.xplane.pb``) to what the readers read.

Reads the file with ``jax.profiler.ProfileData`` and nothing else. The
arithmetic (busy = union of the device-op intervals, as in
``tools/profile_analyze.py``; kernel time = sum of the durations of the events
with that name; an idle gap is named by the host span that covers it) is kept
here, under the benchmark's own directory, and checked on the small recorded
trace in ``benchmark/tests``.

The traced slice is what lies between the two marker spans the drivers write
(``MARK_START``, ``MARK_END``); all times are seconds on the trace's clock,
relative to the start marker.
"""
import glob
import os
import re

MARK_START = "bench[slice_start]"
MARK_END = "bench[slice_end]"
SPAN = re.compile(r"^(decode|bench)\[")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# a control-flow op spans its body's ops: its own time is theirs
CONTAINERS = re.compile(r"^(while|conditional|call)([.\d]*)$")


def union_length(intervals):
    """Total length covered by ``intervals`` (pairs of start, end)."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo, hi):
    """The idle stretches of [lo, hi] that ``intervals`` leave uncovered."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def span_kind(name):
    """``decode[step fill=0.75 b32]`` -> ``decode[step]``."""
    m = re.match(r"^(\w+)\[([^\s\]]+)", name)
    return "%s[%s]" % (m.group(1), m.group(2)) if m else name


class Trace:
    """Events of one trace, clipped to the slice between the markers.

    ``ops[d]`` / ``modules[d]``: lists of (name, start_s, duration_s) on
    device ``d``; ``spans``: host spans (name, start_s, duration_s) whose
    name matches ``decode[...]`` or ``bench[...]``."""

    def __init__(self, ops, modules, spans, lo, hi):
        self.lo, self.hi = lo, hi
        clip = lambda evs: [(n, s - lo, d) for n, s, d in evs
                            if s + d > lo and s < hi]
        self.ops = {k: clip(v) for k, v in ops.items()}
        self.modules = {k: clip(v) for k, v in modules.items()}
        self.spans = clip(spans)
        self.window_s = hi - lo
        busy = [union_length([(max(s, 0.0), min(s + d, self.window_s))
                              for _n, s, d in evs])
                for evs in self.ops.values()]
        self.busy_s = sum(busy) / len(busy) if busy else 0.0

    # ---------------------------------------------------------- queries
    def idle_share(self):
        return 1.0 - self.busy_s / self.window_s

    def op_time(self, pattern):
        """(seconds, calls) of the device ops whose name matches
        ``pattern``, a device's mean."""
        rx = re.compile(pattern)
        per = [[d for n, _s, d in evs if rx.search(n)]
               for evs in self.ops.values()]
        if not per or not any(per):
            return 0.0, 0
        return (sum(sum(x) for x in per) / len(per),
                sum(len(x) for x in per) // len(per))

    def op_time_within(self, pattern, runs):
        """Seconds (a device's mean) of the device ops whose name matches
        ``pattern`` and that start between the first of ``runs`` starting
        and the last of them ending (``runs`` as :meth:`module_runs` gives
        them)."""
        lo, hi = min(s for s, _d in runs), max(s + d for s, d in runs)
        rx = re.compile(pattern)
        per = [sum(d for n, s, d in evs if rx.search(n) and lo <= s < hi)
               for evs in self.ops.values()]
        return sum(per) / len(per)

    def module_runs(self, pattern):
        """(start_s, duration_s) of every whole run inside the slice of the
        programs whose name matches ``pattern``, on the first device."""
        rx = re.compile(pattern)
        first = self.modules[min(self.modules)] if self.modules else []
        return [(s, d) for n, s, d in first
                if rx.search(n) and s >= 0.0 and s + d <= self.window_s]

    def spans_named(self, pattern):
        rx = re.compile(pattern)
        return [(n, s, d) for n, s, d in self.spans if rx.search(n)]

    def breakdown(self, top=10):
        """The kinds of device op that took most time and the idle time by
        what the host was doing, on the first device."""
        if not self.ops:
            return {"device_ops": [], "idle_gaps": []}
        evs = self.ops[min(self.ops)]
        by_op = {}
        for n, _s, d in evs:
            if not CONTAINERS.match(n):
                # fusion.5988 and its 23 twins of the other layers count as
                # one kind of operation: the name without its number
                kind = re.sub(r"[.\d]+$", "", n)
                by_op[kind] = by_op.get(kind, 0.0) + d
        by_gap = {}
        for s, e in gaps([(s, s + d) for _n, s, d in evs], 0.0,
                         self.window_s):
            mid = 0.5 * (s + e)
            cover = [n for n, ss, dd in self.spans
                     if ss <= mid <= ss + dd and not n.startswith("bench[slice")]
            name = span_kind(cover[-1]) if cover else "between spans"
            by_gap[name] = by_gap.get(name, 0.0) + (e - s)
        rank = lambda d: [[k, v] for k, v in sorted(
            d.items(), key=lambda kv: -kv[1])[:top]]
        return {"device_ops": rank(by_op), "idle_gaps": rank(by_gap)}


def _stat(ev, key):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def load(path):
    """(ops, modules, spans, host_ops) of one ``.xplane.pb``: device events
    by device, the host's ``decode[...]`` / ``bench[...]`` spans, and the XLA
    ops that ran on host threads (a CPU backend has only those)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, modules, spans, host_ops = {}, {}, [], []
    # a device op's name is its HLO line, "%fusion.3 = bf16[...] fusion(...)":
    # the short name before the " = " is what the readers match
    as_s = lambda e: (e.name.split(" = ")[0].lstrip("%"), e.start_ns * 1e-9,
                      e.duration_ns * 1e-9)
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[int(m.group(1))] = [as_s(e) for e in line.events]
                elif line.name == "XLA Modules":
                    modules[int(m.group(1))] = [as_s(e) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if SPAN.match(e.name):
                        spans.append(as_s(e))
                    elif e.duration_ns > 0 and \
                            _stat(e, "hlo_module") is not None:
                        host_ops.append(as_s(e))
    return ops, modules, spans, host_ops


def reduce_file(path):
    """The :class:`Trace` of one ``.xplane.pb``, cut to the marked slice (to
    the extent of the device events where no marker was written)."""
    ops, modules, spans, host_ops = load(path)
    if not ops and host_ops:
        # a CPU trace (the rehearsal, the tests) has no device plane: the
        # XLA ops on the host's threads stand in, so the same code is walked
        ops, modules = {0: host_ops}, {0: []}
    spans.sort(key=lambda x: x[1])
    starts = [s for n, s, _d in spans if n == MARK_START]
    ends = [s for n, s, _d in spans if n == MARK_END]
    if not any(ops.values()):
        raise SystemExit("benchmark: the trace %s holds no device operation"
                         % path)
    every = [(s, s + d) for evs in list(ops.values()) + list(modules.values())
             for _n, s, d in evs]
    lo = starts[0] if starts else min(s for s, _e in every)
    hi = ends[-1] if ends else max(e for _s, e in every)
    return Trace(ops, modules, spans, lo, hi)


def start(trace_dir):
    """Starts jax's profiler into ``trace_dir`` with the Python tracer off
    (it slows the host and swells the file) and no HLO dump."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def reduce_dir(trace_dir):
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise SystemExit("benchmark: the profiler wrote no .xplane.pb under "
                         "%s" % trace_dir)
    return reduce_file(found[-1])
