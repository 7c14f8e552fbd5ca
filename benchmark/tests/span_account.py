"""The traced account of a serving cell's idle time, for PERF.md section 5.

    python benchmark/tests/span_account.py <out.json> <run.py arguments, with --trace 1>

Runs ``benchmark/run.py`` once in this process and, from the very trace its
readers read, writes what the result line has no room for: the device's idle
seconds by the kind of the program's span that covers each gap (all kinds,
not the ten longest), every kind's count and mean length, what a tick and a
join spend outside the spans they are parents of, the device time of each
named program, the names of all the server's programs, and how long the
profiler took to stop. (The spans' own metrics are in the result line.)
For the builder's sessions on the chip; the driver's check never calls it.
"""
import json
import os
import re
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run                                   # noqa: E402
from lib import trace_reduce                 # noqa: E402

# a parent kind and the kinds of its own children (profiler.decode_scope)
PARENTS = {"decode[tick": re.compile(
               r"^decode\[(join|chunk|ctl|step|verify|deliver)"),
           "decode[join": re.compile(r"^decode\[(prefill|readout)")}


def account(trace):
    """The numbers above that one :class:`trace_reduce.Trace` holds."""
    whole = [(trace_reduce.span_kind(n), s, d) for n, s, d in trace.spans
             if n.startswith("decode[") and s >= 0.0
             and s + d <= trace.window_s]
    kinds = {}
    for k, _s, d in whole:
        n, total = kinds.get(k, (0, 0.0))
        kinds[k] = (n + 1, total + d)
    parents = {}
    for k, s, d in whole:
        child = next((rx for p, rx in PARENTS.items() if k.startswith(p)),
                     None)
        if child is None:
            continue
        inside = sum(dd for kk, ss, dd in whole
                     if child.match(kk) and s <= ss and ss + dd <= s + d)
        n, total, self_s = parents.get(k, (0, 0.0, 0.0))
        parents[k] = (n + 1, total + d, self_s + d - inside)
    first = trace.modules[min(trace.modules)] if trace.modules else []
    programs = {}
    for name, s, d in first:
        if s >= 0.0 and s + d <= trace.window_s:
            name = re.sub(r"\(\d+\)$", "", name)
            n, total = programs.get(name, (0, 0.0))
            programs[name] = (n + 1, total + d)
    mean = lambda n, total: {"n": n, "mean_ms": 1e3 * total / n,
                             "total_s": total}
    return {
        "window_s": trace.window_s, "busy_s": trace.busy_s,
        "idle_s_by_span_kind": trace.breakdown(top=10 ** 6)["idle_gaps"],
        "span_kinds": {k: mean(*v) for k, v in sorted(kinds.items())},
        "parents": {k: dict(mean(n, total), self_mean_ms=1e3 * self_s / n,
                            self_share=self_s / total)
                    for k, (n, total, self_s) in sorted(parents.items())},
        "programs_in_slice": {k: mean(*v)
                              for k, v in sorted(programs.items())},
    }


def program_names():
    """The module name of every program of every live generative server."""
    from mxnet_tpu import serve

    names = []
    for srv in list(serve._SERVERS):
        for ent in getattr(srv, "export_executables", lambda: [])():
            mods = ent["compiled"].runtime_executable().hlo_modules()
            names.append(mods[0].name)
    return sorted(names)


def main(argv):
    out_path, args = argv[0], argv[1:]
    from mxnet_tpu import profiler

    found = {}
    reduce_dir, stop = trace_reduce.reduce_dir, profiler.stop

    def reduce_and_account(trace_dir):
        trace = reduce_dir(trace_dir)
        found.update(account(trace))
        found["program_names"] = program_names()
        return trace

    def timed_stop(*a, **k):
        t0 = time.perf_counter()
        try:
            return stop(*a, **k)
        finally:
            found["profiler_stop_s"] = time.perf_counter() - t0

    trace_reduce.reduce_dir, profiler.stop = reduce_and_account, timed_stop
    try:
        rc = run.main(args)
    finally:
        trace_reduce.reduce_dir, profiler.stop = reduce_dir, stop
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(found, f, indent=1)
    print("span_account: %s" % json.dumps(found), file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
