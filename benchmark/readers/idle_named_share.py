"""Of the first device's idle time in the traced slice (the same gaps as
``Trace.breakdown``), the share, in percent, whose midpoint lies under one of
the program's own spans (``span``, ``decode[...]``): the rest is idle time
that no span of the program accounts for. The benchmark's ``bench[...]``
spans and no span at all count as unnamed. Nothing where the device was
never idle or the trace holds no device operation."""
import bisect

from lib import trace_reduce


def read(record, params):
    trace = record.get("trace")
    if trace is None or not trace.ops:
        return None
    evs = trace.ops[min(trace.ops)]
    idle = trace_reduce.gaps([(s, s + d) for _n, s, d in evs], 0.0,
                             trace.window_s)
    total = sum(e - s for s, e in idle)
    if total <= 0:
        return None
    # the spans nest and repeat: merged into disjoint stretches, one look-up
    # a gap
    covered = []
    for s, e in sorted((s, s + d) for _n, s, d in
                       trace.spans_named(params["span"])):
        if covered and s <= covered[-1][1]:
            covered[-1][1] = max(covered[-1][1], e)
        else:
            covered.append([s, e])
    starts = [s for s, _e in covered]
    named = 0.0
    for s, e in idle:
        mid = 0.5 * (s + e)
        k = bisect.bisect_right(starts, mid) - 1
        if k >= 0 and mid <= covered[k][1]:
            named += e - s
    return 100.0 * named / total
