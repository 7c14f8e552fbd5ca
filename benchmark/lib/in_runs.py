"""Device time of a kernel inside the runs of one program, and nowhere else.

``Trace.op_time_within`` takes every event between the first run's start and
the last run's end: right for a kernel that only that program calls, wrong
for one that other programs call between the runs (the expert FFN runs in
every decode step and in every prefill between two steps)."""
import bisect
import re


def op_time_in_runs(trace, pattern, runs):
    """Seconds (a device's mean) of the device ops whose name matches
    ``pattern`` and that start inside one of ``runs``, each run being its own
    ``[start, start + duration)`` as ``Trace.module_runs`` gives them."""
    runs = sorted(runs)
    starts = [s for s, _d in runs]
    rx = re.compile(pattern)

    def inside(s):
        i = bisect.bisect_right(starts, s) - 1
        return i >= 0 and s < runs[i][0] + runs[i][1]

    per = [sum(d for n, s, d in evs if rx.search(n) and inside(s))
           for evs in trace.ops.values()]
    return sum(per) / len(per)
