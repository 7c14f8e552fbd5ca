"""A kernel's share of the step's device time, in percent: the device time
of the events that carry the kernel's name inside the whole steps of the
traced slice, over the device time of those steps (a device's mean where
there are several). Silent where the kernel is not on the cell's path."""
import re


def read(record, params):
    trace = record.get("trace")
    if trace is None:
        return None
    runs = trace.module_runs(params["program"])
    if not runs or not trace.ops:
        return None
    lo, hi = min(s for s, _d in runs), max(s + d for s, d in runs)
    rx = re.compile(params["kernel"])
    per_device = [sum(d for n, s, d in evs if rx.search(n) and lo <= s < hi)
                  for evs in trace.ops.values()]
    spent = sum(per_device) / len(per_device)
    if spent <= 0:
        return None
    return 100.0 * spent / sum(d for _s, d in runs)
