"""A kernel's share of its memory roofline, in percent: the bytes the
algorithm has to move through HBM for the whole steps of the traced slice
(``lib/flops.py``'s function named by ``bytes_per_step``, from shapes) over
the chip's bandwidth, over the device time of the events that carry the
kernel's name inside those steps (a device's mean where there are several).
Only for a kernel whose operands live in HBM: where the compiler keeps them
in VMEM, HBM bytes are no lower bound of its time (PERF.md, Findings).
Silent where the kernel is not on the cell's path."""
import re

from lib import flops, peaks


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
    need = len(runs) * getattr(flops, params["bytes_per_step"])(
        record["sizes"], record["traffic"]) / record["chips"]
    return 100.0 * need / peaks.peak(record["device_kind"],
                                     "hbm_bytes_per_s") / spent
