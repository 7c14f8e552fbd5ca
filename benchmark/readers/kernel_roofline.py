"""A kernel's share of its memory roofline, in percent: the bytes the
algorithm has to move through HBM in the traced slice over the chip's
bandwidth, over the device time of the events that carry the kernel's name
inside the runs of ``program`` (a device's mean where there are several).
The bytes are the function named by ``bytes`` of the configuration's
``counts/<module>.py``, from the sizes and the traffic: once a run of the
program, or, where the metric's file names under ``each`` a list of the
record's ``samples``, once an entry of it (the tokens decoded in the slice:
work that follows the traffic, not the program's shapes).
Only for a kernel whose operands live in HBM: where the compiler keeps them
in VMEM, HBM bytes are no lower bound of its time (PERF.md, Findings).
Silent where the kernel is not on the cell's path."""
from lib import peaks


def read(record, params):
    trace = record.get("trace")
    if trace is None:
        return None
    runs = trace.module_runs(params["program"])
    if not runs or not trace.ops:
        return None
    spent = trace.op_time_within(params["kernel"], runs)
    if spent <= 0:
        return None
    times = len(record["samples"].get(params["each"], ())) \
        if "each" in params else len(runs)
    if not times:
        return None
    need = times * getattr(record["counts"], params["bytes"])(
        record["sizes"], record["traffic"]) / record["chips"]
    return 100.0 * need / peaks.peak(record["device_kind"],
                                     "hbm_bytes_per_s") / spent
