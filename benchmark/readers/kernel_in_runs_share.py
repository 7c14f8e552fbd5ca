"""A kernel's share of one program's device time, in percent: the device
time of the events that carry the kernel's name inside the whole runs of
``program`` in the traced slice, each run taken by itself, over the device
time of those runs. For a kernel that other programs call too
(``kernel_step_share`` would count their calls between two runs). Silent
where the kernel is not on the cell's path."""
from lib import in_runs


def read(record, params):
    trace = record.get("trace")
    if trace is None:
        return None
    runs = trace.module_runs(params["program"])
    if not runs or not trace.ops:
        return None
    spent = in_runs.op_time_in_runs(trace, params["kernel"], runs)
    if spent <= 0:
        return None
    return 100.0 * spent / sum(d for _s, d in runs)
