"""A kernel's share of the step's device time, in percent: the device time
of the events that carry the kernel's name inside the whole steps of the
traced slice, over the device time of those steps (a device's mean where
there are several). Silent where the kernel is not on the cell's path."""


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
    return 100.0 * spent / sum(d for _s, d in runs)
