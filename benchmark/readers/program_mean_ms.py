"""Mean device time, in milliseconds, of the whole runs inside the traced
slice of the program whose name on the device's "XLA Modules" line matches
``program``, on the first device: found by its name, with no vote. Nothing
where no program carries such a name (a trace of a program whose serving
programs are all ``jit_pure``, or a CPU trace, which has no such line)."""


def read(record, params):
    trace = record.get("trace")
    if trace is None:
        return None
    runs = trace.module_runs(params["program"])
    return 1e3 * sum(d for _s, d in runs) / len(runs) if runs else None
