"""Mean duration, in milliseconds, of the program's spans whose name matches
``span`` and that lie whole inside the traced slice (one that straddles an
edge of the slice is left out: its length there is not its length)."""


def read(record, params):
    trace = record.get("trace")
    if trace is None:
        return None
    whole = [d for _n, s, d in trace.spans_named(params["span"])
             if s >= 0.0 and s + d <= trace.window_s]
    return 1e3 * sum(whole) / len(whole) if whole else None
