"""A kernel's share of its memory roofline where the bytes depend on what
the tokens of a step touch, in percent: the bytes the runs of ``program``
inside the traced slice need over the chip's bandwidth, over the device time
of the events that carry the kernel's name inside those runs, each run taken
by itself (other programs call the kernel between them). The program's step
spans say what a step touched in the field named by ``hits`` (``xhit=38``:
how many experts' matrices it had to read; the clients cannot see a router's
choice); the tokens are counted on the client's side (the tokens received in
the slice that a decode step produced). The bytes are
``counts.<touched_bytes>(sizes, hits in all, tokens in all)``, the spans' sum
brought to the number of runs. Silent where the kernel is not on the cell's
path or no span carries the field."""
import re

from lib import in_runs, peaks


def read(record, params):
    trace = record.get("trace")
    if trace is None:
        return None
    runs = trace.module_runs(params["program"])
    if not runs or not trace.ops:
        return None
    spent = in_runs.op_time_in_runs(trace, params["kernel"], runs)
    rx = re.compile(r"\b%s=(\d+)" % re.escape(params["hits"]))
    hits = [int(m.group(1)) for n, _s, _d in
            trace.spans_named(params["span"]) for m in [rx.search(n)] if m]
    if spent <= 0 or not hits:
        return None
    need = getattr(record["counts"], params["touched_bytes"])(
        record["sizes"], sum(hits) * len(runs) / len(hits),
        len(record["samples"]["slice_decode_context"]))
    return 100.0 * need / record["chips"] / peaks.peak(
        record["device_kind"], "hbm_bytes_per_s") / spent
