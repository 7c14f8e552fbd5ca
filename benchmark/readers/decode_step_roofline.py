"""The decode step's share of its memory roofline, in percent: the bytes the
steps of the traced slice need (the weights a step over its live tokens has
to read, and the K and V that each token decoded has to read at its own
context, not the capacity's) over the chip's bandwidth, over the device time
of the runs of ``program``. The live tokens are counted on the client's
side, one context per token received in the slice; the bytes are the
configuration's own (``counts/<module>.py``)."""
from lib import peaks


def read(record, params):
    trace = record.get("trace")
    if trace is None:
        return None
    runs = trace.module_runs(params["program"])
    if not runs:
        return None
    counts, sizes = record["counts"], record["sizes"]
    contexts = record["samples"]["slice_decode_context"]
    need = len(runs) * counts.decode_weight_bytes(
        sizes, len(contexts) / len(runs)) \
        + sum(counts.kv_bytes(sizes, c) for c in contexts)
    least = need / peaks.peak(record["device_kind"], "hbm_bytes_per_s")
    return 100.0 * least / sum(d for _s, d in runs)
