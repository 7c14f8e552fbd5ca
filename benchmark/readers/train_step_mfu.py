"""The whole training step's share of the chips' peak, in percent: the
FLOPs the mathematics needs (the configuration's own, ``counts/<module>.py``)
for the samples of the whole steps that ran inside the traced slice, over
the time from the first of them starting to the last ending (gaps between
steps included), over chips x peak."""
from lib import peaks


def read(record, params):
    trace = record.get("trace")
    if trace is None:
        return None
    runs = trace.module_runs(params["program"])
    if len(runs) < 2:
        return None
    elapsed = max(s + d for s, d in runs) - min(s for s, _d in runs)
    work = record["counts"].train_flops(
        record["sizes"], record["traffic"],
        len(runs) * record["traffic"]["batch"])
    return 100.0 * work / (elapsed * record["chips"] * peaks.peak(
        record["device_kind"], "bf16_flops"))
