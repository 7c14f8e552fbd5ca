"""The serving programs' share of the chip's peak over the traced slice, in
percent: forward FLOPs (the configuration's own, ``counts/<module>.py``) of
every prompt prefilled and every token decoded whose token the clients
received inside the slice, each at its own context length, over slice
seconds x peak."""
from lib import peaks


def read(record, params):
    trace = record.get("trace")
    if trace is None:
        return None
    flops, sizes = record["counts"].forward_flops, record["sizes"]
    work = sum(flops(sizes, 0, n, heads=1)
               for n in record["samples"]["slice_prefill_len"])
    work += sum(flops(sizes, c - 1, 1, heads=1)
                for c in record["samples"]["slice_decode_context"])
    if work <= 0:
        return None
    return 100.0 * work / (trace.window_s * peaks.peak(
        record["device_kind"], "bf16_flops"))
