"""The decode step's share of its memory roofline, in percent: the bytes the
steps of the traced slice need (the weights once a step, and the K and V of
the tokens that were live, not of the capacity) over the chip's bandwidth,
over the device time of the decode program's runs. The runs are those the
program's ``decode[step ...]`` spans launched; the live tokens are counted on
the client's side, one context per token received in the slice."""
from lib import flops, peaks


def read(record, params):
    trace = record.get("trace")
    if trace is None:
        return None
    runs = trace.runs_launched_by(params["span"], params["program"])
    if not runs:
        return None
    cfg = record["sizes"]
    need = len(runs) * flops.gpt_param_bytes(cfg) + \
        sum(record["samples"]["slice_decode_context"]) \
        * flops.gpt_kv_bytes_per_token(cfg)
    least = need / peaks.peak(record["device_kind"], "hbm_bytes_per_s")
    return 100.0 * least / sum(d for _s, d in runs)
