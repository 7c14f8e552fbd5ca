"""Mean of the ``fill=`` field (active slots over slots) of the program's
``decode[step fill=0.75 b32]`` spans inside the traced slice, in percent."""
import re


def read(record, params):
    trace = record.get("trace")
    if trace is None:
        return None
    fills = [float(m.group(1)) for n, _s, _d in
             trace.spans_named(params["span"])
             for m in [re.search(r"fill=([0-9.]+)", n)] if m]
    return 100.0 * sum(fills) / len(fills) if fills else None
