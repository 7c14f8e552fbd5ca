"""Mean of a numeric ``<field>=<number>`` of the names of the program's
spans inside the traced slice (``decode[step fill=0.41 b32 xmax=2.50]``,
``field`` ``xmax``), as it stands. Silent where no span carries the
field."""
import re


def read(record, params):
    trace = record.get("trace")
    if trace is None:
        return None
    rx = re.compile(r"\b%s=([0-9.]+)" % re.escape(params["field"]))
    xs = [float(m.group(1)) for n, _s, _d in
          trace.spans_named(params["span"]) for m in [rx.search(n)] if m]
    return sum(xs) / len(xs) if xs else None
