"""Mean, in milliseconds, of what a span holds besides some of its children.

For each span whose kind matches ``span`` and that lies whole inside the
traced slice, holds at least one span whose kind matches ``less`` and none
whose kind matches ``skip``: its length less the summed length of the
``less`` spans that lie inside it (a ``decode[tick]`` less its
``decode[wait]``s is the host's own part of the tick). A kind is a span's
name without its fields (``decode[wait]`` of ``decode[wait fill=0.41
b32]``). Nothing where no span qualifies, as on a program that writes no
``less`` span."""
import bisect
import re

from lib import trace_reduce

# nested spans of one thread may share an end to the nanosecond, which the
# slice's float seconds can round either way
SLACK_S = 1e-9


def _inside(evs, starts, s, e):
    """Lengths of the (start, length) pairs of ``evs`` (sorted, their starts
    ``starts``) that lie inside [s, e]."""
    out = []
    for i in range(bisect.bisect_left(starts, s - SLACK_S), len(evs)):
        cs, cd = evs[i]
        if cs > e:
            break
        if cs + cd <= e + SLACK_S:
            out.append(cd)
    return out


def read(record, params):
    trace = record.get("trace")
    if trace is None:
        return None
    kinds = [(trace_reduce.span_kind(n), s, d) for n, s, d in trace.spans]
    picked = {}
    for key in ("less", "skip"):
        rx = re.compile(params[key]) if params.get(key) else None
        evs = sorted((s, d) for k, s, d in kinds if rx and rx.search(k))
        picked[key] = (evs, [s for s, _d in evs])
    rx = re.compile(params["span"])
    own = []
    for k, s, d in kinds:
        if not rx.search(k) or s < 0.0 or s + d > trace.window_s:
            continue
        if _inside(*picked["skip"], s, s + d):
            continue
        less = _inside(*picked["less"], s, s + d)
        if less:
            own.append(d - sum(less))
    return 1e3 * sum(own) / len(own) if own else None
