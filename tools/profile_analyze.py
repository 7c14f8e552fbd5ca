"""Summarize a jax.profiler trace captured by bench.py (BENCH_PROFILE_DIR).

Parses the Chrome-trace json (``*.trace.json.gz`` under
``<dir>/<mode>/plugins/profile/...``) and emits, per device lane:

- total busy time vs wall span (device utilization of the captured window)
- the top-K ops by cumulative self duration (the concrete "attack this
  sink next" list the MFU hunt needs)
- collective ops split out (all-reduce / all-gather / ...): on a multi-chip
  run their busy time vs the lane's compute busy time bounds the dp
  all-reduce OVERLAP the scaling model assumes (tools/scaling_model.py) —
  the measured-overlap input the scaling model asks for once multi-chip
  hardware exists.

Usage: python tools/profile_analyze.py /tmp/profile_r5/bert [--top 15]
                                       [--json out.json]
Works on any backend's trace (the CPU smoke path produces host lanes).
"""
from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import re
import sys

_COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")


def load_trace(root):
    paths = sorted(glob.glob(
        os.path.join(root, "**", "*.trace.json.gz"), recursive=True))
    if not paths:
        raise FileNotFoundError("no *.trace.json.gz under %s" % root)
    with gzip.open(paths[-1]) as f:  # latest capture
        return json.loads(f.read()), paths[-1]


def summarize(trace, top=15):
    events = trace.get("traceEvents", [])
    # thread lanes: metadata events name them; complete events carry dur
    lane_names = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "thread_name":
            lane_names[(e.get("pid"), e.get("tid"))] = \
                e.get("args", {}).get("name", "")
    lanes = {}
    for e in events:
        if e.get("ph") != "X" or not e.get("dur"):
            continue
        key = (e.get("pid"), e.get("tid"))
        lane = lanes.setdefault(key, {
            "lane": lane_names.get(key, str(key)),
            "intervals": [], "ops": {}, "collective_us": 0.0})
        dur = float(e["dur"])
        ts = float(e.get("ts", 0.0))
        lane["intervals"].append((ts, ts + dur))
        name = e.get("name", "?")
        lane["ops"][name] = lane["ops"].get(name, 0.0) + dur
        if _COLLECTIVE.search(name):
            lane["collective_us"] += dur
    out = []
    for lane in lanes.values():
        # busy = UNION of event intervals: Chrome traces nest events on a
        # thread, so summing durations double-counts parents over children
        ivs = sorted(lane["intervals"])
        busy = 0.0
        cur_a, cur_b = ivs[0]
        for a, b in ivs[1:]:
            if a > cur_b:
                busy += cur_b - cur_a
                cur_a, cur_b = a, b
            else:
                cur_b = max(cur_b, b)
        busy += cur_b - cur_a
        span = max(ivs[-1][1] - ivs[0][0],
                   max(b for _, b in ivs) - ivs[0][0], 1e-9)
        top_ops = sorted(lane["ops"].items(), key=lambda kv: -kv[1])[:top]
        out.append({
            "lane": lane["lane"],
            "busy_ms": round(busy / 1e3, 3),
            "span_ms": round(span / 1e3, 3),
            "utilization": round(busy / span, 4),
            "collective_ms": round(lane["collective_us"] / 1e3, 3),
            # op times are INCLUSIVE (parent spans include children) —
            # exact for XLA device lanes, which are flat
            "top_ops": [{"name": n, "total_ms": round(d / 1e3, 3)}
                        for n, d in top_ops],
        })
    out.sort(key=lambda r: -r["busy_ms"])
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("trace_dir")
    ap.add_argument("--top", type=int, default=15)
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)
    trace, path = load_trace(args.trace_dir)
    lanes = summarize(trace, top=args.top)
    rec = {"trace": path, "lanes": lanes}
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rec, f, indent=1)
        print("wrote %s" % args.json)
    for lane in lanes[:4]:
        print("%-40s busy %8.1fms / span %8.1fms (util %.0f%%, "
              "collectives %.1fms)"
              % (lane["lane"][:40], lane["busy_ms"], lane["span_ms"],
                 lane["utilization"] * 100, lane["collective_ms"]))
        for op in lane["top_ops"][:5]:
            print("    %9.2fms  %s" % (op["total_ms"], op["name"][:70]))
    return rec


if __name__ == "__main__":
    main()
