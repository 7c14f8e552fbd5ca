#!/usr/bin/env python
"""Join a captured TPU profile with the optimized HLO text: name the sinks.

A raw xplane/trace says "fusion.2248 took 2.1 ms" — useless without knowing
what fusion.2248 computes. The optimized HLO text (saved by
`tools/roofline.py --backend tpu --save-hlo DIR`, compiled by the SAME jax
version for the same step) carries the definition: opcode, output shape,
fusion kind, and the called computation's instruction mix. This tool joins
the two and rolls the per-op times up into categories (matmul/conv fusions
vs elementwise vs reduce vs copy ...), producing the ranked, NAMED target
list for MFU work.

Usage:
  python tools/profile_hlo_map.py --trace /tmp/profile_r5/bert \
      --hlo tools/hlo_tpu_bert.txt [--top 20] [--json out.json]

No jax import — pure parsing; needs no chip.
"""
from __future__ import annotations

import argparse
import collections
import glob
import gzip
import json
import os
import re
import sys

_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+)\s+=\s+")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_CALLS_RE = re.compile(r"calls=(%[\w.\-]+)")
_KIND_RE = re.compile(r"kind=(\w+)")
# jax.named_scope provenance: optimized HLO carries
# `metadata={op_name="jit(f)/jit(main)/<scopes>/<primitive>" ...}` —
# the scopes are OUR op/block names (ir/graph.py build_runner, _trace.F)
_META_RE = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')


def _line_opcode(line):
    """`%n = f32[2,3]{1,0} fusion(...), kind=kLoop` -> "fusion"."""
    after = line.split(" = ", 1)[1]
    depth, i = 0, 0
    while i < len(after):
        c = after[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
        elif c == " " and depth == 0:
            break
        i += 1
    return after[i:].strip().split("(", 1)[0].strip()


def parse_hlo(text):
    """name -> {opcode, shape, kind, calls}; computation -> opcode histogram."""
    instrs = {}
    comp_ops = collections.defaultdict(collections.Counter)
    comp = None
    for line in text.splitlines():
        stripped = line.strip()
        if stripped.endswith("{") and "(" in stripped:
            head = stripped.split("(", 1)[0].strip()
            comp = head.split()[-1]  # `%fused_computation.3` / `ENTRY %main`
            continue
        if stripped == "}":
            comp = None
            continue
        m = _INSTR_RE.match(line)
        if not m or " = " not in line:
            continue
        name = m.group(1)
        try:
            op = _line_opcode(line)
        except IndexError:
            continue
        if not op:
            continue
        if comp is not None:
            comp_ops[comp][op] += 1
        shape_m = _SHAPE_RE.search(line.split(" = ", 1)[1])
        rec = {"opcode": op,
               "shape": ("%s[%s]" % shape_m.groups()) if shape_m else ""}
        km = _KIND_RE.search(line)
        if km:
            rec["kind"] = km.group(1)
        cm = _CALLS_RE.search(line)
        if cm:
            rec["calls"] = cm.group(1)
        mm = _META_RE.search(line)
        if mm and mm.group(1):
            rec["op_name"] = _clean_op_name(mm.group(1))
        instrs[name.lstrip("%")] = rec
    return instrs, comp_ops


def _clean_op_name(op_name):
    """Drop the jit(...) wrapper components: the residual path is the
    named_scope provenance (block/op names) ending in the primitive."""
    parts = [p for p in op_name.split("/")
             if p and not (p.startswith("jit(") and p.endswith(")"))]
    return "/".join(parts)


def provenance_scope(op_name):
    """The human scope of a cleaned op_name: everything but the trailing
    jax primitive ('dense0/FullyConnected/dot_general' -> scope
    'dense0/FullyConnected')."""
    parts = op_name.split("/")
    return "/".join(parts[:-1]) if len(parts) > 1 else parts[0]


# primitive-name rules for op_name-based categorization (first match
# wins); these are jax primitive names, not HLO opcodes
_PRIM_RULES = (
    (("dot", "conv"), "matmul/conv"),
    (("scatter",), "scatter"),
    (("reduce", "argmax", "argmin", "cumsum", "sort", "top_k"),
     "reduce/stats"),
    (("psum", "all_gather", "all_to_all", "ppermute", "reduce_scatter",
      "collective"), "collective"),
    (("random", "rng", "threefry"), "rng"),
    (("transpose", "copy", "broadcast", "reshape", "concatenate", "pad",
      "slice", "gather", "rev", "squeeze", "bitcast", "convert"),
     "copy/layout"),
)


def _categorize_primitive(prim):
    for keys, cat in _PRIM_RULES:
        if any(k in prim for k in keys):
            return cat
    return None


def parse_trace_ops(trace_path):
    """The 'XLA Ops' lane of a Chrome trace: op name -> total ms."""
    if os.path.isdir(trace_path):
        hits = sorted(glob.glob(os.path.join(
            trace_path, "**", "*.trace.json.gz"), recursive=True))
        if not hits:
            raise FileNotFoundError("no *.trace.json.gz under %s" % trace_path)
        trace_path = hits[-1]
    opener = gzip.open if trace_path.endswith(".gz") else open
    with opener(trace_path, "rt") as f:
        tr = json.load(f)
    names = {}
    for e in tr["traceEvents"]:
        if e.get("ph") == "M" and e.get("name") == "thread_name":
            names[(e["pid"], e["tid"])] = e["args"]["name"]
    times = collections.defaultdict(float)
    for e in tr["traceEvents"]:
        if e.get("ph") != "X":
            continue
        if "XLA Ops" not in str(names.get((e.get("pid"), e.get("tid")), "")):
            continue
        times[e["name"]] += e.get("dur", 0) / 1000.0
    return dict(times)


# category rules, first match wins; fusions are classified by their called
# computation's instruction mix (a "fusion" wrapping a dot IS the matmul).
# When the instruction carries named_scope provenance (metadata op_name=),
# the jax primitive name is preferred — it survives fusion better than the
# HLO opcode — EXCEPT when the opcode/inner-mix evidence names a stronger
# category (the fusion root's metadata can be a weak broadcast while the
# fusion body holds the dot). Old saved HLO without metadata takes the
# opcode-only path unchanged.
def categorize(rec, inner):
    base = _categorize_opcode(rec, inner)
    opn = rec.get("op_name", "")
    if not opn:
        return base
    named = _categorize_primitive(opn.rsplit("/", 1)[-1])
    if named in (None, "copy/layout") and base in (
            "matmul/conv", "scatter", "reduce/stats", "collective",
            "custom-call (pallas kernel)"):
        return base
    return named or "elementwise/other"


def _categorize_opcode(rec, inner):
    op = rec.get("opcode", "")
    if op in ("custom-call",):
        return "custom-call (pallas kernel)"
    if op in ("copy", "copy-start", "copy-done", "slice-start", "slice-done",
              "bitcast", "transpose"):
        return "copy/layout"
    if op in ("all-reduce", "all-gather", "reduce-scatter",
              "collective-permute", "all-to-all"):
        return "collective"
    if op in ("rng-bit-generator",):
        return "rng"
    if "dot" in inner or "convolution" in inner or op in ("dot",
                                                          "convolution"):
        return "matmul/conv"
    if "scatter" in inner or op == "scatter":
        return "scatter"
    if "reduce" in inner or "reduce-window" in inner or op == "reduce":
        return "reduce/stats"
    return "elementwise/other"


def join(times, instrs, comp_ops, top=20):
    total = sum(times.values()) or 1.0
    rows = []
    cat_ms = collections.Counter()
    scope_ms = collections.Counter()   # named_scope provenance rollup
    for name, ms in times.items():
        base = re.sub(r"^%", "", name)
        rec = instrs.get(base, {})
        inner = comp_ops.get(rec.get("calls", ""), {})
        cat = categorize(rec, inner) if rec else "unmatched"
        cat_ms[cat] += ms
        opn = rec.get("op_name", "")
        if opn:
            scope_ms[provenance_scope(opn)] += ms
        rows.append({"name": base, "total_ms": round(ms, 3),
                     "pct": round(100 * ms / total, 2),
                     "opcode": rec.get("opcode", "?"),
                     "kind": rec.get("kind", ""),
                     "shape": rec.get("shape", ""),
                     "op_name": opn,
                     "category": cat,
                     "inner_ops": dict(collections.Counter(inner)
                                       .most_common(6))})
    rows.sort(key=lambda r: -r["total_ms"])
    matched = sum(1 for r in rows if r["category"] != "unmatched")
    return {"total_ms": round(total, 3),
            "matched_ops": matched, "trace_ops": len(rows),
            "named_ops": sum(1 for r in rows if r["op_name"]),
            "category_ms": {k: round(v, 3)
                            for k, v in cat_ms.most_common()},
            "category_pct": {k: round(100 * v / total, 2)
                             for k, v in cat_ms.most_common()},
            "scope_ms": {k: round(v, 3)
                         for k, v in scope_ms.most_common(top)},
            "top_ops": rows[:top]}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", required=True,
                    help="profile dir (plugins/profile/... autodiscovered) "
                         "or a .trace.json[.gz] file")
    ap.add_argument("--hlo", required=True,
                    help="optimized HLO text from roofline --save-hlo; MUST "
                         "be from the same backend/shapes as the trace or "
                         "fusion numbers will not line up")
    ap.add_argument("--top", type=int, default=20)
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)

    with open(args.hlo) as f:
        instrs, comp_ops = parse_hlo(f.read())
    times = parse_trace_ops(args.trace)
    out = join(times, instrs, comp_ops, top=args.top)
    out["trace"] = args.trace
    out["hlo"] = args.hlo
    if out["matched_ops"] * 2 < out["trace_ops"]:
        out["warning"] = ("under half the traced ops matched the HLO text — "
                          "trace and HLO are probably from different "
                          "compiles; regenerate both in the same session")
        print("WARNING: %s" % out["warning"], file=sys.stderr)
    print("total device time %.2f ms over %d ops (%d matched, %d named)"
          % (out["total_ms"], out["trace_ops"], out["matched_ops"],
             out["named_ops"]))
    for k, v in out["category_pct"].items():
        print("  %5.1f%%  %s" % (v, k))
    if out["scope_ms"]:
        print("named sinks (metadata op_name provenance):")
        for k, v in out["scope_ms"].items():
            print("  %8.3fms  %s" % (v, k))
    for r in out["top_ops"][:args.top]:
        print("%8.3fms %5.1f%%  %-28s %-12s %s %s"
              % (r["total_ms"], r["pct"], r["name"], r["category"],
                 r["shape"], dict(r["inner_ops"])))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1, sort_keys=True)
        print("wrote %s" % args.json)
    return out


if __name__ == "__main__":
    main()
