#!/usr/bin/env python
"""Summarize the hardware sweep artifacts into tuning recommendations.

Reads the newest sweep artifacts (tools/flash_sweep_r*.json for
flash-attention block sizes, tools/batch_sweep_r*.jsonl for bench
--batch/--remat configs) and prints:
  - best (block_q, block_k) per sequence length vs the current defaults
  - samples/s and MFU per bench config vs the 2026-08-01 chip record
    (BENCH_RESULTS.json — older than PRs 1-18; bench.py no longer writes it)
Run: python tools/sweep_report.py  (host-only; no TPU access needed)
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def flash_report(path):
    try:
        data = json.load(open(path))
    except (OSError, ValueError):
        # ValueError: mid-write/truncated artifact — report what exists
        print("no flash sweep at %s yet" % path)
        return
    rows = data["rows"]
    print("== flash sweep (%s, measured %s) ==" %
          (data["config"].get("platform"), data["config"].get("measured_at")))
    if data["config"].get("timing") != "slope-chained-v2":
        print("   WARNING: artifact predates the slope timer — these "
              "timings are dispatch-dominated noise; rerun "
              "tools/flash_sweep.py")
    for seq in sorted({r["seq"] for r in rows}):
        dense = [r for r in rows if r["seq"] == seq and r["kernel"] == "dense"]
        flash = [r for r in rows if r["seq"] == seq and r["kernel"] == "flash"]
        if not flash:
            continue
        best_f = min(flash, key=lambda r: r["fwd_ms"])
        best_b = min(flash, key=lambda r: r["fwd_bwd_ms"])
        line = ("seq %5d: best fwd bq=%d bk=%d (%.3f ms); "
                "best fwd+bwd bq=%d bk=%d (%.3f ms)"
                % (seq, best_f["block_q"], best_f["block_k"],
                   best_f["fwd_ms"], best_b["block_q"], best_b["block_k"],
                   best_b["fwd_bwd_ms"]))
        if dense:
            line += "; dense %.3f/%.3f ms" % (dense[0]["fwd_ms"],
                                              dense[0]["fwd_bwd_ms"])
        print(line)
    try:
        from mxnet_tpu.ops.pallas.flash_attention import BLOCK_DEFAULTS
        print("current defaults (ops/pallas/flash_attention.py "
              "BLOCK_DEFAULTS): %s" % (BLOCK_DEFAULTS,))
    except Exception:
        print("current defaults: see ops/pallas/flash_attention.py "
              "BLOCK_DEFAULTS")


def batch_report(path):
    try:
        lines = [l for l in open(path) if l.strip()]
    except OSError:
        print("no batch sweep at %s yet" % path)
        return
    print("== batch/remat sweep ==")
    tag = None
    for l in lines:
        try:
            rec = json.loads(l)
        except ValueError:
            continue  # truncated in-progress line
        if set(rec) == {"args"}:
            tag = rec["args"]
            continue
        if "value" in rec:
            print("%-28s %10.2f %s  mfu=%s  hbm_peak=%sGB%s"
                  % (tag or rec.get("metric", "?"), rec["value"], rec["unit"],
                     rec.get("mfu", "-"), rec.get("hbm_process_peak_gb", "-"),
                     "  [REPLAYED]" if rec.get("replayed") else ""))
            tag = None


def _newest(pattern):
    import glob
    hits = sorted(glob.glob(os.path.join(HERE, pattern)))
    return hits[-1] if hits else os.path.join(HERE, pattern.replace("r*", "r4"))


def main():
    flash_report(_newest("flash_sweep_r*.json"))
    print()
    batch_report(_newest("batch_sweep_r*.jsonl"))
    print()
    try:
        results = json.load(open(os.path.join(HERE, "..",
                                              "BENCH_RESULTS.json")))
        print("== 2026-08-01 chip record (BENCH_RESULTS.json) ==")
        for mode, r in sorted(results.items()):
            print("%-10s %10.2f %s  vs_baseline=%.2f  mfu=%s  (%s)"
                  % (mode, r["value"], r["unit"], r["vs_baseline"],
                     r.get("mfu", "-"), r["measured_at"]))
    except (OSError, ValueError):
        pass


if __name__ == "__main__":
    main()
