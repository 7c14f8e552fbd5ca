#!/usr/bin/env python
"""Ranked per-program cost report: what each compiled program costs.

Every program built through the ``base._jit_backed`` funnel records a
CostProfile (observability.costs): flops, bytes accessed, output bytes,
argument/donation bytes, and the peak-HBM working set — deterministic
XLA ``cost_analysis()``/``memory_analysis()`` columns, keyed by the
comp-cache's content hash. This tool renders the ranked per-program
table, the per-server/trainer HBM ledger, and a step-time decomposition
(compute vs dispatch-gap vs comm-overlap) from the existing tracing
spans — replacing the old hand-run join of ``roofline.py --save-hlo``
with ``profile_hlo_map.py`` for the "which op is the sink" question
(PERF.md §3).

``--quick`` runs the four PINNED programs (the same builders the
counter baseline replays): the 160-tensor fused optimizer step, the
chain50 compiled tape, the mlp64 serve bucket set, and the gpt_nano
decode step. The per-scenario gate columns (programs / flops /
bytes_accessed / peak_hbm_bytes) are deterministic on CPU, committed in
``tools/cost_report_quick.json``, and replayed + asserted EQUAL by
``tests/test_costs.py`` — a perf regression in any capture path (a
rewrite pass that doubles the fused step's flops, a decode step that
re-reads the whole KV cache) becomes a CPU test failure, no TPU
required. Gate rows come from deterministic build points (first stepped
call, warmup) only; timing breakdowns are host-dependent and excluded
from comparison.

Usage:
  python tools/cost_report.py --quick [--json PATH] [--top N]
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

ARTIFACT = os.path.join(HERE, "cost_report_quick.json")

# the deterministic per-scenario gate columns (exact equality in CI)
GATE_COLS = ("programs", "flops", "bytes_accessed", "peak_hbm_bytes")


def _tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(HERE, "%s.py" % name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _costs():
    from mxnet_tpu.observability import costs
    return costs


def _tier_rows(tier, since_keys, hint=None):
    """Profiles of ``tier`` recorded since ``since_keys``, ranked by
    flops (ties broken by key so the order is deterministic)."""
    costs = _costs()
    costs.materialize()
    rows = [p for k, p in costs.profiles().items()
            if p["tier"] == tier and k not in since_keys
            and (hint is None or p["hint"] == hint)]
    rows.sort(key=lambda r: (-r["flops"], r["key"]))
    return rows


def _mark():
    costs = _costs()
    costs.materialize()
    return set(costs.profiles())


def _gate_cols(tier, rows):
    # summed in ranked order (fixed fp association) and rounded: the
    # columns must reproduce bit-for-bit across processes
    return {"tier": tier, "programs": len(rows),
            "flops": round(sum(r["flops"] for r in rows), 1),
            "bytes_accessed": round(sum(r["bytes_accessed"]
                                        for r in rows), 1),
            "peak_hbm_bytes": int(max([r["peak_hbm_bytes"]
                                       for r in rows] or [0]))}


# ------------------------------------------------------------- scenarios
def scenario_optstep():
    """One fused-optimizer training step (tier jit, hint fused_step) —
    the 160-tensor resnet50-sized quick trainer the counter baseline
    pins."""
    bench = _tool("opt_step_bench")
    before = _mark()
    tr, ps = bench.build_trainer(160, quick=True, optimizer="sgd",
                                 fused=True)
    bench.time_loop(tr, ps, iters=2)
    rows = _tier_rows("jit", before, hint="fused_step")
    row = {"case": "optstep"}
    row.update(_gate_cols("jit", rows))
    row["detail"] = rows
    row["hbm_ledger"] = _costs().trainer_ledger(tr)
    return row


def scenario_chain50_tape():
    """The chain50 record→compiled-backward program (tier tape)."""
    bench = _tool("autograd_bench")
    before = _mark()
    bench.run_case(50, "compiled", iters=2, quick=True)
    rows = _tier_rows("tape", before)
    row = {"case": "chain50_tape"}
    row.update(_gate_cols("tape", rows))
    row["detail"] = rows
    return row


def scenario_serve_mlp64():
    """The mlp64 bucket programs (tier serve). Gate rows come from the
    constructor's deterministic warmup compile of every bucket; the
    request wave afterwards only feeds the tracing-span breakdown."""
    import numpy as np

    import mxnet_tpu as mx

    bench = _tool("serve_bench")
    before = _mark()
    net = bench.build_model(features=64)
    srv = mx.serve.ModelServer(net, [((64,), "float32")],
                               buckets=(8, 32, 64), max_wait_ms=1.0,
                               max_queue=4096, timeout_ms=30000.0,
                               name="cost_report:mlp64")
    with srv:
        rows = _tier_rows("serve", before)   # warmup-compiled buckets
        rng = np.random.default_rng(0)
        handles = [srv.submit(rng.normal(size=(64,)).astype(np.float32))
                   for _ in range(64)]
        for h in handles:
            h.result(30)
        ledger = _costs().hbm_ledger()["servers"].get(
            "cost_report:mlp64", {})
        breakdown = _wave_breakdown(
            [h.timing() for h in handles
             if getattr(h, "timing", None) and h.timing()])
    row = {"case": "serve_mlp64"}
    row.update(_gate_cols("serve", rows))
    row["detail"] = rows
    row["hbm_ledger"] = ledger
    row["step_breakdown"] = breakdown
    return row


def scenario_gpt_nano_decode():
    """The gpt_nano prefill/decode step programs (tier decode). Gate
    rows come from ``warmup()`` — the deterministic compile point; the
    short live wave afterwards only feeds the breakdown."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.models.gpt import gpt_nano

    before = _mark()
    m = gpt_nano()
    m.initialize()
    m.hybridize()
    srv = mx.serve.GenerativeServer(m, slots=4, max_wait_ms=1.0,
                                    max_queue=64, timeout_ms=120000.0,
                                    name="cost_report:gpt_nano")
    srv.warmup(prompt_buckets=(4, 8), max_tokens=32)
    rows = _tier_rows("decode", before)     # warmup-compiled programs
    try:
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 256, size=(int(n),)).astype(np.int32)
                   for n in rng.integers(3, 8, size=4)]
        streams = [srv.submit(p, max_new_tokens=8) for p in prompts]
        srv.start()
        for s in streams:
            s.result(60)
        ledger = _costs().hbm_ledger()["servers"].get(
            "cost_report:gpt_nano", {})
        breakdown = _wave_breakdown([s.timing() for s in streams])
    finally:
        srv.stop()
    row = {"case": "gpt_nano_decode"}
    row.update(_gate_cols("decode", rows))
    row["detail"] = rows
    row["hbm_ledger"] = ledger
    row["step_breakdown"] = breakdown
    return row


# ------------------------------------------------- step-time decomposition
def _wave_breakdown(timings):
    """Decompose request wall time into queue / pad (dispatch-gap) /
    dispatch (device compute+transfer) from the tracing spans. Timing is
    host-dependent — reported for reading, excluded from the CI gate."""
    timings = [t for t in timings if t]
    if not timings:
        return {"tracing": "off (set_tracing(True) for span breakdowns)"}
    n = len(timings)

    def avg(k):
        return round(sum(float(t.get(k) or 0.0) for t in timings) / n, 3)

    row = {"requests": n, "queue_ms_avg": avg("queue_ms"),
           "pad_ms_avg": avg("pad_ms"), "dispatch_ms_avg": avg("dispatch_ms"),
           "total_ms_avg": avg("total_ms")}
    row["gap_ms_avg"] = round(
        max(row["total_ms_avg"] - row["queue_ms_avg"] - row["pad_ms_avg"]
            - row["dispatch_ms_avg"], 0.0), 3)
    return row


def dist_breakdown(snap):
    """Comm-overlap decomposition for the dist exchange, from the
    overlap-window histogram the bucketer already feeds. Only present
    once mxnet_tpu.dist is loaded."""
    dd = snap.get("dist", {})
    if "attached_trainers" not in dd:
        return {"subsystem": "not loaded"}
    hist = snap.get("metrics", {}).get("histograms", {})
    out = {"exchanges": dd.get("exchanges"),
           "bucket_dispatches": dd.get("bucket_dispatches")}
    for name, h in hist.items():
        if "overlap" in name or "dist" in name:
            out[name] = h
    return out


# ----------------------------------------------------------------- report
def run_quick():
    import jax

    from mxnet_tpu import observability

    observability.set_tracing(True)
    scenarios = [scenario_optstep(), scenario_chain50_tape(),
                 scenario_serve_mlp64(), scenario_gpt_nano_decode()]
    snap = observability.snapshot()
    sec = snap["costs"]
    ranked = sorted(sec["profiles"].values(),
                    key=lambda r: (-r["flops"], r["key"]))
    return {"schema": 1, "mode": "quick", "jax": jax.__version__,
            "backend": jax.default_backend(),
            "rows": scenarios,
            "ranked": ranked[:40],
            "totals": sec["totals"],
            "hbm_ledger": sec["ledger"],
            "dist_breakdown": dist_breakdown(snap)}


def compare(baseline, replay, cols=GATE_COLS):
    """The CI gate: exact equality of the deterministic per-scenario
    cost columns. Returns a list of mismatch strings (empty = pass) —
    each prefixed 'case:' so a seeded regression in one capture path
    fails exactly that scenario."""
    base_rows = {r["case"]: r for r in baseline["rows"]}
    rep_rows = {r["case"]: r for r in replay["rows"]}
    problems = []
    for case in sorted(base_rows):
        if case not in rep_rows:
            problems.append("%s: missing from replay" % case)
            continue
        for col in cols:
            b, r = base_rows[case].get(col), rep_rows[case].get(col)
            if b != r:
                problems.append("%s: %s %r != baseline %r"
                                % (case, col, r, b))
    return problems


def _print_report(out, top):
    print("cost report (%s, jax %s, backend %s)"
          % (out["mode"], out["jax"], out["backend"]))
    print("%-8s %-18s %-22s %12s %12s %10s"
          % ("tier", "key", "hint", "GFLOP", "MB accessed", "peak MB"))
    for r in out["ranked"][:top]:
        print("%-8s %-18s %-22s %12.6f %12.3f %10.3f"
              % (r["tier"], r["key"], r["hint"][:22], r["flops"] / 1e9,
                 r["bytes_accessed"] / 1e6, r["peak_hbm_bytes"] / 1e6))
    print("\npinned gate rows (compared exactly by tests/test_costs.py):")
    for r in out["rows"]:
        print("  %-16s tier=%-6s programs=%-3d flops=%.1f bytes=%.1f "
              "peak=%d" % (r["case"], r["tier"], r["programs"], r["flops"],
                           r["bytes_accessed"], r["peak_hbm_bytes"]))
        if r.get("step_breakdown"):
            print("    step: %s" % json.dumps(r["step_breakdown"],
                                              sort_keys=True))
    led = out["hbm_ledger"]
    if led.get("servers"):
        print("\nHBM ledger:")
        for name, row in sorted(led["servers"].items()):
            print("  %-24s %s" % (name, json.dumps(row, sort_keys=True)))
    for r in out["rows"]:
        if "hbm_ledger" in r and r["case"] == "optstep":
            print("  %-24s %s" % ("trainer:optstep",
                                  json.dumps(r["hbm_ledger"],
                                             sort_keys=True)))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="run the pinned bench programs and report their "
                         "cost profiles (the CI-gated artifact mode)")
    ap.add_argument("--json", default=None,
                    help="write the report dict as JSON (commit as %s for "
                         "the gate)" % os.path.relpath(ARTIFACT, REPO))
    ap.add_argument("--top", type=int, default=20)
    args = ap.parse_args(argv)
    if not args.quick:
        ap.error("only --quick is implemented: the pinned-program report "
                 "(full-model mode rides the roofline/profile tools)")
    out = run_quick()
    _print_report(out, args.top)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1, sort_keys=True)
        print("\nwrote %s" % args.json)
    return out


if __name__ == "__main__":
    main()
