#!/usr/bin/env python
"""Graph-IR microbench: cross-dispatch graph optimization (mxnet_tpu.ir).

Runs a repeated-subexpression imperative chain — the pattern XLA cannot
clean up across per-op dispatch boundaries but the unified IR's rewrite
passes must: each loop iteration recomputes the SAME ``tanh(x*a)``
subexpression (CSE collapses the repeats to one slot) and issues a dead
product nobody reads (DCE drops it). The chain lowers through
``ir.lower_forward``; the bench records the node counts before/after the
pass pipeline (captured → canonical → final) and the host-loop time of
the IR-lowered lazy window vs pure eager per-op dispatch.

Counter columns (1 dispatch/iter, zero steady-state recompiles, the
node-shrink numbers) are the CI baseline — tests/test_counter_baseline.py
replays this scenario and asserts them against the committed artifact
``tools/ir_bench_quick.json``.

Run: python tools/ir_bench.py [--quick] [--iters 30] [--reps 12]
     [--json PATH]

--quick pins the CPU backend and keeps tensors tiny so per-step device
compute is negligible and the loop time is the host dispatch overhead
under test (the tier-1 CI mode; wired as ``python bench.py ir --smoke``).
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _chain(x, a, reps):
    """``reps`` iterations, each recomputing tanh(x*a) (CSE fodder),
    accumulating it, and issuing a dead product (DCE fodder)."""
    acc = x
    last_dead = None
    for _ in range(reps):
        u = (x * a).tanh()      # identical subexpression every iteration
        acc = acc + u
        last_dead = u * a       # never observed: dead subgraph
    del last_dead
    return acc


def run_case(name, reps, side, iters, quick):
    import numpy as np

    from mxnet_tpu import engine, nd
    from mxnet_tpu import base
    from mxnet_tpu.ir import lower as irl, passes as irp

    rng = np.random.default_rng(0)
    shape = (32, 32) if quick else (1024, 1024)
    x = nd.array(rng.normal(size=shape).astype(np.float32))
    a = nd.array(np.full(shape, 0.9, np.float32))
    window = 4 * reps + 8

    def step():
        if side == "lazy":
            with engine.bulk(window):
                out = _chain(x, a, reps)
                return np.asarray(out._data)
        with engine.bulk(0):
            out = _chain(x, a, reps)
            return np.asarray(out._data)

    build = None
    pass_delta = {}
    if side == "lazy":
        # force a cold canonical build so the node-shrink stats are
        # deterministic regardless of process-level cache warmth
        base._BULK_CACHE.clear()
        base._IR_CACHE.clear()
        irl.reset_stats()
        p0 = irp.pass_stats()
        step()
        build = dict(irl.stats()["builds"]["last_build"] or {})
        p1 = irp.pass_stats()
        # CSE rewires duplicates (rewrites); DCE then removes the
        # stranded nodes — report each pass by the delta it owns
        pass_delta = {
            "cse": p1["cse"]["rewrites"] - p0["cse"]["rewrites"],
            "dce": p1["dce"]["nodes_removed"] - p0["dce"]["nodes_removed"],
        }
    ref = step()  # warm
    best = float("inf")
    for _ in range(3):
        engine.dispatch_counter.reset()
        engine.bulk_compile_counter.reset()
        t0 = time.perf_counter()
        for _ in range(iters):
            out = step()
        best = min(best, time.perf_counter() - t0)
        disp = engine.dispatch_counter.count / iters
        recompiles = engine.bulk_compile_counter.count
    assert np.allclose(out, ref, atol=1e-6), "drift across iterations"
    return best / iters * 1e3, disp, recompiles, build, pass_delta, out


def run_pair(name, reps, iters, quick):
    import numpy as np

    lazy_ms, lazy_disp, lazy_rc, build, pdelta, lazy_out = run_case(
        name, reps, "lazy", iters, quick)
    eager_ms, eager_disp, _rc, _b, _p, eager_out = run_case(
        name, reps, "eager", iters, quick)
    assert np.allclose(lazy_out, eager_out, atol=1e-6), \
        "IR-lowered window lost parity with eager dispatch"
    assert lazy_rc == 0, "steady-state retrace: %d bulk compiles" % lazy_rc
    assert build and build["nodes_final"] < build["nodes_captured"], \
        "pass pipeline failed to shrink the seeded redundant graph"
    return {
        "case": name,
        "reps": reps,
        "ops_per_iter": 4 * reps,
        "iters": iters,
        "nodes_captured": build["nodes_captured"],
        "nodes_canonical": build["nodes_canonical"],
        "nodes_final": build["nodes_final"],
        "cse_rewrites": pdelta.get("cse", 0),
        "dce_nodes_removed": pdelta.get("dce", 0),
        "lazy_ms_per_iter": round(lazy_ms, 3),
        "eager_ms_per_iter": round(eager_ms, 3),
        "host_loop_speedup": round(eager_ms / lazy_ms, 2),
        "lazy_dispatches_per_iter": lazy_disp,
        "eager_dispatches_per_iter": eager_disp,
        "steady_state_recompiles": lazy_rc,
        "parity_atol": 1e-6,
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CPU backend + tiny tensors: isolate host dispatch "
                         "overhead (the CI mode)")
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--reps", type=int, default=12,
                    help="repeated-subexpression iterations in the chain")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the structured results artifact")
    args = ap.parse_args(argv)

    if args.quick:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    if args.quick:
        jax.config.update("jax_platforms", "cpu")

    rows = []
    for name, reps in (("cse_chain%d" % args.reps, args.reps),
                       ("cse_chain4", 4)):
        rec = run_pair(name, reps, args.iters, args.quick)
        print(json.dumps(rec), flush=True)
        rows.append(rec)

    if args.json:
        meta = {"quick": args.quick, "iters": args.iters,
                "platform": jax.devices()[0].platform,
                "timing": "host-loop, np.asarray readback per iter "
                          "(PERF.md)",
                "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                             time.gmtime())}
        with open(args.json, "w") as f:
            json.dump({"config": meta, "rows": rows}, f, indent=1)
            f.write("\n")
        print("wrote %d rows to %s" % (len(rows), args.json))
    return 0


if __name__ == "__main__":
    sys.exit(main())
