#!/usr/bin/env python
"""Imperative per-op dispatch microbench: lazy bulk execution vs eager.

Measures the HOST-side loop time and jit-dispatch count for a pure
imperative elementwise chain — the path ported MXNet code that never calls
``hybridize()`` lives on. Eager mode (``engine.bulk(0)``) pays one jitted
XLA dispatch per op; lazy bulk mode (``engine.bulk(K)``, the default-on
behavior) defers the chain into one composed, cache-keyed jitted program
per flush (PERF.md §3; the dynamic-fusion
cousin of TVM/Relay operator fusion applied to the imperative tape).

Every timed iteration is closed by an np.asarray host readback of the chain
result. The readback is also the lazy path's flush point, so both modes
time build + execute + fetch.

Run: python tools/imperative_bench.py [--quick] [--iters 50] [--ops 50]
     [--json PATH]

--quick pins the CPU backend and keeps tensors tiny so per-step device
compute is negligible and the loop time is the host dispatch overhead
under test (the tier-1 CI mode; wired as `python bench.py imperative
--smoke` and committed to tools/imperative_bench_quick.json).
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _chain(x, a, b, n_ops):
    """n_ops-long single-output elementwise chain mixing the three shapes
    real imperative code is made of — tensor-tensor binaries, scalar-const
    binaries (`x * 0.9`, the running-stat/normalize idiom), and unaries —
    in a 1:2:1 round-robin. Pure functional — no mutation, so nothing
    forces an early flush."""
    y = x
    ops = 0
    while ops < n_ops:
        y = y * 0.9
        ops += 1
        if ops < n_ops:
            y = y + a
            ops += 1
        if ops < n_ops:
            y = y.tanh()
            ops += 1
        if ops < n_ops:
            y = y - 0.05
            ops += 1
    return y


def run_case(name, n_ops, side, iters, quick):
    import numpy as np

    from mxnet_tpu import engine, nd

    rng = np.random.default_rng(0)
    # quick: small enough that per-op device compute is negligible (the
    # host dispatch overhead is the thing under test), large enough that
    # eager's per-op output-buffer management is realistically priced
    shape = (32, 32) if quick else (1024, 1024)
    x = nd.array(rng.normal(size=shape).astype(np.float32))
    a = nd.array(np.full(shape, 0.9, np.float32))
    b = nd.array(np.full(shape, 0.05, np.float32))

    bulk = 0 if side == "eager" else n_ops
    with engine.bulk(bulk):
        # warmup: compile both the per-op programs (eager) or the composed
        # chain program (lazy); readback closes it per PERF.md
        ref = np.asarray(_chain(x, a, b, n_ops)._data)
        np.asarray(_chain(x, a, b, n_ops)._data)
        # best-of-3 repeats: the minimum is the run least disturbed by
        # scheduler noise (the standard microbench estimator); dispatch
        # counts are deterministic, so one repeat's counter suffices
        best = float("inf")
        for _ in range(3):
            engine.dispatch_counter.reset()
            t0 = time.perf_counter()
            for _ in range(iters):
                y = _chain(x, a, b, n_ops)
                out = np.asarray(y._data)  # readback = completion (PERF.md)
            best = min(best, time.perf_counter() - t0)
            disp = engine.dispatch_counter.count / iters
    assert np.allclose(out, ref, atol=1e-6), "chain result drifted across iters"
    return best / iters * 1e3, disp, out


def run_pair(name, n_ops, iters, quick):
    import numpy as np

    lazy_ms, lazy_disp, lazy_out = run_case(name, n_ops, "lazy", iters, quick)
    eager_ms, eager_disp, eager_out = run_case(name, n_ops, "eager", iters, quick)
    assert np.allclose(lazy_out, eager_out, atol=1e-6), \
        "lazy/eager parity violated"
    return {
        "case": name,
        "ops_per_iter": n_ops,
        "iters": iters,
        "lazy_ms_per_iter": round(lazy_ms, 3),
        "eager_ms_per_iter": round(eager_ms, 3),
        "lazy_dispatches_per_iter": lazy_disp,
        "eager_dispatches_per_iter": eager_disp,
        "host_loop_speedup": round(eager_ms / lazy_ms, 2),
        "dispatch_reduction": round(eager_disp / max(lazy_disp, 1e-9), 1),
        "parity_atol": 1e-6,
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CPU backend + tiny tensors: isolate host dispatch "
                         "overhead (the CI mode)")
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--ops", type=int, default=50,
                    help="chain length of the headline case")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the structured results artifact")
    args = ap.parse_args(argv)

    if args.quick:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    if args.quick:
        jax.config.update("jax_platforms", "cpu")

    cases = [("chain%d" % args.ops, args.ops), ("chain15", 15)]
    rows = []
    for name, n in cases:
        rec = run_pair(name, n, args.iters, args.quick)
        print(json.dumps(rec), flush=True)
        rows.append(rec)

    if args.json:
        meta = {"quick": args.quick, "iters": args.iters,
                "platform": jax.devices()[0].platform,
                "timing": "host-loop, np.asarray readback-closed per iter "
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
