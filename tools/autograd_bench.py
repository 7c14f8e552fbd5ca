#!/usr/bin/env python
"""Autograd backward-dispatch microbench: compiled tape replay vs the
per-node eager walk.

Measures the HOST-side loop time and jit-dispatch count for a full
``record → loss → backward`` iteration over a pure imperative elementwise
chain — the define-by-run path ported MXNet training loops that never call
``hybridize()`` live on. Eager mode (``MXNET_TAPE_COMPILE=0`` semantics via
``autograd.set_tape_compile(False)``) pays one jitted dispatch per op in
the recorded forward (``jax.vjp``) plus one per node in the backward walk
— ~2N per iteration; compiled mode (the default) defers the recorded
region and lowers forward+backward into ONE cached jitted program
(PERF.md §3; the whole-program-compilation
move of TVM/Relay, arXiv 1802.04799 / 1810.00952, applied to the tape).

Every timed iteration is closed by np.asarray host readbacks of the loss
AND the gradient. Both modes therefore time record + backward + fetch.

Run: python tools/autograd_bench.py [--quick] [--iters 30] [--ops 50]
     [--json PATH]

--quick pins the CPU backend and keeps tensors tiny so per-step device
compute is negligible and the loop time is the host dispatch overhead
under test (the tier-1 CI mode; wired as `python bench.py autograd
--smoke` and committed to tools/autograd_bench_quick.json).
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _chain(x, a, n_ops):
    """n_ops-long differentiable elementwise chain mixing tensor-tensor
    binaries, scalar-const binaries, and unaries in the same 1:2:1
    round-robin as tools/imperative_bench.py."""
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


def run_case(n_ops, side, iters, quick):
    import numpy as np

    from mxnet_tpu import autograd, engine, nd

    rng = np.random.default_rng(0)
    shape = (32, 32) if quick else (1024, 1024)
    x = nd.array(rng.normal(size=shape).astype(np.float32))
    a = nd.array(np.full(shape, 0.9, np.float32))
    x.attach_grad()

    def step():
        with autograd.record():
            loss = _chain(x, a, n_ops).sum()
        loss.backward()
        # readback closes the iteration (PERF.md): loss AND grad
        lv = np.asarray(loss._data)
        gv = np.asarray(x.grad._data)
        return lv, gv

    prev = autograd.set_tape_compile(side == "compiled")
    try:
        # warmup: compile the tape program (compiled) / per-op programs
        # (eager); second rep proves the cache is warm
        ref_loss, ref_grad = step()
        step()
        best = float("inf")
        for _ in range(3):
            engine.dispatch_counter.reset()
            engine.tape_compile_counter.reset()
            t0 = time.perf_counter()
            for _ in range(iters):
                lv, gv = step()
            best = min(best, time.perf_counter() - t0)
            disp = engine.dispatch_counter.count / iters
            recompiles = engine.tape_compile_counter.count
    finally:
        autograd.set_tape_compile(prev)
    assert np.allclose(gv, ref_grad, atol=1e-6), "grad drifted across iters"
    return best / iters * 1e3, disp, recompiles, gv


def run_pair(name, n_ops, iters, quick):
    import numpy as np

    comp_ms, comp_disp, comp_rc, comp_g = run_case(n_ops, "compiled", iters,
                                                   quick)
    eager_ms, eager_disp, _, eager_g = run_case(n_ops, "eager", iters, quick)
    assert np.allclose(comp_g, eager_g, atol=1e-6), \
        "compiled/eager gradient parity violated"
    assert comp_rc == 0, "steady-state retrace: %d tape compiles" % comp_rc
    return {
        "case": name,
        "ops_per_iter": n_ops,
        "iters": iters,
        "compiled_ms_per_iter": round(comp_ms, 3),
        "eager_ms_per_iter": round(eager_ms, 3),
        "compiled_dispatches_per_iter": comp_disp,
        "eager_dispatches_per_iter": eager_disp,
        "steady_state_tape_recompiles": comp_rc,
        "host_loop_speedup": round(eager_ms / comp_ms, 2),
        "dispatch_reduction": round(eager_disp / max(comp_disp, 1e-9), 1),
        "parity_atol": 1e-6,
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CPU backend + tiny tensors: isolate host dispatch "
                         "overhead (the CI mode)")
    ap.add_argument("--iters", type=int, default=30)
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
                "timing": "host-loop, np.asarray readback of loss+grad per "
                          "iter (PERF.md)",
                "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                             time.gmtime())}
        with open(args.json, "w") as f:
            json.dump({"config": meta, "rows": rows}, f, indent=1)
            f.write("\n")
        print("wrote %d rows to %s" % (len(rows), args.json))
    return 0


if __name__ == "__main__":
    sys.exit(main())
