#!/usr/bin/env python
"""Optimizer-step dispatch microbench: fused multi-tensor vs per-param.

Measures the HOST-side step-loop time and jit-dispatch count of the
optimizer step (PERF.md §3): Trainer._update used to issue one jitted
XLA call per parameter per step (~160 for ResNet-50, ~200 for BERT-base).
The fused path (Optimizer.fused_update) collapses them into ONE donated
dispatch.

Drives the REAL gluon Trainer both ways over synthetic parameter sets
shaped like the two priority configs:

- resnet50_sized: 160 tensors (conv-kernel / bn-vector shape mix)
- bert_sized:     200 tensors (projection / ffn / layernorm shape mix)

The timed loop is closed by an np.asarray host readback of an updated
weight.

Run: python tools/opt_step_bench.py [--quick] [--iters 30] [--json PATH]
     [--optimizer sgd|adam]

--quick pins the CPU backend and shrinks tensors so the measurement
isolates host dispatch overhead (the tier-1 CI mode; wired in
tests/test_fused_optimizer.py and `python bench.py optstep --smoke`).
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _shapes(n, quick):
    """Shape mix cycling bn-vector / conv-kernel / matmul tensors. quick
    keeps every tensor tiny so per-step device compute is negligible and
    the loop time is the host dispatch overhead under test."""
    c = 8 if quick else 256
    cycle = [(c,), (c,), (c, c), (c, c, 3, 3)]
    return [cycle[i % len(cycle)] for i in range(n)]


def build_trainer(n_tensors, quick, optimizer, fused, seed=0):
    import jax.numpy as jnp
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.parameter import Parameter

    rng = np.random.default_rng(seed)
    params = []
    for i, shape in enumerate(_shapes(n_tensors, quick)):
        p = Parameter("p%03d" % i, shape=shape)
        p.initialize()
        p.set_data(mx.nd.array(rng.normal(size=shape).astype(np.float32)))
        p.grad()._data = jnp.asarray(
            (rng.normal(size=shape) * 0.01).astype(np.float32))
        params.append(p)
    kw = {"sgd": {"learning_rate": 0.01, "momentum": 0.9},
          "adam": {"learning_rate": 1e-3}}[optimizer]
    tr = gluon.Trainer(params, optimizer, kw)
    tr._fused_opt = fused
    return tr, params


def time_loop(trainer, params, iters):
    import numpy as np

    from mxnet_tpu import optimizer as opt_mod

    trainer.step(1)  # state init + compile
    trainer.step(1)  # steady-state warm call
    np.asarray(params[0].data()._data)
    opt_mod.dispatch_counter.reset()
    t0 = time.perf_counter()
    for _ in range(iters):
        trainer.step(1)
    np.asarray(params[0].data()._data)  # readback = completion (PERF.md)
    dt = time.perf_counter() - t0
    return dt / iters * 1e3, opt_mod.dispatch_counter.count / iters


def run_case(name, n_tensors, quick, optimizer, iters):
    tr_f, ps_f = build_trainer(n_tensors, quick, optimizer, fused=True)
    fused_ms, fused_disp = time_loop(tr_f, ps_f, iters)
    tr_p, ps_p = build_trainer(n_tensors, quick, optimizer, fused=False)
    pp_ms, pp_disp = time_loop(tr_p, ps_p, iters)
    return {
        "case": name,
        "tensors": n_tensors,
        "optimizer": optimizer,
        "iters": iters,
        "fused_ms_per_step": round(fused_ms, 3),
        "per_param_ms_per_step": round(pp_ms, 3),
        "fused_dispatches_per_step": fused_disp,
        "per_param_dispatches_per_step": pp_disp,
        "host_loop_speedup": round(pp_ms / fused_ms, 2),
        "dispatch_reduction": round(pp_disp / fused_disp, 1),
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CPU backend + tiny tensors: isolate host dispatch "
                         "overhead (the CI mode)")
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--optimizer", default="sgd", choices=("sgd", "adam"))
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the structured results artifact")
    args = ap.parse_args(argv)

    if args.quick:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    if args.quick:
        jax.config.update("jax_platforms", "cpu")

    cases = [("resnet50_sized", 160), ("bert_sized", 200)]
    rows = []
    for name, n in cases:
        rec = run_case(name, n, args.quick, args.optimizer, args.iters)
        print(json.dumps(rec), flush=True)
        rows.append(rec)

    if args.json:
        meta = {"quick": args.quick, "optimizer": args.optimizer,
                "iters": args.iters,
                "platform": jax.devices()[0].platform,
                "timing": "host-loop, np.asarray readback-closed (PERF.md)",
                "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                             time.gmtime())}
        with open(args.json, "w") as f:
            json.dump({"config": meta, "rows": rows}, f, indent=1)
            f.write("\n")
        print("wrote %d rows to %s" % (len(rows), args.json))
    return 0


if __name__ == "__main__":
    sys.exit(main())
