#!/usr/bin/env python
"""Distributed gradient-exchange bench: overlapped hierarchical bucketed
allreduce (mxnet_tpu.dist) vs the serialized flat baseline.

The scenario is the multi-worker stacked harness on one host: an 8-device
CPU mesh laid out {dcn: 2, dp: 4} — 8 simulated workers, 2 "hosts" of 4 —
training the same tiny MLP two ways:

* ``overlapped``: the compiled backward's stacked per-worker grads are
  handed to :class:`~mxnet_tpu.dist.GradientBucketer` the moment the
  program is dispatched — size-capped bucket reductions
  (reduce-scatter on dp, cross dcn, all-gather) queue behind the
  still-executing backward, so exchange rides under compute;
* ``serialized``: block until EVERY grad is materialized, then ONE
  monolithic flat psum over both axes, block again, then update — the
  pattern dist_async existed to avoid.

Both modes compute the identical mean-gradient update, so their loss
trajectories must agree to fp32 parity (asserted, atol 1e-6); the wall
clock difference is pure exchange scheduling. Counter columns
(bucket dispatches/step, dispatches/step, zero steady-state bucket-program
builds with the retrace watchdog armed) are the CI baseline —
``tests/test_counter_baseline.py`` replays the quick mode and pins them
against the committed artifact ``tools/dist_bench_quick.json``.

Run: python tools/dist_bench.py [--quick] [--steps 12] [--json PATH]

--quick pins the CPU backend with 8 virtual devices (the tier-1 CI mode;
wired as ``python bench.py dist --smoke``).
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

LAYERS = 6
WIDTH = 256
BATCH = 32


def _build_problem(mesh, W):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    rng = np.random.default_rng(0)
    params = [jnp.asarray(rng.normal(size=(WIDTH, WIDTH)).astype(np.float32)
                          * (1.0 / WIDTH ** 0.5))
              for _ in range(LAYERS)]
    rep = NamedSharding(mesh, P())
    params = [jax.device_put(p, rep) for p in params]
    xs = jnp.asarray(rng.normal(size=(W, BATCH, WIDTH)).astype(np.float32))
    ys = jnp.asarray(rng.normal(size=(W, BATCH, WIDTH)).astype(np.float32))
    wspec = NamedSharding(mesh, P(("dcn", "dp"), None, None))
    xs = jax.device_put(xs, wspec)
    ys = jax.device_put(ys, wspec)

    def per_worker_loss(ps, x, y):
        h = x
        for w in ps:
            h = jnp.tanh(h @ w)
        return jnp.mean((h - y) ** 2)

    @jax.jit
    def backward(ps, x, y):
        # vmap over the leading worker axis: stacked (W, ...) grads, one
        # loss per simulated worker — the compiled-backward stand-in
        losses, grads = jax.vmap(
            jax.value_and_grad(per_worker_loss), in_axes=(None, 0, 0))(
                ps, x, y)
        return jnp.mean(losses), grads

    @jax.jit
    def apply(ps, gs, lr):
        return [w - lr * g for w, g in zip(ps, gs)]

    return params, xs, ys, backward, apply


def run_mode(mode, steps, bucket_mb, lr=0.05):
    """One training run; returns (losses, ms_per_step, counters dict)."""
    import jax
    import numpy as np

    from mxnet_tpu import engine
    from mxnet_tpu.parallel.mesh import make_mesh
    import mxnet_tpu.dist as dist

    mesh = make_mesh({"dcn": 2, "dp": 4})
    W = 8
    params, xs, ys, backward, apply = _build_problem(mesh, W)
    if mode == "overlapped":
        strat = dist.HierarchicalAllreduce(mesh, ici_axis="dp",
                                           dcn_axis="dcn", average=True)
        bucketer = dist.GradientBucketer(strat, bucket_mb=bucket_mb,
                                         stacked=True)
    else:
        strat = dist.FlatAllreduce(mesh, axes=("dcn", "dp"), average=True)
        # one monolithic bucket: the serialized baseline reduces everything
        # in a single flat program after the full blocking sync
        bucketer = dist.GradientBucketer(strat, bucket_mb=1 << 20,
                                         stacked=True)

    def step(ps):
        loss, grads = backward(ps, xs, ys)
        glist = list(grads)
        if mode == "serialized":
            # the serialization under test: wait for EVERY grad, reduce
            # once, wait for the reduction, only then update
            jax.block_until_ready(glist)
            reduced = bucketer.exchange(glist)
            jax.block_until_ready(reduced)
        else:
            # async: bucket reductions queue behind the still-executing
            # backward; nothing blocks until the loss readback
            reduced = bucketer.exchange(glist)
        return apply(ps, reduced, lr), loss

    # warmup: build every program (backward, buckets, apply) out of band
    warm, l0 = step(params)
    jax.block_until_ready(warm)

    from mxnet_tpu import observability

    observability.arm_watchdog()
    try:
        d0 = engine.dispatch_counter.count
        b0 = engine.dist_bucket_counter.count
        c0 = engine.dist_compile_counter.count
        losses = []
        t0 = time.perf_counter()
        ps = params
        for _ in range(steps):
            ps, loss = step(ps)
            losses.append(float(loss))   # the only per-step sync point
        dt = time.perf_counter() - t0
    finally:
        observability.disarm_watchdog()
    return losses, dt / steps * 1e3, {
        "dispatches_per_step": (engine.dispatch_counter.count - d0) / steps,
        "buckets_per_step": (engine.dist_bucket_counter.count - b0) / steps,
        "steady_state_bucket_builds": engine.dist_compile_counter.count - c0,
        "bucket_programs": bucketer.stats()["programs"],
    }


def run_pair(steps, bucket_mb, reps=3):
    import numpy as np

    best = {}
    for mode in ("overlapped", "serialized"):
        losses, ms, counters = run_mode(mode, steps, bucket_mb)
        for _ in range(reps - 1):
            l2, ms2, c2 = run_mode(mode, steps, bucket_mb)
            assert np.allclose(losses, l2, atol=1e-6), \
                "%s drifted across reps" % mode
            ms = min(ms, ms2)
        best[mode] = (losses, ms, counters)
        assert counters["steady_state_bucket_builds"] == 0, \
            "steady-state retrace in %s mode: %d builds" \
            % (mode, counters["steady_state_bucket_builds"])
    lo, mo, co = best["overlapped"]
    ls, ms_, cs = best["serialized"]
    parity = float(np.max(np.abs(np.asarray(lo) - np.asarray(ls))))
    assert parity <= 1e-6, \
        "overlapped vs serialized loss trajectories diverged: %g" % parity
    return {
        "case": "mlp_%dx%d_w8" % (LAYERS, WIDTH),
        "steps": steps,
        "bucket_mb": bucket_mb,
        "overlapped_ms_per_step": round(mo, 3),
        "serialized_ms_per_step": round(ms_, 3),
        "overlap_speedup": round(ms_ / mo, 3),
        "overlapped_buckets_per_step": co["buckets_per_step"],
        "serialized_buckets_per_step": cs["buckets_per_step"],
        "overlapped_dispatches_per_step": co["dispatches_per_step"],
        "serialized_dispatches_per_step": cs["dispatches_per_step"],
        "steady_state_bucket_builds": co["steady_state_bucket_builds"],
        "loss_trajectory_max_diff": parity,
        "parity_atol": 1e-6,
        "final_loss": lo[-1],
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CPU backend + 8 virtual devices (the CI mode)")
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--bucket-mb", type=float, default=0.25,
                    help="bucket payload cap; 0.25 MB splits the %d-layer "
                         "MLP into multiple buckets" % LAYERS)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the structured results artifact")
    args = ap.parse_args(argv)

    if args.quick:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
    import jax

    if len(jax.devices()) < 8:
        print("dist_bench needs 8 devices (got %d) — run with --quick or "
              "XLA_FLAGS=--xla_force_host_platform_device_count=8"
              % len(jax.devices()))
        return 1

    rec = run_pair(args.steps, args.bucket_mb)
    print(json.dumps(rec), flush=True)

    if args.json:
        meta = {"quick": args.quick, "steps": args.steps,
                "platform": jax.devices()[0].platform,
                "mesh": {"dcn": 2, "dp": 4},
                "timing": "host-loop wall clock, float(loss) readback per "
                          "step is the only sync (PERF.md)",
                "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                             time.gmtime())}
        with open(args.json, "w") as f:
            json.dump({"config": meta, "rows": [rec]}, f, indent=1)
            f.write("\n")
        print("wrote 1 row to %s" % args.json)
    return 0


if __name__ == "__main__":
    sys.exit(main())
