#!/usr/bin/env python
"""Fleet serving bench: the serve.fleet acceptance numbers, dryrun-provable
on CPU with REAL subprocess workers (ISSUE 20).

Five scenarios, each a row in the artifact:

* ``kill9_drill`` — a request wave over 2 replicas with ``kill -9`` of one
  mid-wave. The router turns connection failures into sibling retries, so
  the wave completes with ``failed == 0`` — the whole point of a fleet.
* ``scale_out_p99`` — one small-queue replica under an offered load it
  must shed; the SLO autoscaler reads the shed rate and spawns a second
  replica; the same wave re-offered no longer sheds and p99 drops. On this
  1-core box the win is QUEUE CAPACITY (shed-retry elimination), not CPU
  parallelism — the honest single-replica-ceiling story (PERF.md).
* ``hot_swap_mid_traffic`` — continuous traffic while a new checkpoint is
  pushed to every replica. Every response must equal the OLD or the NEW
  model's output exactly (the per-dispatch params seam makes the flip
  atomic — no torn weight set), with zero dropped requests.
* ``warm_spawn`` — a replica spawned from an AOT serving snapshot reaches
  its first request with ZERO compiles (scraped from the worker's own
  ``/snapshot``: ``serve_compile_counter == 0`` and no armed-watchdog
  retrace events) — the horizontal-autoscale spin-up unit.
* ``session_affinity`` — generative: a pinned session hits its replica's
  prefix cache across turns; retiring that replica migrates the prefix
  entries to a sibling and the session's next turn HITS the migrated
  entry (PagedKVCache state crossing a process boundary).

Wall-clock columns are host-dependent context; the COUNTER columns
(failed, sheds after scale-out, mixed outputs, warm compiles, migrated
hits) are deterministic and gated by tests/test_counter_baseline.py.

Run: python tools/fleet_bench.py --quick [--json PATH]
--quick puts the workers on the CPU backend and keeps waves small (the CI
mode; wired as ``python bench.py fleet --cpu`` and committed to
tools/fleet_bench_quick.json).

One process for each chip: the router process (this one) pins ITSELF to the
CPU backend — its reference outputs are computed there — by its own config,
which the workers do not inherit; the workers are the processes that take
chips. A worker takes every chip its host shows it and nothing assigns it one
yet (ROADMAP D7), so an accelerator host serves ONE worker however many chips
it has, and every scenario here needs two at once: without ``--quick`` the
bench refuses, and says so, rather than let the second worker die before
READY.
"""
import argparse
import json
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TOOLS = os.path.dirname(os.path.abspath(__file__))
FACTORY = os.path.join(TOOLS, "fleet_factory.py")


def _fact(name):
    return "%s:%s" % (FACTORY, name)


def _load_factory():
    import importlib.util

    spec = importlib.util.spec_from_file_location("fleet_factory", FACTORY)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _sample():
    import numpy as np

    return np.random.default_rng(0).standard_normal((16,)).astype(np.float32)


def _percentile(vals, q):
    if not vals:
        return None
    vals = sorted(vals)
    return round(vals[min(len(vals) - 1, int(q * (len(vals) - 1) + 0.5))], 3)


# ------------------------------------------------------------- scenarios
def run_kill9(requests=60, kill_at=0.25):
    """Wave over 2 replicas, SIGKILL one mid-wave; count failures (must be
    zero — in-flight work on the victim is retried on the sibling)."""
    import numpy as np

    from mxnet_tpu.serve.fleet import FleetRouter, WorkerSpec

    x = _sample()
    with FleetRouter() as router:
        router.register(spec=WorkerSpec(factory=_fact("model_server")),
                        workers=2)
        ref = router.predict(x)
        results = {"ok": 0, "failed": 0}
        lock = threading.Lock()

        def client():
            try:
                y = router.predict(x)
                assert np.allclose(y, ref, atol=1e-6)
                with lock:
                    results["ok"] += 1
            except Exception:
                with lock:
                    results["failed"] += 1

        threads = [threading.Thread(target=client) for _ in range(requests)]
        victim = router.workers()[0]
        t0 = time.perf_counter()
        for i, t in enumerate(threads):
            t.start()
            if i == int(requests * kill_at):
                victim.kill9()
            time.sleep(0.002)
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        return {"case": "kill9_drill", "requests": requests,
                "ok": results["ok"], "failed": results["failed"],
                "router_retries": router.retries,
                "workers_lost": router.workers_lost,
                "workers_left": len(router.workers()),
                "wall_s": round(wall, 3)}


def run_scale_out(requests=48, concurrency=8, sustain=2):
    """One shed-prone replica vs. the autoscaled pair, same offered wave.
    Client-side retry-on-busy (what a real caller does) is what inflates
    p99 while the fleet sheds; after scale-out nothing sheds."""
    from mxnet_tpu.serve.fleet import Autoscaler, FleetRouter, WorkerSpec

    x = _sample()

    def wave(router):
        lats, sheds, failed = [], [0], [0]
        lock = threading.Lock()
        sem = threading.Semaphore(concurrency)

        def client():
            with sem:
                t0 = time.perf_counter()
                for _ in range(50):  # retry-on-busy with backoff
                    try:
                        router.predict(x)
                        break
                    except Exception as e:
                        if type(e).__name__ != "ServerBusy":
                            with lock:
                                failed[0] += 1
                            return
                        with lock:
                            sheds[0] += 1
                        time.sleep(0.005)
                else:
                    with lock:
                        failed[0] += 1
                    return
                with lock:
                    lats.append((time.perf_counter() - t0) * 1e3)

        threads = [threading.Thread(target=client)
                   for _ in range(requests)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return lats, sheds[0], failed[0]

    with FleetRouter() as router:
        router.register(
            spec=WorkerSpec(factory=_fact("model_server_slow_tiny_queue")),
            workers=1)
        # live control loop DURING the wave: the shed-rate breach must be
        # seen on `sustain` consecutive samples, which only happens while
        # the wave is actually shedding (idle= huge: no scale-in here,
        # wave2 must run against the scaled pair)
        scaler = Autoscaler(router, min_workers=1, max_workers=2,
                            slo_p95_ms=1e9, shed_rate=0.01, sustain=sustain,
                            idle=10 ** 6, interval_s=0.1)
        scaler.start()
        lats1, sheds1, failed1 = wave(router)
        for _ in range(5):  # keep offering load until the spawn lands
            if len(router.workers()) == 2:
                break
            lat, sh, fl = wave(router)
            lats1 += lat
            sheds1 += sh
            failed1 += fl
        scaler.stop()
        workers_after = len(router.workers())
        lats2, sheds2, failed2 = wave(router)
        events = [e["event"] for e in router.events]
        return {"case": "scale_out_p99", "requests": requests,
                "offered_concurrency": concurrency,
                "workers_before": 1, "workers_after": workers_after,
                "autoscaled": "autoscale_out" in events
                              and "scale_out" in events,
                "failed": failed1 + failed2,
                "shed_retries_before": sheds1,
                "shed_retries_after": sheds2,
                "p50_before_ms": _percentile(lats1, 0.50),
                "p99_before_ms": _percentile(lats1, 0.99),
                "p50_after_ms": _percentile(lats2, 0.50),
                "p99_after_ms": _percentile(lats2, 0.99)}


def run_hot_swap(requests=80):
    """Continuous traffic while the v2 checkpoint rolls across both
    replicas: zero drops, and every response is exactly v1's or v2's
    output — a torn (half-swapped) weight set would match neither."""
    import numpy as np

    from mxnet_tpu import nd
    from mxnet_tpu.serve.fleet import FleetRouter, WorkerSpec

    ff = _load_factory()
    x = _sample()
    with tempfile.TemporaryDirectory() as td:
        v2 = os.path.join(td, "v2.params")
        net_v2 = ff._mlp(salt=1)
        net_v2.save_parameters(v2)
        with FleetRouter() as router:
            router.register(spec=WorkerSpec(factory=_fact("model_server")),
                            workers=2)
            ref_v1 = np.asarray(router.predict(x))
            ref_v2 = np.asarray(net_v2(nd.array(x[None])).asnumpy()[0])
            counts = {"v1": 0, "v2": 0, "mixed": 0, "failed": 0}
            lock = threading.Lock()
            stop = threading.Event()

            def client():
                while not stop.is_set():
                    try:
                        y = np.asarray(router.predict(x))
                    except Exception:
                        with lock:
                            counts["failed"] += 1
                        continue
                    if np.allclose(y, ref_v1, atol=1e-5):
                        k = "v1"
                    elif np.allclose(y, ref_v2, atol=1e-5):
                        k = "v2"
                    else:
                        k = "mixed"
                    with lock:
                        counts[k] += 1
                        if counts["v1"] + counts["v2"] >= requests:
                            stop.set()

            threads = [threading.Thread(target=client) for _ in range(4)]
            for t in threads:
                t.start()
            while counts["v1"] < requests // 4 and not stop.is_set():
                time.sleep(0.005)
            epochs = router.hot_swap(v2)
            stop.wait(timeout=60.0)
            stop.set()
            for t in threads:
                t.join()
            return {"case": "hot_swap_mid_traffic",
                    "requests": counts["v1"] + counts["v2"],
                    "dropped": counts["failed"],
                    "mixed_outputs": counts["mixed"],
                    "old_model_responses": counts["v1"],
                    "new_model_responses": counts["v2"],
                    "replicas_swapped": len(epochs),
                    "swap_epochs": sorted(epochs.values())}


def run_warm_spawn():
    """Snapshot-warm replica spin-up: spawn from an AOT artifact, serve one
    request, scrape the worker's OWN /snapshot for compile counters and
    armed-watchdog retrace events — both must be zero."""
    from mxnet_tpu.serve.fleet import FleetRouter, WorkerSpec

    ff = _load_factory()
    with tempfile.TemporaryDirectory() as td:
        prefix = os.path.join(td, "fleet_snap")
        srv = ff.model_server()
        srv.start()
        srv.snapshot(prefix)
        srv.stop()
        t0 = time.perf_counter()
        with FleetRouter() as router:
            router.register(spec=WorkerSpec(snapshot=prefix), workers=1)
            spawn_s = time.perf_counter() - t0
            y = router.predict(_sample())
            first_request_ok = y is not None and len(y) == ff.CLASSES
            w = router.workers()[0]
            snap = json.loads(w._checked("GET", "/snapshot"))
            warm_compiles = snap.get("serve", {}).get(
                "serve_compile_counter", -1)
            wd = snap.get("watchdog", {})
            retraces = int(wd.get("events") or 0)
            return {"case": "warm_spawn",
                    "spawn_to_ready_s": round(spawn_s, 3),
                    "first_request_ok": bool(first_request_ok),
                    "warm_compiles": warm_compiles,
                    "watchdog_armed": bool(wd.get("armed", False)),
                    "watchdog_retraces": retraces}


def run_affinity(turns=3):
    """Generative session affinity + prefix migration across retirement."""
    from mxnet_tpu.serve.fleet import FleetRouter, WorkerSpec

    prompt = [5, 6, 7, 8]
    with FleetRouter() as router:
        router.register("gen",
                        spec=WorkerSpec(factory=_fact("generative_server")),
                        workers=2)
        toks = [router.generate(prompt, model="gen", session="s0",
                                max_new_tokens=8, seed=3)
                for _ in range(turns)]
        pinned = router._models["gen"].affinity["s0"]
        hits_before = pinned.server_stats().get("prefix_hits") or 0
        sibling = [w for w in router.workers("gen") if w is not pinned][0]
        router.retire(pinned, model="gen")
        migrated = sibling.server_stats().get("prefix_entries") or 0
        h0 = sibling.server_stats().get("prefix_hits") or 0
        tok_after = router.generate(prompt, model="gen", session="s0",
                                    max_new_tokens=8, seed=3)
        h1 = sibling.server_stats().get("prefix_hits") or 0
        return {"case": "session_affinity", "turns": turns,
                "prefix_hits_on_pinned": hits_before,
                "migrated_entries": migrated,
                "hit_on_migrated_prefix": h1 - h0,
                "tokens_stable_across_migration":
                    bool(tok_after == toks[0] and all(t == toks[0]
                                                      for t in toks))}


# ------------------------------------------------------------------ main
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="CPU backend, small waves (the CI artifact mode)")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--json", default=None, help="write artifact here")
    args = ap.parse_args(argv)
    if not args.quick:
        raise SystemExit(
            "fleet_bench needs two workers at once, and its workers would "
            "land on the accelerator: a chip belongs to one process at a "
            "time, a worker takes every chip its host shows it, and nothing "
            "gives each worker a chip of its own yet (WorkerSpec.env; "
            "ROADMAP D7) — so on this host a fleet is one worker and the "
            "second would die before READY. Run it on the CPU backend: "
            "`python bench.py fleet --cpu` (tools/fleet_bench.py --quick).")
    # the workers inherit the environment: the CPU backend for them
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # the router stays off the accelerator by its OWN choice, whatever the
    # environment says: it only routes and computes reference outputs
    import jax

    jax.config.update("jax_platforms", "cpu")
    n = args.requests or 60
    rows = []
    t0 = time.perf_counter()
    rows.append(run_kill9(requests=n))
    print("kill9_drill: %(ok)d/%(requests)d ok, failed=%(failed)d, "
          "retries=%(router_retries)d" % rows[-1])
    rows.append(run_scale_out(requests=max(48, n // 2)))
    print("scale_out_p99: p99 %.1fms -> %.1fms, sheds %d -> %d"
          % (rows[-1]["p99_before_ms"], rows[-1]["p99_after_ms"],
             rows[-1]["shed_retries_before"], rows[-1]["shed_retries_after"]))
    rows.append(run_hot_swap(requests=n))
    print("hot_swap: dropped=%(dropped)d mixed=%(mixed_outputs)d "
          "(old=%(old_model_responses)d new=%(new_model_responses)d)"
          % rows[-1])
    rows.append(run_warm_spawn())
    print("warm_spawn: compiles=%(warm_compiles)d retraces="
          "%(watchdog_retraces)d in %(spawn_to_ready_s).2fs" % rows[-1])
    rows.append(run_affinity())
    print("session_affinity: migrated=%(migrated_entries)d "
          "hit_after=%(hit_on_migrated_prefix)d" % rows[-1])
    out = {"config": {"quick": True,
                      "platform": os.environ["JAX_PLATFORMS"],
                      "timing": "end-to-end over real subprocess workers; "
                                "counter columns are the gate, wall-clock "
                                "is context (1-core CI box)",
                      "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                                   time.gmtime()),
                      "wall_s": round(time.perf_counter() - t0, 1)},
           "rows": rows}
    path = args.json or os.path.join(TOOLS, "fleet_bench_quick.json")
    with open(path, "w") as fh:
        json.dump(out, fh, indent=1)
        fh.write("\n")
    print("wrote", path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
