#!/usr/bin/env python
"""Environment diagnosis (ref: incubator-mxnet tools/diagnose.py).

Prints platform, Python, key package versions, mxnet_tpu feature flags, and
device visibility — the report users attach to bug reports. Every runtime
telemetry section (tape replay, compilation cache, serving, observability)
is a thin renderer over ``mxnet_tpu.observability.snapshot()`` — the same
dict the ``/metrics`` endpoint and ``serve.stats()`` feed from.

Run: python tools/diagnose.py [--no-device] [--json]

``--no-device`` skips the jax device probe: the probe initialises the
backend, and a chip belongs to one process at a time, so beside a live
server it would fail or take the server's chip. ``--json`` emits ``observability.snapshot()`` verbatim as JSON —
the machine-readable mode (round-trips through ``json.loads``; schema key
``schema`` versions it).
"""
import argparse
import json
import os
import platform
import sys


def _fmt(v):
    return "-" if v is None else v


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--no-device", action="store_true",
                    help="skip the jax device probe (it initialises the "
                         "backend; a chip belongs to one process at a time)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit mxnet_tpu.observability.snapshot() verbatim "
                         "as JSON and exit")
    args = ap.parse_args()

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    if args.as_json:
        from mxnet_tpu import observability
        print(json.dumps(observability.snapshot(device=not args.no_device),
                         indent=1, sort_keys=True, default=str))
        return

    print("----------Platform Info----------")
    print("Platform     :", platform.platform())
    print("system       :", platform.system())
    print("node         :", platform.node())
    print("release      :", platform.release())
    print("version      :", platform.version())

    print("----------Python Info----------")
    print("Version      :", platform.python_version())
    print("Compiler     :", platform.python_compiler())
    print("Build        :", platform.python_build())

    print("----------Environment----------")
    for k in sorted(os.environ):
        if any(s in k for s in ("MXNET", "JAX", "XLA", "TPU", "OMP")):
            print("%s=\"%s\"" % (k, os.environ[k]))

    print("----------Package Info----------")
    import importlib

    for name in ("jax", "jaxlib", "numpy", "flax", "optax", "orbax.checkpoint"):
        try:
            mod = importlib.import_module(name)  # resolves dotted submodules
            print("%-16s: %s" % (name, getattr(mod, "__version__", "?")))
        except Exception as e:
            print("%-16s: unavailable (%s)" % (name, e))
    import mxnet_tpu
    from mxnet_tpu import observability
    print("%-16s: %s" % ("mxnet_tpu", mxnet_tpu.__version__))

    # one telemetry snapshot renders every runtime section below — the
    # sections are views, the snapshot is the data
    snap = observability.snapshot()

    print("----------Autograd Tape Replay----------")
    # compiled tape replay state: the knob, the program cache, and the
    # hit/miss counters backing the zero-retrace contract — attach when
    # reporting backward()-speed regressions
    tape = snap["caches"]["tape"]
    eng = snap["engine"]
    print("tape compile : %s (MXNET_TAPE_COMPILE)"
          % ("on" if tape.get("compile_enabled") else "off — eager walk"))
    print("program cache: %d entries / cap %d (MXNET_TAPE_CACHE_CAP)"
          % (tape["entries"], tape["cap"]))
    print("cache hits   : %d   compiles (misses): %d"
          % (eng["tape_cache_hit"], eng["tape_compile"]))

    print("----------Compilation Cache----------")
    # persistent cross-process compilation layer (mxnet_tpu.cache): per-tier
    # disk entries/bytes plus this process's hit/miss/deserialize counters
    # and the store's GC/robustness tallies — attach when reporting replica
    # cold-start or warm-start-still-compiles regressions
    cc = snap["comp_cache"]
    if "error" in cc:
        print("cache unavailable:", cc["error"])
    else:
        if not cc["enabled"]:
            print("store        : disabled (set MXNET_COMP_CACHE_DIR to "
                  "persist compiled executables across processes)")
        else:
            print("store        : %s (cap %d MiB)"
                  % (cc["dir"], cc["cap_bytes"] // (1 << 20)))
            print("entries      : %d (%d KiB): %s"
                  % (cc["entries"], cc["bytes"] // 1024,
                     ", ".join("%s=%d" % (t, d["entries"])
                               for t, d in sorted(cc["tiers"].items())
                               if d["entries"])
                     or "empty"))
            print("gc/robustness: writes=%d evictions=%d stale=%d "
                  "corrupt=%d wrong_key=%d"
                  % (cc["writes"], cc["evictions"], cc["stale"],
                     cc["corrupt"], cc["wrong_key"]))
        print("this process : hits=%d misses=%d deserializes=%d "
              "(deserializes include serve-snapshot preloads)"
              % (cc["hits"], cc["misses"], cc["deserializes"]))

    print("----------Graph IR----------")
    # the unified typed graph IR (mxnet_tpu.ir): all three captures — bulk
    # window, autograd tape, Symbol executors — lower through ONE canonical
    # program cache after the rewrite-pass pipeline. Attach when reporting
    # "same math compiles twice" or pass-pipeline regressions.
    ir = snap["ir"]
    eng_ir = snap["engine"]
    print("canonical    : %d entrie(s) / cap %d, %d compiled program(s), "
          "%d eviction(s) (MXNET_IR_CACHE_CAP)"
          % (ir["cache"]["entries"], ir["cache"]["cap"],
             ir["cache"]["programs"], ir["cache"]["evictions"]))
    print("compiles     : bulk=%d tape=%d symbol=%d (per-capture program "
          "builds; identical math across captures compiles once)"
          % (eng_ir["bulk_compile"], eng_ir["tape_compile"],
             eng_ir["symbol_compile"]))
    print("interner     : %d signature(s) / cap %d (shared by every "
          "capture's key assembly)"
          % (ir["interner"]["entries"], ir["interner"]["cap"]))
    passes = ir["passes"]
    print("passes       : " + "  ".join(
        "%s[-%dn/-%de]" % (name, st["nodes_removed"], st["edges_removed"])
        for name, st in sorted(passes.items())))
    if ir["builds"]["last_build"]:
        lb = ir["builds"]["last_build"]
        print("last build   : %s… %d captured → %d canonical → %d final "
              "node(s)" % (lb["key"], lb["nodes_captured"],
                           lb["nodes_canonical"], lb["nodes_final"]))

    print("----------Serving----------")
    # mxnet_tpu.serve state: the executor-pool compile counter (a nonzero
    # steady-state delta here means bucket programs are retracing — attach
    # when reporting serving-latency regressions) plus every live server's
    # stats() snapshot (latency percentiles, queue/shed/timeout counters)
    sv = snap["serve"]
    if "error" in sv:
        print("serve unavailable:", sv["error"])
    else:
        print("pool compiles: %d bucket program(s) built this process"
              % sv["serve_compile_counter"])
        print("decode builds: %d generative program(s) (prefill/decode/"
              "inject buckets — a steady-state delta here means the token "
              "loop is retracing)" % sv["decode_compile_counter"])
        if sv["servers"]:
            for sname, s in sorted(sv["servers"].items()):
                print("%-13s: req=%d done=%d shed=%d timeout=%d err=%d "
                      "batches=%d fill=%s p50=%s p99=%s"
                      % (sname, s["requests"], s["completed"], s["shed"],
                         s["timeouts"], s["errors"], s["batches"],
                         s["batch_fill_ratio"], s["p50_ms"], s["p99_ms"]))
                if "tokens" in s:  # generative server: token-level counters
                    print("%-13s  tokens=%s tok/s=%s ttft_p50=%s itl_p50=%s "
                          "itl_p99=%s fill=%s inflight=%s/%s cap=%s "
                          "prefix=%s/%s"
                          % ("", s["tokens"], s["tokens_per_s"],
                             s["ttft_p50_ms"], s["itl_p50_ms"],
                             s["itl_p99_ms"], s["inflight_fill"],
                             s["in_flight"], s["slots"], s["capacity"],
                             s["prefix_hits"], s["prefix_misses"]))
                if s.get("draft"):
                    # speculative decode: the accept rate is THE health
                    # number — a drop means the draft stopped predicting
                    # the traffic and every round pays the wide verify
                    # for ~1 token
                    print("%-13s  spec: draft=%s k=%s rounds=%s accept=%s "
                          "(%s/%s drafted) verify_dispatches=%s"
                          % ("", s["draft"], s["spec_k"], s["spec_rounds"],
                             s["accept_rate"], s["accepted_tokens"],
                             s["drafted_tokens"], s["verify_dispatches"]))
                if s.get("prefill_chunk"):
                    print("%-13s  chunked prefill: chunk=%s chunks_run=%s "
                          "in_queue=%s itl_under_prefill_p95=%s"
                          % ("", s["prefill_chunk"], s["prefill_chunks"],
                             s["chunk_queue_depth"],
                             s["itl_prefill_p95_ms"]))
        else:
            print("live servers : none (snapshots appear while a "
                  "serve.ModelServer is alive)")

    print("----------Fleet----------")
    # serve.fleet: the router lives in the caller's process and its workers
    # are subprocesses, so there is no cross-process registry to scrape —
    # report the committed acceptance artifact (tools/fleet_bench_quick
    # .json, regenerated by `python bench.py fleet --smoke`) instead
    try:
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "fleet_bench_quick.json")) as fh:
            frows = {r["case"]: r for r in json.load(fh)["rows"]}
        k9, so = frows["kill9_drill"], frows["scale_out_p99"]
        hs, ws = frows["hot_swap_mid_traffic"], frows["warm_spawn"]
        af = frows["session_affinity"]
        print("kill -9 drill: %d/%d ok, failed=%d, retries=%d (artifact)"
              % (k9["ok"], k9["requests"], k9["failed"],
                 k9["router_retries"]))
        print("autoscale    : %d->%d workers, sheds %d->%d, "
              "p99 %.1f->%.1fms"
              % (so["workers_before"], so["workers_after"],
                 so["shed_retries_before"], so["shed_retries_after"],
                 so["p99_before_ms"], so["p99_after_ms"]))
        print("hot swap     : dropped=%d mixed=%d across %d replica(s)"
              % (hs["dropped"], hs["mixed_outputs"],
                 hs["replicas_swapped"]))
        print("warm spawn   : %d compile(s), %d retrace(s), %.2fs to ready"
              % (ws["warm_compiles"], ws["watchdog_retraces"],
                 ws["spawn_to_ready_s"]))
        print("affinity     : %d migrated prefix entrie(s), %d hit(s) "
              "after retirement"
              % (af["migrated_entries"], af["hit_on_migrated_prefix"]))
    except (OSError, KeyError, ValueError) as e:
        print("artifact     : unavailable (%s) — run `python bench.py "
              "fleet --smoke`" % e)

    print("----------Distributed----------")
    # mxnet_tpu.dist: the overlapped gradient exchange (bucket dispatches
    # vs bucket-program builds — a steady-state build delta means the
    # exchange is retracing) plus the resilience event counters the
    # heartbeat/checkpoint/elastic machinery feeds into the registry
    dd = snap["dist"]
    print("exchange     : %d bucket dispatch(es), %d bucket program "
          "build(s)" % (dd["bucket_dispatches"], dd["bucket_compiles"]))
    if "attached_trainers" in dd:
        print("trainers     : %d attached, %d layout(s), %d program(s), "
              "%d exchange(s), bucket cap %.1f MB (MXNET_DIST_BUCKET_MB)"
              % (dd["attached_trainers"], dd["bucket_layouts"],
                 dd["bucket_programs"], dd["exchanges"],
                 dd["bucket_mb_default"]))
    else:
        print("trainers     : subsystem not loaded (import mxnet_tpu.dist)")
    print("resilience   : stalls=%d saves=%d restores=%d recoveries=%d"
          % (dd["heartbeat_stalls"], dd["checkpoint_saves"],
             dd["checkpoint_restores"], dd["elastic_recoveries"]))
    if dd.get("last_recovery"):
        lr = dd["last_recovery"]
        print("last recovery: failed_step=%s survivors=%s resumed_from=%s"
              % (lr.get("failed_step"), lr.get("survivors"),
                 lr.get("resumed_from")))

    print("----------Quantization----------")
    # mxnet_tpu.quant: the serving-grade quantized-inference subsystem —
    # swap/calibration tallies plus the weight-bytes ratio. Attach when
    # reporting quantized-serving accuracy or throughput regressions.
    qt = snap["quant"]
    if qt.get("subsystem") == "not loaded":
        print("layers       : subsystem not loaded (import mxnet_tpu.quant)")
    else:
        ratio = (float(qt["weight_bytes_quantized"])
                 / qt["weight_bytes_fp32"]) if qt["weight_bytes_fp32"] else 0.0
        print("layers       : %d quantized (mode=%s), %d calibrated "
              "(calib=%s)" % (qt["quantized_layers"], qt["mode"],
                              qt["calibrated_layers"], qt["calib_mode"]))
        print("weight bytes : %d quantized vs %d fp32 (%.2fx)"
              % (qt["weight_bytes_quantized"], qt["weight_bytes_fp32"],
                 ratio))

    print("----------Observability----------")
    # the unified-telemetry layer itself: registry size, compile-time
    # accounting, the retrace watchdog, request tracing, and the bounded
    # profiler record buffer — attach when a replica's /metrics disagrees
    # with its behavior
    m = snap["metrics"]
    wd = snap["watchdog"]
    prof = snap["profiler"]
    print("registry     : %d counter(s), %d gauge(s), %d histogram(s)"
          % (len(m["counters"]), len(m["gauges"]), len(m["histograms"])))
    print("compiles     : %s build(s), %.2fs wall (cache.AotFn lower/"
          "compile)" % (_fmt(m["counters"].get("compiles_total")),
                        m["counters"].get("compile_seconds_total", 0.0)))
    print("watchdog     : %s, %d retrace event(s)%s"
          % ("ARMED" if wd["armed"] else "disarmed", wd["events"],
             " — last: %s" % wd["last_event"]["key"]
             if wd["last_event"] else ""))
    print("tracing      : %s (MXNET_REQUEST_TRACING)"
          % ("on" if snap["tracing"]["enabled"] else "off"))
    print("op telemetry : %s (%d op name(s) counted)"
          % ("on" if snap["ops"]["enabled"] else "off",
             len(snap["ops"]["dispatches"])))
    print("profiler     : %s, %d/%d record(s), %d dropped "
          "(MXNET_PROFILER_RECORD_CAP)"
          % ("running" if prof["running"] else "stopped", prof["records"],
             prof["records_cap"], prof["records_dropped"]))

    print("----------Cost Attribution----------")
    # per-program flops/bytes/peak-HBM ledger (observability.costs):
    # every _jit_backed program profiles itself; ranked detail + the CI
    # gate artifact live in tools/cost_report.py
    cs = snap["costs"]
    print("collection   : %s (MXNET_COST_ATTRIBUTION), %d profile(s), "
          "%d pending, %d dropped, %d error(s)"
          % ("on" if cs["enabled"] else "off", len(cs["profiles"]),
             cs["pending"], cs["dropped"], cs["errors"]))
    for tier, tot in sorted(cs["totals"].items()):
        print("  tier %-7s: %d program(s), %.3g flops, %.3g bytes, "
              "peak %s B" % (tier, tot["programs"], tot["flops"],
                             tot["bytes_accessed"],
                             _fmt(tot["peak_hbm_bytes"])))
    top = sorted(cs["profiles"].values(),
                 key=lambda p: (-p["flops"], p["key"]))[:3]
    for p in top:
        print("  top %s:%s %-18s %.3g flops, peak %s B"
              % (p["tier"], p["key"], p["hint"][:18], p["flops"],
                 _fmt(p["peak_hbm_bytes"])))
    for sname, row in sorted(cs["ledger"].get("servers", {}).items()):
        print("  hbm %-14s: params %s B, kv %s B, total %s B"
              % (sname, _fmt(row.get("params_bytes")),
                 _fmt(row.get("kv_cache_bytes", 0)),
                 _fmt(row.get("total_bytes"))))

    print("----------Autotuning----------")
    # cost-model-driven schedule search (ir.tune): tuned-config store
    # shape, lower-path hit/miss, and the last search's budget — attach
    # when a topology retunes every process (store path unset?) or a
    # tuned config is suspected of a regression
    tn = snap.get("tune", {})
    if tn.get("subsystem") == "not loaded":
        print("tuner        : subsystem not loaded (import mxnet_tpu.ir.tune)")
    elif tn:
        st = tn.get("store", {})
        print("store        : %s, %d entrie(s) (MXNET_TUNE_STORE / "
              "MXNET_COMP_CACHE_DIR)"
              % (st.get("path") or "in-memory only", st.get("entries", 0)))
        for key in st.get("keys", [])[:6]:
            print("  entry      : %s" % key)
        print("lower lookups: %d tuned hit(s), %d default fallback(s)"
              % (tn.get("store_hits", 0), tn.get("store_misses", 0)))
        print("searches     : %d run(s), %d candidate(s), %d pruned by "
              "cost ledger, %d timed, %d parity reject(s), %d install(s)"
              % (tn.get("searches", 0), tn.get("candidates", 0),
                 tn.get("pruned", 0), tn.get("timed", 0),
                 tn.get("parity_rejects", 0), tn.get("installs", 0)))
        if tn.get("last_search"):
            ls = tn["last_search"]
            print("last search  : %s… %d candidate(s) → %d timed @ %d "
                  "pair(s), winner %s"
                  % (ls["key"], ls["candidates"], ls["timed"], ls["pairs"],
                     ls["winner"] or "none (defaults kept)"))
    else:
        print("tune section unavailable")

    print("----------Graphlint Summary----------")
    # tracing-hygiene static pass over the package (tools/graphlint.py);
    # anything non-allowlisted here also fails the tier-1 suite
    try:
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        from mxnet_tpu.analysis import graphlint as _gl
        prev = os.getcwd()
        os.chdir(repo)
        try:
            findings = _gl.lint_paths(["mxnet_tpu"])
        finally:
            os.chdir(prev)
        allow_path = os.path.join(repo, "tools", "graphlint_allow.json")
        allow = (_gl.load_allowlist(allow_path)
                 if os.path.exists(allow_path) else {})
        kept, suppressed, _stale = _gl.split_allowed(findings, allow)
        counts = {}
        for f in findings:
            counts[f.rule] = counts.get(f.rule, 0) + 1
        print("findings     : %d (%s)" % (
            len(findings),
            ", ".join("%s=%d" % kv for kv in sorted(counts.items()))
            or "clean"))
        print("allowlisted  : %d" % len(suppressed))
        print("ci status    : %s" % ("PASS" if not kept else
                                     "FAIL (%d unallowlisted)" % len(kept)))
    except Exception as e:
        print("graphlint unavailable:", e)

    print("----------HLO Lint----------")
    # program-level static pass over the lowered StableHLO corpus
    # (analysis.hlolint, captured at the costs seam); the pinned-scenario
    # gate is tools/hlolint.py --ci, also run by the tier-1 suite
    hs = snap.get("hlolint", {})
    if hs:
        print("capture      : %s (MXNET_HLOLINT), %d program(s), "
              "%d dropped, %d error(s)"
              % ("on" if hs.get("enabled") else "off", hs.get("programs", 0),
                 hs.get("dropped", 0), hs.get("errors", 0)))
        print("findings     : %d (%s)" % (
            hs.get("total_findings", 0),
            ", ".join("%s=%d" % kv
                      for kv in sorted(hs.get("counts", {}).items()))
            or "clean"))
        for f in hs.get("findings", [])[:3]:
            print("  %s [%s] %s (%s B)" % (f["key"], f["rule"],
                                           (f["op_name"] or f["op"])[:40],
                                           _fmt(f["nbytes"])))
    else:
        print("hlolint section unavailable")

    print("----------Concurrency----------")
    # racecheck runtime stage (analysis.concurrency): armed via
    # MXNET_LOCK_CHECK=1 + instrument_locks(); the lock-order graph and
    # race probes fill only while armed — tools/race_stress.py drives a
    # worst-case mixed workload through them
    cc = snap["concurrency"]
    print("lock check   : %s (MXNET_LOCK_CHECK)"
          % ("ARMED" if cc["enabled"] else "off"))
    print("lock graph   : %d lock(s), %d order edge(s), %d dropped"
          % (cc["graph_nodes"], cc["graph_edges"], cc["edges_dropped"]))
    print("watched      : %d shared structure(s)%s"
          % (len(cc["watched"]),
             " — " + ", ".join(cc["watched"]) if cc["watched"] else ""))
    print("cycles       : %d potential deadlock(s)" % len(cc["cycles"]))
    for cyc in cc["cycles"]:
        print("  DEADLOCK   : %s" % " -> ".join(cyc["cycle"]))
    print("races        : %d overlapping-writer report(s)" % len(cc["races"]))
    for r in cc["races"]:
        print("  RACE       : %s (threads %s)"
              % (r["shared"], r["threads"]))

    if not args.no_device:
        # Features() also probes the backend (jax.default_backend inside
        # runtime._detect) — it must sit behind the same flag
        print("----------Feature Info----------")
        print(mxnet_tpu.runtime.Features())
        print("----------Device Info----------")
        import jax
        try:
            print("backend      :", jax.default_backend())
            print("devices      :", jax.devices())
        except Exception as e:
            print("device probe failed:", e)


if __name__ == "__main__":
    main()
