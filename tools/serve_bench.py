#!/usr/bin/env python
"""Serving-dispatch microbench: dynamic-batched bucketed executors vs the
naive per-request path.

Measures end-to-end requests/sec and jitted-dispatch counts for a stream of
single-sample inference requests, two ways:

* naive — each request is its own compiled call (``block(x[None])``), one
  cached jitted dispatch PER REQUEST: what ported ``Module.predict``-style
  code does when every request arrives alone;
* served — the same requests through ``mxnet_tpu.serve.ModelServer``:
  requests coalesce in the dynamic batcher into bucket-padded batches, ONE
  cached dispatch per BATCH (PERF.md §3; the
  request-side cousin of μ-cuDNN micro-batch decomposition onto fixed
  compiled shapes, arXiv 1804.04806).

Both sides are host-readback-closed per request (np.asarray results — the
PERF.md §2; the server's dispatch path gathers to host
anyway because a response leaves the process). Parity is asserted ≤1e-6.

``--mode coldstart`` benches REPLICA SPIN-UP instead: process-spawn →
first served request, cold (fresh process compiles every bucket) vs
snapshot-warm (fresh process ``serve.load(prefix, snapshot=True)``
deserializes every bucket executable — zero compiles, asserted via
``engine.serve_compile_counter``). Each side runs in its own subprocess
so the in-process jit caches cannot leak between them; parity of the
served outputs is asserted ≤1e-6. This is the cache Tier B acceptance
number (PERF.md §3; artifact
tools/serve_coldstart_bench_quick.json).

``--mode decode`` benches the GENERATIVE path instead: mixed-length
concurrent token streams through ``serve.GenerativeServer`` (continuous
batching: paged KV cache, one fused dispatch per token step, sampling
in-program) vs. naive per-request ``GPTModel.generate`` — the numbers are
tokens/sec and dispatches per decode step (PERF.md §3). Parity is exact
token ids against the same greedy decode.

Run: python tools/serve_bench.py [--quick] [--mode serve|decode]
     [--requests 256] [--json PATH]

--quick pins the CPU backend and keeps the model tiny so device compute is
negligible and the number under test is dispatch+batching overhead (the CI
mode; wired as `python bench.py serve --smoke` / `python bench.py decode
--smoke` and committed to tools/serve_bench_quick.json /
tools/serve_decode_bench_quick.json).
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_model(features=64, hidden=128, classes=10):
    import numpy as np

    from mxnet_tpu import gluon, nd

    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(hidden, activation="relu"))
        net.add(gluon.nn.Dense(classes))
    net.initialize()
    net(nd.array(np.zeros((1, features), np.float32)))  # materialize shapes
    net.hybridize()
    return net


def run_naive(net, samples, iters):
    """One compiled call per request — block batch-1 inference, jit cached
    (this is the FAVORABLE naive baseline: no per-request recompiles)."""
    import numpy as np

    from mxnet_tpu import engine, nd

    xs = [nd.array(s[None]) for s in samples]
    outs = [np.asarray(net(x)._data) for x in xs]  # warmup + reference
    best = float("inf")
    for _ in range(3):
        engine.dispatch_counter.reset()
        t0 = time.perf_counter()
        for _ in range(iters):
            for x in xs:
                out = np.asarray(net(x)._data)
            _ = out
        best = min(best, time.perf_counter() - t0)
        disp = engine.dispatch_counter.count / iters
    return len(samples) * iters / best, disp, outs


def run_served(net, samples, iters, buckets, max_wait_ms):
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import engine

    feat = samples[0].shape[0]
    srv = mx.serve.ModelServer(net, [((feat,), "float32")], buckets=buckets,
                               max_wait_ms=max_wait_ms, max_queue=4096,
                               timeout_ms=30000.0)
    with srv:
        # warmup through the batcher once
        handles = [srv.submit(s) for s in samples]
        outs = [h.result(30)[0][0] for h in handles]
        best = float("inf")
        for _ in range(3):
            engine.dispatch_counter.reset()
            engine.serve_compile_counter.reset()
            t0 = time.perf_counter()
            for _ in range(iters):
                handles = [srv.submit(s) for s in samples]
                for h in handles:
                    h.result(30)
            best = min(best, time.perf_counter() - t0)
            disp = engine.dispatch_counter.count / iters
            recompiles = engine.serve_compile_counter.count
        stats = srv.stats()
    return (len(samples) * iters / best, disp, outs, recompiles, stats)


def run_decode(requests, iters, max_new, slots, seed=0, quantize=None):
    """Generative decode bench: naive per-request ``generate()`` (the
    imperative KV-cached loop — one step ROUND of per-op dispatches per
    token per request) vs. continuous batching (ONE fused dispatch per
    token step for ALL in-flight requests). Greedy both sides; parity is
    exact token ids — except under ``--quantize``, where the served side
    runs int8 weights + int8 KV pages and parity becomes top-1 agreement
    against the fp32 naive decode (tools/quant_bench.py is the dedicated
    quantized-decode artifact). Returns the artifact row."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import engine, nd
    from mxnet_tpu.models.gpt import gpt_nano

    rng = np.random.default_rng(seed)
    m = gpt_nano()
    m.initialize()
    m.hybridize()
    prompts = [rng.integers(0, 256, size=(int(l),)).astype(np.int32)
               for l in rng.integers(3, 12, size=requests)]

    # ---- naive: one KV-cached generate() per request, sequential
    refs = [m.generate(nd.array(p[None], dtype="int32"),
                       max_new_tokens=max_new).asnumpy()[0, len(p):].tolist()
            for p in prompts]  # warmup + reference
    tokens_total = requests * max_new
    naive_best = float("inf")
    for _ in range(iters):
        engine.dispatch_counter.reset()
        t0 = time.perf_counter()
        for p in prompts:
            m.generate(nd.array(p[None], dtype="int32"),
                       max_new_tokens=max_new)
        nd.waitall()
        naive_best = min(naive_best, time.perf_counter() - t0)
        naive_disp = engine.dispatch_counter.count
    naive_tps = tokens_total / naive_best
    # dispatches per generated token step, per request stream
    naive_dps = naive_disp / max(requests * max_new, 1)

    # ---- served: all requests in flight, manual stepping for exact
    # dispatch accounting (the background loop runs the same tick)
    srv = mx.serve.GenerativeServer(m, slots=slots, max_wait_ms=1.0,
                                    max_queue=max(64, requests),
                                    timeout_ms=120000.0, quantize=quantize)
    srv.warmup(prompt_buckets=(4, 8, 16), max_tokens=32)
    served_best, served_dps, recompiles = float("inf"), 0.0, 0
    for _ in range(iters):
        streams = [srv.submit(p, max_new_tokens=max_new) for p in prompts]
        time.sleep(0.05)  # admission handover
        engine.decode_compile_counter.reset()
        pure_disp = pure_steps = 0
        t0 = time.perf_counter()
        while not all(s.done() for s in streams):
            # a tick that admits joins also pays prefill/inject dispatches;
            # dispatches/step is measured over PURE decode ticks only, in
            # steady state: the tick sends one step ahead and reads the one
            # before it (the tick that reads a stretch's last step sends
            # none and leaves none in flight)
            joins0 = srv.metrics.prefills + (srv.prefix.hits
                                             if srv.prefix else 0)
            engine.dispatch_counter.reset()
            n = srv.step()
            joins1 = srv.metrics.prefills + (srv.prefix.hits
                                             if srv.prefix else 0)
            if n and joins1 == joins0 and srv._flight is not None:
                pure_disp += engine.dispatch_counter.count
                pure_steps += 1
            elif n == 0:
                time.sleep(0.001)
        served_best = min(served_best, time.perf_counter() - t0)
        served_dps = pure_disp / max(pure_steps, 1)
        recompiles = engine.decode_compile_counter.count
        agree = same = 0
        for s, ref in zip(streams, refs):
            got = s.result(10)
            if quantize is None:
                assert got == ref, "decode parity violated"
            else:
                same += sum(1 for a, b in zip(got, ref) if a == b)
                agree += len(ref)
    served_tps = tokens_total / served_best
    stats = srv.stats()
    srv.stop()
    return {
        "case": ("gpt_nano decode" if quantize is None
                 else "gpt_nano decode (%s)" % quantize),
        "quantize": quantize,
        "requests": requests,
        "max_new_tokens": max_new,
        "slots": slots,
        "iters": iters,
        "served_tokens_per_sec": round(served_tps, 1),
        "naive_tokens_per_sec": round(naive_tps, 1),
        "speedup": round(served_tps / naive_tps, 2),
        "dispatches_per_step": round(served_dps, 2),
        "naive_dispatches_per_token": round(naive_dps, 1),
        "steady_state_recompiles": recompiles,
        "inflight_fill": stats["inflight_fill"],
        "ttft_p50_ms": stats["ttft_p50_ms"],
        "itl_p50_ms": stats["itl_p50_ms"],
        "prefix_hits": stats["prefix_hits"],
        "kv_cache_bytes": stats["kv_cache_bytes"],
        "parity": ("exact token ids vs per-request generate()"
                   if quantize is None else
                   "top-1 agreement %.4f vs fp32 generate()"
                   % (same / max(agree, 1))),
    }


def run_specdecode(max_new, spec_k=4, seed=0, pair_reps=3):
    """Speculative-decode bench (PERF.md §3), two scenarios:

    A. LATENCY REGIME (the regime speculative decoding exists for): a
    single greedy stream on a one-slot server, plain decode vs the same
    server with an ``NGramDraft`` (k=``spec_k``). The model is a nano GPT
    whose per-token compute is small next to per-dispatch overhead — the
    CPU stand-in for memory-bound TPU decode, where the k-wide verify
    window rides the same HBM-bound weight sweep as a 1-token step.
    Timing is PAIRED-STEP: both servers run live and the loop alternates
    one plain tick with one speculation round, so both sides of every
    pair see the same instantaneous machine load (run-level A/B timing on
    a shared CI box swings ±50%; adjacent-step pairing cancels it).
    Tokens/s on each side is tokens-per-step over the median step wall.
    Parity is exact token ids.

    B. CHUNKED-PREFILL INTERFERENCE: a short victim stream decodes while
    4k-token prompts arrive; the victim's host-observed inter-token gaps
    DURING each arrival's prefill window (submit → long stream's first
    token) are the number chunking exists to bound — p95 of those gaps,
    whole-prompt prefill vs ``prefill_chunk=256``. Both servers are
    pre-warmed with the same long+victim traffic so zero compiles land in
    the measured window."""
    import statistics

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import engine
    from mxnet_tpu.models.gpt import GPTModel

    # ---- A. latency regime: paired-step plain tick vs speculation round
    nano = GPTModel(vocab_size=64, units=32, num_layers=1, num_heads=2,
                    max_length=512, dropout=0.0)
    nano.initialize()
    nano.hybridize()
    # a periodic prompt: the order-3 matcher's honest regime (code/logs/
    # templated text stand-in) — greedy continuations of the untrained
    # model settle into a loop the n-gram draft predicts almost perfectly
    prompt = np.asarray([5, 6, 7] * 2, np.int32)

    def _start(srv):
        s = srv.submit(prompt, max_new_tokens=max_new)
        while len(s.tokens) < 1:
            srv.step()
            time.sleep(0.001)
        return s

    def _mk(draft):
        kw = dict(slots=1, max_wait_ms=1.0, timeout_ms=120000.0,
                  prefix_cache=False)
        if draft:
            kw.update(draft=mx.serve.NGramDraft(), spec_k=spec_k)
        return mx.serve.GenerativeServer(nano, **kw)

    speedups, accepts, rows_meta = [], [], None
    recompiles = 0
    verify_disp = 0
    rounds_total = 0
    spec_toks_total = 0
    ptps_list, stps_list = [], []
    for rep in range(pair_reps):
        plain, spec = _mk(False), _mk(True)
        # warm run to completion on both (compiles every program incl.
        # the capacity-grown buckets) + exact-parity assertion
        sp, ss = _start(plain), _start(spec)
        while not (sp.done() and ss.done()):
            plain.step()
            spec.step()
        refs, got = sp.result(10), ss.result(10)
        assert got == refs, "speculative decode parity violated"
        # timed: alternate one plain tick with one speculation round
        sp, ss = _start(plain), _start(spec)
        s0 = spec.stats()
        v0 = engine.verify_dispatch_counter.count
        engine.decode_compile_counter.reset()
        pw, sw = [], []
        p0, s0tok = len(sp.tokens), len(ss.tokens)
        while not ss.done() and not sp.done():
            t0 = time.perf_counter()
            plain.step()
            t1 = time.perf_counter()
            spec.step()
            pw.append(t1 - t0)
            sw.append(time.perf_counter() - t1)
        recompiles += engine.decode_compile_counter.count
        verify_disp += engine.verify_dispatch_counter.count - v0
        ptoks = len(sp.tokens) - p0
        stoks = len(ss.tokens) - s0tok
        s1 = spec.stats()
        acc = ((s1["accepted_tokens"] - s0["accepted_tokens"])
               / max(s1["drafted_tokens"] - s0["drafted_tokens"], 1))
        ptps = (ptoks / len(pw)) / statistics.median(pw)
        stps = (stoks / len(sw)) / statistics.median(sw)
        speedups.append(stps / ptps)
        accepts.append(acc)
        ptps_list.append(ptps)
        stps_list.append(stps)
        rounds_total += len(sw)
        spec_toks_total += stoks
        plain.stop()
        spec.stop()
    mid = sorted(range(pair_reps), key=lambda i: speedups[i])[pair_reps // 2]

    # ---- B. chunked prefill: victim ITL during 4k-prompt prefill windows
    long_len = 4096
    big = GPTModel(vocab_size=256, units=64, num_layers=2, num_heads=2,
                   max_length=8192, dropout=0.0)
    big.initialize()
    big.hybridize()
    rng = np.random.default_rng(seed)
    long_prompts = [rng.integers(1, 256, size=(long_len,)).astype(np.int32)
                    for _ in range(2)]
    victim_prompt = rng.integers(1, 256, size=(6,)).astype(np.int32)
    itl = {}
    for label, chunk in (("unchunked", None), ("chunked", 256)):
        srv = mx.serve.GenerativeServer(big, slots=4, max_wait_ms=1.0,
                                        timeout_ms=600000.0,
                                        prefix_cache=False,
                                        prefill_chunk=chunk)
        # warm: same victim + long buckets/capacity as the timed phase,
        # so the measured stall is pure prefill execution, not compile
        wv = srv.submit(victim_prompt, max_new_tokens=4)
        wl = srv.submit(long_prompts[0], max_new_tokens=2)
        while not (wv.done() and wl.done()):
            if srv.step() == 0:
                time.sleep(0.001)
        victim = srv.submit(victim_prompt, max_new_tokens=120)
        while len(victim.tokens) < 1:
            srv.step()
            time.sleep(0.001)
        gaps_all, gaps_under = [], []
        last = time.perf_counter()
        launched, in_flight = 0, []
        # "under arrival": a long prompt is submitted but has not produced
        # its first token — its prefill work (whole-prompt or chunked) is
        # what the victim is living through. Sample the condition BEFORE
        # each tick and latch it: the unchunked prefill grants the long
        # stream its first token inside the very step that stalls the
        # victim, so a post-step check would miss exactly the gap that
        # matters.
        pending = False
        while not victim.done():
            n_before = len(victim.tokens)
            pending = pending or any(not s.tokens for s in in_flight)
            srv.step()
            now = time.perf_counter()
            if len(victim.tokens) > n_before:
                gap = (now - last) * 1e3
                gaps_all.append(gap)
                if pending:
                    gaps_under.append(gap)
                pending = False
                last = now
            if launched < len(long_prompts) \
                    and len(victim.tokens) >= 20 * (launched + 1):
                in_flight.append(
                    srv.submit(long_prompts[launched], max_new_tokens=2))
                launched += 1
        stats = srv.stats()
        srv.stop()

        def _pct(xs, q):
            xs = sorted(xs)
            return xs[min(len(xs) - 1, int(q * (len(xs) - 1) + 0.5))]

        itl[label] = {
            "victim_itl_under_prefill_p95_ms": round(_pct(gaps_under, .95), 3),
            "victim_itl_under_prefill_max_ms": round(max(gaps_under), 3),
            "victim_itl_overall_p50_ms": round(_pct(gaps_all, .50), 3),
            "gaps_under_prefill": len(gaps_under),
            "prefill_chunks": stats["prefill_chunks"],
        }

    return {
        "case": "nano GPT latency-regime specdecode (ngram draft, k=%d)"
                % spec_k,
        "slots": 1,
        "max_new_tokens": max_new,
        "spec_k": spec_k,
        "pair_reps": pair_reps,
        "timing": "paired-step: alternate plain tick / speculation round, "
                  "median step wall per side (shared-box contention hits "
                  "both sides of each pair equally)",
        "spec_tokens_per_sec": round(stps_list[mid], 1),
        "plain_tokens_per_sec": round(ptps_list[mid], 1),
        "speedup": round(speedups[mid], 2),
        "speedup_all_reps": [round(s, 2) for s in speedups],
        "accept_rate": round(sum(accepts) / len(accepts), 4),
        "spec_rounds": rounds_total,
        "verify_dispatches": verify_disp,
        "tokens_per_verify_dispatch": round(
            spec_toks_total / max(verify_disp, 1), 2),
        "dispatches_per_round": 1,   # NGramDraft: verify only
        "steady_state_recompiles": recompiles,
        "long_prompt_len": long_len,
        "prefill_chunk": 256,
        "victim_itl_unchunked": itl["unchunked"],
        "victim_itl_chunked": itl["chunked"],
        "chunked_itl_p95_improvement": round(
            itl["unchunked"]["victim_itl_under_prefill_p95_ms"]
            / max(itl["chunked"]["victim_itl_under_prefill_p95_ms"], 1e-9),
            2),
        "parity": "exact token ids vs plain continuous-batching decode",
    }


def _coldstart_model(quick):
    """Deterministic-shape serving model for the spin-up bench. --quick: a
    4-layer MLP (CPU CI); full: resnet18 (real bucket compiles)."""
    import numpy as np

    from mxnet_tpu import gluon, nd

    if quick:
        feat = 128
        net = gluon.nn.HybridSequential()
        with net.name_scope():
            for _ in range(3):
                net.add(gluon.nn.Dense(256, activation="relu"))
            net.add(gluon.nn.Dense(10))
        net.initialize()
        net(nd.array(np.zeros((1, feat), np.float32)))
        net.hybridize()
        return net, ((feat,), "float32")
    from mxnet_tpu.gluon.model_zoo.vision import resnet18_v1

    net = resnet18_v1()
    net.initialize()
    net(nd.array(np.zeros((1, 3, 224, 224), np.float32)))
    net.hybridize()
    return net, ((3, 224, 224), "float32")


def coldstart_child(which, prefix, quick, buckets, t_entry):
    """One replica spin-up, timed inside the child process. ``cold``
    builds + warm-compiles + serves + WRITES the snapshot (untimed);
    ``warm`` loads the snapshot and serves. Prints one JSON line."""
    import numpy as np

    t_import0 = time.perf_counter()
    import jax  # noqa: F401  (the dominant import)

    import mxnet_tpu as mx
    from mxnet_tpu import engine

    import_s = time.perf_counter() - t_import0
    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    if which == "cold":
        net, spec = _coldstart_model(quick)
        srv = mx.serve.ModelServer(net, [spec], buckets=buckets,
                                   max_wait_ms=0.5, timeout_ms=30000.0)
    else:
        srv = mx.serve.load(prefix, snapshot=True, max_wait_ms=0.5,
                            timeout_ms=30000.0)
        spec = srv._specs[0]
    x = rng.normal(size=spec[0]).astype(np.dtype(spec[1]))
    with srv:
        out = srv.predict(x)
    first_request_s = time.perf_counter() - t0
    spawn_env = os.environ.get("MXNET_SPAWN_T0")
    spawn_to_first_s = (time.time() - float(spawn_env)) if spawn_env else None
    rec = {
        "which": which,
        "first_request_s": round(first_request_s, 4),
        "spawn_to_first_s": (round(spawn_to_first_s, 4)
                             if spawn_to_first_s is not None else None),
        "spawn_to_main_s": round(t_entry, 4),
        "import_s": round(import_s, 4),
        "serve_compiles": engine.serve_compile_counter.count,
        "deserializes": engine.comp_cache_deserialize_counter.count,
        "out": np.asarray(out).ravel()[:8].astype(float).tolist(),
        "out_sum": float(np.asarray(out).sum()),
    }
    if which == "cold":
        srv.snapshot(prefix)  # untimed: the artifact is built once, offline
    print(json.dumps(rec), flush=True)
    return 0


def run_coldstart(quick, prefix=None):
    """Spawn the cold and warm children, check parity + the zero-compile
    contract, and return the artifact row."""
    import subprocess
    import tempfile

    import numpy as np

    buckets = (1, 2, 4, 8, 16, 32)
    tmp = None
    if prefix is None:
        tmp = tempfile.mkdtemp(prefix="mxc_coldstart_")
        prefix = os.path.join(tmp, "snap")
    here = os.path.abspath(__file__)
    out = {}
    for which in ("cold", "warm"):
        env = dict(os.environ, MXNET_SPAWN_T0=repr(time.time()))
        argv = [sys.executable, here, "--mode", "coldstart",
                "--coldstart-child", which, "--prefix", prefix]
        if quick:
            argv.append("--quick")
        r = subprocess.run(argv, capture_output=True, text=True, env=env,
                           timeout=1800)
        if r.returncode != 0:
            raise RuntimeError("%s child failed:\n%s\n%s"
                               % (which, r.stdout, r.stderr))
        out[which] = json.loads(r.stdout.strip().splitlines()[-1])
    cold, warm = out["cold"], out["warm"]
    assert warm["serve_compiles"] == 0, \
        "snapshot-warm replica traced %d bucket programs (must be 0: the " \
        "Tier B zero-compile contract)" % warm["serve_compiles"]
    assert np.allclose(cold["out"], warm["out"], atol=1e-6) and \
        abs(cold["out_sum"] - warm["out_sum"]) < 1e-4, \
        "cold/warm output parity violated"
    rec = {
        "case": ("mlp128 coldstart" if quick else "resnet18 coldstart"),
        "buckets": list(buckets),
        "cold_first_request_s": cold["first_request_s"],
        "warm_first_request_s": warm["first_request_s"],
        # the headline: replica-ready time once the interpreter is up —
        # build+compile+serve vs snapshot-load+serve. Interpreter + jax
        # import are identical on both sides and reported separately.
        "speedup": round(cold["first_request_s"]
                         / warm["first_request_s"], 2),
        "cold_spawn_to_first_s": cold["spawn_to_first_s"],
        "warm_spawn_to_first_s": warm["spawn_to_first_s"],
        "spawn_speedup": (round(cold["spawn_to_first_s"]
                                / warm["spawn_to_first_s"], 2)
                          if cold.get("spawn_to_first_s")
                          and warm.get("spawn_to_first_s") else None),
        "import_s": warm["import_s"],
        "warm_serve_compiles": warm["serve_compiles"],
        "cold_serve_compiles": cold["serve_compiles"],
        "warm_deserializes": warm["deserializes"],
        "parity_atol": 1e-6,
    }
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CPU backend + tiny model: isolate dispatch and "
                         "batching overhead (the CI mode)")
    ap.add_argument("--mode",
                    choices=("serve", "decode", "coldstart", "specdecode"),
                    default="serve",
                    help="serve: fixed-shape inference batching; decode: "
                         "continuous-batching generative token streams; "
                         "coldstart: replica spin-up cold vs snapshot-warm "
                         "(subprocess-isolated); specdecode: speculative "
                         "draft/verify decode + chunked-prefill ITL vs the "
                         "plain decode path")
    ap.add_argument("--coldstart-child", choices=("cold", "warm"),
                    default=None, help=argparse.SUPPRESS)
    ap.add_argument("--prefix", default=None,
                    help="coldstart: snapshot artifact prefix (default: "
                         "a temp dir)")
    ap.add_argument("--requests", type=int, default=128,
                    help="requests per timed iteration")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--max-new", type=int, default=16,
                    help="decode mode: tokens generated per request")
    ap.add_argument("--slots", type=int, default=8,
                    help="decode mode: in-flight request pages")
    ap.add_argument("--quantize", choices=("int8", "e4m3", "e5m2"),
                    default=None,
                    help="decode mode: serve with quantized weights + int8 "
                         "KV pages (parity becomes top-1 agreement)")
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--json", default=None, metavar="PATH")
    args = ap.parse_args(argv)

    if args.quick:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    if args.mode == "coldstart":
        if args.coldstart_child:
            # child: time everything INSIDE the spawned process (jax not
            # yet imported here — that's part of what's being measured);
            # t_entry = spawn→main latency (interpreter + this module)
            t0 = os.environ.get("MXNET_SPAWN_T0")
            t_entry = (time.time() - float(t0)) if t0 else 0.0
            return coldstart_child(args.coldstart_child, args.prefix,
                                   args.quick, (1, 2, 4, 8, 16, 32),
                                   t_entry)
        rec = run_coldstart(args.quick, prefix=args.prefix)
        print(json.dumps(rec), flush=True)
        if args.json:
            meta = {"quick": args.quick, "mode": "coldstart",
                    "timing": "per-side subprocess: first_request_s = "
                              "model build/snapshot load + warmup/preload "
                              "+ first served response (imports excluded, "
                              "identical both sides and reported); "
                              "spawn_to_first_s includes interpreter+jax "
                              "import",
                    "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                                 time.gmtime())}
            with open(args.json, "w") as f:
                json.dump({"config": meta, "rows": [rec]}, f, indent=1)
                f.write("\n")
            print("wrote %s" % args.json)
        return 0

    import jax

    if args.quick:
        jax.config.update("jax_platforms", "cpu")
    import numpy as np

    if args.mode == "specdecode":
        # default --max-new 16 is the decode-mode knob; the paired-step
        # latency run needs a long stream for stable per-step medians
        rec = run_specdecode(args.max_new if args.max_new > 64 else 480)
        print(json.dumps(rec), flush=True)
        if args.json:
            meta = {"quick": args.quick, "mode": "specdecode",
                    "platform": jax.devices()[0].platform,
                    "timing": "A: paired-step latency regime (alternate "
                              "plain tick / speculation round, median step "
                              "wall per side); B: victim ITL gaps host-"
                              "observed during 4k-prompt prefill windows "
                              "(PERF.md)",
                    "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                                 time.gmtime())}
            with open(args.json, "w") as f:
                json.dump({"config": meta, "rows": [rec]}, f, indent=1)
                f.write("\n")
            print("wrote %s" % args.json)
        return 0

    if args.mode == "decode":
        rec = run_decode(args.requests if args.requests != 128 else 16,
                         args.iters, args.max_new, args.slots,
                         quantize=args.quantize)
        print(json.dumps(rec), flush=True)
        if args.json:
            meta = {"quick": args.quick, "mode": "decode",
                    "platform": jax.devices()[0].platform,
                    "timing": "end-to-end mixed-length concurrent streams, "
                              "host-readback closed per token (PERF.md)",
                    "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                                 time.gmtime())}
            with open(args.json, "w") as f:
                json.dump({"config": meta, "rows": [rec]}, f, indent=1)
                f.write("\n")
            print("wrote %s" % args.json)
        return 0

    rng = np.random.default_rng(0)
    feat = 64
    buckets = (1, 8, 32)
    samples = [rng.normal(size=(feat,)).astype(np.float32)
               for _ in range(args.requests)]

    net = build_model(features=feat)
    naive_rps, naive_disp, naive_outs = run_naive(net, samples, args.iters)
    (served_rps, served_disp, served_outs, recompiles,
     stats) = run_served(net, samples, args.iters, buckets, args.max_wait_ms)

    for a, b in zip(naive_outs, served_outs):
        assert np.allclose(a[0], b, atol=1e-6), "served/naive parity violated"
    assert recompiles == 0, \
        "steady-state serving retraced %d times" % recompiles

    rec = {
        "case": "mlp%d" % feat,
        "requests_per_iter": args.requests,
        "iters": args.iters,
        "buckets": list(buckets),
        "max_wait_ms": args.max_wait_ms,
        "served_requests_per_sec": round(served_rps, 1),
        "naive_requests_per_sec": round(naive_rps, 1),
        "speedup": round(served_rps / naive_rps, 2),
        "served_dispatches_per_iter": served_disp,
        "naive_dispatches_per_iter": naive_disp,
        "dispatch_reduction": round(naive_disp / max(served_disp, 1e-9), 1),
        "steady_state_recompiles": recompiles,
        "batch_fill_ratio": stats["batch_fill_ratio"],
        "p50_ms": stats["p50_ms"],
        "p99_ms": stats["p99_ms"],
        "parity_atol": 1e-6,
    }
    print(json.dumps(rec), flush=True)

    if args.json:
        meta = {"quick": args.quick,
                "platform": jax.devices()[0].platform,
                "timing": "end-to-end request round-trip, host-readback "
                          "closed (PERF.md)",
                "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                             time.gmtime())}
        with open(args.json, "w") as f:
            json.dump({"config": meta, "rows": [rec]}, f, indent=1)
            f.write("\n")
        print("wrote %s" % args.json)
    return 0


if __name__ == "__main__":
    sys.exit(main())
