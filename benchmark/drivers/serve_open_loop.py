"""Driver of a serving window: an open loop against ``serve.GenerativeServer``.

The entry is ``GenerativeServer.submit`` on the configuration's model (bf16,
hybridized), the server built with the configuration's ``server`` arguments
and every other argument at its default. Requests arrive on a schedule made
from the seed (``lib/loadgen.py``), at the rate fixed in the cell, whatever
the server does: one sender thread submits each when it is due, one consumer
thread per stream stamps every token as the client receives it. A request is
timed from when it was due, not from when it was sent; how late the sender
ran is reported. The schedule starts ``preroll_s`` before the window opens
(counted as set-up), so that the window sees the server in flight.

What ``correct`` compares, after the window has closed and every stream has
ended: for a sample of the finished requests drawn from the seed, the longest
among them, the plain reference is run once over prompt + served tokens, and
the widest gap by which a served token's logit lies below the reference's
best is held to the cell's limit; every request of the window must have
ended with exactly the tokens it asked for.
"""
import os
import shutil
import threading
import time

import numpy as np

from lib import build, loadgen, trace_reduce, weights
from lib.log import note

SLICE_S = 3.0
DRAIN_S = 60.0


class Client(threading.Thread):
    """Consumes one stream, stamping every token on the client's side."""

    def __init__(self, request, stream):
        super().__init__(daemon=True, name="bench-client")
        self.request, self.stream = request, stream
        self.stamps, self.tokens, self.error = [], [], None

    def run(self):
        try:
            for tok in self.stream:
                self.stamps.append(time.perf_counter())
                self.tokens.append(int(tok))
        except Exception as e:             # shed, timed out, server error
            self.error = e


class Run:
    def __init__(self, cell, config, reference, seed, seconds, trace, devices,
                 t_process_start, scratch, control=None):
        self.cell, self.config, self.reference = cell, config, reference
        self.sizes, self.traffic = config["sizes"], cell["traffic"]
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.devices, self.t0 = devices, t_process_start
        self.trace_dir = os.path.join(scratch, "trace")
        self.control = control
        self.srv = None

    # ------------------------------------------------------------- set-up
    def set_up(self):
        import jax.numpy as jnp

        from mxnet_tpu import engine, serve
        from mxnet_tpu.base import next_pow2     # the server's own bucketing

        note("imports done")
        model = build.construct(self.config)
        model.collect_params().setattr("grad_req", "null")
        model.cast("bfloat16")
        self.specs = self.reference.param_specs(self.sizes)
        build.install_weights(
            self.config, list(model.collect_params().values()),
            weights.make(self.seed, self.specs, jnp.bfloat16))
        model.hybridize()
        note("model built, weights made from the seed")
        self.preroll = float(self.traffic.get("preroll_s", 0.0))
        self.requests = loadgen.schedule(
            self.traffic, self.seed, self.sizes["vocab_size"],
            self.preroll + self.seconds)
        buckets = {}
        for r in self.requests:
            n = len(r["prompt"])
            buckets[next_pow2(n)] = max(n, buckets.get(next_pow2(n), 0))
        need = max(len(r["prompt"]) + r["max_new_tokens"]
                   for r in self.requests)
        self.srv = serve.GenerativeServer(model, **self.config["server"])
        self.srv.warmup(prompt_buckets=[buckets[b] for b in sorted(buckets)],
                        max_tokens=max(need, int(self.traffic.get(
                            "capacity", 0))))
        note("server warmed: %d programs traced"
             % engine.decode_compile_counter.count)
        self.srv.start()
        # one request through the whole served path before the schedule
        warm = self.srv.submit(self.requests[0]["prompt"][:8],
                               max_new_tokens=4)
        warm.result(timeout_s=600.0)
        self.compiles = engine.decode_compile_counter
        self.model = model

    # ------------------------------------------------------------- window
    def window(self):
        from mxnet_tpu import profiler
        from mxnet_tpu.serve import ServeError

        import jax

        clients, failed_submit = [], []
        t_sched = time.perf_counter()
        t_open, t_close = t_sched + self.preroll, \
            t_sched + self.preroll + self.seconds
        marks = {}

        def tracer():
            slice_s = min(SLICE_S, self.seconds / 3.0)
            time.sleep(max(0.0, t_open + self.seconds / 3.0
                           - time.perf_counter()))
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            os.makedirs(self.trace_dir)
            # jax's trace is started here, with the benchmark's options; the
            # program's own switch then only has to say that it runs, so
            # that the server writes its decode[...] spans (its own attempt
            # to start a second trace is refused and passed over)
            trace_reduce.start(self.trace_dir)
            profiler.set_config(filename=os.path.join(self.trace_dir,
                                                      "profile.json"))
            profiler.set_state("run")
            with jax.profiler.TraceAnnotation(trace_reduce.MARK_START):
                marks["start"] = time.perf_counter()
            time.sleep(slice_s)
            with jax.profiler.TraceAnnotation(trace_reduce.MARK_END):
                marks["end"] = time.perf_counter()
            profiler.set_state("stop")

        tracer_thread = None
        if self.trace:
            tracer_thread = threading.Thread(target=tracer, daemon=True,
                                             name="bench-tracer")
            tracer_thread.start()
        c_open = None
        for r in self.requests:
            due = t_sched + r["due_s"]
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            if c_open is None and r["due_s"] >= self.preroll:
                c_open = self.compiles.count
            r["due"] = due
            r["sent"] = time.perf_counter()
            try:
                stream = self.srv.submit(
                    r["prompt"], max_new_tokens=r["max_new_tokens"])
            except ServeError as e:        # shed at the door
                r["error"] = e
                failed_submit.append(r)
                continue
            c = Client(r, stream)
            c.start()
            clients.append(c)
        time.sleep(max(0.0, t_close - time.perf_counter()))
        c_close = self.compiles.count
        # every answer due in the window is waited for, a minute if need be
        t_drain = time.perf_counter() + DRAIN_S
        for c in clients:
            c.join(max(0.0, t_drain - time.perf_counter()))
        t_end = time.perf_counter()
        if tracer_thread is not None:
            tracer_thread.join(120.0)
        self.clients = clients
        return self._record(clients, failed_submit, t_open, t_close, t_end,
                            c_close - (c_open or 0), marks)

    def _record(self, clients, failed_submit, t_open, t_close, t_end,
                compiles, marks):
        in_window = lambda r: t_open <= r["due"] < t_close
        ttft, itl, late, queue = [], [], [], []
        tokens_in_window, failed, attempted = 0, 0, 0
        for r in failed_submit:
            if in_window(r):
                attempted += 1
                failed += 1
                ttft.append((t_end - r["due"]) * 1e3)
        for c in clients:
            r = c.request
            st = np.asarray(c.stamps)
            tokens_in_window += int(np.sum((st >= t_open) & (st < t_close)))
            gaps = np.diff(st)
            itl.extend((gaps[(st[1:] >= t_open) & (st[1:] < t_close)]
                        * 1e3).tolist())
            if not in_window(r):
                continue
            attempted += 1
            late.append((r["sent"] - r["due"]) * 1e3)
            ok = (not c.is_alive() and c.error is None
                  and len(c.tokens) == r["max_new_tokens"])
            failed += 0 if ok else 1
            # a request that failed, was shed or timed out counts as the
            # worst: it waited until the drain ended
            ttft.append((st[0] - r["due"]) * 1e3 if ok
                        else (t_end - r["due"]) * 1e3)
            timing = c.stream.timing() if ok else None
            if timing is not None:
                queue.append(timing["queue_ms"])
        if itl:
            # where the tail lies among the gaps (read when a bound is set)
            qs = (50, 80, 85, 90, 93, 95, 97, 99)
            note("%d token gaps in the window, ms at p%s: %s" % (
                len(itl), "/".join(map(str, qs)), " ".join(
                    "%.2f" % v for v in np.percentile(itl, qs))))
        if ttft:
            # a queue that grows shows as first tokens that come later in
            # the window's second half (read when a rate is chosen)
            half = len(ttft) // 2
            note("first tokens, ms from due: p50 %.1f p90 %.1f p95 %.1f; "
                 "p50 of the earlier half %.1f, of the later %.1f" % (
                     *np.percentile(ttft, (50, 90, 95)),
                     np.median(ttft[:half] or ttft), np.median(ttft[half:])))
        record = {
            "attempted": attempted, "failed": failed,
            "scalars": {"setup_s": t_open - self.t0,
                        "window_s": t_close - t_open,
                        "tokens_received": tokens_in_window,
                        "compiles_in_window": compiles},
            "samples": {"ttft_ms": ttft, "itl_ms": itl, "late_ms": late,
                        "queue_ms": queue},
            "sizes": self.sizes, "traffic": self.traffic, "trace": None,
        }
        if self.trace:
            record["trace"] = trace_reduce.reduce_dir(self.trace_dir)
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            # what the server worked on inside the slice, from the client's
            # side: every token received there is one slot of one decode
            # step (or the end of a prefill) at a known context length
            lo, hi = marks["start"], marks["end"]
            decode_ctx, prefill_len = [], []
            for c in clients:
                n0 = len(c.request["prompt"])
                for j, t in enumerate(c.stamps):
                    if lo <= t < hi:
                        if j == 0:
                            prefill_len.append(n0)
                        else:
                            decode_ctx.append(n0 + j)
            record["samples"]["slice_decode_context"] = decode_ctx
            record["samples"]["slice_prefill_len"] = prefill_len
        return record

    def free(self):
        if self.srv is not None:
            self.srv.stop()       # ends every stream that is still open
        for c in getattr(self, "clients", ()):
            c.join(5.0)
        self.srv = self.model = None

    # -------------------------------------------------------------- check
    def check(self):
        import jax.numpy as jnp

        limits = self.cell["limits"]
        done = [c for c in self.clients
                if not c.is_alive() and c.error is None and c.tokens]
        out = {"requests_not_answered": (
            float(sum(1 for c in self.clients if c not in done or
                      len(c.tokens) != c.request["max_new_tokens"])), 0.0)}
        if not done:
            out["served_logit_gap"] = (float("nan"),
                                       limits["served_logit_gap"])
            return out
        rng = np.random.default_rng([int(self.seed), 0xc4ec])
        longest = max(done, key=lambda c: len(c.request["prompt"])
                      + len(c.tokens))
        k = min(int(self.traffic.get("check_requests", 6)) - 1,
                len(done) - 1)
        others = [c for c in done if c is not longest]
        sample = [longest] + [others[i] for i in
                              rng.choice(len(others), k, replace=False)]
        params = weights.make(self.seed, self.specs, jnp.bfloat16)
        # gaps by which a token's logit lies below the reference's best, at
        # every compared position: of the served tokens, and under
        # ``--control`` of the tokens that the reference in the lower
        # precision puts first at the same positions
        gaps = {"served": []}
        for c in sample:
            lg = np.asarray(self.reference.served_logits(
                self.sizes, params, c.request["prompt"], c.tokens))
            at = np.arange(len(c.tokens))
            best = lg.max(axis=-1)
            gaps["served"].append(best - lg[at, np.asarray(c.tokens)])
            if self.control:
                low = np.asarray(self.reference.served_logits(
                    self.sizes, params, c.request["prompt"], c.tokens,
                    precision=self.control)).argmax(axis=-1)
                gaps.setdefault("control", []).append(best - lg[at, low])
        gaps = {k: np.concatenate(v) for k, v in gaps.items()}
        if self.control:
            note("the served tokens themselves read: widest gap %.6g, mean "
                 "%.6g over %d tokens" % (gaps["served"].max(),
                                          gaps["served"].mean(),
                                          len(gaps["served"])))
        gap = gaps["control" if self.control else "served"]
        out["served_logit_gap"] = (float(gap.max()),
                                   limits["served_logit_gap"])
        # the widest gap swings by its nature; the mean over the same tokens
        # is the steady reading beside it, and the one a lower precision moves
        out["served_logit_gap_mean"] = (float(gap.mean()),
                                        limits["served_logit_gap_mean"])
        note("compared %d tokens of %d requests" % (len(gap), len(sample)))
        return out

    def close(self):
        if self.srv is not None:
            self.srv.stop()
        shutil.rmtree(self.trace_dir, ignore_errors=True)
