"""Serving observability (ref: mxnet-model-server metrics — QPS, latency
percentiles, queue telemetry — mms/metrics/*; here collected in-process).

One ``ServeMetrics`` instance per server/pool. Two export paths:

* ``snapshot()`` — the ``serve.stats()`` dict tools/diagnose.py prints:
  request/batch counters, shed/timeout/error counts, p50/p95/p99 request
  latency, mean batch-fill ratio, current queue depth;
* profiler counter events — when the profiler is running, queue depth and
  shed/timeout totals are emitted as Chrome-trace 'C' tracks (and each
  dispatched batch gets a ``serve[...]`` duration event from the pool via
  profiler.serve_scope), so serving pressure lines up with the XLA trace.

Latency percentiles come from a bounded ring of the most recent ``window``
request latencies — O(1) per request, no unbounded growth in long-running
servers (the same concern graphlint GL006 polices for caches).

These objects are ABSORBED by ``mxnet_tpu.observability``: the registry's
``serve`` collector reads every live server's ``stats()`` (this module's
snapshots) at snapshot time, so they appear in
``observability.snapshot()``/``prometheus()`` and the opt-in ``/metrics``
endpoint without any push-site wiring here — this module stays the
recording surface, the registry is the export surface (GL009 polices new
metric state landing anywhere else).
"""
from __future__ import annotations

import threading

import numpy as np

from .. import profiler


class ServeMetrics:
    def __init__(self, name="serve", window=2048):
        self.name = name
        self._lock = threading.Lock()
        self._window = int(window)
        self._lat = [0.0] * self._window  # ring buffer, ms
        self._lat_n = 0                   # total latencies ever recorded
        self.requests = 0                 # admitted requests
        self.completed = 0
        self.shed = 0                     # rejected at admission (ServerBusy)
        self.timeouts = 0                 # expired before a result arrived
        self.errors = 0                   # model/fault failures propagated
        self.batches = 0                  # dispatched batches
        self.batched_rows = 0             # real rows across batches
        self.bucket_rows = 0              # padded bucket rows across batches
        self.pad_rows = 0                 # bucket_rows - batched_rows, running
        self.row_bytes = None             # bytes per input row (server-set)
        # measured traffic shape — the autotuner's input (ir.tune
        # fit_buckets) and the pad-waste evidence pow2 defaults hide.
        # Both maps are bounded: request sizes are capped by the largest
        # admissible bucket and batches land on configured buckets only,
        # so keys ≤ max_bucket / len(buckets) — not per-request state
        # (GL006)
        self._request_rows = {}           # rows(int) -> request count
        self._bucket_hist = {}            # bucket -> {batches, rows, pad_rows}
        self._queue_depth = 0
        # work the server has admitted but not yet completed — with
        # queue_depth, the two gauges a fleet router scrapes per pick
        # (cheap /health reads, never a full snapshot parse)
        self._tokens_in_flight = 0
        # profiler 'C' counters are created lazily so importing serve never
        # touches profiler state; events are only emitted while it runs
        self._prof = None

    # ------------------------------------------------------------ recording
    def _counters(self):
        if self._prof is None:
            dom = profiler.Domain("serve")
            self._prof = {
                "queue": dom.new_counter("%s.queue_depth" % self.name),
                "shed": dom.new_counter("%s.shed" % self.name),
                "timeout": dom.new_counter("%s.timeouts" % self.name),
            }
        return self._prof

    def record_admit(self, n=1, rows=None):
        with self._lock:
            self.requests += n
            if rows is not None:
                r = int(rows)
                self._request_rows[r] = self._request_rows.get(r, 0) + 1

    def record_queue_depth(self, depth):
        with self._lock:
            self._queue_depth = depth
        if profiler.is_running():
            self._counters()["queue"].set_value(depth)

    def record_tokens_in_flight(self, n):
        """Gauge: tokens (generative) or rows (batch serving) admitted but
        not yet delivered — the load score a least-loaded router sums with
        queue depth."""
        with self._lock:
            self._tokens_in_flight = int(n)

    def load_gauges(self):
        """The two router-scraped gauges as a tiny dict — what the worker's
        ``/health`` endpoint embeds (no percentile sort, no history walk)."""
        with self._lock:
            return {"queue_depth": self._queue_depth,
                    "tokens_in_flight": self._tokens_in_flight}

    def record_shed(self, n=1):
        with self._lock:
            self.shed += n
            total = self.shed
        if profiler.is_running():
            self._counters()["shed"].set_value(total)

    def record_timeout(self, n=1):
        with self._lock:
            self.timeouts += n
            total = self.timeouts
        if profiler.is_running():
            self._counters()["timeout"].set_value(total)

    def record_error(self, n=1):
        with self._lock:
            self.errors += n

    def record_batch(self, n_real, bucket):
        with self._lock:
            self.batches += 1
            self.batched_rows += int(n_real)
            self.bucket_rows += int(bucket)
            self.pad_rows += max(0, int(bucket) - int(n_real))
            h = self._bucket_hist.get(int(bucket))
            if h is None:
                h = self._bucket_hist[int(bucket)] = {
                    "batches": 0, "rows": 0, "pad_rows": 0}
            h["batches"] += 1
            h["rows"] += int(n_real)
            h["pad_rows"] += max(0, int(bucket) - int(n_real))

    def request_rows(self):
        """Measured request-size histogram ``{rows: count}`` — what
        ``ir.tune.fit_buckets`` fits bucket sets to."""
        with self._lock:
            return dict(self._request_rows)

    def record_latency(self, ms):
        with self._lock:
            self._lat[self._lat_n % self._window] = float(ms)
            self._lat_n += 1
            self.completed += 1

    # ------------------------------------------------------------ snapshot
    def _percentiles(self):
        n = min(self._lat_n, self._window)
        if n == 0:
            return {"p50_ms": None, "p95_ms": None, "p99_ms": None}
        vals = sorted(self._lat[:n])
        # nearest-rank on the retained window
        pick = lambda q: vals[min(n - 1, int(q * (n - 1) + 0.5))]  # noqa: E731
        return {"p50_ms": round(pick(0.50), 3),
                "p95_ms": round(pick(0.95), 3),
                "p99_ms": round(pick(0.99), 3)}

    def snapshot(self):
        with self._lock:
            snap = {
                "name": self.name,
                "requests": self.requests,
                "completed": self.completed,
                "shed": self.shed,
                "timeouts": self.timeouts,
                "errors": self.errors,
                "batches": self.batches,
                "queue_depth": self._queue_depth,
                "tokens_in_flight": self._tokens_in_flight,
                "batch_fill_ratio": (round(self.batched_rows
                                           / self.bucket_rows, 4)
                                     if self.bucket_rows else None),
                "mean_batch_size": (round(self.batched_rows / self.batches, 2)
                                    if self.batches else None),
                "latency_window": min(self._lat_n, self._window),
                "pad_rows_total": self.pad_rows,
                "pad_waste_bytes": (self.pad_rows * self.row_bytes
                                    if self.row_bytes else None),
                "request_rows": {str(r): c for r, c in
                                 sorted(self._request_rows.items())},
                "bucket_hist": {str(b): dict(h) for b, h in
                                sorted(self._bucket_hist.items())},
            }
            snap.update(self._percentiles())
        return snap


def _ring_percentiles(ring, n, prefix):
    """Nearest-rank p50/p95/p99 over the retained window of a latency ring
    (same estimator as ServeMetrics._percentiles)."""
    out = {"%s_p50_ms" % prefix: None, "%s_p95_ms" % prefix: None,
           "%s_p99_ms" % prefix: None}
    if n == 0:
        return out
    vals = sorted(ring[:n])
    pick = lambda q: vals[min(n - 1, int(q * (n - 1) + 0.5))]  # noqa: E731
    out["%s_p50_ms" % prefix] = round(pick(0.50), 3)
    out["%s_p95_ms" % prefix] = round(pick(0.95), 3)
    out["%s_p99_ms" % prefix] = round(pick(0.99), 3)
    return out


def _max_over_mean(load):
    """The largest expert's load over the mean load, the worst layer's, of
    a (layers, experts) array of picks (1.0 where a layer has none)."""
    mean = load.mean(axis=1)
    return float(np.max(load.max(axis=1) / np.where(mean > 0, mean, 1.0)))


class GenerativeMetrics(ServeMetrics):
    """ServeMetrics plus the token-level counters autoregressive serving
    is judged by: tokens/s (over decode-active wall time, not idle time),
    time-to-first-token (admission → first sampled token, the user-visible
    prefill latency), inter-token latency (one decode step of the shared
    batch), and in-flight batch fill (live slots / padded slots — how much
    of every decode dispatch is real work)."""

    def __init__(self, name="serve", window=2048):
        super().__init__(name, window)
        self._ttft = [0.0] * self._window   # admission → first token, ms
        self._ttft_n = 0
        self._itl = [0.0] * self._window    # per decode step, ms
        self._itl_n = 0
        self._itl_pf = [0.0] * self._window  # steps under chunked prefill
        self._itl_pf_n = 0
        self.tokens = 0                     # generated tokens, all requests
        self.steps = 0                      # decode dispatches
        self.prefills = 0                   # whole-prompt forward dispatches
        self.prefill_rows = 0               # their prompts' tokens
        self.prefill_bucket_rows = 0        # their padded buckets' rows
        self.prefill_chunks = 0             # chunked-prefill dispatches
        self._decode_s = 0.0                # decode-active wall time
        self._active_slot_steps = 0         # live slots summed over steps
        self._slot_steps = 0                # padded slots summed over steps
        # speculative decode: drafted = proposals offered to verify
        # (active_slots × (k-1) per round), accepted = proposals the target
        # kept — accepted/drafted is the accept rate the k-vs-overhead
        # trade lives or dies by
        self.spec_rounds = 0
        self.drafted_tokens = 0
        self.accepted_tokens = 0
        # TTFT split by pow2 prompt-length bucket: long prompts have
        # honest multi-chunk TTFTs and must not hide behind short-prompt
        # medians (each bucket gets its own ring → per-bucket percentiles
        # under `bucket=` labels in /metrics)
        self._ttft_by_bucket = {}           # bucket(int) -> [ring, n]
        # a routing model (the server's spec ``routed``): picks that went
        # to each expert held, by layer, those that went to experts held
        # elsewhere, and the last decode step's imbalance
        self._expert_load = None            # (layers, held) int64
        self.picks_elsewhere = 0
        self._xmax = None
        self._xhit = 0

    @staticmethod
    def _pow2_bucket(n):
        b = 1
        while b < n:
            b <<= 1
        return b

    def record_first_token(self, ms, prompt_len=None):
        with self._lock:
            self._ttft[self._ttft_n % self._window] = float(ms)
            self._ttft_n += 1
            self.tokens += 1   # the first token is sampled by prefill
            if prompt_len is not None:
                b = self._pow2_bucket(int(prompt_len))
                ent = self._ttft_by_bucket.get(b)
                if ent is None:
                    # bounded: one ring per pow2 bucket, log2(max_length)
                    # buckets total — not per-prompt state (GL006)
                    ent = self._ttft_by_bucket[b] = [[0.0] * self._window, 0]
                ent[0][ent[1] % self._window] = float(ms)
                ent[1] += 1

    def record_prefill(self, rows=0, bucket=0):
        """One prefill; a whole-prompt one gives its prompt's ``rows`` and
        the ``bucket`` it was padded to: ``1 - prefill_rows /
        prefill_bucket_rows`` is the share of the rows computed whose
        attention the flash forward skips."""
        with self._lock:
            self.prefills += 1
            self.prefill_rows += int(rows)
            self.prefill_bucket_rows += int(bucket)

    def record_chunk(self, n=1):
        with self._lock:
            self.prefill_chunks += n

    def record_step(self, step_s, n_tokens, n_active, slots,
                    under_prefill=False):
        """One decode (or verify) dispatch: ``n_tokens`` emitted across
        ``n_active`` live slots. ``under_prefill`` marks steps taken while
        chunked prefills were in flight — their ITLs land in a separate
        ``itl_prefill`` ring so the interference chunking is supposed to
        bound is directly measurable."""
        with self._lock:
            self._itl[self._itl_n % self._window] = float(step_s) * 1e3
            self._itl_n += 1
            if under_prefill:
                self._itl_pf[self._itl_pf_n % self._window] = \
                    float(step_s) * 1e3
                self._itl_pf_n += 1
            self.steps += 1
            self.tokens += int(n_tokens)
            self._decode_s += float(step_s)
            self._active_slot_steps += int(n_active)
            self._slot_steps += int(slots)

    def record_expert_load(self, load, tag=False):
        """``load`` (layers, held + 1) int: the picks one prefill or decode
        step sent to each expert held and, last, to experts held elsewhere.
        With ``tag`` (a decode step's, while the profiler runs) it also sets
        what ``expert_tag`` reports: the largest expert's load over the mean
        load, the worst layer's, and the number of (layer, expert) pairs
        that got a pick."""
        load = np.asarray(load, np.int64)
        here = load[:, :-1]
        with self._lock:
            if self._expert_load is None:
                self._expert_load = np.zeros_like(here)
            self._expert_load += here
            self.picks_elsewhere += int(load[:, -1].sum())
            if tag:
                self._xmax = _max_over_mean(here)
                self._xhit = int((here > 0).sum())

    def expert_tag(self):
        """``xmax=<...> xhit=<...>`` of the last traced decode step for its
        successor's span (profiler.decode_scope), or None before the first:
        the imbalance, and how many (layer, expert) pairs got a pick, which
        is how many experts' matrices the step had to read."""
        x = self._xmax
        return None if x is None else "xmax=%.2f xhit=%d" % (x, self._xhit)

    def record_spec_round(self, drafted, accepted):
        with self._lock:
            self.spec_rounds += 1
            self.drafted_tokens += int(drafted)
            self.accepted_tokens += int(accepted)

    def snapshot(self):
        snap = super().snapshot()
        with self._lock:
            snap.update({
                "tokens": self.tokens,
                "decode_steps": self.steps,
                "prefills": self.prefills,
                "prefill_rows": self.prefill_rows,
                "prefill_bucket_rows": self.prefill_bucket_rows,
                "prefill_chunks": self.prefill_chunks,
                "tokens_per_s": (round(self.tokens / self._decode_s, 1)
                                 if self._decode_s > 0 else None),
                "inflight_fill": (round(self._active_slot_steps
                                        / self._slot_steps, 4)
                                  if self._slot_steps else None),
                "spec_rounds": self.spec_rounds,
                "drafted_tokens": self.drafted_tokens,
                "accepted_tokens": self.accepted_tokens,
                "accept_rate": (round(self.accepted_tokens
                                      / self.drafted_tokens, 4)
                                if self.drafted_tokens else None),
            })
            if self._expert_load is not None:
                here = self._expert_load
                snap.update({
                    "expert_picks_here": int(here.sum()),
                    "expert_picks_elsewhere": self.picks_elsewhere,
                    "expert_load": here.tolist(),
                    "expert_load_max_over_mean": _max_over_mean(here),
                })
            snap.update(_ring_percentiles(
                self._ttft, min(self._ttft_n, self._window), "ttft"))
            snap.update(_ring_percentiles(
                self._itl, min(self._itl_n, self._window), "itl"))
            snap.update(_ring_percentiles(
                self._itl_pf, min(self._itl_pf_n, self._window),
                "itl_prefill"))
            snap["ttft_by_bucket"] = {
                str(b): {
                    k.replace("b_", ""): v for k, v in _ring_percentiles(
                        ring, min(n, self._window), "b").items()}
                for b, (ring, n) in sorted(self._ttft_by_bucket.items())}
        return snap
