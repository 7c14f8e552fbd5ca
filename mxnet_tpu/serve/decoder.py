"""GenerativeServer — token-level continuous batching over a paged KV cache.

The autoregressive complement of ``ModelServer``: instead of coalescing
whole fixed-shape forward passes, the scheduler coalesces TOKEN STEPS.
Requests join and leave between steps by slot assignment into a padded
batch (μ-cuDNN-style request decomposition, arXiv 1804.04806, applied to
the decode loop); every step runs ONE fused compiled program for the whole
in-flight batch — embed → N transformer blocks (each writing its slot's
K/V in place at its own position) → logits → SAMPLING (greedy + temperature
/top-k over per-slot threefry keys) — so there is no per-step host argmax
and exactly one dispatch per token step with zero steady-state retrace
(``engine.decode_compile_counter`` bumps inside the traced bodies, the same
proof-hook discipline as ``serve_compile_counter``).

Prefill is split from decode (the compute-bound vs. latency-bound halves):
a joining request's whole prompt runs through one forward pass at a pow2
prompt-length bucket, writing its cache page in a single dispatch and
sampling the first token inside the program. Identical prompts hit the
``PrefixCache`` instead: the stored pages are injected by a tiny compiled
program, skipping the forward entirely.

Admission reuses ``DynamicBatcher``'s bounded queue — priority classes and
SLO-aware preemptive shedding (batcher.submit) apply to generation
requests unchanged; per-request deadlines keep ticking while a request
waits for a slot and mid-stream. Tokens stream back through per-request
iterators (``GenerationStream``).

    m = gpt_nano(); m.initialize()
    srv = mxnet_tpu.serve.GenerativeServer(m, slots=8, eos_id=None)
    with srv:
        s = srv.submit([1, 2, 3], max_new_tokens=16, temperature=0.8)
        for tok in s:          # streams as decode steps complete
            print(tok)
"""
from __future__ import annotations

import contextlib
import os
import queue
import re
import threading
import time
from collections import deque

import numpy as np

import jax
import jax.numpy as jnp

from .. import _trace, engine, profiler
from ..base import next_pow2
from .batcher import DynamicBatcher, ServeError, ServeTimeout
from . import kv_cache
from .kv_cache import CacheError, PagedKVCache, PrefixCache
from .metrics import GenerativeMetrics

_DONE = object()
_NO_SPAN = contextlib.nullcontext()   # what a tick enters with the profiler off


def sample_tokens(logits, keys, positions, temps, top_k):
    """Fused in-program sampling: greedy argmax per slot, or temperature/
    top-k categorical when ``temps[slot] > 0``. Each slot's threefry key is
    folded with the generated token's sequence position, so a request's
    token stream is deterministic in (seed, position) and independent of
    every other in-flight request. Runs INSIDE the compiled step — the
    sampled ids are the only thing the host reads back."""
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def _sampled(operands):
        lg, ks, pos, tp = operands
        if top_k and top_k > 0:
            kth = jax.lax.top_k(lg, int(top_k))[0][:, -1:]
            lg = jnp.where(lg < kth, -jnp.inf, lg)
        scaled = lg / jnp.maximum(tp, 1e-6)[:, None]
        subkeys = jax.vmap(jax.random.fold_in)(ks, pos)
        sampled = jax.vmap(jax.random.categorical)(subkeys, scaled)
        return jnp.where(tp > 0, sampled.astype(jnp.int32), greedy)

    # the categorical branch (top-k + per-row threefry fold/bits) is the
    # expensive half; lax.cond skips it AT RUNTIME for all-greedy batches —
    # the speculative verify samples S*K rows per round, so it saves k×
    # what the plain step does
    return jax.lax.cond(jnp.any(temps > 0), _sampled,
                        lambda operands: greedy,
                        (logits, keys, positions, temps))


class GenerationStream:
    """Per-request streaming handle: iterate generated token ids as decode
    steps complete, or block for the full sequence with ``result()``.
    Queue-phase failures (shed by priority admission, queue timeout) and
    mid-stream failures (deadline, server stop) surface as the typed
    serve exceptions on the consumer side."""

    def __init__(self, prompt, max_new_tokens, temperature, seed, priority):
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        if self.prompt.size == 0:
            raise ServeError("empty prompt")
        self.max_new_tokens = max(1, int(max_new_tokens))
        self.temperature = float(temperature)
        self.seed = int(seed)
        self.priority = int(priority)
        self.tokens = []          # generated ids, in order
        self._prompt_ids = None   # lazy python-int view for draft histories
        self._q = queue.Queue()
        self._done = threading.Event()
        self._error = None
        self._admission = None    # batcher request handle (queue-phase SLO)
        # observability.RequestTrace (set at submit; None = tracing off):
        # queue/pad/dispatch spans + per-token step attribution; read the
        # breakdown from stream.timing() when the stream completes
        self.trace = None

    def prompt_ids(self):
        """Prompt as a list of python ints, converted once — the draft
        history path reads it every speculation round."""
        if self._prompt_ids is None:
            self._prompt_ids = [int(x) for x in self.prompt]
        return self._prompt_ids

    # ------------------------------------------------------- producer side
    def _push(self, tok):
        self.tokens.append(int(tok))
        self._q.put(int(tok))

    def _finish(self, error=None):
        if self._done.is_set():
            return False
        self._error = error
        self._done.set()
        self._q.put(_DONE)
        return True

    # ------------------------------------------------------- consumer side
    def _check_admission(self):
        # the batcher fails queue-phase requests (timeout sweep, preemptive
        # shed) on ITS handle; mirror that failure onto the stream
        a = self._admission
        if a is not None and a.done() and a._error is not None:
            self._finish(a._error)

    def __iter__(self):
        while True:
            self._check_admission()
            try:
                item = self._q.get(timeout=0.05)
            except queue.Empty:
                continue
            if item is _DONE:
                break
            yield item
        if self._error is not None:
            raise self._error

    def done(self):
        return self._done.is_set()

    @property
    def trace_id(self):
        return self.trace.trace_id if self.trace is not None else None

    def timing(self):
        """Per-request breakdown (queue_ms/pad_ms/dispatch_ms/tokens);
        None when tracing is disabled."""
        return self.trace.timing() if self.trace is not None else None

    def result(self, timeout_s=None):
        """Block until generation completes; returns the list of generated
        token ids (prompt excluded). Raises the typed failure if the
        request was shed, timed out, or errored."""
        deadline = (time.perf_counter() + timeout_s) if timeout_s else None
        while not self._done.wait(0.05):
            self._check_admission()
            if deadline is not None and time.perf_counter() > deadline:
                raise ServeTimeout("no completion within %.1fs" % timeout_s)
        if self._error is not None:
            raise self._error
        return list(self.tokens)


class _StepInFlight:
    """A decode step the device has been sent and the host has not read yet,
    with what it was sent with: the array the host will read (the tokens,
    behind them a routing model's aux), the stream that owned each live slot
    at the dispatch ({slot: stream}), and when it went out."""
    __slots__ = ("host", "streams", "t0")

    def __init__(self, host, streams, t0):
        self.host, self.streams, self.t0 = host, streams, t0


class GenerativeServer:
    """Continuous-batching generative decode scheduler.

    Parameters
    ----------
    model : block implementing the served decode protocol, three methods
        (``models.gpt.GPTModel`` is the reference implementation;
        ``models.cohere_moe.CohereMoEModel`` routes;
        ``models.brumby.BrumbyModel`` keeps a recurrent state in the place
        of K and V; ``models.latent_moe.LatentMoEModel`` keeps one
        compressed row a position). Must be initialized;
        its parameter dtype decides the cache dtype.

        ``decode_state_spec()``: the cache geometry (``layers``, ``heads``,
        ``head_dim``, ``max_length``, ``dtype``; layer by layer
        ``kv_heads``, the K/V heads a buffer under grouped-query attention,
        and ``windows``, a ring length for each sliding-window layer,
        ``None`` for a full page) and what the model's step can take:
        ``int8_pages`` (int8 pages: ``quantize``), ``multi_token`` (more
        than one token a slot: ``draft``, ``prefill_chunk``), ``page`` (a
        page record of the model's own), ``routed`` (the shape of the
        ``aux`` array below, for ``stats()``).

        ``forward_collect_kv(F, tokens, plen) -> (logits, kvs, aux)``: the
        prefill. ``tokens`` (1, bucket) holds a prompt of ``plen`` tokens;
        ``kvs`` is one (K, V) a layer as the pages keep them; ``logits``
        every row's, or row ``plen - 1`` alone (1, 1, V).

        ``decode_step(F, tokens, state, valid_len, active) -> (logits,
        state, aux)``: ``tokens`` (slots, K): K = 1 is the decode step,
        ``spec_k`` the verify window, the chunk the chunked prefill;
        ``valid_len`` (slots,) the tokens cached; ``active`` (slots,) 0/1:
        a free slot reads nothing of its page.

        ``state`` is the cache's: one page record a layer
        (``serve/kv_cache.py``: ``PlainPage``, ``Int8Page``; ``StatePage``,
        a recurrent state of fixed size in the place of K and V, whose
        prefill hands over (S, z) a layer; ``LatentPage``, one compressed
        row a position, whose prefill hands over (c_kv, k_pe) a layer),
        whose type says its format.
        The server carries it from program to program (donated) and never
        looks inside: the model's attention layer reads
        and writes a page, the record's own methods move a prompt or a slot
        in and out of it. ``aux`` is ``None`` or an array the host reads
        behind the tokens (a routing model's expert load: pad rows and free
        slots route nowhere).
    slots : int
        In-flight request pages — the padded decode batch. One decode
        dispatch serves all of them; free slots are masked, so join/leave
        never recompiles.
    top_k : int
        STATIC top-k filter compiled into the sampling head (0 = off).
        Temperature is per-request (0 = greedy) and traced, so mixing
        greedy and sampled requests in one batch costs nothing.
    eos_id : int or None
        Token id that completes a request early.
    max_wait_ms / max_queue / timeout_ms
        Admission-queue knobs, as in ModelServer. ``max_queue`` is in
        REQUESTS; priority classes and SLO-aware preemptive shedding are
        the DynamicBatcher's (see batcher.submit).
    prefix_cache : bool
        Cache finished prefills keyed by the prompt's token hash; a repeat
        prompt injects the stored pages instead of re-running the forward.
    donate : bool or None
        Donate cache/state buffers to the step programs (default: ON —
        the executor-pool donation discipline; hlolint GL022 flags the
        per-step KV page allocation the undonated programs would make).
        Safe on every backend: ``cache.update()`` replaces the host
        references after each call, so a donated-away buffer is never
        re-read. The per-slot input tokens are never donated: the host
        reads a step's tokens after they have gone into the next program
        (``_decode_once``). ``MXNET_DECODE_DONATE=0`` force-disables
        (debugging escape hatch: keeps step inputs alive for inspection).
    quantize : None or 'int8' / 'e4m3' / 'e5m2'
        Quantized serving: weight-quantize the model in place
        (``quantization.quantize_model`` — per-channel quantized matmuls
        with MXU accumulation) AND store KV pages as int8 with per-page-
        per-head scales. Decode stays ONE dispatch per token step with
        zero steady-state retrace; the cache costs ~0.5× the bf16 bytes.
        The model's spec must say ``int8_pages`` (GPTModel's does). fp8
        modes require :func:`quantization.fp8_supported`.
    draft : None, speculative draft object, or a draft model
        Enables speculative decode: per scheduler tick the draft proposes
        ``spec_k - 1`` tokens per slot and the target scores the whole
        window in ONE wide verify dispatch (``decode_step`` with K =
        ``spec_k``), emitting 1..spec_k tokens. Pass ``serve.NGramDraft()``
        (host-side pattern matcher, zero extra dispatches),
        ``serve.ModelDraft(m)``
        (a smaller same-API model, one multi-step dispatch per round), or
        a bare model (wrapped in ``ModelDraft``). Greedy streams emit
        byte-identical tokens to plain greedy decode; sampled streams emit
        the same per-(seed, position) tokens as the plain path (the
        deterministic-draft rejection-sampling identity — see
        serve.speculative).
    spec_k : int
        Verify window width (tokens scored per verify dispatch) when a
        ``draft`` is set; ``spec_k=1`` degenerates to plain decode through
        the verify program. Static — compiled into the window shape.
    prefill_chunk : None or int
        Chunked prefill budget (pow2-rounded): prompts longer than this
        fill their cache page in fixed ``prefill_chunk``-sized chunks, ONE
        chunk per scheduler tick interleaved with decode steps, so a long
        prompt never stalls in-flight streams for more than one chunk.
        Chunked prompts bypass the prefix cache (partial pages are never
        stored). Must be >= ``spec_k`` when both are set (in-flight
        speculation windows must stay behind the chunk frontier).
    """

    def __init__(self, model, slots=8, top_k=0, eos_id=None,
                 max_wait_ms=1.0, max_queue=64, timeout_ms=30000.0,
                 prefix_cache=True, donate=None, name=None,
                 metrics_port=None, quantize=None, draft=None, spec_k=4,
                 prefill_chunk=None):
        self._quantize = quantize or None
        spec = model.decode_state_spec()
        if self._quantize is not None:
            if not spec.get("int8_pages"):
                raise ServeError(
                    "quantize=%r: model %s serves no int8 pages (its "
                    "decode_state_spec() does not say int8_pages; see "
                    "models.gpt.GPTModel)" % (quantize, type(model).__name__))
            from ..quantization import quantize_model

            # weight quantization BEFORE the param-list capture below so
            # the serving param store carries qweight/w_scale pages;
            # idempotent on an already-quantized model (snapshot load)
            quantize_model(model, mode=self._quantize)
        self.model = model
        self.name = name or ("generate:%s" % type(model).__name__.lower())
        self.slots = int(slots)
        self.top_k = int(top_k)
        self.eos_id = eos_id
        self.timeout_ms = float(timeout_ms)
        self._plist = list(model.collect_params().values())
        # hot-swap seam: every dispatch snapshots the param list under
        # this lock (_params), and swap_parameters writes under it — a
        # decode step sees all-old or all-new weights, never a mix
        self._params_lock = threading.Lock()
        self._swap_epoch = 0
        self.cache = PagedKVCache(
            spec["layers"], spec.get("kv_heads", spec["heads"]),
            spec["head_dim"], self.slots, spec["max_length"],
            dtype=spec["dtype"], quantize=self._quantize is not None,
            windows=spec.get("windows"), page=spec.get("page"))
        # (layers, columns) of the expert-load array a routing model's
        # programs return as their aux; None for a dense model
        self._routed = spec.get("routed")
        self.prefix = PrefixCache() if prefix_cache else None
        self.metrics = GenerativeMetrics(self.name)
        if donate is None:
            # default ON everywhere (not just TPU): the step/prefill/
            # inject programs overwrite their cache args wholesale, and
            # cache.update() drops the stale references after every
            # call, so aliasing input→output buffers is always safe and
            # saves one KV-page allocation per dispatch (hlolint GL022)
            donate = os.environ.get("MXNET_DECODE_DONATE", "1") != "0"
        self._donate = bool(donate)
        # compiled-program caches: the pow2 bucketing bounds each at
        # log2(max) entries — the executor-pool discipline
        self._decode_fns = {}    # capacity -> jitted step
        self._prefill_fns = {}   # (tp, capacity) -> jitted prompt fill
        self._inject_fns = {}    # (tp, capacity) -> jitted prefix replay
        self._extract_fns = {}   # (tp, capacity) -> jitted page read-out
        self._verify_fns = {}    # capacity -> jitted speculative verify
        self._chunk_fns = {}     # (tc, capacity) -> jitted prefill chunk
        # speculative decode: draft proposer + static verify window width
        self.spec_k = max(1, int(spec_k))
        if draft is not None and not hasattr(draft, "propose"):
            from .speculative import ModelDraft

            draft = ModelDraft(draft)   # a bare model: wrap it
        self._draft = draft
        if self._draft is not None:
            if not spec.get("multi_token"):
                raise ServeError(
                    "draft: model %s takes one token a slot (its "
                    "decode_state_spec() does not say multi_token) — the "
                    "verify window is decode_step with K = spec_k (see "
                    "models.gpt.GPTModel)" % type(model).__name__)
            self._draft.bind(self)
        # speculation windows write K/V through valid+spec_k-1: capacity
        # sizing must leave that margin past the generation budget or the
        # clamped window write would fold back onto live positions
        self._spec_margin = (self.spec_k - 1) if self._draft is not None \
            else 0
        # chunked prefill: pow2 chunk budget + in-flight chunk jobs
        # (slot -> job dict); slots mid-chunk are owned but masked out of
        # decode until their final chunk lands
        self._prefill_chunk = None
        if prefill_chunk is not None:
            if not spec.get("multi_token"):
                raise ServeError(
                    "prefill_chunk: model %s takes one token a slot (its "
                    "decode_state_spec() does not say multi_token) — a "
                    "chunk is decode_step with K = the chunk (see "
                    "models.gpt.GPTModel)" % type(model).__name__)
            self._prefill_chunk = next_pow2(max(8, int(prefill_chunk)))
            if self._draft is not None and self._prefill_chunk < self.spec_k:
                raise ServeError(
                    "prefill_chunk=%d < spec_k=%d: speculation windows "
                    "must fit behind the chunk frontier"
                    % (self._prefill_chunk, self.spec_k))
        self._chunk_jobs = {}
        # snapshots of a recurrent state (a ``StatePage`` pool) read out to
        # the prefix store and injected from it: [count, bytes] each way
        self._snapshots_out, self._snapshots_in = [0, 0], [0, 0]
        # device-side carried state beyond the cache: current input token
        # per slot, and the per-slot sampling controls
        self._tok = jnp.zeros((self.slots,), jnp.int32)
        self._keys = np.zeros((self.slots, 2), np.uint32)
        self._temps = np.zeros((self.slots,), np.float32)
        self._dev_keys = None
        self._dev_temps = None
        self._dev_active = None
        self._ctl_dirty = True
        self._ctl_mask = None    # the mask _dev_active holds
        # the plain decode path keeps ONE step in flight ahead of the host
        # (_decode_once): sent, not read yet
        self._flight = None
        self._t_read = 0.0       # when the host last read a step's tokens
        self._steps_ahead = 0    # steps sent while another was in flight
        self._rows_discarded = 0  # rows computed for a stream that had ended
        # host bookkeeping per slot
        self._slot_req = [None] * self.slots   # admission handle (deadline)
        self._remaining = [0] * self.slots     # tokens left to generate
        self._join_q = deque()
        self._join_cond = threading.Condition()
        self._batcher = DynamicBatcher(
            self._admit_batch, max_batch=self.slots, max_wait_ms=max_wait_ms,
            max_queue=max_queue, num_dispatchers=1, metrics=self.metrics)
        self._loop_thread = None
        self._stop_flag = False
        self._idle = None   # the open decode[idle] span (profiler running)
        # opt-in /metrics scrape endpoint (observability.http); None = off
        self._metrics_port = metrics_port
        self.metrics_http = None
        from . import _register
        _register(self)

    # ------------------------------------------------------------ lifecycle
    def start(self):
        """Start the background scheduler loop (admit → one fused decode
        step → stream tokens, forever). Tests drive the same tick
        synchronously via :meth:`step`."""
        self._batcher.start()
        if self._metrics_port is not None and self.metrics_http is None:
            from ..observability import MetricsHTTPServer

            self.metrics_http = MetricsHTTPServer(self._metrics_port,
                                                  health_fn=self.health)
        if self._loop_thread is None or not self._loop_thread.is_alive():
            self._stop_flag = False
            self._loop_thread = threading.Thread(
                target=self._loop, daemon=True, name="serve-decode")
            self._loop_thread.start()
        return self

    def stop(self, timeout_s=5.0, reason="server stopped"):
        """Stop the scheduler loop, reject everything in flight, and tear
        the dispatcher pool down. The loop join is bounded by
        ``timeout_s``; active slots are retired and the join queue is
        drained only AFTER the join, so slot tables keep their
        single-writer discipline (racecheck GL011 allowlist). Idempotent,
        and start() after stop() rebuilds every thread — repeated cycles
        leak no threads (pinned by tests/test_concurrency.py)."""
        self._stop_flag = True
        with self._join_cond:
            self._join_cond.notify_all()
        loop, self._loop_thread = self._loop_thread, None
        if loop is not None:
            loop.join(timeout=timeout_s)
        self._batcher.stop(drain=False, timeout_s=timeout_s, reason=reason)
        self._flight = None   # the step in flight is dropped, never read
        for slot in self.cache.active_slots:
            self._retire(slot, error=ServeError(reason))
        with self._join_cond:
            pending = list(self._join_q)
            self._join_q.clear()
        for req in pending:
            err = ServeError(reason)
            if req.finish(error=err):
                req.inputs._finish(err)
        if self.metrics_http is not None:
            self.metrics_http.close()
            self.metrics_http = None

    def __enter__(self):
        return self.start()

    def __exit__(self, *a):
        self.stop()

    # ------------------------------------------------------------ hot swap
    def _params(self):
        """Per-dispatch param snapshot — the seam swap_parameters flips
        through (one coherent weight set per compiled call)."""
        with self._params_lock:
            return [p.data()._data for p in self._plist]

    def swap_parameters(self, params_file):
        """Zero-downtime weight hot-swap for the generative server:
        structural validation (``checkpoint.validate_swap`` — a mismatched
        tree, including quantized qweight/w_scale pages, is rejected with
        the old weights still serving), then an atomic flip under the
        per-dispatch param lock. The prefix cache is flushed — its stored
        KV pages were computed by the OLD weights; in-flight streams keep
        their already-written pages and finish (continuity over purity:
        no request is dropped by a swap). Returns the new swap epoch."""
        from ..checkpoint import validate_swap
        from ..ndarray import NDArray

        picked = validate_swap(self.model, params_file)
        params = self.model._collect_params_with_prefix()
        staged = {n: NDArray(jnp.asarray(a)) for n, a in picked.items()}
        with self._params_lock:
            for name, arr in staged.items():
                params[name].set_data(arr)
            self._swap_epoch += 1
        if self.prefix is not None:
            self.prefix._store.clear()
        return self._swap_epoch

    # ----------------------------------------------------------- fleet
    def tokens_in_flight(self):
        """Gauge: tokens still owed across live slots + queued admissions
        (each queued request owes at least its max_new_tokens=… budget is
        unknown until join, so queued requests count 1 row each via the
        batcher queue) — the router's least-loaded score component."""
        owed = sum(self._remaining[s] for s in self.cache.active_slots)
        return int(owed)

    def health(self):
        """Cheap liveness payload for ``/health`` (and the fleet router's
        per-pick scrape): warm flag + load gauges, no ring sorts."""
        tif = self.tokens_in_flight()
        self.metrics.record_tokens_in_flight(tif)
        return {"warm": bool(self._decode_fns or self._prefill_fns),
                "running": (self._loop_thread is not None
                            and self._loop_thread.is_alive()),
                "kind": "generative",
                "queue_depth": self._batcher.queue_depth(),
                "in_flight": self.cache.num_active,
                "tokens_in_flight": tif,
                "swap_epoch": self._swap_epoch}

    def export_prefixes(self):
        """Read the prefix cache out as host arrays for cross-process
        migration: [(tokens, k_stack, v_stack, prompt_len, last_logits)].
        The retirement path: a draining worker exports, the sibling that
        inherits its sessions imports, and multi-turn conversations keep
        their KV pages across the retire."""
        if self.prefix is None:
            return []
        out = []
        for key, ent in list(self.prefix._store.items()):
            k_stack, v_stack, plen, last = ent
            out.append((np.asarray(key, np.int32), k_stack, v_stack,
                        int(plen), last))
        return out

    def import_prefixes(self, entries):
        """Adopt migrated prefix entries (see export_prefixes). Stored
        host-side; the next prompt hit injects them through the compiled
        inject program like any locally-computed prefix."""
        if self.prefix is None:
            return 0
        n = 0
        for tokens, k_stack, v_stack, plen, last in entries:
            self.prefix.put(tokens, k_stack, v_stack, plen, last)
            n += 1
        return n

    # ------------------------------------------------------------ admission
    def submit(self, prompt, max_new_tokens=16, temperature=0.0, seed=0,
               priority=0, timeout_ms=None):
        """Enqueue one generation request; returns a ``GenerationStream``.
        Sheds with ``ServerBusy`` when the admission queue is full (unless
        ``priority`` preempts a lower class — see DynamicBatcher.submit);
        the deadline covers queue wait, prefill AND generation."""
        stream = GenerationStream(prompt, max_new_tokens, temperature, seed,
                                  priority)
        tmo = self.timeout_ms if timeout_ms is None else float(timeout_ms)
        # fail impossible requests at the door, not after a queue wait
        self.cache.capacity_bucket(stream.prompt.size + stream.max_new_tokens
                                   + self._spec_margin)
        if not self._batcher._worker or not self._batcher._worker.is_alive():
            self._batcher.start()
        from ..observability import new_trace

        stream.trace = new_trace(self.name)
        req = self._batcher.submit(stream, 1, timeout_ms=tmo,
                                   priority=priority, trace=stream.trace)
        stream._admission = req
        return stream

    def generate(self, prompt, **kwargs):
        """Synchronous convenience: submit + wait; returns generated ids."""
        tmo = kwargs.get("timeout_ms", self.timeout_ms)
        return self.submit(prompt, **kwargs).result(timeout_s=tmo / 1e3 + 5.0)

    def _admit_batch(self, requests, rows):
        """Batcher dispatch callback: hand admitted requests to the decode
        loop. BLOCKS while the handover buffer is full so saturation backs
        up into the bounded admission queue (where shedding and timeouts
        live) instead of an unbounded join list."""
        for req in requests:
            with self._join_cond:
                while (not self._stop_flag
                       and len(self._join_q) >= self.slots):
                    self._join_cond.wait(0.05)
                    if req.expired():
                        break
                if self._stop_flag:
                    err = ServeError("server stopped")
                    if req.finish(error=err):
                        req.inputs._finish(err)
                    continue
                self._join_q.append(req)

    # ------------------------------------------------------------ scheduler
    def step(self):
        """One scheduler tick: admit pending joins (prefill/inject, one
        dispatch each — or a chunk-job handoff for long prompts), run AT
        MOST ONE prefill chunk, then send ONE fused decode step for the
        whole in-flight batch ahead of the host and deliver each live
        slot's token(s) of the step before it (``_decode_once``: in steady
        state one dispatch and one host gather a call; the first call of a
        stretch sends two steps). Returns the number of slots progressed
        (0 = idle). The background loop calls this continuously; tests call
        it directly for counter-exact assertions."""
        # the one read of the profiler's switch a tick makes: while it runs
        # the loop's time lies under decode[...] spans end to end
        # (profiler.decode_scope lists the kinds), off it enters none
        tracing = profiler.is_running()
        # a tick with something to do is one decode[tick], the parent of
        # all it does; the others are gathered into decode[idle]
        busy = tracing and bool(self._join_q or self.cache.num_active
                                or self._flight is not None)
        if self._idle is not None and (busy or not tracing):
            self._end_idle()
        with self._span(busy, "tick", self.cache.num_active):
            self._admit_pending(tracing)
            chunked = self._chunk_once(tracing)
            n = self._decode_once(tracing) + chunked
        if n:
            self._end_idle()   # work that arrived after the look above
        elif tracing and self._idle is None:
            # ONE span from the first empty tick to the next tick that
            # works: one a sleep would be 1,000 records a second
            self._idle = profiler.decode_scope("idle", self.slots, 0)
            self._idle.__enter__()
        return n

    def _end_idle(self):
        idle, self._idle = self._idle, None
        if idle is not None:
            idle.__exit__(None, None, None)

    def _span(self, tracing, kind, n_active, args=None, tag=None):
        return (profiler.decode_scope(kind, self.slots, n_active, args, tag)
                if tracing else _NO_SPAN)

    def _loop(self):
        try:
            while not self._stop_flag:
                if self.step() == 0:
                    time.sleep(0.001)
        finally:
            self._end_idle()   # on the thread that opened it

    # ------------------------------------------------------------- joining
    def _admit_pending(self, tracing=False):
        while self.cache._free:
            with self._join_cond:
                req = self._join_q.popleft() if self._join_q else None
                self._join_cond.notify_all()
            if req is None:
                return
            stream = req.inputs
            now = time.perf_counter()
            if req.done():      # queue sweep got it first
                continue
            if req.expired(now):
                err = ServeTimeout("timed out after %.1fms waiting for a "
                                   "slot" % ((now - req.t_submit) * 1e3))
                if req.finish(error=err):
                    stream._finish(err)
                    self.metrics.record_timeout()
                continue
            try:
                self._join(req, stream, tracing)
            except Exception as e:   # cache exhaustion, model error
                self.metrics.record_error()
                if req.finish(error=e):
                    stream._finish(e)

    def _join(self, req, stream, tracing=False):
        """Admit one request into a slot, under its ``decode[join<tp>]``
        span while the profiler runs: how long the join holds every
        stream."""
        t_join = time.perf_counter()
        t0_len = int(stream.prompt.size)
        # the prompt's bucket (the capacity ensured below is never under it)
        tp = min(next_pow2(t0_len), self.cache.max_capacity)
        args = None
        if tracing:
            args = {"prompt_len": t0_len}
            if stream.trace is not None:
                args["trace_id"] = stream.trace.trace_id
        with self._span(tracing, "join%d" % tp, self.cache.num_active,
                        args) as span:
            self._join_slot(req, stream, t_join, tp, tracing, span)

    def _join_slot(self, req, stream, t_join, tp, tracing, span):
        tr = stream.trace
        if tr is not None:
            # queue phase for a generation request spans admission →
            # slot assignment (batcher queue + join handover)
            tr.add_span("queue", req.t_submit, t_join)
        t0_len = int(stream.prompt.size)
        need = t0_len + stream.max_new_tokens + self._spec_margin
        self.cache.ensure_capacity(need)
        if self._draft is not None:
            self._draft.ensure_capacity()
        key = np.asarray(jax.random.PRNGKey(stream.seed), np.uint32)
        if (self._prefill_chunk is not None
                and t0_len > self._prefill_chunk):
            # chunked prefill: own the slot now, fill the page one chunk
            # per tick (interleaved with decode by step()); the slot stays
            # masked out of decode until the final chunk samples the first
            # token. Bypasses the prefix cache — partial pages are never
            # stored, and storing only whole ones would hold the very
            # stall this path removes.
            slot = self.cache.acquire(stream)
            self._chunk_jobs[slot] = {
                "req": req, "stream": stream, "pos": 0, "key": key,
                "t_join": t_join}
            self._ctl_dirty = True
            if span is not None:
                span["kind"] = "chunked"
            return
        slot = self.cache.acquire(stream)
        padded = np.zeros((1, tp), np.int32)
        padded[0, :t0_len] = stream.prompt
        hit = self.prefix.get(stream.prompt) if self.prefix is not None \
            else None
        t_disp0 = time.perf_counter()
        if tr is not None:
            # host-side prompt pad-to-bucket (the decode analogue of the
            # pool's pad span)
            tr.add_span("pad", t_join, t_disp0, bucket=tp)
        if span is not None:
            span["kind"] = "inject" if hit is not None else "prefill"
        engine.dispatch_counter.bump()
        c = self.cache
        scope = (profiler.decode_scope("prefill%d" % tp, self.slots,
                                       c.num_active)
                 if tracing else None)
        try:
            if scope is not None:
                scope.__enter__()
            aux = None
            if hit is not None:
                # the store's entries are fp pages whatever the pool's
                # format: the page record takes them in as it takes a prompt
                k_stack, v_stack, plen, last = hit
                as_dev = lambda st: jax.tree_util.tree_map(jnp.asarray, st)
                self._count_snapshot(self._snapshots_in, tp)
                state, valid, toks = self._inject_fn(tp, c.capacity)(
                    c.state, c.valid, self._tok, as_dev(k_stack),
                    as_dev(v_stack), jnp.int32(plen), jnp.int32(slot),
                    jnp.asarray(last), jnp.asarray(key),
                    jnp.float32(stream.temperature))
            else:
                state, valid, toks, last, *aux = self._prefill_fn(
                    tp, c.capacity)(
                    self._params(), c.state, c.valid, self._tok,
                    jnp.asarray(padded), jnp.int32(t0_len), jnp.int32(slot),
                    jnp.asarray(key), jnp.float32(stream.temperature))
        finally:
            if scope is not None:
                scope.__exit__(None, None, None)
        c.update(state, valid)
        self._tok = toks
        if hit is None:
            self.metrics.record_prefill()
            if self.prefix is not None:
                # one page read-out per UNIQUE prompt; repeats skip the
                # whole forward from then on. The store keeps host copies:
                # put() waits for the prefill and copies the page off the
                # device on this, the loop's own, thread
                engine.dispatch_counter.bump()
                copied = None
                if tracing:
                    # the K and V page as the store keeps them
                    copied = {"mb": round(1e-6 * c.page_bytes(tp), 3)}
                with self._span(tracing, "readout%d" % tp, c.num_active,
                                copied):
                    ks, vs = self._extract_fn(tp, c.capacity)(
                        c.state, jnp.int32(slot))
                    self.prefix.put(stream.prompt, ks, vs, t0_len,
                                    np.asarray(last))
                self._count_snapshot(self._snapshots_out, tp)
        first = int(np.asarray(self._tok)[slot])
        now = time.perf_counter()
        if aux:
            # read behind the first token: the prefill has finished
            self.metrics.record_expert_load(np.asarray(aux[0]))
        if tr is not None:
            # prefill (or prefix-inject) dispatch, closed by the first-token
            # host readback; the first token is sampled inside this program
            tr.add_span("dispatch", t_disp0, now,
                        kind="inject" if hit is not None else "prefill")
            tr.tokens += 1
        if not req.finish(result=stream):
            # timed out in the same instant admission landed: roll back
            self.cache.release(slot)
            return
        if self._draft is not None:
            # draft cache fill for the new stream (one small dispatch for
            # ModelDraft, free for NGramDraft) — a target prefix hit still
            # pays this: the draft keeps no prefix cache
            self._draft.join(slot, stream, padded, t0_len)
        self._slot_req[slot] = req
        self._remaining[slot] = stream.max_new_tokens
        self._keys[slot] = key
        self._temps[slot] = stream.temperature
        self._ctl_dirty = True
        self.metrics.record_first_token((now - req.t_submit) * 1e3, t0_len)
        self._deliver(slot, first)

    def _count_snapshot(self, counter, tp):
        if self.cache.snapshots:
            counter[0] += 1
            counter[1] += self.cache.page_bytes(tp)

    # ------------------------------------------------------------- decoding
    def _decode_once(self, tracing=False):
        """The plain path keeps ONE decode step in flight ahead of the host.
        A tick sends step t+1, whose state, lengths and input tokens are
        step t's outputs and may still be futures, THEN reads step t's
        tokens, THEN delivers them: the device starts t+1 the moment t ends,
        while the host copies, walks the slots and prepares t+2. The first
        tick of a stretch, with nothing to read, sends two.

        Who is live in the step sent ahead is ``_live_ahead``: a budget's
        end is known before the read. What the host learns only from the
        read (an EOS, a deadline) reaches the device one step late: t+1 has
        run that slot once more, the row is thrown away
        (``rows_discarded``), and its write lies in the slot's own page,
        which the next join rewrites. With a draft set the order stays
        serial (``_speculate_once``: the next drafts need the tokens on the
        host)."""
        if self._draft is not None:
            return self._speculate_once(tracing)
        flight = self._flight
        shown = active = self._live_ahead()
        going = bool(active.any())
        if flight is None and not going:
            return 0
        if not going:
            # none goes out: the span is of the step read
            shown = np.zeros_like(active)
            shown[list(flight.streams)] = 1
        # ``ahead``: the span's step went out while another was in flight
        with self._span(tracing, "step", int(shown.sum()),
                        tag="%s ahead=%d" % (self._step_tag(shown),
                                             going and flight is not None)
                        if tracing else None):
            sent = self._send_step(active, tracing)
            if flight is None:
                flight = self._flight = sent
                sent = self._send_step(self._live_ahead(), tracing)
            self._flight = sent
            # ONE host gather per tick: the tokens, then what the model's
            # step returned behind the logits (a routing model's load)
            host = np.asarray(flight.host)
        with self._span(tracing, "deliver", len(flight.streams)):
            now = time.perf_counter()
            # a step's time as a stream sees it: from the read before it
            # (from its dispatch, where it is the first of a stretch)
            dt = now - max(flight.t0, self._t_read)
            self._t_read = now
            # a row goes to the stream that owned the slot at the dispatch,
            # if it still does: never to one that took the slot since
            rows = [(slot, stream) for slot, stream
                    in flight.streams.items()
                    if self.cache.owner(slot) is stream
                    and not stream.done()]
            self._rows_discarded += len(flight.streams) - len(rows)
            self.metrics.record_step(dt, len(rows), len(flight.streams),
                                     self.slots,
                                     under_prefill=bool(self._chunk_jobs))
            if host.size > self.slots:
                self.metrics.record_expert_load(
                    host[self.slots:].reshape(self._routed), tag=tracing)
            for slot, stream in rows:
                self._deliver(slot, int(host[slot]), now, step_s=dt)
        return len(flight.streams)

    def _live_ahead(self):
        """(slots,) mask of who is live in the NEXT step to send: the live
        pages (a slot mid-chunked-prefill is owned, so admission cannot
        reuse it, but not decodable until its final chunk) less the slots
        whose budget ends with the token of the step in flight, which the
        host knows before it reads that step."""
        active = self.cache.active_mask(exclude=self._chunk_jobs)
        flight = self._flight
        if flight is not None:
            for slot, stream in flight.streams.items():
                if self._remaining[slot] <= 1 \
                        and self.cache.owner(slot) is stream:
                    active[slot] = 0
        return active

    def _sync_ctl(self, active, tracing):
        """Uploads the per-slot controls (keys, temperatures, the mask of
        live slots) after a join or a retire, or where the mask to send is
        not the one the device holds (a budget that ends in flight)."""
        if self._ctl_dirty or not np.array_equal(active, self._ctl_mask):
            with self._span(tracing, "ctl", int(active.sum())):
                self._dev_keys = jnp.asarray(self._keys)
                self._dev_temps = jnp.asarray(self._temps)
                self._dev_active = jnp.asarray(active)
            self._ctl_dirty, self._ctl_mask = False, active

    def _send_step(self, active, tracing):
        """Dispatches one decode step over the slots of ``active`` behind
        whatever the device has been sent, and installs its outputs (not
        computed yet) as the cache's state and the next input tokens, so
        that a join, a chunk or the next step dispatches behind it. Returns
        the record of the step in flight; None where no slot is live (none
        is sent)."""
        if not active.any():
            return None
        self._sync_ctl(active, tracing)
        c = self.cache
        fn = self._decode_fn(c.capacity)
        engine.dispatch_counter.bump()
        self._steps_ahead += self._flight is not None
        t0 = time.perf_counter()
        # what the model's step returned behind the logits (a routing
        # model's load) rides behind the tokens in an array of its own
        state, valid, self._tok, *packed = fn(
            self._params(), c.state, c.valid, self._tok, self._dev_active,
            self._dev_keys, self._dev_temps)
        c.update(state, valid)
        return _StepInFlight(packed[0] if packed else self._tok,
                             {int(slot): c.owner(int(slot)) for slot
                              in np.nonzero(active)[0]}, t0)

    def _step_tag(self, active):
        """The fields of a traced ``decode[step ...]`` span's name beside
        ``fill=``: a routing model's ``xmax=``/``xhit=`` (of the step
        before), and the page record's own field, from the tokens each live
        slot has cached, this step's included: ``kvread=<share>`` of a
        record with a time axis (how much of the pool the step's attention
        has to read), ``state=<MB>`` of a ``StatePage`` (the state the step
        reads and writes). From lengths the host knows (prompt plus tokens
        delivered, plus the one of the step in flight): no device read."""
        c = self.cache
        unread = self._flight.streams if self._flight is not None else {}
        contexts = []
        for slot in np.nonzero(active)[0]:
            stream = c.owner(int(slot))
            # the token of the step in flight is cached and not delivered
            contexts.append(int(stream.prompt.size) + len(stream.tokens)
                            + (unread.get(int(slot)) is stream))
        tags = [self.metrics.expert_tag() if self._routed is not None
                else None, c.page.step_tag(c.state, contexts)]
        return " ".join(t for t in tags if t)

    def _speculate_once(self, tracing=False):
        """One speculation round: draft proposes spec_k-1 tokens per slot
        (0 or 1 dispatch), the target scores the whole window in ONE wide
        verify dispatch, and each live slot receives its accepted prefix
        plus the verify sample at the first mismatch (1..spec_k tokens).
        Rejected draft positions need no device-side scrub: ``valid_len``
        advances only past accepted tokens and the next window overwrites
        the dead suffix in place."""
        active = self.cache.active_mask(exclude=self._chunk_jobs)
        n_active = int(active.sum())
        if n_active == 0:
            return 0
        self._sync_ctl(active, tracing)
        k = self.spec_k
        draft = self._draft
        if draft.needs_history:
            hists = []
            for s in range(self.slots):
                o = self.cache.owner(s)
                hists.append(
                    o.prompt_ids() + o.tokens
                    if (o is not None and active[s]) else [])
            # host np array goes straight into the compiled call — the
            # executable's own arg staging is the cheap C++ transfer path
            # (an explicit jnp.asarray here costs a python device_put per
            # round)
            drafts = draft.propose(hists, k)
        else:
            drafts = draft.propose(None, k)
        fn = self._verify_fn(self.cache.capacity)
        args = (self._params(), self.cache.state, self.cache.valid,
                self._tok, drafts, self._dev_active, self._dev_keys,
                self._dev_temps)
        engine.dispatch_counter.bump()
        engine.verify_dispatch_counter.bump()
        t0 = time.perf_counter()
        with self._span(tracing, "verify%d" % k, n_active):
            state, valid, nxt, emit, n_emit = fn(*args)
            # ONE batched host gather for both outputs (two np.asarray
            # calls would sync the device twice per round)
            emit_h, n_emit_h = jax.device_get((emit, n_emit))
        with self._span(tracing, "deliver", n_active):
            self.cache.update(state, valid)
            self._tok = nxt
            dt = time.perf_counter() - t0
            emitted = int(n_emit_h.sum())
            self.metrics.record_step(dt, emitted, n_active, self.slots,
                                     under_prefill=bool(self._chunk_jobs))
            self.metrics.record_spec_round(n_active * (k - 1),
                                           emitted - n_active)
            now = time.perf_counter()
            for slot in np.nonzero(active)[0]:
                slot = int(slot)
                stream = self.cache.owner(slot)
                for tok in emit_h[slot, :n_emit_h[slot]]:
                    if self.cache.owner(slot) is not stream:
                        break   # retired mid-window (EOS/budget/deadline)
                    self._deliver(slot, int(tok), now, step_s=dt)
        return n_active

    def _chunk_once(self, tracing=False):
        """Run AT MOST one prefill chunk (FIFO across jobs): extract the
        slot's page, run ``prefill_chunk`` prompt positions through the
        wide-window step at offset ``pos``, write the page back — one
        bounded dispatch, so in-flight decode never stalls longer than one
        chunk. The final chunk samples the first token and activates the
        slot."""
        if not self._chunk_jobs:
            return 0
        slot, job = next(iter(self._chunk_jobs.items()))
        req, stream = job["req"], job["stream"]
        now = time.perf_counter()
        if req.done() or req.expired(now):
            del self._chunk_jobs[slot]
            self.cache.release(slot)
            self._ctl_dirty = True
            err = ServeTimeout("timed out after %.1fms mid-prefill"
                               % ((now - req.t_submit) * 1e3))
            if req.finish(error=err):
                stream._finish(err)
                self.metrics.record_timeout()
            with self._join_cond:
                self._join_cond.notify_all()
            return 1
        tc = self._prefill_chunk
        plen = int(stream.prompt.size)
        pos0 = job["pos"]
        seg = stream.prompt[pos0:pos0 + tc]
        chunk = np.zeros((1, tc), np.int32)
        chunk[0, :seg.size] = seg
        fn = self._chunk_fn(tc, self.cache.capacity)
        params = self._params()
        engine.dispatch_counter.bump()
        scope = (profiler.decode_scope("chunk%d" % tc, self.slots,
                                       self.cache.num_active)
                 if tracing else None)
        try:
            if scope is not None:
                scope.__enter__()
            state, valid, toks = fn(
                params, self.cache.state, self.cache.valid, self._tok,
                jnp.asarray(chunk), jnp.int32(pos0), jnp.int32(plen),
                jnp.int32(slot), jnp.asarray(job["key"]),
                jnp.float32(stream.temperature))
            self.cache.update(state, valid)
        finally:
            if scope is not None:
                scope.__exit__(None, None, None)
        self._tok = toks
        self.metrics.record_chunk()
        job["pos"] = pos0 + tc
        if job["pos"] < plen:
            return 1
        # final chunk: the first token was sampled in-program — activate
        del self._chunk_jobs[slot]
        first = int(np.asarray(self._tok)[slot])
        now = time.perf_counter()
        if stream.trace is not None:
            stream.trace.add_span("dispatch", job["t_join"], now,
                                  kind="chunked_prefill")
            stream.trace.tokens += 1
        self.metrics.record_prefill()
        if not req.finish(result=stream):
            self.cache.release(slot)
            self._ctl_dirty = True
            return 1
        if self._draft is not None:
            tp = min(next_pow2(plen), self.cache.capacity)
            padded = np.zeros((1, tp), np.int32)
            padded[0, :plen] = stream.prompt
            self._draft.join(slot, stream, padded, plen)
        self._slot_req[slot] = req
        self._remaining[slot] = stream.max_new_tokens
        self._keys[slot] = job["key"]
        self._temps[slot] = stream.temperature
        self._ctl_dirty = True
        self.metrics.record_first_token((now - req.t_submit) * 1e3, plen)
        self._deliver(slot, first)
        with self._join_cond:
            self._join_cond.notify_all()
        return 1

    def _deliver(self, slot, tok, now=None, step_s=None):
        """Hand one token to a slot's stream and retire the request when it
        completes (EOS / budget) or blows its deadline."""
        stream = self.cache.owner(slot)
        req = self._slot_req[slot]
        if step_s is not None and stream.trace is not None:
            # O(1) per token: attribute the shared step dispatch to this
            # request (a float add, never a span)
            stream.trace.note_decode_step(step_s, now)
        stream._push(tok)
        self._remaining[slot] -= 1
        if (self.eos_id is not None and tok == self.eos_id) \
                or self._remaining[slot] <= 0:
            self._retire(slot)
            return
        if req is not None and req.expired(now):
            self._retire(slot, error=ServeTimeout(
                "deadline passed mid-generation (after %d tokens)"
                % len(stream.tokens)))
            self.metrics.record_timeout()

    def _retire(self, slot, error=None):
        stream = self.cache.owner(slot)
        req = self._slot_req[slot]
        if stream is not None:
            if stream.trace is not None:
                # one aggregate decode span per request, emitted at retire
                stream.trace.close_decode()
            stream._finish(error)
            if error is None and req is not None:
                self.metrics.record_latency(
                    (time.perf_counter() - req.t_submit) * 1e3)
        self._slot_req[slot] = None
        self._temps[slot] = 0.0
        self._ctl_dirty = True
        if self._draft is not None:
            self._draft.release(slot)
        self.cache.release(slot)
        with self._join_cond:
            self._join_cond.notify_all()

    # ------------------------------------------------- compiled programs
    def _trace_ctx(self, params):
        ctx = _trace.trace_scope(jax.random.PRNGKey(0), False)
        return ctx

    def _jit(self, fn, donate, hint=""):
        """Decode-loop programs compile through ``cache.AotFn`` in
        single-signature mode: shapes are fixed by the (slots, capacity /
        prompt-bucket) key, so the hot per-token path is one attribute
        read — and every program has an exportable executable handle for
        Tier B snapshots plus the Tier A disk store underneath."""
        from ..cache import AotFn

        hint = hint or "decode"
        # one name per kind of program where all were ``jit_pure``: the
        # device trace's "XLA Modules" line reads jit_pure_step_c1024,
        # jit_pure_prefill_t256c1024, ... and a reader finds each by name
        fn.__name__ = "pure_" + re.sub(r"\W", "_", hint)
        return AotFn(fn,
                     donate_argnums=donate if (self._donate and donate)
                     else (),
                     tier="decode", hint=hint,
                     single_signature=True)

    def _decode_fn(self, capacity):
        fn = self._decode_fns.get(capacity)
        if fn is not None:
            return fn
        model, plist, top_k = self.model, self._plist, self.top_k

        def pure(params, state, valid, toks, active, keys, temps):
            # trace-time bump: fires exactly when XLA retraces — the
            # zero-steady-state-retrace proof tests assert (whatever the
            # pages' format: the contract is the same)
            engine.decode_compile_counter.bump()
            with _trace.trace_scope(jax.random.PRNGKey(0), False) as t:
                t.param_store = {id(p): a for p, a in zip(plist, params)}
                # a free slot reads nothing of its page (and routes nowhere)
                logits, state, aux = model.decode_step(
                    _trace.F, jnp.reshape(toks, (-1, 1)), state, valid,
                    active)
            # the generated token's position is valid+1 (prefill used
            # `prompt_len` for the first token) — every token of a stream
            # folds a distinct position into its slot key
            nxt = sample_tokens(
                jnp.reshape(logits, (logits.shape[0], -1)), keys, valid + 1,
                temps, top_k)
            act = active > 0
            nxt = jnp.where(act, nxt, 0)
            valid = valid + act.astype(jnp.int32)
            if aux is not None:
                # what the host reads: the tokens, then the model's aux
                return state, valid, nxt, jnp.concatenate(
                    [nxt, aux.astype(jnp.int32).reshape(-1)])
            return state, valid, nxt

        # ``toks`` is not donated, here or in the programs of a join (32
        # int32: donation buys nothing): a step's tokens have gone into the
        # next program, the step sent ahead or a prefill, an inject or a
        # chunk behind it, before the host reads them
        fn = self._jit(pure, donate=(1, 2), hint="step@c%d" % capacity)
        self._decode_fns[capacity] = fn
        return fn

    def _verify_fn(self, capacity):
        """Speculative verify program: score the (current token + drafted)
        k-window in one wide dispatch (``decode_step`` with K = spec_k),
        sample every row at its own sequence position with the slot's
        folded key, and accept the longest prefix where the sample equals
        the draft — the first mismatching row's sample IS the
        rejection-resample (exact for deterministic drafts: the proposal is
        one-hot, so accept-w.p.-p(d) and the residual distribution both
        collapse to 'sample from p, keep on agreement'). Greedy rows
        therefore reproduce plain greedy decode bit-for-bit; k=1
        degenerates to the plain step."""
        fn = self._verify_fns.get(capacity)
        if fn is not None:
            return fn
        model, plist, top_k = self.model, self._plist, self.top_k
        k = self.spec_k

        def accept_emit(logits, valid, drafts, active, keys, temps):
            S, K, V = logits.shape
            # row j's token lands at sequence position valid+1+j — the
            # same per-(seed, position) fold plain decode uses, so spec
            # and plain streams sample identical tokens
            pos = valid[:, None] + 1 + jnp.arange(K, dtype=jnp.int32)[None]
            y = sample_tokens(jnp.reshape(logits, (S * K, V)),
                              jnp.repeat(keys, K, axis=0),
                              jnp.reshape(pos, (-1,)),
                              jnp.repeat(temps, K), top_k)
            y = jnp.reshape(y, (S, K))
            if K > 1:
                match = (y[:, :K - 1] == drafts).astype(jnp.int32)
                al = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
            else:
                al = jnp.zeros((S,), jnp.int32)
            act = active > 0
            n_emit = jnp.where(act, al + 1, 0)
            emit = jnp.where(
                (jnp.arange(K, dtype=jnp.int32)[None] <= al[:, None])
                & act[:, None], y, 0)
            nxt = jnp.where(
                act, jnp.take_along_axis(y, al[:, None], axis=1)[:, 0], 0)
            return valid + n_emit, nxt, emit, n_emit

        def pure(params, state, valid, toks, drafts, active, keys, temps):
            # trace-time bump: zero-steady-state-retrace proof (the verify
            # DISPATCH count is engine.verify_dispatch_counter, bumped at
            # the call site)
            engine.decode_compile_counter.bump()
            window = jnp.concatenate([toks[:, None], drafts], axis=1)
            with _trace.trace_scope(jax.random.PRNGKey(0), False) as t:
                t.param_store = {id(p): a for p, a in zip(plist, params)}
                logits, state, _aux = model.decode_step(
                    _trace.F, window, state, valid)
            valid, nxt, emit, n_emit = accept_emit(
                logits, valid, drafts, active, keys, temps)
            return state, valid, nxt, emit, n_emit

        fn = self._jit(pure, donate=(1, 2, 3),
                       hint="verify%d@c%d" % (k, capacity))
        self._verify_fns[capacity] = fn
        return fn

    def _chunk_fn(self, tc, capacity):
        """Prefill-chunk program: take the slot's page out of the shared
        state, run ``tc`` prompt positions through ``decode_step`` at offset
        ``pos0`` (K = tc with a (1,) valid vector — prefix attention +
        in-window causality + the per-row window write are exactly the
        verify semantics), and put the page back. The final chunk (pos0 +
        tc >= plen) samples the first token at its true row and sets valid
        to the full prompt length; non-final chunks park valid at the chunk
        frontier, so interleaved decode garbage for this masked slot lands
        exactly where the next chunk overwrites it."""
        fn = self._chunk_fns.get((tc, capacity))
        if fn is not None:
            return fn
        model, plist, top_k = self.model, self._plist, self.top_k
        zero = jnp.int32(0)

        def finish(logits, valid, toks, pos0, plen, slot, key, temp):
            nvalid = jnp.minimum(pos0 + tc, plen)
            valid = jax.lax.dynamic_update_slice(
                valid, jnp.reshape(nvalid, (1,)), (slot,))
            # first-token row (clamped: garbage until the final chunk,
            # overwritten by it)
            row = jnp.clip(plen - 1 - pos0, 0, tc - 1)
            last = jnp.reshape(jax.lax.dynamic_slice(
                logits, (zero, row, zero),
                (1, 1, logits.shape[2])), (1, -1))
            t0 = sample_tokens(last, key[None], plen[None], temp[None],
                               top_k)
            return valid, jax.lax.dynamic_update_slice(toks, t0, (slot,))

        def pure(params, state, valid, toks, tokens, pos0, plen, slot, key,
                 temp):
            engine.decode_compile_counter.bump()
            # the first chunk starts a stream: the page carries nothing over
            # from the one that held the slot before
            pages = kv_cache.take_slot(state, slot, pos0 == 0)
            with _trace.trace_scope(jax.random.PRNGKey(0), False) as t:
                t.param_store = {id(p): a for p, a in zip(plist, params)}
                logits, pages, _aux = model.decode_step(
                    _trace.F, tokens, pages, jnp.reshape(pos0, (1,)))
            state = kv_cache.put_slot(state, slot, pages)
            valid, toks = finish(logits, valid, toks, pos0, plen, slot,
                                 key, temp)
            return state, valid, toks

        fn = self._jit(pure, donate=(1, 2),
                       hint="chunk%d@c%d" % (tc, capacity))
        self._chunk_fns[(tc, capacity)] = fn
        return fn

    def _prefill_fn(self, tp, capacity):
        fn = self._prefill_fns.get((tp, capacity))
        if fn is not None:
            return fn
        model, plist, top_k = self.model, self._plist, self.top_k
        zero = jnp.int32(0)

        def pure(params, state, valid, toks, tokens, plen, slot, key, temp):
            engine.decode_compile_counter.bump()
            with _trace.trace_scope(jax.random.PRNGKey(0), False) as t:
                t.param_store = {id(p): a for p, a in zip(plist, params)}
                logits, kvs, aux = model.forward_collect_kv(
                    _trace.F, tokens, plen)
            # every row's logits, or (a model that cuts the last live row
            # itself: pad rows route nowhere) that row's alone
            if logits.shape[1] != 1:
                logits = jax.lax.dynamic_slice(
                    logits, (zero, plen - 1, zero), (1, 1, logits.shape[2]))
            last = jnp.reshape(logits, (1, -1))
            state = kv_cache.write_prompt(state, kvs, plen, slot)
            valid = jax.lax.dynamic_update_slice(
                valid, jnp.reshape(plen, (1,)), (slot,))
            t0 = sample_tokens(last, key[None], plen[None], temp[None],
                               top_k)
            toks = jax.lax.dynamic_update_slice(toks, t0, (slot,))
            out = (state, valid, toks, jnp.reshape(last, (-1,)))
            return out if aux is None else out + (aux.astype(jnp.int32),)

        fn = self._jit(pure, donate=(1, 2),
                       hint="prefill@t%dc%d" % (tp, capacity))
        self._prefill_fns[(tp, capacity)] = fn
        return fn

    def _inject_fn(self, tp, capacity):
        fn = self._inject_fns.get((tp, capacity))
        if fn is not None:
            return fn
        top_k = self.top_k

        def pure(state, valid, toks, k_stack, v_stack, plen, slot, last,
                 key, temp):
            engine.decode_compile_counter.bump()
            state = kv_cache.write_prompt(
                state, kv_cache.stored_kvs(k_stack, v_stack), plen, slot)
            valid = jax.lax.dynamic_update_slice(
                valid, jnp.reshape(plen, (1,)), (slot,))
            t0 = sample_tokens(last[None], key[None], plen[None], temp[None],
                               top_k)
            toks = jax.lax.dynamic_update_slice(toks, t0, (slot,))
            return state, valid, toks

        fn = self._jit(pure, donate=(0, 1),
                       hint="inject@t%dc%d" % (tp, capacity))
        self._inject_fns[(tp, capacity)] = fn
        return fn

    def _extract_fn(self, tp, capacity):
        fn = self._extract_fns.get((tp, capacity))
        if fn is not None:
            return fn
        lengths = self.cache.page_lengths(tp)

        def pure(state, slot):
            engine.decode_compile_counter.bump()
            return kv_cache.read_prompt(state, slot, lengths)

        # reads the live state: never donate
        fn = self._jit(pure, donate=(),
                       hint="extract@t%dc%d" % (tp, capacity))
        self._extract_fns[(tp, capacity)] = fn
        return fn

    # ------------------------------------------------------------ warmup
    def warmup(self, prompt_buckets=(), max_tokens=None):
        """Compile ahead of traffic: the decode step at the current (or
        requested) capacity, plus prefill programs for the given pow2
        prompt-length buckets — after this a steady token stream never
        bumps ``engine.decode_compile_counter``."""
        need = max(int(max_tokens or 0),
                   max([int(b) for b in prompt_buckets], default=1) + 1)
        self.cache.ensure_capacity(need + self._spec_margin)
        for b in prompt_buckets:
            stream = GenerationStream([1] * int(b), 1, 0.0, 0, 0)
            slot = self.cache.acquire(stream)
            if slot is None:
                break
            tp = min(next_pow2(int(b)), self.cache.capacity)
            c = self.cache
            key = jnp.asarray(jax.random.PRNGKey(0), jnp.uint32)
            plen, at, temp = jnp.int32(int(b)), jnp.int32(slot), \
                jnp.float32(0.0)
            state, valid, self._tok, last, *_aux = self._prefill_fn(
                tp, c.capacity)(
                self._params(), c.state, c.valid, self._tok,
                jnp.zeros((1, tp), jnp.int32), plen, at, key, temp)
            c.update(state, valid)
            if self.prefix is not None:
                # prefix-store (extract) and replay (inject) programs are
                # part of the join path: compile them now too
                ks, vs = self._extract_fn(tp, c.capacity)(c.state, at)
                state, valid, self._tok = self._inject_fn(tp, c.capacity)(
                    c.state, c.valid, self._tok, ks, vs, plen, at, last,
                    key, temp)
                c.update(state, valid)
            self.cache.release(slot)
        if self._draft is not None:
            # draft-side programs (cache fill per prompt bucket + the
            # k-unrolled propose step); the dummy decode below compiles
            # the verify program through the normal speculation path
            self._draft.warm([min(next_pow2(int(b)), self.cache.capacity)
                              for b in prompt_buckets])
        if (self._prefill_chunk is not None
                and self.cache.capacity >= self._prefill_chunk):
            self._warm_chunk()
        # one masked all-free decode dispatch compiles the step program
        # (the verify program when a draft is configured)
        dummy = GenerationStream([1], 1, 0.0, 0, 0)
        slot = self.cache.acquire(dummy)
        if slot is not None:
            self._remaining[slot] = 1
            self._decode_once()
            if self.cache.owner(slot) is dummy:
                self._retire(slot)
        return self

    def _warm_chunk(self):
        """Compile the chunked-prefill program on a throwaway slot (a
        single final chunk: pos0=0, plen=chunk — same program every real
        chunk reuses, only the scalar operands differ)."""
        tc = self._prefill_chunk
        dummy = GenerationStream([1] * tc, 1, 0.0, 0, 0)
        slot = self.cache.acquire(dummy)
        if slot is None:
            return
        fn = self._chunk_fn(tc, self.cache.capacity)
        params = self._params()
        key = np.asarray(jax.random.PRNGKey(0), np.uint32)
        chunk = np.zeros((1, tc), np.int32)
        state, valid, self._tok = fn(
            params, self.cache.state, self.cache.valid, self._tok,
            jnp.asarray(chunk), jnp.int32(0), jnp.int32(tc),
            jnp.int32(slot), jnp.asarray(key), jnp.float32(0.0))
        self.cache.update(state, valid)
        self.cache.release(slot)

    # ------------------------------------------------ snapshot interface
    def export_executables(self):
        """Every compiled generative program, tagged for the snapshot
        manifest: [{key, kind, tp, capacity, compiled}] covering decode
        steps AND the join path (prefill/inject/extract buckets) — a warm
        replica must reach its first token with zero compiles."""
        out = []
        for cap, fn in sorted(self._decode_fns.items()):
            c = fn.compiled_for()
            if c is not None:
                out.append({"key": "decode@c%d" % cap, "kind": "decode",
                            "tp": 0, "capacity": int(cap), "compiled": c})
        for cap, fn in sorted(self._verify_fns.items()):
            c = fn.compiled_for()
            if c is not None:
                out.append({"key": "verify@c%d" % cap, "kind": "verify",
                            "tp": 0, "capacity": int(cap), "compiled": c})
        for kind, fns in (("prefill", self._prefill_fns),
                          ("inject", self._inject_fns),
                          ("extract", self._extract_fns)):
            for (tp, cap), fn in sorted(fns.items()):
                c = fn.compiled_for()
                if c is not None:
                    out.append({"key": "%s@t%dc%d" % (kind, tp, cap),
                                "kind": kind, "tp": int(tp),
                                "capacity": int(cap), "compiled": c})
        # chunk programs key on (chunk_len, capacity) like prompt buckets
        for (tc, cap), fn in sorted(self._chunk_fns.items()):
            c = fn.compiled_for()
            if c is not None:
                out.append({"key": "chunk@t%dc%d" % (tc, cap),
                            "kind": "chunk", "tp": int(tc),
                            "capacity": int(cap), "compiled": c})
        if self._draft is not None:
            out.extend(self._draft.export_executables())
        return out

    def preload_executable(self, kind, tp, capacity, compiled):
        """Adopt one deserialized program (snapshot warm start): builds
        the wrapper for its key — cheap, no trace — and installs the
        executable. A mismatched executable recompiles with one warning at
        first use (AotFn's recovery path)."""
        if kind == "decode":
            fn = self._decode_fn(capacity)
        elif kind == "verify":
            fn = self._verify_fn(capacity)
        elif kind == "prefill":
            fn = self._prefill_fn(tp, capacity)
        elif kind == "inject":
            fn = self._inject_fn(tp, capacity)
        elif kind == "extract":
            fn = self._extract_fn(tp, capacity)
        elif kind == "chunk":
            fn = self._chunk_fn(tp, capacity)
        elif kind in ("draftstep", "draftfill"):
            if self._draft is None:
                raise ServeError(
                    "snapshot carries %r programs but this server has no "
                    "draft configured" % kind)
            self._draft.preload_executable(kind, tp, capacity, compiled)
            return
        else:
            raise ServeError("unknown snapshot program kind %r" % kind)
        fn.adopt(compiled)

    def snapshot(self, prefix):
        """Write the AOT serving artifact for this server (checkpoint +
        decode config + every warmed program's serialized executable) —
        see serve.snapshot / cache.snapshot."""
        from ..cache.snapshot import save_snapshot

        return save_snapshot(self, prefix)

    # ------------------------------------------------------------- stats
    def stats(self):
        """Snapshot for ``serve.stats()`` / tools/diagnose.py: generative
        counters on top of the base queue/latency metrics."""
        snap = self.metrics.snapshot()
        snap.update(
            slots=self.slots,
            capacity=self.cache.capacity,
            in_flight=self.cache.num_active,
            tokens_in_flight=self.tokens_in_flight(),
            swap_epoch=self._swap_epoch,
            cache_migrations=self.cache.migrations,
            prefix_hits=self.prefix.hits if self.prefix is not None else None,
            prefix_misses=(self.prefix.misses if self.prefix is not None
                           else None),
            prefix_entries=(len(self.prefix) if self.prefix is not None
                            else None),
            decode_compile_counter=engine.decode_compile_counter.count,
            # the look-ahead of the plain path: steps sent while another was
            # in flight, and rows a step computed for a stream that had
            # ended (an EOS or a deadline is found one step late)
            steps_ahead=self._steps_ahead,
            rows_discarded=self._rows_discarded,
            verify_dispatches=engine.verify_dispatch_counter.count,
            spec_k=self.spec_k if self._draft is not None else None,
            draft=(type(self._draft).__name__
                   if self._draft is not None else None),
            prefill_chunk=self._prefill_chunk,
            chunk_queue_depth=len(self._chunk_jobs),
            quantize=self._quantize,
            kv_cache_bytes=self.cache.nbytes(),
            kv_cache_bytes_unquantized=self.cache.nbytes_unquantized(),
            # a pool of recurrent state (``StatePage``), and its snapshots
            # to the prefix store and back
            state_bytes=self.cache.nbytes() if self.cache.snapshots else 0,
            state_snapshots_out=self._snapshots_out[0],
            state_snapshot_bytes_out=self._snapshots_out[1],
            state_snapshots_in=self._snapshots_in[0],
            state_snapshot_bytes_in=self._snapshots_in[1],
            running=(self._loop_thread is not None
                     and self._loop_thread.is_alive()),
        )
        return snap
