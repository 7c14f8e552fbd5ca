"""Continuous-batching generative decode (ISSUE 6).

Covers the acceptance contract: fixed-capacity paged-KV decode parity
≤1e-6 against the ``use_cache=False`` O(T²) oracle (incl. bf16), exactly
ONE dispatch per decode step with zero steady-state retrace
(``engine.decode_compile_counter`` bumps inside the traced bodies), mixed
length requests joining/leaving mid-stream by slot assignment with no
recompile, prefix-cache hit correctness, capacity-bucket growth, priority
classes + SLO-aware shedding on the admission queue, in-program sampling
(greedy + temperature/top-k over per-slot threefry keys), streaming
iterators, and the generative serve metrics.
"""
import time
from typing import Any, NamedTuple

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import _trace, engine, nd
from mxnet_tpu.models.gpt import GPTModel, _CausalSelfAttention, gpt_nano
from mxnet_tpu.serve import (CacheError, NGramDraft, PagedKVCache, ServerBusy,
                             ServeTimeout)
from mxnet_tpu.serve.batcher import DynamicBatcher


@pytest.fixture(scope="module")
def model():
    m = gpt_nano()
    m.initialize()
    return m


@pytest.fixture
def rng():
    return np.random.RandomState(7)


def _oracle(model, prompt, n):
    """Generated ids from the O(T²) full-re-forward oracle."""
    out = model.generate(nd.array(np.asarray(prompt)[None], dtype="int32"),
                         max_new_tokens=n, use_cache=False)
    return out.asnumpy()[0, len(prompt):].tolist()


def _pump(srv, streams, ticks=200):
    """Drive the scheduler synchronously until every stream finishes."""
    for _ in range(ticks):
        srv.step()
        if all(s.done() for s in streams):
            return
        time.sleep(0.005)
    raise AssertionError("streams did not finish in %d ticks" % ticks)


# ----------------------------------------------------- model-level parity
def test_fixed_cache_step_logits_parity_vs_full_forward(model, rng):
    """Every step's logits through the fixed-capacity cache == the full
    forward's logits at that position, ≤1e-6 — and no cache shape ever
    changes across steps."""
    toks = nd.array(rng.randint(0, 256, (2, 10)), dtype="int32")
    full = model(toks).asnumpy()
    caches = model.init_cache(2, capacity=16)
    logits, caches = model.prefill(
        nd.slice_axis(toks, axis=1, begin=0, end=4), caches)
    np.testing.assert_allclose(logits.asnumpy(), full[:, 3], atol=1e-6)
    shapes = [c[0].shape for c in caches]
    for t in range(4, 10):
        logits, caches = model.step(
            nd.slice_axis(toks, axis=1, begin=t, end=t + 1), caches, t)
        np.testing.assert_allclose(logits.asnumpy(), full[:, t], atol=1e-6,
                                   err_msg="step %d" % t)
        assert [c[0].shape for c in caches] == shapes, \
            "cache shape changed at step %d (the GL007 retrace hazard)" % t


def test_fixed_cache_parity_bf16(rng):
    m = gpt_nano()
    m.initialize()
    m.cast("bfloat16")
    toks = nd.array(rng.randint(0, 256, (2, 6)), dtype="int32")
    full = np.asarray(m(toks).asnumpy(), np.float32)
    caches = m.init_cache(2, capacity=8)
    assert np.dtype(caches[0][0].dtype).name == "bfloat16", \
        "cache must inherit the parameter dtype"
    logits, caches = m.prefill(toks, caches)
    np.testing.assert_allclose(np.asarray(logits.asnumpy(), np.float32),
                               full[:, -1], atol=1e-6)
    out_c = m.generate(toks, max_new_tokens=4, use_cache=True)
    out_f = m.generate(toks, max_new_tokens=4, use_cache=False)
    np.testing.assert_array_equal(out_c.asnumpy(), out_f.asnumpy())


def test_generate_prefill_is_single_forward(model, rng):
    """The cached generate path prefills the whole prompt in ONE
    forward-pass round (not T per-token step rounds): its dispatch count
    must stay well under the old token-by-token loop's."""
    prompt = nd.array(rng.randint(0, 256, (1, 12)), dtype="int32")
    ref = model.generate(prompt, max_new_tokens=3, use_cache=False)
    engine.dispatch_counter.reset()
    out = model.generate(prompt, max_new_tokens=3, use_cache=True)
    cached_disp = engine.dispatch_counter.count
    np.testing.assert_array_equal(out.asnumpy(), ref.asnumpy())
    # per-token prefill would cost ~12 step rounds; one forward + 2 steps
    # must cost strictly fewer dispatch rounds than 12 steps' worth
    caches = model.init_cache(1, capacity=16)
    engine.dispatch_counter.reset()
    model.step(nd.slice_axis(prompt, axis=1, begin=0, end=1), caches, 0)
    per_step = max(engine.dispatch_counter.count, 1)
    assert cached_disp < 12 * per_step, (cached_disp, per_step)


# ------------------------------------------------------------ paged cache
def test_paged_cache_slots_and_capacity_buckets():
    c = PagedKVCache(layers=2, heads=2, head_dim=4, slots=3, max_capacity=64)
    assert c.capacity_bucket(5) == 8
    assert c.capacity_bucket(33) == 64
    with pytest.raises(CacheError):
        c.capacity_bucket(65)
    assert c.ensure_capacity(5) is True      # first allocation
    assert c.capacity == 8
    assert c.ensure_capacity(3) is False     # shrink never migrates
    assert c.ensure_capacity(9) is True      # pow2 growth, zero-padded
    assert c.capacity == 16 and c.migrations == 1
    assert c.state[0].k.shape == (3, 2, 16, 4)
    s0 = c.acquire("a")
    s1 = c.acquire("b")
    s2 = c.acquire("c")
    assert c.acquire("d") is None            # fully booked
    assert c.num_active == 3
    c.release(s1)
    assert c.acquire("d") == s1              # page reuse
    assert sorted([s0, s1, s2]) == [0, 1, 2]


# ----------------------------------------------------- server: the headline
def test_decode_one_dispatch_zero_retrace_steady_state(model, rng):
    """ISSUE 6 acceptance: mixed-length concurrent streams at exactly ONE
    dispatch per decode step, zero steady-state retrace, parity with the
    uncached oracle — requests join and leave between steps with no
    recompile."""
    srv = mx.serve.GenerativeServer(model, slots=4, max_wait_ms=1.0,
                                    timeout_ms=60000.0)
    srv.warmup(prompt_buckets=(4, 8), max_tokens=32)
    p1 = rng.randint(0, 256, (3,)).astype(np.int32)
    p2 = rng.randint(0, 256, (7,)).astype(np.int32)
    p3 = rng.randint(0, 256, (5,)).astype(np.int32)
    s1 = srv.submit(p1, max_new_tokens=12)
    s2 = srv.submit(p2, max_new_tokens=6)
    time.sleep(0.05)
    srv.step()   # admit both (prefill dispatches) + first decode
    engine.decode_compile_counter.reset()
    for _ in range(3):           # steady state, 2 in flight
        engine.dispatch_counter.reset()
        assert srv.step() == 2
        assert engine.dispatch_counter.count == 1
    s3 = srv.submit(p3, max_new_tokens=4)  # joins mid-stream
    time.sleep(0.05)
    srv.step()
    while not (s1.done() and s2.done() and s3.done()):
        engine.dispatch_counter.reset()
        n = srv.step()
        if n:   # steady decode (incl. after s2/s3 leave): ONE dispatch, the
            # step sent ahead; the tick that reads the last step sends none
            last = s1.done() and s2.done() and s3.done()
            assert engine.dispatch_counter.count == (0 if last else 1)
        time.sleep(0.002)
    assert engine.decode_compile_counter.count == 0, \
        "steady-state decode retraced"
    assert s1.result(5) == _oracle(model, p1, 12)
    assert s2.result(5) == _oracle(model, p2, 6)
    assert s3.result(5) == _oracle(model, p3, 4)
    snap = srv.stats()
    assert snap["completed"] == 3 and snap["tokens"] >= 12 + 6 + 4 - 3
    srv.stop()


def test_threaded_streaming_iterator_parity(model, rng):
    """Background-loop mode: tokens stream through the per-request
    iterator as steps complete, matching the oracle order."""
    prompt = rng.randint(0, 256, (4,)).astype(np.int32)
    with mx.serve.GenerativeServer(model, slots=2,
                                   timeout_ms=60000.0) as srv:
        got = list(srv.submit(prompt, max_new_tokens=8))
    assert got == _oracle(model, prompt, 8)


def test_capacity_bucket_growth_mid_flight(model, rng):
    """A long request joining grows the cache to the next pow2 bucket
    (one migration) without corrupting the in-flight short request."""
    srv = mx.serve.GenerativeServer(model, slots=2, timeout_ms=60000.0)
    p_short = rng.randint(0, 256, (3,)).astype(np.int32)
    p_long = rng.randint(0, 256, (20,)).astype(np.int32)
    s1 = srv.submit(p_short, max_new_tokens=10)
    time.sleep(0.05)
    srv.step()
    cap0 = srv.cache.capacity
    s2 = srv.submit(p_long, max_new_tokens=10)   # needs a bigger bucket
    time.sleep(0.05)
    _pump(srv, [s1, s2])
    assert srv.cache.capacity > cap0
    assert srv.cache.migrations >= 1
    assert s1.result(5) == _oracle(model, p_short, 10)
    assert s2.result(5) == _oracle(model, p_long, 10)
    srv.stop()


def test_request_longer_than_max_length_rejected(model):
    srv = mx.serve.GenerativeServer(model, slots=2)
    with pytest.raises(CacheError):
        srv.submit(list(range(60)), max_new_tokens=10)  # 70 > max_len 64
    srv.stop()


# ------------------------------------------------------------ prefix cache
def test_prefix_cache_hit_parity_and_counters(model, rng):
    srv = mx.serve.GenerativeServer(model, slots=2, timeout_ms=60000.0)
    prompt = rng.randint(0, 256, (6,)).astype(np.int32)
    s1 = srv.submit(prompt, max_new_tokens=5)
    time.sleep(0.05)
    _pump(srv, [s1])
    assert srv.prefix.misses == 1 and srv.prefix.hits == 0
    prefills_before = srv.metrics.prefills
    s2 = srv.submit(prompt, max_new_tokens=5)     # identical prompt
    time.sleep(0.05)
    _pump(srv, [s2])
    assert srv.prefix.hits == 1
    assert srv.metrics.prefills == prefills_before, \
        "prefix hit must skip the whole-prompt forward"
    ref = _oracle(model, prompt, 5)
    assert s1.result(5) == ref
    assert s2.result(5) == ref                    # replayed pages are exact
    srv.stop()


# ------------------------------------------------------------ sampling
def test_sampling_deterministic_per_seed_and_topk1_greedy(model, rng):
    prompt = rng.randint(0, 256, (4,)).astype(np.int32)
    ref = _oracle(model, prompt, 6)
    with mx.serve.GenerativeServer(model, slots=2, top_k=1,
                                   timeout_ms=60000.0) as srv:
        a = srv.generate(prompt, max_new_tokens=6, temperature=0.9, seed=11)
        b = srv.generate(prompt, max_new_tokens=6, temperature=0.9, seed=11)
        g = srv.generate(prompt, max_new_tokens=6)   # temperature 0
    assert a == b, "same seed must reproduce the stream"
    assert a == ref, "top_k=1 sampling collapses to greedy"
    assert g == ref, "temperature=0 is greedy"


def test_mixed_greedy_and_sampled_slots_one_batch(model, rng):
    """Greedy and sampled requests share one decode dispatch (temperature
    is a traced per-slot input); the greedy slot's stream is unaffected by
    its sampled neighbor."""
    p1 = rng.randint(0, 256, (5,)).astype(np.int32)
    p2 = rng.randint(0, 256, (5,)).astype(np.int32)
    srv = mx.serve.GenerativeServer(model, slots=2, top_k=4,
                                    timeout_ms=60000.0)
    s1 = srv.submit(p1, max_new_tokens=6)                    # greedy
    s2 = srv.submit(p2, max_new_tokens=6, temperature=1.2, seed=3)
    time.sleep(0.05)
    _pump(srv, [s1, s2])
    assert s1.result(5) == _oracle(model, p1, 6)
    assert len(s2.result(5)) == 6
    srv.stop()


# ------------------------------------------- priority classes + SLO shed
def test_priority_preemptive_shedding_in_admission_queue():
    held = []
    b = DynamicBatcher(lambda reqs, rows: held.extend(reqs), max_batch=1,
                       max_queue=2)
    # unstarted batcher = requests wait in the admission queue
    low1 = b.submit(["l1"], 1, timeout_ms=10000.0, priority=0)
    low2 = b.submit(["l2"], 1, timeout_ms=500.0, priority=0)
    hi = b.submit(["hi"], 1, timeout_ms=10000.0, priority=5)
    # the victim is the lowest class with the least deadline slack: low2
    with pytest.raises(ServerBusy):
        low2.result(0.5)
    assert not low1.done() and not hi.done()
    # equal priority cannot preempt: the NEW request sheds
    with pytest.raises(ServerBusy):
        b.submit(["l3"], 1, priority=0)
    # drain order: highest class first
    with b._cond:
        order = [r.inputs[0] for r in b._queue]
    assert order == ["hi", "l1"]


def test_generative_queue_timeout_surfaces_on_stream(model, rng):
    """A request that times out while queued (all slots busy) fails its
    stream with ServeTimeout — the SLO covers slot wait, not just decode."""
    srv = mx.serve.GenerativeServer(model, slots=1, timeout_ms=60000.0)
    p = rng.randint(0, 256, (4,)).astype(np.int32)
    s1 = srv.submit(p, max_new_tokens=20)
    time.sleep(0.05)
    srv.step()                       # s1 occupies the only slot
    doomed = srv.submit(p, max_new_tokens=4, timeout_ms=30.0)
    time.sleep(0.1)                  # expires while waiting for a slot
    for _ in range(30):
        srv.step()
        if doomed.done():
            break
        time.sleep(0.01)
    with pytest.raises(ServeTimeout):
        doomed.result(1)
    _pump(srv, [s1])
    assert s1.result(5) == _oracle(model, p, 20)  # survivor unaffected
    assert srv.stats()["timeouts"] >= 1
    srv.stop()


# ------------------------------------------------------------ observability
def test_generative_stats_and_profiler_events(model, rng, tmp_path):
    from mxnet_tpu import profiler

    srv = mx.serve.GenerativeServer(model, slots=2, timeout_ms=60000.0)
    p = rng.randint(0, 256, (4,)).astype(np.int32)
    profiler.set_config(filename=str(tmp_path / "p.json"))
    profiler.start()
    try:
        s = srv.submit(p, max_new_tokens=5)
        time.sleep(0.05)
        _pump(srv, [s])
    finally:
        profiler.stop()
    snap = srv.stats()
    for key in ("tokens", "tokens_per_s", "ttft_p50_ms", "itl_p50_ms",
                "itl_p99_ms", "inflight_fill", "decode_steps", "prefills",
                "prefix_hits", "slots", "capacity", "in_flight"):
        assert key in snap, key
    assert snap["tokens"] == 5 and snap["prefills"] == 1
    assert snap["tokens_per_s"] > 0
    assert 0 < snap["inflight_fill"] <= 1.0
    dump = profiler.dumps()
    assert "decode[step" in dump and "decode[prefill" in dump
    agg = mx.serve.stats()
    assert srv.name in agg["servers"]
    assert "decode_compile_counter" in agg
    srv.stop()


# ------------------------------------------- the seam of the page's format
class FusedPage(NamedTuple):
    """A page kind the library does not know: K and V of a layer kept as
    ONE leaf (2, slots, heads, length, head_dim). What ``serve/kv_cache.py``
    asks of a page record: allocation, growth, the traced operations that
    move a prompt's or a slot's page in and out of the pool, and what the
    cache's accounting and the scheduler's step span ask of a layer."""

    kv: Any

    @classmethod
    def zeros(cls, slots, heads, length, head_dim, dtype):
        return cls(jnp.zeros((2, slots, heads, length, head_dim), dtype))

    def grow(self, more):
        return FusedPage(jnp.pad(
            self.kv, ((0, 0), (0, 0), (0, 0), (0, more), (0, 0))))

    def _at(self, slot):
        zero = jnp.int32(0)
        return (zero, slot, zero, zero, zero)

    def write_prompt(self, k, v, plen, slot):
        return FusedPage(jax.lax.dynamic_update_slice(
            self.kv, jnp.stack([k, v]).astype(self.kv.dtype),
            self._at(slot)))

    def read_prompt(self, slot, n):
        _two, _slots, H, _length, D = self.kv.shape
        page = jax.lax.dynamic_slice(self.kv, self._at(slot),
                                     (2, 1, H, n, D))
        return page[0, 0], page[1, 0]

    def prompt_length(self, tp):
        return min(int(tp), self.kv.shape[3])

    def prompt_bytes(self, n):
        _two, _slots, H, _length, D = self.kv.shape
        return 2 * H * n * D * self.kv.dtype.itemsize

    def plain_bytes(self, itemsize):
        return self.kv.size * itemsize

    @staticmethod
    def step_tag(pages, contexts):
        return "fused=%d" % len(contexts)

    def take_slot(self, slot, fresh):
        shape = self.kv.shape
        return FusedPage(jax.lax.dynamic_slice(
            self.kv, self._at(slot), shape[:1] + (1,) + shape[2:]))

    def put_slot(self, slot, page):
        return FusedPage(jax.lax.dynamic_update_slice(
            self.kv, page.kv, self._at(slot)))


class _FusedAttention(_CausalSelfAttention):
    """The attention layer that handles a ``FusedPage`` (the eager decode
    loop's plain pages go the library's way)."""

    def step_cached(self, F, x, page, start, lengths=None):
        if not isinstance(page, FusedPage):
            return super().step_cached(F, x, page, start, lengths)
        q, k_new, v_new = self._qkv_heads(F, x)
        k = F.cache_write(page.kv[0], k_new, start)
        v = F.cache_write(page.kv[1], v_new, start)
        out = F.cached_attention(
            q, k, v, start + 1 if lengths is None else lengths)
        return (self.attn_out(self._merge_heads(F, out)),
                FusedPage(jnp.stack([k, v])))


class _FusedGPT(GPTModel):
    """``gpt_nano`` serving its own page kind."""

    def __init__(self):
        super().__init__(vocab_size=256, units=64, num_layers=2, num_heads=2,
                         max_length=64, dropout=0.0)
        for blk in self.blocks:
            blk.attn.__class__ = _FusedAttention

    def decode_state_spec(self):
        return dict(super().decode_state_spec(), page=FusedPage,
                    int8_pages=False)


def _served(model, prompts, n, **kwargs):
    srv = mx.serve.GenerativeServer(model, slots=2, timeout_ms=60000.0,
                                    **kwargs)
    out = []
    for p in prompts:        # one at a time: the third finds the first stored
        s = srv.submit(p, max_new_tokens=n)
        time.sleep(0.02)
        _pump(srv, [s], ticks=400)
        out.append(s.result(timeout_s=2))
    stats = srv.stats()
    srv.stop()
    return out, stats, srv


def test_a_page_kind_of_the_models_own_is_served_unseen(model, rng):
    """The seam is where ISSUE 33 says it is: a page record defined HERE
    and a model whose attention layer handles it go through
    ``GenerativeServer`` (prefill, read-out into the prefix store, inject,
    growth of the pool, step; and chunk by chunk) and yield the tokens of
    plain ``gpt_nano`` on the same weights. ``serve/decoder.py`` carries the
    state without looking inside."""
    fused = _FusedGPT()
    fused.initialize()
    for src, dst in zip(model.collect_params().values(),
                        fused.collect_params().values()):
        dst.set_data(src.data())
    a = rng.randint(0, 256, (5,)).tolist()
    b = rng.randint(0, 256, (19,)).tolist()      # grows the pool: 16 -> 32
    want, _stats, _srv = _served(model, [a, b, a], 6)
    got, stats, srv = _served(fused, [a, b, a], 6)
    assert got == want
    assert stats["prefix_hits"] == 1 and stats["cache_migrations"] == 1
    assert all(type(page) is FusedPage for page in srv.cache.state)
    assert stats["kv_cache_bytes"] == 2 * 2 * (2 * 2 * 32 * 32) * 4
    got, stats, _srv = _served(fused, [b], 6, prefix_cache=False,
                               prefill_chunk=8)
    assert got == want[1:2] and stats["prefill_chunks"] == 3


@pytest.mark.parametrize("quantize", [None, "int8"])
def test_one_decode_step_serves_step_verify_and_chunk(quantize, rng):
    """ONE ``decode_step`` whatever K and whatever the pages' format: the
    K = 1 call gives the K = 2 call's first row (to the last few bits: the
    CPU's matmul sums a row in another order beside a second row), and a
    greedy stream through the step, the verify (K = spec_k) and the chunk
    (K = the chunk) programs is one token sequence."""
    m = gpt_nano()
    m.initialize()
    cache = PagedKVCache(2, 2, 32, slots=2, max_capacity=64,
                         quantize=quantize is not None)
    cache.ensure_capacity(16)
    plist = list(m.collect_params().values())

    def decode_step(tokens, state, valid):
        with _trace.trace_scope(jax.random.PRNGKey(0), False) as t:
            t.param_store = {id(p): p.data()._data for p in plist}
            return m.decode_step(_trace.F, jnp.asarray(tokens, jnp.int32),
                                 state, jnp.asarray(valid, jnp.int32))

    toks = rng.randint(0, 256, (2, 6))
    _logits, state, aux = decode_step(toks[:, :4], cache.state, [0, 0])
    assert aux is None
    # (an int8 page's scale is a running max over the window written: on a
    # page whose scale covers both tokens already, K = 2 moves it no more
    # than K = 1 does)
    _logits, covered, _aux = decode_step(toks[:, 4:], state, [4, 4])
    if quantize:
        state = [p._replace(k_scale=c.k_scale, v_scale=c.v_scale)
                 for p, c in zip(state, covered)]
    one, state1, _aux = decode_step(toks[:, 4:5], state, [4, 4])
    two, state2, _aux = decode_step(toks[:, 4:], state, [4, 4])
    assert one.shape == (2, 1, 256) and two.shape == (2, 2, 256)
    np.testing.assert_allclose(np.asarray(one[:, 0]), np.asarray(two[:, 0]),
                               rtol=0, atol=2e-6)
    assert float(jnp.abs(one).max()) > 0.1
    assert [type(p) for p in state1] == [type(p) for p in state2] \
        == [cache.page] * 2

    prompt = rng.randint(0, 256, (21,)).tolist()
    kwargs = dict(prefix_cache=False, quantize=quantize)
    (want,), _stats, _srv = _served(m, [prompt], 10, **kwargs)
    (spec,), stats, _srv = _served(m, [prompt], 10, draft=NGramDraft(),
                                   spec_k=3, **kwargs)
    assert spec == want and stats["verify_dispatches"] > 0
    (chunked,), stats, _srv = _served(m, [prompt], 10, prefill_chunk=8,
                                      **kwargs)
    assert chunked == want and stats["prefill_chunks"] == 3


# ------------------------------------------------------------------ bench
# ------------------------------------------- one step in flight (ISSUE 36)
def _submit_now(srv, prompt, **kw):
    """Submit and wait until the request stands in the join queue, so that
    the next ``step()`` admits it (or leaves it queued for a slot)."""
    before = len(srv._join_q)
    s = srv.submit(prompt, **kw)
    deadline = time.perf_counter() + 10.0
    while len(srv._join_q) <= before:
        assert time.perf_counter() < deadline, "request never reached the loop"
        time.sleep(0.002)
    return s


def _strictly_serial(srv):
    """The order before the look-ahead: with a step in flight nobody is
    live in the next, so every tick sends one step and reads it."""
    ahead = srv._live_ahead
    srv._live_ahead = lambda: (ahead() if srv._flight is None else
                               np.zeros(srv.slots, np.int32))
    return srv


def _page_model(kind):
    if kind == "state":
        from mxnet_tpu.models.brumby import brumby_nano

        m = brumby_nano()
    else:
        m = gpt_nano()      # its own: ``quantize`` rewrites the weights
    m.initialize()
    return m, {"quantize": "int8"} if kind == "int8" else {}


def _replay(srv, script, ticks=400):
    """Hand-stepped: request i of ``script`` (tick, prompt, keywords) is
    submitted before tick ``tick``; returns every stream's tokens."""
    streams, tick = [], 0
    while len(streams) < len(script) or not all(s.done() for s in streams):
        assert tick < ticks, "streams did not finish in %d ticks" % ticks
        for at, prompt, kw in script[len(streams):]:
            if at > tick:
                break
            streams.append(_submit_now(srv, prompt, **kw))
        srv.step()
        tick += 1
    while srv.step():       # the step an EOS left in flight
        pass
    return [s.result(5) for s in streams]


@pytest.mark.parametrize("kind", ["plain", "int8", "state"])
def test_look_ahead_serves_the_tokens_of_a_strictly_serial_replay(kind):
    """Greedy and sampled streams over two slots, across joins in
    mid-flight, budget retires and an EOS met while the next step is
    already out: token for token what a server reads that sends one step
    and reads it before it sends the next. A ``PlainPage``, an ``Int8Page``
    and a ``StatePage`` pool: the server does not look inside."""
    model, kw = _page_model(kind)
    rs = np.random.RandomState(36)
    script = [(0, rs.randint(1, 256, (5,)), dict(max_new_tokens=9)),
              (0, rs.randint(1, 256, (7,)),
               dict(max_new_tokens=12, temperature=0.9, seed=3)),
              (2, rs.randint(1, 256, (3,)), dict(max_new_tokens=6)),
              (4, rs.randint(1, 256, (6,)),
               dict(max_new_tokens=7, temperature=0.7, seed=11)),
              (9, rs.randint(1, 256, (4,)), dict(max_new_tokens=5))]

    def serve(eos_id, serial):
        srv = mx.serve.GenerativeServer(model, slots=2, eos_id=eos_id,
                                        timeout_ms=60000.0,
                                        prefix_cache=False, **kw)
        try:
            out = _replay(_strictly_serial(srv) if serial else srv, script)
            return out, srv.stats()
        finally:
            srv.stop()

    free, _ = serve(None, True)
    assert [len(t) for t in free] == [9, 12, 6, 7, 5]
    # an EOS that a stream meets in mid-flight: a decode step brings it
    # (not the prefill's own first token), with budget left
    i, k, eos = next((i, k, t[k]) for i, t in enumerate(free)
                     for k in range(1, len(t) - 1) if t[k] != t[0])
    want, serial_stats = serve(eos, True)
    got, stats = serve(eos, False)
    assert got == want
    assert len(got[i]) <= k + 1 and got[i][-1] == eos
    assert serial_stats["steps_ahead"] == 0 == serial_stats["rows_discarded"]
    assert stats["steps_ahead"] > 0 and stats["rows_discarded"] >= 1
    # without an EOS too, and no row is computed in vain: a budget's end is
    # known before the step in flight is read
    got, stats = serve(None, False)
    assert got == free
    assert stats["rows_discarded"] == 0


def test_a_stale_row_never_reaches_the_stream_that_took_the_slot(model):
    """One slot. The first stream meets its EOS at the read of step t with
    step t+1 already out; the queued request takes the slot before t+1 is
    read. Its row of t+1 is thrown away and counted, never handed on."""
    rs = np.random.RandomState(5)
    # an EOS that a decode step brings (not the prefill's own first token),
    # with budget left, and a second prompt whose answer does not hold it
    pa, eos = next((p, t[4]) for p, t in (
        (p, _oracle(model, p, 8)) for p in (
            rs.randint(1, 256, (6,)) for _ in range(50))) if t[4] != t[0])
    pb = next(p for p in (rs.randint(1, 256, (4,)) for _ in range(50))
              if eos not in _oracle(model, p, 6))
    srv = mx.serve.GenerativeServer(model, slots=1, eos_id=eos,
                                    timeout_ms=60000.0, prefix_cache=False)
    try:
        a = _submit_now(srv, pa, max_new_tokens=8)
        srv.step()
        b = _submit_now(srv, pb, max_new_tokens=6)   # waits for the slot
        while not a.done():
            assert srv.step() == 1
        # the EOS came with a read: the step behind it is still in flight,
        # sent for the stream that has just ended
        assert a.result(5)[-1] == eos and len(a.tokens) <= 5
        assert srv._flight is not None and srv._flight.streams == {0: a}
        assert srv.stats()["rows_discarded"] == 0
        assert srv.step() == 1      # b joins behind it; the stale row read
        assert srv.cache.owner(0) is b
        assert srv.stats()["rows_discarded"] == 1
        while srv.step():
            pass
        assert b.result(5) == _oracle(model, pb, 6)
        assert srv.stats()["rows_discarded"] == 1
    finally:
        srv.stop()


def test_one_dispatch_and_one_host_gather_a_step_call(model, monkeypatch):
    """Steady state: a ``step()`` call sends one step and reads one. A
    stream of N tokens costs at most N + 1 step dispatches (here N - 1:
    the first token is the prefill's, and a budget's end is known before
    the step in flight is read), all but the first sent ahead."""
    srv = mx.serve.GenerativeServer(model, slots=2, timeout_ms=60000.0,
                                    prefix_cache=False)
    srv.warmup(prompt_buckets=(8,), max_tokens=32)
    gathers = []
    asarray = np.asarray

    def counting(a, *args, **kwargs):
        if isinstance(a, jax.Array):
            gathers.append(a.shape)
        return asarray(a, *args, **kwargs)

    n = 10
    try:
        s = _submit_now(srv, np.arange(1, 6), max_new_tokens=n)
        ahead0 = srv.stats()["steps_ahead"]
        engine.dispatch_counter.reset()
        assert srv.step() == 1
        # the prefill, then the first call of a stretch sends two steps
        assert engine.dispatch_counter.count == 3
        sent = 2
        monkeypatch.setattr(np, "asarray", counting)
        while not s.done():
            engine.dispatch_counter.reset()
            del gathers[:]
            assert srv.step() == 1
            # the call that reads the last step sends none behind it
            assert engine.dispatch_counter.count == (0 if s.done() else 1)
            assert gathers == [(2,)]
            sent += engine.dispatch_counter.count
        monkeypatch.setattr(np, "asarray", asarray)
        assert len(s.result(5)) == n and sent == n - 1 <= n + 1
        assert srv.stats()["steps_ahead"] - ahead0 == sent - 1
        assert srv._flight is None and srv.step() == 0
    finally:
        monkeypatch.setattr(np, "asarray", asarray)
        srv.stop()


@pytest.mark.slow
def test_serve_decode_bench_quick_subprocess():
    """tools/serve_bench.py --quick --mode decode end-to-end: ≥5× tokens/s
    over naive per-request generate() at 1 dispatch/step with zero
    steady-state recompiles (the committed artifact's acceptance bar)."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "serve_bench.py"),
         "--quick", "--mode", "decode", "--requests", "8", "--iters", "2"],
        capture_output=True, text=True, timeout=600, cwd=repo)
    assert r.returncode == 0, r.stderr
    rec = json.loads(r.stdout.strip().splitlines()[0])
    assert rec["speedup"] >= 5.0
    assert rec["steady_state_recompiles"] == 0
    assert rec["dispatches_per_step"] == 1.0
