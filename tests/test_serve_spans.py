"""The decode loop's own spans and the serving programs' names (ISSUE 28).

While the profiler runs, every stretch of a ``GenerativeServer`` tick lies
under a ``decode[<kind> fill=... b<slots>]`` span (``profiler.decode_scope``
lists the kinds), so that a device trace can put each idle gap of the device
down to what the loop thread was doing; with the profiler off a tick reads
the switch once and enters no span. Counts and structure only: no time is
compared. The serving programs carry one name per kind
(``jit_pure_step_c64``, ``jit_pure_prefill_t8c64``, ...), which is what a
trace reader finds them by.
"""
import json
import re
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler
from mxnet_tpu.models.gpt import gpt_nano
from mxnet_tpu.serve import NGramDraft

SLOTS = 4


@pytest.fixture(scope="module")
def model():
    m = gpt_nano()
    m.initialize()
    return m


def _submit(srv, prompt, n):
    """Submit and wait until the request stands in the join queue, so that
    the next ``step()`` admits it."""
    before = len(srv._join_q)
    s = srv.submit(prompt, max_new_tokens=n)
    deadline = time.perf_counter() + 10.0
    while len(srv._join_q) <= before:
        assert time.perf_counter() < deadline, "request never reached the loop"
        time.sleep(0.002)
    return s


def _pump(srv, streams, ticks=200):
    """Ticks until every stream is done; returns how many ticks it took."""
    for i in range(ticks):
        assert srv.step() > 0
        if all(s.done() for s in streams):
            return i + 1
    raise AssertionError("streams did not finish in %d ticks" % ticks)


def _kind(rec):
    return re.match(r"decode\[([a-z]+)", rec["name"]).group(1)


def _inside(child, parent):
    return (parent["ts_us"] <= child["ts_us"] and
            child["ts_us"] + child["dur_ms"] * 1e3
            <= parent["ts_us"] + parent["dur_ms"] * 1e3)


@pytest.fixture(scope="module")
def capture(model, tmp_path_factory):
    """One profiled session: a server with the prefix store on takes two
    unique prompts, idles, then takes the first prompt again; a second
    server speculates. Returns the ``decode[...]`` records of each and what
    the servers counted meanwhile."""
    srv = mx.serve.GenerativeServer(model, slots=SLOTS, timeout_ms=60000.0)
    srv.warmup(prompt_buckets=[5, 12], max_tokens=40)
    spec = mx.serve.GenerativeServer(model, slots=SLOTS // 2,
                                     timeout_ms=60000.0, draft=NGramDraft(),
                                     spec_k=3, prefix_cache=False)
    spec.warmup(prompt_buckets=[5], max_tokens=40)
    rng = np.random.RandomState(28)
    pa = rng.randint(1, 256, (5,)).astype(np.int32)
    pb = rng.randint(1, 256, (12,)).astype(np.int32)
    profiler.set_config(filename=str(
        tmp_path_factory.mktemp("spans") / "p.json"))
    out = {}
    profiler.set_state("run")
    try:
        n0, steps0 = profiler.num_records(), srv.metrics.steps
        for _ in range(5):                 # one idle stretch, five ticks
            assert srv.step() == 0
        a, b = _submit(srv, pa, 4), _submit(srv, pb, 3)
        out["ticks"] = _pump(srv, [a, b])
        for _ in range(3):
            assert srv.step() == 0
        c = _submit(srv, pa, 2)            # a repeated prompt: prefix hit
        out["ticks"] += _pump(srv, [c])
        assert srv.step() == 0             # opens the third idle stretch
        out["steps"] = srv.metrics.steps - steps0
        vsteps0 = spec.metrics.steps
        v = _submit(spec, pa, 6)
        _pump(spec, [v])
        out["verify_steps"] = spec.metrics.steps - vsteps0
    finally:
        profiler.set_state("stop")
    srv.step()          # a tick with the profiler off ends the idle span
    spec.step()
    recs = [r for r in json.loads(profiler.dumps())[n0:]
            if r["name"].startswith("decode[")]
    # the two servers' records are told apart by their number of slots
    out["plain"] = [r for r in recs if r["args"]["slots"] == SLOTS]
    out["spec"] = [r for r in recs if r["args"]["slots"] == SLOTS // 2]
    out.update(srv=srv, streams={"a": a, "b": b, "c": c})
    yield out
    srv.stop()
    spec.stop()


def test_every_kind_of_span_appears_in_the_given_format(capture):
    recs = capture["plain"]
    for r in recs:
        m = re.match(r"^decode\[([a-z]+)\d* fill=(\d\.\d\d) b%d"
                     r"(?: kvread=(\d\.\d{3}) ahead=[01])?\]$" % SLOTS,
                     r["name"])
        assert m, r["name"]
        assert r["cat"] == "serve"
        # a step says what share of the pool's 128-position K/V blocks its
        # live slots hold: pages this short are one block a slot, the fill
        kind, fill, kvread = m.groups()
        assert (kvread is not None) == (kind == "step"), r["name"]
        if kvread is not None:
            assert float(kvread) == float(fill)
    kinds = {re.match(r"decode\[(\w+)", r["name"]).group(1) for r in recs}
    assert kinds == {"idle", "tick", "join8", "join16", "prefill8",
                     "prefill16", "readout8", "readout16", "ctl", "step",
                     "deliver"}


def test_one_join_record_per_request_carries_its_trace_id(capture):
    joins = [r for r in capture["plain"] if _kind(r) == "join"]
    assert len(joins) == 3
    for name, plen, kind in (("a", 5, "prefill"), ("b", 12, "prefill"),
                             ("c", 5, "inject")):
        stream = capture["streams"][name]
        mine = [r for r in joins
                if r["args"]["trace_id"] == stream.trace_id]
        assert len(mine) == 1, name
        assert mine[0]["args"]["prompt_len"] == plen
        assert mine[0]["args"]["kind"] == kind


def test_prefill_and_readout_lie_inside_their_join(capture):
    recs = capture["plain"]
    joins = [r for r in recs if _kind(r) == "join"]
    prefills = [r for r in recs if _kind(r) == "prefill"]
    readouts = [r for r in recs if _kind(r) == "readout"]
    assert len(prefills) == 3 and len(readouts) == 2
    for child in prefills + readouts:
        assert sum(_inside(child, j) for j in joins) == 1, child["name"]
    # a repeated prompt is injected from the store: nothing is read out
    inject = [j for j in joins if j["args"]["kind"] == "inject"]
    assert len(inject) == 1
    assert not any(_inside(r, inject[0]) for r in readouts)
    assert sum(_inside(p, inject[0]) for p in prefills) == 1


def test_readout_counts_the_bytes_the_store_keeps(capture):
    srv = capture["srv"]
    stored = {k_stack.shape[2]: k_stack.nbytes + v_stack.nbytes
              for k_stack, v_stack, _n, _last in srv.prefix._store.values()}
    assert sorted(stored) == [8, 16]
    for r in capture["plain"]:
        m = re.match(r"decode\[readout(\d+)", r["name"])
        if m:
            assert r["args"]["mb"] == round(stored[int(m.group(1))] / 1e6, 3)


@pytest.mark.parametrize("which,step_kind,counted", [
    ("plain", "step", "steps"), ("spec", "verify", "verify_steps")])
def test_one_step_and_one_deliver_record_per_counted_step(
        capture, which, step_kind, counted):
    recs = capture[which]
    assert capture[counted] > 0
    assert sum(_kind(r) == step_kind for r in recs) == capture[counted]
    assert sum(_kind(r) == "deliver" for r in recs) == capture[counted]


def test_a_step_says_whether_it_went_out_ahead_and_a_verify_does_not(
        capture):
    """One step in flight (ISSUE 36): every busy tick of the plain path
    writes one ``step`` span that carries ``ahead=`` (1: its dispatch went
    out while another step was in flight) and one ``deliver`` span; a
    stretch opens with ``ahead=0``. Speculation keeps its serial order: its
    ``verify<k>`` ticks carry no such field."""
    recs = capture["plain"]
    ticks = [r for r in recs if _kind(r) == "tick"]
    steps = [r for r in recs if _kind(r) == "step"]
    delivers = [r for r in recs if _kind(r) == "deliver"]
    for t in ticks:
        assert sum(_inside(r, t) for r in steps) == 1
        assert sum(_inside(r, t) for r in delivers) == 1
    ahead = [int(re.search(r" ahead=([01])\]$", r["name"]).group(1))
             for r in steps]
    # a (4 tokens) and b (3) together: the opening tick finds no step in
    # flight and sends two, the next sends a's last step behind them, the
    # third reads it and sends none (a budget's end is known beforehand);
    # then c, whose two tokens are its prefill's and one step's
    assert ahead == [0, 1, 0, 0]
    stats = capture["srv"].stats()
    assert stats["steps_ahead"] == 2 and stats["rows_discarded"] == 0
    spec = capture["spec"]
    assert any(_kind(r) == "verify" for r in spec)
    assert not any("ahead=" in r["name"] for r in spec)


def test_a_busy_tick_is_one_span_and_an_idle_stretch_is_one_span(capture):
    recs = capture["plain"]
    idle = [r for r in recs if _kind(r) == "idle"]
    ticks = [r for r in recs if _kind(r) == "tick"]
    # five empty ticks, three, and the one that the profiler's stop ended
    assert len(idle) == 3
    assert all(r["args"]["active"] == 0 for r in idle)
    assert len(ticks) == capture["ticks"]
    # every other record lies inside exactly one tick, none inside an idle
    for r in recs:
        if _kind(r) not in ("idle", "tick"):
            assert sum(_inside(r, t) for t in ticks) == 1, r["name"]
            assert not any(_inside(r, i) for i in idle)
    assert not any(_inside(t, i) for t in ticks for i in idle)


def test_profiler_off_one_switch_read_a_tick_and_no_span(model, monkeypatch):
    srv = mx.serve.GenerativeServer(model, slots=SLOTS, timeout_ms=60000.0)
    srv.warmup(prompt_buckets=[5], max_tokens=40)

    def never(*a, **k):
        raise AssertionError("decode_scope entered with the profiler off")

    reads = []
    monkeypatch.setattr(profiler, "decode_scope", never)
    monkeypatch.setattr(profiler, "is_running",
                        lambda: reads.append(1) is not None and False)
    n0 = profiler.num_records()
    assert srv.step() == 0 and len(reads) == 1
    s = _submit(srv, np.arange(1, 6, dtype=np.int32), 8)
    assert srv.step() == 1                 # the join and the first step
    del reads[:]
    for _ in range(3):                     # decode steps alone
        assert srv.step() == 1
    assert len(reads) == 3
    _pump(srv, [s])
    assert len(s.result(5)) == 8
    assert profiler.num_records() == n0
    srv.stop()


@pytest.mark.parametrize("quantize", [None, "int8"])
def test_each_kind_of_program_carries_its_own_name(quantize):
    """One program a kind whatever the pages' format: the names say the
    kind and the buckets, never the format."""
    model = gpt_nano()      # its own: ``quantize`` rewrites the weights
    model.initialize()
    srv = mx.serve.GenerativeServer(model, slots=SLOTS, timeout_ms=60000.0,
                                    quantize=quantize)
    srv.warmup(prompt_buckets=[5, 12], max_tokens=40)
    spec = mx.serve.GenerativeServer(model, slots=SLOTS, timeout_ms=60000.0,
                                     draft=NGramDraft(), spec_k=3,
                                     prefill_chunk=8, prefix_cache=False,
                                     quantize=quantize)
    spec.warmup(prompt_buckets=[5], max_tokens=40)
    names = {}
    for kind, fns in (("step", srv._decode_fns),
                      ("prefill", srv._prefill_fns),
                      ("extract", srv._extract_fns),
                      ("inject", srv._inject_fns),
                      ("verify", spec._verify_fns),
                      ("chunk", spec._chunk_fns)):
        assert fns, kind
        for fn in fns.values():
            head = fn.compiled_for().as_text().splitlines()[0]
            name = re.match(r"HloModule (\w+)", head).group(1)
            assert name.startswith("jit_pure_" + kind), (kind, name)
            names.setdefault(kind, set()).add(name)
    assert names["step"] == {"jit_pure_step_c64"}
    assert names["prefill"] == {"jit_pure_prefill_t8c64",
                                "jit_pure_prefill_t16c64"}
    every = [n for ns in names.values() for n in ns]
    assert len(every) == len(set(every)) == 9
    srv.stop()
    spec.stop()


def test_kvread_counts_the_blocks_the_live_slots_hold():
    """``kvread=`` of a step's span: the 128-position K/V blocks that hold a
    live position of an active slot (the step's own token included) over
    the blocks of the pool, from the prompt's length and the tokens
    delivered: a free slot holds none."""
    from mxnet_tpu.models.gpt import GPTModel

    m = GPTModel(vocab_size=256, units=64, num_layers=2, num_heads=2,
                 max_length=512, dropout=0.0)
    m.initialize()
    rng = np.random.RandomState(32)
    srv = mx.serve.GenerativeServer(m, slots=SLOTS, timeout_ms=60000.0)
    try:                                        # stepped by hand: no thread
        long = _submit(srv, rng.randint(1, 256, (130,)).astype(np.int32), 300)
        short = _submit(srv, rng.randint(1, 256, (5,)).astype(np.int32), 300)
        while not (long.tokens and short.tokens):
            assert srv.step() > 0
        assert srv.cache.capacity == 512        # 4 blocks a slot and layer
        active = srv.cache.active_mask()
        held = sum(-(-(n + len(s.tokens)) // 128)
                   for n, s in ((130, long), (5, short)))
        assert held == 3
        assert srv._step_tag(active) == "kvread=%.3f" % (held / (SLOTS * 4))
        assert srv._step_tag(np.zeros(SLOTS, np.int32)) == "kvread=0.000"
    finally:
        srv.stop()
