"""The Brumby style decoder (``models/brumby.py``: power retention, whose
cache is a recurrent state of fixed size a slot) on the served path, at a
small size on the CPU, against the benchmark's plain reference
(``benchmark/reference/brumby_14b.py``, the quadratic form, which imports
nothing of the program): d 64, 10 query / 2 K/V heads of width 16 (5 queries
a K/V head, as published), inner width 96, 2 layers, seeded float32 weights.
"""
import importlib.util
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import _trace, profiler, serve
from mxnet_tpu.models.brumby import BrumbyModel, brumby_nano
from mxnet_tpu.ndarray import NDArray
from mxnet_tpu.ops import attention as A
from mxnet_tpu.ops import functional as Fn
from mxnet_tpu.ops import retention as R
from mxnet_tpu.serve import ServeError
from mxnet_tpu.serve.kv_cache import PagedKVCache, StatePage

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    spec = importlib.util.spec_from_file_location(
        "reference_brumby_14b",
        os.path.join(ROOT, "benchmark", "reference", "brumby_14b.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.ROWS = 8          # its query-row block, at the tiny size
    return mod


ref = _reference()

CFG = dict(vocab_size=256, units=64, num_layers=2, num_heads=10,
           num_kv_heads=2, head_dim=16, hidden=96, rope_theta=1000000.0,
           rms_norm_eps=1e-6, max_length=128)


def _seeded(seed=0):
    """(sizes, model, the reference's parameters) with the same seeded
    float32 weights in both."""
    model = BrumbyModel(**CFG)
    model.initialize()
    rs = np.random.RandomState(seed)
    params = {}
    for p in model.collect_params().values():
        name = re.sub(r"^brumbymodel\d+_", "", p.name)
        w = rs.normal(0, 0.05, p.shape).astype(np.float32)
        if name.endswith("gamma"):
            w += 1
        p.set_data(NDArray(jnp.asarray(w)))
        params[name] = jnp.asarray(w)
    assert sorted((n, tuple(s)) for n, s in ref.param_specs(CFG)) \
        == sorted((n, tuple(a.shape)) for n, a in params.items())
    return CFG, model, params


@pytest.fixture(scope="module")
def served():
    cfg, model, params = _seeded()
    model.hybridize()
    srv = serve.GenerativeServer(model, slots=4)
    srv.start()
    yield cfg, model, params, srv
    srv.stop()


# -------------------------------------------------- the two small ops
def test_rms_norm_is_the_three_line_form():
    rs = np.random.RandomState(0)
    x = rs.normal(0, 3, (5, 7, 32)).astype(np.float32)
    g = rs.normal(1, 0.1, (32,)).astype(np.float32)
    want = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6) * g
    got = Fn.rms_norm(jnp.asarray(x), jnp.asarray(g), eps=1e-6)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-6, atol=2e-6)
    half = Fn.rms_norm(jnp.asarray(x, jnp.bfloat16), jnp.asarray(g), eps=1e-6)
    assert half.dtype == jnp.bfloat16      # float32 inside, one cast back


def test_rotary_half_split_is_the_three_line_form():
    rs = np.random.RandomState(1)
    x = rs.normal(0, 1, (2, 3, 6, 16)).astype(np.float32)
    pos = np.array([[0, 1, 2, 3, 40, 1000], [7, 7, 0, 5, 6, 90]])
    ang = pos[:, None, :, None] * 1e6 ** (-np.arange(0, 16, 2) / 16.0)
    x1, x2 = x[..., :8], x[..., 8:]
    want = np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                           x2 * np.cos(ang) + x1 * np.sin(ang)], -1)
    got = A.rotary(jnp.asarray(x), jnp.asarray(pos), theta=1e6,
                   pairing="half")
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
    # the other pairing is another function of the same input
    inter = A.rotary(jnp.asarray(x), jnp.asarray(pos), theta=1e6)
    assert float(jnp.abs(inter - got).max()) > 0.1
    with pytest.raises(ValueError, match="pairing"):
        A.rotary(jnp.asarray(x), jnp.asarray(pos), pairing="quarter")


# ------------------------------------------ the op: three forms, one function
def _quadratic(q, k, v, log_c, eps=1e-6):
    """``A_ij`` from a running sum of ``log c``, in float64 ``numpy``."""
    B, H, T, D = q.shape
    G = H // k.shape[1]
    q, k, v = (a.astype(np.float64) for a in (q, k, v))
    L = np.cumsum(log_c.astype(np.float64), -1)
    out = np.zeros(q.shape)
    for b in range(B):
        for h in range(H):
            s = (q[b, h] @ k[b, h // G].T) ** 2 / D
            a = np.tril(s * np.exp(L[b, h // G][:, None]
                                   - L[b, h // G][None, :]))
            out[b, h] = a @ v[b, h // G] / (a.sum(-1, keepdims=True) + eps)
    return out


def test_phi_is_the_symmetric_square():
    rs = np.random.RandomState(2)
    a, b = rs.normal(0, 1, (2, 3, 16)).astype(np.float32)
    pa, pb = R.phi(jnp.asarray(a)), R.phi(jnp.asarray(b))
    assert pa.shape == (3, 9, 16)
    np.testing.assert_allclose(np.asarray(jnp.sum(pa * pb, (-2, -1))),
                               (a * b).sum(-1) ** 2, rtol=1e-5)
    assert R.phi_rows(128) * 128 == 8320        # <= 9216, >= 8256


def test_retention_recurrent_chunked_and_quadratic_agree():
    """Over a few hundred positions WITH GATES CLOSE TO 1 (``log c`` in
    [-0.02, 0]): a state that remembers hundreds of tokens, so that a fault
    in old state shows (seeded N(0, 0.02^2) weights give ``c`` near 0.5 and a
    state that forgets in some ten tokens). Grouped heads, 5 queries a K/V
    head; several chunks and a ragged last one; rows past ``plen`` are pad."""
    rs = np.random.RandomState(3)
    B, H, Hkv, T, D = 2, 10, 2, 300, 16
    q = rs.normal(0, 1, (B, H, T, D)).astype(np.float32)
    k = rs.normal(0, 1, (B, Hkv, T, D)).astype(np.float32)
    v = rs.normal(0, 1, (B, Hkv, T, D)).astype(np.float32)
    log_c = rs.uniform(-0.02, 0, (B, Hkv, T)).astype(np.float32)
    plen = np.array([300, 211])
    live = np.arange(T)[None] < plen[:, None]
    want = _quadratic(q, k, v, log_c)
    jq, jk, jv, jc = (jnp.asarray(a) for a in (q, k, v, log_c))

    o, S, z = R.power_retention(jq, jk, jv, jc, None, jnp.asarray(live),
                                chunk=64, rows=16)       # 5 chunks, last 44
    assert S.shape == (B, Hkv, 9 * D, D) and z.shape == (B, Hkv, 9, D)
    assert S.dtype == z.dtype == jnp.float32

    state, rows = R.zero_state(B, Hkv, D), []
    step = jax.jit(R.power_retention)
    for t in range(T):
        at = slice(t, t + 1)
        o_t, *state = step(jq[:, :, at], jk[:, :, at], jv[:, :, at],
                           jc[:, :, at], tuple(state),
                           jnp.asarray(live[:, t]))
        rows.append(np.asarray(o_t))
    rec = np.concatenate(rows, 2)
    # the first rows divide by the square of ONE small dot product, where
    # the recurrent form's float32 sum of 144 products cancels: from row 8
    for b in range(B):
        rows_b = slice(8, plen[b])
        np.testing.assert_allclose(np.asarray(o)[b, :, :plen[b]],
                                   want[b, :, :plen[b]], atol=2e-5)
        np.testing.assert_allclose(rec[b, :, rows_b], want[b, :, rows_b],
                                   atol=2e-4)
    # the state after the prompt's live rows, whichever way it was folded:
    # the pad rows of row 1 added nothing and turned no gate
    np.testing.assert_allclose(np.asarray(state[0]), np.asarray(S),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(state[1]), np.asarray(z),
                               rtol=1e-4, atol=1e-4)
    assert float(jnp.abs(S).max()) > 10          # hundreds of tokens in it

    # a prompt in two calls: the state between them is all that is carried
    o1, S1, z1 = R.power_retention(jq[:, :, :128], jk[:, :, :128],
                                   jv[:, :, :128], jc[:, :, :128], chunk=64)
    o2, _S, _z = R.power_retention(jq[:, :, 128:200], jk[:, :, 128:200],
                                   jv[:, :, 128:200], jc[:, :, 128:200],
                                   (S1, z1), chunk=64)
    np.testing.assert_allclose(np.asarray(o2), want[:, :, 128:200],
                               atol=2e-5)


def test_a_free_slots_state_is_untouched_by_a_step():
    rs = np.random.RandomState(4)
    B, H, Hkv, D = 3, 10, 2, 16
    q = jnp.asarray(rs.normal(0, 1, (B, H, 1, D)), jnp.float32)
    k, v = (jnp.asarray(rs.normal(0, 1, (B, Hkv, 1, D)), jnp.float32)
            for _ in range(2))
    log_c = jnp.asarray(rs.uniform(-1, 0, (B, Hkv, 1)), jnp.float32)
    S = jnp.asarray(rs.normal(0, 1, (B, Hkv, 9 * D, D)), jnp.float32)
    S = S.at[1, 0, 0, 0].set(-0.0)       # c * -0.0 + 0.0 would be +0.0
    z = jnp.asarray(rs.normal(3, 1, (B, Hkv, 9, D)), jnp.float32)
    _o, S1, z1 = R.power_retention(q, k, v, log_c, (S, z),
                                   jnp.asarray([1, 0, 1]))
    for new, old in ((S1, S), (z1, z)):
        assert np.asarray(new[1]).tobytes() == np.asarray(old[1]).tobytes()
        assert float(jnp.abs(new[0] - old[0]).max()) > 0.1


# ------------------------------------------------- the model, the reference
def test_full_forward_is_the_references_logits():
    cfg, model, params = _seeded(seed=5)
    model.hybridize()
    toks = np.random.RandomState(5).randint(0, cfg["vocab_size"], (1, 48))
    got = np.asarray(model(NDArray(jnp.asarray(toks, jnp.int32)))._data)[0]
    want = np.asarray(ref.logits(cfg, params, toks[0], 1, 47))
    assert got.shape == (48, 256) and np.abs(want).max() > 0.3
    np.testing.assert_allclose(got[:47], want, atol=2e-5)


@pytest.mark.parametrize("prompt_len,new", [
    (5, 30),      # nearly all of it decoded through the state
    (40, 20),     # a bucket of 64: 24 pad rows behind the prompt
    (64, 8),      # a bucket filled to its last row
    (1, 6)])      # a state that starts from one token
def test_prefill_then_decode_is_the_references_forward(served, prompt_len,
                                                       new):
    """Prefill, then decode through the state pages, against the
    reference's one full forward over prompt + served tokens: logits, not
    tokens: every served token lies within rounding of the reference's
    best (float32 both)."""
    cfg, _model, params, srv = served
    rs = np.random.RandomState(prompt_len)
    prompt = rs.randint(0, cfg["vocab_size"], prompt_len)
    toks = srv.submit(prompt, max_new_tokens=new).result(timeout_s=120)
    lg = np.asarray(ref.served_logits(cfg, params, prompt, toks, pad_to=8))
    gap = lg.max(-1) - lg[np.arange(new), toks]
    assert gap.max() <= 1e-4, gap


def test_decode_step_logits_are_the_references(served):
    """The step's own logits, slot by slot at different positions, one slot
    free: against the reference's row for each live slot."""
    cfg, model, params, _srv = served
    plist = list(model.collect_params().values())
    cache = PagedKVCache(2, 2, 16, slots=3, max_capacity=128, page=StatePage)
    cache.ensure_capacity(64)
    rs = np.random.RandomState(9)
    seqs = [rs.randint(0, 256, n) for n in (9, 4, 17)]

    def trace(fn, *args):
        with _trace.trace_scope(jax.random.PRNGKey(0), False) as t:
            t.param_store = {id(p): p.data()._data for p in plist}
            return fn(_trace.F, *args)

    state = cache.state
    for slot, seq in enumerate(seqs):
        tokens = jnp.asarray(seq[None, :-1], jnp.int32)
        _lg, kvs, _aux = trace(model.forward_collect_kv, tokens,
                               jnp.int32(len(seq) - 1))
        state = [page.write_prompt(S, z, len(seq) - 1, jnp.int32(slot))
                 for page, (S, z) in zip(state, kvs)]
    before = [np.asarray(page.S[1]).copy() for page in state]
    logits, state, aux = trace(
        model.decode_step, jnp.asarray([[s[-1]] for s in seqs], jnp.int32),
        state, jnp.asarray([len(s) - 1 for s in seqs], jnp.int32),
        jnp.asarray([1, 0, 1], jnp.int32))
    assert aux is None and all(type(p) is StatePage for p in state)
    for slot in (0, 2):
        seq = np.concatenate([seqs[slot], [0]])
        want = np.asarray(ref.served_logits(cfg, params, seq[:-1], seq[-1:],
                                            pad_to=8))
        np.testing.assert_allclose(np.asarray(logits)[slot, 0], want[0],
                                   atol=2e-5)
    for page, old in zip(state, before):
        assert np.asarray(page.S[1]).tobytes() == old.tobytes()


# --------------------------------------------------------- the state page
def test_a_repeated_prompt_is_a_hit_that_injects_the_snapshot(served):
    cfg, _model, params, srv = served
    prompt = np.random.RandomState(7).randint(0, cfg["vocab_size"], 37)
    s0 = srv.stats()
    first = srv.submit(prompt, max_new_tokens=12).result(timeout_s=120)
    again = srv.submit(prompt, max_new_tokens=12).result(timeout_s=120)
    s1 = srv.stats()
    assert again == first
    assert s1["prefix_hits"] - s0["prefix_hits"] == 1
    # what the store keeps is the state, not positions: two stacks that are
    # not K and V, of one size whatever the prompt
    S_stack, z_stack, plen, _last = srv.prefix.get(prompt)
    assert plen == 37
    assert S_stack.shape == (2, 2, 9 * 16, 16) and S_stack.dtype == np.float32
    assert z_stack.shape == (2, 2, 9, 16)
    one = S_stack.nbytes + z_stack.nbytes
    assert s1["state_snapshots_out"] - s0["state_snapshots_out"] == 1
    assert s1["state_snapshots_in"] - s0["state_snapshots_in"] == 1
    assert s1["state_snapshot_bytes_out"] - s0["state_snapshot_bytes_out"] \
        == s1["state_snapshot_bytes_in"] - s0["state_snapshot_bytes_in"] \
        == one
    assert s1["state_bytes"] == 4 * one == srv.cache.nbytes()
    # and the injected snapshot yields the reference's logits
    lg = np.asarray(ref.served_logits(cfg, params, prompt, again, pad_to=8))
    assert (lg.max(-1) - lg[np.arange(12), again]).max() <= 1e-4


def test_a_slot_taken_again_starts_from_nothing():
    """One slot, two streams in turn: the second's tokens are those of a
    fresh server (the join's snapshot replaces all of the slot's state), and
    a chunk program's ``take_slot(fresh)`` hands a new stream zeros."""
    cfg, model, params = _seeded(seed=6)
    model.hybridize()
    rs = np.random.RandomState(6)
    a, b = rs.randint(0, 256, 30), rs.randint(0, 256, 11)
    with serve.GenerativeServer(model, slots=1, prefix_cache=False) as srv:
        srv.submit(a, max_new_tokens=20).result(timeout_s=120)
        second = srv.submit(b, max_new_tokens=10).result(timeout_s=120)
    lg = np.asarray(ref.served_logits(cfg, params, b, second, pad_to=8))
    assert (lg.max(-1) - lg[np.arange(10), second]).max() <= 1e-4
    page = StatePage(jnp.ones((2, 2, 9 * 16, 16)), jnp.ones((2, 2, 9, 16)))
    fresh = page.take_slot(jnp.int32(1), jnp.bool_(True))
    kept = page.take_slot(jnp.int32(1), jnp.bool_(False))
    assert float(jnp.abs(fresh.S).max()) == float(jnp.abs(fresh.z).max()) == 0
    assert float(kept.S.min()) == 1 and kept.S.shape == (1, 2, 9 * 16, 16)


def test_the_state_page_has_no_time_axis():
    c = PagedKVCache(2, 2, 16, slots=4, max_capacity=128, page=StatePage)
    assert c.ensure_capacity(16) and c.capacity == 16
    before = c.nbytes()
    assert c.ensure_capacity(100) and c.capacity == 128   # names programs
    assert c.migrations == 1 and c.nbytes() == before     # sizes nothing
    one = 2 * 2 * (9 * 16 * 16 + 9 * 16) * 4
    assert before == 4 * one == c.nbytes_unquantized()
    assert c.page_lengths(8) == c.page_lengths(64) == [0, 0]
    assert c.page_bytes(8) == c.page_bytes(64) == one     # not the prompt's
    assert c.snapshots
    assert StatePage.step_tag(c.state, [5, 90, 7]) == "state=%.1f" % (
        3 * one * 1e-6)


def test_step_spans_carry_state_in_the_place_of_kvread(served):
    _cfg, _model, _params, srv = served
    profiler.set_config(filename=os.devnull)
    n0 = profiler.num_records()      # the records before are other tests'
    profiler.set_state("run")
    try:
        srv.submit([1, 2, 3], max_new_tokens=4).result(timeout_s=120)
    finally:
        profiler.set_state("stop")
    steps = [r["name"] for r in json.loads(profiler.dumps())[n0:]
             if r["name"].startswith("decode[step ")]
    assert steps and all(" state=0.0" in s and "kvread" not in s
                         for s in steps), steps


@pytest.mark.parametrize("kwargs,word", [
    (dict(quantize="int8"), "int8_pages"),
    (dict(draft=serve.NGramDraft()), "multi_token"),
    (dict(prefill_chunk=16), "multi_token")])
def test_what_a_state_cannot_do_is_refused_by_name(kwargs, word):
    model = brumby_nano()
    model.initialize()
    with pytest.raises(ServeError, match=word):
        serve.GenerativeServer(model, slots=2, **kwargs)
