"""The Nemotron-H style hybrid (``models/nemotron_h.py``: Mamba-2 mixers,
whose cache is a snapshot of state, beside an attention layer with K/V
pages, and latent relu^2 experts with a selection bias) on the served path,
at a small size on the CPU, against the benchmark's plain reference
(``benchmark/reference/nemotron_3_super.py``, whose recurrence is sequential
and which imports nothing of the program): d 64, the pattern ``MEM*E``, 8
Mamba heads of 16 in 2 groups of state 16 (conv 4, chunk 128), 4 query / 2
K/V heads of 16 without rotary, 8 experts of 32 top-2 in a latent of 48
(experts 2..5 held) beside a shared expert of 64, seeded float32 weights.

Tolerances: program and reference compute in float32 in different orders
(chunked against sequential, a masked cache against a full forward), which
moves a logit by some 1e-6; 1e-4 holds that with room and is a hundred times
under what the reference's own int8 control moves
(``test_the_int8_control_fails_the_tolerance``).
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
from mxnet_tpu.models import nemotron_h as NH
from mxnet_tpu.ndarray import NDArray
from mxnet_tpu.ops import ssm
from mxnet_tpu.serve import ServeError
from mxnet_tpu.serve.kv_cache import (PagedKVCache, PlainPage, SSMPage,
                                      read_prompt, stored_kvs, write_prompt)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 1e-4     # a logit's gap below the reference's best (module docstring)


def _reference():
    spec = importlib.util.spec_from_file_location(
        "reference_nemotron_3_super",
        os.path.join(ROOT, "benchmark", "reference", "nemotron_3_super.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.ROWS, mod.FFN_ROWS = 16, 32                 # its blocks, at this size
    return mod


ref = _reference()

CFG = dict(vocab_size=256, units=64, pattern="MEM*E", num_experts=8,
           experts_held=4, first_expert=2, experts_per_token=2,
           moe_latent=48, expert_hidden=32, shared_hidden=64, mamba_heads=8,
           mamba_head_dim=16, n_groups=2, state=16, conv=4, chunk=128,
           num_heads=4, num_kv_heads=2, head_dim=16,
           routed_scaling_factor=5.0, rms_norm_eps=1e-5, max_length=512)


def _seeded(seed=0, set_=None, **over):
    """(sizes, model, the reference's parameters) with the same seeded
    float32 weights in both; ``set_`` (name -> array) overrides leaves."""
    cfg = dict(CFG, **over)
    model = NH.NemotronHModel(**cfg)
    model.initialize()
    rs = np.random.RandomState(seed)
    params = {}
    for p in model.collect_params().values():
        name = re.sub(r"^nemotronhmodel\d+_", "", p.name)
        w = rs.normal(0, 0.08, p.shape).astype(np.float32)
        if name.endswith("gamma"):
            w += 1
        if set_ and name in set_:
            w = np.asarray(set_[name], np.float32)
        p.set_data(NDArray(jnp.asarray(w)))
        params[name] = jnp.asarray(w)
    assert sorted((n, tuple(s)) for n, s in ref.param_specs(cfg)) \
        == sorted((n, tuple(a.shape)) for n, a in params.items())
    return cfg, model, params


@pytest.fixture(scope="module")
def served():
    cfg, model, params = _seeded()
    model.hybridize()
    srv = serve.GenerativeServer(model, slots=4)
    srv.start()
    yield cfg, model, params, srv
    srv.stop()


def _trace_call(model, fn, *args):
    plist = list(model.collect_params().values())
    with _trace.trace_scope(jax.random.PRNGKey(0), False) as t:
        t.param_store = {id(p): p.data()._data for p in plist}
        return fn(_trace.F, *args)


def _gap(cfg, params, prompt, toks):
    lg = np.asarray(ref.served_logits(cfg, params, prompt, toks, pad_to=32))
    return (lg.max(-1) - lg[np.arange(len(toks)), toks]).max()


# ------------------------------------------ the model against the reference
def test_full_forward_is_the_references_logits():
    cfg, model, params = _seeded(seed=5)
    model.hybridize()
    toks = np.random.RandomState(5).randint(0, 256, (1, 160))
    got = np.asarray(model(NDArray(jnp.asarray(toks, jnp.int32)))._data)[0]
    want = np.asarray(ref.logits(cfg, params, toks[0], 1, 159))
    assert got.shape == (160, 256) and np.abs(want).max() > 0.3
    np.testing.assert_allclose(got[:159], want, atol=3e-5)


@pytest.mark.parametrize("prompt_len,new", [
    (5, 40),      # nearly all decoded
    (127, 8),     # one short of a chunk
    (129, 8),     # one past a chunk of 128: two chunks, pad rows behind
    (200, 12),    # a bucket of 256: 56 pad rows that must add nothing
    (1, 6)])      # a state that starts from one token
def test_prefill_then_decode_is_the_references_forward(served, prompt_len,
                                                       new):
    """Prefill (chunked scan, attention over the bucket), then decode
    through the hybrid cache (one token against the snapshots and the
    rows), against the reference's one full forward (sequential
    recurrence) over prompt + served tokens: every served token lies within
    ``TOL`` of the reference's best logit."""
    cfg, _model, params, srv = served
    prompt = np.random.RandomState(prompt_len).randint(0, 256, prompt_len)
    toks = srv.submit(prompt, max_new_tokens=new).result(timeout_s=120)
    assert _gap(cfg, params, prompt, toks) <= TOL


def test_the_int8_control_fails_the_tolerance(served):
    """The reference's own int8 control, at the positions of a served
    answer, lies far outside ``TOL``: the comparison can tell a lower
    precision from the program's float32."""
    cfg, _model, params, srv = served
    prompt = np.random.RandomState(31).randint(0, 256, 40)
    toks = srv.submit(prompt, max_new_tokens=12).result(timeout_s=120)
    lg = np.asarray(ref.served_logits(cfg, params, prompt, toks, pad_to=32))
    low = np.asarray(ref.served_logits(cfg, params, prompt, toks,
                                       precision="int8", pad_to=32))
    assert np.abs(low - lg).max() > 100 * TOL


def _long_memory():
    """Leaves that make both M layers decay by exp(-softplus(-5)) = 0.9933
    a token: ``A_log`` 0, ``dt_bias`` -5, and no row of the input
    projection feeding dt."""
    H, P, G, N = 8, 16, 2, 16
    inner = H * P
    rows = 2 * inner + 2 * G * N + H
    proj = np.random.RandomState(40).normal(0, 0.08, (rows, 64))
    proj[-H:] = 0.0                              # dt = softplus(dt_bias)
    set_ = {}
    for i in (0, 2):
        set_.update({"layer%d_mixer_A_log" % i: np.zeros(H),
                     "layer%d_mixer_dt_bias" % i: np.full(H, -5.0),
                     "layer%d_mixer_in_proj_weight" % i: proj})
    return set_


def test_long_memory_reaches_back_300_positions():
    """With ``A_log`` 0 and ``dt_bias`` -5, and no row of the input
    projection feeding dt, every head decays by exp(-softplus(-5)) = 0.9933
    a token: after 320 positions the first token still weighs 0.12. The
    served answer agrees with the reference there, and a different first
    token still moves the state after the prompt: the comparison sees old
    state, which seeded weights (a decay of 0.1-0.8) hide."""
    decay = float(np.exp(-np.log1p(np.exp(-5.0))))
    assert decay >= 0.99 and decay ** 320 > 0.1
    cfg, model, params = _seeded(seed=41, set_=_long_memory())
    model.hybridize()
    prompt = np.random.RandomState(41).randint(0, 256, 320)
    other = prompt.copy()
    other[0] = (other[0] + 1) % 256
    with serve.GenerativeServer(model, slots=2) as srv:
        toks = srv.submit(prompt, max_new_tokens=8).result(timeout_s=120)
        srv.submit(other, max_new_tokens=1).result(timeout_s=120)
        # the first M layer's state after each prompt, as the store keeps it
        states = [srv.prefix.get(p)[1][0] for p in (prompt, other)]
    assert _gap(cfg, params, prompt, toks) <= TOL
    moved = np.abs(states[0] - states[1]).max() / np.abs(states[0]).max()
    assert moved > 1e-3


def test_the_bf16_state_control_departs_more_where_the_state_remembers():
    """The reference's ``bf16_state`` control (the state and the operands of
    its update rounded to bfloat16 a position, the matmuls float32) lies
    far outside ``TOL`` of the float32 logits, and after a prompt of 320
    positions it departs over 5 x further where the state keeps them
    (decay 0.9933) than where seeded weights forget within a few tokens:
    the rounding builds up in old state, which the cell's seeded weights
    do not keep (PERF.md section 7)."""
    toks = np.random.RandomState(41).randint(0, 256, 352)
    moved = []
    for set_ in (None, _long_memory()):
        cfg, _model, params = _seeded(seed=41, set_=set_)
        lg, low = (np.asarray(ref.served_logits(
            cfg, params, toks[:320], toks[320:], precision=p, pad_to=32))
            for p in ("float32", "bf16_state"))
        moved.append(np.abs(low - lg).max())
    assert moved[0] > 10 * TOL and moved[1] > 5 * moved[0]


# ------------------------------------------ the three forms of the recurrence
@pytest.mark.parametrize("T,chunk,plen", [(45, 16, 45), (45, 16, 30),
                                          (130, 128, 129)])
def test_chunked_sequential_and_the_step_agree(T, chunk, plen):
    """The chunked scan of a prompt of ``plen`` live rows in a bucket of
    ``T``, the XLA step run a token at a time, and the Pallas ``ssd_step``
    in interpret mode: the same outputs and the same state, to float32
    rounding of sums over up to 130 decayed terms."""
    from mxnet_tpu.ops.pallas import ssd_step as K

    rs = np.random.RandomState(T + plen)
    Bt, H, P, G, N = 2, 8, 16, 2, 128
    f = lambda *s: jnp.asarray(rs.normal(0, 1, s), jnp.float32)
    x, B, C = f(Bt, T, H, P), f(Bt, T, G, N), f(Bt, T, G, N)
    dt = jax.nn.softplus(f(Bt, T, H))
    A = -jnp.exp(0.5 * f(H))
    live = jnp.arange(T)[None, :] < jnp.asarray([[T], [plen]])
    y, S = ssm.ssd_scan(x, dt, A, B, C, live, chunk=chunk)
    Sx = Sk = jnp.zeros((Bt, H, P, N))
    for t in range(T):
        on = live[:, t].astype(jnp.int32)
        yx, Sx = ssm._step_xla(x[:, t], dt[:, t], A, B[:, t], C[:, t], Sx, on)
        yk, Sk = K.ssd_step(x[:, t], dt[:, t], A, B[:, t], C[:, t], Sk, on,
                            interpret=True)
        ok = np.asarray(on, bool)
        np.testing.assert_allclose(np.asarray(yk)[ok], np.asarray(yx)[ok],
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(y[:, t])[ok],
                                   np.asarray(yx)[ok], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(Sk), np.asarray(Sx), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(S), np.asarray(Sx), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("live", [[1, 0, 1], [0, 0, 0], [0, 1, 0]])
def test_a_free_slots_state_is_untouched_by_a_step(live):
    """Kernel (interpret mode) and XLA step: a slot that is not live keeps
    its state bit for bit and reads zeros; the live ones agree."""
    from mxnet_tpu.ops.pallas import ssd_step as K

    rs = np.random.RandomState(sum(live))
    S_, H, P, G, N = 3, 16, 8, 2, 128
    f = lambda *s: jnp.asarray(rs.normal(0, 1, s), jnp.float32)
    S = f(S_, H, P, N)
    args = (f(S_, H, P), jax.nn.softplus(f(S_, H)), -jnp.exp(f(H)),
            f(S_, G, N), f(S_, G, N))
    on = jnp.asarray(live, jnp.int32)
    yk, Sk = K.ssd_step(*args, S, on, interpret=True)
    yx, Sx = ssm.ssd_step(*args, S, on)
    for slot, is_on in enumerate(live):
        if is_on:
            np.testing.assert_allclose(np.asarray(Sk[slot]),
                                       np.asarray(Sx[slot]), atol=1e-5)
            np.testing.assert_allclose(np.asarray(yk[slot]),
                                       np.asarray(yx[slot]), atol=1e-5)
        else:
            for got in (Sk, Sx):
                assert np.asarray(got[slot]).tobytes() \
                    == np.asarray(S[slot]).tobytes()
            assert not np.asarray(yk[slot]).any()
            assert not np.asarray(yx[slot]).any()


def test_the_conv_state_shifts_as_the_whole_conv():
    """The conv a token at a time through its state is the conv of the
    whole sequence; a prefill's state is the inputs before its last live
    row; a row that is not live keeps the state."""
    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.normal(0, 1, (2, 10, 6)), jnp.float32)
    w = jnp.asarray(rs.normal(0, 1, (6, 4)), jnp.float32)
    b = jnp.asarray(rs.normal(0, 1, (6,)), jnp.float32)
    live = jnp.arange(10)[None] < jnp.asarray([[10], [7]])
    y, st = ssm.causal_conv(x, w, b, None, live)
    assert np.array_equal(np.asarray(st[0]), np.asarray(x[0, 7:10]))
    assert np.array_equal(np.asarray(st[1]), np.asarray(x[1, 4:7]))
    s = jnp.zeros((2, 3, 6))
    for t in range(10):
        yt, s = ssm.causal_conv(x[:, t:t + 1], w, b, s,
                                jnp.asarray([1, 1], jnp.int32))
        np.testing.assert_allclose(np.asarray(yt[:, 0]),
                                   np.asarray(y[:, t]), atol=1e-6)
    _, kept = ssm.causal_conv(x[:, :1], w, b, s, jnp.asarray([0, 1]))
    assert np.array_equal(np.asarray(kept[0]), np.asarray(s[0]))


# ------------------------------------------------------ the hybrid cache
def test_the_spec_names_a_record_per_cache_layer():
    model = NH.nemotron_h_nano()
    model.initialize()
    spec = model.decode_state_spec()
    assert spec["layers"] == 3 and spec["routed"] == (2, 9)
    assert [rec for rec, _sizes in spec["page"]] \
        == [SSMPage, SSMPage, PlainPage]
    assert spec["page"][0][1] == dict(heads=8, head_dim=16, state=128,
                                      channels=128 + 2 * 2 * 128, width=4)
    assert spec["page"][2][1] == dict(heads=2, head_dim=16)
    assert "int8_pages" not in spec and "multi_token" not in spec


def test_the_hybrid_cache_holds_snapshots_beside_rows():
    ssm = dict(heads=8, head_dim=16, state=16, channels=192, width=4)
    layout = [(SSMPage, ssm), (PlainPage, dict(heads=2, head_dim=16)),
              (SSMPage, ssm)]
    c = PagedKVCache(3, 4, 16, slots=3, max_capacity=256, dtype=np.float32,
                     page=layout)
    assert c.snapshots
    c.ensure_capacity(40)
    assert [type(p) for p in c.state] == [SSMPage, PlainPage, SSMPage]
    assert c.state[0].conv.shape == (3, 3, 192)
    assert c.state[0].S.shape == (3, 8, 16, 16)
    assert c.state[1].k.shape == (3, 2, 64, 16)
    snap = 3 * 192 * 4 + 8 * 16 * 16 * 4
    assert c.snapshot_bytes() == 2 * snap
    assert c.state_nbytes() == 3 * 2 * snap
    assert c.page_lengths(32) == [0, 32, 0]
    assert c.page_bytes(32) == 2 * snap + 2 * 2 * 32 * 16 * 4
    c.ensure_capacity(100)                 # only the rows grow
    assert c.state[1].k.shape == (3, 2, 128, 16)
    assert c.state[0].S.shape == (3, 8, 16, 16) and c.migrations == 1
    tag = __import__("mxnet_tpu.serve.kv_cache", fromlist=["step_tag"]) \
        .step_tag(c.state, [5, 90])
    assert tag == "state=%.1f kvread=0.667" % (1e-6 * 2 * 2 * snap)
    # a single record serves every layer, with the cache's own sizes
    one = PagedKVCache(2, 2, 16, slots=2, max_capacity=64)
    assert one.layout == [(PlainPage, dict(heads=2, head_dim=16))] * 2
    assert not one.snapshots


def test_a_snapshot_and_rows_go_to_the_store_and_back_bit_for_bit():
    rs = np.random.RandomState(11)
    layout = [(SSMPage, dict(heads=4, head_dim=8, state=16, channels=96,
                             width=4)),
              (PlainPage, dict(heads=2, head_dim=16))]
    c = PagedKVCache(2, 2, 16, slots=3, max_capacity=64, dtype=jnp.bfloat16,
                     page=layout)
    c.ensure_capacity(64)
    kept = [(jnp.asarray(rs.normal(0, 1, (1, 3, 96)), jnp.bfloat16),
             jnp.asarray(rs.normal(0, 1, (1, 4, 8, 16)), jnp.float32)),
            (jnp.asarray(rs.normal(0, 1, (1, 2, 32, 16)), jnp.bfloat16),
             jnp.asarray(rs.normal(0, 1, (1, 2, 32, 16)), jnp.bfloat16))]
    state = write_prompt(c.state, kept, 29, jnp.int32(1))
    ks, vs = read_prompt(state, jnp.int32(1), c.page_lengths(32))
    assert isinstance(ks, tuple) and ks[0].shape == (3, 96)
    assert vs[0].shape == (4, 8, 16) and ks[1].shape == (2, 32, 16)
    host = (tuple(np.asarray(a) for a in ks), tuple(np.asarray(a)
                                                    for a in vs))
    state = write_prompt(state, stored_kvs(*[tuple(map(jnp.asarray, h))
                                             for h in host]), 29,
                         jnp.int32(2))
    for a, b in ((state[0].conv, kept[0][0]), (state[0].S, kept[0][1])):
        assert np.asarray(a[2]).tobytes() == np.asarray(b[0]).tobytes()
        assert not np.asarray(a[0].astype(jnp.float32)).any()
    assert np.asarray(state[1].k[2, :, :32]).tobytes() \
        == np.asarray(kept[1][0][0]).tobytes()


def test_a_slot_taken_again_starts_from_zero_state():
    """``take_slot`` with ``fresh``: zero state and zero conv window,
    whatever the slot held; without it, what the slot held."""
    layout = [(SSMPage, dict(heads=4, head_dim=8, state=16, channels=96,
                             width=4))]
    c = PagedKVCache(1, 4, 8, slots=2, max_capacity=64, page=layout)
    c.ensure_capacity(16)
    page = SSMPage(c.state[0].conv + 1.0, c.state[0].S + 2.0)
    fresh = page.take_slot(jnp.int32(1), jnp.bool_(True))
    assert not np.asarray(fresh.conv).any() and not np.asarray(fresh.S).any()
    kept = page.take_slot(jnp.int32(1), jnp.bool_(False))
    assert (np.asarray(kept.S) == 2.0).all()
    assert (np.asarray(kept.conv) == 1.0).all()
    back = page.put_slot(jnp.int32(0), fresh)
    assert not np.asarray(back.S[0]).any() and (np.asarray(back.S[1])
                                                == 2.0).all()


def test_a_slot_taken_again_reads_none_of_the_stream_before():
    cfg, model, params = _seeded(seed=6)
    model.hybridize()
    rs = np.random.RandomState(6)
    a, b = rs.randint(0, 256, 50), rs.randint(0, 256, 11)
    with serve.GenerativeServer(model, slots=1, prefix_cache=False) as srv:
        srv.submit(a, max_new_tokens=20).result(timeout_s=120)
        second = srv.submit(b, max_new_tokens=10).result(timeout_s=120)
        stats = srv.stats()
    assert _gap(cfg, params, b, second) <= TOL
    # 2 expert layers x top-2 a token, prompts and decoded tokens
    assert stats["expert_picks_here"] + stats["expert_picks_elsewhere"] \
        == 2 * 2 * (50 + 19 + 11 + 9)


def test_a_repeated_prompt_is_a_hit_that_restores_snapshots_and_rows(
        served):
    cfg, _model, params, srv = served
    prompt = np.random.RandomState(7).randint(0, 256, 37)
    s0 = srv.stats()
    first = srv.submit(prompt, max_new_tokens=12).result(timeout_s=120)
    again = srv.submit(prompt, max_new_tokens=12).result(timeout_s=120)
    s1 = srv.stats()
    assert again == first
    assert s1["prefix_hits"] - s0["prefix_hits"] == 1
    ks, vs, plen, _last = srv.prefix.get(prompt)
    assert plen == 37 and len(ks) == 3
    assert ks[0].shape == (3, 192) and vs[0].shape == (8, 16, 16)
    assert ks[2].shape == (2, 64, 16)               # the attention's rows
    snap = srv.cache.snapshot_bytes()
    assert snap == 2 * (3 * 192 * 4 + 8 * 16 * 16 * 4)
    assert s1["state_snapshots_out"] - s0["state_snapshots_out"] == 1
    assert s1["state_snapshots_in"] - s0["state_snapshots_in"] == 1
    assert s1["state_snapshot_bytes_in"] - s0["state_snapshot_bytes_in"] \
        == snap
    assert s1["state_bytes"] == srv.cache.state_nbytes() == 4 * snap
    assert _gap(cfg, params, prompt, again) <= TOL


def test_spans_carry_state_and_kvread_and_the_readouts_bytes(served):
    _cfg, _model, _params, srv = served
    profiler.set_config(filename=os.devnull)
    n0 = profiler.num_records()      # the records before are other tests'
    profiler.set_state("run")
    try:
        srv.submit(np.arange(3, 30), max_new_tokens=5).result(timeout_s=120)
    finally:
        profiler.set_state("stop")
    records = json.loads(profiler.dumps())[n0:]
    steps = [r["name"] for r in records
             if r["name"].startswith("decode[step ")]
    assert steps and all(re.search(
        r"( xmax=[\d.]+ xhit=\d+)? state=[\d.]+ kvread=0\.\d+ ahead=[01]\]",
        s) for s in steps), steps
    assert sum(" xhit=" in s for s in steps) >= len(steps) - 1
    readout = [r for r in records if r["name"].startswith("decode[readout")]
    assert readout and readout[0]["args"]["state_mb"] > 0
    assert readout[0]["args"]["rows_mb"] > 0
    assert abs(readout[0]["args"]["state_mb"] + readout[0]["args"]["rows_mb"]
               - readout[0]["args"]["mb"]) < 2e-3


@pytest.mark.parametrize("kwargs,word", [
    (dict(quantize="int8"), "int8_pages"),
    (dict(draft=serve.NGramDraft()), "multi_token"),
    (dict(prefill_chunk=16), "multi_token")])
def test_what_the_hybrid_cannot_do_is_refused_by_name(kwargs, word):
    model = NH.nemotron_h_nano()
    model.initialize()
    with pytest.raises(ServeError, match=word):
        serve.GenerativeServer(model, slots=2, **kwargs)


def test_the_model_checks_its_pattern_share_and_lengths():
    with pytest.raises(ValueError, match="are not among"):
        NH.nemotron_h_nano(experts_held=4, first_expert=6)
    with pytest.raises(ValueError, match="pattern"):
        NH.nemotron_h_nano(pattern="M-E")
    model = NH.nemotron_h_nano()
    model.initialize()
    with pytest.raises(ValueError, match="max_length"):
        model(NDArray(jnp.zeros((1, 129), jnp.int32)))
    with pytest.raises(ValueError, match="one token"):
        _trace_call(model, model.decode_step, jnp.zeros((2, 2), jnp.int32),
                    [], jnp.zeros((2,), jnp.int32),
                    jnp.ones((2,), jnp.int32))
