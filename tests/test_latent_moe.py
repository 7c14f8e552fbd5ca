"""The DeepSeek-V3 lineage decoder (``models/latent_moe.py``: latent
attention, whose cache is one compressed row a position, a dense layer and
then routed experts with a scaled sum) on the served path, at a small size on
the CPU, against the benchmark's plain reference
(``benchmark/reference/ax_k1.py``, the EXPANDED form, which imports nothing
of the program): d 64, 4 heads of 16 + 8 (value 16) over a latent of 32 and a
query rank of 48, one dense layer (96) and two expert layers (8 experts of 32,
top-2, experts 2..5 held, one shared, scale 2.5), YaRN factor 4 over 32
original positions, seeded float32 weights.
"""
import importlib.util
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import _trace, profiler, serve
from mxnet_tpu.models import latent_moe as L
from mxnet_tpu.ndarray import NDArray
from mxnet_tpu.ops import attention as A
from mxnet_tpu.ops import moe as M
from mxnet_tpu.serve import ServeError
from mxnet_tpu.serve.kv_cache import (LatentPage, PagedKVCache, read_prompt,
                                      stored_kvs, write_prompt)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    spec = importlib.util.spec_from_file_location(
        "reference_ax_k1",
        os.path.join(ROOT, "benchmark", "reference", "ax_k1.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.ROWS, mod.HEADS, mod.FFN_ROWS = 8, 2, 16   # its blocks, at this size
    return mod


ref = _reference()

CFG = dict(vocab_size=256, units=64, num_layers=3, num_heads=4,
           q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
           qk_rope_head_dim=8, v_head_dim=16, dense_hidden=96,
           first_k_dense=1, expert_hidden=32, num_experts=8, experts_held=4,
           first_expert=2, experts_per_token=2, num_shared_experts=1,
           routed_scaling_factor=2.5, rope_theta=10000.0, rope_factor=4.0,
           original_max_length=32, beta_fast=32, beta_slow=1,
           mscale_all_dim=1.0, max_length=128, rms_norm_eps=1e-6)
PUBLISHED = dict(qk_nope_head_dim=128, qk_rope_head_dim=64,
                 rope_theta=10000.0, rope_factor=32.0,
                 original_max_length=4096, beta_fast=32, beta_slow=1,
                 mscale_all_dim=1.0)


def _seeded(seed=0, **over):
    """(sizes, model, the reference's parameters) with the same seeded
    float32 weights in both."""
    cfg = dict(CFG, **over)
    model = L.LatentMoEModel(**cfg)
    model.initialize()
    rs = np.random.RandomState(seed)
    params = {}
    for p in model.collect_params().values():
        name = re.sub(r"^latentmoemodel\d+_", "", p.name)
        w = rs.normal(0, 0.08, p.shape).astype(np.float32)
        if name.endswith("gamma"):
            w += 1
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


# ------------------------------------------------------ (e) YaRN's numbers
def test_yarn_at_the_published_keys():
    dim, theta = 64, 10000.0
    low, high = L.yarn_correction_range(dim, theta, 4096, 32, 1)
    assert (low, high) == (10, 23)
    inv = L.yarn_inv_freq(dim, theta, 32.0, 4096, 32, 1)
    f = theta ** (-np.arange(0, dim, 2) / dim)
    assert inv.shape == (32,) and inv[0] == 1.0
    np.testing.assert_allclose(inv[:11], f[:11], rtol=1e-12)   # unstretched
    np.testing.assert_allclose(inv[23:], f[23:] / 32, rtol=1e-12)
    assert inv[31] == f[31] / 32
    assert f[15] / 32 < inv[15] < f[15]                        # blended
    assert abs(L.attention_scale(192, 32.0, 1.0) - 0.130861) < 5e-7
    assert abs(L.attention_scale(192, 1.0, 1.0) - 1 / math.sqrt(192)) < 1e-12
    # the reference's own arithmetic gives the same numbers
    r_inv, r_low, r_high, r_scale = ref.yarn(PUBLISHED)
    assert (r_low, r_high) == (10, 23) and abs(r_scale - 0.130861) < 5e-7
    np.testing.assert_allclose(r_inv, inv, rtol=1e-12)


def test_the_tiny_yarn_blend_is_not_the_identity():
    inv = L.yarn_inv_freq(8, 10000.0, 4.0, 32, 32, 1)
    f = 10000.0 ** (-np.arange(0, 8, 2) / 8)
    assert inv[0] == f[0] and np.allclose(inv[1:], f[1:] / 4)


def test_rotary_takes_given_frequencies_and_keeps_its_default():
    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.normal(0, 1, (2, 3, 6, 8)).astype(np.float32))
    pos = jnp.asarray([[0, 1, 2, 3, 40, 100], [7, 7, 0, 5, 6, 90]])
    plain = A.rotary(x, pos, theta=10000.0)
    f = tuple(float(v) for v in 10000.0 ** (-np.arange(0, 8, 2) / 8))
    np.testing.assert_allclose(np.asarray(A.rotary(x, pos, inv_freq=f)),
                               np.asarray(plain), rtol=1e-5, atol=1e-5)
    inv = L.yarn_inv_freq(8, 10000.0, 4.0, 32, 32, 1)
    got = np.asarray(A.rotary(x, pos, inv_freq=tuple(inv)))
    want = np.stack([np.asarray(ref.rotate(x[b].transpose(1, 0, 2),
                                           pos[b], inv)).transpose(1, 0, 2)
                     for b in range(2)])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert np.abs(got - np.asarray(plain)).max() > 0.1


# --------------------------------------------------- the router's scale
def test_route_scales_the_normalised_weights_and_defaults_to_one():
    rs = np.random.RandomState(2)
    h = jnp.asarray(rs.normal(0, 1, (9, 64)).astype(np.float32))
    w = jnp.asarray(rs.normal(0, 0.3, (8, 64)).astype(np.float32))
    one, e1 = M.route(h, w, 2)
    scaled, e2 = M.route(h, w, 2, 2.5)
    assert np.array_equal(np.asarray(e1), np.asarray(e2))
    np.testing.assert_allclose(np.asarray(one).sum(-1), 1.0, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(scaled), 2.5 * np.asarray(one),
                               rtol=1e-6)
    r_w, r_e = ref.route(CFG, h, w)
    assert np.array_equal(np.asarray(r_e), np.asarray(e2))
    np.testing.assert_allclose(np.asarray(r_w), np.asarray(scaled),
                               rtol=1e-6)


# ------------------------------------------------ (d) the share test
def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts of ALL shares (4 chips of 2 experts each) plus the
    shared expert counted once are the reference's uncut expert layer."""
    rs = np.random.RandomState(3)
    d, f, E = 64, 32, 8
    h = jnp.asarray(rs.normal(0, 1, (21, d)).astype(np.float32))
    lp = {"router_weight": rs.normal(0, 0.3, (E, d)),
          "experts_gate_weight": rs.normal(0, 0.1, (E, f, d)),
          "experts_up_weight": rs.normal(0, 0.1, (E, f, d)),
          "experts_down_weight": rs.normal(0, 0.1, (E, f, d)),
          "ffn_gate_weight": rs.normal(0, 0.1, (f, d)),
          "ffn_up_weight": rs.normal(0, 0.1, (f, d)),
          "ffn_down_weight": rs.normal(0, 0.1, (d, f))}
    lp = {k: jnp.asarray(v.astype(np.float32)) for k, v in lp.items()}
    total, picks = 0.0, 0
    for first in range(0, E, 2):
        part, load = M.expert_ffn(
            h, lp["router_weight"],
            *(lp["experts_%s_weight" % n][first:first + 2]
              for n in ("gate", "up", "down")),
            first_expert=first, top_k=2, routed_scale=2.5)
        total = total + part
        picks += int(load[:2].sum())
        assert int(load.sum()) == 21 * 2
    assert picks == 21 * 2                 # every pick computed exactly once
    total = total + M.gated_ffn(h, lp["ffn_gate_weight"],
                                lp["ffn_up_weight"], lp["ffn_down_weight"])
    uncut = dict(CFG, experts_held=E, first_expert=0)
    want = ref.moe(uncut, h, lp, "float32")
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-5)
    # and one share alone is not the layer
    assert float(jnp.abs(part - want).max()) > 0.05


# ------------------------------------------- (a) the model, the reference
def test_full_forward_is_the_references_logits():
    cfg, model, params = _seeded(seed=5)
    model.hybridize()
    toks = np.random.RandomState(5).randint(0, cfg["vocab_size"], (1, 96))
    got = np.asarray(model(NDArray(jnp.asarray(toks, jnp.int32)))._data)[0]
    want = np.asarray(ref.logits(cfg, params, toks[0], 1, 95))
    assert got.shape == (96, 256) and np.abs(want).max() > 0.3
    np.testing.assert_allclose(got[:95], want, atol=3e-5)


def test_the_whole_model_is_the_share_with_every_expert():
    cfg, model, params = _seeded(seed=6, experts_held=8, first_expert=0)
    model.hybridize()
    toks = np.random.RandomState(6).randint(0, cfg["vocab_size"], (1, 32))
    got = np.asarray(model(NDArray(jnp.asarray(toks, jnp.int32)))._data)[0]
    want = np.asarray(ref.logits(cfg, params, toks[0], 1, 31))
    np.testing.assert_allclose(got[:31], want, atol=3e-5)


# --------------------------- (b) prefill, then decode through the latent page
@pytest.mark.parametrize("prompt_len,new", [
    (5, 60),      # nearly all decoded, past the 32 original positions
    (40, 40),     # a bucket of 64: 24 pad rows behind the prompt
    (64, 30),     # a bucket filled to its last row
    (1, 6)])      # a page that starts from one token
def test_prefill_then_decode_is_the_references_forward(served, prompt_len,
                                                       new):
    """Prefill (expanded), then decode through the latent page (absorbed),
    against the reference's one full forward (expanded) over prompt +
    served tokens: logits, not tokens: every served token lies within
    rounding of the reference's best (float32 both), also at positions past
    ``original_max_length``, where YaRN's stretched pairs differ."""
    cfg, _model, params, srv = served
    rs = np.random.RandomState(prompt_len)
    prompt = rs.randint(0, cfg["vocab_size"], prompt_len)
    toks = srv.submit(prompt, max_new_tokens=new).result(timeout_s=120)
    lg = np.asarray(ref.served_logits(cfg, params, prompt, toks, pad_to=16))
    gap = lg.max(-1) - lg[np.arange(new), toks]
    assert gap.max() <= 1e-4, gap


# ------------------------------------------ (c) absorbed = expanded
def test_decode_step_logits_are_the_references(served):
    """The ABSORBED step's own logits, slot by slot at different positions,
    one slot free, against the reference's EXPANDED row for each live slot:
    float32, to rounding. The free slot's page keeps its bits and routes
    nowhere."""
    cfg, model, params, _srv = served
    cache = PagedKVCache(3, 1, (32, 8), slots=3, max_capacity=128,
                         page=LatentPage)
    cache.ensure_capacity(64)
    rs = np.random.RandomState(9)
    seqs = [rs.randint(0, 256, n) for n in (9, 4, 47)]
    state = cache.state
    for slot, seq in enumerate(seqs):
        tokens = jnp.asarray(seq[None, :-1], jnp.int32)
        _lg, kept, load = _trace_call(model, model.forward_collect_kv, tokens,
                                      jnp.int32(len(seq) - 1))
        assert load.shape == (2, 5)
        state = write_prompt(state, kept, len(seq) - 1, jnp.int32(slot))
    before = [np.asarray(page.c_kv[1]).copy() for page in state]
    logits, state, load = _trace_call(
        model, model.decode_step,
        jnp.asarray([[s[-1]] for s in seqs], jnp.int32), state,
        jnp.asarray([len(s) - 1 for s in seqs], jnp.int32),
        jnp.asarray([1, 0, 1], jnp.int32))
    assert all(type(p) is LatentPage for p in state)
    assert np.asarray(load).sum(-1).tolist() == [4, 4]    # 2 live x top-2
    for slot in (0, 2):
        seq = np.concatenate([seqs[slot], [0]])
        want = np.asarray(ref.served_logits(cfg, params, seq[:-1], seq[-1:],
                                            pad_to=16))
        np.testing.assert_allclose(np.asarray(logits)[slot, 0], want[0],
                                   atol=3e-5)
    for page, old in zip(state, before):
        assert np.asarray(page.c_kv[1]).tobytes() == old.tobytes()


def test_latent_attention_is_the_expanded_attention():
    """The op alone, in float64 ``numpy``: scores through the absorbed
    queries and a mix of latent rows taken through ``W_uv`` are the per-head
    K and V attention, for T = 1 and T = 3 (the dense lowering's mask
    ``position < length + t``), with a row of length 0 finite."""
    rs = np.random.RandomState(4)
    B, H, C, R, P, D = 3, 4, 24, 32, 8, 16
    c = rs.normal(0, 1, (B, 1, C, R))
    pe = rs.normal(0, 1, (B, 1, C, P))
    w_uk, w_uv = rs.normal(0, 0.3, (2, H, D, R))
    lengths = np.array([0, 7, 20])
    for T in (1, 3):
        q_nope = rs.normal(0, 1, (B, H, T, D))
        q_pe = rs.normal(0, 1, (B, H, T, P))
        q_lat = np.einsum("bhtd,hdr->bhtr", q_nope, w_uk)
        ctx = np.asarray(A.latent_attention(
            *(jnp.asarray(a, jnp.float32) for a in (q_lat, q_pe, c, pe)),
            jnp.asarray(lengths), scale=0.2))
        got = np.einsum("bhtr,hdr->bhtd", ctx, w_uv)
        assert np.isfinite(got).all()
        k = np.einsum("bcr,hdr->bhcd", c[:, 0], w_uk)
        v = np.einsum("bcr,hdr->bhcd", c[:, 0], w_uv)
        s = 0.2 * (np.einsum("bhtd,bhcd->bhtc", q_nope, k)
                   + np.einsum("bhtp,bcp->bhtc", q_pe, pe[:, 0]))
        seen = np.arange(C)[None, None, None, :] \
            < (lengths[:, None, None, None] + np.arange(T)[None, None, :, None])
        s = np.where(seen, s, -np.inf)
        for b in (1, 2):
            p = np.exp(s[b] - s[b].max(-1, keepdims=True))
            want = np.einsum("htc,hcd->htd", p / p.sum(-1, keepdims=True),
                             v[b])
            np.testing.assert_allclose(got[b], want, atol=2e-5)


def test_attention_takes_values_of_their_own_width():
    """The prefill's expanded attention: keys of nope + rope, values of
    ``v_head_dim``, through ``scaled_dot_attention``'s dense path."""
    rs = np.random.RandomState(8)
    q, k = rs.normal(0, 1, (2, 1, 3, 12, 24)).astype(np.float32)
    v = rs.normal(0, 1, (1, 3, 12, 16)).astype(np.float32)
    got = np.asarray(A.scaled_dot_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True,
        scale=0.13))
    s = np.where(np.tril(np.ones((12, 12), bool)),
                 0.13 * np.einsum("bhqd,bhkd->bhqk", q, k), -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bhqk,bhkd->bhqd", p / p.sum(-1, keepdims=True), v)
    assert got.shape == (1, 3, 12, 16)
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("plen", [1, 37, 63, 64])
def test_rows_past_the_prompt_attend_to_nothing(monkeypatch, plen):
    """The prefill with the prompt's length handed to its attention against
    the same prefill without it (the length went no further than the
    experts before): the last row's logits, the experts' load and every
    kept row before the length are the same bits; the kept rows at and past
    it stay finite (the decode reads their blocks and masks them)."""
    cfg, model, _params = _seeded(seed=12)
    tokens = jnp.asarray(np.random.RandomState(12).randint(0, 256, (1, 64)),
                         jnp.int32)
    at = jnp.int32(plen)
    logits, kept, load = _trace_call(model, model.forward_collect_kv, tokens,
                                     at)
    whole = L._LatentAttention.forward_kv
    monkeypatch.setattr(L._LatentAttention, "forward_kv",
                        lambda self, F, h, plen=None: whole(self, F, h))
    logits0, kept0, load0 = _trace_call(model, model.forward_collect_kv,
                                        tokens, at)
    assert logits.shape == (1, 1, 256)
    assert np.asarray(logits).tobytes() == np.asarray(logits0).tobytes()
    assert np.array_equal(np.asarray(load), np.asarray(load0))
    for rows, rows0 in zip(kept, kept0):
        for a, a0 in zip(rows, rows0):
            a, a0 = np.asarray(a), np.asarray(a0)
            assert a[:, :, :plen].tobytes() == a0[:, :, :plen].tobytes()
            assert np.isfinite(a).all()
    # the length reached the attention: from the second layer on, the rows
    # past the prompt are made from zeros where they were made from scores
    late = [np.asarray(a)[:, :, plen:] for kv in kept[1:] for a in kv]
    late0 = [np.asarray(a)[:, :, plen:] for kv in kept0[1:] for a in kv]
    assert (plen == 64) == all(np.array_equal(a, a0)
                               for a, a0 in zip(late, late0))


def test_the_server_counts_a_prefills_rows_and_its_bucket(served):
    """``prefill_rows`` / ``prefill_bucket_rows``: a prompt's tokens and
    the pow2 bucket it was padded to, once a prefill; an inject from the
    prefix store adds to neither."""
    _cfg, _model, _params, srv = served
    prompt = np.random.RandomState(21).randint(0, 256, 45)
    s0 = srv.stats()
    srv.submit(prompt, max_new_tokens=3).result(timeout_s=120)
    s1 = srv.stats()
    srv.submit(prompt, max_new_tokens=3).result(timeout_s=120)
    s2 = srv.stats()
    assert s1["prefills"] - s0["prefills"] == 1
    assert s1["prefill_rows"] - s0["prefill_rows"] == 45
    assert s1["prefill_bucket_rows"] - s0["prefill_bucket_rows"] == 64
    assert s2["prefix_hits"] - s1["prefix_hits"] == 1
    assert [s2[k] - s1[k] for k in ("prefills", "prefill_rows",
                                    "prefill_bucket_rows")] == [0, 0, 0]
    assert serve.stats()["servers"][srv.name]["prefill_rows"] \
        == s2["prefill_rows"]


# ------------------------------------------------------ the latent page
def test_the_latent_page_is_one_row_a_position():
    c = PagedKVCache(3, 1, (32, 8), slots=4, max_capacity=128,
                     dtype=np.float32, page=LatentPage)
    assert c.ensure_capacity(16) and c.capacity == 16
    row = (32 + 8) * 4
    assert c.nbytes() == 3 * 4 * 16 * row == c.nbytes_unquantized()
    assert c.nbytes_unquantized(itemsize=2) == c.nbytes() // 2
    assert [type(p) for p in c.state] == [LatentPage] * 3
    assert c.state[0].c_kv.shape == (4, 1, 16, 32)
    assert c.state[0].k_pe.shape == (4, 1, 16, 8)
    assert c.ensure_capacity(100) and c.capacity == 128 and c.migrations == 1
    assert c.nbytes() == 3 * 4 * 128 * row
    assert c.page_lengths(64) == [64, 64, 64]
    assert c.page_bytes(64) == 3 * 64 * row and not c.snapshots
    # 128-position blocks: 3 streams hold one block a layer each of 4 slots'
    assert LatentPage.step_tag(c.state, [5, 90, 128]) == "kvread=0.750"


def test_a_page_goes_out_to_the_store_and_comes_back_bit_for_bit():
    """(f) ``read_prompt`` of a slot's rows, through the store's host copy
    and ``stored_kvs``, written into another slot: the same bits."""
    rs = np.random.RandomState(11)
    c = PagedKVCache(2, 1, (32, 8), slots=3, max_capacity=64,
                     dtype=jnp.bfloat16, page=LatentPage)
    c.ensure_capacity(64)
    kept = [(jnp.asarray(rs.normal(0, 1, (1, 1, 32, 32)), jnp.bfloat16),
             jnp.asarray(rs.normal(0, 1, (1, 1, 32, 8)), jnp.bfloat16))
            for _ in range(2)]
    state = write_prompt(c.state, kept, 29, jnp.int32(1))
    ks, vs = read_prompt(state, jnp.int32(1), [32, 32])
    assert ks.shape == (2, 1, 32, 32) and vs.shape == (2, 1, 32, 8)
    assert ks.dtype == jnp.bfloat16
    host = (np.asarray(ks), np.asarray(vs))           # the store's copy
    state = write_prompt(state, stored_kvs(*map(jnp.asarray, host)), 29,
                         jnp.int32(2))
    for page, (c_kv, k_pe) in zip(state, kept):
        for buf, new in ((page.c_kv, c_kv), (page.k_pe, k_pe)):
            assert np.asarray(buf[2, :, :32]).tobytes() \
                == np.asarray(buf[1, :, :32]).tobytes() \
                == np.asarray(new[0]).tobytes()
            assert float(jnp.abs(buf[0].astype(jnp.float32)).max()) == 0
    one = state[0].take_slot(jnp.int32(1), jnp.bool_(True))
    assert one.c_kv.shape == (1, 1, 64, 32) and one.k_pe.shape == (1, 1, 64, 8)
    back = state[0].put_slot(jnp.int32(0), one)
    assert np.asarray(back.c_kv[0]).tobytes() \
        == np.asarray(state[0].c_kv[1]).tobytes()


def test_a_repeated_prompt_is_a_hit_that_injects_the_rows(served):
    cfg, _model, params, srv = served
    prompt = np.random.RandomState(7).randint(0, cfg["vocab_size"], 37)
    s0 = srv.stats()
    first = srv.submit(prompt, max_new_tokens=12).result(timeout_s=120)
    again = srv.submit(prompt, max_new_tokens=12).result(timeout_s=120)
    s1 = srv.stats()
    assert again == first
    assert s1["prefix_hits"] - s0["prefix_hits"] == 1
    c_stack, pe_stack, plen, _last = srv.prefix.get(prompt)
    assert plen == 37
    assert c_stack.shape == (3, 1, 64, 32) and pe_stack.shape == (3, 1, 64, 8)
    assert s1["kv_cache_bytes"] == srv.cache.nbytes()
    assert s1["state_bytes"] == 0 and s1["state_snapshots_out"] == 0
    lg = np.asarray(ref.served_logits(cfg, params, prompt, again, pad_to=16))
    assert (lg.max(-1) - lg[np.arange(12), again]).max() <= 1e-4


def test_a_slot_taken_again_reads_none_of_the_stream_before():
    cfg, model, params = _seeded(seed=6)
    model.hybridize()
    rs = np.random.RandomState(6)
    a, b = rs.randint(0, 256, 50), rs.randint(0, 256, 11)
    with serve.GenerativeServer(model, slots=1, prefix_cache=False) as srv:
        srv.submit(a, max_new_tokens=20).result(timeout_s=120)
        second = srv.submit(b, max_new_tokens=10).result(timeout_s=120)
        loads = srv.stats()
    lg = np.asarray(ref.served_logits(cfg, params, b, second, pad_to=16))
    assert (lg.max(-1) - lg[np.arange(10), second]).max() <= 1e-4
    # the expert counters of a routing model: 2 expert layers x top-2 a token
    assert loads["expert_picks_here"] + loads["expert_picks_elsewhere"] \
        == 2 * 2 * (50 + 19 + 11 + 9)


def test_step_spans_carry_kvread_and_the_experts_fields(served):
    _cfg, _model, _params, srv = served
    profiler.set_config(filename=os.devnull)
    n0 = profiler.num_records()      # the records before are other tests'
    profiler.set_state("run")
    try:
        srv.submit([1, 2, 3], max_new_tokens=5).result(timeout_s=120)
    finally:
        profiler.set_state("stop")
    steps = [r["name"] for r in json.loads(profiler.dumps())[n0:]
             if r["name"].startswith("decode[step ")]
    # the experts' fields are of the step before: the first span has none
    assert steps and all(re.search(
        r"( xmax=[\d.]+ xhit=\d+)? kvread=0\.\d+ ahead=[01]\]", s)
        and "state=" not in s for s in steps), steps
    assert sum(" xmax=" in s for s in steps) >= len(steps) - 1


@pytest.mark.parametrize("kwargs,word", [
    (dict(quantize="int8"), "int8_pages"),
    (dict(draft=serve.NGramDraft()), "multi_token"),
    (dict(prefill_chunk=16), "multi_token")])
def test_what_a_latent_page_cannot_do_is_refused_by_name(kwargs, word):
    model = L.latent_moe_nano()
    model.initialize()
    with pytest.raises(ServeError, match=word):
        serve.GenerativeServer(model, slots=2, **kwargs)


def test_the_model_checks_its_share_and_its_lengths():
    with pytest.raises(ValueError, match="are not among"):
        L.latent_moe_nano(experts_held=4, first_expert=6)
    with pytest.raises(ValueError, match="first_k_dense"):
        L.latent_moe_nano(first_k_dense=4)
    model = L.latent_moe_nano()
    model.initialize()
    with pytest.raises(ValueError, match="max_length"):
        model(NDArray(jnp.zeros((1, 129), jnp.int32)))
    spec = model.decode_state_spec()
    assert spec["page"] is LatentPage and spec["head_dim"] == (32, 8)
    assert spec["kv_heads"] == 1 and spec["routed"] == (2, 9)
    assert "int8_pages" not in spec and "multi_token" not in spec
