"""The Cohere2-MoE share (``models/cohere_moe.py``) on the served path, at a
small size on the CPU, against the benchmark's plain reference
(``benchmark/reference/command_a_plus.py``, which imports nothing of the
program): d 64, 8 query / 2 K/V heads of width 16, 8 experts top-2 with 2
shared, window 16, 4 layers (3 window layers to 1 full), seeded weights.
"""
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import nd, serve
from mxnet_tpu.models.cohere_moe import CohereMoEModel, cohere_moe_nano
from mxnet_tpu.ndarray import NDArray
from mxnet_tpu.ops import attention as A
from mxnet_tpu.ops import moe as M

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    spec = importlib.util.spec_from_file_location(
        "reference_command_a_plus",
        os.path.join(ROOT, "benchmark", "reference", "command_a_plus.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.ROWS = 8          # its query-row block, at the tiny size
    return mod


ref = _reference()

CFG = dict(vocab_size=256, units=64, num_layers=4, num_heads=8,
           num_kv_heads=2, head_dim=16, expert_hidden=32, num_experts=8,
           experts_held=8, first_expert=0, experts_per_token=2,
           num_shared_experts=2,
           layer_types=["sliding_attention"] * 3 + ["full_attention"],
           sliding_window=16, rope_theta=50000.0, max_length=128,
           layer_norm_eps=1e-5)


def _seeded(seed=0, **over):
    """(sizes, model, the reference's parameters) with the same seeded
    float32 weights in both."""
    cfg = dict(CFG, **over)
    model = CohereMoEModel(**cfg)
    model.initialize()
    rs = np.random.RandomState(seed)
    params = {}
    for p in model.collect_params().values():
        name = re.sub(r"^coheremoemodel\d+_", "", p.name)
        w = rs.normal(0, 0.05, p.shape).astype(np.float32)
        if name.endswith("gamma"):
            w += 1
        p.set_data(NDArray(jnp.asarray(w)))
        params[name] = jnp.asarray(w)
    assert sorted((n, tuple(s)) for n, s in ref.param_specs(cfg)) \
        == sorted((n, tuple(a.shape)) for n, a in params.items())
    return cfg, model, params


# ------------------------------------------------ (a) the served path
@pytest.fixture(scope="module")
def served():
    """One share (experts 2-5 of 8) behind a 4-slot server."""
    cfg, model, params = _seeded(experts_held=4, first_expert=2)
    model.hybridize()
    srv = serve.GenerativeServer(model, slots=4)
    srv.start()
    yield cfg, params, srv
    srv.stop()


@pytest.mark.parametrize("prompt_len,new", [
    (5, 30),      # decode runs across the ring's wrap (window 16)
    (40, 20),     # the prompt is longer than the window: ring by gather
    (20, 8),      # prompt past the window, in a bucket of 32 > ring
    (12, 3)])     # nothing wraps
def test_served_tokens_are_the_references_best(served, prompt_len, new):
    """Prefill, then decode through the rings and the full page, against
    the reference's one full forward: every served token is the one the
    reference's logits put first (float32 both: no near-tie decides)."""
    cfg, params, srv = served
    rs = np.random.RandomState(prompt_len)
    prompt = rs.randint(0, cfg["vocab_size"], prompt_len)
    toks = srv.submit(prompt, max_new_tokens=new).result(timeout_s=120)
    lg = np.asarray(ref.served_logits(cfg, params, prompt, toks, pad_to=8))
    gap = lg.max(-1) - lg[np.arange(new), toks]
    assert gap.max() <= 1e-4, gap


def test_prefix_hit_replays_rings_and_pages(served):
    """A repeated prompt (longer than the window) is injected from the
    prefix store, rings in ring order, and decodes to the same tokens."""
    cfg, params, srv = served
    prompt = np.random.RandomState(7).randint(0, cfg["vocab_size"], 37)
    hits = srv.prefix.hits
    a = srv.submit(prompt, max_new_tokens=12).result(timeout_s=120)
    b = srv.submit(prompt, max_new_tokens=12).result(timeout_s=120)
    assert a == b and srv.prefix.hits == hits + 1
    k_stack = srv.prefix.get(prompt)[0]
    assert [k.shape[1] for k in k_stack] == [16, 16, 16, 64]


def test_the_server_counts_the_experts_load(served):
    cfg, params, srv = served
    srv.submit(np.arange(9), max_new_tokens=4).result(timeout_s=120)
    snap = srv.stats()
    here, away = snap["expert_picks_here"], snap["expert_picks_elsewhere"]
    assert here > 0 and away > 0
    assert np.asarray(snap["expert_load"]).shape == (4, 4)
    assert np.asarray(snap["expert_load"]).sum() == here
    assert snap["expert_load_max_over_mean"] >= 1.0
    # the step spans' tag is worked out only while the profiler runs
    assert srv.metrics.expert_tag() is None
    srv.metrics.record_expert_load(np.array([[3, 0, 1, 0, 9]] * 4), tag=True)
    assert srv.metrics.expert_tag() == "xmax=3.00 xhit=8"
    # ... and stands before the share of the pool the step has to read
    assert srv._step_tag(np.zeros(srv.slots, np.int32)) \
        == "xmax=3.00 xhit=8 kvread=0.000"


@pytest.mark.parametrize("option", [
    {"quantize": "int8"}, {"draft": serve.NGramDraft()},
    {"prefill_chunk": 16}])
def test_unsupported_options_raise_by_name(option):
    model = cohere_moe_nano()
    model.initialize()
    with pytest.raises(serve.ServeError, match=next(iter(option))):
        serve.GenerativeServer(model, slots=2, **option)


# ------------------------------------------------ (b) the shares add up
def test_the_shares_add_up_to_the_uncut_layer():
    """Four shares of two experts each: their local parts, with the shared
    experts (which every chip computes alike) counted once, are the uncut
    reference's expert layer."""
    cfg, whole, params = _seeded(seed=3, num_layers=1,
                                 layer_types=["full_attention"])
    lp = {k[len("layer0_"):]: v for k, v in params.items()
          if k.startswith("layer0_")}
    h = jnp.asarray(np.random.RandomState(5).normal(0, 1, (1, 50, 64)),
                    jnp.float32)
    want = np.asarray(ref.moe(cfg, h[0], lp, "float32"))
    live = nd.array(np.ones(50), dtype="int32")
    total, loads = 0.0, []
    for first in (0, 2, 4, 6):
        part = CohereMoEModel(**dict(cfg, experts_held=2,
                                     first_expert=first))
        part.initialize()
        held = slice(first, first + 2)
        for p, q in zip(part.collect_params().values(),
                        whole.collect_params().values()):
            w = q.data()._data
            p.set_data(NDArray(w[held] if "experts_" in p.name else w))
        routed, shared, load = part.blocks[0]._moe(nd, NDArray(h), live)
        total = total + routed.asnumpy()[0]
        loads.append(load.asnumpy())
    total = total + shared.asnumpy()[0] * part.blocks[0]._shared_scale
    np.testing.assert_allclose(total, want, atol=2e-6)
    # every pick is counted once as local, by the share that holds it
    assert sum(int(l[:-1].sum()) for l in loads) == 50 * 2
    assert all(int(l.sum()) == 50 * 2 for l in loads)


# ------------------------------------------------ (c) the new attention ops
def test_rotary_is_the_interleaved_rotation():
    x = jnp.asarray(np.random.RandomState(0).normal(size=(2, 3, 5, 16)),
                    jnp.float32)
    pos = jnp.asarray([[3, 4, 5, 6, 7], [40, 41, 42, 43, 44]])
    got = A.rotary(x, pos, theta=50000.0)
    inv = 50000.0 ** (-np.arange(0, 16, 2) / 16)
    ang = np.asarray(pos)[:, None, :, None] * inv          # (2, 1, 5, 8)
    x1, x2 = np.asarray(x)[..., 0::2], np.asarray(x)[..., 1::2]
    want = np.stack([x1 * np.cos(ang) - x2 * np.sin(ang),
                     x1 * np.sin(ang) + x2 * np.cos(ang)], -1)
    np.testing.assert_allclose(got, want.reshape(x.shape), atol=1e-5)
    np.testing.assert_allclose(A.rotary(x[:1], pos[0], theta=50000.0),
                               got[:1], atol=1e-6)          # (T,) positions


@pytest.mark.parametrize("window", [None, 4])
def test_grouped_heads_and_window_mask(window):
    rs = np.random.RandomState(1)
    q = jnp.asarray(rs.normal(size=(2, 8, 10, 16)), jnp.float32)
    k, v = (jnp.asarray(rs.normal(size=(2, 2, 10, 16)), jnp.float32)
            for _ in range(2))
    got = A.scaled_dot_attention(q, k, v, causal=True, window=window)
    # query head h reads K/V head h // 4; key j visible iff 0 <= i-j < window
    gap = np.arange(10)[:, None] - np.arange(10)[None, :]
    see = (gap >= 0) if window is None else (gap >= 0) & (gap < window)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, 4, axis=1)) / 4.0
    p = jax.nn.softmax(jnp.where(see, s, -1e30), axis=-1)
    want = jnp.einsum("bhqk,bhkd->bhqd", p, jnp.repeat(v, 4, axis=1))
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_flash_kernel_takes_grouped_heads_and_the_window():
    """The kernel itself (interpret mode), blocks smaller than the window
    and than the sequence so that whole blocks fall outside."""
    from mxnet_tpu.ops.pallas.flash_attention import flash_attention

    rs = np.random.RandomState(2)
    q = jnp.asarray(rs.normal(size=(1, 4, 64, 16)), jnp.float32)
    k, v = (jnp.asarray(rs.normal(size=(1, 2, 64, 16)), jnp.float32)
            for _ in range(2))
    for window in (None, 24):
        got = flash_attention(q, k, v, causal=True, window=window,
                              block_q=8, block_k=8, interpret=True)
        want = A._grouped_attention(q, k, v, None, True, None, window)
        np.testing.assert_allclose(got, want, atol=2e-5)


# which of the five slots hold a stream (PR 34): a length or a 0/1 flag, 0
# for a free slot. The slots stand at 0, 17, 63, 31 (17's tile of 16 rows in
# bf16) and 70, which clamps
_ROW_WRITE_LIVE = {
    "not_told": None,
    "no_slot_live": [0, 0, 0, 0, 0],
    "one_slot_live": [0, 0, 64, 0, 0],
    "some_slots_live_not_side_by_side": [1, 0, 1, 0, 1],
    "all_slots_live": [1, 18, 64, 32, 1],
    "two_live_slots_in_one_tile_index": [0, 18, 0, 32, 0],
    "live_at_a_clamped_and_a_negative_position": [0, 0, 0, 1, 1],
}


@pytest.mark.parametrize("live", list(_ROW_WRITE_LIVE))
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_row_write_at_head_width_128_matches_the_scatter(dtype, live):
    """``kv_cache_write``'s row path (D a whole lane tile), interpret mode:
    bit-identical to vmap(dynamic_update_slice) in the slots it is told are
    live (all, where it is not told), no bit of the others changed."""
    from mxnet_tpu.ops.pallas import kv_write

    rs = np.random.RandomState(3)
    cache = jnp.asarray(rs.normal(size=(5, 2, 64, 128)), dtype)
    upd = jnp.asarray(rs.normal(size=(5, 2, 1, 128)), dtype)
    idx = [0, 17, 63, 31, 70]                               # one clamps
    if live == "live_at_a_clamped_and_a_negative_position":
        idx[3] = -40
    idx = jnp.asarray(idx, jnp.int32)
    live = _ROW_WRITE_LIVE[live]
    assert kv_write.tiles(cache.shape, upd.shape, cache.dtype)
    told = () if live is None else (jnp.asarray(live, jnp.int32),)
    got = kv_write.kv_cache_write(cache, upd, idx, *told, interpret=True)
    zero = jnp.int32(0)
    want = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
        c, u, (zero, i, zero)))(cache, upd, idx)
    if live is not None:
        want = jnp.where((jnp.asarray(live) != 0).reshape(-1, 1, 1, 1), want,
                         cache)
    assert (np.asarray(got, np.float32) == np.asarray(want, np.float32)).all()
    if live is not None and all(live):
        untold = kv_write.kv_cache_write(cache, upd, idx, interpret=True)
        assert (np.asarray(got, np.float32)
                == np.asarray(untold, np.float32)).all()


# ------------------------------------------------ (d) no token dropped
def _dense_part(h, weight, expert, wg, wu, wd, first):
    """sum over the held experts of w_e FFN_e(h), every expert over every
    token."""
    out = 0.0
    for e in range(wg.shape[0]):
        w_e = jnp.sum(jnp.where(expert == first + e, weight, 0.0), -1)
        a = jax.nn.silu(h @ wg[e].T) * (h @ wu[e].T)
        out = out + w_e[:, None] * (a @ wd[e])
    return out


@pytest.mark.parametrize("tokens", [40, 300])   # decode tiles, prefill tiles
def test_no_token_is_dropped_when_one_expert_takes_all(tokens):
    """A router forced to send every token to expert 5 (held) and expert 1
    (held elsewhere): the layer computes all of them, in as many row tiles
    as that takes."""
    rs = np.random.RandomState(4)
    h = jnp.asarray(rs.normal(size=(tokens, 64)), jnp.float32).at[:, 0].set(1)
    router = jnp.zeros((8, 64)).at[5, 0].set(100.0).at[1, 0].set(50.0)
    wg, wu, wd = (jnp.asarray(rs.normal(0, 0.1, (4, 32, 64)), jnp.float32)
                  for _ in range(3))
    out, load = M.expert_ffn(h, router, wg, wu, wd, first_expert=4, top_k=2)
    weight, expert = M.route(h, router, 2)
    assert set(np.asarray(expert).ravel()) == {1, 5}
    np.testing.assert_allclose(
        out, _dense_part(h, weight, expert, wg, wu, wd, 4), atol=1e-5)
    assert load.tolist() == [0, tokens, 0, 0, tokens]


def test_dead_rows_route_nowhere_and_chunks_agree(monkeypatch):
    rs = np.random.RandomState(6)
    h = jnp.asarray(rs.normal(size=(64, 64)), jnp.float32)
    router = jnp.asarray(rs.normal(size=(8, 64)), jnp.float32)
    wg, wu, wd = (jnp.asarray(rs.normal(0, 0.1, (8, 32, 64)), jnp.float32)
                  for _ in range(3))
    live = jnp.asarray(np.arange(64) < 50)
    out, load = M.expert_ffn(h, router, wg, wu, wd, live, top_k=2)
    weight, expert = M.route(h, router, 2)
    want = _dense_part(h, weight, expert, wg, wu, wd, 0)
    np.testing.assert_allclose(out[:50], want[:50], atol=1e-5)
    assert not np.asarray(out[50:]).any()
    assert int(load.sum()) == 100 and int(load[-1]) == 0
    # tokens routed in chunks (a long prefill) give the same parts and load
    monkeypatch.setattr(M, "_CHUNK", 16)
    out2, load2 = M.expert_ffn(h, router, wg, wu, wd, live, top_k=2)
    np.testing.assert_allclose(out2, out, atol=1e-6)
    assert load2.tolist() == load.tolist()


def test_grouped_kernel_matches_its_xla_formulation():
    """``ops/pallas/moe_ffn.py`` in interpret mode: tiles of two experts,
    an empty tile and a tile past the last routed one."""
    from mxnet_tpu.ops.pallas import moe_ffn as K

    rs = np.random.RandomState(8)
    x = jnp.asarray(rs.normal(size=(40, 128)), jnp.float32)
    wg, wu, wd = (jnp.asarray(rs.normal(0, 0.1, (3, 256, 128)), jnp.float32)
                  for _ in range(3))
    te = jnp.asarray([0, 2, 2, 2, 2], jnp.int32)
    tv = jnp.asarray([1, 1, 1, 0, 0], jnp.int32)
    got = K.moe_ffn(x, te, tv, wg, wu, wd, 8, interpret=True)
    want = M._grouped_ffn_xla(x, te, tv, wg, wu, wd, 8)
    np.testing.assert_allclose(got, want, atol=1e-4)
    assert not np.asarray(got[24:]).any()


# ------------------------------------------------ (e) the cache manager
def test_cache_geometry_follows_the_layers():
    from mxnet_tpu.serve.kv_cache import CacheError, PagedKVCache

    c = PagedKVCache(layers=4, heads=2, head_dim=16, slots=3,
                     max_capacity=128, windows=[16, 16, 16, None])
    c.ensure_capacity(8)
    assert [p.k.shape for p in c.state] == [(3, 2, 8, 16)] * 4
    c.state = [p._replace(k=p.k + 1) for p in c.state]
    c.ensure_capacity(60)           # rings stop at their window
    assert [p.k.shape[2] for p in c.state] == [16, 16, 16, 64]
    assert all(float(p.k[:, :, :8].min()) == 1
               and float(p.k[:, :, 8:].max()) == 0 for p in c.state)
    assert c.page_lengths(32) == [16, 16, 16, 32]
    assert c.page_bytes(32) == 2 * (3 * 16 + 32) * 2 * 16 * 4
    assert c.nbytes() == c.nbytes_unquantized()
    with pytest.raises(CacheError, match="rings"):
        PagedKVCache(2, 2, 16, 3, 64, quantize=True, windows=[16, None])


def test_gpt_nano_serves_what_it_generates():
    """``gpt_nano`` through the reworked cache manager and programs: the
    server's greedy tokens are the model's own uncached greedy decode."""
    from mxnet_tpu.models.gpt import gpt_nano

    model = gpt_nano()
    model.initialize()
    prompt = np.random.RandomState(9).randint(0, 256, 11)
    want = model.generate(nd.array(prompt[None], dtype="int32"),
                          max_new_tokens=10, use_cache=False
                          ).asnumpy()[0, 11:].astype(int).tolist()
    with serve.GenerativeServer(model, slots=2) as srv:
        assert srv.cache.windows == [None, None]
        got = srv.submit(prompt, max_new_tokens=10).result(timeout_s=120)
        assert srv.prefix.get(prompt)[0].shape == (2, 2, 16, 32)
    assert got == want
