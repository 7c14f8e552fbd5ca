"""The flash forward's walk over the (Q block, K block) pairs it computes.

The pair list is checked against the blocks in which some query sees some
key, worked out element by element; the kernel (interpret mode) against the
rectangle-walking forward it replaced, kept below as the reference: the same
blocks fold in the same order, so every output is equal bit for bit.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mxnet_tpu.observability import registry
from mxnet_tpu.ops.pallas import flash_attention as FA


def _seen_blocks(Tq, Tk, bq, bk, causal, window):
    """Brute force: block (i, j) is read iff some query row of block i sees
    some key column of block j."""
    rows = np.arange(Tq)[:, None]
    cols = np.arange(Tk)[None, :]
    see = np.ones((Tq, Tk), bool)
    if causal or window is not None:
        see &= rows >= cols
    if window is not None:
        see &= rows - cols < window
    return see.reshape(Tq // bq, bq, Tk // bk, bk).any(axis=(1, 3))


_CASES = [
    # Tq, Tk, bq, bk, causal, window
    (256, 256, 32, 64, True, None),      # bq < bk
    (256, 256, 64, 64, True, None),      # bq = bk
    (256, 256, 128, 32, True, None),     # bq > bk
    (512, 256, 64, 64, True, None),      # more queries than keys
    (128, 512, 32, 64, True, None),      # more keys than queries
    (256, 256, 32, 64, True, 72),        # a window of neither block's size
    (256, 256, 64, 32, True, 40),
    (256, 256, 64, 64, True, 64),
    (512, 128, 64, 32, True, 24),        # Q blocks past every key's window
    (256, 256, 32, 64, False, None),     # the whole rectangle
    (128, 512, 64, 32, False, None),
]


@pytest.mark.parametrize("Tq,Tk,bq,bk,causal,window", _CASES)
def test_pair_list_holds_the_blocks_some_query_sees(Tq, Tk, bq, bk, causal,
                                                   window):
    nq, nk = Tq // bq, Tk // bk
    q_of, k_of = FA._fwd_pairs(nq, nk, bq, bk, causal or window is not None,
                               window)
    assert q_of.dtype == k_of.dtype == np.int32 and len(q_of) == len(k_of)
    # q-major: every Q block's pairs side by side, in order, K ascending
    assert (np.diff(q_of) >= 0).all()
    assert sorted(set(q_of.tolist())) == list(range(nq))
    seen = _seen_blocks(Tq, Tk, bq, bk, causal, window)
    for i in range(nq):
        ks = k_of[q_of == i]
        assert len(ks) >= 1                  # a first and a last pair
        assert (np.diff(ks) == 1).all()
        want = np.flatnonzero(seen[i])
        if len(want):
            np.testing.assert_array_equal(ks, want)
        else:                                # reads nothing: one pair kept,
            assert len(ks) == 1              # which the kernel skips
    if not causal and window is None:
        assert len(q_of) == nq * nk


def test_pair_list_of_the_largest_causal_bucket():
    """16,384 tokens at the shipped 256 x 512 blocks: 1,056 of 2,048."""
    q_of, _ = FA._fwd_pairs(64, 32, 256, 512, True, None)
    assert len(q_of) == 1056


# ------------------------------------------------ the rectangle's forward
def _rect_kernel(q_ref, k_ref, v_ref, *rest, scale, causal, bq, bk,
                 emit_lse, masked, window):
    if masked:
        vl_ref, rest = rest[0], rest[1:]
        vl = vl_ref[0, 0, 0]
    else:
        vl = None
    o_ref, rest = rest[0], rest[1:]
    if emit_lse:
        lse_ref, m_ref, l_ref, acc_ref = rest
    else:
        (m_ref, l_ref, acc_ref), lse_ref = rest, None
    kv_idx = pl.program_id(2)
    q_idx = pl.program_id(1)

    @pl.when(kv_idx == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, FA.NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    run = True
    if causal:
        run = kv_idx <= (q_idx * bq + bq - 1) // bk
        if window is not None:
            lo = jnp.maximum(q_idx * bq - (window - 1), 0) // bk
            run = jnp.logical_and(run, kv_idx >= lo)
    if masked:
        run = jnp.logical_and(run, kv_idx * bk < vl)

    @pl.when(run)
    def _compute():
        v = v_ref[0]
        s = FA._scores(q_ref, k_ref, q_idx, kv_idx, scale=scale,
                       causal=causal, bq=bq, bk=bk, vl=vl, window=window)
        m_prev = m_ref[:]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_ref[:] = l_ref[:] * corr + jnp.broadcast_to(
            jnp.sum(p, axis=1, keepdims=True), m_prev.shape)
        acc_ref[:] = acc_ref[:] * corr[:, :1] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    @pl.when(kv_idx == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:, :1], 1e-30)).astype(
            o_ref.dtype)
        if emit_lse:
            lse_ref[0] = m_ref[:] + jnp.log(jnp.maximum(l_ref[:], 1e-30))


def _rect_fwd(q, k, v, kv_valid_len, scale, causal, bq, bk, return_lse=False,
              window=None):
    """The forward over the whole (row, Q block, K block) rectangle, as it
    was before the pair list: skipped blocks still take a grid step."""
    B, H, Tq, D = q.shape
    Hkv, Tk, Dv = k.shape[1], k.shape[2], v.shape[3]
    group = H // Hkv
    masked = kv_valid_len is not None
    LANES = FA.LANES

    def kv_block(b, i, j):
        if causal:
            j = jnp.minimum(j, (i * bq + bq - 1) // bk)
        if window is not None:
            j = jnp.maximum(j, jnp.maximum(i * bq - (window - 1), 0) // bk)
        return ((b // H) * Hkv + (b % H) // group, j, 0)

    in_specs = [pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, bk, D), kv_block),
                pl.BlockSpec((1, bk, Dv), kv_block)]
    operands = [q.reshape(B * H, Tq, D), k.reshape(B * Hkv, Tk, D),
                v.reshape(B * Hkv, Tk, Dv)]
    if masked:
        in_specs.append(pl.BlockSpec((1, 1, LANES), lambda b, i, j: (b, 0, 0)))
        operands.append(FA._vl_operand(kv_valid_len, B, H))
    out_specs = [pl.BlockSpec((1, bq, Dv), lambda b, i, j: (b, i, 0))]
    out_shape = [jax.ShapeDtypeStruct((B * H, Tq, Dv), q.dtype)]
    if return_lse:
        out_specs.append(pl.BlockSpec((1, bq, LANES),
                                      lambda b, i, j: (b, i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((B * H, Tq, LANES),
                                              jnp.float32))
    res = pl.pallas_call(
        functools.partial(_rect_kernel, scale=scale, causal=causal, bq=bq,
                          bk=bk, emit_lse=return_lse, masked=masked,
                          window=window),
        interpret=True,
        grid=(B * H, Tq // bq, Tk // bk),
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bq, LANES), jnp.float32),
                        pltpu.VMEM((bq, LANES), jnp.float32),
                        pltpu.VMEM((bq, Dv), jnp.float32)],
    )(*operands)
    if return_lse:
        return res[0].reshape(B, H, Tq, Dv), res[1][..., :1]
    return res[0].reshape(B, H, Tq, Dv)


def _arrays(B, H, Hkv, Tq, Tk, D, Dv):
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.normal(size=(B, H, Tq, D)), jnp.float32)
    k = jnp.asarray(rs.normal(size=(B, Hkv, Tk, D)), jnp.float32)
    v = jnp.asarray(rs.normal(size=(B, Hkv, Tk, Dv)), jnp.float32)
    return q, k, v


_KERNEL_CASES = {
    # B, H, Hkv, Tq, Tk, D, Dv, bq, bk, causal, window, valid lengths, lse
    "causal_two_widths_bq_lt_bk": (1, 2, 2, 256, 256, 48, 32, 32, 64, True,
                                   None, None, False),
    "causal_two_widths_bq_gt_bk": (1, 2, 2, 256, 256, 48, 32, 128, 32, True,
                                   None, None, False),
    "grouped": (1, 4, 2, 64, 64, 16, 16, 8, 8, True, None, None, False),
    "grouped_window": (1, 4, 2, 64, 64, 16, 16, 8, 8, True, 24, None, False),
    "grouped_window_blocks_of_two_sizes": (1, 4, 2, 64, 64, 16, 16, 8, 16,
                                           True, 20, None, False),
    "window_with_q_blocks_past_every_key": (1, 2, 2, 128, 64, 16, 16, 16, 16,
                                            True, 16, None, False),
    "valid_len_causal": (3, 2, 2, 256, 256, 32, 32, 64, 64, True, None,
                         [0, 100, 256], False),
    "valid_len_not_causal": (3, 2, 2, 256, 256, 32, 32, 64, 128, False, None,
                             [0, 100, 256], False),
    "lse_causal": (1, 2, 2, 256, 256, 32, 32, 32, 64, True, None, None, True),
    "lse_valid_len_not_causal": (2, 2, 2, 256, 256, 32, 32, 64, 64, False,
                                 None, [0, 200], True),
}


@pytest.mark.parametrize("case", sorted(_KERNEL_CASES))
def test_pair_walk_equals_the_rectangle_bit_for_bit(case):
    B, H, Hkv, Tq, Tk, D, Dv, bq, bk, causal, window, vl, lse = \
        _KERNEL_CASES[case]
    q, k, v = _arrays(B, H, Hkv, Tq, Tk, D, Dv)
    vl = None if vl is None else jnp.asarray(vl, jnp.int32)
    causal = causal or window is not None
    walked = registry.counter("flash_fwd_pairs_walked").value
    grid = registry.counter("flash_fwd_pairs_grid").value
    got = FA._flash_fwd(q, k, v, vl, 0.13, causal, bq, bk, interpret=True,
                        return_lse=lse, window=window)
    want = _rect_fwd(q, k, v, vl, 0.13, causal, bq, bk, return_lse=lse,
                     window=window)
    got, want = (got, want) if lse else ((got,), (want,))
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    q_of, _ = FA._fwd_pairs(Tq // bq, Tk // bk, bq, bk, causal, window)
    assert (registry.counter("flash_fwd_pairs_walked").value - walked
            == B * H * len(q_of))
    assert (registry.counter("flash_fwd_pairs_grid").value - grid
            == B * H * (Tq // bq) * (Tk // bk))
    if vl is not None:              # the example of valid length 0: zeros
        np.testing.assert_array_equal(np.asarray(got[0])[0], 0.0)


def test_counters_read_the_share_walked_at_16384_tokens():
    """Tracing ax-k1's 16-head prefill at its largest bucket bumps the two
    counters by 16 x 1,056 and 16 x 2,048: 0.516 of the rectangle."""
    walked = registry.counter("flash_fwd_pairs_walked").value
    grid = registry.counter("flash_fwd_pairs_grid").value
    sd = lambda d: jax.ShapeDtypeStruct((1, 16, 16384, d), jnp.bfloat16)
    out = jax.eval_shape(lambda q, k, v: FA.flash_attention(
        q, k, v, causal=True, scale=0.130861, interpret=True),
        sd(192), sd(192), sd(128))
    assert out.shape == (1, 16, 16384, 128)
    dw = registry.counter("flash_fwd_pairs_walked").value - walked
    dg = registry.counter("flash_fwd_pairs_grid").value - grid
    assert (dw, dg) == (16 * 1056, 16 * 2048)
    assert round(dw / dg, 3) == 0.516


# ----------------------------------------- the walk that stops at a length
def _capture_calls(monkeypatch):
    """Records what each ``pallas_call`` of the forward is built from: the
    grid spec and the kernel's keywords, then the arguments it is called
    with (the prefetched arrays first)."""
    calls = []
    real = FA.pl.pallas_call

    def spy(kernel, **kw):
        call = real(kernel, **kw)

        def run(*args):
            calls.append(dict(spec=kw["grid_spec"], kernel=kernel.keywords,
                              args=args))
            return call(*args)

        return run

    monkeypatch.setattr(FA.pl, "pallas_call", spy)
    return calls


_LEN_CASES = {
    # B, H, Hkv, T, D, Dv, bq, bk, window
    "causal": (1, 2, 2, 256, 32, 32, 32, 64, None),
    "window": (1, 2, 2, 256, 32, 32, 32, 32, 72),
    "grouped_4_over_1": (1, 4, 1, 128, 16, 16, 16, 32, None),
    "two_widths_192_128": (1, 2, 2, 128, 192, 128, 32, 64, None),
}


def _lengths(T, bq, bk, window):
    """1, bq - 1, bq, bq + 1, T - 1, T, and a length whose last live Q block
    holds one pair (there the first pair is the last: a step after it must
    not start the block again)."""
    q_of, _ = FA._fwd_pairs(T // bq, T // bk, bq, bk, True, window)
    single = [i for i in range(T // bq) if (q_of == i).sum() == 1]
    assert single, "no Q block of one pair at these blocks"
    one_pair = single[-1] * bq + bq // 2
    return sorted({1, bq - 1, bq, bq + 1, one_pair, T - 1, T})


@pytest.mark.parametrize("case", sorted(_LEN_CASES))
def test_a_query_length_keeps_the_live_rows_and_zeroes_the_rest(case):
    """Rows under the length equal the call without one bit for bit (the
    same K/V blocks fold in the same order); the rows at and past it are
    zeros."""
    B, H, Hkv, T, D, Dv, bq, bk, window = _LEN_CASES[case]
    q, k, v = _arrays(B, H, Hkv, T, T, D, Dv)
    full = np.asarray(FA._flash_fwd(q, k, v, None, 0.13, True, bq, bk,
                                    interpret=True, window=window))
    for n in _lengths(T, bq, bk, window):
        got = np.asarray(FA._flash_fwd(
            q, k, v, None, 0.13, True, bq, bk, interpret=True, window=window,
            q_valid_len=jnp.asarray([n], jnp.int32)))
        assert got.shape == full.shape
        assert got[:, :, :n].tobytes() == full[:, :, :n].tobytes(), n
        np.testing.assert_array_equal(got[:, :, n:], 0.0)


def test_each_example_stops_at_its_own_length():
    B, H, T, bq, bk = 2, 2, 256, 32, 64
    q, k, v = _arrays(B, H, H, T, T, 32, 32)
    full = np.asarray(FA._flash_fwd(q, k, v, None, 0.13, True, bq, bk,
                                    interpret=True))
    for lens in ([1, 256], [100, 33], [0, 64]):
        got = np.asarray(FA.flash_attention(
            q, k, v, causal=True, scale=0.13, block_q=bq, block_k=bk,
            interpret=True, q_valid_len=jnp.asarray(lens, jnp.int32)))
        for b, n in enumerate(lens):
            assert got[b, :, :n].tobytes() == full[b, :, :n].tobytes()
            np.testing.assert_array_equal(got[b, :, n:], 0.0)


@pytest.mark.parametrize("case", sorted(_LEN_CASES))
def test_nothing_is_fetched_past_the_last_live_pair(monkeypatch, case):
    """The index maps, evaluated over every grid step: up to a row's last
    live pair they are the pair list's; from there on they return that
    pair's blocks, so the block indices change on live steps alone and
    nothing is fetched after them."""
    B, H, Hkv, T, D, Dv, bq, bk, window = _LEN_CASES[case]
    calls = _capture_calls(monkeypatch)
    q, k, v = _arrays(2, H, Hkv, T, T, D, Dv)
    lens = np.asarray([37, T], np.int32)
    FA._flash_fwd(q, k, v, None, 0.13, True, bq, bk, interpret=True,
                  window=window, q_valid_len=jnp.asarray(lens))
    (call,) = calls
    spec = call["spec"]
    q_of, k_of, last = (np.asarray(a) for a in call["args"][:3])
    assert spec.num_scalar_prefetch == 3 and last.shape == (2 * H,)
    q_map = spec.in_specs[0].index_map
    kv_map = spec.in_specs[1].index_map
    out_map = spec.out_specs[0].index_map
    for b in range(2 * H):
        # the pairs of the Q blocks that hold a row under the length
        end = int((q_of < -(-lens[b // H] // bq)).sum())
        assert last[b] == end - 1
        seen = []
        for t in range(len(q_of)):
            at = (b, t, q_of, k_of, last)
            qi = tuple(int(x) for x in q_map(*at))
            ki = tuple(int(x) for x in kv_map(*at))
            tt = min(t, end - 1)
            assert qi == (b, q_of[tt], 0)
            assert tuple(int(x) for x in out_map(*at)) == qi
            assert ki == ((b // H) * Hkv + (b % H) // (H // Hkv), k_of[tt], 0)
            seen.append((qi, ki))
        changes = [t for t in range(1, len(seen)) if seen[t] != seen[t - 1]]
        assert all(t < end for t in changes)
        assert (end == len(q_of)) == (lens[b // H] == T)


def test_without_a_length_the_call_is_built_as_before(monkeypatch):
    """No length: the grid, the two prefetched pair arrays and the operands
    of the pair walk, and the kernel's keywords, as they were; a length
    adds each grid row's last live step to the prefetch and nothing
    else."""
    calls = _capture_calls(monkeypatch)
    B, H, Hkv, T, D, Dv, bq, bk = 1, 4, 2, 128, 16, 8, 16, 32
    q, k, v = _arrays(B, H, Hkv, T, T, D, Dv)
    FA.flash_attention(q, k, v, causal=True, scale=0.13, block_q=bq,
                       block_k=bk, interpret=True)
    FA.flash_attention(q, k, v, causal=True, scale=0.13, block_q=bq,
                       block_k=bk, interpret=True,
                       q_valid_len=jnp.asarray([50], jnp.int32))
    plain, bounded = calls
    q_of, k_of = FA._fwd_pairs(T // bq, T // bk, bq, bk, True, None)
    for call in (plain, bounded):
        assert call["spec"].grid == (B * H, len(q_of))
        assert np.array_equal(np.asarray(call["args"][0]), q_of)
        assert np.array_equal(np.asarray(call["args"][1]), k_of)
    assert plain["spec"].num_scalar_prefetch == 2
    assert [a.shape for a in plain["args"][2:]] == [
        (B * H, T, D), (B * Hkv, T, D), (B * Hkv, T, Dv)]
    assert set(plain["kernel"]) == {"scale", "causal", "bq", "bk",
                                    "emit_lse", "masked", "window"}
    assert bounded["spec"].num_scalar_prefetch == 3
    assert [a.shape for a in bounded["args"][3:]] == [
        a.shape for a in plain["args"][2:]]
    assert bounded["kernel"] == dict(plain["kernel"], bounded=True)


def test_the_dense_lowerings_keep_the_same_contract():
    """Off the chip the seam's dense paths (plain, grouped, windowed) give
    the rows before the length as without it and zeros from it on."""
    from mxnet_tpu.ops import attention as A

    q, k, v = _arrays(2, 4, 2, 48, 48, 16, 16)
    lens = jnp.asarray([5, 48], jnp.int32)
    for kk, vv, window in ((k, v, None), (k, v, 12),
                           (jnp.repeat(k, 2, 1), jnp.repeat(v, 2, 1), None)):
        full = np.asarray(A.scaled_dot_attention(q, kk, vv, causal=True,
                                                 window=window))
        got = np.asarray(A.scaled_dot_attention(q, kk, vv, causal=True,
                                                window=window,
                                                q_valid_len=lens))
        assert got[0, :, :5].tobytes() == full[0, :, :5].tobytes()
        np.testing.assert_array_equal(got[0, :, 5:], 0.0)
        assert got[1].tobytes() == full[1].tobytes()
