"""Flash attention Pallas kernels (forward + backward) for TPU.

Replaces the reference's unfused softmax(QK^T)V chain (three HBM round trips
for the T×T score matrix) with blockwise kernels: Q blocks stay resident in
VMEM while K/V blocks stream through, online-softmax accumulating in fp32
scratch — O(T) HBM traffic instead of O(T^2), forward AND backward. The
forward's grid walks a list of the (Q block, K block) pairs it computes (the
lower triangle when causal, the band under a window), so a K/V block above
the diagonal or outside the window is neither fetched nor stepped over; given
each example's query length (a prompt inside its padded bucket) a row's walk
stops after the last Q block that holds a live query row. It also emits the
per-row logsumexp (lane-broadcast, matching the (bq, 128) scratch layout
Mosaic likes); the backward is the flash-attention-2 recompute scheme as two
kernels — dq over (q-block, k-inner) and dk/dv over
(k-block, q-inner) — so no T×T tensor ever materializes in either pass.

Pattern source: /opt/skills/guides/pallas_guide.md (double-buffered matmul,
custom-VJP kernels). Falls back to the jnp reference off-TPU (ops/attention.py).
"""
from __future__ import annotations

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def _scores(q_ref, k_ref, q_idx, kv_idx, *, scale, causal, bq, bk, vl=None,
            window=None):
    """Shared Q·Kᵀ score-block recompute — the ONE definition of scaling,
    causal masking, and key-padding masking used by forward and both backward
    kernels, so their numerics can never desynchronize. ``vl`` is a traced
    per-example valid K length: columns >= vl are masked (BERT-style prefix
    padding). ``window`` (static, forward only) also masks columns more than
    ``window - 1`` behind the row."""
    # native-dtype (bf16) MXU operands with fp32 accumulation; scale applied
    # to the fp32 scores so no extra bf16 rounding hits the matmul inputs
    q = q_ref[0]                              # (bq, d)
    k = k_ref[0]                              # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        rows = q_idx * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = kv_idx * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(rows >= cols, s, NEG_INF)
        if window is not None:
            s = jnp.where(rows - cols < window, s, NEG_INF)
    if vl is not None:
        cols = kv_idx * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(cols < vl, s, NEG_INF)
    return s


def _kv_block_range(q_idx, bq, bk, causal, window):
    """First and last K/V block that a Q block of a causal (windowed)
    attention reads: the one definition the kernel's skip and the forward's
    pair list share. Plain integer arithmetic, so that it takes a traced
    block index and a Python one alike; ``lo`` is negative where the window
    reaches past the first key (every block from 0 on is read)."""
    hi = (q_idx * bq + bq - 1) // bk if causal else None
    lo = (q_idx * bq - (window - 1)) // bk if window is not None else None
    return lo, hi


def _fwd_pairs(nq, nk, bq, bk, causal, window):
    """The (Q block, K block) pairs the forward walks, as two int32 arrays:
    for each Q block in turn the K blocks ``_kv_block_range`` says it reads,
    ascending (the lower triangle when causal, the band under a window, the
    whole rectangle otherwise), so that each row folds the same blocks in
    the same order as a walk of the whole rectangle would. A Q block that
    reads no block (a window wholly past the keys) keeps one pair, which
    the kernel skips, so that its zeros are still written."""
    q_of, k_of = [], []
    for i in range(nq):
        lo, hi = _kv_block_range(i, bq, bk, causal, window)
        hi = nk - 1 if hi is None else min(hi, nk - 1)
        lo = 0 if lo is None else min(max(lo, 0), hi)
        q_of += [i] * (hi - lo + 1)
        k_of += range(lo, hi + 1)
    return np.asarray(q_of, np.int32), np.asarray(k_of, np.int32)


def _last_live_steps(q_valid_len, q_of, nq, bq, heads):
    """Each grid row's last live step (B * heads,) int32: the last pair of
    the last Q block that holds a query row under its example's length (Q
    block 0's at a length of 0, whose rows are all zeroed after the call).
    Worked out once a call, so that the index maps and the kernel read one
    word a step from it."""
    starts = np.searchsorted(q_of, np.arange(1, nq + 1)).astype(np.int32)
    n = jnp.clip((jnp.asarray(q_valid_len, jnp.int32) + bq - 1) // bq, 1, nq)
    return jnp.repeat(jnp.asarray(starts)[n - 1] - 1, heads)


def _fwd_step(b, t, last):
    """The pair step ``t`` of row ``b`` reads: itself, or with query lengths
    (``last``: the prefetched last live steps) on a step past the row's last
    live pair that pair, so that the index maps fetch nothing more."""
    return jnp.minimum(t, last[0][b]) if last else t


def _fwd_index_maps(H, Hkv):
    """The forward's Q (and output) and K/V index maps over grid (row, pair
    step): step t of row b = batch * H + head computes Q block q_of[t]
    against K/V block k_of[t] of K/V row batch * Hkv + head // group. The
    prefetched refs follow the grid indices: ``q_of, k_of`` and, with query
    lengths, each row's last live step."""
    group = H // Hkv

    def q_block(b, t, q_of, k_of, *last):
        return (b, q_of[_fwd_step(b, t, last)], 0)

    def kv_block(b, t, q_of, k_of, *last):
        return ((b // H) * Hkv + (b % H) // group,
                k_of[_fwd_step(b, t, last)], 0)

    return q_block, kv_block


def _and(a, b):
    return b if a is True else jnp.logical_and(a, b)


def _fwd_kernel(q_of, k_of, *refs, scale, causal, bq, bk, emit_lse, masked,
                window=None, bounded=False):
    # ``bounded``: query lengths came in (each row's last live step, one
    # more prefetched ref), and a step past it does nothing at all
    live = True
    if bounded:
        last_ref, *refs = refs
        live = pl.program_id(1) <= last_ref[pl.program_id(0)]
    q_ref, k_ref, v_ref, *rest = refs
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
    t = pl.program_id(1)
    n = pl.num_programs(1)
    q_idx = q_of[t]
    kv_idx = k_of[t]
    # a Q block's pairs lie side by side in the list: its first pair is the
    # one after another Q block's, its last the one before the next's
    first = _and(live, jnp.logical_or(
        t == 0, q_of[jnp.maximum(t - 1, 0)] != q_idx))
    last = _and(live, jnp.logical_or(
        t == n - 1, q_of[jnp.minimum(t + 1, n - 1)] != q_idx))

    @pl.when(first)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    run = live
    if causal:
        # the list holds only blocks this Q block reads, but for the pair
        # kept for a Q block that reads none, which this skips: the first
        # query row of this Q block is q_idx*bq; a block contributes iff
        # kv_idx*bk <= q_idx*bq + bq - 1 (and, under a window, iff its last
        # column is still in the first row's window)
        lo, hi = _kv_block_range(q_idx, bq, bk, causal, window)
        run = _and(run, kv_idx <= hi)
        if lo is not None:
            run = jnp.logical_and(run, kv_idx >= lo)
    if masked:
        # dynamic skip: K blocks entirely past this example's valid length
        run = jnp.logical_and(run, kv_idx * bk < vl)

    @pl.when(run)
    def _compute():
        v = v_ref[0]                            # (bk, d) native dtype
        s = _scores(q_ref, k_ref, q_idx, kv_idx, scale=scale, causal=causal,
                    bq=bq, bk=bk, vl=vl, window=window)
        m_prev = m_ref[:]                       # (bq, 128) broadcast lanes
        m_cur = jnp.max(s, axis=1, keepdims=True)  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])           # (bq, bk) fp32
        l_ref[:] = l_ref[:] * corr + jnp.broadcast_to(
            jnp.sum(p, axis=1, keepdims=True), m_prev.shape)
        # p downcast to the operand dtype for the MXU; accumulator stays fp32
        acc_ref[:] = acc_ref[:] * corr[:, :1] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    @pl.when(last)
    def _finish():
        # max guard: a vl=0 example has an all-masked row (l == 0)
        o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:, :1], 1e-30)).astype(
            o_ref.dtype)
        if emit_lse:
            lse_ref[0] = m_ref[:] + jnp.log(jnp.maximum(l_ref[:], 1e-30))


def _count_pairs(walked, grid):
    """How far the pair list engages, counted once a forward is traced: the
    (row, Q block, K block) steps walked against those of the whole
    rectangle. For a call with query lengths, which are known only when it
    runs, ``walked`` is an upper bound: the steps past a row's last live
    pair are counted, and do nothing. Lazy import: the kernels stay
    importable before the observability package is."""
    from ...observability import registry

    registry.counter("flash_fwd_pairs_walked").inc(walked)
    registry.counter("flash_fwd_pairs_grid").inc(grid)


def _vl_operand(kv_valid_len, B, H):
    """valid_len (B,) → (B*H, 1, LANES) int32 VMEM operand (one scalar per
    grid row, lane-broadcast to the native tile width)."""
    vl = jnp.broadcast_to(kv_valid_len.astype(jnp.int32)[:, None, None, None],
                          (B, H, 1, LANES))
    return vl.reshape(B * H, 1, LANES)


def _flash_fwd(q, k, v, kv_valid_len, scale, causal, bq, bk, interpret=False,
               return_lse=False, window=None, q_valid_len=None):
    B, H, Tq, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    Dv = v.shape[3]          # the values' own width (and the result's)
    bq = min(bq, Tq)
    bk = min(bk, Tk)
    qr = q.reshape(B * H, Tq, D)
    kr = k.reshape(B * Hkv, Tk, D)
    vr = v.reshape(B * Hkv, Tk, Dv)
    masked = kv_valid_len is not None
    nq, nk = Tq // bq, Tk // bk
    q_of, k_of = _fwd_pairs(nq, nk, bq, bk, causal, window)
    _count_pairs(B * H * len(q_of), B * H * nq * nk)
    # the grid walks the pair list, so that no block the kernel would skip
    # is fetched and no step is spent on it; with query lengths a row's
    # walk ends at the last pair of the last Q block that holds a live
    # query row (the steps after it fetch nothing and do nothing)
    q_block, kv_block = _fwd_index_maps(H, Hkv)
    prefetch = [jnp.asarray(q_of), jnp.asarray(k_of)]
    bounded = {}
    if q_valid_len is not None:
        q_valid_len = jnp.broadcast_to(
            jnp.asarray(q_valid_len, jnp.int32), (B,))
        prefetch.append(_last_live_steps(q_valid_len, q_of, nq, bq, H))
        bounded = dict(bounded=True)
    in_specs = [
        pl.BlockSpec((1, bq, D), q_block),
        pl.BlockSpec((1, bk, D), kv_block),
        pl.BlockSpec((1, bk, Dv), kv_block),
    ]
    operands = [qr, kr, vr]
    if masked:
        in_specs.append(pl.BlockSpec((1, 1, LANES), lambda b, *_: (b, 0, 0)))
        operands.append(_vl_operand(kv_valid_len, B, H))
    out_specs = [pl.BlockSpec((1, bq, Dv), q_block)]
    out_shape = [jax.ShapeDtypeStruct((B * H, Tq, Dv), q.dtype)]
    if return_lse:  # inference path skips the lse output entirely — XLA
        # cannot DCE an output of an opaque pallas_call
        out_specs.append(pl.BlockSpec((1, bq, LANES), q_block))
        out_shape.append(jax.ShapeDtypeStruct((B * H, Tq, LANES), jnp.float32))
    res = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal, bq=bq, bk=bk,
                          emit_lse=return_lse, masked=masked, window=window,
                          **bounded),
        name="flash_fwd",
        interpret=interpret,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(B * H, len(q_of)),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((bq, LANES), jnp.float32),  # running max (lanes)
                pltpu.VMEM((bq, LANES), jnp.float32),  # running denom
                pltpu.VMEM((bq, Dv), jnp.float32),     # output accumulator
            ],
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(*prefetch, *operands)
    if return_lse:
        out, lse = res
        # keep only one lane as the residual (saving the full 128-lane
        # broadcast would hold 128x the memory across fwd→bwd)
        return out.reshape(B, H, Tq, Dv), lse[..., :1]
    out = res[0].reshape(B, H, Tq, Dv)
    if q_valid_len is not None:
        # the Q blocks past the last live one are never visited: whatever
        # their buffer held becomes zeros, as do the live block's rows at
        # and past the length
        out = rows_before(out, q_valid_len)
    return out


def rows_before(x, q_valid_len):
    """``x`` (B, H, T, D) with the rows at and past each example's query
    length ``q_valid_len`` (B,) (or one for all) set to zeros: a select,
    so that nothing the rows held, not even a NaN, reaches the result."""
    rows = jnp.arange(x.shape[2], dtype=jnp.int32)[None, None, :, None]
    n = jnp.asarray(q_valid_len, jnp.int32).reshape(-1, 1, 1, 1)
    return jnp.where(rows < n, x, jnp.zeros((), x.dtype))


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
               scale, causal, bq, bk, masked):
    if masked:
        vl_ref, dq_ref, dq_acc = rest
        vl = vl_ref[0, 0, 0]
    else:
        (dq_ref, dq_acc), vl = rest, None
    kv_idx = pl.program_id(2)
    q_idx = pl.program_id(1)

    @pl.when(kv_idx == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = True
    if causal:
        run = kv_idx * bk <= q_idx * bq + bq - 1
    if masked:
        run = jnp.logical_and(run, kv_idx * bk < vl)

    @pl.when(run)
    def _compute():
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = _scores(q_ref, k_ref, q_idx, kv_idx, scale=scale, causal=causal,
                    bq=bq, bk=bk, vl=vl)
        p = jnp.exp(s - lse_ref[0][:, :1])                       # (bq, bk)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0][:, :1])
        # one fp32→native downcast of ds before the MXU matmul (FA2 recipe)
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(kv_idx == pl.num_programs(2) - 1)
    def _finish():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                scale, causal, bq, bk, masked):
    if masked:
        vl_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
        vl = vl_ref[0, 0, 0]
    else:
        (dk_ref, dv_ref, dk_acc, dv_acc), vl = rest, None
    q_idx = pl.program_id(2)   # inner: sweep q blocks
    kv_idx = pl.program_id(1)  # outer: this kernel instance's k/v block

    @pl.when(q_idx == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = True
    if causal:
        # q block contributes iff its last row >= first k row
        run = q_idx * bq + bq - 1 >= kv_idx * bk
    if masked:
        # whole K block past valid length → dk = dv = 0 there
        run = jnp.logical_and(run, kv_idx * bk < vl)

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = _scores(q_ref, k_ref, q_idx, kv_idx, scale=scale, causal=causal,
                    bq=bq, bk=bk, vl=vl)
        p = jnp.exp(s - lse_ref[0][:, :1])                       # (bq, bk)
        # dv += p^T @ do — p downcast to the operand dtype for the MXU
        dv_acc[:] += jax.lax.dot_general(p.astype(do.dtype), do,
                                         (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0][:, :1])                      # (bq, bk)
        # dk += ds^T @ q * scale
        dk_acc[:] += jax.lax.dot_general(ds.astype(q.dtype), q,
                                         (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32) * scale

    @pl.when(q_idx == pl.num_programs(2) - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, o, lse, do, kv_valid_len, scale, causal, bq, bk,
               interpret=False):
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    bq = min(bq, Tq)
    bk = min(bk, Tk)
    qr = q.reshape(B * H, Tq, D)
    kr = k.reshape(B * H, Tk, D)
    vr = v.reshape(B * H, Tk, D)
    dor = do.reshape(B * H, Tq, D)
    masked = kv_valid_len is not None
    # delta_i = rowsum(dO ⊙ O); both row stats lane-broadcast to the
    # (bq, 128) layout transiently (the saved lse residual is 1-lane)
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta.reshape(B * H, Tq, 1), (B * H, Tq, LANES))
    lse = jnp.broadcast_to(lse, (B * H, Tq, LANES))

    spec_q = pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0))
    spec_kv_in = pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0))
    spec_row = pl.BlockSpec((1, bq, LANES), lambda b, i, j: (b, i, 0))
    spec_vl = pl.BlockSpec((1, 1, LANES), lambda b, i, j: (b, 0, 0))

    dq_in_specs = [spec_q, spec_kv_in, spec_kv_in, spec_q, spec_row, spec_row]
    dq_operands = [qr, kr, vr, dor, lse, delta]
    if masked:
        dq_in_specs.append(spec_vl)
        dq_operands.append(_vl_operand(kv_valid_len, B, H))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal, bq=bq,
                          bk=bk, masked=masked),
        name="flash_dq",
        interpret=interpret,
        grid=(B * H, Tq // bq, Tk // bk),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Tq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(*dq_operands)

    # dk/dv: k block is the resident (outer) axis, q blocks stream (inner)
    spec_q_inner = pl.BlockSpec((1, bq, D), lambda b, i, j: (b, j, 0))
    spec_kv_outer = pl.BlockSpec((1, bk, D), lambda b, i, j: (b, i, 0))
    spec_row_inner = pl.BlockSpec((1, bq, LANES), lambda b, i, j: (b, j, 0))
    dkv_in_specs = [spec_q_inner, spec_kv_outer, spec_kv_outer, spec_q_inner,
                    spec_row_inner, spec_row_inner]
    dkv_operands = [qr, kr, vr, dor, lse, delta]
    if masked:
        dkv_in_specs.append(spec_vl)
        dkv_operands.append(_vl_operand(kv_valid_len, B, H))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal, bq=bq,
                          bk=bk, masked=masked),
        name="flash_dkv",
        interpret=interpret,
        grid=(B * H, Tk // bk, Tq // bq),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tk, D), k.dtype),
            jax.ShapeDtypeStruct((B * H, Tk, D), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(*dkv_operands)

    return (dq.reshape(B, H, Tq, D), dk.reshape(B, H, Tk, D),
            dv.reshape(B, H, Tk, D))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, kv_valid_len, scale, causal, bq, bk, interpret=False):
    return _flash_fwd(q, k, v, kv_valid_len, scale, causal, bq, bk,
                      interpret=interpret)


def _flash_vjp_fwd(q, k, v, kv_valid_len, scale, causal, bq, bk,
                   interpret=False):
    o, lse = _flash_fwd(q, k, v, kv_valid_len, scale, causal, bq, bk,
                        interpret=interpret, return_lse=True)
    return o, (q, k, v, kv_valid_len, o, lse)


def _flash_vjp_bwd(scale, causal, bq, bk, interpret, res, do):
    q, k, v, kv_valid_len, o, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, o, lse, do, kv_valid_len, scale, causal,
                            bq, bk, interpret=interpret)
    return dq, dk, dv, None  # int valid-length carries no tangent


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


# Tuned (block_q, block_k) per sequence-length bucket — ONE table every
# caller picks up. Keys are the smallest seq the row applies to, scanned
# descending. The tuned table is DATA, not code: tools/flash_sweep.py
# measures candidates on hardware and (with --apply) writes the winners to
# flash_blocks.json next to this file; import picks it up. Until a sweep
# lands, the single fallback row is the VMEM-friendly 256x512 point.
BLOCK_DEFAULTS = {
    0: (256, 512),
}

# Measured flash-vs-dense crossover seq from the sweep artifact ("min_len"),
# or None until a hardware sweep lands — attention.py's gate falls back to
# its static _FLASH_MIN_LEN guess while this is None.
MIN_LEN = None

_BLOCKS_ARTIFACT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "flash_blocks.json")

# provenance of the loaded artifact (tuned_by/swept_at/backend/source) —
# empty until _load_block_artifact succeeds; diagnose and the interim
# warning read it
_ARTIFACT_META = {}

_INTERIM_WARNED = False


def _warn_if_interim():
    """Warn ONCE per process when serving from an interim table — either
    no artifact loaded at all, or one whose ``swept_at`` is null (hand-
    authored placeholder, never measured on hardware). Tuned tables from
    flash_sweep --apply or ir.tune.tune_flash_blocks carry a timestamp
    and stay silent."""
    global _INTERIM_WARNED
    if _INTERIM_WARNED:
        return
    if _ARTIFACT_META.get("swept_at"):
        return
    _INTERIM_WARNED = True
    import warnings

    warnings.warn(
        "flash_attention is serving an INTERIM block table (%s) — blocks "
        "were never measured on this hardware; run tools/flash_sweep.py "
        "--apply or ir.tune.tune_flash_blocks(apply=True) to tune them"
        % (_ARTIFACT_META.get("source") or "built-in fallback"))


def write_block_artifact(blocks, source, swept_at=None, tuned_by=None,
                         backend=None, min_len=None, note=None, path=None):
    """THE writer for flash_blocks.json — flash_sweep --apply and
    ir.tune.tune_flash_blocks both emit through here, so the two formats
    cannot diverge. Validates the table shape, writes atomically
    (tmp + os.replace), reloads the live BLOCK_DEFAULTS, and returns the
    artifact dict."""
    table = {}
    for seq, blk in dict(blocks).items():
        bq, bk = int(blk[0]), int(blk[1])
        if bq <= 0 or bk <= 0:
            raise ValueError("non-positive block pair %r for seq %r"
                             % (blk, seq))
        table[str(int(seq))] = [bq, bk]
    if not table:
        raise ValueError("refusing to write an empty block table")
    if "0" not in table:
        raise ValueError("block table needs a catch-all '0' row")
    artifact = {
        "blocks": {k: table[k] for k in sorted(table, key=int)},
        "min_len": int(min_len) if min_len is not None else None,
        "source": source,
        "tuned_by": tuned_by,
        "swept_at": swept_at,
        "backend": backend,
        "note": note,
    }
    out = path or _BLOCKS_ARTIFACT
    tmp = out + ".tmp"
    with open(tmp, "w") as f:
        json.dump(artifact, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, out)
    _load_block_artifact(out)
    return artifact


def _load_block_artifact(path=None):
    """Replace BLOCK_DEFAULTS with the committed hardware-sweep winners.

    The artifact maps seq-bucket lower bounds to [block_q, block_k] and
    carries provenance ("swept_at", "source"). An ABSENT default artifact
    leaves the fallback table untouched silently (tuning must never break
    import); a PRESENT-but-malformed file warns — a corrupted
    ``flash_sweep --apply`` output silently reverting every bench to the
    untuned table is exactly the failure that must not be quiet. An
    explicit ``path`` argument raises on any failure: the caller asked for
    that file specifically."""
    global BLOCK_DEFAULTS, MIN_LEN, _ARTIFACT_META, _INTERIM_WARNED
    explicit = path is not None
    path = path or _BLOCKS_ARTIFACT
    if not os.path.exists(path):
        if explicit:
            raise FileNotFoundError("flash block artifact %r not found" % path)
        return False
    try:
        with open(path) as f:
            raw = json.load(f)
        table = {int(k): (int(v[0]), int(v[1]))
                 for k, v in raw["blocks"].items()}
        if not table:
            raise ValueError("empty 'blocks' table")
    except Exception as e:
        if explicit:
            raise ValueError(
                "flash block artifact %r is malformed: %s" % (path, e)) from e
        import warnings

        warnings.warn(
            "ignoring malformed flash block artifact %s (%s); "
            "falling back to the untuned table" % (path, e))
        return False
    BLOCK_DEFAULTS = table
    # reset too: a reloaded artifact without min_len must not leave a stale
    # crossover from a superseded sweep paired with the new block table
    MIN_LEN = raw["min_len"] if isinstance(raw.get("min_len"), int) else None
    # fixed-key provenance record (replaced whole on every load, GL006-safe)
    _ARTIFACT_META = dict(
        {k: raw.get(k) for k in
         ("source", "tuned_by", "swept_at", "backend", "note")},
        path=path)
    # a freshly tuned table may land mid-process: re-arm the interim check
    _INTERIM_WARNED = False
    return True


_load_block_artifact()


def _default_blocks(seq):
    for lo in sorted(BLOCK_DEFAULTS, reverse=True):
        if seq >= lo:
            return BLOCK_DEFAULTS[lo]
    return BLOCK_DEFAULTS[min(BLOCK_DEFAULTS)]


def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, interpret=False, kv_valid_len=None,
                    window=None, q_valid_len=None):
    """q,k,v: (B, H, T, D). D should be a multiple of 128 lanes ideally;
    T must be divisible by the chosen blocks (callers pad).

    Grouped K/V heads (``k``, ``v`` of ``Hkv`` heads, ``H % Hkv == 0``:
    query head h reads K/V head ``h // (H // Hkv)`` through the K/V index
    map, nothing is repeated in memory) and ``window`` (static int: key j
    visible to query i iff ``0 <= i - j < window``) run the forward kernel
    alone: the backward kernels know neither, so these calls carry no
    gradient.
    Nor do values of a width of their own (``v`` (B, Hkv, T, Dv) beside q
    and k of width D: the result is (B, H, T, Dv)).

    block_q/block_k default from the seq-bucketed BLOCK_DEFAULTS table
    (where the committed hardware sweep lands its winners).

    kv_valid_len: optional (B,) int — BERT-style key-padding: each example
    attends only to K/V positions < its valid length (columns beyond are
    masked AND their blocks skipped entirely, forward and backward).

    q_valid_len: optional (B,) int (or one for all) — the query rows that
    count, a prompt's length inside its padded bucket: each example's walk
    ends at the last Q block that holds a row under its length, the rows
    before it are the call's without a length bit for bit, and the rows at
    and past it are zeros. The forward alone: no gradient."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if block_q is None or block_k is None:
        _warn_if_interim()  # explicit blocks aren't served from the table
    Tq, Tk = q.shape[2], k.shape[2]
    # bucket each axis by ITS length: cross-attention (short queries, long
    # keys) must not take the long-seq row's block_q
    if block_q is None:
        block_q = _default_blocks(Tq)[0]
    if block_k is None:
        block_k = _default_blocks(Tk)[1]
    bq = _largest_divisor_block(Tq, block_q)
    bk = _largest_divisor_block(Tk, block_k)
    if (window is not None or k.shape[1] != q.shape[1]
            or v.shape[3] != q.shape[3] or q_valid_len is not None):
        return _flash_fwd(q, k, v, kv_valid_len, float(scale),
                          bool(causal) or window is not None, bq, bk,
                          interpret=interpret,
                          window=None if window is None else int(window),
                          q_valid_len=q_valid_len)
    return _flash(q, k, v, kv_valid_len, float(scale), bool(causal), bq, bk,
                  interpret)


def _largest_divisor_block(t, prefer):
    b = min(prefer, t)
    while t % b:
        b //= 2
    return max(b, 1)
