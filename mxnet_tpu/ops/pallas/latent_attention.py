"""The absorbed decode read of latent attention: every query head of a slot
against ONE compressed row a position, reading only the blocks that hold the
slot's live positions (Pallas).

A latent page (``serve/kv_cache.py: LatentPage``) keeps, a position, the
normalised latent ``c_kv`` (R = 512 values) and the rotated key ``k_pe``
(P = 64) that all heads share, and nothing per head. The decode step takes
its queries through the key expansion first (``q_lat = q_nope W_uk^T``), so
the scores are ``scale * (q_lat . c_kv + q_pe . k_pe)`` and the value of a
position is its ``c_kv`` again: one fetch of a block serves the H x W scores
and the H x R accumulation of all H heads (121 FLOP a byte at 64 heads: the
HBM is the bound on a v5e). As a dense masked attention XLA would stream all
S x C rows every step, whatever is live.

The walk is ``decode_attention``'s: the lengths are a scalar prefetch, the
scalar core lists the (slot, block) pairs that hold a live position, and one
loop fetches them with its own double-buffered DMA while a running maximum,
sum and accumulator a slot (fp32) fold them in. A slot of length 0 is never
visited and gives zeros.

The two buffers lie as ``kv_write`` leaves them: ``c_kv`` (S, 1, C, R) with
R on the lanes and the positions on the sublanes (a block is ``(W, R)``
rows), ``k_pe`` (S, 1, C, P) with the capacity on the lanes, which the
kernel takes as the view (S, 1, P, C) (a bitcast): a block is ``(P, W)``,
already the transposed operand of ``q_pe . k_pe``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_attention import (_LANES, _MAX_ITEMS, _fold, _reset, _row_block,
                               _stream, _work_list)

# the queries and the result of every slot stand whole in VMEM (twice: the
# pipeline's buffers), beside two blocks of each buffer, inside Mosaic's
# default 16 MiB
_MAX_QUERY_BYTES = 4 << 20


def tiles(q_lat_shape, q_pe_shape, c_shape, pe_shape, dtype):
    """Whether ``q_lat`` (S, H, 1, R), ``q_pe`` (S, H, 1, P) against
    ``c_cache`` (S, 1, C, R) and ``pe_cache`` (S, 1, C, P) map onto the
    kernel's blocks: one query token a slot, 16- or 32-bit values, a latent
    of whole lane tiles, a rotated part under one lane tile that fills whole
    sublane tiles, query heads in whole sublane tiles, a buffer length of
    whole lane tiles; every slot's queries fit VMEM and the work list SMEM.
    The gate in ops/attention.py asks at trace time."""
    if not all(len(s) == 4 for s in (q_lat_shape, q_pe_shape, c_shape,
                                     pe_shape)):
        return False
    S, H, T, R = q_lat_shape
    _, _, C, P = pe_shape
    itemsize = jnp.dtype(dtype).itemsize
    sub = 32 // itemsize if itemsize in (2, 4) else 0
    return bool(
        sub and T == 1 and q_pe_shape == (S, H, 1, P)
        and c_shape == (S, 1, C, R) and pe_shape == (S, 1, C, P)
        and R % _LANES == 0 and 0 < P < _LANES and P % sub == 0
        and H % sub == 0 and C % _LANES == 0
        and S * H * R * itemsize <= _MAX_QUERY_BYTES
        and S * (C // _row_block(C)) <= _MAX_ITEMS)


def _latent_kernel(len_ref, ql_ref, qp_ref, c_hbm, pe_hbm, o_ref, cbuf, pbuf,
                   sem, item_slot, item_blk, m_ref, l_ref, acc, *, scale):
    """ql_ref, o_ref (S, H, R); qp_ref (S, H, P); c_hbm (S, 1, C, R) and
    pe_hbm (S, 1, P, C) left in HBM; cbuf (2, W, R), pbuf (2, P, W)."""
    W = cbuf.shape[1]
    n = _work_list(len_ref, item_slot, item_blk, W)
    o_ref[...] = jnp.zeros_like(o_ref)
    f32 = jnp.float32

    def work(i, half):
        s, b = item_slot[i], item_blk[i]
        length = len_ref[s]

        @pl.when(b == 0)
        def _():
            _reset(m_ref, l_ref, acc)

        # all heads against the block's rows, fetched once: the latent part
        # contracts over R with the rows as they lie, the rotated part over
        # P with the block already transposed
        c = cbuf[half]
        sc = scale * (
            jax.lax.dot_general(ql_ref[s], c, (((1,), (1,)), ((), ())),
                                preferred_element_type=f32)
            + jnp.dot(qp_ref[s], pbuf[half], preferred_element_type=f32))
        live = b * W + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1) \
            < length
        a, l_new = _fold(
            sc[None], live[None], m_ref, l_ref, acc,
            lambda p: jnp.dot(p[0].astype(c.dtype), c,
                              preferred_element_type=f32)[None])

        @pl.when((b + 1) * W >= length)
        def _():
            o_ref[s] = (a / l_new)[0].astype(o_ref.dtype)

    def window(ref, s, at):
        return ref.at[s, 0, at, :] if ref is c_hbm else ref.at[s, 0, :, at]

    _stream(n, item_slot, item_blk, W, window, (c_hbm, pe_hbm), (cbuf, pbuf),
            sem, work)


def latent_attention(q_lat, q_pe, c_cache, pe_cache, lengths, scale,
                     interpret=False):
    """``softmax(scale * (q_lat . c + q_pe . pe)) c`` of one query token a
    slot, ``q_lat`` (S, H, 1, R) and ``q_pe`` (S, H, 1, P), over the
    positions ``[0, lengths[s])`` of ``c_cache`` (S, 1, C, R) and
    ``pe_cache`` (S, 1, C, P); ``lengths`` (S,) int clamps into ``[0, C]``,
    and a slot of length 0 gives zeros. bf16 (or fp32) operands, fp32
    scores with the scale applied to them, fp32 softmax with a running
    maximum across blocks, fp32 accumulation; the result (S, H, 1, R) in
    ``q_lat``'s type. See :func:`tiles` for what compiles."""
    return _attend(q_lat, q_pe, c_cache, pe_cache, lengths, float(scale),
                   interpret)


# one trace and one lowering for all the layers of a step
@functools.partial(jax.jit, static_argnums=(5, 6))
def _attend(q_lat, q_pe, c_cache, pe_cache, lengths, scale, interpret):
    S, H, _, R = q_lat.shape
    C, P = pe_cache.shape[2], pe_cache.shape[3]
    W = _row_block(C)
    lengths = jnp.clip(lengths.astype(jnp.int32), 0, C)
    ql, qp = q_lat[:, :, 0, :], q_pe[:, :, 0, :]
    whole = lambda a: pl.BlockSpec(a.shape,
                                   lambda i, lens: (0,) * len(a.shape))
    out = pl.pallas_call(
        functools.partial(_latent_kernel, scale=scale),
        name="latent_attention",
        interpret=interpret,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[whole(ql), whole(qp),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=whole(ql),
            scratch_shapes=[
                pltpu.VMEM((2, W, R), c_cache.dtype),
                pltpu.VMEM((2, P, W), pe_cache.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((S * (C // W),), jnp.int32),
                pltpu.SMEM((S * (C // W),), jnp.int32),
                pltpu.VMEM((1, H, 1), jnp.float32),
                pltpu.VMEM((1, H, 1), jnp.float32),
                pltpu.VMEM((1, H, R), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(ql.shape, q_lat.dtype),
    )(lengths, ql, qp, c_cache, jnp.swapaxes(pe_cache, 2, 3))
    return out[:, :, None, :]
