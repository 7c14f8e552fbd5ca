"""One new token per slot into the paged K/V buffers, in place (Pallas).

The decode step of ``GenerativeServer`` writes ``update`` (S, H, 1, D) into
``cache`` (S, H, C, D) at a per-slot position. As ``vmap(dynamic_update_slice)``
that is a ``scatter``, which XLA on the TPU expands into a ``while`` loop of
S iterations per buffer, each a read-merge-write of one unaligned column:
latency, not bandwidth (PERF.md, PR 29).

For head widths under one lane tile the device holds the buffer with the
capacity axis on the lanes (``{2,3,1,0:T(8,128)(2,1)}``): one token is a
one-lane column down H x D sublane rows. The kernel therefore works on the
view ``(S, H, D, C)``, which is that same memory read row-major, so XLA makes
the ``swapaxes`` around the call a bitcast and the aliased buffer is updated
where it lies. A formulation that shows Mosaic ``(S, H, C, D)`` would force a
relayout of the whole buffer before and after every call.

For head widths that are whole lane tiles (128, 256) the device keeps D on
the lanes and the positions on the sublanes: one token is one sublane row
in each head. There the kernel takes the buffer as it is and moves, a slot,
the one sublane tile of rows (8 of 32 bits, 16 of bf16) that holds the
slot's position: ``H`` tiles in and out, where the scatter loop ran one
iteration a slot.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
# one (H, D, 128) block; the pipeline holds six (cache in and out and the
# update, each double-buffered) inside Mosaic's default 16 MiB of VMEM
_MAX_BLOCK_BYTES = 2 << 20


def tiles(cache_shape, update_shape, dtype):
    """Whether ``cache`` (S, H, C, D) and ``update`` (S, H, T, D) map onto
    the kernel's blocks: one token a slot, a capacity of whole lane tiles,
    a head width under one lane tile that fills whole sublane tiles of the
    dtype (8 rows of 32 bits: 16 for bf16), and a block that fits VMEM; or
    a head width of whole lane tiles (the device then keeps D on the lanes:
    the row path) and a capacity of whole sublane tiles. The gate in
    ops/attention.py asks at trace time."""
    _, H, C, D = cache_shape
    itemsize = jnp.dtype(dtype).itemsize
    if update_shape[2] != 1 or itemsize not in (2, 4):
        return False
    if D % _LANES == 0:
        # D on the lanes: blocks of one sublane tile of positions
        return C % (32 // itemsize) == 0
    return (C % _LANES == 0 and D < _LANES and D % (32 // itemsize) == 0
            and H * D * _LANES * itemsize <= _MAX_BLOCK_BYTES)


def _rotate_lanes(x, shift):
    """``pltpu.roll`` along the lanes by a traced shift. Mosaic rotates
    32-bit lanes only, so a 16-bit array goes through as pairs of sublane
    rows, which is how its tiles hold it anyway."""
    if x.dtype.itemsize == 4:
        return pltpu.roll(x, shift, x.ndim - 1)
    packed = pltpu.roll(pltpu.bitcast(x, jnp.uint32), shift, x.ndim - 1)
    return pltpu.bitcast(packed, x.dtype)


def _kv_write_kernel(idx_ref, upd_ref, cache_ref, out_ref):
    s = pl.program_id(0)
    lane = idx_ref[s] % _LANES
    # slot s's new token lies on lane s % 128 of the update: turn it onto
    # the lane of its position, and let it replace that one column
    upd = _rotate_lanes(upd_ref[...], (lane - s) % _LANES)
    lanes = jax.lax.broadcasted_iota(jnp.int32, cache_ref.shape, 3)
    out_ref[...] = jnp.where(lanes == lane, upd[None], cache_ref[...])


def _kv_write_rows_kernel(idx_ref, upd_ref, cache_ref, out_ref):
    s = pl.program_id(0)
    rows = cache_ref.shape[2]
    at = jax.lax.broadcasted_iota(jnp.int32, cache_ref.shape, 2)
    # the select runs on 32-bit values: a 16-bit tile packs two rows a
    # sublane, and a row mask has no such layout
    old = cache_ref[...].astype(jnp.float32)
    new = jnp.broadcast_to(upd_ref[...].astype(jnp.float32), old.shape)
    out_ref[...] = jnp.where(at == idx_ref[s] % rows, new,
                             old).astype(out_ref.dtype)


def _kv_cache_write_rows(cache, update, index, interpret):
    """The write for head widths of whole lane tiles: grid (slots,), one
    ``(1, H, rows, D)`` block a slot, ``rows`` one sublane tile."""
    S, H, C, D = cache.shape
    rows = 32 // cache.dtype.itemsize

    def block_of(s, idx):
        return (s, 0, idx[s] // rows, 0)

    return pl.pallas_call(
        _kv_write_rows_kernel,
        name="kv_cache_write",
        interpret=interpret,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(S,),
            in_specs=[
                pl.BlockSpec((1, H, 1, D), lambda s, idx: (s, 0, 0, 0)),
                pl.BlockSpec((1, H, rows, D), block_of),
            ],
            out_specs=pl.BlockSpec((1, H, rows, D), block_of),
        ),
        out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        input_output_aliases={2: 0},
    )(index, update, cache)


def kv_cache_write(cache, update, index, interpret=False):
    """``cache`` (S, H, C, D) with ``update`` (S, H, 1, D) written at
    ``index`` (S,) along axis 2, bit-identical to
    ``vmap(dynamic_update_slice)``: a negative start counts from the end,
    then the start clamps into ``[0, C - 1]``.

    One grid step a slot moves the 128-lane block that holds the slot's
    position through VMEM and back; ``index`` is a scalar prefetch, so the
    block's address is known when the step's DMA is issued. The update goes
    in as ``(H, D, S)``, slots on the lanes like the positions they land
    on (as ``(S, H, D, 1)`` every slot's column would be padded to a whole
    lane tile). See :func:`tiles` for what compiles."""
    S, H, C, D = cache.shape
    index = index.astype(jnp.int32)
    index = jnp.clip(jnp.where(index < 0, index + C, index), 0, C - 1)
    if D % _LANES == 0:
        return _kv_cache_write_rows(cache, update, index, interpret)
    update = jnp.transpose(update[:, :, 0, :], (1, 2, 0))
    update = jnp.pad(update, ((0, 0), (0, 0), (0, -S % _LANES)))

    def block_of(s, idx):
        return (s, 0, 0, idx[s] // _LANES)

    out = pl.pallas_call(
        _kv_write_kernel,
        name="kv_cache_write",
        interpret=interpret,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(S,),
            in_specs=[
                pl.BlockSpec((H, D, _LANES),
                             lambda s, idx: (0, 0, s // _LANES)),
                pl.BlockSpec((1, H, D, _LANES), block_of),
            ],
            out_specs=pl.BlockSpec((1, H, D, _LANES), block_of),
        ),
        out_shape=jax.ShapeDtypeStruct((S, H, D, C), cache.dtype),
        # operand 0 is the prefetched index, 1 the update, 2 the cache
        input_output_aliases={2: 0},
    )(index, update, jnp.swapaxes(cache, 2, 3))
    return jnp.swapaxes(out, 2, 3)
