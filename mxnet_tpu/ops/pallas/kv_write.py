"""One new token per live slot into the paged K/V buffers, in place (Pallas).

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
relayout of the whole buffer before and after every call. The least a write
can move there is the 128-lane block that holds the slot's position.

For head widths that are whole lane tiles (128, 256) the device keeps D on
the lanes and the positions on the sublanes: one token is one sublane row
in each head. There the kernel takes the buffer as it is and moves, a slot,
the one sublane tile of rows (8 of 32 bits, 16 of bf16) that holds the
slot's position: ``H`` tiles in and out.

Both layouts are one walk: the buffer stays in HBM, the scalar core lists
the slots that hold a stream (``live``, a scalar prefetch like the
positions), and a loop of as many iterations fetches a live slot's block
into VMEM, replaces the one column or row, and sends the block back, with
its own DMA and three buffers, so that a block's way in, the select and the
block's way out overlap. A slot that holds no stream is never visited: a
step with 5 of 32 slots live moves 5 blocks a buffer, where a grid of one
step a slot moved 32 (PERF.md, PR 34: the grid with the empty steps
revisiting a block was measured too, and paid some 0.2 us an empty step).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
# one (H, D, 128) block, of which the walk holds three, and every slot's
# update (the pipeline holds it twice), inside Mosaic's default 16 MiB of VMEM
_MAX_BLOCK_BYTES = 2 << 20
_MAX_UPDATE_BYTES = 4 << 20
_BUFFERS = 3


def tiles(cache_shape, update_shape, dtype):
    """Whether ``cache`` (S, H, C, D) and ``update`` (S, H, T, D) map onto
    the kernel's blocks: one token a slot, a capacity of whole lane tiles,
    a head width under one lane tile that fills whole sublane tiles of the
    dtype (8 rows of 32 bits: 16 for bf16), and a block that fits VMEM; or
    a head width of whole lane tiles (the device then keeps D on the lanes:
    the row path) and a capacity of whole sublane tiles; and every slot's
    update fits VMEM beside the blocks. The gate in ops/attention.py asks
    at trace time."""
    S, H, C, D = cache_shape
    itemsize = jnp.dtype(dtype).itemsize
    if update_shape[2] != 1 or itemsize not in (2, 4):
        return False
    if D % _LANES == 0:
        # D on the lanes: blocks of one sublane tile of positions, and a
        # slot's update one row of such a tile a head
        return (C % (32 // itemsize) == 0
                and H * 32 * D <= _MAX_BLOCK_BYTES
                and S * H * 32 * D <= _MAX_UPDATE_BYTES)
    return (C % _LANES == 0 and D < _LANES and D % (32 // itemsize) == 0
            and H * D * _LANES * itemsize <= _MAX_BLOCK_BYTES
            and H * D * (S + -S % _LANES) * itemsize <= _MAX_UPDATE_BYTES)


def _rotate_lanes(x, shift):
    """``pltpu.roll`` along the lanes by a traced shift. Mosaic rotates
    32-bit lanes only, so a 16-bit array goes through as pairs of sublane
    rows, which is how its tiles hold it anyway."""
    if x.dtype.itemsize == 4:
        return pltpu.roll(x, shift, x.ndim - 1)
    packed = pltpu.roll(pltpu.bitcast(x, jnp.uint32), shift, x.ndim - 1)
    return pltpu.bitcast(packed, x.dtype)


def _walk(idx_ref, live_ref, cache_hbm, out_hbm, buf, sem, order, window,
          put):
    """List the live slots, then visit each once: slot ``s``'s block,
    ``window(ref, s, idx_ref[s])`` of the buffer in HBM, comes into one of
    ``buf``'s buffers, ``put(buffer, s)`` writes the slot's token into it,
    and it goes back where it came from (``out_hbm`` is ``cache_hbm``,
    aliased). Block i + 1 is on its way in and block i - 1 on its way out
    while block i is worked on. Runs on the scalar core."""
    B = buf.shape[0]

    def fill(s, n):
        order[n] = s
        return n + (live_ref[s] != 0).astype(jnp.int32)

    n = jax.lax.fori_loop(0, idx_ref.shape[0], fill, jnp.int32(0))

    def block(ref, i):
        return window(ref, order[i], idx_ref[order[i]])

    def fetch(i):
        return pltpu.make_async_copy(block(cache_hbm, i), buf.at[i % B],
                                     sem.at[0, i % B])

    def store(i):
        return pltpu.make_async_copy(buf.at[i % B], block(out_hbm, i),
                                     sem.at[1, i % B])

    @pl.when(n > 0)
    def _():
        fetch(0).start()

    def visit(i, carry):
        # block i + 1 comes into the buffer block i + 1 - B goes out of
        @pl.when(i + 1 >= B)
        def _():
            store(i + 1 - B).wait()

        @pl.when(i + 1 < n)
        def _():
            fetch(i + 1).start()

        fetch(i).wait()
        put(i % B, order[i])
        store(i).start()
        return carry

    jax.lax.fori_loop(0, n, visit, 0)
    for back in range(B - 1, 0, -1):
        @pl.when(n >= back)
        def _():
            store(n - back).wait()


def _kv_write_kernel(idx_ref, live_ref, upd_ref, cache_hbm, out_hbm, buf,
                     sem, order):
    """upd_ref (H, D, S'), slots on the lanes; cache_hbm, out_hbm
    (S, H, D, C) left in HBM; buf (B, H, D, 128)."""
    def window(ref, s, at):
        return ref.at[s, :, :, pl.ds(
            pl.multiple_of(at // _LANES * _LANES, _LANES), _LANES)]

    def put(b, s):
        lane = idx_ref[s] % _LANES
        tile = pl.ds(pl.multiple_of(s // _LANES * _LANES, _LANES), _LANES)
        # slot s's new token lies on lane s % 128 of the update: turn it
        # onto the lane of its position, and let it replace that one column
        upd = _rotate_lanes(upd_ref[:, :, tile], (lane - s) % _LANES)
        lanes = jax.lax.broadcasted_iota(jnp.int32, upd.shape, 2)
        buf[b] = jnp.where(lanes == lane, upd, buf[b])

    _walk(idx_ref, live_ref, cache_hbm, out_hbm, buf, sem, order, window, put)


def _kv_write_rows_kernel(idx_ref, live_ref, upd_ref, cache_hbm, out_hbm,
                          buf, sem, order):
    """upd_ref (S, H, 1, D); cache_hbm, out_hbm (S, H, C, D) left in HBM;
    buf (B, H, rows, D), ``rows`` one sublane tile."""
    rows = buf.shape[2]

    def window(ref, s, at):
        return ref.at[s, :, pl.ds(pl.multiple_of(at // rows * rows, rows),
                                  rows), :]

    def put(b, s):
        # the select runs on 32-bit values: a 16-bit tile packs two rows a
        # sublane, and a row mask has no such layout
        old = buf[b].astype(jnp.float32)
        at = jax.lax.broadcasted_iota(jnp.int32, old.shape, 1)
        new = jnp.broadcast_to(upd_ref[s].astype(jnp.float32), old.shape)
        buf[b] = jnp.where(at == idx_ref[s] % rows, new,
                           old).astype(buf.dtype)

    _walk(idx_ref, live_ref, cache_hbm, out_hbm, buf, sem, order, window, put)


def _call(kernel, index, live, update, cache, block, interpret):
    """The one ``pallas_call`` of both layouts: the positions and the
    liveness as scalar prefetch, every slot's update whole in VMEM, the
    cache left where it is and aliased to the result, ``_BUFFERS`` buffers
    of one ``block`` and the list of live slots in SMEM."""
    return pl.pallas_call(
        kernel,
        name="kv_cache_write",
        interpret=interpret,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[pl.BlockSpec(update.shape,
                                   lambda i, *_: (0,) * update.ndim),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((_BUFFERS,) + block, cache.dtype),
                            pltpu.SemaphoreType.DMA((2, _BUFFERS)),
                            pltpu.SMEM(index.shape, jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        # operands 0 and 1 are the prefetched scalars, 2 the update, 3 the
        # cache
        input_output_aliases={3: 0},
    )(index, live, update, cache)


# jitted so that the layers of a step that share shapes share one trace and
# one lowering (72 calls in gpt2-large's: 2 s of every start otherwise)
@functools.partial(jax.jit, static_argnames=("interpret",))
def kv_cache_write(cache, update, index, live=None, interpret=False):
    """``cache`` (S, H, C, D) with ``update`` (S, H, 1, D) written at
    ``index`` (S,) along axis 2 in every slot where ``live`` (S,) is not 0
    (in every slot, where it is not given), bit-identical to
    ``vmap(dynamic_update_slice)`` there: a negative start counts from the
    end, then the start clamps into ``[0, C - 1]``. A slot that is not live
    keeps its page as it was and costs nothing: nothing of it is moved.

    A live slot costs the block that holds its position (128 lanes of
    positions; one sublane tile of rows at head widths of whole lane tiles)
    through VMEM and back. In the column layout the update goes in as
    ``(H, D, S)``, slots on the lanes like the positions they land on (as
    ``(S, H, D, 1)`` every slot's column would be padded to a whole lane
    tile). See :func:`tiles` for what compiles."""
    S, H, C, D = cache.shape
    index = index.astype(jnp.int32)
    index = jnp.clip(jnp.where(index < 0, index + C, index), 0, C - 1)
    live = (jnp.ones((S,), jnp.int32) if live is None
            else (live != 0).astype(jnp.int32))
    if D % _LANES == 0:
        return _call(_kv_write_rows_kernel, index, live, update, cache,
                     (H, 32 // cache.dtype.itemsize, D), interpret)
    update = jnp.transpose(update[:, :, 0, :], (1, 2, 0))
    update = jnp.pad(update, ((0, 0), (0, 0), (0, -S % _LANES)))
    out = _call(_kv_write_kernel, index, live, update,
                jnp.swapaxes(cache, 2, 3), (H, D, _LANES), interpret)
    return jnp.swapaxes(out, 2, 3)
