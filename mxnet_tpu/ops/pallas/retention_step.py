"""The decode step of power retention: one token per live slot folded into
the slot's state and read out of it, in place (Pallas).

``ops/retention.py`` says what is computed and how ``phi`` is laid out. The
state of one K/V head, ``S`` (rows x 128, 128) and ``z`` (rows, 128) in
float32 (4.3 MB at 65 rows), is the step's whole cost: it is read once and
written once, and everything else about a token (q, k, v, the gate) is a few
lane tiles. So the kernel is a walk over the live slots' heads, as
``kv_write.py`` walks the live slots' blocks: both buffers stay in HBM, the
scalar core lists the slots that hold a stream, and for each (slot, K/V head)
``_BLOCKS`` blocks of ``S`` come into VMEM through a ring of ``_BUFFERS``
buffers (block i + 1 on its way in and block i - 1 on its way out while block
i is worked on), with the head's ``z`` beside them through a ring of its own.
A slot that holds no stream is never visited: its state is not touched and
costs nothing.

For each of a block's rows d (one (128, 128) tile of ``S``: ``v`` down the
sublanes, ``phi``'s element i along the lanes) the vector unit scales the
tile by the gate and adds ``v phi(k)[d]^T``, ``phi(k)[d]`` being one lane
rotation of k times k, and the matrix unit contracts the new tile with
``phi(q)[d]`` of the group's query heads (eight rows: a group of up to
eight). ``phi`` is built a row at a time from k and q in registers and never
stands in memory.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_GROUP = 8          # query heads of a K/V head, padded to one sublane tile
_BUFFERS = 3
_HI = jax.lax.Precision.HIGHEST


def _blocks(rows):
    """How many blocks a head's ``rows`` tiles are walked in: the most, up
    to 8, that divide them (65 rows: 5 blocks of 13, 0.85 MB each)."""
    return max(n for n in range(1, 9) if rows % n == 0)


def tiles(q_shape, k_shape):
    """Whether q (S, H, 1, D) and k (S, Hkv, 1, D) map onto the kernel: one
    token a slot, a head width of one lane tile, at most eight query heads a
    K/V head. The gate in ops/retention.py asks at trace time."""
    S, H, T, D = q_shape
    Hkv = k_shape[1]
    return (T == 1 and D == _LANES and H % Hkv == 0
            and H // Hkv <= _GROUP)


def _kernel(live_ref, q_ref, k_ref, v_ref, c_ref, s_hbm, z_hbm,
            o_ref, s_out, z_out, sbuf, zbuf, sem, zsem, order, *, eps):
    """live_ref (S,) SMEM; q_ref (S, Hkv, 8, 128), rows the group's query
    heads scaled by D ** -0.5; k_ref, v_ref, c_ref (S, Hkv, 8, 128), eight
    equal rows each; s_hbm, s_out (S, Hkv, rows * 128, 128) and z_hbm, z_out
    (S, Hkv, rows, 128) left in HBM; o_ref (S, Hkv, 8, 128); sbuf (B, tiles
    * 128, 128), zbuf (B, rows, 128)."""
    slots, heads = q_ref.shape[0], q_ref.shape[1]
    rows = zbuf.shape[1]
    per = sbuf.shape[1] // _LANES          # tiles a block
    blocks = rows // per                   # blocks a head
    B = sbuf.shape[0]

    def fill(s, n):
        order[n] = s
        return n + (live_ref[s] != 0).astype(jnp.int32)

    n_live = jax.lax.fori_loop(0, slots, fill, jnp.int32(0))
    units = n_live * heads                 # (slot, head) pairs to visit
    total = units * blocks

    def where(u):
        return order[u // heads], u % heads

    def s_window(ref, i):
        s, h = where(i // blocks)
        at = pl.multiple_of((i % blocks) * (per * _LANES), _LANES)
        return ref.at[s, h, pl.ds(at, per * _LANES), :]

    def z_window(ref, u):
        s, h = where(u)
        return ref.at[s, h]

    def fetch(i):
        return pltpu.make_async_copy(s_window(s_hbm, i), sbuf.at[i % B],
                                     sem.at[0, i % B])

    def store(i):
        return pltpu.make_async_copy(sbuf.at[i % B], s_window(s_out, i),
                                     sem.at[1, i % B])

    def z_fetch(u):
        return pltpu.make_async_copy(z_window(z_hbm, u), zbuf.at[u % B],
                                     zsem.at[0, u % B])

    def z_store(u):
        return pltpu.make_async_copy(zbuf.at[u % B], z_window(z_out, u),
                                     zsem.at[1, u % B])

    # a slot that holds no stream reads zeros
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(total > 0)
    def _():
        fetch(0).start()
        z_fetch(0).start()

    def unit(u, carry):
        s, h = where(u)
        zb = u % B

        # the next head's z comes into the buffer that head u + 1 - B left
        @pl.when(u + 1 >= B)
        def _():
            z_store(u + 1 - B).wait()

        @pl.when(u + 1 < units)
        def _():
            z_fetch(u + 1).start()

        z_fetch(u).wait()
        q8, k8 = q_ref[s, h], k_ref[s, h]
        c1 = c_ref[s, h][0:1, :]
        # v down the sublanes, the same in every lane
        vb = jnp.broadcast_to(v_ref[s, h][0:1, :], (_LANES, _LANES)).T
        num = jnp.zeros((_GROUP, _LANES), jnp.float32)
        den = jnp.zeros((_GROUP, _LANES), jnp.float32)
        for b in range(blocks):
            i = u * blocks + b
            sb = i % B

            @pl.when(i + 1 >= B)
            def _():
                store(i + 1 - B).wait()

            @pl.when(i + 1 < total)
            def _():
                fetch(i + 1).start()

            fetch(i).wait()
            for dd in range(per):
                d = b * per + dd
                w = 1.0 if d in (0, rows - 1) else math.sqrt(2.0)
                turn = lambda a: a * pltpu.roll(a, d, 1) if d else a * a
                pk = w * turn(k8)[0:1, :]
                pq = w * turn(q8)
                at = pl.ds(dd * _LANES, _LANES)
                tile = c1 * sbuf[sb, at, :] + vb * pk
                sbuf[sb, at, :] = tile
                num = num + jax.lax.dot_general(
                    pq, tile, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32, precision=_HI)
                zn = c1 * zbuf[zb, d:d + 1, :] + pk
                zbuf[zb, d:d + 1, :] = zn
                den = den + pq * zn
            store(i).start()
        z_store(u).start()
        o_ref[s, h] = num / (jnp.sum(den, axis=1, keepdims=True) + eps)
        return carry

    jax.lax.fori_loop(0, units, unit, 0)
    for back in range(B - 1, 0, -1):
        @pl.when(total >= back)
        def _():
            store(total - back).wait()

        @pl.when(units >= back)
        def _():
            z_store(units - back).wait()


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def retention_step(q, k, v, log_c, S, z, live=None, *, eps=1e-6,
                   interpret=False):
    """``(o, S, z)`` of ``ops.retention.power_retention`` for one token a
    slot: q (slots, H, 1, 128), k, v (slots, Hkv, 1, 128), log_c (slots,
    Hkv), S (slots, Hkv, rows * 128, 128) and z (slots, Hkv, rows, 128) in
    float32, live (slots,) (every slot, where it is not given). S and z are
    updated where they lie (aliased: donate them). A slot that is not live
    keeps its state bit for bit, costs nothing, and reads zeros."""
    slots, H, _one, D = q.shape
    Hkv, rows = k.shape[1], z.shape[2]
    G = H // Hkv
    live = (jnp.ones((slots,), jnp.int32) if live is None
            else (jnp.asarray(live) != 0).astype(jnp.int32))
    q8 = q[:, :, 0].astype(jnp.float32).reshape(slots, Hkv, G, D) * D ** -0.5
    q8 = jnp.pad(q8, ((0, 0), (0, 0), (0, _GROUP - G), (0, 0)))
    eight = lambda a: jnp.broadcast_to(
        a.astype(jnp.float32)[:, :, None, :], (slots, Hkv, _GROUP, D))
    c8 = jnp.broadcast_to(jnp.exp(log_c.astype(jnp.float32))[:, :, None, None],
                          (slots, Hkv, _GROUP, D))
    small = (slots, Hkv, _GROUP, D)
    whole = pl.BlockSpec(small, lambda i, *_: (0, 0, 0, 0))
    per = rows // _blocks(rows)
    o, S, z = pl.pallas_call(
        functools.partial(_kernel, eps=float(eps)),
        name="retention_step",
        interpret=interpret,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[whole, whole, whole, whole,
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[whole, pl.BlockSpec(memory_space=pl.ANY),
                       pl.BlockSpec(memory_space=pl.ANY)],
            scratch_shapes=[
                pltpu.VMEM((_BUFFERS, per * _LANES, _LANES), jnp.float32),
                pltpu.VMEM((_BUFFERS, rows, _LANES), jnp.float32),
                pltpu.SemaphoreType.DMA((2, _BUFFERS)),
                pltpu.SemaphoreType.DMA((2, _BUFFERS)),
                pltpu.SMEM((slots,), jnp.int32)],
        ),
        out_shape=[jax.ShapeDtypeStruct(small, jnp.float32),
                   jax.ShapeDtypeStruct(S.shape, S.dtype),
                   jax.ShapeDtypeStruct(z.shape, z.dtype)],
        # operand 0 is the prefetched scalar; 5 and 6 are S and z
        input_output_aliases={5: 1, 6: 2},
    )(live, q8, eight(k[:, :, 0]), eight(v[:, :, 0]), c8, S, z)
    o = o[:, :, :G].reshape(slots, H, 1, D)
    return o.astype(q.dtype), S, z
