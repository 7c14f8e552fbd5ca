"""Grouped expert FFN over tokens sorted by expert (Pallas): the expert
layer's matmuls, with work proportional to the tokens routed to the experts
held and not to tokens x experts held. Two activations: the gated SwiGLU
``silu(x Wg^T) * (x Wu^T)`` (three matrices an expert) and the non-gated
``relu(x Wu^T)^2`` (two: ``act="relu2"``, no gate).

``x`` (M, d) holds the routed rows in tiles of ``tm``: every tile belongs to
one expert (``ops/moe.py`` pads each expert's run of rows to whole tiles),
named by the scalar-prefetched ``tile_expert``. The grid is (tiles, blocks of
the inner width): a step loads one ``(bf, d)`` block of that expert's gate,
up and down matrices, computes ``silu(x Wg^T) * (x Wu^T)`` for the block and
adds its product with the down block into a float32 accumulator that lives
across the inner blocks. All three matrices are stored ``(experts, f, d)``,
so a block is ``bf`` whole rows: contiguous in HBM, and read once a tile.
Where an expert's matrices fit the VMEM budget whole, the block is the whole
inner width (one step a tile). Tiles past the last routed one (the static
grid is the worst case) name the last real tile's expert and its last block:
the pipeline fetches nothing for them, and they write zeros; each still
costs its steps, which is why a tile walks as few blocks as fit.

In a decode step a tile is 16 rows and the kernel's time is the bytes of the
experts the live tokens chose; in a prefill a tile is 256 rows and an
expert's matrices stream through once a tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# three weight blocks, double-buffered, beside the row tile, the output tile
# and the accumulator: 24 MiB of weights at (512, 4096) bf16
_VMEM_LIMIT = 96 << 20


def _block_f(f, d, itemsize, mats=3, prefer=512):
    """Rows of the inner width a step loads, where ``mats`` double-buffered
    blocks stay within half the limit: the whole of an ``f`` of whole lane
    tiles where that fits (2688 x 1024 relu^2: one block), else the largest
    multiple of 128 up to ``prefer`` that divides ``f`` (4096 x 4096 and
    2048 x 7168 SwiGLU: 512); an ``f`` that no multiple of 128 divides
    halves from ``prefer`` instead."""
    fits = lambda bf: 2 * mats * bf * d * itemsize <= _VMEM_LIMIT // 2
    if f % 128 == 0 and fits(f):
        return f
    whole = [bf for bf in range(128, min(prefer, f) + 1, 128)
             if f % bf == 0 and fits(bf)]
    if whole:
        return whole[-1]
    bf = min(prefer, f)
    while f % bf or not fits(bf):
        bf //= 2
    return max(bf, 1)


def _mats(act):
    return 3 if act == "swiglu" else 2


def tiles(rows, d, f, dtype, act="swiglu"):
    """Whether row tiles of ``rows`` and blocks of ``(bf, d)`` map onto
    Mosaic's tiling: whole sublane tiles of rows, lanes of 128."""
    itemsize = jnp.dtype(dtype).itemsize
    return (itemsize in (2, 4) and rows % (32 // itemsize) == 0
            and d % 128 == 0
            and _block_f(f, d, itemsize, _mats(act)) % 128 == 0)


def _count_grid(tiles, steps):
    """How far the whole-width block engages, counted once a call is traced:
    the grid's row tiles and its steps, whose ratio is the blocks a tile
    walks. Lazy import: the kernels stay importable before the
    observability package is."""
    from ...observability import registry

    registry.counter("moe_ffn_grid_tiles").inc(tiles)
    registry.counter("moe_ffn_grid_steps").inc(steps)


def _moe_ffn_kernel(act, te_ref, tv_ref, x_ref, *refs):
    # refs: the expert's input matrices (gate and up, or up alone), its down
    # matrix, the output and the accumulator
    *w_refs, wd_ref, o_ref, acc_ref = refs
    t, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(tv_ref[t] > 0)
    def _compute():
        x = x_ref[...]                                       # (tm, d)
        nt = (((1,), (1,)), ((), ()))
        h = [jax.lax.dot_general(x, w_ref[0], nt,
                                 preferred_element_type=jnp.float32)
             for w_ref in w_refs]
        if act == "swiglu":
            g, u = h
            a = g * jax.nn.sigmoid(g) * u
        else:
            r = jnp.maximum(h[0], 0.0)
            a = r * r
        a = a.astype(x.dtype)                                # (tm, bf)
        acc_ref[...] += jnp.dot(a, wd_ref[0],
                                preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def moe_ffn(x, tile_expert, tile_valid, w_gate, w_up, w_down, tm,
            interpret=False, act="swiglu"):
    """``x`` (M, d), ``M % tm == 0``; ``tile_expert``, ``tile_valid``
    (M // tm,) int32; ``w_gate`` (None where ``act`` is "relu2"), ``w_up``,
    ``w_down`` (experts, f, d). Row r of the result is ``(silu(x[r]
    Wg[e]^T) * (x[r] Wu[e]^T)) Wd[e]``, or ``relu(x[r] Wu[e]^T)^2 Wd[e]``,
    with ``e = tile_expert[r // tm]``, zero in tiles that are not valid."""
    M, d = x.shape
    f = w_up.shape[1]
    bf = _block_f(f, d, x.dtype.itemsize, _mats(act))
    nj = f // bf
    _count_grid(M // tm, M // tm * nj)

    def w_block(t, j, te, tv):
        return (te[t], jnp.where(tv[t] > 0, j, nj - 1), 0)

    mats = (w_gate, w_up, w_down) if act == "swiglu" else (w_up, w_down)
    return pl.pallas_call(
        functools.partial(_moe_ffn_kernel, act),
        name="moe_ffn",
        interpret=interpret,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(M // tm, nj),
            in_specs=[pl.BlockSpec((tm, d), lambda t, j, te, tv: (t, 0))]
            + [pl.BlockSpec((1, bf, d), w_block) for _ in mats],
            out_specs=pl.BlockSpec((tm, d), lambda t, j, te, tv: (t, 0)),
            scratch_shapes=[pltpu.VMEM((tm, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
    )(tile_expert, tile_valid, x, *mats)
