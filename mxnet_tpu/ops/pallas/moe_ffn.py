"""Grouped gated FFN over tokens sorted by expert (Pallas): the expert
layer's matmuls, with work proportional to the tokens routed to the experts
held and not to tokens x experts held.

``x`` (M, d) holds the routed rows in tiles of ``tm``: every tile belongs to
one expert (``ops/moe.py`` pads each expert's run of rows to whole tiles),
named by the scalar-prefetched ``tile_expert``. The grid is (tiles, blocks of
the inner width): a step loads one ``(bf, d)`` block of that expert's gate,
up and down matrices, computes ``silu(x Wg^T) * (x Wu^T)`` for the block and
adds its product with the down block into a float32 accumulator that lives
across the inner blocks. All three matrices are stored ``(experts, f, d)``,
so a block is ``bf`` whole rows: contiguous in HBM, and read once a tile.
Tiles past the last routed one (the static grid is the worst case) name the
last real tile's expert and its last block: the pipeline fetches nothing for
them, and they write zeros.

In a decode step a tile is 16 rows and the kernel's time is the bytes of the
experts the live tokens chose; in a prefill a tile is 256 rows and an
expert's matrices stream through once a tile.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# three weight blocks, double-buffered, beside the row tile, the output tile
# and the accumulator: 24 MiB of weights at (512, 4096) bf16
_VMEM_LIMIT = 96 << 20


def _block_f(f, d, itemsize, prefer=512):
    """Rows of the inner width a step loads: ``prefer`` where it divides
    ``f`` and three double-buffered blocks stay within a third of the
    limit."""
    bf = min(prefer, f)
    while f % bf or 6 * bf * d * itemsize > _VMEM_LIMIT // 2:
        bf //= 2
    return max(bf, 1)


def tiles(rows, d, f, dtype):
    """Whether row tiles of ``rows`` and blocks of ``(bf, d)`` map onto
    Mosaic's tiling: whole sublane tiles of rows, lanes of 128."""
    itemsize = jnp.dtype(dtype).itemsize
    return (itemsize in (2, 4) and rows % (32 // itemsize) == 0
            and d % 128 == 0 and _block_f(f, d, itemsize) % 128 == 0)


def _moe_ffn_kernel(te_ref, tv_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref,
                    acc_ref):
    t, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(tv_ref[t] > 0)
    def _compute():
        x = x_ref[...]                                       # (tm, d)
        nt = (((1,), (1,)), ((), ()))
        g = jax.lax.dot_general(x, wg_ref[0], nt,
                                preferred_element_type=jnp.float32)
        u = jax.lax.dot_general(x, wu_ref[0], nt,
                                preferred_element_type=jnp.float32)
        a = (g * jax.nn.sigmoid(g) * u).astype(x.dtype)      # (tm, bf)
        acc_ref[...] += jnp.dot(a, wd_ref[0],
                                preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def moe_ffn(x, tile_expert, tile_valid, w_gate, w_up, w_down, tm,
            interpret=False):
    """``x`` (M, d), ``M % tm == 0``; ``tile_expert``, ``tile_valid``
    (M // tm,) int32; ``w_gate``, ``w_up``, ``w_down`` (experts, f, d).
    Row r of the result is ``(silu(x[r] Wg[e]^T) * (x[r] Wu[e]^T)) Wd[e]``
    with ``e = tile_expert[r // tm]``, zero in tiles that are not valid."""
    M, d = x.shape
    f = w_gate.shape[1]
    bf = _block_f(f, d, x.dtype.itemsize)
    nj = f // bf

    def w_block(t, j, te, tv):
        return (te[t], jnp.where(tv[t] > 0, j, nj - 1), 0)

    return pl.pallas_call(
        _moe_ffn_kernel,
        name="moe_ffn",
        interpret=interpret,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(M // tm, nj),
            in_specs=[
                pl.BlockSpec((tm, d), lambda t, j, te, tv: (t, 0)),
                pl.BlockSpec((1, bf, d), w_block),
                pl.BlockSpec((1, bf, d), w_block),
                pl.BlockSpec((1, bf, d), w_block),
            ],
            out_specs=pl.BlockSpec((tm, d), lambda t, j, te, tv: (t, 0)),
            scratch_shapes=[pltpu.VMEM((tm, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
    )(tile_expert, tile_valid, x, w_gate, w_up, w_down)
