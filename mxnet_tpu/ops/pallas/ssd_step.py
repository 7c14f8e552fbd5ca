"""The decode step of a Mamba-2 mixer: one token per live slot folded into
the slot's state and read out of it, in place (Pallas).

``ops/ssm.py`` says what is computed. The state of one slot and layer, ``S``
(heads, P, N) in float32 (128 x 64 x 128: 4.19 MB), is the step's whole
cost: it is read once and written once, and everything else about a token
(x, dt, B, C) is a few lane tiles. So the grid is (slots, groups of B/C),
one step a slot and group: the block of ``S`` of the group's heads (16 x 64
x 128: 0.52 MB) comes in, is decayed and added to, goes out to the same
place (the state is aliased to the output), and is contracted with ``C``.
A head's P values stand down the sublanes of its (P, N) tile, N along the
lanes; x, dt x and the output keep the group's heads along the lanes, so
that a head's column broadcasts across the N lanes of its tile.

The grid visits the live slots first (the scalar core is handed their list):
a step past the last live slot names the last live block again, so the
pipeline fetches and writes nothing for it, and a free slot's state is not
touched and costs nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128


def tiles(x_shape, b_shape):
    """Whether x (S, H, P) and B (S, G, N) map onto the kernel: a state
    width of one lane tile, head widths of whole sublane tiles, at most one
    lane tile of heads a group."""
    _S, H, P = x_shape
    G, N = b_shape[1], b_shape[2]
    return (N == _LANES and P % 8 == 0 and H % G == 0
            and H // G <= _LANES)


def _kernel(order_ref, count_ref, xdt_ref, decay_ref, b_ref, c_ref, s_ref,
            y_ref, s_out):
    """xdt_ref (1, 1, P, Hg): ``dt x`` of the group's heads; decay_ref (1,
    1, 1, Hg): ``exp(dt A)``; b_ref, c_ref (1, 1, 1, N); s_ref, s_out (1,
    Hg, P, N); y_ref (1, 1, P, Hg)."""

    @pl.when(pl.program_id(0) < count_ref[0])
    def _():
        xdt = xdt_ref[0, 0]                               # (P, Hg)
        decay = decay_ref[0, 0]                           # (1, Hg)
        b, c = b_ref[0, 0], c_ref[0, 0]                   # (1, N)
        heads = jax.lax.broadcasted_iota(jnp.int32, xdt.shape, 1)
        y = jnp.zeros(xdt.shape, jnp.float32)
        for h in range(xdt.shape[1]):
            new = decay[:, h:h + 1] * s_ref[0, h] + xdt[:, h:h + 1] * b
            s_out[0, h] = new
            y = jnp.where(heads == h,
                          jnp.sum(new * c, axis=1, keepdims=True), y)
        y_ref[0, 0] = y

    # no slot is live: the one block the grid names goes out as it came
    @pl.when(count_ref[0] == 0)
    def _():
        s_out[...] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_step(x, dt, A, B, C, S, live=None, *, interpret=False):
    """``(y, S)`` of ``ops.ssm.ssd_step``: x (slots, H, P), dt (slots, H),
    A (H,), B, C (slots, G, N), S (slots, H, P, N) float32, live (slots,)
    (every slot where not given). S is updated where it lies (aliased:
    donate it). A slot that is not live keeps its state bit for bit, costs
    nothing, and reads zeros."""
    slots, H, P = x.shape
    G, N = B.shape[1], B.shape[2]
    Hg = H // G
    live = (jnp.ones((slots,), jnp.int32) if live is None
            else (jnp.asarray(live) != 0).astype(jnp.int32))
    # the live slots first, then the last live one again (slot 0 where
    # none is live: its block comes in and goes out unchanged)
    rank = jnp.argsort(1 - live, stable=True).astype(jnp.int32)
    count = jnp.sum(live).reshape(1)
    last = rank[jnp.maximum(count[0] - 1, 0)]
    order = jnp.where(jnp.arange(slots) < count[0], rank, last)
    dt = dt.astype(jnp.float32)
    # the group's heads along the lanes
    lanes = lambda a: jnp.swapaxes(a.reshape(slots, G, Hg, -1), 2, 3)
    xdt = lanes(x.astype(jnp.float32) * dt[..., None])        # (S,G,P,Hg)
    decay = lanes(jnp.exp(dt * A.astype(jnp.float32))[..., None])
    row = lambda a: a.astype(jnp.float32).reshape(slots, G, 1, N)

    def at(s, g, order_ref, count_ref):
        # a step past the live slots names the last live block
        on = s < count_ref[0]
        return (order_ref[s], jnp.where(on, g, G - 1))

    small = lambda w: pl.BlockSpec(
        (1, 1, w[0], w[1]), lambda s, g, o, n: at(s, g, o, n) + (0, 0))
    state = pl.BlockSpec(
        (1, Hg, P, N), lambda s, g, o, n: (at(s, g, o, n)[0],
                                           at(s, g, o, n)[1], 0, 0))
    y, S = pl.pallas_call(
        _kernel,
        name="ssd_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(slots, G),
            in_specs=[small((P, Hg)), small((1, Hg)), small((1, N)),
                      small((1, N)), state],
            out_specs=[small((P, Hg)), state],
        ),
        out_shape=[jax.ShapeDtypeStruct((slots, G, P, Hg), jnp.float32),
                   jax.ShapeDtypeStruct(S.shape, S.dtype)],
        # operands 0 and 1 are the prefetched scalars; 6 is S
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(order, count, xdt, decay, row(B), row(C), S)
    y = jnp.swapaxes(y, 2, 3).reshape(slots, H, P)
    return jnp.where(live[:, None, None] > 0, y, 0.0), S
