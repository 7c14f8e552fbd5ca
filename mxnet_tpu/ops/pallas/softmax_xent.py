"""Fused softmax cross-entropy Pallas kernel.

MXNet fuses softmax+grad in SoftmaxOutput's CUDA kernels (ref:
src/operator/softmax_output.cu); for the LM/BERT loss the hot pattern is
logits (N, V≈30k) → per-row NLL. Done naively that is three HBM sweeps of the
logits (max, sum-exp, gather). This kernel produces loss AND logsumexp in one
VMEM-resident pass per row block; the backward kernel forms
``(softmax − onehot)·dy`` in one more pass, reusing the saved lse instead of
recomputing the reduction. fp32 math inside regardless of logits dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _fwd_kernel(x_ref, l_ref, loss_ref, lse_ref):
    x = x_ref[:].astype(jnp.float32)            # (br, V)
    lab = l_ref[:]                              # (br, 1) int32
    m = jnp.max(x, axis=-1, keepdims=True)
    s = jnp.sum(jnp.exp(x - m), axis=-1, keepdims=True)
    lse = jnp.log(s) + m                        # (br, 1)
    cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    picked = jnp.sum(jnp.where(cols == lab, x, 0.0), axis=-1, keepdims=True)
    loss_ref[:] = (lse - picked).astype(loss_ref.dtype)
    lse_ref[:] = lse.astype(lse_ref.dtype)


def _bwd_kernel(x_ref, l_ref, lse_ref, dy_ref, dx_ref):
    x = x_ref[:].astype(jnp.float32)
    lab = l_ref[:]
    lse = lse_ref[:]
    dy = dy_ref[:]
    p = jnp.exp(x - lse)                        # softmax via saved lse
    cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    onehot = (cols == lab).astype(jnp.float32)
    dx_ref[:] = ((p - onehot) * dy).astype(dx_ref.dtype)


def _block_rows(R, V, want=128, vmem_budget=2 << 20):
    """Rows per block, capped so one fp32 logits block stays within a VMEM
    budget (double-buffered pipelining means the real footprint is ~2x) —
    at V≈30k that is ~16 rows, not 128."""
    cap = max(1, vmem_budget // (V * 4))
    br = min(want, cap, R)
    while R % br:
        br -= 1
    return max(br, 1)


def tiles(R, V):
    """Whether (R, V) logits map onto blocks Mosaic accepts: a row block
    that is a multiple of 8 or the whole array (V is lane-aligned by
    :func:`_pad_lanes`). The gate in ops/functional.py asks at trace
    time."""
    br = _block_rows(R, V + (-V) % 128)
    return br % 8 == 0 or br == R


_PAD_NEG = -1e30  # finite: exp(_PAD_NEG - m) underflows to 0, no inf-inf NaN


def _pad_lanes(logits):
    """Lane-align V to a multiple of 128 by padding with a large negative
    constant. Mosaic's guarantees are simplest (and fastest) for aligned
    lane dims, and real vocabularies (BERT 30522, GPT-2 50257) are NOT
    aligned — padding costs one fused pad (+<0.3% lanes) and keeps the
    kernel itself aligned by construction. Padded lanes contribute
    exp(-1e30 - m) = 0 to the row lse and can never be a label."""
    V = logits.shape[-1]
    pad = (-V) % 128
    if pad:
        logits = jnp.pad(logits, ((0, 0), (0, pad)), constant_values=_PAD_NEG)
    return logits, V


def _run_fwd(logits, labels, interpret=False):
    R, V = logits.shape
    br = _block_rows(R, V)
    lab2 = labels.astype(jnp.int32).reshape(R, 1)
    loss, lse = pl.pallas_call(
        _fwd_kernel,
        name="softmax_xent_fwd",
        interpret=interpret,
        grid=(R // br,),
        in_specs=[pl.BlockSpec((br, V), lambda i: (i, 0)),
                  pl.BlockSpec((br, 1), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((br, 1), lambda i: (i, 0)),
                   pl.BlockSpec((br, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((R, 1), jnp.float32),
                   jax.ShapeDtypeStruct((R, 1), jnp.float32)],
    )(logits, lab2)
    return loss[:, 0], lse


def _run_bwd(logits, labels, lse, dy, interpret=False):
    R, V = logits.shape
    br = _block_rows(R, V)
    lab2 = labels.astype(jnp.int32).reshape(R, 1)
    dy2 = dy.astype(jnp.float32).reshape(R, 1)
    return pl.pallas_call(
        _bwd_kernel,
        name="softmax_xent_bwd",
        interpret=interpret,
        grid=(R // br,),
        in_specs=[pl.BlockSpec((br, V), lambda i: (i, 0)),
                  pl.BlockSpec((br, 1), lambda i: (i, 0)),
                  pl.BlockSpec((br, 1), lambda i: (i, 0)),
                  pl.BlockSpec((br, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, V), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, V), logits.dtype),
    )(logits, lab2, lse, dy2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def softmax_xent(logits, labels, interpret=False):
    """Per-row NLL of int labels under softmax(logits). logits (N, V) any
    float dtype and ANY V (lane-aligned internally), labels (N,) int.
    Returns (N,) fp32."""
    padded, _ = _pad_lanes(logits)
    return _run_fwd(padded, labels, interpret)[0]


def _sx_fwd(logits, labels, interpret):
    padded, v_real = _pad_lanes(logits)
    loss, lse = _run_fwd(padded, labels, interpret)
    return loss, (padded, v_real, labels, lse)


def _sx_bwd(interpret, res, dy):
    padded, v_real, labels, lse = res
    dx = _run_bwd(padded, labels, lse, dy, interpret)
    # padded lanes carry p·dy (p=0 there), so the slice drops exact zeros
    return dx[:, :v_real], None


softmax_xent.defvjp(_sx_fwd, _sx_bwd)
