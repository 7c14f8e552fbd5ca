"""Fused LayerNorm Pallas kernel.

MXNet's LayerNorm is a handwritten CUDA kernel (ref: src/operator/nn/
layer_norm.cu). XLA already fuses the naive formulation into ~2 passes; this
kernel does the whole normalize-scale-shift in ONE VMEM-resident pass per row
block with fp32 statistics — saves an HBM round trip for bf16 activations at
transformer widths. Used by ops/functional.py:LayerNorm on TPU for 2-D inputs;
interpret mode covers CPU tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ln_kernel(x_ref, g_ref, b_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    m = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - m
    v = jnp.mean(xc * xc, axis=-1, keepdims=True)
    y = xc * jax.lax.rsqrt(v + eps)
    o_ref[:] = (y * g_ref[:].astype(jnp.float32) +
                b_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def layernorm(x, gamma, beta, eps=1e-5, interpret=False):
    """Differentiable fused LN: pallas forward, analytic XLA backward."""
    return fused_layernorm(x, gamma, beta, eps, interpret=interpret)


def _ln_fwd(x, gamma, beta, eps, interpret):
    return fused_layernorm(x, gamma, beta, eps, interpret=interpret), (x, gamma)


def _ln_bwd(eps, interpret, res, dy):
    x, gamma = res
    xf = x.astype(jnp.float32)
    dyf = dy.astype(jnp.float32)
    gf = gamma.astype(jnp.float32)
    m = jnp.mean(xf, axis=-1, keepdims=True)
    v = jnp.mean(jnp.square(xf - m), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(v + eps)
    xhat = (xf - m) * inv
    dg = jnp.sum(dyf * xhat, axis=0)
    db = jnp.sum(dyf, axis=0)
    t = dyf * gf
    dx = inv * (t - jnp.mean(t, axis=-1, keepdims=True)
                - xhat * jnp.mean(t * xhat, axis=-1, keepdims=True))
    return dx.astype(x.dtype), dg.astype(gamma.dtype), db.astype(gamma.dtype)


layernorm.defvjp(_ln_fwd, _ln_bwd)


def _block_rows(R, block_rows=256):
    br = min(block_rows, R)
    while R % br:
        br //= 2
    return max(br, 1)


def tiles(R, C):
    """Whether an (R, C) input maps onto blocks Mosaic accepts: lanes a
    multiple of 128, and a row block that is a multiple of 8 or the whole
    array. The gate in ops/functional.py asks at trace time."""
    br = _block_rows(R)
    return C % 128 == 0 and (br % 8 == 0 or br == R)


def fused_layernorm(x, gamma, beta, eps=1e-5, block_rows=256, interpret=False):
    """x: (R, C); gamma/beta: (C,). See :func:`tiles` for what compiles."""
    R, C = x.shape
    br = _block_rows(R, block_rows)
    return pl.pallas_call(
        functools.partial(_ln_kernel, eps=eps),
        name="layernorm_fwd",
        interpret=interpret,
        grid=(R // br,),
        in_specs=[
            pl.BlockSpec((br, C), lambda i: (i, 0)),
            pl.BlockSpec((C,), lambda i: (0,)),
            pl.BlockSpec((C,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, C), x.dtype),
    )(x, gamma, beta)
