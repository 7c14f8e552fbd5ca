"""Long-tail mx.nd.contrib ops (ref: src/operator/contrib/*).

The attention ops reproduce upstream's interleaved-projection layout
(contrib/transformer.cc) — gluonnlp's fused-transformer path — as einsums
XLA tiles straight onto the MXU; the rest are small utility/coder ops.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..base import register_op

__all__ = []


@register_op("arange_like", nondiff=True)
def arange_like(data, *, start=0.0, step=1.0, repeat=1, axis=None,
                ctx=None):
    """(ref: contrib/arange_like) arange shaped like data (or its one
    axis) — the shape is STATIC under jit, unlike a host-side arange."""
    def fill(n):
        # `repeat` repeats each VALUE (nd.arange semantics): 0,0,1,1,...
        base = start + step * jnp.arange(-(-n // repeat))
        return jnp.repeat(base, repeat)[:n].astype(data.dtype)

    if axis is None:
        return fill(data.size).reshape(data.shape)
    return fill(data.shape[axis])


@register_op("index_array", nondiff=True)
def index_array(data, *, axes=None):
    """(ref: contrib/index_array.cc) element coordinates of data: shape
    data.shape + (len(axes),). int32 (TPU-native; upstream emits int64)."""
    nd_ = data.ndim
    axes = tuple(range(nd_)) if axes is None else tuple(a % nd_ for a in axes)
    grids = [lax.broadcasted_iota(jnp.int32, data.shape, a) for a in axes]
    return jnp.stack(grids, axis=-1)


@register_op("index_copy", nondiff=True)
def index_copy(old, index, new_tensor):
    """(ref: contrib/index_copy.cc) rows of old at `index` replaced by
    new_tensor's rows."""
    return old.at[index.astype(jnp.int32)].set(new_tensor)


@register_op("allclose", nondiff=True)
def allclose(a, b, *, rtol=1e-5, atol=1e-8, equal_nan=False):
    """(ref: contrib/allclose_op.cc) 1.0/0.0 scalar array."""
    ok = jnp.allclose(a, b, rtol=rtol, atol=atol, equal_nan=equal_nan)
    return ok.astype(jnp.float32).reshape(1)


@register_op("div_sqrt_dim")
def div_sqrt_dim(data):
    """(ref: contrib/transformer.cc DivSqrtDim) data / sqrt(last dim)."""
    return data / jnp.sqrt(jnp.asarray(data.shape[-1], data.dtype))


@jax.custom_vjp
def _grad_multiply(data, scalar):
    return data


def _gm_fwd(data, scalar):
    return data, scalar


def _gm_bwd(scalar, g):
    return g * scalar.astype(g.dtype), None


_grad_multiply.defvjp(_gm_fwd, _gm_bwd)


@register_op("gradientmultiplier")
def gradientmultiplier(data, *, scalar=1.0):
    """(ref: contrib/gradient_multiplier_op.cc) BIT-EXACT identity forward,
    gradient scaled by `scalar` (the GRL trick at scalar < 0). custom_vjp,
    not the ``x*s + stop_gradient(x - x*s)`` algebra: upstream applies the
    scale only in backward, and the algebraic form drifts by a rounding ulp
    (a + (b - a) != b in floating point)."""
    return _grad_multiply(data, jnp.asarray(scalar, data.dtype))


@register_op("quantize_v2", nondiff=True, n_outputs=3)
def quantize_v2(data, *, out_type="int8", min_calib_range=None,
                max_calib_range=None):
    """(ref: quantization/quantize_v2.cc) affine uint8 / symmetric int8
    quantization; calibrated when ranges are given, else from data.
    out_type='auto' picks uint8 for an all-non-negative calibrated range
    (upstream's rule), int8 otherwise."""
    if out_type not in ("auto", "int8", "uint8"):
        raise ValueError("out_type must be auto/int8/uint8, got %r"
                         % (out_type,))
    if min_calib_range is not None and max_calib_range is not None:
        dmin = jnp.asarray(min_calib_range, jnp.float32)
        dmax = jnp.asarray(max_calib_range, jnp.float32)
        if out_type == "auto":
            out_type = "uint8" if min_calib_range >= 0 else "int8"
    else:
        dmin = jnp.min(data).astype(jnp.float32)
        dmax = jnp.max(data).astype(jnp.float32)
        if out_type == "auto":
            out_type = "int8"  # data-dependent sign can't pick a dtype under jit
    if out_type == "uint8":
        scale = 255.0 / jnp.maximum(dmax - dmin, 1e-20)
        q = jnp.clip(jnp.round((data - dmin) * scale), 0, 255).astype(jnp.uint8)
        return q, dmin.reshape(1), dmax.reshape(1)
    absmax = jnp.maximum(jnp.abs(dmin), jnp.abs(dmax))
    scale = 127.0 / jnp.maximum(absmax, 1e-20)
    q = jnp.clip(jnp.round(data * scale), -127, 127).astype(jnp.int8)
    return q, (-absmax).reshape(1), absmax.reshape(1)


@register_op("group_adagrad_update", nondiff=True, n_outputs=2)
def group_adagrad_update(weight, grad, history, *, lr, rescale_grad=1.0,
                         clip_gradient=-1.0, epsilon=1e-5):
    """(ref: contrib/optimizer_op.cc GroupAdagradUpdate) AdaGrad with ONE
    accumulator per row (dim-0 group) — the embedding optimizer."""
    from .legacy_ops import _clip
    g = _clip(grad * rescale_grad, clip_gradient)
    axes = tuple(range(1, g.ndim))
    h = history + jnp.mean(jnp.square(g), axis=axes, keepdims=True) \
        if axes else history + jnp.square(g)
    return weight - lr * g / (jnp.sqrt(h) + epsilon), h


def _corner_to_center(box):
    x0, y0, x1, y1 = jnp.split(box, 4, axis=-1)
    w = x1 - x0
    h = y1 - y0
    return x0 + w * 0.5, y0 + h * 0.5, w, h


@register_op("box_encode", nondiff=True, n_outputs=2)
def box_encode(samples, matches, anchors, refs, means=(0., 0., 0., 0.),
               stds=(0.1, 0.1, 0.2, 0.2)):
    """(ref: contrib/bounding_box.cc BoxEncode) matched gt boxes vs anchors
    -> normalized (dx,dy,dw,dh) targets + positive-sample masks.
    samples (B,N) in {+1,0,-1}; matches (B,N) gt indices; anchors (B,N,4)
    and refs (B,M,4) corner format."""
    matched = jnp.take_along_axis(
        refs, jnp.clip(matches, 0, refs.shape[1] - 1)[..., None]
        .astype(jnp.int32).repeat(4, axis=-1), axis=1)
    ax, ay, aw, ah = _corner_to_center(anchors)
    gx, gy, gw, gh = _corner_to_center(matched)
    t = jnp.concatenate([
        ((gx - ax) / aw - means[0]) / stds[0],
        ((gy - ay) / ah - means[1]) / stds[1],
        (jnp.log(gw / aw) - means[2]) / stds[2],
        (jnp.log(gh / ah) - means[3]) / stds[3]], axis=-1)
    mask = (samples > 0.5)[..., None].astype(t.dtype) * jnp.ones_like(t)
    return t * mask, mask


@register_op("box_decode", nondiff=True)
def box_decode(data, anchors, std0=0.1, std1=0.1, std2=0.2, std3=0.2,
               clip=-1.0, format="corner"):
    """(ref: contrib/bounding_box.cc BoxDecode) inverse of box_encode:
    (dx,dy,dw,dh) deltas + anchors -> corner boxes."""
    if format == "corner":
        ax, ay, aw, ah = _corner_to_center(anchors)
    else:
        ax, ay, aw, ah = jnp.split(anchors, 4, axis=-1)
    dx, dy, dw, dh = jnp.split(data, 4, axis=-1)
    cx = dx * std0 * aw + ax
    cy = dy * std1 * ah + ay
    dw = dw * std2
    dh = dh * std3
    if clip is not None and clip > 0:
        dw = jnp.minimum(dw, clip)
        dh = jnp.minimum(dh, clip)
    w = jnp.exp(dw) * aw
    h = jnp.exp(dh) * ah
    return jnp.concatenate([cx - w * 0.5, cy - h * 0.5,
                            cx + w * 0.5, cy + h * 0.5], axis=-1)


@register_op("contrib_fft", nondiff=True)
def contrib_fft(data, *, compute_size=128):
    """(ref: contrib/fft.cc) FFT along the last axis, output interleaved
    [re0, im0, re1, im1, ...] — last dim doubles."""
    out = jnp.fft.fft(data.astype(jnp.complex64), axis=-1)
    ri = jnp.stack([out.real, out.imag], axis=-1)
    return ri.reshape(data.shape[:-1] + (2 * data.shape[-1],)) \
        .astype(jnp.float32)


@register_op("contrib_ifft", nondiff=True)
def contrib_ifft(data, *, compute_size=128):
    """(ref: contrib/ifft.cc) inverse of contrib_fft: interleaved pairs in,
    real part out (last dim halves). Like upstream (cuFFT), UNNORMALIZED —
    ifft(fft(x)) == n * x."""
    n = data.shape[-1] // 2
    ri = data.reshape(data.shape[:-1] + (n, 2))
    comp = ri[..., 0] + 1j * ri[..., 1]
    return (jnp.fft.ifft(comp, axis=-1).real * n).astype(jnp.float32)


# ---------------------------------------------- interleaved attention ops
# (ref: src/operator/contrib/transformer.cc — gluonnlp's fused self/encdec
# attention path). Layout: projections per head are interleaved along the
# feature dim: qkv (L, B, H*3*D) = per-head [q; k; v].

def _split_qkv(qkv, heads):
    L, B, F = qkv.shape
    d = F // (3 * heads)
    x = qkv.reshape(L, B, heads, 3, d)
    return x[..., 0, :], x[..., 1, :], x[..., 2, :], d


@register_op("interleaved_matmul_selfatt_qk")
def interleaved_matmul_selfatt_qk(queries_keys_values, *, heads):
    q, k, _, d = _split_qkv(queries_keys_values, heads)
    scores = jnp.einsum("lbhd,mbhd->bhlm", q * (1.0 / jnp.sqrt(
        jnp.asarray(d, q.dtype))), k)
    B, H, L, M = scores.shape
    return scores.reshape(B * H, L, M)


@register_op("interleaved_matmul_selfatt_valatt")
def interleaved_matmul_selfatt_valatt(queries_keys_values, attention, *,
                                      heads):
    _, _, v, d = _split_qkv(queries_keys_values, heads)
    L, B = v.shape[0], v.shape[1]
    att = attention.reshape(B, heads, attention.shape[1],
                            attention.shape[2])
    out = jnp.einsum("bhlm,mbhd->lbhd", att, v)
    return out.reshape(L, B, heads * d)


@register_op("interleaved_matmul_encdec_qk")
def interleaved_matmul_encdec_qk(queries, keys_values, *, heads):
    Lq, B, F = queries.shape
    d = F // heads
    q = queries.reshape(Lq, B, heads, d)
    kv = keys_values.reshape(keys_values.shape[0], B, heads, 2, d)
    k = kv[..., 0, :]
    scores = jnp.einsum("lbhd,mbhd->bhlm", q * (1.0 / jnp.sqrt(
        jnp.asarray(d, q.dtype))), k)
    return scores.reshape(B * heads, Lq, keys_values.shape[0])


@register_op("interleaved_matmul_encdec_valatt")
def interleaved_matmul_encdec_valatt(keys_values, attention, *, heads):
    M, B, F = keys_values.shape
    d = F // (2 * heads)
    kv = keys_values.reshape(M, B, heads, 2, d)
    v = kv[..., 1, :]
    att = attention.reshape(B, heads, attention.shape[1],
                            attention.shape[2])
    out = jnp.einsum("bhlm,mbhd->lbhd", att, v)
    return out.reshape(attention.shape[1], B, heads * d)
