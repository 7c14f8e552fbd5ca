"""Pure functional op library (the kernel registry).

TPU-native replacement for MXNet's operator library (ref: src/operator/tensor/*,
src/operator/nn/*, registered via NNVM_REGISTER_OP). Every op here is a pure
function over ``jax.Array`` built on jax.numpy / lax so XLA can fuse and tile it
onto the MXU/VPU; the imperative ``nd`` namespace and the traced (hybridize)
path are both generated from this registry (see mxnet_tpu/ndarray.py and
mxnet_tpu/_trace.py). Static configuration is keyword-only; positional args are
traced arrays.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.scipy import special as jsp

from ..base import is_tpu_backend, register_op, resolve_dtype
from .pallas import layernorm as _ln
from .pallas import softmax_xent as _sx
from .pallas import under_mesh

# ---------------------------------------------------------------- unary


def _u(name, f, nondiff=False):
    register_op(name, nondiff=nondiff)(f)
    return f


abs = _u("abs", lambda x: jnp.abs(x))
sign = _u("sign", jnp.sign)
ceil = _u("ceil", jnp.ceil, nondiff=True)
floor = _u("floor", jnp.floor, nondiff=True)
trunc = _u("trunc", jnp.trunc, nondiff=True)
round = _u("round", jnp.round, nondiff=True)
rint = _u("rint", jnp.rint, nondiff=True)
fix = _u("fix", jnp.trunc, nondiff=True)  # alias: round toward zero
exp = _u("exp", jnp.exp)
expm1 = _u("expm1", jnp.expm1)
log = _u("log", jnp.log)
log1p = _u("log1p", jnp.log1p)
log2 = _u("log2", jnp.log2)
log10 = _u("log10", jnp.log10)
sqrt = _u("sqrt", jnp.sqrt)
rsqrt = _u("rsqrt", lambda x: lax.rsqrt(x))
cbrt = _u("cbrt", jnp.cbrt)
rcbrt = _u("rcbrt", lambda x: 1.0 / jnp.cbrt(x))
square = _u("square", jnp.square)
reciprocal = _u("reciprocal", lambda x: 1.0 / x)
negative = _u("negative", jnp.negative)
sin = _u("sin", jnp.sin)
cos = _u("cos", jnp.cos)
tan = _u("tan", jnp.tan)
arcsin = _u("arcsin", jnp.arcsin)
arccos = _u("arccos", jnp.arccos)
arctan = _u("arctan", jnp.arctan)
sinh = _u("sinh", jnp.sinh)
cosh = _u("cosh", jnp.cosh)
tanh = _u("tanh", jnp.tanh)
arcsinh = _u("arcsinh", jnp.arcsinh)
arccosh = _u("arccosh", jnp.arccosh)
arctanh = _u("arctanh", jnp.arctanh)
degrees = _u("degrees", jnp.degrees)
radians = _u("radians", jnp.radians)
erf = _u("erf", jsp.erf)
erfinv = _u("erfinv", jsp.erfinv)
gammaln = _u("gammaln", jsp.gammaln)
gamma = _u("gamma", lambda x: jnp.exp(jsp.gammaln(x)))
digamma = _u("digamma", jsp.digamma)


@register_op("polygamma")
def polygamma(n, x):
    """n-th derivative of digamma at x (ref: special_functions-inl.h); n is a
    static non-negative int order, x the array argument."""
    return jsp.polygamma(jnp.asarray(n), x)
sigmoid = _u("sigmoid", jax.nn.sigmoid)
softsign = _u("softsign", jax.nn.soft_sign)
relu = _u("relu", jax.nn.relu)
logical_not = _u("logical_not", jnp.logical_not, nondiff=True)
isnan = _u("isnan", jnp.isnan, nondiff=True)
isinf = _u("isinf", jnp.isinf, nondiff=True)
isfinite = _u("isfinite", jnp.isfinite, nondiff=True)


@register_op("softrelu")
def softrelu(x):
    return jax.nn.softplus(x)


@register_op("clip")
def clip(x, a_min, a_max):
    # positional a_min/a_max: upstream's `mx.nd.clip(data, -1, 1)` form
    # (ref: src/operator/tensor/matrix_op.cc clip)
    return jnp.clip(x, a_min, a_max)


@register_op("cast", nondiff=False)
def cast(x, *, dtype):
    return x.astype(resolve_dtype(dtype))


# ---------------------------------------------------------------- binary

add = _u("add", jnp.add)
subtract = _u("subtract", jnp.subtract)
multiply = _u("multiply", jnp.multiply)
divide = _u("divide", jnp.divide)
mod = _u("mod", jnp.mod)
power = _u("power", jnp.power)
maximum = _u("maximum", jnp.maximum)
minimum = _u("minimum", jnp.minimum)
hypot = _u("hypot", jnp.hypot)
arctan2 = _u("arctan2", jnp.arctan2)
equal = _u("equal", lambda a, b: (a == b).astype(jnp.result_type(a)), nondiff=True)
not_equal = _u("not_equal", lambda a, b: (a != b).astype(jnp.result_type(a)), nondiff=True)
greater = _u("greater", lambda a, b: (a > b).astype(jnp.result_type(a)), nondiff=True)
greater_equal = _u("greater_equal", lambda a, b: (a >= b).astype(jnp.result_type(a)), nondiff=True)
lesser = _u("lesser", lambda a, b: (a < b).astype(jnp.result_type(a)), nondiff=True)
lesser_equal = _u("lesser_equal", lambda a, b: (a <= b).astype(jnp.result_type(a)), nondiff=True)
logical_and = _u("logical_and", lambda a, b: jnp.logical_and(a, b).astype(jnp.float32), nondiff=True)
logical_or = _u("logical_or", lambda a, b: jnp.logical_or(a, b).astype(jnp.float32), nondiff=True)
logical_xor = _u("logical_xor", lambda a, b: jnp.logical_xor(a, b).astype(jnp.float32), nondiff=True)

# MXNet broadcast_* aliases (broadcasting is implicit in jnp)
for _n, _f in [
    ("broadcast_add", jnp.add), ("broadcast_sub", jnp.subtract),
    ("broadcast_mul", jnp.multiply), ("broadcast_div", jnp.divide),
    ("broadcast_mod", jnp.mod), ("broadcast_power", jnp.power),
    ("broadcast_maximum", jnp.maximum), ("broadcast_minimum", jnp.minimum),
    ("broadcast_hypot", jnp.hypot),
]:
    register_op(_n)(_f)

for _n, _f in [
    ("broadcast_equal", equal), ("broadcast_not_equal", not_equal),
    ("broadcast_greater", greater), ("broadcast_greater_equal", greater_equal),
    ("broadcast_lesser", lesser), ("broadcast_lesser_equal", lesser_equal),
    ("broadcast_logical_and", logical_and), ("broadcast_logical_or", logical_or),
    ("broadcast_logical_xor", logical_xor),
]:
    register_op(_n, nondiff=True)(_f)


@register_op("where")
def where(condition, x, y):
    return jnp.where(condition.astype(bool), x, y)


@register_op("smooth_l1")
def smooth_l1(x, *, scalar=1.0):
    s2 = scalar * scalar
    return jnp.where(jnp.abs(x) < 1.0 / s2, 0.5 * s2 * x * x, jnp.abs(x) - 0.5 / s2)


# ---------------------------------------------------------------- reductions


@register_op("sum")
def sum(x, *, axis=None, keepdims=False):
    return jnp.sum(x, axis=axis, keepdims=keepdims)


@register_op("nansum")
def nansum(x, *, axis=None, keepdims=False):
    return jnp.nansum(x, axis=axis, keepdims=keepdims)


@register_op("mean")
def mean(x, *, axis=None, keepdims=False):
    return jnp.mean(x, axis=axis, keepdims=keepdims)


@register_op("prod")
def prod(x, *, axis=None, keepdims=False):
    return jnp.prod(x, axis=axis, keepdims=keepdims)


@register_op("nanprod")
def nanprod(x, *, axis=None, keepdims=False):
    return jnp.nanprod(x, axis=axis, keepdims=keepdims)


@register_op("max")
def max(x, *, axis=None, keepdims=False):
    return jnp.max(x, axis=axis, keepdims=keepdims)


@register_op("min")
def min(x, *, axis=None, keepdims=False):
    return jnp.min(x, axis=axis, keepdims=keepdims)


@register_op("var")
def var(x, *, axis=None, keepdims=False):
    return jnp.var(x, axis=axis, keepdims=keepdims)


@register_op("std")
def std(x, *, axis=None, keepdims=False):
    return jnp.std(x, axis=axis, keepdims=keepdims)


@register_op("argmax", nondiff=True)
def argmax(x, *, axis=None, keepdims=False):
    r = jnp.argmax(x, axis=axis)
    if keepdims and axis is not None:
        r = jnp.expand_dims(r, axis)
    return r.astype(jnp.float32)  # MXNet returns float indices


@register_op("argmin", nondiff=True)
def argmin(x, *, axis=None, keepdims=False):
    r = jnp.argmin(x, axis=axis)
    if keepdims and axis is not None:
        r = jnp.expand_dims(r, axis)
    return r.astype(jnp.float32)


@register_op("norm")
def norm(x, *, ord=2, axis=None, keepdims=False):
    if ord == 2:
        return jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=keepdims))
    if ord == 1:
        return jnp.sum(jnp.abs(x), axis=axis, keepdims=keepdims)
    raise ValueError("norm only supports ord 1/2 (ref: src/operator/tensor/broadcast_reduce_op_value.cc)")


@register_op("cumsum")
def cumsum(x, *, axis=None, dtype=None):
    return jnp.cumsum(x, axis=axis, dtype=resolve_dtype(dtype))


@register_op("cumprod")
def cumprod(x, *, axis=None, dtype=None):
    """(ref: np_cumprod — upstream's mx.np surface; flat nd alias here)."""
    return jnp.cumprod(x, axis=axis, dtype=resolve_dtype(dtype))


@register_op("L2Normalization")
def L2Normalization(x, *, eps=1e-10, mode="instance"):
    if mode == "instance":
        ax = tuple(range(1, x.ndim))
    elif mode == "channel":
        ax = (1,)
    else:  # spatial
        ax = tuple(range(2, x.ndim))
    return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=ax, keepdims=True) + eps)


@register_op("topk", nondiff=True)
def topk(x, *, axis=-1, k=1, ret_typ="indices", is_ascend=False, dtype="float32"):
    xm = jnp.moveaxis(x, axis, -1)
    vals, idx = lax.top_k(-xm if is_ascend else xm, k)
    if is_ascend:
        vals = -vals
    vals = jnp.moveaxis(vals, -1, axis)
    idx = jnp.moveaxis(idx, -1, axis).astype(resolve_dtype(dtype))
    if ret_typ == "value":
        return vals
    if ret_typ == "both":
        return vals, idx
    return idx


@register_op("sort")
def sort(x, *, axis=-1, is_ascend=True):
    s = jnp.sort(x, axis=axis)
    return s if is_ascend else jnp.flip(s, axis=axis)


@register_op("argsort", nondiff=True)
def argsort(x, *, axis=-1, is_ascend=True, dtype="float32"):
    i = jnp.argsort(x, axis=axis)
    if not is_ascend:
        i = jnp.flip(i, axis=axis)
    return i.astype(resolve_dtype(dtype))


# ---------------------------------------------------------------- shape ops


@register_op("reshape")
def reshape(x, *, shape):
    # MXNet magic values: 0 copy dim, -1 infer (ref: src/operator/tensor/matrix_op.cc)
    out = []
    for i, s in enumerate(shape):
        out.append(x.shape[i] if s == 0 else s)
    return jnp.reshape(x, tuple(out))


@register_op("flatten")
def flatten(x):
    return jnp.reshape(x, (x.shape[0], -1))


@register_op("transpose")
def transpose(x, *, axes=None):
    return jnp.transpose(x, axes=axes)


@register_op("swapaxes")
def swapaxes(x, *, dim1=0, dim2=0):
    return jnp.swapaxes(x, dim1, dim2)


@register_op("expand_dims")
def expand_dims(x, *, axis):
    return jnp.expand_dims(x, axis)


@register_op("squeeze")
def squeeze(x, *, axis=None):
    return jnp.squeeze(x, axis=axis)


@register_op("broadcast_to")
def broadcast_to(x, *, shape):
    shape = tuple(x.shape[i] if s == 0 else s for i, s in enumerate(shape))
    return jnp.broadcast_to(x, shape)


@register_op("broadcast_like")
def broadcast_like(x, y):
    return jnp.broadcast_to(x, y.shape)


@register_op("tile")
def tile(x, *, reps):
    return jnp.tile(x, reps)


@register_op("repeat")
def repeat(x, *, repeats, axis=None):
    return jnp.repeat(x, repeats, axis=axis)


@register_op("pad")
def pad(x, *, mode="constant", pad_width=None, constant_value=0.0):
    pw = [(pad_width[2 * i], pad_width[2 * i + 1]) for i in range(len(pad_width) // 2)]
    if mode == "constant":
        return jnp.pad(x, pw, mode="constant", constant_values=constant_value)
    if mode == "edge":
        return jnp.pad(x, pw, mode="edge")
    return jnp.pad(x, pw, mode="reflect")


@register_op("flip")
def flip(x, *, axis):
    return jnp.flip(x, axis=axis)


reverse = register_op("reverse")(lambda x, *, axis: jnp.flip(x, axis=axis))


@register_op("concat")
def concat(*xs, dim=1):
    return jnp.concatenate(xs, axis=dim)


@register_op("stack")
def stack(*xs, axis=0):
    return jnp.stack(xs, axis=axis)


@register_op("split")
def split(x, *, num_outputs, axis=1, squeeze_axis=False):
    parts = jnp.split(x, num_outputs, axis=axis)
    if squeeze_axis:
        parts = [jnp.squeeze(p, axis=axis) for p in parts]
    return tuple(parts)


@register_op("slice")
def slice(x, *, begin, end, step=None):
    import builtins

    step = step or [None] * len(begin)
    sl = tuple(builtins.slice(b, e, s) for b, e, s in zip(begin, end, step))
    return x[sl]


@register_op("slice_axis")
def slice_axis(x, *, axis, begin, end):
    import builtins

    idx = [builtins.slice(None)] * x.ndim
    if end is None:
        end = x.shape[axis]
    idx[axis] = builtins.slice(begin, end)
    return x[tuple(idx)]


@register_op("slice_like")
def slice_like(x, y, *, axes=None):
    import builtins

    idx = [builtins.slice(None)] * x.ndim
    axes = axes if axes is not None else range(x.ndim)
    for ax in axes:
        idx[ax] = builtins.slice(0, y.shape[ax])
    return x[tuple(idx)]


@register_op("take")
def take(x, indices, *, axis=0, mode="clip"):
    return jnp.take(x, indices.astype(jnp.int32), axis=axis, mode=mode)


@register_op("pick")
def pick(x, index, *, axis=-1, keepdims=False):
    idx = jnp.expand_dims(index.astype(jnp.int32), axis)
    out = jnp.take_along_axis(x, idx, axis=axis)
    return out if keepdims else jnp.squeeze(out, axis=axis)


@register_op("gather_nd")
def gather_nd(data, indices):
    # indices: (M, ...) selecting along the first M dims (ref: src/operator/tensor/indexing_op.cc)
    idx = tuple(indices[i].astype(jnp.int32) for i in range(indices.shape[0]))
    return data[idx]


@register_op("scatter_nd")
def scatter_nd(data, indices, *, shape):
    idx = tuple(indices[i].astype(jnp.int32) for i in range(indices.shape[0]))
    return jnp.zeros(shape, data.dtype).at[idx].set(data)


@register_op("one_hot", nondiff=True)
def one_hot(indices, *, depth, on_value=1.0, off_value=0.0, dtype="float32"):
    oh = jax.nn.one_hot(indices.astype(jnp.int32), depth, dtype=resolve_dtype(dtype))
    return oh * (on_value - off_value) + off_value


@register_op("diag")
def diag(x, *, k=0):
    return jnp.diag(x, k=k) if x.ndim <= 2 else jnp.diagonal(x, offset=k)


@register_op("trace")
def trace(x, *, offset=0, axis1=0, axis2=1):
    """Sum along a diagonal (ref: np_trace_op.cc; flat nd alias here)."""
    return jnp.trace(x, offset=offset, axis1=axis1, axis2=axis2)


@register_op("depth_to_space")
def depth_to_space(x, *, block_size):
    n, c, h, w = x.shape
    b = block_size
    y = x.reshape(n, b, b, c // (b * b), h, w)
    y = y.transpose(0, 3, 4, 1, 5, 2)
    return y.reshape(n, c // (b * b), h * b, w * b)


@register_op("space_to_depth")
def space_to_depth(x, *, block_size):
    n, c, h, w = x.shape
    b = block_size
    y = x.reshape(n, c, h // b, b, w // b, b)
    y = y.transpose(0, 3, 5, 1, 2, 4)
    return y.reshape(n, c * b * b, h // b, w // b)


@register_op("_onnx_shape", nondiff=True)
def _onnx_shape(x):
    """ONNX Shape: the (static under jit) shape as an int64 tensor."""
    return jnp.asarray(x.shape, jnp.int64)


@register_op("zeros_like")
def zeros_like(x):
    return jnp.zeros_like(x)


@register_op("ones_like")
def ones_like(x):
    return jnp.ones_like(x)


@register_op("shape_array", nondiff=True)
def shape_array(x):
    return jnp.array(x.shape, dtype=jnp.int64)


@register_op("size_array", nondiff=True)
def size_array(x):
    return jnp.array([x.size], dtype=jnp.int64)


@register_op("BlockGrad")
def BlockGrad(x):
    return lax.stop_gradient(x)


stop_gradient = BlockGrad


# ---------------------------------------------------------------- linalg


@register_op("dot")
def dot(a, b, *, transpose_a=False, transpose_b=False):
    """MXNet dot: contract last axis of a with first axis of b
    (ref: src/operator/tensor/dot-inl.h)."""
    if transpose_a:
        a = jnp.moveaxis(a, 0, -1) if a.ndim > 1 else a
    if transpose_b:
        b = jnp.moveaxis(b, -1, 0) if b.ndim > 1 else b
    return jnp.tensordot(a, b, axes=1) if (a.ndim > 1 or b.ndim > 1) else jnp.dot(a, b)


@register_op("batch_dot")
def batch_dot(a, b, *, transpose_a=False, transpose_b=False):
    if transpose_a:
        a = jnp.swapaxes(a, -1, -2)
    if transpose_b:
        b = jnp.swapaxes(b, -1, -2)
    return jnp.matmul(a, b)


@register_op("matmul")
def matmul(a, b):
    return jnp.matmul(a, b)


@register_op("linalg_gemm2")
def linalg_gemm2(a, b, *, transpose_a=False, transpose_b=False, alpha=1.0):
    return alpha * batch_dot(a, b, transpose_a=transpose_a, transpose_b=transpose_b)


@register_op("khatri_rao")
def khatri_rao(*xs):
    out = xs[0]
    for m in xs[1:]:
        out = jnp.einsum("ir,jr->ijr", out, m).reshape(-1, out.shape[1])
    return out


# ---------------------------------------------------------------- neural net


@register_op("FullyConnected")
def FullyConnected(x, weight, bias=None, *, num_hidden=None, no_bias=False, flatten=True):
    """y = x @ W^T + b, weight (num_hidden, in) as in MXNet
    (ref: src/operator/nn/fully_connected.cc). Maps straight onto the MXU."""
    if num_hidden is not None and weight.shape[0] != num_hidden:
        raise ValueError(
            "FullyConnected: weight rows %d != num_hidden %d (infer-shape "
            "mismatch)" % (weight.shape[0], num_hidden))
    if flatten and x.ndim > 2:
        x = jnp.reshape(x, (x.shape[0], -1))
    weight = weight.astype(x.dtype)  # compute in the input's dtype (AMP)
    y = jnp.matmul(x, weight.T)
    if bias is not None and not no_bias:
        y = y + bias.astype(y.dtype)  # fp32 bias must not re-widen bf16 y
    return y


def _pair(v, n=2):
    if isinstance(v, int):
        return (v,) * n
    return tuple(v)


@register_op("Convolution")
def Convolution(x, weight, bias=None, *, kernel=None, stride=1, pad=0, dilate=1,
                num_group=1, num_filter=None, no_bias=False, layout="NCHW"):
    """N-d convolution via lax.conv_general_dilated (ref:
    src/operator/nn/convolution.cc; cuDNN path replaced by XLA:TPU which tiles
    convs onto the MXU)."""
    if num_filter is not None and weight.shape[0] != num_filter:
        raise ValueError(
            "Convolution: weight out-channels %d != num_filter %d (infer-"
            "shape mismatch)" % (weight.shape[0], num_filter))
    nd = x.ndim - 2
    stride = _pair(stride, nd)
    pad = _pair(pad, nd)
    dilate = _pair(dilate, nd)
    spatial = "DHW"[-nd:] if nd <= 3 else None
    lhs = "NC" + spatial
    rhs = "OI" + spatial
    weight = weight.astype(x.dtype)  # compute in the input's dtype (AMP)
    dn = lax.conv_dimension_numbers(x.shape, weight.shape, (lhs, rhs, lhs))
    # NOTE: no preferred_element_type here — the TPU MXU accumulates bf16
    # convs in fp32 natively, and jax's conv transpose rule mishandles the
    # widened fp32 output under reverse AD (fp32 cotangent vs bf16 operand)
    y = lax.conv_general_dilated(
        x, weight, window_strides=stride, padding=[(p, p) for p in pad],
        rhs_dilation=dilate, dimension_numbers=dn, feature_group_count=num_group,
    )
    if bias is not None and not no_bias:
        y = y + bias.astype(x.dtype).reshape((1, -1) + (1,) * nd)
    return y


@register_op("Deconvolution")
def Deconvolution(x, weight, bias=None, *, kernel=None, stride=1, pad=0, dilate=1,
                  num_group=1, num_filter=None, adj=0, no_bias=False, layout="NCHW"):
    if num_filter is not None and weight.shape[1] * num_group != num_filter:
        raise ValueError(
            "Deconvolution: weight out-channels %d != num_filter %d (infer-"
            "shape mismatch)" % (weight.shape[1] * num_group, num_filter))
    nd = x.ndim - 2
    stride = _pair(stride, nd)
    pad = _pair(pad, nd)
    adj = _pair(adj, nd)
    spatial = "DHW"[-nd:]
    dn = lax.conv_dimension_numbers(x.shape, weight.shape, ("NC" + spatial, "IO" + spatial, "NC" + spatial))
    k = weight.shape[2:]
    padding = [(ki - 1 - p, ki - 1 - p + a) for ki, p, a in zip(k, pad, adj)]
    y = lax.conv_general_dilated(
        x, jnp.flip(weight.astype(x.dtype), axis=tuple(range(2, 2 + nd))),
        window_strides=(1,) * nd, padding=padding, lhs_dilation=stride,
        dimension_numbers=dn, feature_group_count=num_group,
    )
    if bias is not None and not no_bias:
        y = y + bias.astype(x.dtype).reshape((1, -1) + (1,) * nd)
    return y


@register_op("Pooling")
def Pooling(x, *, kernel=1, pool_type="max", stride=None, pad=0,
            global_pool=False, count_include_pad=True):
    """max/avg/sum pooling via lax.reduce_window (ref: src/operator/nn/pooling.cc)."""
    nd = x.ndim - 2
    if global_pool:
        ax = tuple(range(2, x.ndim))
        if pool_type == "max":
            return jnp.max(x, axis=ax, keepdims=True)
        if pool_type == "sum":
            return jnp.sum(x, axis=ax, keepdims=True)
        return jnp.mean(x, axis=ax, keepdims=True)
    kernel = _pair(kernel, nd)
    stride = _pair(stride if stride is not None else kernel, nd)
    pad = _pair(pad, nd)
    dims = (1, 1) + kernel
    strides = (1, 1) + stride
    padding = ((0, 0), (0, 0)) + tuple((p, p) for p in pad)
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        return lax.reduce_window(x, init, lax.max, dims, strides, padding)
    s = lax.reduce_window(x, 0.0, lax.add, dims, strides, padding)
    if pool_type == "sum":
        return s
    if count_include_pad:
        return s / math.prod(kernel)
    ones = jnp.ones(x.shape[2:], x.dtype)[None, None]
    cnt = lax.reduce_window(ones, 0.0, lax.add, dims, strides, padding)
    return s / jnp.maximum(cnt, 1.0)


@register_op("BatchNorm", needs_training=True, n_outputs=3)
def BatchNorm(x, gamma, beta, moving_mean, moving_var, *, eps=1e-5, momentum=0.9,
              fix_gamma=False, use_global_stats=False, axis=1, training=False):
    """Returns (y, new_moving_mean, new_moving_var)
    (ref: src/operator/nn/batch_norm.cc)."""
    if fix_gamma:
        gamma = jnp.ones_like(gamma)
    shape = [1] * x.ndim
    shape[axis] = x.shape[axis]
    shape = tuple(shape)
    red = tuple(i for i in range(x.ndim) if i != axis)
    # normalize entirely in fp32 with ONE cast boundary at input and output:
    # bf16-in → bf16-out AND bf16 cotangents. (Mixing per-factor casts made
    # jnp.var's fp32 accumulation leak an fp32 cotangent into bf16 inputs,
    # blowing up conv transpose rules under AMP.)
    xf = x.astype(jnp.float32)
    if training and not use_global_stats:
        m = jnp.mean(xf, axis=red)
        v = jnp.var(xf, axis=red)
        new_mean = momentum * moving_mean + (1 - momentum) * m
        new_var = momentum * moving_var + (1 - momentum) * v
    else:
        m, v = moving_mean.astype(jnp.float32), moving_var.astype(jnp.float32)
        new_mean, new_var = moving_mean, moving_var
    inv = lax.rsqrt(v + eps)
    y = ((xf - m.reshape(shape)) * inv.reshape(shape)
         * gamma.reshape(shape).astype(jnp.float32)
         + beta.reshape(shape).astype(jnp.float32)).astype(x.dtype)
    return y, lax.stop_gradient(new_mean), lax.stop_gradient(new_var)


@register_op("LayerNorm")
def LayerNorm(x, gamma, beta, *, axis=-1, eps=1e-5):
    """(ref: src/operator/nn/layer_norm.cc). fp32 statistics (the standard TPU
    recipe); last-axis LN at MXU-aligned widths takes the fused pallas kernel
    (ops/pallas/layernorm.py), one VMEM pass per row block."""
    last = axis in (-1, x.ndim - 1)
    # gate decided at trace time from static shapes, like softmax_xent_rows
    # below: a kernel that fails raises (a Mosaic failure surfaces at compile
    # time)
    if (is_tpu_backend() and not under_mesh() and last and x.ndim >= 2
            and gamma.ndim == 1 and _ln.tiles(math.prod(x.shape[:-1]), x.shape[-1])):
        lead = x.shape[:-1]
        y = _ln.layernorm(x.reshape(-1, x.shape[-1]), gamma, beta, eps)
        return y.reshape(lead + (x.shape[-1],))
    # fp32 stats with ONE cast boundary back to x.dtype (same recipe as
    # BatchNorm above): `y.astype * gamma` would re-promote bf16 activations
    # to f32 through the affine and poison every downstream matmul
    xf = x.astype(jnp.float32)
    m = jnp.mean(xf, axis=axis, keepdims=True)
    v = jnp.var(xf, axis=axis, keepdims=True)
    y = ((xf - m) * lax.rsqrt(v + eps) * gamma.astype(jnp.float32)
         + beta.astype(jnp.float32))
    return y.astype(x.dtype)


@register_op("rms_norm")
def rms_norm(x, gamma, *, eps=1e-6):
    """``x / sqrt(mean(x^2) + eps) * gamma`` over the last axis: float32
    statistics and one cast back to ``x``'s type, as :func:`LayerNorm`
    without the mean and the shift."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * lax.rsqrt(ms + eps)
            * gamma.astype(jnp.float32)).astype(x.dtype)


@register_op("InstanceNorm")
def InstanceNorm(x, gamma, beta, *, eps=1e-5):
    red = tuple(range(2, x.ndim))
    m = jnp.mean(x, axis=red, keepdims=True)
    v = jnp.var(x, axis=red, keepdims=True)
    shape = (1, -1) + (1,) * (x.ndim - 2)
    return (x - m) * lax.rsqrt(v + eps) * gamma.reshape(shape) + beta.reshape(shape)


@register_op("GroupNorm")
def GroupNorm(x, gamma, beta, *, num_groups=1, eps=1e-5):
    n, c = x.shape[:2]
    xr = x.reshape((n, num_groups, c // num_groups) + x.shape[2:])
    red = tuple(range(2, xr.ndim))
    m = jnp.mean(xr, axis=red, keepdims=True)
    v = jnp.var(xr, axis=red, keepdims=True)
    xr = (xr - m) * lax.rsqrt(v + eps)
    shape = (1, -1) + (1,) * (x.ndim - 2)
    return xr.reshape(x.shape) * gamma.reshape(shape) + beta.reshape(shape)


@register_op("Dropout", needs_rng=True, needs_training=True)
def Dropout(x, *, p=0.5, training=False, key=None, mode="training"):
    if not training or p <= 0.0 or key is None:
        return x
    keep = 1.0 - p
    mask = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(mask, x / keep, 0.0).astype(x.dtype)


@register_op("Activation")
def Activation(x, *, act_type="relu"):
    if act_type == "relu":
        return jax.nn.relu(x)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(x)
    if act_type == "tanh":
        return jnp.tanh(x)
    if act_type == "softrelu":
        return jax.nn.softplus(x)
    if act_type == "softsign":
        return jax.nn.soft_sign(x)
    if act_type == "gelu":
        return jax.nn.gelu(x, approximate=False)
    if act_type == "gelu_tanh":
        return jax.nn.gelu(x, approximate=True)
    if act_type == "swish" or act_type == "silu":
        return jax.nn.silu(x)
    if act_type == "relu6":
        from .extra import relu6 as _relu6  # ONE relu6 definition
        return _relu6(x)
    raise ValueError("unknown act_type %r" % act_type)


@register_op("LeakyReLU")
def LeakyReLU(x, gamma=None, *, act_type="leaky", slope=0.25, lower_bound=0.125,
              upper_bound=0.334, key=None):
    if act_type == "leaky":
        return jnp.where(x >= 0, x, slope * x)
    if act_type == "prelu":
        g = gamma
        if g.ndim == 1 and x.ndim > 1:
            g = g.reshape((1, -1) + (1,) * (x.ndim - 2))
        return jnp.where(x >= 0, x, g * x)
    if act_type == "elu":
        return jnp.where(x >= 0, x, slope * (jnp.exp(x) - 1))
    if act_type == "selu":
        return jax.nn.selu(x)
    if act_type == "gelu":
        return jax.nn.gelu(x, approximate=False)
    raise ValueError("unknown act_type %r" % act_type)


@register_op("softmax")
def softmax(x, *, axis=-1, temperature=None):
    if temperature is not None and temperature != 1.0:
        x = x / temperature
    return jax.nn.softmax(x, axis=axis)


@register_op("log_softmax")
def log_softmax(x, *, axis=-1):
    return jax.nn.log_softmax(x, axis=axis)


@register_op("softmax_cross_entropy")
def softmax_cross_entropy(logits, labels):
    """(ref: src/operator/loss_binary_op.cc). On TPU the fused pallas kernel
    (ops/pallas/softmax_xent.py) computes the row NLLs in one HBM pass of
    the logits instead of three."""
    return jnp.sum(softmax_xent_rows(logits, labels))


@register_op("softmax_xent_rows")
def softmax_xent_rows(logits, labels, *, axis=-1):
    """Per-row sparse-label NLL under softmax — the shared hot path behind
    softmax_cross_entropy, gluon.loss.SoftmaxCrossEntropyLoss, and the LM
    benches. logits (..., V) along ``axis``, int labels shaped like logits
    minus that axis; returns fp32 NLLs in the labels' shape.

    Gate is deterministic at trace time (a try/except cannot catch Mosaic
    compile failures, which surface at jit-compile time): the fused kernel
    runs on TPU for any V — it lane-aligns internally — and any row count
    that tiles, while non-TPU backends take the jnp path (interpret-mode
    kernel parity is pinned by tests/test_kernels.py)."""
    axis = axis % logits.ndim
    if axis != logits.ndim - 1:
        logits = jnp.moveaxis(logits, axis, -1)
    rows_shape = logits.shape[:-1]
    flat = logits.reshape((-1, logits.shape[-1]))
    lab = labels.astype(jnp.int32).reshape((-1,))
    if is_tpu_backend() and not under_mesh() and _sx.tiles(*flat.shape):
        nll = _sx.softmax_xent(flat, lab)
    else:
        # fp32 like the kernel (which does fp32 math and returns fp32
        # regardless of logits dtype) — backends must agree in precision
        lp = jax.nn.log_softmax(flat.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(lp, lab[:, None], axis=-1)[:, 0]
    return nll.reshape(rows_shape)


@jax.custom_vjp
def _softmax_output_passthrough(x):
    return jax.nn.softmax(x, axis=-1)


def _so_fwd(x):
    return jax.nn.softmax(x, axis=-1), None


def _so_bwd(_, g):
    # MXNet SoftmaxOutput semantics (ref: src/operator/softmax_output-inl.h):
    # the incoming gradient is delivered to the LOGITS unchanged — the layer's
    # backward is (prob - one_hot), which callers (Module) supply directly.
    return (g,)


_softmax_output_passthrough.defvjp(_so_fwd, _so_bwd)


@register_op("SoftmaxOutput")
def SoftmaxOutput(x, label=None, *, grad_scale=1.0, ignore_label=-1,
                  use_ignore=False, preserve_shape=False, multi_output=False):
    return _softmax_output_passthrough(x)


@register_op("Embedding")
def Embedding(indices, weight, *, input_dim=None, output_dim=None, dtype=None,
              sparse_grad=False):
    """(ref: src/operator/tensor/indexing_op.cc:Embedding). Gather tiles well on
    TPU when the table's trailing dim is a multiple of 128."""
    return jnp.take(weight, indices.astype(jnp.int32), axis=0)


@register_op("SequenceMask")
def SequenceMask(x, sequence_length=None, *, use_sequence_length=False, value=0.0, axis=0):
    if not use_sequence_length or sequence_length is None:
        return x
    T = x.shape[axis]
    pos = jnp.arange(T)
    shape = [1] * x.ndim
    shape[axis] = T
    pos = pos.reshape(shape)
    lshape = [1] * x.ndim
    batch_axis = 1 if axis == 0 else 0
    lshape[batch_axis] = x.shape[batch_axis]
    mask = pos < sequence_length.reshape(lshape)
    return jnp.where(mask, x, value).astype(x.dtype)


@register_op("SequenceLast")
def SequenceLast(x, sequence_length=None, *, use_sequence_length=False, axis=0):
    if not use_sequence_length or sequence_length is None:
        import builtins

        idx = [builtins.slice(None)] * x.ndim
        idx[axis] = -1
        return x[tuple(idx)]
    last = (sequence_length.astype(jnp.int32) - 1)
    return jnp.take_along_axis(
        jnp.moveaxis(x, axis, 0), last[None, :, None] if x.ndim > 2 else last[None, :], axis=0
    )[0]


@register_op("SequenceReverse")
def SequenceReverse(x, sequence_length=None, *, use_sequence_length=False, axis=0):
    if not use_sequence_length or sequence_length is None:
        return jnp.flip(x, axis=axis)
    T = x.shape[axis]
    xm = jnp.moveaxis(x, axis, 0)
    pos = jnp.arange(T)[:, None]
    L = sequence_length.astype(jnp.int32)[None, :]
    src = jnp.where(pos < L, L - 1 - pos, pos)
    out = jnp.take_along_axis(xm, src.reshape(src.shape + (1,) * (xm.ndim - 2)).astype(jnp.int32), axis=0)
    return jnp.moveaxis(out, 0, axis)


@register_op("LRN")
def LRN(x, *, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5):
    """Local response norm across channels (ref: src/operator/nn/lrn.cc)."""
    sq = jnp.square(x)
    s = lax.reduce_window(sq, 0.0, lax.add, (1, nsize, 1, 1), (1, 1, 1, 1),
                          ((0, 0), (nsize // 2, nsize // 2), (0, 0), (0, 0)))
    return x / jnp.power(knorm + (alpha / nsize) * s, beta)


@register_op("UpSampling")
def UpSampling(x, *, scale=2, sample_type="nearest"):
    n, c, h, w = x.shape
    if sample_type == "nearest":
        return jnp.repeat(jnp.repeat(x, scale, axis=2), scale, axis=3)
    return jax.image.resize(x, (n, c, h * scale, w * scale), method="bilinear")


def adaptive_avg_matrix(n_in, n_out):
    """Row-averaging matrix for adaptive pooling, window
    [floor(i·n/o), ceil((i+1)·n/o)) — single source for the on-device op
    AND its ONNX two-matmul export (onnx/export.py)."""
    m = np.zeros((n_out, n_in), np.float32)
    for i in range(n_out):
        s, e = (i * n_in) // n_out, -((-(i + 1) * n_in) // n_out)
        m[i, s:e] = 1.0 / (e - s)
    return m


@register_op("AdaptiveAvgPooling2D")
def AdaptiveAvgPooling2D(x, *, output_size=None):
    """Adaptive average pool of (B, C, H, W) to (B, C, oh, ow) (ref:
    src/operator/contrib/adaptive_avg_pooling.cc, torch-style windows
    [floor(i·H/oh), ceil((i+1)·H/oh))). Output sizes are static, so the pool
    is two small matmuls (row/col averaging matrices built at trace time) —
    MXU-tiled by XLA instead of a gather loop. An omitted/empty output_size
    keeps the input size (upstream's empty-param branch)."""
    if output_size is None or output_size == ():
        return x
    if isinstance(output_size, (tuple, list)):
        oh, ow = (int(output_size[0]),
                  int(output_size[1 if len(output_size) > 1 else 0]))
    else:
        oh = ow = int(output_size)
    h, w = x.shape[2], x.shape[3]
    left = jnp.asarray(adaptive_avg_matrix(h, oh), x.dtype)
    right = jnp.asarray(adaptive_avg_matrix(w, ow), x.dtype).T
    return jnp.einsum("oh,bchw,wp->bcop", left, x, right)


@register_op("BilinearResize2D")
def BilinearResize2D(x, *, height=None, width=None, scale_height=None,
                     scale_width=None):
    """ALIGN-CORNERS bilinear (src maps out pixel i to i·(H-1)/(h-1)) — the
    reference's convention (src/operator/contrib/bilinear_resize-inl.h);
    jax.image.resize's half-pixel centers would shift every sample (caught
    by the torch-oracle test)."""
    h = int(height) if height is not None else int(x.shape[2] * scale_height)
    w = int(width) if width is not None else int(x.shape[3] * scale_width)
    return _resize_bilinear_align_corners(x, h, w)


def _resize_bilinear_align_corners(x, h, w):
    H, W = x.shape[2], x.shape[3]
    ys = (jnp.linspace(0.0, H - 1.0, h) if h > 1
          else jnp.zeros((1,), jnp.float32))
    xs = (jnp.linspace(0.0, W - 1.0, w) if w > 1
          else jnp.zeros((1,), jnp.float32))
    return _bilinear_gather(x, ys, xs)


def _bilinear_gather(x, ys, xs):
    """Sample NCHW ``x`` at float source rows ``ys`` × cols ``xs`` with
    bilinear weights (coords pre-clamped to [0, dim-1]). Integer inputs
    (uint8 image subgraphs) interpolate in float32 and round back —
    weights cast to an int dtype would truncate to 0 and silently degrade
    to floor-nearest sampling."""
    H, W = x.shape[2], x.shape[3]
    in_dtype = x.dtype
    integral = jnp.issubdtype(in_dtype, jnp.integer)
    compute = jnp.float32 if integral else in_dtype
    y0 = jnp.floor(ys).astype(jnp.int32)
    x0 = jnp.floor(xs).astype(jnp.int32)
    y1 = jnp.minimum(y0 + 1, H - 1)
    x1 = jnp.minimum(x0 + 1, W - 1)
    wy = (ys - y0).astype(compute)[:, None]
    wx = (xs - x0).astype(compute)[None, :]
    x = x.astype(compute)
    v00 = x[:, :, y0[:, None], x0[None, :]]
    v01 = x[:, :, y0[:, None], x1[None, :]]
    v10 = x[:, :, y1[:, None], x0[None, :]]
    v11 = x[:, :, y1[:, None], x1[None, :]]
    top = v00 * (1 - wx) + v01 * wx
    bot = v10 * (1 - wx) + v11 * wx
    out = top * (1 - wy) + bot * wy
    if integral:
        out = jnp.rint(out).astype(in_dtype)
    return out


@register_op("_resize_linear_asymmetric")
def _resize_linear_asymmetric(x, *, height=None, width=None,
                              scale_height=None, scale_width=None):
    """ONNX ctm=asymmetric linear Resize: x_original = x_resized / scale,
    no half-pixel shift (onnx.ai Resize spec; common in TF exports and
    opset-10 Upsample upgrades). Kept exact via the shared bilinear gather
    rather than approximated as half_pixel."""
    H, W = x.shape[2], x.shape[3]
    h = int(height) if height is not None else int(H * scale_height)
    w = int(width) if width is not None else int(W * scale_width)
    sh = float(scale_height) if scale_height is not None else h / H
    sw = float(scale_width) if scale_width is not None else w / W
    ys = jnp.minimum(jnp.arange(h, dtype=jnp.float32) / sh, H - 1.0)
    xs = jnp.minimum(jnp.arange(w, dtype=jnp.float32) / sw, W - 1.0)
    return _bilinear_gather(x, ys, xs)


@register_op("_resize_linear_half_pixel")
def _resize_linear_half_pixel(x, *, height=None, width=None,
                              scale_height=None, scale_width=None,
                              pytorch_mode=False):
    """Half-pixel-centers bilinear (the ONNX Resize default) — kept as its
    own op so importing external half_pixel models stays exact while
    BilinearResize2D keeps MXNet's align-corners parity. Scales resolve
    against x's (static-under-trace) shape. antialias=False: ONNX Resize
    has no antialiasing before opset 18, and jax's default triangle filter
    on downscale would silently diverge from the producer's runtime."""
    n, c = x.shape[:2]
    h = int(height) if height is not None else int(x.shape[2] * scale_height)
    w = int(width) if width is not None else int(x.shape[3] * scale_width)
    if pytorch_mode and (h == 1 or w == 1):
        # pytorch_half_pixel maps a length-1 output dim to source 0 where
        # half_pixel maps it mid-image — refuse rather than sample wrong
        raise NotImplementedError(
            "pytorch_half_pixel Resize with an output dim of 1 differs "
            "from half_pixel and is not implemented")
    return jax.image.resize(x, (n, c, h, w), method="bilinear",
                            antialias=False)
