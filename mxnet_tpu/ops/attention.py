"""Attention ops.

Single functional seam for all transformer models: models call
``F.scaled_dot_attention``; the implementation dispatches to the Pallas flash
kernel on TPU (mxnet_tpu/ops/pallas/flash_attention.py) and to a reference
jnp implementation elsewhere (CPU tests, interpret mode). This replaces the
reference's unfused softmax(QK^T)V graph (MXNet had no flash attention;
ref: gluonnlp attention_cell.py:DotProductAttentionCell).
"""
from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp

from ..base import is_tpu_backend, register_op
from .pallas import under_mesh

_FLASH_MIN_LEN = 256  # static GUESS, used only until a hardware sweep lands


def _flash_min_len():
    """Measured flash-vs-dense crossover from the sweep artifact when one
    exists (flash_blocks.json "min_len", written by flash_sweep --apply),
    else the static guess. The headline bert runs at seq 128 — whether it
    takes the flash kernel is hardware's call, not a constant's."""
    from .pallas import flash_attention as _fa

    return _fa.MIN_LEN if _fa.MIN_LEN is not None else _FLASH_MIN_LEN


_SP_SCOPE = threading.local()


class sequence_parallel_scope:
    """Route every ``F.scaled_dot_attention`` inside the scope through
    sequence-parallel attention over ``mesh``'s ``axis_name`` axis —
    ``impl='ring'`` (ppermute ring, any head count) or ``'ulysses'``
    (all_to_all head scatter, needs H % axis == 0). Models need no edits;
    this is how a single-chip model becomes a long-context sp model.
    Exposed as ``mxnet_tpu.parallel.sequence_parallel_scope``.

    The scope is consulted AT TRACE TIME: a ``jax.jit``/``hybridize`` cache
    entry keeps whichever dispatch was active when it was first traced
    (same contract as ``autograd.train_mode`` and the keyed-jit stochastic
    executors) — enter the scope before the first call, and don't reuse a
    function jitted outside it."""

    def __init__(self, mesh, axis_name="sp", impl="ring"):
        if impl not in ("ring", "ulysses"):
            raise ValueError("impl must be 'ring' or 'ulysses', got %r"
                             % (impl,))
        self._cfg = (mesh, axis_name, impl)

    def __enter__(self):
        stack = getattr(_SP_SCOPE, "stack", None)
        if stack is None:
            stack = _SP_SCOPE.stack = []
        stack.append(self._cfg)
        return self

    def __exit__(self, *a):
        _SP_SCOPE.stack.pop()


def _current_sp_scope():
    stack = getattr(_SP_SCOPE, "stack", None)
    return stack[-1] if stack else None


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _dense_attention_core(q, k, v, bias, scale):
    """Mixed-precision dense attention: bf16 MXU matmuls with fp32
    accumulation (preferred_element_type); softmax in fp32; ``bias`` is the
    additive fp32 mask (0 keep / -1e30 drop), already combining key-padding
    and causal terms."""
    out, _ = _dense_attention_fwd(q, k, v, bias, scale)
    return out


def _dense_attention_fwd(q, k, v, bias, scale):
    # scale applied to the fp32 logits, not to bf16 q: exact in scale and no
    # extra bf16 rounding before the MXU matmul
    s = scale * jnp.einsum("bhqd,bhkd->bhqk", q, k,
                           preferred_element_type=jnp.float32)
    if bias is not None:
        s = s + bias
    p = jax.nn.softmax(s, axis=-1)
    pb = p.astype(v.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", pb, v,
                     preferred_element_type=jnp.float32).astype(q.dtype)
    return out, (q, k, v, pb, bias)


def _dense_attention_bwd(scale, res, do):
    # Without this hand-written VJP the fp32 softmax cotangent promotes
    # every backward matmul to f32 (measured: 48 of the BERT step's 228
    # dots). Standard recipe: softmax-grad math in f32, then ONE cast of ds
    # down to the compute dtype before the dq/dk/dv MXU matmuls.
    q, k, v, pb, bias = res
    do = do.astype(v.dtype)
    dv = jnp.einsum("bhqk,bhqd->bhkd", pb, do,
                    preferred_element_type=jnp.float32).astype(v.dtype)
    dp = jnp.einsum("bhqd,bhkd->bhqk", do, v,
                    preferred_element_type=jnp.float32)
    pf = pb.astype(jnp.float32)
    ds = pf * (dp - jnp.sum(dp * pf, axis=-1, keepdims=True))
    # s = scale·(q·kᵀ) + bias  →  both dq and dk carry the scale factor
    dsb = (ds * scale).astype(q.dtype)
    dq = jnp.einsum("bhqk,bhkd->bhqd", dsb, k,
                    preferred_element_type=jnp.float32).astype(q.dtype)
    dk = jnp.einsum("bhqk,bhqd->bhkd", dsb, q,
                    preferred_element_type=jnp.float32).astype(k.dtype)
    # the mask bias derives from non-differentiable booleans upstream; its
    # cotangent is structurally zero (None for the bias=None pytree)
    dbias = jax.tree_util.tree_map(lambda b: jnp.zeros(b.shape, b.dtype),
                                   bias)
    return dq, dk, dv, dbias


_dense_attention_core.defvjp(_dense_attention_fwd, _dense_attention_bwd)


def _mask_bias(mask, causal, T, S):
    """Combine key-padding mask + causal triangle into one additive fp32
    bias (or None)."""
    bias = None
    if mask is not None:
        bias = jnp.where(mask.astype(bool), 0.0, -1e30).astype(jnp.float32)
    if causal:
        cm = jnp.arange(T)[:, None] >= jnp.arange(S)[None, :]
        cb = jnp.where(cm, 0.0, -1e30).astype(jnp.float32)[None, None]
        bias = cb if bias is None else bias + cb
    return bias


def _reference_attention(q, k, v, mask=None, *, causal=False, scale=None):
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    try:
        scale = float(scale)  # nondiff_argnums needs a static python scalar
    except (TypeError, jax.errors.TracerArrayConversionError,
            jax.errors.ConcretizationTypeError):
        # traced/learned scale: fall back to the upcast reference (rare;
        # keeps the public op seam's accepted domain unchanged)
        s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
        bias = _mask_bias(mask, causal, q.shape[-2], k.shape[-2])
        if bias is not None:
            s = s + bias
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p,
                          v.astype(jnp.float32)).astype(q.dtype)
    bias = _mask_bias(mask, causal, q.shape[-2], k.shape[-2])
    return _dense_attention_core(q, k, v, bias, scale)


def _grouped_attention(q, k, v, mask, causal, scale, window):
    """Dense attention with ``Hkv`` K/V heads shared by groups of
    ``H // Hkv`` query heads (query head h reads K/V head ``h // G``) and
    an optional sliding window (key j visible to query i iff
    ``0 <= i - j < window``; a window implies causality). bf16 MXU
    operands, fp32 scores and softmax, like :func:`_dense_attention_fwd`;
    K and V are read once a group, never repeated to H heads. Inference
    only (no hand-written VJP)."""
    B, H, T, D = q.shape
    Hkv, S = k.shape[1], k.shape[2]
    G = H // Hkv
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    bias = _mask_bias(mask, causal or window is not None, T, S)
    if window is not None:
        near = (jnp.arange(T)[:, None] - jnp.arange(S)[None, :]) < window
        bias = bias + jnp.where(near, 0.0, -1e30).astype(jnp.float32)
    s = scale * jnp.einsum("bkgqd,bksd->bkgqs", q.reshape(B, Hkv, G, T, D),
                           k, preferred_element_type=jnp.float32)
    if bias is not None:
        # (B | 1, H | 1, T, S) -> (B | 1, Hkv | 1, G | 1, T, S)
        b0, b1 = bias.shape[:2]
        bias = bias.reshape((b0, Hkv, G) + bias.shape[2:]) if b1 == H \
            else bias[:, :, None]
        s = s + bias
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bksd->bkgqd", p, v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, H, T, D).astype(q.dtype)


@register_op("rotary", nondiff=True)
def rotary(x, positions, *, theta=10000.0):
    """Rotary position embedding over the whole head width of ``x``
    (B, H, T, D), interleaved pairs: ``(x[2i], x[2i+1])`` turn by
    ``positions * theta ** (-2i / D)``. ``positions`` is (T,) or per row
    (B, T), any integer type. Angles, sines and the rotation are float32;
    the result is rounded once to ``x``'s type.

    The pair's other element, signed (``(-x[2i+1], x[2i])``), is ``x``
    times a fixed D x D matrix of 0 and +-1: exact in any type, one small
    matmul, and it leaves the lane axis alone (a reshape to pairs or a
    lane rotation would stand as float32 copies of a whole prompt's q)."""
    D = x.shape[-1]
    pos = jnp.asarray(positions).astype(jnp.float32)
    pos = pos[None, None] if pos.ndim == 1 else pos[:, None]   # (B|1,1,T)
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.repeat(pos[..., None] * inv, 2, axis=-1)         # (B|1,1,T,D)
    i = jnp.arange(D)
    swap = jnp.where(i[:, None] == (i ^ 1)[None, :],
                     jnp.where(i[:, None] % 2 == 1, -1.0, 1.0), 0.0)
    other = jnp.einsum("...i,ij->...j", x, swap.astype(x.dtype))
    return (x.astype(jnp.float32) * jnp.cos(ang)
            + other.astype(jnp.float32) * jnp.sin(ang)).astype(x.dtype)


@register_op("scaled_dot_attention")
def scaled_dot_attention(q, k, v, mask=None, *, causal=False, scale=None,
                         prefix_mask=False, window=None):
    """q,k,v: (B, H, T, D); mask broadcastable to (B, H, Tq, Tk), 1=keep.

    Grouped K/V heads: ``k`` and ``v`` may carry fewer heads than ``q``
    (``H % Hkv == 0``; query head h reads K/V head ``h // (H // Hkv)``).
    ``window`` (static int) is a sliding causal window: key j is visible to
    query i iff ``0 <= i - j < window``. Both are inference paths: on a TPU
    at flash lengths the flash kernel takes them (blocks wholly outside the
    window are skipped), elsewhere :func:`_grouped_attention`.

    prefix_mask=True is the caller's STATIC declaration that ``mask`` is a
    key-padding prefix (mask[b, ..., t] = t < valid_len[b], BERT-style) —
    then the O(T)-memory flash path applies with a per-example valid length
    recovered as the mask's row sum, instead of falling back to the dense
    T×T reference the way arbitrary masks must.

    Inside ``parallel.sequence_parallel_scope(mesh, ...)`` this seam
    dispatches to ring/ulysses attention over the scope's mesh axis — the
    model code doesn't change, the sequence dimension just shards."""
    sp = _current_sp_scope()
    if sp is not None:
        mesh, axis_name, impl = sp
        if mask is not None:
            raise ValueError(
                "sequence_parallel_scope: ring/ulysses attention supports "
                "causal or unmasked only — key-padding masks would need "
                "per-shard valid lengths (pad to full length instead)")
        n_sp = int(mesh.shape[axis_name])
        if q.shape[2] % n_sp or k.shape[2] % n_sp:
            raise ValueError(
                "sequence_parallel_scope: sequence length %d/%d must divide "
                "the %r axis (%d) — incremental decode (T=1) and ragged "
                "lengths cannot shard; run generation outside the scope"
                % (q.shape[2], k.shape[2], axis_name, n_sp))
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as _P

        from ..parallel import ring_attention, ulysses_attention

        fn = ring_attention if impl == "ring" else ulysses_attention
        # eager NDArray data is committed to one device; the shard_map needs
        # the whole mesh, so reshard in and gather back out to the caller's
        # original placement. Inside a single-device jit both puts are
        # no-ops; users doing whole-program mesh sharding should call
        # parallel.ring_attention directly.
        orig = getattr(q, "sharding", None)  # None for tracers
        s_in = NamedSharding(mesh, _P(None, None, axis_name, None))
        q, k, v = (jax.device_put(a, s_in) for a in (q, k, v))
        out = fn(q, k, v, mesh, axis_name=axis_name, causal=causal,
                 scale=scale)
        return jax.device_put(out, orig if orig is not None
                              else mesh.devices.flat[0])
    grouped = k.shape[1] != q.shape[1]
    if grouped and q.shape[1] % k.shape[1]:
        raise ValueError("scaled_dot_attention: %d query heads do not "
                         "divide into %d K/V heads"
                         % (q.shape[1], k.shape[1]))
    if (is_tpu_backend() and not under_mesh()
            and q.shape[2] >= _flash_min_len()
            and (mask is None or prefix_mask)):
        from .pallas.flash_attention import flash_attention

        vl = None if mask is None else _prefix_mask_to_valid_len(mask)
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               kv_valid_len=vl, window=window)
    if grouped or window is not None:
        return _grouped_attention(q, k, v, mask, causal, scale, window)
    return _reference_attention(q, k, v, mask, causal=causal, scale=scale)


def _prefix_mask_to_valid_len(mask):
    """(B, ..., Tk) prefix key-padding mask → (B,) valid lengths. A prefix
    mask is row-constant, so any one row's sum is the length."""
    return jnp.sum(mask.reshape(mask.shape[0], -1, mask.shape[-1])
                   [:, 0, :].astype(jnp.int32), axis=-1)


@register_op("masked_softmax")
def masked_softmax(x, mask=None, *, axis=-1):
    if mask is not None:
        x = jnp.where(mask.astype(bool), x, -1e30)
    return jax.nn.softmax(x, axis=axis)


@register_op("cache_write", nondiff=True)
def cache_write(cache, update, index):
    """Write ``update`` (B, H, T, D) into the fixed-capacity KV cache
    ``cache`` (B, H, C, D) at time offset ``index`` along axis 2 — the
    decode-cache primitive: the cache shape NEVER changes across steps, so
    a jitted decode step compiles once instead of retracing per token (the
    growing-``concat`` cache layout graphlint GL007 flags).

    ``index`` is a scalar (whole-batch write at one offset: prefill, the
    uniform imperative decode loop) or a per-row ``(B,)`` vector (continuous
    batching: each slot is at its own position). With the cache buffer
    donated, every path updates it in place. What each lowers to:

    - scalar ``index``: one ``lax.dynamic_update_slice``;
    - per-row ``index``, one token a row, on a TPU, where the shapes tile
      (``kv_write.tiles``) and no device mesh is being traced: the Pallas
      kernel ``kv_cache_write``, one pass over the blocks that hold the
      rows' positions (128-position blocks with the capacity on the lanes
      for head widths under 128; one sublane tile of rows for head widths
      that are whole lane tiles);
    - per-row ``index`` otherwise: ``vmap(dynamic_update_slice)``, which
      is a ``scatter``, which XLA on the TPU expands into a serial
      ``while`` loop of B column updates.

    All three give the same bits. Writes past the capacity are the caller's
    bug; like dynamic_update_slice, the start index clamps to ``C - T``."""
    index = jnp.asarray(index, jnp.int32)
    update = update.astype(cache.dtype)
    zero = jnp.int32(0)
    if index.ndim == 0:
        return jax.lax.dynamic_update_slice(cache, update,
                                            (zero, zero, index, zero))
    if is_tpu_backend() and not under_mesh():
        from .pallas import kv_write

        if kv_write.tiles(cache.shape, update.shape, cache.dtype):
            return kv_write.kv_cache_write(cache, update, index)
    return jax.vmap(
        lambda c, u, i: jax.lax.dynamic_update_slice(c, u, (zero, i, zero))
    )(cache, update, index)


@register_op("quant_cache_write", nondiff=True, n_outputs=2)
def quant_cache_write(cache, scale, update, index):
    """:func:`cache_write` for the int8 paged KV cache: quantize ``update``
    (B, H, T, D) fp on write into ``cache`` (B, H, C, D) int8 with a
    per-page-per-head scale ``scale`` (B, H, 1, 1) fp32, returning
    ``(new_cache, new_scale)``.

    The scale is a RUNNING per-(page, head) max — monotone non-decreasing,
    so already-written positions only ever rescale DOWN (ratio ≤ 1) and the
    branchless requantize below is an exact no-op when the scale did not
    move (int8→fp32 × 1.0 → round reproduces the integer). Both buffers are
    donated by the decode step, so the whole thing is an in-place page
    update; shapes never change across steps — one compiled program."""
    index = jnp.asarray(index, jnp.int32)
    zero = jnp.int32(0)
    update = update.astype(jnp.float32)
    amax = jnp.max(jnp.abs(update), axis=(2, 3), keepdims=True)
    new_scale = jnp.maximum(scale, jnp.maximum(amax / 127.0, 1e-8))
    ratio = scale / new_scale            # ≤ 1; 0 for never-written pages
    requant = jnp.clip(jnp.round(cache.astype(jnp.float32) * ratio),
                       -127, 127).astype(jnp.int8)
    qupd = jnp.clip(jnp.round(update / new_scale), -127, 127).astype(jnp.int8)
    if index.ndim == 0:
        out = jax.lax.dynamic_update_slice(requant, qupd,
                                           (zero, zero, index, zero))
    else:
        out = jax.vmap(
            lambda c, u, i: jax.lax.dynamic_update_slice(c, u, (zero, i, zero))
        )(requant, qupd, index)
    return out, new_scale


@register_op("quant_cache_write_read", nondiff=True, n_outputs=3)
def quant_cache_write_read(cache, scale, update, index):
    """:func:`quant_cache_write` fused with the :func:`dequant_cache` read
    of the page it just wrote, returning ``(new_cache, new_scale, deq)``
    with ``deq`` (B, H, C, D) fp32 ready for attention.

    The separate write-then-read pair is the hlolint GL024 convert churn:
    the write quantizes the full page f32→int8 and the read immediately
    converts the SAME page int8→f32 with nothing but the cache update in
    between — two full-page converts per layer per step, which is what
    caps int8 decode below units=256. Here the fp32 requant/quantize
    values computed for the write are reused for the read, so the int8
    round trip never happens. Bit-exact with the unfused pair: the
    written values are integer-valued fp32 in [-127, 127], which int8
    represents exactly, so ``deq == dequant_cache(new_cache, new_scale)``
    to the last ulp."""
    index = jnp.asarray(index, jnp.int32)
    zero = jnp.int32(0)
    update = update.astype(jnp.float32)
    amax = jnp.max(jnp.abs(update), axis=(2, 3), keepdims=True)
    new_scale = jnp.maximum(scale, jnp.maximum(amax / 127.0, 1e-8))
    ratio = scale / new_scale            # ≤ 1; 0 for never-written pages
    requant = jnp.clip(jnp.round(cache.astype(jnp.float32) * ratio),
                       -127, 127)
    qupd = jnp.clip(jnp.round(update / new_scale), -127, 127)
    if index.ndim == 0:
        starts = (zero, zero, index, zero)
        out = jax.lax.dynamic_update_slice(
            requant.astype(jnp.int8), qupd.astype(jnp.int8), starts)
        deq = jax.lax.dynamic_update_slice(requant, qupd, starts)
    else:
        def _dus(c, u, i):
            return jax.lax.dynamic_update_slice(c, u, (zero, i, zero))

        out = jax.vmap(_dus)(requant.astype(jnp.int8),
                             qupd.astype(jnp.int8), index)
        deq = jax.vmap(_dus)(requant, qupd, index)
    return out, new_scale, deq * new_scale


@register_op("dequant_cache", nondiff=True)
def dequant_cache(cache, scale):
    """int8 KV pages → fp32 for attention: ``cache`` (B, H, C, D) int8 ×
    ``scale`` (B, H, 1, 1) fp32. XLA fuses the convert+scale into the
    attention matmul's operand read — no materialized fp32 cache copy."""
    return cache.astype(jnp.float32) * scale
