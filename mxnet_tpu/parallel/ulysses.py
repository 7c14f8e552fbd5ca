"""Ulysses-style all-to-all sequence parallelism (DeepSpeed-Ulysses; see
PAPERS.md). The second of the two long-context strategies the framework
ships (the other is ring attention — see ring_attention.py for when each
wins).

Shape story, per device on an 'sp' axis of size n:
  in:  q/k/v (B, H, T/n, D)   — sequence sharded, all heads local
  a2a: (B, H/n, T, D)         — HEADS sharded, full sequence local
  attn: exact dense (or flash) attention per local head group
  a2a back: (B, H, T/n, D)    — sequence sharded again

Two all-to-alls per call (vs ring's n ppermute hops): better for moderate
T with enough heads (H % n == 0), while ring attention has O(T/n · T/n)
score memory and no head-divisibility requirement but pays n hops. Both
ride ICI when 'sp' maps to a physical ring.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .mesh import shard_map
from .ring_attention import full_attention


def _a2a_heads_to_seq(x, axis_name, n):
    """(B, H, T/n, D) → (B, H/n, T, D): scatter head groups, gather sequence.

    all_to_all(tiled=False) removes split_axis (sending slice j to device j)
    and inserts a new size-n axis at concat_axis indexed by SOURCE device —
    here the source owns sequence block `src`, so that axis is the sequence
    block index."""
    B, H, Tl, D = x.shape
    x = x.reshape(B, n, H // n, Tl, D)            # axis1 = dest head group
    x = jnp.moveaxis(x, 1, 0)                     # (n, B, H/n, Tl, D)
    # split==concat: the transpose rule is the identity-shaped inverse
    # (split!=concat trips jax's all_to_all transpose with a cotangent
    # shape mismatch)
    x = lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0,
                       tiled=False)               # axis0 = source seq block
    x = jnp.moveaxis(x, 0, 2)                     # (B, H/n, n, Tl, D)
    return x.reshape(B, H // n, n * Tl, D)


def _a2a_seq_to_heads(x, axis_name, n):
    """(B, H/n, T, D) → (B, H, T/n, D): inverse of _a2a_heads_to_seq."""
    B, Hl, T, D = x.shape
    x = x.reshape(B, Hl, n, T // n, D)            # axis2 = dest seq block
    x = jnp.moveaxis(x, 2, 0)                     # (n, B, Hl, T/n, D)
    x = lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0,
                       tiled=False)               # axis0 = source head group
    x = jnp.moveaxis(x, 0, 1)                     # (B, n, Hl, T/n, D)
    return x.reshape(B, n * Hl, T // n, D)


def _ulysses_local(q, k, v, axis_name, n, causal, scale):
    q = _a2a_heads_to_seq(q, axis_name, n)
    k = _a2a_heads_to_seq(k, axis_name, n)
    v = _a2a_heads_to_seq(v, axis_name, n)
    o = full_attention(q, k, v, causal=causal, scale=scale)
    return _a2a_seq_to_heads(o, axis_name, n)


def ulysses_attention(q, k, v, mesh, axis_name="sp", causal=False,
                      scale=None, batch_axis=None):
    """q,k,v: (B, H, T, D), T sharded over `axis_name`; requires
    H % mesh.shape[axis_name] == 0. Differentiable: all_to_all transposes to
    the inverse all_to_all, so the backward pass is two more a2a hops.

    ``batch_axis`` additionally shards B over that mesh axis (dp×sp
    composition: every dp replica runs its own pair of all-to-alls over
    its batch shard — same convention as ep.moe_ffn's batch_axis)."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    n = int(mesh.shape[axis_name])
    for name, t in (("q", q), ("k", k), ("v", v)):
        if t.shape[1] % n:
            raise ValueError(
                "ulysses_attention: %s=%d (%s heads) is not divisible by "
                "the %r mesh axis (%d) — use ring_attention when the axis "
                "does not divide the head count"
                % (name, t.shape[1], name, axis_name, n))
    spec = P(batch_axis, None, axis_name, None)
    f = shard_map(functools.partial(_ulysses_local, axis_name=axis_name,
                                    n=n, causal=causal, scale=scale),
                  mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return f(q, k, v)
