"""Ring attention: sequence/context parallelism over the 'sp' mesh axis.

Not present in the reference (its max context is bounded by one GPU's memory);
required here as first-class long-context support. Blockwise attention with
online-softmax accumulation; K/V shards rotate around the ring with
``lax.ppermute`` (one ICI hop per step) while each device computes its local
Q-block against the visiting K/V block — compute/communication overlap is
XLA's job, memory per device is O(T/n · T/n) instead of O(T²).

Layout: q, k, v are (B, H, T, D) sharded over T ('sp' axis) — specs
P(None, None, 'sp', None).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .mesh import shard_map


def _ring_attn_local(q, k, v, axis_name, n, causal, scale):
    """One device's shard of the ring. ``n`` (ring length) is a STATIC python
    int — the mesh axis size — so the loop is a ``lax.scan`` of known length
    and the whole thing is reverse-mode differentiable (``ppermute``
    transposes to the inverse rotation, so the backward pass is itself a ring
    in the opposite direction). r1 used ``fori_loop`` with a traced
    ``psum(1, axis)`` bound, which cannot be transposed.
    """
    my = lax.axis_index(axis_name)
    Tq = q.shape[2]
    Tk = k.shape[2]
    qf = q.astype(jnp.float32) * scale

    o0 = jnp.zeros(q.shape[:3] + (v.shape[-1],), jnp.float32)
    l0 = jnp.zeros(q.shape[:3], jnp.float32)
    m0 = jnp.full(q.shape[:3], -jnp.inf, jnp.float32)
    perm = [(j, (j + 1) % n) for j in range(n)]

    # checkpoint: backward recomputes the (Tq, Tk) score block per step
    # instead of saving n of them — avoids the O(T²/n) score residuals; the
    # scan still saves each step's carry (o/l/m + visiting k/v block), so
    # activation memory is O(T · D) per device
    @jax.checkpoint
    def body(carry, i):
        o, l, m, k_cur, v_cur = carry
        src = (my - i) % n  # which global shard this k/v block came from
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, k_cur.astype(jnp.float32))
        if causal:
            q_pos = my * Tq + jnp.arange(Tq)
            k_pos = src * Tk + jnp.arange(Tk)
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # guard fully-masked rows (m_new == -inf)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        o = o * corr[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, v_cur.astype(jnp.float32))
        l = l * corr + jnp.sum(p, axis=-1)
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        return (o, l, m_new, k_next, v_next), None

    (o, l, m, _, _), _ = lax.scan(body, (o0, l0, m0, k, v), jnp.arange(n))
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def ring_attention(q, k, v, mesh, axis_name="sp", causal=False, scale=None,
                   batch_axis=None):
    """q,k,v: (B, H, T, D) with T sharded over `axis_name` on `mesh`.

    Differentiable: gradients flow through the scan + ppermute ring (the
    transpose rotates cotangents the opposite way around the ring), so this
    is the training path for sp-sharded long context, not just inference.

    ``batch_axis`` additionally shards B over that mesh axis (dp×sp
    composition: each dp replica runs its own independent ring over its
    batch shard — same convention as ep.moe_ffn's batch_axis).
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    spec = P(batch_axis, None, axis_name, None)
    n = int(mesh.shape[axis_name])
    f = shard_map(functools.partial(_ring_attn_local, axis_name=axis_name,
                                    n=n, causal=causal, scale=scale),
                  mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return f(q, k, v)


def full_attention(q, k, v, causal=False, scale=None):
    """Single-device reference (used by tests and the non-sp path)."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    if causal:
        T, S = s.shape[-2], s.shape[-1]
        mask = jnp.arange(T)[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)
