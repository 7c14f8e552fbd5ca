"""Power retention of degree 2: gated linear attention whose cache is a state
of fixed size (Gelada, Buckman, Zhang, Bach, "Scaling Context Requires
Rethinking Attention", arXiv:2507.04239).

With ``s_ij = (q_i . k_j)^2 / D`` and a gate ``c_t`` in (0, 1], query i
weighs key j <= i by ``A_ij = s_ij * c_{j+1} * ... * c_i`` and reads
``o_i = sum_j A_ij v_j / (sum_j A_ij + eps)``. Because ``(a . b)^2 =
phi(a) . phi(b)`` for ``phi`` the symmetric square of a vector, the sums over
j fold into a state that does not grow with the context:

    S_t = c_t S_{t-1} + phi(k_t) v_t^T        z_t = c_t z_{t-1} + phi(k_t)
    o_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + eps)

**The layout of phi** (this module's own: the page record and the model
carry the state without looking inside). Row d of ``phi(a)`` holds the
products of the elements d apart, ``w_d a_i a_{(i - d) mod D}`` for i in
0..D-1, a lane rotation of ``a`` times ``a``: rows d and D - d hold the same
pairs, so rows 0..D/2 hold every pair: the diagonal once (``w_0`` = 1), the
pairs 1..D/2-1 apart once (``w_d`` = sqrt 2), the pairs D/2 apart twice
(``w_{D/2}`` = 1). That is ``(D/2 + 1) x D`` entries, 65 x 128 = 8320 at
width 128: the 8256 of the symmetric square and 64 kept twice, so that every
row is one lane tile and is built by one rotation. The state of one K/V head
is ``S`` (rows x D, D): entry ``[d * D + u, i]`` pairs ``phi`` row d, element
i with ``v[u]``; ``z`` is (rows, D).

One function serves the prefill (a prompt in chunks: scores squared inside a
chunk, the state between chunks) and the decode step (one token a slot, on a
TPU the Pallas kernel ``retention_step``). Gates, state and the quotient are
float32 whatever the type of q, k and v.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..base import is_tpu_backend, register_op
from .pallas import under_mesh

EPS = 1e-6
CHUNK = 4096     # tokens folded into the state at once
ROWS = 256       # query rows scored, and keys folded, at once
_HI = jax.lax.Precision.HIGHEST


def phi_rows(head_dim):
    """Rows of :func:`phi` at a head width (which has to be even)."""
    if head_dim % 2:
        raise ValueError("power retention: odd head width %d" % head_dim)
    return head_dim // 2 + 1


def phi_weights(head_dim):
    """(rows,) float32: 1 for the diagonal and for the pairs kept twice,
    sqrt 2 between."""
    rows = phi_rows(head_dim)
    d = jnp.arange(rows)
    return jnp.where((d == 0) | (d == rows - 1), 1.0,
                     math.sqrt(2.0)).astype(jnp.float32)


def phi(a):
    """The symmetric square of ``a`` (..., D) as (..., D/2 + 1, D) float32:
    ``phi(a) . phi(b) == (a . b)^2`` summed over both axes."""
    a = a.astype(jnp.float32)
    D = a.shape[-1]
    turned = jnp.stack([jnp.roll(a, d, axis=-1) for d in range(phi_rows(D))],
                       axis=-2)
    return turned * a[..., None, :] * phi_weights(D)[:, None]


def zero_state(batch, kv_heads, head_dim):
    """(S, z) of streams that have seen nothing."""
    rows = phi_rows(head_dim)
    return (jnp.zeros((batch, kv_heads, rows * head_dim, head_dim),
                      jnp.float32),
            jnp.zeros((batch, kv_heads, rows, head_dim), jnp.float32))


def _step(q, k, v, log_c, S, z, live, eps):
    """One token a row against its state, in plain ``jax.numpy``."""
    B, H, _one, D = q.shape
    Hkv, rows = k.shape[1], phi_rows(D)
    c = jnp.exp(log_c.astype(jnp.float32)).reshape(B, Hkv, 1, 1)
    pk = phi(k[:, :, 0])                                   # (B, Hkv, rows, D)
    vf = v[:, :, 0].astype(jnp.float32)
    S5 = S.reshape(B, Hkv, rows, D, D)
    S_new = c[..., None] * S5 + pk[:, :, :, None, :] * vf[:, :, None, :, None]
    z_new = c * z + pk
    if live is not None:
        on = (jnp.asarray(live) != 0).reshape(B, 1, 1, 1)
        S_new = jnp.where(on[..., None], S_new, S5)
        z_new = jnp.where(on, z_new, z)
    pq = phi(q[:, :, 0]).reshape(B, Hkv, H // Hkv, rows, D) / D
    num = jnp.einsum("bhgdi,bhdvi->bhgv", pq, S_new, precision=_HI)
    den = jnp.einsum("bhgdi,bhdi->bhg", pq, z_new, precision=_HI)
    o = (num / (den[..., None] + eps)).reshape(B, H, 1, D)
    return o.astype(q.dtype), S_new.reshape(S.shape), z_new


def _chunked(q, k, v, log_c, state, live, chunk, rows, eps):
    """A run of T tokens a row from ``state`` (None: from nothing),
    ``chunk`` tokens at a time: inside a chunk the scores squared under the
    gates' running product (``rows`` query rows at a time against the
    chunk's keys), before it the state; then the chunk's keys are folded
    into the state (``rows`` at a time). A first chunk from nothing reads no
    state, so a prompt of one chunk never contracts ``phi(q)`` at all: up
    to some 8,000 tokens (the state's rows) the scores are the cheaper way."""
    B, H, T, D = q.shape
    Hkv, G, R = k.shape[1], H // k.shape[1], phi_rows(D)
    C = min(int(chunk), T)
    pad = -T % C
    live = jnp.ones((B, T), bool) if live is None else jnp.asarray(live) != 0
    if pad:
        widen = lambda a, axis: jnp.pad(
            a, [(0, pad) if i == axis else (0, 0) for i in range(a.ndim)])
        q, k, v = (widen(a, 2) for a in (q, k, v))
        log_c, live = widen(log_c, 2), widen(live, 1)
    n = (T + pad) // C
    # the block of query rows, and of keys folded at once: a divisor of C
    W = math.gcd(C, int(rows))
    # a row that is no token neither turns the gate nor adds a key
    log_c = jnp.where(live[:, None, :], log_c.astype(jnp.float32), 0.0)
    chunks = lambda a, axis: jnp.moveaxis(
        a.reshape(a.shape[:axis] + (n, C) + a.shape[axis + 1:]), axis, 0)
    xs = (chunks(q.reshape(B, Hkv, G, T + pad, D), 3), chunks(k, 2),
          chunks(v, 2), chunks(log_c, 2), chunks(live, 1))
    at = jnp.arange(C)

    def fold(state, x, seen_before):
        S, z = state
        S5 = S.reshape(B, Hkv, R, D, D)
        qc, kc, vc, lc, on = x
        vf = vc.astype(jnp.float32)
        L = jnp.cumsum(lc, axis=-1)                          # (B, Hkv, C)

        def read(start):
            qb = jax.lax.dynamic_slice_in_dim(qc, start, W, axis=3)
            Lq = jax.lax.dynamic_slice_in_dim(L, start, W, axis=2)
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qb, kc,
                           preferred_element_type=jnp.float32)
            seen = ((start + at[:W, None] >= at[None, :])[None, None]
                    & on[:, None, None, :])                  # (B, 1, W, C)
            A = jnp.where(seen, jnp.exp(jnp.minimum(
                Lq[..., :, None] - L[..., None, :], 0.0)), 0.0)[:, :, None] \
                * s * s / D
            num = jnp.einsum("bhgqk,bhkv->bhgqv", A, vf)
            den = jnp.sum(A, axis=-1)
            if seen_before:
                before = jnp.exp(Lq)[:, :, None]             # the state's gate
                pq = phi(qb) / D                           # (B,Hkv,G,W,R,D)
                num = num + before[..., None] * jnp.einsum(
                    "bhgqdi,bhdvi->bhgqv", pq, S5)
                den = den + before * jnp.einsum("bhgqdi,bhdi->bhgq", pq, z)
            return num / (den[..., None] + eps)

        o = jax.lax.map(read, jnp.arange(0, C, W))     # (C/W, B,Hkv,G,W,D)
        o = jnp.moveaxis(o, 0, 3).reshape(B, Hkv, G, C, D)
        # the chunk's keys under the gates that follow them in it
        after = jnp.where(on[:, None, :], jnp.exp(L[..., -1:] - L), 0.0)

        def add(state, start):
            S5, z = state
            cut = lambda a: jax.lax.dynamic_slice_in_dim(a, start, W, axis=2)
            pk = phi(cut(kc)) * cut(after)[..., None, None]
            return (S5 + jnp.einsum("bhkdi,bhkv->bhdvi", pk, cut(vf)),
                    z + jnp.sum(pk, axis=2)), None

        end = jnp.exp(L[..., -1])[..., None, None]
        (S5, z), _ = jax.lax.scan(add, (end[..., None] * S5, end * z),
                                  jnp.arange(0, C, W))
        return (S5.reshape(S.shape), z), o

    outs = []
    if state is None:
        # the first chunk starts from nothing and reads no state
        state, o = fold(zero_state(B, Hkv, D), tuple(a[0] for a in xs), False)
        outs, xs = [o[None]], tuple(a[1:] for a in xs)
    if len(outs) < n:
        state, o = jax.lax.scan(lambda st, x: fold(st, x, True), state, xs)
        outs.append(o)
    o = jnp.concatenate(outs)
    o = jnp.moveaxis(o, 0, 3).reshape(B, H, T + pad, D)[:, :, :T]
    return (o.astype(q.dtype),) + tuple(state)


@register_op("power_retention", nondiff=True, n_outputs=3)
def power_retention(q, k, v, log_c, state=None, live=None, *, chunk=CHUNK,
                    rows=ROWS, eps=EPS):
    """Power retention of degree 2 over T tokens a row, from a state and
    into it.

    ``q`` (B, H, T, D); ``k``, ``v`` (B, Hkv, T, D), ``H % Hkv == 0`` (query
    head h reads K/V head ``h // (H // Hkv)``); ``log_c`` (B, Hkv, T), the
    log of each token's gate; ``state`` ``(S, z)`` as :func:`zero_state`
    shapes them, or None for rows that have seen nothing; ``live`` (B, T), or
    (B,) where T is 1, marks the rows that are tokens: a row that is not
    neither turns the gate nor adds to the state, and its output is
    discarded by the caller. Returns ``(o, S, z)``: ``o`` (B, H, T, D) in
    ``q``'s type, the state after the last live token in float32.

    One token a row against a state is the decode step: on a TPU, where the
    head width is one lane tile and no mesh is traced, the Pallas kernel
    ``retention_step`` (a row that is not live is not touched: its state
    costs nothing); elsewhere plain ``jax.numpy`` that a mesh can split.
    More tokens go ``chunk`` at a time, ``rows`` query rows at a time
    inside a chunk (:func:`_chunked`)."""
    B, H, T, D = q.shape
    if T > 1:
        return _chunked(q, k, v, log_c, state, live, chunk, rows, eps)
    S, z = zero_state(B, k.shape[1], D) if state is None else state
    log_c = jnp.reshape(log_c, (B, k.shape[1]))
    if live is not None:
        live = jnp.reshape(live, (B,))
    if is_tpu_backend() and not under_mesh():
        from .pallas import retention_step as _k

        if _k.tiles(q.shape, k.shape):
            return _k.retention_step(q, k, v, log_c, S, z, live, eps=eps)
    return _step(q, k, v, log_c, S, z, live, eps)
