"""The selective state-space mixer of Mamba-2 (Dao, Gu, "Transformers are
SSMs", arXiv:2405.21060), in the pieces a served model needs.

A mixer of ``heads`` heads of width ``P`` reads ``x`` (a head's P values a
token), a step ``dt > 0`` and a decay rate ``A < 0`` a head, and ``B``,
``C`` (``N`` values a token) of ``groups`` groups: head h reads group ``h //
(heads // groups)``. Its state is ``S`` (P, N) a head, in float32:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        y_t = S_t C_t

(the skip ``D x_t`` is the model's). Before it, a depthwise causal conv of
width ``K`` over the channels of ``[x | B | C]`` (:func:`causal_conv`),
whose state is the last ``K - 1`` inputs; after it, a gated RMSNorm by
groups (:func:`gated_rms_norm`).

Two forms of the same recurrence:

- :func:`ssd_scan`, the prefill: the chunked SSD form, ``chunk`` tokens at a
  time. Inside a chunk the output is a masked product ``(C B^T o L) (dt
  x)`` with ``L[l, s] = exp(sum of dt A over s < r <= l)``; between chunks
  the state, carried by a scan over the chunks. Rows that are not tokens
  (past a prompt's length) have ``dt = 0``: they neither decay the state nor
  add to it.
- :func:`ssd_step`, the decode step: one token a slot folded into the
  slot's state and read out of it. On a TPU (no mesh traced) it is the
  Pallas kernel ``ssd_step`` (``ops/pallas/ssd_step.py``), which reads and
  writes the live slots' state once, in place; elsewhere the same arithmetic
  in XLA. A slot that is not live keeps its state.

Everything after the conv (state, decay, products) is float32 whatever the
type of the activations; the contractions run at ``HIGHEST`` precision.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..base import is_tpu_backend, register_op
from .pallas import under_mesh

CHUNK = 128
_HI = jax.lax.Precision.HIGHEST


def causal_conv(x, weight, bias, state=None, live=None):
    """Depthwise causal conv with bias and SiLU, and its state's shift.

    ``x`` (B, T, C); ``weight`` (C, K), ``bias`` (C,); ``state`` (B, K - 1,
    C), the inputs before ``x`` (None: zeros); ``live`` (B, T), or (B,)
    where T is 1: the rows that are tokens, which have to be the first of
    each row. Returns ``(y, state)``: ``y[t] = silu(sum_k weight[:, k] *
    window[t + k] + bias)`` over ``window = [state | x]`` in float32, in
    ``x``'s type; the new state is the last ``K - 1`` inputs up to the last
    live row (the old state where a row has none), in ``state``'s type."""
    Bt, T, Ch = x.shape
    K = weight.shape[1]
    if state is None:
        state = jnp.zeros((Bt, K - 1, Ch), x.dtype)
    window = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    wf = window.astype(jnp.float32)
    w = weight.astype(jnp.float32)
    y = jax.nn.silu(bias.astype(jnp.float32) + sum(
        wf[:, k:k + T] * w[:, k] for k in range(K)))
    n = jnp.full((Bt,), T, jnp.int32) if live is None else jnp.sum(
        jnp.reshape(jnp.asarray(live) != 0, (Bt, -1)).astype(jnp.int32), 1)
    # the window's rows n .. n + K - 2 are the K - 1 inputs before row n
    new = jax.vmap(lambda win, at: jax.lax.dynamic_slice_in_dim(
        win, at, K - 1, axis=0))(window, n)
    return y.astype(x.dtype), new.astype(state.dtype)


def gated_rms_norm(y, z, gamma, *, groups=1, eps=1e-5):
    """``RMS_group(y * silu(z)) * gamma``: statistics by ``groups`` groups
    of the last axis, in float32, one cast to ``z``'s type at the end."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    shape = g.shape
    g = g.reshape(shape[:-1] + (groups, shape[-1] // groups))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return (g.reshape(shape) * gamma.astype(jnp.float32)).astype(z.dtype)


def _by_group(a, groups):
    """(..., H, P) -> (..., G, H / G, P)."""
    return a.reshape(a.shape[:-2] + (groups, a.shape[-2] // groups,
                                     a.shape[-1]))


def ssd_scan(x, dt, A, B, C, live=None, *, chunk=CHUNK):
    """The recurrence over T tokens a row, from a zero state, chunk by
    chunk (the SSD form).

    ``x`` (Bt, T, H, P); ``dt`` (Bt, T, H), the steps after their softplus;
    ``A`` (H,), negative; ``B``, ``C`` (Bt, T, G, N); ``live`` (Bt, T): rows
    that are not tokens add nothing and do not decay the state. Returns
    ``(y, S)``: ``y`` (Bt, T, H, P) float32, without the skip; ``S`` (Bt,
    H, P, N) float32, the state after the last live row."""
    with jax.named_scope("ssd_scan"):
        return _scan(x, dt, A, B, C, live, chunk)


def _scan(x, dt, A, B, C, live, chunk):
    Bt, T, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    L = min(int(chunk), T)
    pad = -T % L
    dt = dt.astype(jnp.float32)
    if live is not None:
        dt = jnp.where(jnp.asarray(live)[..., None] != 0, dt, 0.0)
    f32 = lambda a: a.astype(jnp.float32)
    x, B, C = f32(x), f32(B), f32(C)
    if pad:
        widen = lambda a: jnp.pad(a, [(0, 0), (0, pad)]
                                  + [(0, 0)] * (a.ndim - 2))
        x, dt, B, C = widen(x), widen(dt), widen(B), widen(C)
    n = (T + pad) // L
    chunks = lambda a: a.reshape((Bt, n, L) + a.shape[2:])
    xdt = _by_group(chunks(x * dt[..., None]), G)      # (Bt,n,L,G,Hg,P)
    B, C = chunks(B), chunks(C)                        # (Bt,n,L,G,N)
    dA = chunks(dt * A.astype(jnp.float32))            # (Bt,n,L,H)
    acs = jnp.cumsum(jnp.moveaxis(dA, 2, 3), axis=-1)  # (Bt,n,H,L)
    acs = acs.reshape(Bt, n, G, H // G, L)
    # inside a chunk: decay from s to l, for s <= l
    tri = jnp.tril(jnp.ones((L, L), bool))
    seg = jnp.where(tri, jnp.exp(jnp.minimum(
        acs[..., :, None] - acs[..., None, :], 0.0)), 0.0)  # (..,Hg,L,L)
    cb = jnp.einsum("bclgn,bcsgn->bcgls", C, B, precision=_HI)
    y = jnp.einsum("bcgls,bcghls,bcsghp->bclghp", cb, seg, xdt,
                   precision=_HI)
    # what each chunk adds to the state, decayed to its end
    tail = jnp.exp(acs[..., -1:] - acs)                # (Bt,n,G,Hg,L)
    adds = jnp.einsum("bclgn,bcghl,bclghp->bcghpn", B, tail, xdt,
                      precision=_HI)
    turn = jnp.exp(acs[..., -1])                       # (Bt,n,G,Hg)

    def carry(S, c):
        add, decay = c
        return decay[..., None, None] * S + add, S

    S, before = jax.lax.scan(
        carry, jnp.zeros((Bt, G, H // G, P, N), jnp.float32),
        (jnp.moveaxis(adds, 1, 0), jnp.moveaxis(turn, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)                # (Bt,n,G,Hg,P,N)
    y = y + jnp.einsum("bclgn,bcghpn,bcghl->bclghp", C, before,
                       jnp.exp(acs), precision=_HI)
    y = y.reshape(Bt, n * L, H, P)[:, :T]
    return y, S.reshape(Bt, H, P, N)


def _step_xla(x, dt, A, B, C, S, live):
    """One token a row against its state, in plain ``jax.numpy``."""
    Bt, H, P = x.shape
    G = B.shape[1]
    x, dt = x.astype(jnp.float32), dt.astype(jnp.float32)
    decay = jnp.exp(dt * A.astype(jnp.float32))                 # (Bt, H)
    S5 = S.reshape(Bt, G, H // G, P, -1)
    new = decay.reshape(Bt, G, H // G)[..., None, None] * S5 \
        + _by_group((x * dt[..., None]), G)[..., None] \
        * B.astype(jnp.float32)[:, :, None, None, :]
    if live is not None:
        new = jnp.where((jnp.asarray(live) != 0).reshape(Bt, 1, 1, 1, 1),
                        new, S5)
    y = jnp.sum(new * C.astype(jnp.float32)[:, :, None, None, :], axis=-1)
    return y.reshape(Bt, H, P), new.reshape(S.shape)


def ssd_step(x, dt, A, B, C, S, live=None):
    """One token a slot: ``x`` (Bt, H, P), ``dt`` (Bt, H) after its
    softplus, ``A`` (H,), ``B``, ``C`` (Bt, G, N), ``S`` (Bt, H, P, N)
    float32; ``live`` (Bt,) marks the slots that hold a stream (every slot
    where not given). Returns ``(y, S)``: ``y`` (Bt, H, P) float32, without
    the skip, zeros where a slot is not live; the state, which a slot that
    is not live keeps bit for bit. On a TPU, where no mesh is traced, the
    Pallas kernel ``ssd_step`` (donate ``S``: it is updated where it
    lies)."""
    if is_tpu_backend() and not under_mesh():
        from .pallas import ssd_step as _k

        if _k.tiles(x.shape, B.shape):
            return _k.ssd_step(x, dt, A, B, C, S, live)
    y, S = _step_xla(x, dt, A, B, C, S, live)
    if live is not None:
        y = jnp.where((jnp.asarray(live) != 0)[:, None, None], y, 0.0)
    return y, S


@register_op("mamba_mix", nondiff=True, n_outputs=3)
def mamba_mix(proj, conv_w, conv_b, dt_bias, A_log, D, norm_gamma,
              conv_state=None, S=None, live=None, *, heads, head_dim, groups,
              state_size, eps=1e-5, chunk=CHUNK):
    """The Mamba-2 mixer between its two projections.

    ``proj`` (B, T, 2 heads head_dim + 2 groups state_size + heads): ``[z |
    x B C | dt]``, the input projection's output. The conv (``conv_w`` (C,
    K), ``conv_b``) and SiLU over ``x B C``; ``dt = softplus(dt +
    dt_bias)``, ``A = -exp(A_log)``; the recurrence (:func:`ssd_scan` from a
    zero state where ``S`` is None, else one token a row by
    :func:`ssd_step` from ``S`` and ``conv_state``); ``y + D x``; the gated
    norm by ``groups`` groups. ``live``: (B, T) rows that are tokens, or
    (B,) slots that hold a stream. Returns ``(y, conv_state, S)``: ``y``
    (B, T, heads head_dim) in ``proj``'s type, the conv window and the state
    after the last live row."""
    Bt, T, _ = proj.shape
    inner, bc = heads * head_dim, groups * state_size
    z = proj[..., :inner]
    xbc = proj[..., inner:2 * inner + 2 * bc]
    dt = jax.nn.softplus(proj[..., 2 * inner + 2 * bc:].astype(jnp.float32)
                         + dt_bias.astype(jnp.float32))
    A = -jnp.exp(A_log.astype(jnp.float32))
    xbc, conv_state = causal_conv(xbc, conv_w, conv_b, conv_state, live)
    x = xbc[..., :inner].reshape(Bt, T, heads, head_dim)
    Bm = xbc[..., inner:inner + bc].reshape(Bt, T, groups, state_size)
    Cm = xbc[..., inner + bc:].reshape(Bt, T, groups, state_size)
    if S is None:
        y, S = ssd_scan(x, dt, A, Bm, Cm, live, chunk=chunk)
    else:
        y, S = ssd_step(x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], S, live)
        y = y[:, None]
    y = y + D.astype(jnp.float32)[:, None] * x.astype(jnp.float32)
    y = gated_rms_norm(y.reshape(Bt, T, inner), z, norm_gamma,
                       groups=groups, eps=eps)
    return y, conv_state, S
