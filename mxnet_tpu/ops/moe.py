"""The served expert layer: one chip's share of a mixture of experts.

``expert_ffn`` is told which experts it holds (``first_expert`` and the
leading axis of the weights), routes every token over ALL experts (sigmoid
scores, the ``top_k`` largest (of the scores plus a selection bias, where
the model has one), weights normalised over the chosen), and
returns the part of ``sum_e w_e FFN_e(h)`` that its own experts contribute:
what a deployment with the experts spread over several chips sums across
them. No token is dropped and no capacity is set: the rows routed here are
sorted by expert, each expert's run is padded to whole row tiles, and one
grouped matmul (``ops/pallas/moe_ffn.py`` on a TPU) computes them, so the
work follows the tokens routed here and not tokens x experts held. It runs
without any exchange; ``parallel/expert_parallel.py`` (top-1, fixed
capacity, token dropping, under ``shard_map``) is another layer and is not
on the served path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..base import is_tpu_backend, register_op
from .pallas import under_mesh

_CHUNK = 2048      # tokens routed at once: bounds the sorted rows' buffers


def _row_tile(n_tokens, dtype):
    """Rows a tile: one sublane tile of the type in a decode step (an
    expert then rarely has more than one tile), 256 in a prefill (an
    expert's matrices stream through once a tile)."""
    return 256 if n_tokens > 256 else 32 // jnp.dtype(dtype).itemsize


def route(h, router_w, top_k, scale=1.0, bias=None):
    """Scores over all experts, in float32 whatever ``h`` is: sigmoid of
    the router's logits, the ``top_k`` largest, their weights normalised to
    sum to one and, where the model has a ``routed_scaling_factor``, times
    that ``scale``. A selection ``bias`` (experts,) (DeepSeek-V3's
    ``e_score_correction_bias``) picks the experts by ``score + bias``; the
    weights are still the unbiased scores of the picked. Returns (weights
    (N, k) float32, experts (N, k) int32)."""
    logits = jnp.dot(h.astype(jnp.float32), router_w.astype(jnp.float32).T,
                     precision=jax.lax.Precision.HIGHEST)
    score = jax.nn.sigmoid(logits)
    if bias is None:
        score, expert = jax.lax.top_k(score, top_k)
    else:
        _biased, expert = jax.lax.top_k(
            score + bias.astype(jnp.float32), top_k)
        score = jnp.take_along_axis(score, expert, axis=-1)
    weight = score / jnp.sum(score, axis=-1, keepdims=True)
    if scale != 1.0:
        weight = weight * scale
    return weight, expert.astype(jnp.int32)


def _grouped_ffn_xla(x, tile_expert, tile_valid, w_gate, w_up, w_down, tm,
                     act="swiglu"):
    """The kernel's arithmetic as XLA ops (off the TPU, under a mesh):
    every tile against its own expert's matrices."""
    M, d = x.shape
    xt = x.reshape(M // tm, tm, d)
    if act == "swiglu":
        g = jnp.einsum("tmd,tfd->tmf", xt, w_gate[tile_expert],
                       preferred_element_type=jnp.float32)
        u = jnp.einsum("tmd,tfd->tmf", xt, w_up[tile_expert],
                       preferred_element_type=jnp.float32)
        a = (g * jax.nn.sigmoid(g) * u).astype(x.dtype)
    else:
        u = jnp.einsum("tmd,tfd->tmf", xt, w_up[tile_expert],
                       preferred_element_type=jnp.float32)
        a = jnp.square(jnp.maximum(u, 0.0)).astype(x.dtype)
    y = jnp.einsum("tmf,tfd->tmd", a, w_down[tile_expert],
                   preferred_element_type=jnp.float32)
    y = jnp.where(tile_valid[:, None, None] > 0, y, 0.0)
    return y.reshape(M, d).astype(x.dtype)


def _local_part(h, live, rows, router_w, w_gate, w_up, w_down, first_expert,
                top_k, routed_scale=1.0, bias=None, act="swiglu"):
    N = h.shape[0]
    d = rows.shape[1]
    held, f = w_up.shape[0], w_up.shape[1]
    weight, expert = route(h, router_w, top_k, routed_scale, bias)
    here = (expert >= first_expert) & (expert < first_expert + held) \
        & (live[:, None] > 0)
    # a pick's group: its expert's index among those held, or ``held`` for
    # a pick that is computed elsewhere (or belongs to a dead row)
    group = jnp.where(here, expert - first_expert, held).reshape(-1)
    count = jnp.sum(jax.nn.one_hot(group, held + 1, dtype=jnp.int32), axis=0)
    n_here = count[:held]
    load = jnp.concatenate(
        [n_here, (jnp.sum((live > 0).astype(jnp.int32)) * top_k
                  - jnp.sum(n_here))[None]])

    tm = _row_tile(N, rows.dtype)
    # every expert's run of rows is padded to whole tiles; the static grid
    # is the worst case (every pick here, every run one row over a tile)
    n_tiles = -(-N * min(top_k, held) // tm) + held
    tiles_of = -(-n_here // tm)
    tile_end = jnp.cumsum(tiles_of)                       # (held,)
    row_start = (tile_end - tiles_of) * tm                # padded, per expert
    pick_start = jnp.cumsum(n_here) - n_here              # sorted, per expert
    t = jnp.arange(n_tiles, dtype=jnp.int32)
    tile_valid = (t < tile_end[-1]).astype(jnp.int32)
    tile_expert = jnp.minimum(
        jnp.sum((tile_end[None, :] <= t[:, None]).astype(jnp.int32), axis=1),
        held - 1)
    # a tile past the last routed one names that one's expert: nothing new
    # is fetched for it
    tile_expert = jnp.where(
        tile_valid > 0, tile_expert,
        tile_expert[jnp.maximum(tile_end[-1] - 1, 0)]).astype(jnp.int32)

    order = jnp.argsort(group, stable=True)               # sorted rank -> pick
    rank = jnp.argsort(order)                             # pick -> sorted rank
    # padded row -> the token it holds
    m = jnp.arange(n_tiles * tm, dtype=jnp.int32)
    e_m = tile_expert[m // tm]
    r_m = m - row_start[e_m]
    row_live = (tile_valid[m // tm] > 0) & (r_m < n_here[e_m])
    pick = order[jnp.clip(pick_start[e_m] + r_m, 0, N * top_k - 1)]
    x = jnp.where(row_live[:, None], rows[pick // top_k],
                  0).astype(rows.dtype)

    if is_tpu_backend() and not under_mesh():
        from .pallas import moe_ffn as _k

        if not _k.tiles(tm, d, f, rows.dtype, act):
            raise ValueError(
                "expert_ffn: row tiles of %d at width %d and inner width "
                "%d do not map onto the grouped kernel's blocks"
                % (tm, d, f))
        y = _k.moe_ffn(x, tile_expert, tile_valid, w_gate, w_up, w_down, tm,
                       act=act)
    else:
        y = _grouped_ffn_xla(x, tile_expert, tile_valid, w_gate, w_up,
                             w_down, tm, act)

    # pick -> its padded row; the weighted sum over a token's local picks,
    # one pick of every token at a time (N rows gathered, not N x k)
    g = jnp.minimum(group, held - 1)
    at = jnp.clip(row_start[g] + rank - pick_start[g], 0,
                  n_tiles * tm - 1).reshape(N, top_k)
    out = jnp.zeros((N, d), jnp.float32)
    for j in range(top_k):
        out = out + jnp.where(here[:, j:j + 1],
                              y[at[:, j]].astype(jnp.float32)
                              * weight[:, j:j + 1], 0.0)
    return out.astype(rows.dtype), load


def _in_chunks(fn, *rows):
    """``fn`` over arrays that share a leading token axis: at once, or,
    past ``_CHUNK`` tokens (in whole chunks), a chunk at a time under
    ``lax.map``. Results with that leading axis are laid end to end again;
    others (a chunk's load) come back one a chunk."""
    N = rows[0].shape[0]
    if N <= _CHUNK or N % _CHUNK:
        return fn(*rows)
    out = jax.lax.map(lambda xs: fn(*xs), tuple(
        r.reshape((N // _CHUNK, _CHUNK) + r.shape[1:]) for r in rows))
    return jax.tree_util.tree_map(
        lambda o: o.reshape((N,) + o.shape[2:])
        if o.ndim >= 2 and o.shape[1] == _CHUNK else o, out)


@register_op("gated_ffn")
def gated_ffn(h, w_gate, w_up, w_down):
    """``(silu(h Wg^T) * (h Wu^T)) Wd^T`` for ``h`` (N, d) with ``w_gate``,
    ``w_up`` (f, d) and ``w_down`` (d, f) (``nn.Dense`` layouts), the
    inner activations in ``h``'s type. More than ``_CHUNK`` tokens go
    through in chunks, so that a long prefill's inner activations
    (N x f, twice) never stand whole."""
    def ffn(x):
        a = jax.nn.silu(jnp.dot(x, w_gate.T)) * jnp.dot(x, w_up.T)
        return jnp.dot(a, w_down.T)

    return _in_chunks(ffn, h)


@register_op("relu2_ffn")
def relu2_ffn(h, w_up, w_down):
    """``relu(h Wu^T)^2 Wd^T`` for ``h`` (N, d) with ``w_up`` (f, d) and
    ``w_down`` (d, f), the inner activations in ``h``'s type; in chunks as
    :func:`gated_ffn`."""
    def ffn(x):
        return jnp.dot(jnp.square(jax.nn.relu(jnp.dot(x, w_up.T))), w_down.T)

    return _in_chunks(ffn, h)


@register_op("expert_ffn", nondiff=True, n_outputs=2)
def expert_ffn(h, router_w, w_gate, w_up, w_down, live=None, *,
               first_expert=0, top_k=8, routed_scale=1.0, act="swiglu",
               bias=None, rows=None):
    """One chip's part of a routed expert layer.

    ``h`` (N, d) tokens; ``router_w`` (experts, d), all experts of the
    layer; ``w_gate``, ``w_up``, ``w_down`` (held, f, d'), the experts
    ``first_expert .. first_expert + held - 1`` (all three with the
    experts' width last). ``act`` "swiglu": ``FFN_e(r) = (silu(r Wg[e]^T) *
    (r Wu[e]^T)) Wd[e]``; "relu2": ``relu(r Wu[e]^T)^2 Wd[e]``, no gate
    (``w_gate`` None). The experts read ``rows`` (N, d'), where given (a
    latent MoE's projection of ``h``: the router reads ``h`` and the
    experts the latent), else ``h``. ``live`` (N,) marks the rows that are
    tokens (pad rows and free slots route nowhere and load no expert).
    Routing is :func:`route`, in float32, its weights times
    ``routed_scale``, the experts picked by the scores plus ``bias`` where
    given. Returns ``(out, load)``: ``out`` (N, d') is ``sum over the chosen
    experts held here of w_e FFN_e(r)`` (zero for a token none of whose
    experts is here), ``load`` (held + 1,) int32 counts the picks that went
    to each expert held and, last, those that went to experts held
    elsewhere."""
    N = h.shape[0]
    live = jnp.ones((N,), jnp.int32) if live is None \
        else jnp.asarray(live).astype(jnp.int32)
    part = lambda x, l, r: _local_part(
        x, l, r, router_w, w_gate, w_up, w_down, first_expert, top_k,
        routed_scale, bias, act)
    if rows is None:
        out, load = _in_chunks(lambda x, l: part(x, l, x), h, live)
    else:
        out, load = _in_chunks(part, h, live, rows)
    return out, load if load.ndim == 1 else jnp.sum(load, axis=0)
