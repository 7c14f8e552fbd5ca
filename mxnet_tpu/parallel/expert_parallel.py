"""Expert parallelism: switch-style MoE over the 'ep' mesh axis.

Not in the reference (MXNet predates MoE serving at scale); first-class here
because EP is one of the standard pod-scale axes. Design: top-1 routing with
fixed capacity (static shapes — XLA requirement), dispatch/combine as one-hot
matmuls (MXU-friendly, the classic Switch/GShard formulation), and
``lax.all_to_all`` over 'ep' to move token slots to their expert's device —
the ICI-riding equivalent of the reference's (nonexistent) NCCL alltoall.

Layout: tokens sharded over 'ep' (each device owns a token shard AND one
expert group); experts' FFN weights sharded over 'ep' on the expert dim.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .mesh import shard_map


def _moe_local(x, router_w, w1, w2, *, axis_name, capacity, mean_axes):
    """Per-device: x (t, C) local tokens; router_w (C, E);
    w1 (e_local, C, H); w2 (e_local, H, C)."""
    n = lax.psum(1, axis_name)
    t, C = x.shape
    E = router_w.shape[1]
    e_local = w1.shape[0]
    cap = capacity

    logits = x @ router_w                       # (t, E)
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)         # (t,)
    gate = jnp.max(probs, axis=-1)              # (t,)

    # position of each token within its expert's capacity buffer
    onehot = jax.nn.one_hot(expert, E, dtype=jnp.int32)          # (t, E)
    pos_in_expert = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=-1) - 1  # (t,)
    keep = pos_in_expert < cap

    # dispatch tensor (t, E, cap): token→(expert, slot), dropped tokens zeroed
    disp = (jax.nn.one_hot(expert, E)[:, :, None] *
            jax.nn.one_hot(jnp.clip(pos_in_expert, 0, cap - 1), cap)[:, None, :] *
            keep[:, None, None].astype(x.dtype))                 # (t, E, cap)
    slots = jnp.einsum("tec,td->ecd", disp, x)                   # (E, cap, C)

    # ship slots: split the expert dim across devices; my device receives its
    # experts' slots from every source device → (e_local, n*cap, C)
    slots = lax.all_to_all(slots, axis_name, split_axis=0, concat_axis=1,
                           tiled=True)

    # expert FFN on the MXU
    h = jax.nn.relu(jnp.einsum("esd,edh->esh", slots, w1))
    y = jnp.einsum("esh,ehd->esd", h, w2)                        # (e_local, n*cap, C)

    # return slots to their source device: inverse all_to_all
    y = lax.all_to_all(y, axis_name, split_axis=1, concat_axis=0, tiled=True)
    # back to (E, cap, C) with experts in global order

    # combine with gates
    out = jnp.einsum("tec,ecd->td", disp, y) * gate[:, None]
    # the Switch aux loss is defined over the GLOBAL batch: average across
    # every shard (ep, and dp when composed) so the P() out-spec's
    # one-device copy is the true global value
    aux = lax.pmean(_load_balance_loss(probs, onehot, E), mean_axes)
    return out.astype(x.dtype), aux


def _load_balance_loss(probs, onehot, E):
    """Switch-transformer auxiliary loss: E * Σ_e f_e · p_e."""
    f = jnp.mean(onehot.astype(jnp.float32), axis=0)
    p = jnp.mean(probs, axis=0)
    return E * jnp.sum(f * p)


def moe_ffn(x, router_w, w1, w2, mesh, axis_name="ep", capacity_factor=2.0,
            batch_axis=None):
    """x: (T, C) tokens sharded over `axis_name`; router_w (C, E) replicated;
    w1 (E, C, H), w2 (E, H, C) sharded over `axis_name` on dim 0.
    Returns (y (T, C) sharded like x, aux_loss scalar).

    With ``batch_axis`` (ep × dp composition) tokens shard over BOTH axes —
    each dp replica routes its batch shard through its own ep all-to-all
    against the dp-replicated experts, the standard MoE data-parallel
    layout; the aux loss is pmean'd to the global value either way."""
    n = mesh.shape[axis_name]
    E = router_w.shape[1]
    assert E % n == 0, "num experts must divide ep axis"
    shards = n * (mesh.shape[batch_axis] if batch_axis else 1)
    t_local = x.shape[0] // shards
    capacity = max(1, int(capacity_factor * t_local / E))
    token_spec = (P((batch_axis, axis_name), None) if batch_axis
                  else P(axis_name, None))
    mean_axes = (batch_axis, axis_name) if batch_axis else (axis_name,)
    f = shard_map(functools.partial(_moe_local, axis_name=axis_name,
                                    capacity=capacity, mean_axes=mean_axes),
                  mesh=mesh,
                  in_specs=(token_spec, P(), P(axis_name, None, None),
                            P(axis_name, None, None)),
                  out_specs=(token_spec, P()))
    y, aux = f(x, router_w, w1, w2)
    return y, jnp.mean(aux)
