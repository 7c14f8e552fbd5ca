"""Compiled distributed train steps (dp / fsdp).

This is the performance path that replaces the reference's
KVStore-push/pull-per-parameter training loop (ref: python/mxnet/gluon/
trainer.py:step + src/kvstore/kvstore_nccl.cc): ONE jitted XLA program per
step containing forward, backward, gradient all-reduce (inserted by the SPMD
partitioner over the 'dp' axis — rides ICI), optimizer update, and donated
parameter buffers (no realloc per step; MXNet needed its memory pool for
this). bf16 compute + fp32 master weights comes from optimizer
multi_precision.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .mesh import make_mesh, use_mesh


def tree_optimizer_step(optimizer):
    """Lift our per-param Optimizer into a pytree update (one fused XLA
    program; the per-index API stays for MXNet parity)."""
    step = optimizer._stepper()

    def init_states(params):
        return jax.tree_util.tree_map(
            lambda p: optimizer.create_state(0, _Box(p)), params)

    def apply(params, grads, states, lr, wd, t):
        leaves_p, treedef = jax.tree_util.tree_flatten(params)
        leaves_g = treedef.flatten_up_to(grads)
        leaves_s = treedef.flatten_up_to(states)
        new_p, new_s = [], []
        for p, g, s in zip(leaves_p, leaves_g, leaves_s):
            np_, ns_ = step(p, g, s, lr, wd, t)
            new_p.append(np_)
            new_s.append(ns_)
        return (jax.tree_util.tree_unflatten(treedef, new_p),
                jax.tree_util.tree_unflatten(treedef, new_s))

    return init_states, apply


class _Box:
    """Minimal NDArray-like shim so Optimizer.create_state sees .dtype/_data."""

    def __init__(self, a):
        self._data = a

    @property
    def dtype(self):
        return self._data.dtype

    @property
    def shape(self):
        return self._data.shape


def weight_update_spec(shape, mesh, axis="dp"):
    """PartitionSpec sharding the first axis of ``shape`` that the replica
    count divides (ZeRO-1 weight-update sharding, Xu et al., arXiv
    2004.13336); replicated when no axis divides."""
    n = mesh.shape[axis]
    for d, s in enumerate(shape):
        if s >= n and s % n == 0:
            return P(*([None] * d + [axis]))
    return P()


def build_train_step(loss_fn, optimizer, mesh=None, param_spec=None,
                     batch_spec=None, donate=True, remat=False,
                     shard_weight_update=False, shard_axis="dp"):
    """Build ``step(params, states, opt_t, key, batch) -> (params, states, loss)``.

    - loss_fn(params, batch, key) -> scalar loss (pure; bf16 inside as desired)
    - mesh: jax Mesh; batch sharded over 'dp' (default), params per param_spec
      (None = replicated; or a pytree/PartitionSpec for fsdp/tp).
    - remat: wrap loss_fn in jax.checkpoint to trade FLOPs for HBM.
    - shard_weight_update: opt-in ZeRO-1-style cross-replica weight-update
      sharding (Xu et al., arXiv 2004.13336). The optimizer update is
      constrained to 1/N shards along ``shard_axis`` — the partitioner turns
      the gradient all-reduce into reduce-scatter, each replica updates its
      weight shard, and the updated weights all-gather back; optimizer state
      stays sharded across replicas between steps (so the first post-build
      call, which receives replicated states, compiles once more than the
      steady state). Requires ``mesh``.
    """
    if remat:
        loss_fn = jax.checkpoint(loss_fn)
    if shard_weight_update and mesh is None:
        raise ValueError("shard_weight_update=True requires a mesh")

    def _wu_con(x):
        spec = weight_update_spec(getattr(x, "shape", ()), mesh, shard_axis)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    def step(params, states, t, key, batch):
        lr = optimizer.learning_rate
        loss, grads = jax.value_and_grad(loss_fn)(params, batch, key)
        _, apply = tree_optimizer_step(optimizer)
        if shard_weight_update:
            tmap = jax.tree_util.tree_map
            params_u = tmap(_wu_con, params)
            grads = tmap(_wu_con, grads)
            states = tmap(_wu_con, states)
            new_params, new_states = apply(params_u, grads, states,
                                           jnp.float32(lr),
                                           jnp.float32(optimizer.wd), t)
            new_params = tmap(lambda x: jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P())), new_params)
        else:
            new_params, new_states = apply(params, grads, states,
                                           jnp.float32(lr),
                                           jnp.float32(optimizer.wd), t)
        return new_params, new_states, loss

    if mesh is None:
        return jax.jit(step, donate_argnums=(0, 1) if donate else ())

    single_device_step = step

    def step(params, states, t, key, batch):
        # traced under the mesh: ops that consult it (tensor_parallel.
        # constrain, the pallas gates — a Mosaic kernel cannot be
        # partitioned) see what the program is compiled for
        with use_mesh(mesh):
            return single_device_step(params, states, t, key, batch)

    bspec = batch_spec if batch_spec is not None else P("dp")
    pspec = param_spec if param_spec is not None else P()

    def _sh(spec):
        return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), spec,
                                      is_leaf=lambda s: isinstance(s, P))

    # states sharding is left unspecified (XLA propagates from params);
    # t/key are replicated scalars.
    return jax.jit(step,
                   in_shardings=(_sh(pspec), None, None, None, _sh(bspec)),
                   donate_argnums=(0, 1) if donate else ())


def replicate_params(params, mesh):
    return jax.device_put(params, NamedSharding(mesh, P()))


def shard_batch(batch, mesh, axis="dp"):
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, NamedSharding(mesh, P(axis))), batch)


def block_loss_fn(block, loss_block, training=True):
    """Adapt a hybridizable Gluon block + loss into a pure
    ``loss_fn(params_list, (x, y), key)`` for build_train_step. params_list
    order follows block.collect_params()."""
    from .. import _trace

    plist = list(block.collect_params().values())

    def loss_fn(param_arrays, batch, key):
        x, y = batch
        with _trace.trace_scope(key, training) as tctx:
            tctx.param_store = {id(p): a for p, a in zip(plist, param_arrays)}
            out = block._call_traced(x)
            loss = loss_block._call_traced(out, y)
        return jnp.mean(loss)

    return loss_fn, plist
