"""Pipeline parallelism over the 'pp' mesh axis (GPipe schedule).

The reference has no pipeline engine (MXNet model-parallel was manual
ctx-placement); required here for pod-scale models. Implementation: every
device holds ONE stage's params (sharded over 'pp'); activations flow around
the ring with ``lax.ppermute`` inside a ``lax.scan`` over
n_micro + n_stages - 1 ticks — the canonical JAX SPMD pipeline pattern.
Microbatch i enters stage 0 at tick i; outputs collect on the last stage and
are psum-broadcast back (cheap relative to the steady-state compute).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .mesh import shard_map


def pipeline_apply(stage_fn, stage_params, microbatches, mesh, axis_name="pp"):
    """stage_fn(params, x) -> y, same activation shape across stages.

    stage_params: pytree whose leaves have a leading 'stages' dim sharded over
    `axis_name` (leaf shape (n_stages, ...)).
    microbatches: (n_micro, mb, ...) replicated input.
    Returns (n_micro, mb, ...) outputs (replicated).
    """

    def local(params, xs):
        # params leaves: (1, ...) local stage slice; xs: full (n_micro, ...)
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        n_stages = lax.psum(1, axis_name)
        stage = lax.axis_index(axis_name)
        n_micro = xs.shape[0]
        ticks = n_micro + n_stages - 1
        perm = [(j, (j + 1) % n_stages) for j in range(n_stages)]

        state = jnp.zeros_like(xs[0])
        outputs = jnp.zeros((n_micro,) + xs.shape[1:], xs.dtype)

        def tick(carry, t):
            state, outputs = carry
            # stage 0 ingests microbatch t (if in range), others use incoming state
            inject = jnp.where(t < n_micro, t, 0)
            x_in = jnp.where(stage == 0, xs[inject], state)
            y = stage_fn(params, x_in)
            # last stage writes its result for microbatch (t - (n_stages-1))
            out_idx = t - (n_stages - 1)
            write = (stage == n_stages - 1) & (out_idx >= 0)
            outputs = lax.cond(
                write,
                lambda o: lax.dynamic_update_index_in_dim(
                    o, y.astype(o.dtype), jnp.maximum(out_idx, 0), 0),
                lambda o: o, outputs)
            state_next = lax.ppermute(y, axis_name, perm)
            return (state_next, outputs), None

        (_, outputs), _ = lax.scan(tick, (state, outputs), jnp.arange(ticks))
        # broadcast final outputs from last stage to all (psum of masked)
        mask = (stage == n_stages - 1).astype(outputs.dtype)
        outputs = lax.psum(outputs * mask, axis_name)
        return outputs

    pspec = jax.tree_util.tree_map(lambda _: P(axis_name), stage_params,
                                   is_leaf=lambda a: hasattr(a, "shape"))
    f = shard_map(local, mesh, in_specs=(pspec, P()), out_specs=P())
    return f(stage_params, microbatches)


def stack_stage_params(per_stage_params):
    """list of per-stage pytrees (same structure/shapes) → stacked pytree with
    leading stage dim, ready to shard over 'pp'."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_stage_params)


def interleave_stage_params(per_stage_params, n_devices):
    """Megatron virtual-chunk assignment: global stage g lives on device
    g % n_devices as its local chunk g // n_devices. Reorders the stage list
    so sharding the stacked leading dim over 'pp' gives each device ITS
    chunks contiguously: row (d*v + j) = global stage (j*n_devices + d)."""
    G = len(per_stage_params)
    if G % n_devices:
        raise ValueError("n_stages %d not divisible by n_devices %d"
                         % (G, n_devices))
    v = G // n_devices
    order = [j * n_devices + d for d in range(n_devices) for j in range(v)]
    return stack_stage_params([per_stage_params[g] for g in order])


def pipeline_apply_interleaved(stage_fn, stage_params, microbatches, mesh,
                               n_virtual, axis_name="pp"):
    """Interleaved-schedule pipeline forward: each device holds ``n_virtual``
    chunks (global stage g on device g % S — ``interleave_stage_params``
    layout), so every microbatch rides the +1 ``ppermute`` ring v times.
    Returning wavefronts take priority over fresh injection at device 0
    (injection fills the bubbles) — the scan-friendly form of Megatron's
    interleaved 1F1B forward order. Same per-device work as a depth-S*v
    pipeline; the interleave cuts pipeline-fill latency by ~v.

    stage_fn(params, x) -> y, uniform activation shape; stage_params leaves
    (S*v, ...) in interleaved row order, sharded over `axis_name`.
    microbatches (n_micro, mb, ...) replicated; returns (n_micro, ...) after
    ALL S*v stages.
    """
    v = int(n_virtual)
    S = int(mesh.shape[axis_name])
    G = S * v
    n_micro = microbatches.shape[0]
    # packets are never delayed once injected (every arriving packet is
    # processed immediately), so the last microbatch injects by tick
    # (n_micro-1)*v and its output lands G-1 ticks later — verified exact
    # (no undershoot, zero slack) by simulating the schedule over
    # S<=9, v<=5, n_micro<=19
    ticks = (n_micro - 1) * v + G

    def local(params, xs):
        # params leaves arrive as this device's (v, ...) chunk block
        stage = lax.axis_index(axis_name)
        perm = [(j, (j + 1) % S) for j in range(S)]

        zero_x = jnp.zeros_like(xs[0])
        outputs = jnp.zeros((n_micro,) + xs.shape[1:], xs.dtype)

        def tick(carry, _):
            rx, rg, rmb, n_inj, outputs = carry
            # device 0: returning wavefront (rg >= 0) beats fresh injection
            ring_valid = rg >= 0
            can_inject = (stage == 0) & (~ring_valid) & (n_inj < n_micro)
            g = jnp.where(ring_valid, jnp.maximum(rg, 0),
                          jnp.where(can_inject, 0, -1))
            mb = jnp.where(ring_valid, rmb,
                           jnp.where(can_inject, n_inj, -1))
            x_in = jnp.where(ring_valid, rx,
                             xs[jnp.clip(mb, 0, n_micro - 1)])
            n_inj = n_inj + can_inject

            chunk = jnp.clip(g // S, 0, v - 1)
            p = jax.tree_util.tree_map(
                lambda a: lax.dynamic_index_in_dim(a, chunk, 0,
                                                   keepdims=False), params)
            y = stage_fn(p, x_in)
            valid = g >= 0
            g_next = jnp.where(valid, g + 1, -1)
            done = valid & (g_next == G)
            outputs = lax.cond(
                done,
                lambda o: lax.dynamic_update_index_in_dim(
                    o, y.astype(o.dtype), jnp.clip(mb, 0, n_micro - 1), 0),
                lambda o: o, outputs)
            send_g = jnp.where(valid & ~done, g_next, -1)
            send_mb = jnp.where(valid & ~done, mb, -1)
            send_x = jnp.where(valid & ~done, y, zero_x)
            rx2, rg2, rmb2 = lax.ppermute((send_x, send_g, send_mb),
                                          axis_name, perm)
            return (rx2, rg2, rmb2, n_inj, outputs), None

        init = (zero_x, jnp.int32(-1), jnp.int32(-1), jnp.int32(0), outputs)
        carry, _ = lax.scan(tick, init, None, length=ticks)
        outputs = carry[-1]
        # results were written on device (G-1) % S == S-1; broadcast
        mask = (stage == S - 1).astype(outputs.dtype)
        return lax.psum(outputs * mask, axis_name)

    pspec = jax.tree_util.tree_map(lambda _: P(axis_name), stage_params,
                                   is_leaf=lambda a: hasattr(a, "shape"))
    f = shard_map(local, mesh, in_specs=(pspec, P()), out_specs=P())
    return f(stage_params, microbatches)


def pipeline_train_step_1f1b(stage_fn, loss_fn, stage_params, microbatches,
                             targets, mesh, axis_name="pp",
                             batch_axis=None, param_spec=None):
    """One-forward-one-backward (PipeDream-flush) pipelined training step.

    Unlike the GPipe schedule above (all forwards, then differentiate through
    the whole scan — activations for every microbatch live simultaneously),
    1F1B starts each microbatch's backward as soon as the last stage finishes
    its forward, so a stage stashes at most ``n_stages`` activations
    regardless of microbatch count. The reference has no pipeline engine
    (MXNet model-parallel was manual ctx placement); this is the schedule its
    large-model users got from DeepSpeed/PipeDream, rebuilt SPMD-style: a
    global tick clock where every tick has an F-slot (activations ride a
    +1 ``ppermute`` ring) and a B-slot (cotangents ride a -1 ring), stage 0
    throttling injection to keep ≤ n_stages microbatches in flight.

    stage_fn(params, x) -> y with y.shape == x.shape (uniform stages);
    loss_fn(y, target) -> scalar (per-microbatch mean).
    stage_params: leaves (n_stages, ...) sharded over `axis_name`.
    microbatches: (n_micro, mb, ...); targets: (n_micro, ...) replicated —
    except with ``batch_axis``, where BOTH microbatches and targets must be
    (n_micro, mb, ...) with mb divisible by the data-axis size (they shard
    together along axis 1).
    Returns (loss, grads) — loss the scalar mean over microbatches, grads
    stacked (n_stages, ...) like stage_params.

    COMPOSITION (Megatron-style dp x tp x pp on ONE mesh): pass
    ``batch_axis="dp"`` to shard the per-microbatch batch dim over a data
    axis (loss/grads pmean over it — each dp rank pipelines its slice of
    every microbatch), and ``param_spec`` (a pytree of PartitionSpecs whose
    leading dim is `axis_name`) to ALSO shard stage weights over a tensor
    axis; stage_fn then closes the tp math with its own lax.psum("tp"),
    exactly like a non-pipelined tp layer.
    """
    n_micro = microbatches.shape[0]

    def local(params, xs, tgts):
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        n_stages = lax.psum(1, axis_name)
        stage = lax.axis_index(axis_name)
        last = stage == n_stages - 1
        K = int(mesh.shape[axis_name]) + 2  # stash ring capacity (static)
        ticks = n_micro + 3 * int(mesh.shape[axis_name]) + 3
        perm_f = [(j, (j + 1) % int(mesh.shape[axis_name]))
                  for j in range(int(mesh.shape[axis_name]))]
        perm_b = [(j, (j - 1) % int(mesh.shape[axis_name]))
                  for j in range(int(mesh.shape[axis_name]))]

        xshape = xs.shape[1:]
        zero_x = jnp.zeros(xshape, xs.dtype)
        zero_g = jax.tree_util.tree_map(jnp.zeros_like, params)

        def tick(carry, _):
            (fx, f_mb, gx, b_mb, stash_x, head, count,
             n_inj, n_done, loss_sum, gparams) = carry

            # ---- F-slot -------------------------------------------------
            inject_ok = (stage == 0) & (n_inj < n_micro) & (n_inj - n_done < n_stages)
            f_valid = jnp.where(stage == 0, inject_ok, f_mb >= 0)
            mbi = jnp.where(stage == 0, jnp.minimum(n_inj, n_micro - 1),
                            jnp.maximum(f_mb, 0))
            x_in = jnp.where(stage == 0, xs[mbi], fx)
            pos = jnp.mod(head, K)
            stash_x = jnp.where(f_valid,
                                lax.dynamic_update_index_in_dim(stash_x, x_in, pos, 0),
                                stash_x)
            head = head + f_valid
            count = count + f_valid
            n_inj = n_inj + inject_ok

            y = stage_fn(params, x_in)
            send_mb = jnp.where(f_valid & (stage < n_stages - 1), mbi, -1)
            fx_next, f_mb_next = lax.ppermute((y, send_mb), axis_name, perm_f)

            # ---- B-slot -------------------------------------------------
            b_valid = jnp.where(last, f_valid, b_mb >= 0)
            b_idx = jnp.where(last, mbi, jnp.maximum(b_mb, 0))
            pop_pos = jnp.mod(head - count, K)
            x_old = stash_x[pop_pos]
            count = count - b_valid

            y2, pull = jax.vjp(stage_fn, params, x_old)
            tgt = tgts[b_idx]
            loss_val, loss_pull = jax.vjp(lambda yy: loss_fn(yy, tgt), y2)
            seed = loss_pull(jnp.asarray(1.0 / n_micro, loss_val.dtype))[0]
            gy = jnp.where(last, seed.astype(gx.dtype), gx)
            dparams, dx = pull(gy.astype(y2.dtype))

            mask = b_valid.astype(loss_sum.dtype)
            loss_sum = loss_sum + jnp.where(last & b_valid, loss_val, 0.0)
            gparams = jax.tree_util.tree_map(
                lambda acc, d: acc + d * mask.astype(d.dtype), gparams, dparams)
            n_done = n_done + b_valid

            send_b = jnp.where(b_valid & (stage > 0), b_idx, -1)
            gx_next, b_mb_next = lax.ppermute((dx, send_b), axis_name, perm_b)

            return (fx_next, f_mb_next, gx_next, b_mb_next, stash_x,
                    head, count, n_inj, n_done, loss_sum, gparams), None

        init = (zero_x, jnp.int32(-1), zero_x, jnp.int32(-1),
                jnp.zeros((K,) + xshape, xs.dtype),
                jnp.int32(0), jnp.int32(0), jnp.int32(0), jnp.int32(0),
                jnp.float32(0.0), zero_g)
        carry, _ = lax.scan(tick, init, None, length=ticks)
        loss_sum, gparams = carry[-2], carry[-1]
        loss = lax.psum(loss_sum, axis_name) / n_micro
        if batch_axis is not None:
            # every dp rank pipelined an equal batch slice of each
            # microbatch; per-microbatch loss_fn means over the local
            # slice, so the global numbers are the dp-mean
            loss = lax.pmean(loss, batch_axis)
            gparams = jax.tree_util.tree_map(
                lambda g: lax.pmean(g, batch_axis), gparams)
        gparams = jax.tree_util.tree_map(lambda g: g[None], gparams)
        return loss, gparams

    if param_spec is not None:
        # every leaf must shard its leading (stage) dim over axis_name, or
        # the per-rank `a[0]` below would silently run stage 0's weights on
        # every pipeline stage
        for spec in jax.tree_util.tree_leaves(
                param_spec, is_leaf=lambda s: isinstance(s, P)):
            if not len(spec) or spec[0] != axis_name:
                raise ValueError(
                    "param_spec leaf %r must lead with %r (the stage dim)"
                    % (spec, axis_name))
    pspec = param_spec if param_spec is not None else \
        jax.tree_util.tree_map(lambda _: P(axis_name), stage_params,
                               is_leaf=lambda a: hasattr(a, "shape"))
    bspec = P(None, batch_axis) if batch_axis is not None else P()
    f = shard_map(local, mesh, in_specs=(pspec, bspec, bspec),
                  out_specs=(P(), pspec))
    return f(stage_params, microbatches, targets)
