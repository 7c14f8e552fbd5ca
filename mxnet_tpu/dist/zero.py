"""ZeRO-2/3 sharded training state (Rajbhandari et al., arXiv 1910.02054;
cross-replica weight-update sharding per Xu et al., arXiv 2004.13336),
layered on the fused-optimizer path:

* stage 1 (pre-existing): ``Trainer.set_weight_update_sharding`` — the
  fused step computes each update on a 1/N replica shard and all-gathers
  the weights; optimizer state lives sharded.
* stage 2: the bucketer's exchanged gradients STAY sharded between
  backward and update (``GradientBucketer(zero=2)`` constrains every
  split-out grad to the same first-divisible-axis shard spec the stepper
  uses, so the update consumes the shard without a reshard).
* stage 3: weights themselves live sharded between steps
  (``Optimizer.fused_update(keep_sharded=True)`` skips the trailing
  all-gather); :class:`Zero3ParamManager` re-gathers them *per bucket, on
  demand* before the next forward — each bucket's gather is one async
  ``device_put`` wave, so later buckets' gathers overlap the forward's
  first layers.

Everything here is placement, not math: an N-step run at any stage must
be bit-comparable (≤1e-6) to the unsharded run — the parity contract
``tests/test_dist.py`` pins.
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..context import current_context
from .bucketer import default_bucket_mb, _nbytes


def shard_spec(shape, nshard, axis):
    """First axis the shard count divides — the SAME placement rule as
    ``optimizer._fused_stepper._spec`` (they must agree or every step pays
    a reshard); tensors too small to split stay replicated."""
    for d, s in enumerate(shape):
        if s >= nshard and s % nshard == 0:
            return P(*([None] * d + [axis]))
    return P()


def _leaf_arrays(state):
    return [l for l in jax.tree_util.tree_leaves(state)
            if hasattr(l, "nbytes")]


def per_device_bytes(tree):
    """Bytes one device actually holds for ``tree`` — the ZeRO memory
    proof (an 8-way sharded state must report ~1/8 of its global size)."""
    total = 0
    for l in _leaf_arrays(tree):
        shards = getattr(l, "addressable_shards", None)
        if shards:
            total += shards[0].data.nbytes
        else:
            total += l.nbytes
    return total


def global_bytes(tree):
    return sum(l.nbytes for l in _leaf_arrays(tree))


class Zero3ParamManager:
    """ZeRO-3 parameter residency: weights live sharded between steps;
    :meth:`gather` rebuilds the replicated copies bucket by bucket before
    a forward (async device_put waves — the on-demand all-gather
    schedule); :meth:`release` returns them to their shards.

    Operates on gluon ``Parameter``s (rebinds ``p.data()._data`` in
    place, the same contract the fused update uses)."""

    def __init__(self, params, mesh, shard_axis="dp", bucket_mb=None):
        self.mesh = mesh
        self.shard_axis = shard_axis
        self.nshard = int(mesh.shape[shard_axis])
        # eager-forward residency target: the default context's device
        # (this process's first chip; meant, also on four chips — the eager
        # forward runs on one device, the mesh holds the shards)
        self.home = current_context().jax_device()
        self.params = [p for p in params
                       if getattr(p, "_data", None) is not None]
        self.gathers = 0
        cap = int((default_bucket_mb() if bucket_mb is None
                   else float(bucket_mb)) * (1 << 20))
        # same greedy size-capped partition as the gradient bucketer, over
        # the (deterministic) parameter list — gather granularity mirrors
        # exchange granularity
        self.buckets, cur, cur_b = [], [], 0
        for p in self.params:
            b = _nbytes(p.shape, p.dtype)
            if cur and cur_b + b > cap:
                self.buckets.append(cur)
                cur, cur_b = [], 0
            cur.append(p)
            cur_b += b
        if cur:
            self.buckets.append(cur)

    def _spec(self, shape):
        return shard_spec(shape, self.nshard, self.shard_axis)

    def _place(self, p, spec):
        nd = p.data()
        tgt = NamedSharding(self.mesh, spec)
        if getattr(nd._data, "sharding", None) == tgt:
            return
        nd._data = jax.device_put(nd._data, tgt)

    def gather_bucket(self, i):
        """All-gather ONE bucket's weights back to the eager home device
        (async device_put — the on-demand all-gather; the eager forward's
        inputs are committed single-device, so that is where 'replicated'
        lives on this path)."""
        for p in self.buckets[i]:
            nd = p.data()
            if len(nd._data.devices()) > 1:
                nd._data = jax.device_put(nd._data, self.home)
        self.gathers += 1

    def gather(self):
        """Schedule every bucket's gather; device_put is async, so bucket
        k+1's gather overlaps whatever consumes bucket k."""
        for i in range(len(self.buckets)):
            self.gather_bucket(i)

    def release(self):
        """Return weights to their shards (a no-op for buffers the
        keep-sharded fused step already left in place)."""
        for p in self.params:
            self._place(p, self._spec(tuple(p.shape)))

    def param_bytes(self):
        """(per-device, global) parameter bytes right now."""
        arrs = [p.data()._data for p in self.params]
        per_dev = 0
        for a in arrs:
            shards = getattr(a, "addressable_shards", None)
            per_dev += shards[0].data.nbytes if shards else a.nbytes
        return per_dev, sum(a.nbytes for a in arrs)
