"""Device prefetcher: double-buffer host→HBM transfers.

MXNet hides H2D copies inside the ThreadedEngine's IO streams; with JAX the
equivalent is issuing ``jax.device_put`` for batch N+1 while the device still
computes batch N (transfers are async). This wrapper gives any DataLoader
(or plain batch iterable) that overlap with one line.

Placement targets (``ctx``):

* ``None`` / a single Context / a single jax device — every array goes to
  that one device (on a CPU-only host this is a same-device no-op);
* a ``jax.sharding.Sharding`` (e.g. ``NamedSharding(mesh, P("dp"))``) —
  each array becomes ONE global array laid out across the mesh, the input
  convention of pjit-style data-parallel steps (parallel.build_train_step);
* a list/tuple of Contexts/devices — each array is split into
  ``len(ctx)`` contiguous shards along axis 0 and device_put per shard, so
  the batch entry becomes a list of per-device NDArrays, mirroring
  ``gluon.utils.split_and_load`` for multi-device gluon loops. All the
  shard transfers are issued back-to-back (async), overlapping with the
  consumer's compute on the previous batch.
"""
from __future__ import annotations

import jax

from ...context import current_context
from ...ndarray import NDArray

__all__ = ["DevicePrefetcher"]


def _as_device(c):
    return c.jax_device() if hasattr(c, "jax_device") else c


def _put_one(x, target):
    if not isinstance(x, NDArray):
        return x
    if isinstance(target, jax.sharding.Sharding):
        return NDArray(jax.device_put(x._data, target))
    if isinstance(target, list):
        data = x._data
        n = len(target)
        rows = data.shape[0]
        # contiguous even-as-possible split along axis 0 (split_and_load's
        # even_split=False behavior: the last shard absorbs the remainder)
        step = max(1, rows // n)
        shards = []
        for k, dev in enumerate(target):
            lo = k * step
            hi = rows if k == n - 1 else min(rows, (k + 1) * step)
            shards.append(NDArray(jax.device_put(data[lo:hi], dev)))
        return shards
    return NDArray(jax.device_put(x._data, target))


def _put(batch, target):
    if isinstance(batch, (list, tuple)):
        return type(batch)(_put_one(b, target) for b in batch)
    return _put_one(batch, target)


class DevicePrefetcher:
    def __init__(self, loader, ctx=None):
        self._loader = loader
        if ctx is None:
            # where eager arrays live: the default context's device (this
            # process's first chip), not jax.devices()[0], which under
            # multi-controller jax is host 0's
            self._target = current_context().jax_device()
        elif isinstance(ctx, jax.sharding.Sharding):
            self._target = ctx
        elif isinstance(ctx, (list, tuple)):
            self._target = [_as_device(c) for c in ctx]
        else:
            self._target = _as_device(ctx)

    def __len__(self):
        return len(self._loader)

    def __iter__(self):
        it = iter(self._loader)
        try:
            ahead = _put(next(it), self._target)  # transfer starts async
        except StopIteration:
            return
        for batch in it:
            nxt = _put(batch, self._target)  # overlap with consumer's compute
            yield ahead
            ahead = nxt
        yield ahead
