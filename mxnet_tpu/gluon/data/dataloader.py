"""DataLoader with background prefetch (ref: python/mxnet/gluon/data/dataloader.py).

MXNet uses multiprocessing workers feeding a queue. Host-side batching here is
numpy (cheap); the important TPU-side property is keeping the device fed:
the loader prefetches batches on a thread pool (the C++ host engine in
src/engine_cc provides the dependency-tracked task queue when built) and the
training loop overlaps host batching with device compute thanks to async
dispatch.
"""
from __future__ import annotations

import numpy as np

from ...ndarray import NDArray, array
from .dataset import Dataset
from .sampler import BatchSampler, RandomSampler, SequentialSampler

__all__ = ["DataLoader", "default_batchify_fn", "default_mp_batchify_fn"]


def default_batchify_fn(data):
    """(ref: dataloader.py:default_batchify_fn)"""
    if isinstance(data[0], NDArray):
        return array(np.stack([d.asnumpy() for d in data]))
    if isinstance(data[0], tuple):
        data = zip(*data)
        return [default_batchify_fn(list(i)) for i in data]
    arr = np.asarray(data)
    if arr.dtype == np.float64:
        arr = arr.astype(np.float32)
    return array(arr)


def default_mp_batchify_fn(data):
    """Batchify that stays in NUMPY — what worker processes return (ref:
    dataloader.py:default_mp_batchify_fn, which uses shared-memory mx
    arrays): device arrays must not be created in (or pickled back from)
    forked children; the parent converts once per batch."""
    if isinstance(data[0], NDArray):
        return np.stack([d.asnumpy() for d in data])
    if isinstance(data[0], tuple):
        data = zip(*data)
        return [default_mp_batchify_fn(list(i)) for i in data]
    arr = np.asarray(data)
    if arr.dtype == np.float64:
        arr = arr.astype(np.float32)
    return arr


_worker_dataset = None


def _pin_worker_to_cpu():
    """Workers must never acquire the accelerator: a chip belongs to one
    process at a time, so a spawned child initializing its own TPU client
    would fail or hang against the parent that already holds it. Resolving
    this function for the unpickle has already imported the package, and
    with it jax, which reads JAX_PLATFORMS as it is imported — so the live
    config is updated too; the env var covers grandchildren."""
    import os

    import jax

    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")


def _unpickle_pinned(payload):
    import pickle

    _pin_worker_to_cpu()
    return pickle.loads(payload)


class _CpuPinnedPayload:
    """Pickles as (pin-CPU, then unpickle the wrapped object).

    ProcessPoolExecutor unpickles initargs BEFORE calling the initializer,
    so a dataset holding NDArray members (e.g. ArrayDataset) would
    otherwise initialize the worker's jax backend — on the inherited
    accelerator platform — during process bootstrap, before any pin could
    run. Nesting the dataset bytes inside this wrapper makes the CPU pin
    part of the unpickle itself: it is guaranteed to run first."""

    def __init__(self, obj):
        self.obj = obj

    def __reduce__(self):
        import pickle

        return _unpickle_pinned, (pickle.dumps(self.obj),)


def _worker_initializer(dataset):
    # runs once per worker process; the dataset rides the initargs pickle
    # (wrapped in _CpuPinnedPayload, so by the time it is reconstructed the
    # backend is already pinned). Pin again for the array-free case where
    # the dataset pickle never triggered the wrapper's import path —
    # __getitem__ may still create NDArrays later (ToTensor & friends).
    _pin_worker_to_cpu()
    global _worker_dataset
    _worker_dataset = dataset


def _worker_fn(indices, batchify_fn):
    return batchify_fn([_worker_dataset[i] for i in indices])


def _to_device(batch):
    if isinstance(batch, np.ndarray):
        return array(batch)
    if isinstance(batch, (list, tuple)):
        return [_to_device(b) for b in batch]
    return batch


class DataLoader:
    def __init__(self, dataset, batch_size=None, shuffle=False, sampler=None,
                 last_batch=None, batch_sampler=None, batchify_fn=None,
                 num_workers=0, pin_memory=False, prefetch=None, thread_pool=True):
        self._dataset = dataset
        if batch_sampler is None:
            if batch_size is None:
                raise ValueError("batch_size required when batch_sampler is None")
            if sampler is None:
                sampler = RandomSampler(len(dataset)) if shuffle else SequentialSampler(len(dataset))
            elif shuffle:
                raise ValueError("shuffle must be False with custom sampler")
            batch_sampler = BatchSampler(sampler, batch_size, last_batch or "keep")
        self._batch_sampler = batch_sampler
        self._thread_pool = thread_pool
        self._user_batchify = batchify_fn
        self._batchify_fn = batchify_fn or default_batchify_fn
        self._num_workers = num_workers
        self._mp_pool = None
        # pin_memory (ref: dataloader.py pin_memory → pinned-memory staging
        # for fast H2D): here the analogue is eager device placement — the
        # epoch iterator is wrapped in DevicePrefetcher, so batch N+1's H2D
        # transfer is issued while the consumer computes on batch N. On a
        # CPU-only host the device_put is a same-device no-op (harmless).
        self._pin_memory = pin_memory
        self._prefetch = max(0, prefetch if prefetch is not None else 2 * max(num_workers, 1))

    def _make_batch(self, indices):
        return self._batchify_fn([self._dataset[i] for i in indices])

    def __iter__(self):
        if self._pin_memory:
            from .prefetcher import DevicePrefetcher

            # a generator is its own iterator, and __iter__ builds a fresh
            # one per epoch, so wrapping it per-call is epoch-safe
            yield from DevicePrefetcher(self._iter_batches())
            return
        yield from self._iter_batches()

    def _iter_batches(self):
        if self._num_workers == 0:
            for indices in self._batch_sampler:
                yield self._make_batch(indices)
            return
        if self._thread_pool:
            yield from self._prefetch_iter()
        else:
            yield from self._mp_iter()

    def _prefetch_iter(self):
        """num_workers batches build CONCURRENTLY on a thread pool (numpy /
        PIL decode release the GIL, so threads genuinely parallelize the
        transform work upstream forks processes for), with a bounded
        in-flight window and strict batch order: futures are consumed
        oldest-first, refilling before each blocking wait."""
        from concurrent.futures import ThreadPoolExecutor
        from collections import deque

        window = max(self._prefetch, self._num_workers)
        pool = ThreadPoolExecutor(self._num_workers)
        try:
            futs = deque()
            it = iter(self._batch_sampler)
            for indices in it:
                futs.append(pool.submit(self._make_batch, indices))
                if len(futs) >= window:
                    break
            while futs:
                f = futs.popleft()
                nxt = next(it, None)
                if nxt is not None:
                    futs.append(pool.submit(self._make_batch, nxt))
                yield f.result()
        finally:
            # an early `break` in the consumer must not stall on the whole
            # in-flight window finishing its (possibly expensive) batches
            pool.shutdown(wait=False, cancel_futures=True)

    def _mp_iter(self):
        """thread_pool=False: num_workers PROCESSES, sidestepping the GIL
        for pure-Python transforms (upstream's default worker model; the
        thread pool remains best for native decode paths that release the
        GIL). Workers batchify in numpy (default_mp_batchify_fn); the parent
        converts to device arrays. Same bounded window + strict order as
        the thread path. Dataset (and a custom batchify_fn) must pickle, and
        the entry script needs the standard ``if __name__ == "__main__"``
        guard: workers are SPAWNED, not forked — forking after jax has
        initialized deadlocks on locks the PJRT client's threads hold across
        fork, so each worker is a fresh interpreter that simply never
        touches the jax backend."""
        import multiprocessing
        from collections import deque
        from concurrent.futures import ProcessPoolExecutor

        batchify = self._user_batchify or default_mp_batchify_fn
        if batchify is default_batchify_fn:
            # the device-array batchify must not run in workers: each child
            # would initialize its own backend client and try to pickle
            # device arrays back — numpy until the parent converts
            batchify = default_mp_batchify_fn
        window = max(self._prefetch, self._num_workers)
        if self._mp_pool is None:
            # the pool outlives one epoch: spawn pays a full interpreter
            # start + package import per worker, so it is created once per
            # loader (workers are stateless beyond the pickled dataset)
            self._mp_pool = ProcessPoolExecutor(
                self._num_workers,
                mp_context=multiprocessing.get_context("spawn"),
                initializer=_worker_initializer,
                # _CpuPinnedPayload: the CPU pin must precede the dataset
                # unpickle itself (initargs deserialize before the
                # initializer runs)
                initargs=(_CpuPinnedPayload(self._dataset),))
        pool = self._mp_pool
        futs = deque()
        try:
            it = iter(self._batch_sampler)
            for indices in it:
                futs.append(pool.submit(_worker_fn, indices, batchify))
                if len(futs) >= window:
                    break
            while futs:
                f = futs.popleft()
                nxt = next(it, None)
                if nxt is not None:
                    futs.append(pool.submit(_worker_fn, nxt, batchify))
                yield _to_device(f.result())
        finally:
            # early break: drop this epoch's in-flight work but KEEP the
            # pool for the next epoch
            for f in futs:
                f.cancel()

    def __del__(self):
        pool = self.__dict__.get("_mp_pool")
        if pool is not None:
            pool.shutdown(wait=False, cancel_futures=True)

    def __len__(self):
        return len(self._batch_sampler)
