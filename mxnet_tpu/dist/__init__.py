"""mxnet_tpu.dist — overlapped hierarchical gradient exchange + elastic
multi-host training (ROADMAP #3, the scale-out pillar).

Four coordinated pieces:

* :class:`GradientBucketer` — size-capped buckets (``MXNET_DIST_BUCKET_MB``)
  in reverse-tape order, each reduction dispatched while the compiled
  backward is still executing (comm/compute overlap as XLA program order);
* :class:`HierarchicalAllreduce` — reduce-scatter on the fast ICI axis,
  cross the slow DCN axis with only the scattered shard (optionally
  fp16/int8/2-bit compressed with error-feedback residuals; the kvstore
  dist_sync wire is a pluggable DCN leg), all-gather back
  (arXiv 1810.11112);
* ZeRO-2/3 (:mod:`.zero`) — gradient and parameter sharding layered on the
  fused-optimizer path's ZeRO-1 weight-update sharding
  (arXiv 2004.13336);
* :class:`ElasticTrainer` (:mod:`.elastic`) — recovery drills: a replica
  dies mid-epoch, survivors re-form the mesh and rejoin from the sharded
  ``ResumableLoop`` checkpoint.

Trainer wiring is one call::

    handle = mxnet_tpu.dist.attach(trainer, mesh, ici_axis="dp",
                                   compression={"type": "int8"}, zero=2)

after which ``trainer.step`` exchanges gradients bucket-by-bucket under
the backward (``Trainer.allreduce_grads`` is a thin shim over
``handle.finish()``). Everything is dryrun-provable on the 8-device CPU
mesh; ``engine.dist_bucket_counter`` / ``dist_compile_counter`` and the
``dist_overlap_window_ms`` histogram are the proof hooks.
"""
from __future__ import annotations

from .hierarchical import HierarchicalAllreduce, FlatAllreduce  # noqa: F401
from .bucketer import (GradientBucketer, BackwardExchanger,  # noqa: F401
                       default_bucket_mb)
from .zero import (Zero3ParamManager, shard_spec,  # noqa: F401
                   per_device_bytes, global_bytes)
from .elastic import ElasticTrainer, ElasticRun  # noqa: F401

__all__ = ["HierarchicalAllreduce", "FlatAllreduce", "GradientBucketer",
           "BackwardExchanger", "Zero3ParamManager", "ElasticTrainer",
           "ElasticRun", "attach", "detach", "stats", "shard_spec",
           "per_device_bytes", "global_bytes", "default_bucket_mb"]

# live exchangers the autograd hook fans out to (normally one; several
# trainers may attach independently)
_EXCHANGERS = []


def _on_backward(targets):
    for ex in _EXCHANGERS:
        ex.on_backward(targets)


def _sync_hook():
    from .. import autograd as _ag

    _ag._GRAD_EXCHANGER = _on_backward if _EXCHANGERS else None


class DistHandle:
    """One trainer's attachment to the dist runtime: the strategy, the
    bucketer, the backward exchanger, and (ZeRO-3) the parameter
    manager. ``Trainer.allreduce_grads`` calls :meth:`finish`; ZeRO-3
    users call :meth:`gather_params` before each forward."""

    def __init__(self, trainer, strategy, bucketer, exchanger, zero,
                 manager=None):
        self.trainer = trainer
        self.strategy = strategy
        self.bucketer = bucketer
        self.exchanger = exchanger
        self.zero = zero
        self.manager = manager

    def finish(self):
        self.exchanger.register_params(self.trainer._params)
        self.exchanger.finish(self.trainer._params)

    def gather_params(self):
        """ZeRO-3: rebuild replicated weights per-bucket, on demand,
        before a forward (async — later buckets overlap the first
        layers' compute). No-op below stage 3."""
        if self.manager is not None:
            self.manager.gather()

    def release_params(self):
        """ZeRO-3: return weights to their shards (the between-steps
        residency). No-op below stage 3."""
        if self.manager is not None:
            self.manager.release()

    def _rehome(self):
        """Bring updated weights back to the eager home device after the
        mesh-resident fused step, so the next eager forward (inputs are
        committed single-device) composes. Gradients never round-trip —
        they are exchanged and consumed on the mesh. ZeRO-3 skips this:
        weights stay sharded; :meth:`gather_params` re-homes per bucket."""
        if self.zero >= 3:
            return
        import jax

        from ..context import current_context

        # the default context's device: where the eager forward runs
        home = current_context().jax_device()
        for p in self.trainer._params:
            if p._data is None:
                continue
            nd = p.data()
            if len(nd._data.devices()) > 1:
                nd._data = jax.device_put(nd._data, home)

    def detach(self):
        detach(self.trainer)


def attach(trainer, mesh, ici_axis="dp", dcn_axis=None, compression=None,
           zero=0, bucket_mb=None, average=False, dcn="jit",
           shard_axis=None):
    """Wire a gluon ``Trainer`` into the overlapped exchange.

    mesh/ici_axis/dcn_axis/compression/dcn configure the
    :class:`HierarchicalAllreduce`; ``zero`` picks the sharding stage
    (1 = weight-update/optimizer-state, 2 = +gradients, 3 = +parameters);
    ``bucket_mb`` overrides ``MXNET_DIST_BUCKET_MB``. Returns the
    :class:`DistHandle` (also stored as ``trainer._dist``)."""
    strategy = HierarchicalAllreduce(mesh, ici_axis=ici_axis,
                                     dcn_axis=dcn_axis,
                                     compression=compression,
                                     average=average, dcn=dcn)
    shard_axis = shard_axis or ici_axis
    bucketer = GradientBucketer(strategy, bucket_mb=bucket_mb,
                                stacked=False, zero=zero,
                                shard_axis=shard_axis)
    exchanger = BackwardExchanger(bucketer)
    exchanger.register_params(trainer._params)
    manager = None
    # the fused update always runs ON the mesh (the exchanged grads live
    # there); zero>=1 additionally shards it, zero=0 stays replicated
    trainer.set_weight_update_sharding(
        mesh, shard_axis if zero >= 1 else None)
    if zero >= 3:
        manager = Zero3ParamManager(trainer._params, mesh,
                                    shard_axis=shard_axis,
                                    bucket_mb=bucket_mb)
    handle = DistHandle(trainer, strategy, bucketer, exchanger, zero,
                        manager)
    trainer._dist = handle
    _EXCHANGERS.append(exchanger)
    _sync_hook()
    return handle


def detach(trainer):
    """Undo :func:`attach`: restore the legacy allreduce path and (ZeRO)
    un-shard the weight update."""
    handle = getattr(trainer, "_dist", None)
    if handle is None:
        return
    trainer._dist = None
    if handle.exchanger in _EXCHANGERS:
        _EXCHANGERS.remove(handle.exchanger)
    trainer.set_weight_update_sharding(None)
    _sync_hook()


def stats():
    """The ``dist`` observability-collector payload (exchange state only;
    the engine counters and registry metrics ride their own sections)."""
    from . import elastic as _el

    agg = {"layouts": 0, "programs": 0, "exchanges": 0}
    for ex in _EXCHANGERS:
        s = ex.bucketer.stats()
        for k in agg:
            agg[k] += s[k]
    return {
        "attached_trainers": len(_EXCHANGERS),
        "bucket_mb_default": default_bucket_mb(),
        "bucket_layouts": agg["layouts"],
        "bucket_programs": agg["programs"],
        "exchanges": agg["exchanges"],
        "elastic_recoveries_recorded": len(_el.events),
        "last_recovery": _el.events[-1] if _el.events else None,
    }
