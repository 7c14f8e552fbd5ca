"""Device mesh helpers.

Replaces the reference's device-group plumbing (kvstore device lists, NCCL
communicators, MPI ranks — ref: src/kvstore/comm.h) with the JAX mesh model:
one named Mesh, shardings as PartitionSpecs, collectives inserted by the XLA
SPMD partitioner and riding ICI. Axis convention (scaling-book style):

    dp    data parallel (outermost, DCN-friendly)
    fsdp  parameter/optimizer sharding (ZeRO-3)
    tp    tensor parallel (innermost, highest-bandwidth ICI)
    sp    sequence/context parallel (ring attention)
    pp    pipeline stages
    ep    expert parallel
"""
from __future__ import annotations

import math
from contextlib import contextmanager

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

P = PartitionSpec

AXES = ("dp", "fsdp", "tp", "sp", "pp", "ep")


def make_mesh(axes=None, devices=None):
    """axes: dict axis_name → size (product must equal #devices; use -1 for one
    inferred axis), e.g. {'dp': -1, 'tp': 2}."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    axes = dict(axes or {"dp": n})
    known = 1
    infer = None
    for k, v in axes.items():
        if v == -1:
            infer = k
        else:
            known *= v
    if infer is not None:
        axes[infer] = n // known
    total = math.prod(axes.values())
    assert total == n, "mesh %s needs %d devices, have %d" % (axes, total, n)
    arr = np.array(devices).reshape(tuple(axes.values()))
    return Mesh(arr, tuple(axes.keys()))


def named_sharding(mesh, *spec):
    return NamedSharding(mesh, P(*spec))


def replicated(mesh):
    return NamedSharding(mesh, P())


_current_mesh = []


@contextmanager
def use_mesh(mesh):
    _current_mesh.append(mesh)
    try:
        with mesh:
            yield mesh
    finally:
        _current_mesh.pop()


def current_mesh():
    return _current_mesh[-1] if _current_mesh else None


def shard_array(x, mesh, *spec):
    return jax.device_put(x, NamedSharding(mesh, P(*spec)))


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication check off: ring collectives
    violate per-device replication invariants by design."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
