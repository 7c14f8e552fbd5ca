"""Pallas TPU kernels for the hot ops (flash attention, fused layernorm,
fused softmax cross-entropy, the decode step's K/V write and read, the grouped
expert FFN, power retention's decode step, the Mamba-2 mixer's decode step).

The ops that own a kernel gate into it at trace time, from what they can
see then: the default backend is a TPU, the static shapes tile, and the
program is not being traced for a device mesh (:func:`under_mesh`)."""


def under_mesh():
    """True while a program is traced for a device mesh
    (``parallel.use_mesh`` — ``parallel.build_train_step(mesh=...)`` enters
    it). The SPMD partitioner cannot split a Mosaic kernel ("Mosaic kernels
    cannot be automatically partitioned. Please wrap the call in a
    shard_map"), so such programs take the XLA formulations, which it can."""
    from ...parallel.mesh import current_mesh

    return current_mesh() is not None
