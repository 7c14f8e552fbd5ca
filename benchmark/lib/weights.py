"""Weights from the seed, made on the device in blocks by one jitted program.

``specs`` is the reference's own list of ``(name, shape)``. Every leaf is
random, so that no parameter is a no-op: matrices, embeddings, biases and
LayerNorm shifts are N(0, 0.02^2), LayerNorm gains 1 + N(0, 0.02^2). Values
are rounded to ``dtype`` (the type they are trained or served in); the
reference upcasts the same values.

One program draws one block of ``BLOCK`` values and is reused for every
leaf: a leaf is the head of one block, a leaf larger than a block is its
row-major run cut into blocks. A block's key is
``fold_in(fold_in(seed_key, crc32(leaf's name)), block index)``, so a leaf's
values depend on the seed, its name and its size alone, not on which other
leaves are asked for. What the program needs beside its output is one
block's, whatever the total (0.2 MB compiled for a v5e, where the flat draw
of all leaves it replaced was counted at 13 bytes a parameter); it compiles
once for a type, and no array it makes has more than ``BLOCK`` elements. A
leaf itself has to stay under 2**31 elements.
"""
import functools
import zlib

BLOCK = 1 << 22


def seed_key(seed):
    """A PRNG key from any non-negative whole number (seeds pass 2**31)."""
    import jax

    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def block_fn(dtype):
    """The function the block program jits: (seed key, uint32 [leaf id,
    block index, shift]) -> ``BLOCK`` values of ``dtype``. The three
    numbers come as one array: one transfer a call, which is most of what a
    call costs."""
    import jax
    import jax.numpy as jnp

    def block(key, which):
        key = jax.random.fold_in(jax.random.fold_in(key, which[0]), which[1])
        x = 0.02 * jax.random.normal(key, (BLOCK,), jnp.float32)
        return (which[2].astype(jnp.float32) + x).astype(dtype)

    return block


@functools.lru_cache(maxsize=4)
def _block_program(dtype):
    import jax

    return jax.jit(block_fn(dtype))


@functools.lru_cache(maxsize=64)
def _cut(count, shape):
    """The program that lays ``count`` blocks end to end and cuts the leaf
    of ``shape`` from their head: one for each distinct leaf shape."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    n = int(np.prod(shape))

    return jax.jit(
        lambda *blocks: jnp.concatenate(blocks)[:n].reshape(shape))


def make(seed, specs, dtype):
    """dict name -> array of ``dtype``, from ``seed`` alone."""
    import numpy as np

    key, block = seed_key(seed), _block_program(np.dtype(dtype))
    out = {}
    for name, shape in specs:
        name, shape = str(name), tuple(int(d) for d in shape)
        n = int(np.prod(shape))
        if n >= 1 << 31:
            raise ValueError("leaf %s%s has 2**31 elements or more"
                             % (name, shape))
        leaf = zlib.crc32(name.encode()) & 0x7FFFFFFF
        shift = 1 if name.endswith("gamma") else 0
        count = max(1, -(-n // BLOCK))
        out[name] = _cut(count, shape)(
            *(block(key, np.array([leaf, i, shift], np.uint32))
              for i in range(count)))
    return out
