"""Weights from the seed, made on the device in one jitted call.

``specs`` is the reference's own list of ``(name, shape)``. Every leaf is
random, so that no parameter is a no-op: matrices, embeddings, biases and
LayerNorm shifts are N(0, 0.02^2), LayerNorm gains 1 + N(0, 0.02^2). Values
are rounded to ``dtype`` (the type they are trained or served in); the
reference upcasts the same values.
"""
import functools


def seed_key(seed):
    """A PRNG key from any non-negative whole number (seeds pass 2**31)."""
    import jax

    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


@functools.lru_cache(maxsize=4)
def _generator(specs, dtype):
    import jax
    import jax.numpy as jnp
    import numpy as np

    sizes = [int(np.prod(shape)) for _n, shape in specs]

    def gen(key):
        # one draw for all leaves, cut up in the order of the specs: a
        # generator of its own for each of some hundred leaves takes the
        # chip's compiler a minute
        flat = 0.02 * jax.random.normal(key, (sum(sizes),), jnp.float32)
        out, at = {}, 0
        for (name, shape), n in zip(specs, sizes):
            x = flat[at:at + n].reshape(shape)
            at += n
            if name.endswith("gamma"):
                x = 1.0 + x
            out[name] = x.astype(dtype)
        return out

    return jax.jit(gen)


def make(seed, specs, dtype):
    """dict name -> array of ``dtype``, from ``seed`` alone."""
    specs = tuple((str(n), tuple(int(d) for d in s)) for n, s in specs)
    return _generator(specs, dtype)(seed_key(seed))
