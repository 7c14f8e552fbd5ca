"""``lib/weights.py``: the same seed gives the same weights, a leaf does not
depend on which other leaves are asked for, the distribution is the one the
limits of ``correct`` were set under, and the one program that draws a block
stays small on the chip whatever the total."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lib import weights

# a gain, a bias, a matrix that is the head of one block, and a matrix
# larger than a block (of 2**22 values: three blocks, the last cut)
SPECS = [("ln_f_gamma", (256,)), ("layer0_ffn_1_bias", (1024,)),
         ("layer0_ffn_1_weight", (1024, 256)),
         ("word_embed_weight", (9000, 1024))]


def test_two_calls_with_one_seed_agree_and_seeds_differ():
    a = weights.make(3_000_000_123, SPECS, jnp.bfloat16)
    b = weights.make(3_000_000_123, SPECS, jnp.bfloat16)
    c = weights.make(3_000_000_124, SPECS, jnp.bfloat16)
    assert list(a) == [n for n, _s in SPECS]
    for name, shape in SPECS:
        assert a[name].shape == shape and a[name].dtype == jnp.bfloat16
        assert np.array_equal(np.asarray(a[name]), np.asarray(b[name]))
        assert not np.array_equal(np.asarray(a[name]), np.asarray(c[name]))


def test_a_leaf_does_not_depend_on_which_others_are_asked_for():
    whole = weights.make(7, SPECS, jnp.bfloat16)
    for spec in SPECS:
        alone = weights.make(7, [spec], jnp.bfloat16)
        assert np.array_equal(np.asarray(alone[spec[0]]),
                              np.asarray(whole[spec[0]]))
    back = weights.make(7, SPECS[::-1], jnp.bfloat16)
    for name, _shape in SPECS:
        assert np.array_equal(np.asarray(back[name]), np.asarray(whole[name]))
    # two leaves of one shape are two draws, and so are a leaf's blocks
    twin = weights.make(7, [("layer1_ffn_1_bias", (1024,))], jnp.bfloat16)
    assert not np.array_equal(np.asarray(twin["layer1_ffn_1_bias"]),
                              np.asarray(whole["layer0_ffn_1_bias"]))
    flat = np.asarray(whole["word_embed_weight"], np.float32).ravel()
    assert not np.array_equal(flat[:weights.BLOCK],
                              flat[weights.BLOCK:2 * weights.BLOCK])


def test_the_distribution_is_normal_0_02_and_gains_are_about_one():
    w = weights.make(11, SPECS, jnp.float32)
    big = np.asarray(w["word_embed_weight"]).ravel()
    assert abs(big.mean()) < 1e-4 and big.std() == pytest.approx(0.02,
                                                                 rel=0.01)
    # the cut at the end of the last block leaves no run of zeros
    assert np.count_nonzero(big[-1000:]) == 1000
    gain = np.asarray(w["ln_f_gamma"])
    assert gain.mean() == pytest.approx(1.0, abs=0.01)
    assert gain.std() == pytest.approx(0.02, rel=0.25)
    bias = np.asarray(w["layer0_ffn_1_bias"])
    assert abs(bias.mean()) < 0.005
    rounded = weights.make(11, SPECS, jnp.bfloat16)["word_embed_weight"]
    assert np.array_equal(np.asarray(rounded),
                          np.asarray(jnp.asarray(big, jnp.bfloat16)
                                     ).reshape(9000, 1024))


def test_a_leaf_of_2_to_the_31_elements_is_refused():
    with pytest.raises(ValueError):
        weights.make(1, [("too_big_weight", (1 << 16, 1 << 15))],
                     jnp.bfloat16)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


def test_the_block_program_stays_under_2_gb_on_a_described_v5e(one_chip):
    """Compiled for the chip with no chip: one block out, and temporaries
    that do not grow with the configuration (the flat draw of all leaves
    that this replaced took 13 bytes a parameter)."""
    from jax.experimental.compilation_cache import compilation_cache

    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((2,), jnp.uint32), ((3,), jnp.uint32))]
    # a compile for a described chip cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        mem = jax.jit(weights.block_fn(jnp.bfloat16)).lower(*shapes
                                                            ).compile(
        ).memory_analysis()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    assert mem.output_size_in_bytes == 2 * weights.BLOCK
    assert mem.temp_size_in_bytes < 2e9
    assert mem.temp_size_in_bytes <= 16 * weights.BLOCK
