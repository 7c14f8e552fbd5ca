"""int8 quantization path."""
import numpy as np

from mxnet_tpu import gluon, nd
from mxnet_tpu.quantization import quantize_model


def test_quantized_dense_close_to_fp32():
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(32, activation="relu", in_units=16),
            gluon.nn.Dense(8, in_units=32))
    net.initialize()
    x = nd.array(np.random.randn(4, 16).astype(np.float32))
    ref = net(x).asnumpy()
    quantize_model(net)
    out = net(x).asnumpy()
    # int8 dynamic quantization: relative error within a few percent
    denom = np.abs(ref).max() + 1e-6
    assert np.abs(out - ref).max() / denom < 0.1


def test_quantized_conv_close_to_fp32():
    from mxnet_tpu import gluon, nd

    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Conv2D(8, 3, padding=1, in_channels=3, activation="relu"),
            gluon.nn.Conv2D(4, 1, in_channels=8))
    net.initialize()
    x = nd.array(np.random.RandomState(0).randn(2, 3, 8, 8).astype(np.float32))
    ref = net(x).asnumpy()
    quantize_model(net)
    out = net(x).asnumpy()
    denom = np.abs(ref).max() + 1e-6
    assert np.abs(out - ref).max() / denom < 0.1


def test_quantized_conv_grouped_strided():
    from mxnet_tpu.quantization import quantize, quantized_conv
    import jax.numpy as jnp
    from mxnet_tpu.ops import functional as F

    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(2, 4, 9, 9).astype(np.float32))
    w = jnp.asarray(rng.randn(8, 2, 3, 3).astype(np.float32))  # groups=2
    ref = np.asarray(F.Convolution(x, w, None, kernel=(3, 3), stride=2, pad=1,
                                   num_group=2, no_bias=True))
    qw, ws = quantize(w, axis=0)
    out = np.asarray(quantized_conv(x, qw, ws, stride=2, pad=1, num_group=2))
    denom = np.abs(ref).max() + 1e-6
    assert out.shape == ref.shape
    assert np.abs(out - ref).max() / denom < 0.1


def test_quantize_zoo_model_end_to_end():
    """Model-level: int8-quantize a real zoo net and keep top-1 agreement."""
    from mxnet_tpu.gluon.model_zoo.vision import get_resnet

    net = get_resnet(1, 18, classes=10, thumbnail=True)
    net.initialize()
    x = nd.array(np.random.RandomState(2).randn(4, 3, 32, 32).astype(np.float32))
    ref = net(x).asnumpy()
    quantize_model(net)
    out = net(x).asnumpy()
    denom = np.abs(ref).max() + 1e-6
    assert np.abs(out - ref).max() / denom < 0.15
    assert (out.argmax(-1) == ref.argmax(-1)).all()


def test_calibrated_quantization_naive_and_entropy():
    """calib_mode naive/entropy freeze static activation scales that match
    fp32 closely and survive hybridize (ref: contrib/quantization.py
    quantize_model calib_mode)."""
    from mxnet_tpu.quantization import QuantizedDense, _quantized_layers

    rng = np.random.RandomState(3)
    batches = [nd.array(rng.randn(8, 16).astype(np.float32)) for _ in range(4)]
    for mode in ("naive", "entropy"):
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(32, activation="relu", in_units=16),
                gluon.nn.Dense(8, in_units=32))
        net.initialize()
        ref = net(batches[0]).asnumpy()
        quantize_model(net, calib_mode=mode, calib_data=batches)
        layers = _quantized_layers(net, [])
        assert len(layers) == 2
        for l in layers:
            assert l._x_scale is not None and l._x_scale > 0
            assert l._collector is None
        out = net(batches[0]).asnumpy()
        denom = np.abs(ref).max() + 1e-6
        # entropy trades tail accuracy for in-range resolution: allow more
        # clip error than naive's exact-max scale on this random-data net
        tol = 0.1 if mode == "naive" else 0.25
        assert np.abs(out - ref).max() / denom < tol, mode
        net.hybridize()   # static scales are trace constants
        out2 = net(batches[0]).asnumpy()
        np.testing.assert_allclose(out2, out, rtol=1e-5, atol=1e-5)


def test_entropy_threshold_clips_outliers():
    """Entropy calibration should pick a threshold below a lone huge outlier
    when the mass is concentrated near zero."""
    from mxnet_tpu.quantization import _optimal_threshold

    hist = np.zeros(8001)
    hist[:400] = 1000.0   # bulk of the distribution in [0, 5% of range]
    hist[8000] = 1.0      # single outlier at the max
    t = _optimal_threshold(hist, amax=100.0)
    assert t < 100.0


def test_entropy_threshold_never_exceeds_amax():
    """Entropy folds clipped mass into the edge bin rather than widening
    the range: on ANY activation distribution the chosen threshold stays
    <= the observed amax (the naive scale), positive, and finite."""
    from mxnet_tpu.quantization import _quantized_layers

    rng = np.random.RandomState(7)
    batches = [nd.array((rng.randn(8, 16) * (1 + 3 * rng.rand()))
                        .astype(np.float32)) for _ in range(3)]
    amax = max(float(np.abs(b.asnumpy()).max()) for b in batches)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8, in_units=16))
    net.initialize()
    quantize_model(net, calib_mode="entropy", calib_data=batches)
    (layer,) = _quantized_layers(net, [])
    # _x_scale = threshold / 127: recover the threshold it froze
    assert 0 < layer._x_scale * 127.0 <= amax + 1e-6


def test_calibration_two_pass_determinism():
    """Identical calibration batches must freeze identical static scales
    (the entropy collector histograms in pass 2 over the pass-1 amax —
    any order- or state-dependence would break replayability)."""
    from mxnet_tpu.quantization import _quantized_layers

    rng = np.random.RandomState(11)
    batches = [nd.array(rng.randn(8, 16).astype(np.float32))
               for _ in range(3)]
    for mode in ("naive", "entropy"):
        scales = []
        for _ in range(2):
            net = gluon.nn.HybridSequential()
            net.add(gluon.nn.Dense(32, activation="relu", in_units=16),
                    gluon.nn.Dense(8, in_units=32))
            net.initialize(init="ones")   # identical nets both rounds
            quantize_model(net, calib_mode=mode, calib_data=batches)
            scales.append([l._x_scale
                           for l in _quantized_layers(net, [])])
        assert scales[0] == scales[1], mode


def test_static_vs_dynamic_scale_parity():
    """Static (calibrated) and dynamic (per-batch amax) activation scales
    must agree closely on data drawn from the calibration distribution —
    naive calibration over batches that INCLUDE the eval batch freezes a
    scale >= the eval batch's amax, so outputs differ only by rounding."""
    rng = np.random.RandomState(13)
    batches = [nd.array(rng.randn(8, 16).astype(np.float32))
               for _ in range(4)]

    def build():
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(32, activation="relu", in_units=16),
                gluon.nn.Dense(8, in_units=32))
        net.initialize()
        return net

    dyn, stat = build(), build()
    for ps, pd in zip(dyn.collect_params().values(),
                      stat.collect_params().values()):
        pd.set_data(ps.data())
    quantize_model(dyn)                   # dynamic scales
    quantize_model(stat, calib_mode="naive", calib_data=batches)
    for b in batches:
        d = dyn(b).asnumpy()
        s = stat(b).asnumpy()
        denom = np.abs(d).max() + 1e-6
        assert np.abs(s - d).max() / denom < 0.05


def test_quantize_model_grouped_conv_block():
    """quantize_model through a grouped Conv2D block (num_group>1): the
    swapped QuantizedConv2D must keep the grouped layout and parity."""
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Conv2D(8, 3, padding=1, groups=2, in_channels=4,
                            activation="relu"),
            gluon.nn.Conv2D(4, 1, in_channels=8))
    net.initialize()
    x = nd.array(np.random.RandomState(5).randn(2, 4, 8, 8)
                 .astype(np.float32))
    ref = net(x).asnumpy()
    quantize_model(net)
    out = net(x).asnumpy()
    assert out.shape == ref.shape
    denom = np.abs(ref).max() + 1e-6
    assert np.abs(out - ref).max() / denom < 0.1


def test_fused_quant_cache_write_read_is_bit_exact():
    """quant_cache_write_read == quant_cache_write + dequant_cache to the
    last bit (scalar AND per-row vector index): the fused op reuses the
    fp32 requant values for the read, and integer-valued fp32 in
    [-127, 127] round-trips int8 exactly. This pins the GL024 fix — the
    fused read must never drift from the unfused pair it replaced."""
    from mxnet_tpu.ops import attention as att

    rng = np.random.RandomState(7)
    for index in (0, 3, np.array([1, 5, 0, 3], np.int32)):
        cache = rng.randint(-127, 128, (4, 2, 8, 16)).astype(np.int8)
        scale = np.abs(rng.randn(4, 2, 1, 1)).astype(np.float32) + 0.01
        update = (rng.randn(4, 2, 1, 16) * 3).astype(np.float32)
        c1, s1 = att.quant_cache_write(cache, scale, update, index)
        deq_ref = att.dequant_cache(c1, s1)
        c2, s2, deq = att.quant_cache_write_read(cache, scale, update,
                                                 index)
        np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
        np.testing.assert_array_equal(np.asarray(deq_ref),
                                      np.asarray(deq))
