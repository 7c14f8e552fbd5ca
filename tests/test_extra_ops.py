"""Extra op families: legacy aliases, elemwise_*, output heads, Correlation
(mirrors reference tests/python/unittest/test_operator.py coverage)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, autograd


def test_legacy_aliases():
    x = nd.array(np.arange(12, dtype=np.float32).reshape(3, 4))
    np.testing.assert_array_equal(nd.Reshape(x, shape=(4, 3)).asnumpy(),
                                  x.asnumpy().reshape(4, 3))
    np.testing.assert_array_equal(nd.Flatten(x).asnumpy(), x.asnumpy())
    assert nd.Cast(x, dtype="int32").dtype == np.int32
    y = nd.SwapAxis(x, dim1=0, dim2=1)
    assert y.shape == (4, 3)
    s = nd.ElementWiseSum(x, x, x)
    np.testing.assert_allclose(s.asnumpy(), 3 * x.asnumpy())
    np.testing.assert_allclose(nd.add_n(x, x).asnumpy(), 2 * x.asnumpy())


def test_elemwise_named():
    a = nd.array(np.random.RandomState(0).rand(2, 3).astype(np.float32) + 1)
    b = nd.array(np.random.RandomState(1).rand(2, 3).astype(np.float32) + 1)
    np.testing.assert_allclose(nd.elemwise_add(a, b).asnumpy(), a.asnumpy() + b.asnumpy())
    np.testing.assert_allclose(nd.elemwise_sub(a, b).asnumpy(), a.asnumpy() - b.asnumpy())
    np.testing.assert_allclose(nd.elemwise_mul(a, b).asnumpy(), a.asnumpy() * b.asnumpy())
    np.testing.assert_allclose(nd.elemwise_div(a, b).asnumpy(), a.asnumpy() / b.asnumpy(),
                               rtol=1e-6)


def test_tensor_ops():
    x = nd.array(np.random.RandomState(2).randn(2, 5, 3).astype(np.float32))
    am = nd.argmax_channel(x)
    np.testing.assert_array_equal(am.asnumpy(), np.argmax(x.asnumpy(), axis=1))

    data = nd.array(np.arange(12, dtype=np.float32).reshape(4, 3))
    idx = nd.array(np.array([0, 2, 1, 0], dtype=np.int64))
    bt = nd.batch_take(data, idx)
    np.testing.assert_array_equal(bt.asnumpy(), [0, 5, 7, 9])

    b = nd.broadcast_axis(nd.ones((1, 3, 1)), axis=(0, 2), size=(4, 5))
    assert b.shape == (4, 3, 5)

    hs = nd.hard_sigmoid(nd.array(np.array([-10.0, 0.0, 10.0], np.float32)))
    np.testing.assert_allclose(hs.asnumpy(), [0.0, 0.5, 1.0])

    rl = nd.reshape_like(nd.ones((6,)), nd.zeros((2, 3)))
    assert rl.shape == (2, 3)

    m, v = nd.moments(x, axes=(0, 2))
    np.testing.assert_allclose(m.asnumpy(), x.asnumpy().mean(axis=(0, 2)), rtol=1e-5)
    np.testing.assert_allclose(v.asnumpy(), x.asnumpy().var(axis=(0, 2)), rtol=1e-5)

    flat = nd.array(np.array([0, 5, 11], np.int64))
    multi = nd.unravel_index(flat, shape=(3, 4))
    np.testing.assert_array_equal(multi.asnumpy(), np.stack(np.unravel_index([0, 5, 11], (3, 4))))
    back = nd.ravel_multi_index(multi, shape=(3, 4))
    np.testing.assert_array_equal(back.asnumpy(), [0, 5, 11])

    r6 = nd.relu6(nd.array(np.array([-1.0, 3.0, 9.0], np.float32)))
    np.testing.assert_allclose(r6.asnumpy(), [0.0, 3.0, 6.0])

    sm = nd.SoftmaxActivation(nd.array(np.random.RandomState(3).randn(2, 4, 3).astype(np.float32)),
                              mode="channel")
    np.testing.assert_allclose(sm.asnumpy().sum(axis=1), np.ones((2, 3)), rtol=1e-5)


def test_regression_outputs_backward():
    """The *Output heads hard-code their backward: d(data) = out - label
    (scaled), regardless of what's applied on top."""
    rng = np.random.RandomState(4)
    d = nd.array(rng.randn(4, 3).astype(np.float32))
    y = nd.array(rng.randn(4, 3).astype(np.float32))
    d.attach_grad()
    with autograd.record():
        out = nd.LinearRegressionOutput(d, y)
        # arbitrary scaling on top must NOT affect the hard-coded grad
        loss = (out * 123.0).sum()
    loss.backward()
    np.testing.assert_allclose(d.grad.asnumpy(),
                               (d.asnumpy() - y.asnumpy()) / 3, rtol=1e-5)

    d2 = nd.array(rng.randn(4, 1).astype(np.float32))
    y2 = nd.array((rng.rand(4, 1) > 0.5).astype(np.float32))
    d2.attach_grad()
    with autograd.record():
        p = nd.LogisticRegressionOutput(d2, y2)
        p.sum().backward()
    sig = 1 / (1 + np.exp(-d2.asnumpy()))
    np.testing.assert_allclose(d2.grad.asnumpy(), sig - y2.asnumpy(), rtol=1e-5)


def test_make_loss_grad():
    d = nd.array(np.random.RandomState(5).randn(2, 3).astype(np.float32))
    d.attach_grad()
    with autograd.record():
        out = nd.MakeLoss(d, grad_scale=2.0)
    out.backward()
    np.testing.assert_allclose(d.grad.asnumpy(), np.full((2, 3), 2.0))


def test_correlation():
    rng = np.random.RandomState(6)
    f1 = rng.randn(1, 4, 6, 6).astype(np.float32)
    f2 = rng.randn(1, 4, 6, 6).astype(np.float32)
    out = nd.Correlation(nd.array(f1), nd.array(f2), max_displacement=2,
                         stride1=1, stride2=1, pad_size=2)
    assert out.shape == (1, 25, 6, 6)
    # zero displacement channel (center of 5x5 grid = 12) equals mean over C
    np.testing.assert_allclose(out.asnumpy()[:, 12], (f1 * f2).mean(axis=1),
                               rtol=1e-5)
    # displacement (dy=+1, dx=0) -> index 3*5+2=17: out[h] = f1[h]·f2[h+1]
    expect = (f1 * np.pad(f2, ((0, 0), (0, 0), (0, 1), (0, 0)))[:, :, 1:7, :]).mean(axis=1)
    np.testing.assert_allclose(out.asnumpy()[:, 17], expect, rtol=1e-5)


def test_shuffle_permutes():
    x = nd.array(np.arange(10, dtype=np.float32))
    y = nd.shuffle(x)
    assert sorted(y.asnumpy().tolist()) == list(range(10))


def test_round2_parity_ops():
    """identity/softmin/SliceChannel/choose_element_0index/
    fill_element_0index/Crop (ref: elemwise_unary_op_basic.cc, softmax.cc,
    slice_channel.cc, broadcast_reduce_op_index.cc, crop.cc)."""
    import numpy as np

    from mxnet_tpu import nd

    x = nd.array(np.random.RandomState(0).randn(2, 3).astype(np.float32))
    np.testing.assert_array_equal(nd.identity(x).asnumpy(), x.asnumpy())
    ref = np.exp(-x.asnumpy())
    ref /= ref.sum(-1, keepdims=True)
    np.testing.assert_allclose(nd.softmin(x, axis=-1).asnumpy(), ref,
                               rtol=1e-5)

    parts = nd.SliceChannel(
        nd.array(np.arange(12, dtype=np.float32).reshape(2, 6)),
        num_outputs=3)
    assert len(parts) == 3 and parts[0].shape == (2, 2)

    a = nd.array(np.arange(6, dtype=np.float32).reshape(2, 3))
    idx = nd.array(np.array([2, 0], np.float32))
    np.testing.assert_array_equal(
        nd.choose_element_0index(a, idx).asnumpy(), [2.0, 3.0])
    filled = nd.fill_element_0index(
        a, nd.array(np.array([9.0, 8.0], np.float32)), idx).asnumpy()
    np.testing.assert_array_equal(filled, [[0, 1, 9], [8, 4, 5]])

    d = nd.array(np.arange(2 * 1 * 6 * 8, dtype=np.float32).reshape(2, 1, 6, 8))
    np.testing.assert_array_equal(
        nd.Crop(d, h_w=(4, 4), offset=(1, 2)).asnumpy(),
        d.asnumpy()[:, :, 1:5, 2:6])
    like = nd.array(np.zeros((2, 1, 3, 3), np.float32))
    np.testing.assert_array_equal(
        nd.Crop(d, like, center_crop=True).asnumpy(),
        d.asnumpy()[:, :, 1:4, 2:5])


def test_im2col_col2im():
    """im2col matches manual patch extraction; col2im is its exact adjoint
    (<im2col(x), y> == <x, col2im(y)>) (ref: src/operator/nn/im2col.h)."""
    import numpy as np

    from mxnet_tpu import nd

    x4 = nd.array(np.random.RandomState(1).randn(1, 2, 4, 4).astype(np.float32))
    cols = nd.im2col(x4, kernel=(2, 2), stride=(1, 1)).asnumpy()
    assert cols.shape == (1, 8, 9)
    xa = x4.asnumpy()
    man = np.stack([xa[0, :, i:i + 2, j:j + 2].reshape(-1)
                    for i in range(3) for j in range(3)], -1)
    np.testing.assert_allclose(cols[0], man, rtol=1e-5)

    y = np.random.RandomState(2).randn(*cols.shape).astype(np.float32)
    back = nd.col2im(nd.array(y), output_size=(4, 4), kernel=(2, 2)).asnumpy()
    np.testing.assert_allclose((cols * y).sum(), (xa * back).sum(), rtol=1e-4)
    # strided + padded case keeps the adjoint identity
    cols2 = nd.im2col(x4, kernel=(3, 3), stride=(2, 2), pad=(1, 1)).asnumpy()
    y2 = np.random.RandomState(3).randn(*cols2.shape).astype(np.float32)
    back2 = nd.col2im(nd.array(y2), output_size=(4, 4), kernel=(3, 3),
                      stride=(2, 2), pad=(1, 1)).asnumpy()
    np.testing.assert_allclose((cols2 * y2).sum(), (xa * back2).sum(),
                               rtol=1e-4)

def test_digamma_polygamma_scipy_oracle():
    """(ref: special_functions-inl.h digamma/trigamma)."""
    import scipy.special as ss
    from mxnet_tpu import nd

    x = np.array([0.3, 1.0, 2.5, 7.7], np.float32)
    got = nd.digamma(nd.array(x)).asnumpy()
    np.testing.assert_allclose(got, ss.digamma(x), rtol=2e-5, atol=2e-6)

    for n in (1, 2, 3):
        got = nd.polygamma(n, nd.array(x)).asnumpy()
        np.testing.assert_allclose(got, ss.polygamma(n, x).astype(np.float32),
                                   rtol=2e-4, atol=2e-5)

    # digamma is differentiable: d/dx digamma = polygamma(1)
    from mxnet_tpu import autograd
    xa = nd.array(x)
    xa.attach_grad()
    with autograd.record():
        y = nd.digamma(xa)
    y.backward(nd.ones(y.shape))
    np.testing.assert_allclose(xa.grad.asnumpy(),
                               ss.polygamma(1, x).astype(np.float32),
                               rtol=2e-4, atol=2e-5)


def test_contrib_long_tail_utility_ops():
    """arange_like / index_array / index_copy / allclose / div_sqrt_dim /
    gradientmultiplier (ref: src/operator/contrib/*)."""
    from mxnet_tpu import autograd, nd

    c = nd.contrib
    x = nd.array(np.arange(12, dtype=np.float32).reshape(3, 4))
    np.testing.assert_allclose(c.arange_like(x).asnumpy(),
                               np.arange(12, dtype=np.float32).reshape(3, 4))
    np.testing.assert_allclose(c.arange_like(x, axis=1, start=2.0).asnumpy(),
                               [2, 3, 4, 5])
    # repeat repeats each VALUE (nd.arange semantics)
    np.testing.assert_allclose(c.arange_like(x, repeat=2).asnumpy().ravel(),
                               np.repeat(np.arange(6), 2))
    np.testing.assert_allclose(c.arange_like(x, axis=1, repeat=2).asnumpy(),
                               [0, 0, 1, 1])
    ia = c.index_array(x).asnumpy()
    assert ia.shape == (3, 4, 2) and ia[2, 1].tolist() == [2, 1]
    assert c.index_array(x, axes=(-1,)).asnumpy()[1, 3].tolist() == [3]

    old = nd.zeros((4, 3))
    new = nd.array(np.ones((2, 3), np.float32))
    out = c.index_copy(old, nd.array(np.array([1, 3], np.int32)), new)
    assert out.asnumpy()[[1, 3]].sum() == 6 and out.asnumpy()[[0, 2]].sum() == 0

    assert float(c.allclose(x, x).asnumpy()[0]) == 1.0
    assert float(c.allclose(x, x + 1).asnumpy()[0]) == 0.0

    np.testing.assert_allclose(c.div_sqrt_dim(x).asnumpy(),
                               x.asnumpy() / 2.0, rtol=1e-6)

    a = nd.array(np.array([3.0], np.float32))
    a.attach_grad()
    with autograd.record():
        y = c.gradientmultiplier(a, scalar=-0.5)
    y.backward()
    np.testing.assert_allclose(y.asnumpy(), [3.0])      # identity forward
    np.testing.assert_allclose(a.grad.asnumpy(), [-0.5])  # scaled backward

    # BIT-exact identity: the x*s + stop_grad(x - x*s) algebra
    # drifts an ulp at awkward value/scale pairs; custom_vjp must not
    v = np.float32(0.1)
    b = nd.array(np.array([v], np.float32))
    b.attach_grad()
    with autograd.record():
        z = c.gradientmultiplier(b, scalar=0.3)
    z.backward()
    assert z.asnumpy()[0] == v
    np.testing.assert_allclose(b.grad.asnumpy(), [0.3], rtol=1e-6)


def test_contrib_boolean_mask_and_quantize_v2():
    from mxnet_tpu import nd

    c = nd.contrib
    data = nd.array(np.arange(12, dtype=np.float32).reshape(4, 3))
    keep = nd.array(np.array([0, 1, 0, 1], np.float32))
    out = c.boolean_mask(data, keep).asnumpy()
    np.testing.assert_allclose(out, data.asnumpy()[[1, 3]])

    import pytest
    with pytest.raises(ValueError, match="out_type"):
        c.quantize_v2(data, out_type="unit8")
    # auto + non-negative calibrated range -> uint8 (upstream rule)
    qa, _, _ = c.quantize_v2(data, out_type="auto", min_calib_range=0.0,
                             max_calib_range=11.0)
    assert qa.dtype == np.uint8
    q, qmin, qmax = c.quantize_v2(data, min_calib_range=-11.0,
                                  max_calib_range=11.0)
    assert q.dtype == np.int8
    np.testing.assert_allclose(q.asnumpy()[-1, -1], 127)
    deq = q.asnumpy().astype(np.float32) * 11.0 / 127.0
    np.testing.assert_allclose(deq, data.asnumpy(), atol=0.06)


def test_contrib_box_encode_decode_roundtrip():
    from mxnet_tpu import nd

    c = nd.contrib
    rng = np.random.default_rng(0)
    anchors = np.zeros((1, 5, 4), np.float32)
    lo = rng.uniform(0, 0.5, (1, 5, 2)).astype(np.float32)
    anchors[..., :2] = lo
    anchors[..., 2:] = lo + rng.uniform(0.1, 0.4, (1, 5, 2)).astype(np.float32)
    refs = anchors + 0.03  # gt = shifted anchors
    samples = np.ones((1, 5), np.float32)
    matches = np.arange(5, dtype=np.float32)[None]

    t, mask = c.box_encode(nd.array(samples), nd.array(matches),
                           nd.array(anchors), nd.array(refs))
    assert mask.asnumpy().min() == 1.0
    dec = c.box_decode(t, nd.array(anchors)).asnumpy()
    np.testing.assert_allclose(dec, refs, atol=1e-5)


def test_contrib_fft_ifft_roundtrip():
    from mxnet_tpu import nd

    c = nd.contrib
    x = nd.array(np.random.default_rng(1)
                 .normal(size=(3, 8)).astype(np.float32))
    f = c.fft(x)
    assert f.shape == (3, 16)
    # upstream (cuFFT) convention: unnormalized — ifft(fft(x)) == n * x
    back = c.ifft(f).asnumpy()
    np.testing.assert_allclose(back, 8 * x.asnumpy(), rtol=1e-4, atol=1e-4)


def test_contrib_interleaved_matmul_matches_reference_attention():
    """The four transformer.cc interleaved ops compose into standard
    multi-head attention — verified against a plain einsum reference."""
    from mxnet_tpu import nd

    c = nd.contrib
    L, B, H, D = 6, 2, 2, 4
    rng = np.random.default_rng(2)
    qkv = rng.normal(size=(L, B, H * 3 * D)).astype(np.float32)

    scores = c.interleaved_matmul_selfatt_qk(nd.array(qkv), heads=H)
    assert scores.shape == (B * H, L, L)

    # reference from the documented interleaved layout
    x = qkv.reshape(L, B, H, 3, D)
    q, k, v = x[..., 0, :], x[..., 1, :], x[..., 2, :]
    ref = np.einsum("lbhd,mbhd->bhlm", q / np.sqrt(D), k).reshape(B * H, L, L)
    np.testing.assert_allclose(scores.asnumpy(), ref, rtol=1e-5, atol=1e-5)

    att = np.exp(ref) / np.exp(ref).sum(-1, keepdims=True)
    out = c.interleaved_matmul_selfatt_valatt(nd.array(qkv), nd.array(att),
                                              heads=H)
    ref_out = np.einsum("bhlm,mbhd->lbhd",
                        att.reshape(B, H, L, L), v).reshape(L, B, H * D)
    np.testing.assert_allclose(out.asnumpy(), ref_out, rtol=1e-5, atol=1e-5)

    # encdec: q (Lq,B,H*D), kv (M,B,H*2*D)
    Lq, M = 3, 5
    qe = rng.normal(size=(Lq, B, H * D)).astype(np.float32)
    kve = rng.normal(size=(M, B, H * 2 * D)).astype(np.float32)
    s2 = c.interleaved_matmul_encdec_qk(nd.array(qe), nd.array(kve), heads=H)
    kv = kve.reshape(M, B, H, 2, D)
    ref2 = np.einsum("lbhd,mbhd->bhlm", qe.reshape(Lq, B, H, D) / np.sqrt(D),
                     kv[..., 0, :]).reshape(B * H, Lq, M)
    np.testing.assert_allclose(s2.asnumpy(), ref2, rtol=1e-5, atol=1e-5)
    att2 = np.exp(ref2) / np.exp(ref2).sum(-1, keepdims=True)
    o2 = c.interleaved_matmul_encdec_valatt(nd.array(kve), nd.array(att2),
                                            heads=H)
    ref_o2 = np.einsum("bhlm,mbhd->lbhd", att2.reshape(B, H, Lq, M),
                       kv[..., 1, :]).reshape(Lq, B, H * D)
    np.testing.assert_allclose(o2.asnumpy(), ref_o2, rtol=1e-5, atol=1e-5)


def test_group_adagrad_update():
    from mxnet_tpu import nd

    rng = np.random.default_rng(3)
    w = rng.normal(size=(5, 4)).astype(np.float32)
    g = rng.normal(size=(5, 4)).astype(np.float32)
    h = np.zeros((5, 1), np.float32)
    new_w, new_h = nd.contrib.group_adagrad_update(
        nd.array(w), nd.array(g), nd.array(h), lr=0.1)
    h_ref = (g ** 2).mean(axis=1, keepdims=True)
    np.testing.assert_allclose(new_h.asnumpy(), h_ref, rtol=1e-6)
    np.testing.assert_allclose(new_w.asnumpy(),
                               w - 0.1 * g / (np.sqrt(h_ref) + 1e-5),
                               rtol=1e-5)


def test_nn_exposes_block_bases():
    from mxnet_tpu.gluon import nn
    assert nn.HybridBlock is not None and nn.Block is not None
