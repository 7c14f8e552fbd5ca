"""Sparse storage types, sparse ops, and lazy row-sparse optimizer updates
(mirrors reference tests/python/unittest/test_sparse_ndarray.py and
test_sparse_operator.py)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, sparse, gluon, autograd


def _rand_dense(shape, density=0.3, seed=0):
    rng = np.random.RandomState(seed)
    a = rng.randn(*shape).astype(np.float32)
    mask = rng.rand(*shape) < density
    return a * mask


def test_cast_storage_roundtrip():
    dense = _rand_dense((6, 5))
    for stype in ("csr", "row_sparse"):
        sp = sparse.cast_storage(nd.array(dense), stype)
        assert sp.stype == stype
        np.testing.assert_allclose(sp.asnumpy(), dense, rtol=1e-6)
        back = sparse.cast_storage(sp, "default")
        np.testing.assert_allclose(back.asnumpy(), dense, rtol=1e-6)


def test_csr_dot_sparse_kernel():
    dense = _rand_dense((8, 6))
    rhs = np.random.RandomState(1).randn(6, 4).astype(np.float32)
    csr = sparse.csr_matrix(dense)
    out = sparse.dot(csr, nd.array(rhs))
    np.testing.assert_allclose(out.asnumpy(), dense @ rhs, rtol=1e-5, atol=1e-5)
    # transpose_a scatters into columns
    rhs2 = np.random.RandomState(2).randn(8, 3).astype(np.float32)
    out_t = sparse.dot(csr, nd.array(rhs2), transpose_a=True)
    np.testing.assert_allclose(out_t.asnumpy(), dense.T @ rhs2, rtol=1e-5, atol=1e-5)


def test_csr_row_slice():
    dense = _rand_dense((7, 5), seed=3)
    csr = sparse.csr_matrix(dense)
    sub = csr[2:5]
    np.testing.assert_allclose(sub.asnumpy(), dense[2:5], rtol=1e-6)


def test_csr_negative_index_and_copyto():
    dense = _rand_dense((7, 5), seed=9)
    csr = sparse.csr_matrix(dense)
    np.testing.assert_allclose(csr[-1].asnumpy(), dense[-1:], rtol=1e-6)
    np.testing.assert_allclose(csr[-3:].asnumpy(), dense[-3:], rtol=1e-6)
    dst = nd.zeros((7, 5))
    csr.copyto(dst)
    np.testing.assert_allclose(dst.asnumpy(), dense, rtol=1e-6)


def test_dense_to_row_sparse_padded():
    g = np.zeros((16, 4), np.float32)
    g[3] = 1.0
    g[11] = -2.0
    g[12] = 0.5
    rsp = sparse.dense_to_row_sparse_padded(nd.array(g))
    # padded to next power of two (4 slots for 3 rows), OOB fill index = 16
    assert rsp.indices.shape[0] == 4
    np.testing.assert_allclose(rsp.asnumpy(), g, rtol=1e-6)
    # lazy update with padded rows leaves every untouched row alone
    import mxnet_tpu.optimizer as optim
    opt = optim.SGD(learning_rate=1.0, momentum=0.9)
    w = nd.array(np.ones((16, 4), np.float32))
    state = opt.create_state(0, w)
    opt.update(0, w, rsp, state)
    out = w.asnumpy()
    untouched = [r for r in range(16) if r not in (3, 11, 12)]
    np.testing.assert_array_equal(out[untouched], np.ones((13, 4), np.float32))
    assert not np.allclose(out[[3, 11, 12]], 1.0)


def test_retain():
    dense = _rand_dense((9, 4), density=0.8, seed=4)
    rsp = sparse.row_sparse_array(dense)
    kept = sparse.retain(rsp, np.array([1, 3, 5]))
    expect = np.zeros_like(dense)
    for r in (1, 3, 5):
        expect[r] = dense[r]
    np.testing.assert_allclose(kept.asnumpy(), expect, rtol=1e-6)


def test_rsp_elemwise_stays_sparse():
    a = _rand_dense((10, 3), seed=5)
    b = _rand_dense((10, 3), seed=6)
    ra, rb = sparse.row_sparse_array(a), sparse.row_sparse_array(b)
    s = sparse.elemwise_add(ra, rb)
    assert s.stype == "row_sparse"
    np.testing.assert_allclose(s.asnumpy(), a + b, rtol=1e-6)
    d = sparse.elemwise_sub(ra, rb)
    np.testing.assert_allclose(d.asnumpy(), a - b, rtol=1e-6)
    m = sparse.elemwise_mul(ra, rb)
    np.testing.assert_allclose(m.asnumpy(), a * b, rtol=1e-6)
    tot = sparse.add_n(ra, rb, ra)
    np.testing.assert_allclose(tot.asnumpy(), 2 * a + b, rtol=1e-6)


def test_sparse_zeros():
    z = sparse.zeros("row_sparse", (4, 3))
    assert z.asnumpy().sum() == 0
    z2 = sparse.zeros("csr", (4, 3))
    assert z2.asnumpy().sum() == 0


@pytest.mark.parametrize("opt_name", ["sgd", "adam"])
def test_lazy_sparse_update_matches_dense_on_touched_rows(opt_name):
    """Lazy update must equal the dense update on touched rows and leave
    untouched rows (and their state) alone — SGDUpdateRsp semantics."""
    import mxnet_tpu.optimizer as optim

    w0 = np.random.RandomState(7).randn(6, 4).astype(np.float32)
    g_rows = np.array([1, 4], dtype=np.int32)
    g_vals = np.random.RandomState(8).randn(2, 4).astype(np.float32)

    kwargs = {"momentum": 0.9} if opt_name == "sgd" else {}
    opt_lazy = optim.create(opt_name, learning_rate=0.1, **kwargs)
    opt_dense = optim.create(opt_name, learning_rate=0.1, **kwargs)
    if hasattr(opt_dense, "lazy_update"):
        opt_dense.lazy_update = False

    w_lazy = nd.array(w0.copy())
    state = opt_lazy.create_state(0, w_lazy)
    rsp = sparse.RowSparseNDArray(g_vals, g_rows, w0.shape)
    state = opt_lazy.update(0, w_lazy, rsp, state)

    w_dense = nd.array(w0.copy())
    state_d = opt_dense.create_state(0, w_dense)
    g_dense = np.zeros_like(w0)
    g_dense[g_rows] = g_vals
    opt_dense.update(0, w_dense, nd.array(g_dense), state_d)

    out_lazy, out_dense = w_lazy.asnumpy(), w_dense.asnumpy()
    # touched rows match the dense update exactly
    np.testing.assert_allclose(out_lazy[g_rows], out_dense[g_rows],
                               rtol=1e-5, atol=1e-6)
    # untouched rows are bit-identical to the initial weights (lazy semantics;
    # dense adam would decay them via bias correction of zero grads)
    untouched = [r for r in range(6) if r not in g_rows.tolist()]
    np.testing.assert_array_equal(out_lazy[untouched], w0[untouched])


def test_embedding_sparse_grad_end_to_end():
    """Embedding(sparse_grad=True) + Trainer: only embedded rows move."""
    emb = gluon.nn.Embedding(20, 8, sparse_grad=True)
    emb.initialize()
    trainer = gluon.Trainer(emb.collect_params(), "sgd",
                            {"learning_rate": 0.5, "momentum": 0.0})
    w0 = emb.weight.data().asnumpy().copy()
    x = nd.array(np.array([[1, 3], [3, 7]], dtype=np.int64))
    with autograd.record():
        y = emb(x)
        loss = (y * y).sum()
    loss.backward()
    trainer.step(1)
    w1 = emb.weight.data().asnumpy()
    moved = sorted(set(np.nonzero(np.abs(w1 - w0).sum(axis=1) > 1e-9)[0].tolist()))
    assert moved == [1, 3, 7]
    untouched = [r for r in range(20) if r not in (1, 3, 7)]
    np.testing.assert_array_equal(w1[untouched], w0[untouched])


def test_contrib_sparse_embedding_is_actually_sparse():
    """gluon.contrib.nn.SparseEmbedding must carry row_sparse gradients and
    take the lazy-update path, not silently alias a dense Embedding."""
    from mxnet_tpu.gluon.contrib.nn import SparseEmbedding

    se = SparseEmbedding(16, 4)
    se.initialize()
    (p,) = se.collect_params().values()
    assert p._grad_stype == "row_sparse"
    trainer = gluon.Trainer(se.collect_params(), "sgd",
                            {"learning_rate": 1.0, "momentum": 0.0})
    w0 = p.data().asnumpy().copy()
    x = nd.array(np.array([[2, 5]], dtype=np.int64))
    with autograd.record():
        loss = (se(x) ** 2).sum()
    loss.backward()
    trainer.step(1)
    w1 = p.data().asnumpy()
    moved = sorted(set(np.nonzero(np.abs(w1 - w0).sum(axis=1) > 1e-9)[0].tolist()))
    assert moved == [2, 5]


def test_kvstore_row_sparse_pull():
    import mxnet_tpu as mx

    kv = mx.kvstore.create("local")
    w = nd.array(np.arange(20, dtype=np.float32).reshape(5, 4))
    kv.init("emb", w)
    out = nd.zeros((5, 4))
    kv.row_sparse_pull("emb", out=out, row_ids=nd.array([1.0, 3.0]))
    got = out.asnumpy()
    np.testing.assert_allclose(got[1], w.asnumpy()[1])
    np.testing.assert_allclose(got[3], w.asnumpy()[3])
    np.testing.assert_allclose(got[[0, 2, 4]], 0.0)


def test_csr_vs_scipy_oracle():
    """CSR construction, dot, transpose-dot, and elemwise vs scipy.sparse —
    an independent external implementation (ref: src/ndarray sparse +
    src/operator/tensor/dot.cc)."""
    import numpy as np
    import scipy.sparse as sp

    from mxnet_tpu import nd, sparse

    rng = np.random.default_rng(0)
    dense = rng.normal(size=(17, 11)).astype(np.float32)
    dense[rng.random((17, 11)) > 0.25] = 0.0  # ~75% sparse
    ref = sp.csr_matrix(dense)

    csr = sparse.csr_matrix(dense)
    # structure matches scipy exactly
    np.testing.assert_array_equal(np.asarray(csr.indptr.asnumpy()), ref.indptr)
    np.testing.assert_array_equal(np.asarray(csr.indices.asnumpy()), ref.indices)
    np.testing.assert_allclose(np.asarray(csr.data.asnumpy()), ref.data, rtol=1e-6)

    rhs = rng.normal(size=(11, 5)).astype(np.float32)
    np.testing.assert_allclose(sparse.dot(csr, nd.array(rhs)).asnumpy(),
                               ref @ rhs, rtol=1e-5, atol=1e-6)
    # transpose_a dot
    rhs2 = rng.normal(size=(17, 3)).astype(np.float32)
    got = sparse.dot(csr, nd.array(rhs2), transpose_a=True)
    np.testing.assert_allclose(got.asnumpy(), ref.T @ rhs2, rtol=1e-5,
                               atol=1e-6)
    # roundtrip through dense
    np.testing.assert_allclose(csr.todense().asnumpy(), ref.toarray(),
                               rtol=1e-6)


def test_csr_slicing_vs_scipy():
    import numpy as np
    import scipy.sparse as sp

    from mxnet_tpu import sparse

    rng = np.random.default_rng(1)
    dense = rng.normal(size=(9, 6)).astype(np.float32)
    dense[rng.random((9, 6)) > 0.4] = 0.0
    ref = sp.csr_matrix(dense)
    csr = sparse.csr_matrix(dense)
    for sl in (slice(2, 7), slice(0, 9), slice(8, 9)):
        np.testing.assert_allclose(csr[sl].todense().asnumpy(),
                                   ref[sl].toarray(), rtol=1e-6)
