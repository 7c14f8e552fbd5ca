"""Module API: graph shape inference (no declared weight shapes), bind flags
(for_training, inputs_need_grad), get_input_grads — mirrors the reference's
tests/python/unittest/test_module.py + executor infer-shape cases."""
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import sym, nd
from mxnet_tpu.io import DataBatch
from mxnet_tpu.module import Module


def _conv_net():
    data = sym.var("data")
    label = sym.var("softmax_label")
    w1 = sym.var("conv_weight")
    b1 = sym.var("conv_bias")
    c = sym.Convolution(data, w1, b1, kernel=(3, 3), num_filter=6, pad=1)
    g = sym.var("bn_gamma")
    be = sym.var("bn_beta")
    mm = sym.var("bn_mm")
    mv = sym.var("bn_mv")
    bn = sym.BatchNorm(c, g, be, mm, mv)[0]
    act = sym.relu(bn)
    p = sym.Pooling(act, kernel=(2, 2), stride=(2, 2), pool_type="max")
    fw = sym.var("fc_weight")
    fb = sym.var("fc_bias")
    fc = sym.FullyConnected(p, fw, fb, num_hidden=5)
    return sym.SoftmaxOutput(fc, label)


def test_infer_shape_no_declared_shapes():
    net = _conv_net()
    arg_shapes, out_shapes, _ = net.infer_shape(data=(2, 3, 8, 8),
                                                softmax_label=(2,))
    byname = dict(zip(net.list_arguments(), arg_shapes))
    assert byname["conv_weight"] == (6, 3, 3, 3)
    assert byname["conv_bias"] == (6,)
    assert byname["bn_gamma"] == (6,)
    assert byname["fc_weight"] == (5, 6 * 4 * 4)
    assert byname["fc_bias"] == (5,)
    assert out_shapes[0] == (2, 5)


def test_module_binds_without_param_shapes():
    net = _conv_net()
    m = Module(net, data_names=("data",), label_names=("softmax_label",))
    m.bind([("data", (2, 3, 8, 8))], [("softmax_label", (2,))])
    m.init_params()
    assert m._arg_params["conv_weight"].shape == (6, 3, 3, 3)
    rng = np.random.default_rng(0)
    batch = DataBatch([nd.array(rng.normal(size=(2, 3, 8, 8)))],
                      [nd.array(rng.integers(0, 5, (2,)))])
    out = m.forward(batch, is_train=False)
    assert out[0].shape == (2, 5)


def test_deconv_embedding_inference():
    data = sym.var("data")
    w = sym.var("deconv_weight")
    y = sym.Deconvolution(data, w, kernel=(2, 2), stride=(2, 2), num_filter=4,
                          no_bias=True)
    args, outs, _ = y.infer_shape(data=(1, 3, 5, 5))
    byname = dict(zip(y.list_arguments(), args))
    assert byname["deconv_weight"] == (3, 4, 2, 2)
    assert outs[0] == (1, 4, 10, 10)

    idx = sym.var("idx")
    ew = sym.var("embed_weight")
    e = sym.Embedding(idx, ew, input_dim=11, output_dim=7)
    args, outs, _ = e.infer_shape(idx=(4, 3))
    assert dict(zip(e.list_arguments(), args))["embed_weight"] == (11, 7)
    assert outs[0] == (4, 3, 7)


def test_inputs_need_grad():
    data = sym.var("data")
    label = sym.var("softmax_label")
    fw = sym.var("fc_weight")
    fb = sym.var("fc_bias")
    fc = sym.FullyConnected(data, fw, fb, num_hidden=3)
    net = sym.SoftmaxOutput(fc, label)
    m = Module(net)
    m.bind([("data", (4, 6))], [("softmax_label", (4,))],
           inputs_need_grad=True)
    m.init_params(initializer=mx.init.Uniform(0.3))
    rng = np.random.default_rng(0)
    batch = DataBatch([nd.array(rng.normal(size=(4, 6)))],
                      [nd.array(rng.integers(0, 3, (4,)))])
    m.forward(batch, is_train=True)
    m.backward()
    (g,) = m.get_input_grads()
    assert g.shape == (4, 6)
    assert float(np.abs(g.asnumpy()).max()) > 0


def test_infer_shape_order_independent():
    """A weight USED (weight-decay term) before the node that determines its
    shape must still resolve — fixpoint iteration, not single-pass DFS."""
    data = sym.var("data")
    w = sym.var("fc_weight")
    reg = sym.sum(w * w)
    fc = sym.FullyConnected(data, w, num_hidden=3, no_bias=True)
    for group in (sym.Group([reg, fc]), sym.Group([fc, reg])):
        args, outs, _ = group.infer_shape(data=(2, 4))
        byname = dict(zip(group.list_arguments(), args))
        assert byname["fc_weight"] == (3, 4)


def test_attr_weight_mismatch_raises():
    data = sym.var("data")
    w = sym.var("w", shape=(7, 4))
    fc = sym.FullyConnected(data, w, num_hidden=3, no_bias=True)
    try:
        fc.infer_shape(data=(2, 4))
        assert False, "expected infer-shape mismatch error"
    except ValueError as e:
        assert "num_hidden" in str(e)


def test_infer_error_names_failing_node():
    data = sym.var("data")
    w = sym.var("w2", shape=(3, 5))  # (2,4)@(5,3) mismatch
    fc = sym.FullyConnected(data, w, num_hidden=3, no_bias=True)
    try:
        fc.infer_shape(data=(2, 4))
        assert False, "expected error"
    except ValueError as e:
        assert "FullyConnected" in str(e)


def test_nhwc_conv_inference():
    data = sym.var("data")
    w = sym.var("w")
    y = sym.Convolution(data, w, kernel=(3, 3), num_filter=8, layout="NHWC",
                        no_bias=True)
    # channel axis is last for NHWC; weight stays OIHW
    from mxnet_tpu.shape_inference import infer_shapes_partial
    var_shapes, _, _ = infer_shapes_partial(y, {"data": (2, 8, 8, 3)})
    assert var_shapes["w"] == (8, 3, 3, 3)


def test_simple_bind_infers_param_shapes():
    net = _conv_net()
    ex = net.simple_bind(data=(2, 3, 8, 8), softmax_label=(2,))
    assert ex.arg_dict["conv_weight"].shape == (6, 3, 3, 3)
    assert ex.arg_dict["fc_weight"].shape == (5, 6 * 4 * 4)


def test_for_training_flag_default():
    data = sym.var("data")
    w = sym.var("fc_weight")
    fc = sym.FullyConnected(data, w, num_hidden=2, no_bias=True)
    m = Module(fc, label_names=())
    m.bind([("data", (2, 3))], for_training=False)
    m.init_params()
    batch = DataBatch([nd.array(np.ones((2, 3)))], None)
    m.forward(batch)  # is_train defaults to for_training=False
    assert m._exec._vjp is None


def test_sequential_module_chains_and_trains():
    """SequentialModule (ref: python/mxnet/module/sequential_module.py):
    outputs feed the next stage, backward hands input grads upstream as
    out_grads, update touches every stage's params."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.io import DataBatch
    from mxnet_tpu.module import Module, SequentialModule

    d = mx.sym.var("data")
    s1 = mx.sym.FullyConnected(d, num_hidden=8, name="fc1")
    s1 = mx.sym.Activation(s1, act_type="relu")
    d2 = mx.sym.var("data")
    s2 = mx.sym.FullyConnected(d2, num_hidden=3, name="fc2")
    s2 = mx.sym.SoftmaxOutput(s2, name="softmax")

    seq = SequentialModule()
    seq.add(Module(s1, label_names=[]))
    seq.add(Module(s2), take_labels=True)
    seq.bind(data_shapes=[("data", (4, 6))],
             label_shapes=[("softmax_label", (4,))])
    seq.init_params()
    seq.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 1.0})

    rng = np.random.default_rng(0)
    x = nd.array(rng.normal(size=(4, 6)).astype(np.float32))
    y = nd.array(np.array([0, 1, 2, 0], np.float32))
    batch = DataBatch(data=[x], label=[y])

    def nll():
        out = seq.forward(batch, is_train=True)[0].asnumpy()
        return -np.log(out[np.arange(4), y.asnumpy().astype(int)] + 1e-9).mean()

    first = nll()
    for _ in range(60):
        seq.forward(batch, is_train=True)
        seq.backward()
        seq.update()
    last = nll()
    assert last < first * 0.5, (first, last)
    arg, _ = seq.get_params()
    assert any(k.startswith("fc1") for k in arg)
    assert any(k.startswith("fc2") for k in arg)


def test_executor_is_train_governs_dropout_and_bn():
    """forward(is_train) selects op behavior at run time like upstream's
    executors (src/executor): dropout actually drops in training and is the
    identity in inference; BatchNorm moving stats update during Module
    training and drive eval-mode outputs."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.io import DataBatch
    from mxnet_tpu.module import Module

    # --- executor-level dropout
    x = mx.sym.var("x", shape=(4, 50))
    ex = mx.sym.Dropout(x, p=0.5).bind(
        args={"x": nd.array(np.ones((4, 50), np.float32))})
    infer = ex.forward(is_train=False)[0].asnumpy()
    np.testing.assert_array_equal(infer, np.ones((4, 50), np.float32))
    train1 = ex.forward(is_train=True)[0].asnumpy()
    train2 = ex.forward(is_train=True)[0].asnumpy()
    assert (train1 == 0).any() and (train2 == 0).any()
    assert not np.array_equal(train1, train2)  # fresh mask per call
    assert set(np.unique(train1)) <= {0.0, 2.0}  # inverted scaling

    # --- Module-level BN stat write-back
    data = mx.sym.var("data")
    net = mx.sym.BatchNorm(data, name="bn0", momentum=0.5)
    net = mx.sym.FullyConnected(net, num_hidden=2, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = Module(net)
    mod.bind(data_shapes=[("data", (8, 4))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params()
    rng = np.random.default_rng(0)
    X = (rng.normal(size=(8, 4)) * 3.0 + 1.5).astype(np.float32)
    Y = np.zeros(8, np.float32)
    batch = DataBatch(data=[nd.array(X)], label=[nd.array(Y)])
    mm0 = mod._arg_params["bn0_moving_mean"].asnumpy().copy()
    mod.forward(batch, is_train=True)
    mod.backward()
    mm1 = mod._arg_params["bn0_moving_mean"].asnumpy()
    # momentum blend toward the batch mean
    want = 0.5 * mm0 + 0.5 * X.mean(0)
    np.testing.assert_allclose(mm1, want, rtol=1e-4, atol=1e-5)
    # eval-mode output uses the UPDATED stats (differs from before training)
    out_a = mod.forward(batch, is_train=False)[0].asnumpy()
    mod._arg_params["bn0_moving_mean"]._data = nd.array(mm0)._data
    out_b = mod.forward(batch, is_train=False)[0].asnumpy()
    assert not np.allclose(out_a, out_b)


def test_executor_backward_after_eval_forward_keeps_key_alignment():
    """Regression: an eval forward between a train forward and backward()
    must not desync the key-cotangent stripping (the vjp remembers whether
    ITS program was keyed)."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import nd

    x = mx.sym.var("x", shape=(4, 8))
    y = mx.sym.Dropout(x, p=0.5) * 2.0
    ex = y.bind(args={"x": nd.array(np.ones((4, 8), np.float32))},
                args_grad={"x": nd.array(np.zeros((4, 8), np.float32))})
    ex.forward(is_train=True)
    ex.forward(is_train=False)  # validation pass in between
    ex.backward()
    g = ex.grad_dict["x"].asnumpy()
    assert g.dtype == np.float32
    assert set(np.unique(g)) <= {0.0, 4.0}  # kept units: 2 / (1-p) = 4


def test_module_group_outputs_preserved_with_bn():
    """A Group-headed Module returns ALL heads, and the BN aux write-back
    tail never bleeds into main outputs (regression: group head count)."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.io import DataBatch
    from mxnet_tpu.module import Module

    d = mx.sym.var("data")
    h = mx.sym.BatchNorm(d, name="bn0")
    g = mx.sym.Group([mx.sym.relu(h), mx.sym.tanh(h)])
    mod = Module(g, label_names=[])
    mod.bind(data_shapes=[("data", (4, 3))])
    mod.init_params()
    batch = DataBatch(data=[nd.array(np.random.default_rng(0)
                                     .normal(size=(4, 3))
                                     .astype(np.float32))], label=[])
    outs = mod.forward(batch, is_train=True)
    assert len(outs) == 2
    assert outs[0].shape == (4, 3) and outs[1].shape == (4, 3)
    # moving stats hold stat-shaped values, not head tensors
    assert mod._arg_params["bn0_moving_mean"].shape == (3,)
    mod.backward([nd.array(np.ones((4, 3), np.float32)),
                  nd.array(np.ones((4, 3), np.float32))])


def test_module_save_checkpoint_and_load(tmp_path):
    """Module.save_checkpoint writes the upstream prefix-symbol.json +
    prefix-NNNN.params layout; Module.load rebuilds and reproduces outputs
    (ref: module/module.py:save_checkpoint/load)."""
    import os

    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.io import DataBatch
    from mxnet_tpu.module import Module

    rng = np.random.default_rng(3)
    d = mx.sym.var("data")
    h = mx.sym.FullyConnected(d, num_hidden=8, name="fc1")
    out = mx.sym.FullyConnected(mx.sym.relu(h), num_hidden=2, name="fc2")
    mod = Module(out, label_names=[])
    mod.bind(data_shapes=[("data", (4, 5))])
    mod.init_params()
    batch = DataBatch(data=[nd.array(rng.normal(size=(4, 5))
                                     .astype(np.float32))], label=[])
    ref = mod.forward(batch, is_train=False)[0].asnumpy()

    prefix = str(tmp_path / "ckpt")
    mod.save_checkpoint(prefix, 7)
    assert os.path.exists(prefix + "-symbol.json")
    assert os.path.exists(prefix + "-0007.params")  # exact upstream name

    mod2 = Module.load(prefix, 7, label_names=[])
    mod2.bind(data_shapes=[("data", (4, 5))])
    mod2.init_params()
    got = mod2.forward(batch, is_train=False)[0].asnumpy()
    np.testing.assert_allclose(got, ref, rtol=1e-6)


def test_module_predict_score_and_properties():
    """BaseModule conveniences: predict (pad-aware concat), score,
    forward_backward/update_metric, and the shape/name properties
    (ref: python/mxnet/module/base_module.py)."""
    import mxnet_tpu as mx
    from mxnet_tpu.module import Module

    rng = np.random.default_rng(0)
    X = rng.normal(size=(10, 6)).astype(np.float32)
    Y = (X[:, 0] > 0).astype(np.float32)

    d = mx.sym.var("data")
    out = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(d, num_hidden=2, name="fc"), name="softmax")
    mod = Module(out)
    it = mx.io.NDArrayIter(X, Y, batch_size=4, last_batch_handle="pad")
    mod.bind(data_shapes=[("data", (4, 6))], label_shapes=[("softmax_label", (4,))])
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd", optimizer_params={"learning_rate": 0.1})

    assert mod.data_names == ["data"]
    assert mod.symbol is out
    assert mod.data_shapes[0].shape == (4, 6)
    assert dict(mod.output_shapes)[mod.output_names[0]] == (4, 2)

    # predict concatenates and strips the final pad batch
    preds = mod.predict(it)
    assert preds.shape == (10, 2)
    np.testing.assert_allclose(preds.asnumpy().sum(1), 1.0, rtol=1e-5)

    # train a few epochs via forward_backward + update_metric
    em = mx.metric.Accuracy()
    for _ in range(15):
        it.reset()
        for batch in it:
            mod.forward_backward(batch)
            mod.update()
            mod.update_metric(em, batch.label)
    (name, acc), = mod.score(it, "accuracy")
    assert name == "accuracy" and acc > 0.7
    # composite metric: upstream flat (name, value) pairs
    pairs = mod.score(it, ["accuracy", "crossentropy"])
    assert [n for n, _ in pairs] == ["accuracy", "cross-entropy"]
    # merge_batches=False: per-batch output lists, pad-stripped on the tail
    per_batch = mod.predict(it, merge_batches=False)
    assert len(per_batch) == 3 and per_batch[0][0].shape == (4, 2)
    assert per_batch[-1][0].shape == (2, 2)


def test_module_checkpoint_aux_split(tmp_path):
    """BN moving stats save under 'aux:' keys in the mx.model layout and
    round-trip through load_checkpoint/set_params
    (ref: python/mxnet/model.py save_checkpoint arg/aux split)."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.io import DataBatch
    from mxnet_tpu.module import Module

    d = mx.sym.var("data")
    out = mx.sym.FullyConnected(mx.sym.BatchNorm(d, name="bn0"),
                                num_hidden=2, name="fc")
    mod = Module(out, label_names=[])
    mod.bind(data_shapes=[("data", (4, 3))])
    mod.init_params()
    batch = DataBatch(data=[nd.array(np.random.default_rng(0)
                                     .normal(size=(4, 3))
                                     .astype(np.float32))], label=[])
    mod.forward(batch, is_train=True)  # updates moving stats

    args, aux = mod.get_params()
    assert "bn0_moving_mean" in aux and "bn0_moving_var" in aux
    assert not any(n.endswith(("moving_mean", "moving_var")) for n in args)

    prefix = str(tmp_path / "m")
    mod.save_checkpoint(prefix, 1)
    _, arg2, aux2 = mx.model.load_checkpoint(prefix, 1)
    assert "bn0_moving_mean" in aux2 and "fc_weight" in arg2
    np.testing.assert_allclose(aux2["bn0_moving_mean"].asnumpy(),
                               aux["bn0_moving_mean"].asnumpy())

    mod2 = Module(out, label_names=[])
    mod2.bind(data_shapes=[("data", (4, 3))])
    mod2.init_params()
    mod2.set_params(arg2, aux2)
    ref = mod.forward(batch, is_train=False)[0].asnumpy()
    np.testing.assert_allclose(mod2.forward(batch, is_train=False)[0].asnumpy(),
                               ref, rtol=1e-6)

def test_set_params_before_bind_warns():
    """Pre-bind there are no known names to validate against, so set_params
    must warn loudly that typo'd names cannot be caught while
    keeping the documented apply-at-bind flow."""
    import pytest

    from mxnet_tpu import nd, sym
    from mxnet_tpu.module import Module

    data = sym.var("data")
    out = sym.FullyConnected(data, name="fc", num_hidden=2)
    mod = Module(out, label_names=[])
    with pytest.warns(UserWarning, match="before bind"):
        mod.set_params({"fc_weight": nd.zeros((2, 3))})
    assert "fc_weight" in mod._arg_params


def test_set_params_after_bind_takes_effect():
    """set_params on a BOUND module must write through to the executor:
    forward reads the bound arg NDArrays, so post-bind set_params has to
    update values in place, not swap dict entries."""
    data = sym.var("data")
    fw = sym.var("fc_weight")
    fb = sym.var("fc_bias")
    out = sym.FullyConnected(data, fw, fb, num_hidden=3)
    m = Module(out, data_names=("data",), label_names=())
    m.bind([("data", (2, 4))], for_training=False)
    m.init_params()
    x = nd.array(np.ones((2, 4), np.float32))
    first = m.forward(DataBatch([x], None), is_train=False)[0].asnumpy()

    w = np.full((3, 4), 0.5, np.float32)
    b = np.arange(3, dtype=np.float32)
    m.set_params({"fc_weight": nd.array(w), "fc_bias": nd.array(b)})
    got = m.forward(DataBatch([x], None), is_train=False)[0].asnumpy()
    want = np.ones((2, 4), np.float32) @ w.T + b
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert not np.allclose(first, got)


def test_set_params_shape_mismatch_raises():
    data = sym.var("data")
    fw = sym.var("fc_weight")
    fb = sym.var("fc_bias")
    out = sym.FullyConnected(data, fw, fb, num_hidden=3)
    m = Module(out, data_names=("data",), label_names=())
    m.bind([("data", (2, 4))], for_training=False)
    m.init_params()
    import pytest
    with pytest.raises(ValueError, match="fc_weight"):
        m.set_params({"fc_weight": nd.array(np.zeros((5, 4), np.float32))},
                     allow_missing=True)


def test_set_params_rejects_unknown_and_missing_names():
    import pytest
    data = sym.var("data")
    fw = sym.var("fc_weight")
    fb = sym.var("fc_bias")
    out = sym.FullyConnected(data, fw, fb, num_hidden=3)
    m = Module(out, data_names=("data",), label_names=())
    m.bind([("data", (2, 4))], for_training=False)
    m.init_params()
    w = nd.array(np.zeros((3, 4), np.float32))
    b = nd.array(np.zeros((3,), np.float32))
    with pytest.raises(ValueError, match="unknown parameter"):
        m.set_params({"fc_weigth": w, "fc_bias": b})  # typo must not be a no-op
    with pytest.raises(ValueError, match="missing parameter"):
        m.set_params({"fc_weight": w})
    m.set_params({"fc_weight": w}, allow_missing=True)  # explicit opt-in ok
    m.set_params({"fc_weight": w, "fc_bias": b, "junk": b}, allow_extra=True)


def test_set_params_before_bind_keeps_all_entries():
    """Pre-bind set_params (empty _arg_params) must store EVERY given param
    — regression: the allow_extra skip once re-checked membership against
    the dict it was filling, dropping all but the first entry."""
    data = sym.var("data")
    fw = sym.var("fc_weight")
    fb = sym.var("fc_bias")
    out = sym.FullyConnected(data, fw, fb, num_hidden=3)
    m = Module(out, data_names=("data",), label_names=())
    w = nd.array(np.full((3, 4), 0.25, np.float32))
    b = nd.array(np.arange(3, dtype=np.float32))
    m.set_params({"fc_weight": w, "fc_bias": b})
    assert set(m._arg_params) == {"fc_weight", "fc_bias"}

    m.bind([("data", (2, 4))], for_training=False)
    x = nd.array(np.ones((2, 4), np.float32))
    got = m.forward(DataBatch([x], None), is_train=False)[0].asnumpy()
    want = np.ones((2, 4), np.float32) @ w.asnumpy().T + b.asnumpy()
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_set_params_force_init_false_keeps_values():
    import pytest
    data = sym.var("data")
    fw = sym.var("fc_weight")
    fb = sym.var("fc_bias")
    out = sym.FullyConnected(data, fw, fb, num_hidden=3)
    m = Module(out, data_names=("data",), label_names=())
    m.bind([("data", (2, 4))], for_training=False)
    m.init_params()
    before = m._arg_params["fc_weight"].asnumpy().copy()
    with pytest.warns(UserWarning, match="force_init"):
        m.set_params({"fc_weight": nd.array(np.zeros((3, 4), np.float32)),
                      "fc_bias": nd.array(np.zeros(3, np.float32))},
                     force_init=False)
    np.testing.assert_allclose(m._arg_params["fc_weight"].asnumpy(), before)


def test_callback_module_checkpoint(tmp_path):
    """(ref: callback.py:module_checkpoint) saves the upstream
    prefix-symbol.json + prefix-NNNN.params layout from a bound Module."""
    import os

    from mxnet_tpu import callback

    data = sym.var("data")
    out = sym.FullyConnected(data, sym.var("fc_weight"), sym.var("fc_bias"),
                             num_hidden=3)
    m = Module(out, data_names=("data",), label_names=())
    m.bind([("data", (2, 4))], for_training=False)
    m.init_params()
    cb = callback.module_checkpoint(m, str(tmp_path / "ck"), period=2)
    cb(0)  # epoch 1: not a period multiple
    assert not os.path.exists(str(tmp_path / "ck-0001.params"))
    cb(1)  # epoch 2
    assert os.path.exists(str(tmp_path / "ck-0002.params"))
    assert os.path.exists(str(tmp_path / "ck-symbol.json"))

    m2 = Module.load(str(tmp_path / "ck"), 2, data_names=("data",),
                     label_names=())
    m2.bind([("data", (2, 4))], for_training=False)
    m2.init_params()  # applies the preloaded checkpoint params
    x = nd.array(np.ones((2, 4), np.float32))
    np.testing.assert_allclose(
        m2.forward(DataBatch([x], None), is_train=False)[0].asnumpy(),
        m.forward(DataBatch([x], None), is_train=False)[0].asnumpy(),
        rtol=1e-6)
