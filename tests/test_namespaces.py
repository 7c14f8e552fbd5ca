"""mx.name / mx.attribute / mx.runtime top-level API parity (ref:
python/mxnet/name.py, attribute.py, runtime.py)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import attribute, name, sym


def test_name_manager_uniquifies_and_prefixes():
    a = sym.var("x", shape=(2, 2))
    s1 = mx.sym.relu(a)
    s2 = mx.sym.relu(a)
    assert s1.name != s2.name
    with name.Prefix("net_"):
        s3 = mx.sym.relu(a)
    assert s3.name.startswith("net_relu")
    with name.NameManager():   # fresh manager restarts counters in scope
        s4 = mx.sym.relu(a)
    assert s4.name == "relu0"
    # explicit names always win
    s5 = mx.sym.relu(a, name="myrelu")
    assert s5.name == "myrelu"


def test_attr_scope_attaches_and_nests():
    a = sym.var("x", shape=(2, 2))
    with attribute.AttrScope(ctx_group="dev1"):
        s = mx.sym.relu(a)
    assert s.attr("ctx_group") == "dev1"
    with attribute.AttrScope(a1="x"):
        with attribute.AttrScope(a2="y"):
            s2 = mx.sym.relu(a)
    assert s2.attr("a1") == "x" and s2.attr("a2") == "y"
    # scope annotations never leak into op kwargs: the node still executes
    with attribute.AttrScope(ctx_group="dev1"):
        s3 = mx.sym.Activation(a, act_type="relu")
    assert s3.attr("ctx_group") == "dev1"
    assert s3.attr("act_type") == "relu"    # op kwargs still visible via attr
    out = s3.eval(x=mx.nd.array([[1.0, -1.0], [2.0, -2.0]]))
    assert out[0].shape == (2, 2)
    with pytest.raises(ValueError):
        attribute.AttrScope(bad=3)


def test_attr_scope_does_not_leak_into_load(tmp_path):
    """symbol.load inside an AttrScope must not absorb scope attributes —
    deserialization rebuilds the graph exactly as saved."""
    from mxnet_tpu import symbol

    a = sym.var("x", shape=(2, 2))
    s = mx.sym.relu(a)
    p = str(tmp_path / "g.json")
    s.save(p)
    with attribute.AttrScope(ctx_group="dev9"):
        loaded = symbol.load(p)
    assert loaded.attr("ctx_group") is None


def test_runtime_features():
    f = mx.runtime.Features()
    assert f.is_enabled("XLA")
    assert not f.is_enabled("CUDA")   # single-backend design (SURVEY §2 #41)
    assert "TPU" in f and "INT8" in f
    assert any(x.enabled for x in mx.runtime.feature_list())
    with pytest.raises(RuntimeError):
        f.is_enabled("NOT_A_FEATURE")


def test_util_np_mode_switches():
    """mx.util numpy-mode scopes/decorators delegate to npx's switch (ref:
    python/mxnet/util.py use_np family)."""
    from mxnet_tpu import npx, util

    npx.reset_np()
    assert not util.is_np_array()
    with util.np_array():
        assert util.is_np_array()
    assert not util.is_np_array()

    @mx.use_np
    def f():
        return util.is_np_array()

    assert f() is True
    assert not util.is_np_array()

    @mx.use_np
    class C:
        def m(self):
            return util.is_np_array()

    assert C().m() is True
    assert not util.is_np_array()


def test_nd_save_load_namespace_visible():
    import numpy as np

    assert callable(mx.nd.save) and callable(mx.nd.load)


def test_contrib_text_vocabulary_and_embedding(tmp_path):
    """mx.contrib.text Vocabulary/CustomEmbedding (ref:
    python/mxnet/contrib/text/{vocab,embedding}.py)."""
    import numpy as np

    from mxnet_tpu.contrib import text

    c = text.count_tokens_from_str("the cat sat on the mat\nthe dog")
    assert c["the"] == 3
    v = text.Vocabulary(c, min_freq=1, reserved_tokens=["<pad>"])
    assert v.to_indices("the") > 1 and v.to_indices("unicorn") == 0
    assert v.to_tokens(0) == "<unk>" and v.idx_to_token[1] == "<pad>"
    assert len(v) == 2 + len(c)
    v2 = text.Vocabulary(c, most_freq_count=2)
    assert len(v2) == 3   # unk + top-2
    with pytest.raises(ValueError):
        text.Vocabulary(c, reserved_tokens=["<unk>"])

    p = tmp_path / "emb.txt"
    p.write_text("the 1 0 0\ncat 0 1 0\nmat 0 0 1\n")
    emb = text.CustomEmbedding(str(p), vocabulary=v)
    assert emb.idx_to_vec.shape == (len(v), 3)
    np.testing.assert_array_equal(emb.idx_to_vec[v.to_indices("cat")],
                                  [0, 1, 0])
    np.testing.assert_array_equal(emb.get_vecs_by_tokens("unicorn"),
                                  [0, 0, 0])   # single token → 1-D
    assert emb.get_vecs_by_tokens(["the", "cat"]).shape == (2, 3)
    # reserved tokens in the counter must not consume most_freq_count slots
    import collections
    c2 = collections.Counter({"<pad>": 10, "a": 5, "b": 3})
    v3 = text.Vocabulary(c2, most_freq_count=2, reserved_tokens=["<pad>"])
    assert "a" in v3.token_to_idx and "b" in v3.token_to_idx
    assert mx.contrib.quantization is not None
    assert hasattr(mx.contrib.ndarray, "box_nms")


def test_metric_np_and_gluon_metric():
    """mx.metric.np wraps a numpy feval; gluon.metric aliases the module
    (ref: python/mxnet/metric.py:np, python/mxnet/gluon/metric.py)."""
    import numpy as np

    from mxnet_tpu import gluon, metric, nd

    m = metric.np(lambda label, pred:
                  float((label == pred.argmax(-1)).mean()), name="acc2")
    m.update(nd.array(np.array([0, 1], np.float32)),
             nd.array(np.array([[0.9, 0.1], [0.2, 0.8]], np.float32)))
    assert m.get() == ("acc2", 1.0)
    assert gluon.metric.Accuracy is metric.Accuracy


def test_sym_random_namespace():
    """mx.sym.random builders (ref: python/mxnet/symbol/random.py)."""
    import numpy as np

    u = mx.sym.random.uniform(low=1.0, high=2.0, shape=(3, 3))
    out = u.eval()[0].asnumpy()
    assert out.shape == (3, 3) and (out >= 1).all() and (out < 2).all()
    m = mx.sym.random.multinomial(
        sym.var("x", shape=(2, 2)), shape=5)
    res = m.eval(x=mx.nd.array(np.array([[0.9, 0.1], [0.1, 0.9]],
                                        np.float32)))[0]
    assert res.shape == (2, 5)

    import mxnet_tpu.sym.random as symrand
    assert symrand is mx.sym.random


def test_test_utils_symbolic_checks():
    """check_symbolic_forward/backward + assert_exception (ref:
    python/mxnet/test_utils.py)."""
    import numpy as np

    from mxnet_tpu import test_utils

    a = sym.var("a", shape=(2, 2))
    b = sym.var("b", shape=(2, 2))
    y = a * b + a
    av = np.random.RandomState(0).randn(2, 2).astype(np.float32)
    bv = np.random.RandomState(1).randn(2, 2).astype(np.float32)
    test_utils.check_symbolic_forward(y, [av, bv], [av * bv + av])
    og = np.ones((2, 2), np.float32)
    test_utils.check_symbolic_backward(y, [av, bv], [og],
                                       {"a": bv + 1, "b": av})
    test_utils.assert_exception(lambda: 1 / 0, ZeroDivisionError)
    with pytest.raises(AssertionError):
        test_utils.assert_exception(lambda: None, ValueError)


def test_profiler_memory_summary():
    from mxnet_tpu import profiler

    s = profiler.device_memory_summary()
    assert isinstance(s, dict)  # CPU backends may report nothing
    out = profiler.dump_memory()
    assert isinstance(out, dict)


def test_sym_auto_param_variables():
    """Unfilled required tensor inputs become auto-named variables (ref:
    python/mxnet/symbol/register.py): fc1_weight/fc1_bias appear in
    list_arguments and infer_shape sizes them."""
    import mxnet_tpu as mx
    d = mx.sym.var("data")
    s = mx.sym.FullyConnected(d, num_hidden=8, name="fc1")
    names = [getattr(a, "name", a) for a in s.list_arguments()]
    assert names == ["data", "fc1_weight", "fc1_bias"]
    args, outs, _ = s.infer_shape(data=(4, 6))
    assert args == [(4, 6), (8, 6), (8,)] and outs == [(4, 8)]
    # no_bias drops the bias var (upstream behavior)
    s2 = mx.sym.FullyConnected(d, num_hidden=8, no_bias=True, name="fcn")
    assert [getattr(a, "name", a) for a in s2.list_arguments()] \
        == ["data", "fcn_weight"]
    # Convolution too
    s3 = mx.sym.Convolution(d, kernel=(3, 3), num_filter=4, name="conv0")
    assert [getattr(a, "name", a) for a in s3.list_arguments()] \
        == ["data", "conv0_weight", "conv0_bias"]
    # explicit weight symbol wins; bias is STILL auto-created (upstream)
    w = mx.sym.var("myw")
    s4 = mx.sym.FullyConnected(d, weight=w, num_hidden=8, name="fcw")
    assert [getattr(a, "name", a) for a in s4.list_arguments()] \
        == ["data", "myw", "fcw_bias"]
    # explicit bias fills ITS slot; weight is auto-created, not displaced
    b = mx.sym.var("myb")
    s5 = mx.sym.FullyConnected(d, bias=b, num_hidden=8, name="fcb")
    argss, _, _ = s5.infer_shape(data=(4, 6))
    names5 = [getattr(a, "name", a) for a in s5.list_arguments()]
    assert names5 == ["data", "fcb_weight", "myb"]
    assert argss[names5.index("myb")] == (8,)  # bias-shaped, not weight
    # keyword-only data also triggers auto-creation
    s6 = mx.sym.FullyConnected(x=d, num_hidden=8, name="fck")
    assert [getattr(a, "name", a) for a in s6.list_arguments()] \
        == ["data", "fck_weight", "fck_bias"]


def test_modifier_cell_base():
    from mxnet_tpu import gluon
    assert issubclass(gluon.rnn.ResidualCell, gluon.rnn.ModifierCell)
    assert issubclass(gluon.rnn.ZoneoutCell, gluon.rnn.ModifierCell)
    base = gluon.rnn.LSTMCell(4, input_size=4)
    wrapped = gluon.rnn.ResidualCell(base)
    assert wrapped.state_info(2) == base.state_info(2)
    assert [s.shape for s in wrapped.begin_state(2)] \
        == [s.shape for s in base.begin_state(2)]


def test_sym_batchnorm_composes_single_output():
    """Upstream BatchNorm is NumVisibleOutputs=1: sym.BatchNorm(x) must feed
    the next op directly (ref: src/operator/nn/batch_norm.cc); the batch
    mean/var outputs stay hidden. Auto-created gamma/beta/moving vars."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import nd

    x = mx.sym.var("data")
    net = mx.sym.Activation(mx.sym.BatchNorm(x, name="bn0"),
                            act_type="relu")
    names = [getattr(a, "name", a) for a in net.list_arguments()]
    assert names[0] == "data" and any("bn0" in n for n in names[1:])
    args, outs, _ = net.infer_shape(data=(2, 3, 4, 4))
    assert outs == [(2, 3, 4, 4)]
    # eval end-to-end through an executor
    ex = net.simple_bind(grad_req="null", data=(2, 3, 4, 4))
    out = ex.forward(is_train=False,
                     data=nd.array(np.random.default_rng(0)
                                   .normal(size=(2, 3, 4, 4))
                                   .astype(np.float32)))
    assert out[0].shape == (2, 3, 4, 4)


def test_registry_machinery():
    """mx.registry register/alias/create incl. the JSON config form
    (ref: python/mxnet/registry.py)."""
    import pytest

    import mxnet_tpu as mx

    class Base:
        def __init__(self, x=1):
            self.x = x

    register = mx.registry.get_register_func(Base, "thing")
    alias = mx.registry.get_alias_func(Base, "thing")
    create = mx.registry.get_create_func(Base, "thing")

    @alias("alpha", "first")
    class A(Base):
        pass

    class B(Base):
        pass
    register(B)

    assert isinstance(create("A"), A)          # class name
    assert isinstance(create("alpha"), A)      # alias, case-insensitive
    assert isinstance(create("FIRST"), A)
    assert isinstance(create("b"), B)
    inst = create('{"type": "b", "x": 7}')     # JSON config form
    assert isinstance(inst, B) and inst.x == 7
    got = create(inst)                         # instance pass-through
    assert got is inst
    with pytest.raises(ValueError):
        create("nope")
    with pytest.raises(AssertionError):
        register(dict)  # not a subclass


def test_executor_namespace_and_parity_members():
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd

    assert mx.executor.Executor is mx.symbol.Executor
    x = mx.sym.var("data")
    w = mx.sym.var("w")
    out = mx.sym.FullyConnected(x, w, mx.sym.var("b"), num_hidden=3)
    ex = out.bind(args={"data": nd.array(np.ones((2, 4), np.float32)),
                        "w": nd.array(np.zeros((3, 4), np.float32)),
                        "b": nd.array(np.zeros(3, np.float32))})
    assert ex.aux_dict == {}
    ex.copy_params_from({"w": nd.array(np.ones((3, 4), np.float32))},
                        allow_extra_params=False)
    o = ex.forward()[0]
    np.testing.assert_allclose(o.asnumpy(), np.full((2, 3), 4.0), rtol=1e-6)
    # reshape returns a rebindable executor at the new shape
    ex2 = ex.reshape(data=(5, 4))
    assert ex2.arg_dict["data"].shape == (5, 4)
    assert ex2.forward()[0].shape == (5, 3)


def test_libinfo_and_kvstore_server():
    import pytest

    import mxnet_tpu as mx

    assert mx.libinfo.__version__.startswith("1.9")
    paths = mx.libinfo.find_lib_path()
    # the repo builds its native helpers — discovery must actually find them
    assert paths and all(p.endswith(".so") for p in paths)
    with pytest.raises(RuntimeError, match="collectives"):
        mx.kvstore_server.KVStoreServer()


def test_metric_nll_and_check_label_shapes():
    import pytest

    import mxnet_tpu as mx
    from mxnet_tpu import nd

    m = mx.metric.NegativeLogLikelihood()
    probs = np.array([[0.2, 0.8], [0.9, 0.1]], np.float32)
    m.update(nd.array(np.array([1, 0])), nd.array(probs))
    want = -(np.log(0.8) + np.log(0.9)) / 2
    assert abs(m.get()[1] - want) < 1e-6
    assert mx.metric.create("negativeloglikelihood") is not None

    ls, ps = mx.metric.check_label_shapes(nd.zeros((2,)), nd.zeros((2, 3)),
                                          wrap=True)
    assert isinstance(ls, list) and isinstance(ps, list)
    with pytest.raises(ValueError, match="does not match"):
        mx.metric.check_label_shapes([nd.zeros((2,))], [])
    with pytest.raises(ValueError, match="does not match"):
        mx.metric.check_label_shapes(nd.zeros((2,)), nd.zeros((3,)),
                                     shape=True)
    # upstream semantics: bare-array batch mismatch raises via
    # len() even without shape=True, and the pair is ALWAYS returned —
    # unwrapped when wrap=False
    with pytest.raises(ValueError, match="does not match"):
        mx.metric.check_label_shapes(nd.zeros((2,)), nd.zeros((3, 4)))
    l0, p0 = nd.zeros((2,)), nd.zeros((2, 3))
    ls, ps = mx.metric.check_label_shapes(l0, p0)
    assert ls is l0 and ps is p0


def test_initializer_load():
    import pytest

    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon import nn

    net = nn.Dense(3, in_units=4)
    w = np.arange(12, dtype=np.float32).reshape(3, 4)
    b = np.ones(3, np.float32)
    net.initialize()
    names = list(net.collect_params().keys())
    wname = [n for n in names if n.endswith("weight")][0]
    bname = [n for n in names if n.endswith("bias")][0]

    net.initialize(mx.initializer.Load({wname: nd.array(w),
                                        bname: nd.array(b)}),
                   force_reinit=True)
    np.testing.assert_allclose(net.weight.data().asnumpy(), w)
    # gluon semantics: the bias keeps its param-level zero init under
    # a global initializer; direct invocation loads it
    mx.initializer.Load({bname: nd.array(b)})(bname, net.bias.data())
    np.testing.assert_allclose(net.bias.data().asnumpy(), b)

    with pytest.raises(ValueError, match="not found"):
        nn.Dense(2, in_units=2).initialize(
            mx.initializer.Load({}), force_reinit=True)


def test_r5_module_level_api_grab_bag():
    """Upstream module-level conveniences: mx.random samplers (delegating
    to nd.random), in-place mx.random.shuffle, engine.bulk scope,
    test_utils.list_gpus/set_default_context, context.gpu_memory_info."""
    import pytest

    import mxnet_tpu as mx
    from mxnet_tpu import nd

    mx.random.seed(1)
    u = mx.random.uniform(0, 1, shape=(200,)).asnumpy()
    assert (u >= 0).all() and (u < 1).all()
    assert mx.random.randn(2, 3).shape == (2, 3)
    a = nd.array(np.arange(8, dtype=np.float32))
    before = a.asnumpy().copy()
    assert mx.random.shuffle(a) is None  # upstream shuffles IN PLACE
    assert sorted(a.asnumpy().tolist()) == before.tolist()

    with mx.engine.bulk(8):
        nd.ones((2,))
    assert mx.test_utils.list_gpus() == []

    from mxnet_tpu import context as ctx_mod
    saved = ctx_mod._default
    try:
        mx.test_utils.set_default_context(mx.cpu())
        assert mx.context.current_context().device_type == "cpu"
    finally:
        ctx_mod._default = saved

    # cpu-only host: no accelerator HBM stats — raises like upstream
    with pytest.raises(RuntimeError):
        mx.context.gpu_memory_info(0)
