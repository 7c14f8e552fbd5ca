"""Forward-shape coverage for every vision zoo family (SURVEY §2 #24)."""
import numpy as np
import pytest

from mxnet_tpu import nd
from mxnet_tpu.gluon.model_zoo.vision import get_model


def _x(n=1, c=3, s=224):
    return nd.array(np.random.randn(n, c, s, s).astype(np.float32))


@pytest.mark.parametrize("name,size", [
    ("vgg11", 64),
    ("alexnet", 224),
    ("mobilenet0.25", 64),
    ("mobilenetv2_1.0", 64),
    ("squeezenet1.1", 96),
    ("densenet121", 64),
])
def test_zoo_forward(name, size):
    net = get_model(name, classes=10)
    net.initialize()
    out = net(_x(1, 3, size))
    assert out.shape == (1, 10)


def test_inception_v3():
    net = get_model("inceptionv3", classes=7)
    net.initialize()
    out = net(_x(1, 3, 299))
    assert out.shape == (1, 7)


def test_resnet_thumbnail():
    from mxnet_tpu.gluon.model_zoo.vision import get_resnet

    net = get_resnet(1, 18, classes=10, thumbnail=True)
    net.initialize()
    out = net(_x(2, 3, 32))
    assert out.shape == (2, 10)


def test_npx_namespace():
    import mxnet_tpu as mx

    x = nd.array(np.random.randn(2, 5).astype(np.float32))
    s = mx.npx.softmax(x, axis=-1)
    np.testing.assert_allclose(s.asnumpy().sum(-1), 1.0, rtol=1e-5)
    assert mx.npx.relu(x).shape == (2, 5)


@pytest.mark.parametrize("name,size,lr,strict", [
    ("resnet18_v1", 32, 0.05, True),
    # vgg's stock init yields huge, init-dependent logits at 32px — one-step
    # loss decrease is not a stable property; assert movement only
    ("vgg11", 32, 1e-5, False),
    ("mobilenetv2_1.0", 32, 0.01, True),
    ("squeezenet1.1", 96, 0.01, True),
])
def test_zoo_one_train_step(name, size, lr, strict):
    """One full train step per zoo family: loss decreases-or-moves and every
    param gets a finite gradient."""
    from mxnet_tpu import autograd, gluon

    net = get_model(name, classes=4)
    net.initialize()
    net.hybridize()   # one XLA program per fwd/bwd — the real training path
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": lr})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    x = _x(2, 3, size)
    y = nd.array(np.array([0, 3], np.float32))

    with autograd.record():
        loss = loss_fn(net(x), y)
    loss.backward()
    l0 = float(loss.asnumpy().mean())
    grads = [p.grad() for p in net.collect_params().values()
             if p.grad_req != "null"]
    assert grads, "no grads collected"
    for g in grads:
        assert np.isfinite(g.asnumpy()).all()
    assert any(float(np.abs(g.asnumpy()).sum()) > 0 for g in grads)
    trainer.step(2)

    with autograd.record():
        loss = loss_fn(net(x), y)
    loss.backward()
    trainer.step(2)
    l1 = float(loss.asnumpy().mean())
    assert np.isfinite(l1)
    if strict:
        assert l1 < l0  # same batch twice: one SGD step must reduce the loss
    else:
        assert l1 != l0


def test_densenet_backward_finite():
    """Backward through the deepest zoo family (dense connectivity stresses
    the vjp tape most); gradient finiteness only — a full train step here
    would dominate suite wall-clock."""
    from mxnet_tpu import autograd, gluon

    net = get_model("densenet121", classes=3)
    net.initialize()
    net.hybridize()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    x = _x(1, 3, 32)
    y = nd.array(np.array([1], np.float32))
    with autograd.record():
        loss = loss_fn(net(x), y)
    loss.backward()
    gsum = sum(float(np.abs(p.grad().asnumpy()).sum())
               for p in net.collect_params().values()
               if p.grad_req != "null")
    assert np.isfinite(gsum) and gsum > 0


def test_get_model_registry_breadth():
    """Every upstream get_model name family resolves (width/depth variants
    upstream's model_store lists; ref: model_zoo/vision/__init__.py)."""
    names = ["resnet50_v2", "mobilenet0.75", "mobilenetv2_0.75",
             "mobilenetv2_0.5", "mobilenetv2_0.25", "densenet161",
             "densenet201", "vgg19_bn"]
    for n in names:
        net = get_model(n, classes=5)
        assert net is not None
    with pytest.raises(ValueError):
        get_model("not_a_model")


def test_profiler_counter_marker_domain(tmp_path, monkeypatch):
    """Domain/Counter/Marker parity (ref: python/mxnet/profiler.py)."""
    import json

    from mxnet_tpu import profiler

    monkeypatch.setitem(profiler._config, "filename", str(tmp_path / "p.json"))
    d = profiler.Domain("dom")
    t = d.new_task("t")
    t.start()
    t.stop()
    c = d.new_counter("ctr", 10)
    c.increment(5)
    c.decrement(3)
    c += 1
    m = d.new_marker("mk")
    m.mark("process")
    profiler.dump()
    ev = json.load(open(profiler._config["filename"]))["traceEvents"]
    counts = [e for e in ev if e["ph"] == "C" and e["name"] == "ctr"]
    assert counts and counts[-1]["args"]["ctr"] == 13
    assert any(e["ph"] == "i" and e["name"] == "mk" for e in ev)
    assert any(e["ph"] == "X" and e["name"] == "t" and e["cat"] == "dom"
               for e in ev)
    agg = profiler.aggregate()
    assert "t" in agg and "ctr" not in agg


def test_pretrained_raises_clearly():
    """pretrained=True must fail loudly — silently returning random weights
    would masquerade as ImageNet initialization."""
    with pytest.raises(ValueError):
        get_model("resnet18_v1", pretrained=True)
    net = get_model("resnet18_v1", pretrained=False, classes=4)
    assert net is not None


def test_resnet_s2d_stem_matches_plain(tmp_path):
    """stem_s2d=True computes the IDENTICAL conv0 (space-to-depth
    reparametrization, ops/spatial.py:space_to_depth_stem_conv) and loads a
    plain checkpoint unchanged: same structural keys, same weight shape."""
    from mxnet_tpu.gluon.model_zoo.vision import get_resnet

    plain = get_resnet(1, 18, classes=7)
    plain.initialize()
    x = nd.array(np.random.default_rng(0).normal(
        size=(2, 3, 64, 64)).astype(np.float32))
    y_plain = plain(x)

    path = str(tmp_path / "p.params")
    plain.save_parameters(path)

    s2d = get_resnet(1, 18, classes=7, stem_s2d=True)
    s2d.load_parameters(path)
    np.testing.assert_allclose(s2d(x).asnumpy(), y_plain.asnumpy(),
                               rtol=1e-4, atol=1e-5)
    s2d.hybridize()
    np.testing.assert_allclose(s2d(x).asnumpy(), y_plain.asnumpy(),
                               rtol=1e-4, atol=1e-5)


def test_s2d_stem_op_grad_parity():
    """Functional parity incl. both grads vs the plain stride-2 conv."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.spatial import space_to_depth_stem_conv

    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(2, 3, 32, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(8, 3, 7, 7)), jnp.float32)
    dn = ("NCHW", "OIHW", "NCHW")

    def plain(x, w):
        return jax.lax.conv_general_dilated(
            x, w, (2, 2), ((3, 3), (3, 3)), dimension_numbers=dn)

    ct = jnp.arange(16.0)[None, None, :, None]
    np.testing.assert_allclose(np.asarray(space_to_depth_stem_conv(x, w)),
                               np.asarray(plain(x, w)), rtol=1e-4, atol=1e-4)
    for arg in (0, 1):
        g1 = jax.grad(lambda *a: (space_to_depth_stem_conv(*a) * ct).sum(),
                      argnums=arg)(x, w)
        g2 = jax.grad(lambda *a: (plain(*a) * ct).sum(), argnums=arg)(x, w)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=1e-4, atol=1e-3)


def test_s2d_stem_odd_size_falls_back_to_plain_conv():
    """Odd H/W can't 2x2-space-to-depth; the op must fall back to the plain
    stride-2 conv so get_resnet(stem_s2d=True) accepts every input size the
    plain stem does (e.g. 225x225)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.gluon.model_zoo.vision import get_resnet
    from mxnet_tpu.ops.spatial import space_to_depth_stem_conv

    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(1, 3, 33, 33)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(8, 3, 7, 7)), jnp.float32)
    plain = jax.lax.conv_general_dilated(
        x, w, (2, 2), ((3, 3), (3, 3)),
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    np.testing.assert_allclose(np.asarray(space_to_depth_stem_conv(x, w)),
                               np.asarray(plain), rtol=1e-4, atol=1e-4)

    net = get_resnet(1, 18, classes=4, stem_s2d=True)
    net.initialize()
    out = net(nd.array(np.random.default_rng(3).normal(
        size=(1, 3, 65, 65)).astype(np.float32)))
    assert out.shape == (1, 4)
