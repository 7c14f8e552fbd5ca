"""Fused multi-tensor optimizer step (Optimizer.fused_update).

Covers the PR-1 perf tentpole: Trainer.step must issue exactly ONE jitted
update dispatch for an all-dense model (vs one per parameter), match the
per-param path numerically to <=1e-6 (f32), keep save/load state layout
compatible across both paths, leave the row-sparse fallback on the
per-param path, and support opt-in ZeRO-1-style weight-update sharding
(Xu et al., arXiv 2004.13336).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd, parallel
from mxnet_tpu import optimizer as opt_mod
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.parameter import Parameter
from jax.sharding import PartitionSpec as P


def _dense_net(seed=0):
    np.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.BatchNorm(), nn.Dense(4))
    net.initialize()
    return net


def _backward(net, seed=1):
    rng = np.random.default_rng(seed)
    x = nd.array(rng.normal(size=(2, 8)).astype(np.float32))
    y = nd.array(rng.integers(0, 4, (2,)).astype(np.float32))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    with autograd.record():
        loss = loss_fn(net(x), y)
    loss.backward()


def _snapshot(net):
    ps = [p for p in net.collect_params().values() if p.grad_req != "null"]
    return {p.name: (np.asarray(p.data()._data, np.float32),
                     np.asarray(p.grad()._data, np.float32)) for p in ps}


def _restore(net, snap):
    for p in net.collect_params().values():
        if p.name in snap:
            w, g = snap[p.name]
            p.set_data(nd.array(w))
            p.grad()._data = jnp.asarray(g).astype(p.dtype)


def test_exactly_one_dispatch_per_step_all_dense():
    """The acceptance assertion: an all-dense model costs exactly 1 jitted
    update dispatch per Trainer.step (counted by the dispatch-counter
    hook), down from one per parameter."""
    net = _dense_net()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})
    n_dense = len(trainer._params)
    assert n_dense > 1
    for step in range(3):
        _backward(net)
        opt_mod.dispatch_counter.reset()
        trainer.step(2)
        assert opt_mod.dispatch_counter.count == 1, \
            "step %d: %d dispatches" % (step, opt_mod.dispatch_counter.count)


def test_per_param_escape_hatch_dispatches_n():
    net = _dense_net()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    trainer._fused_opt = False
    _backward(net)
    opt_mod.dispatch_counter.reset()
    trainer.step(2)
    assert opt_mod.dispatch_counter.count == len(trainer._params)


@pytest.mark.parametrize("name,kw", [
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-4}),
    ("adam", {"learning_rate": 0.01, "wd": 1e-4}),
])
def test_fused_matches_per_param_fast(name, kw):
    """Tier-1 parity: fused vs per-param to <=1e-6 over two steps on a
    small dense net (the zoo-net variant below is slow-marked)."""
    def run(fused):
        np.random.seed(42)
        mx.random.seed(42)
        net = _dense_net(seed=42)
        tr = gluon.Trainer(net.collect_params(), name, dict(kw))
        tr._fused_opt = fused
        for _ in range(2):
            _backward(net)
            tr.step(2)
        return [np.asarray(p.data()._data, np.float32) for p in tr._params]

    for a, b in zip(run(True), run(False)):
        np.testing.assert_allclose(a, b, atol=1e-6)


@pytest.mark.slow
@pytest.mark.parametrize("name,kw", [
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-4}),
    ("adam", {"learning_rate": 0.01, "wd": 1e-4}),
])
def test_fused_matches_per_param_on_zoo_net(name, kw):
    """Fused and per-param paths agree to <=1e-6 (f32) on a model_zoo net
    over two steps (stateful: momentum/moments must match too)."""
    from mxnet_tpu.gluon.model_zoo.vision import get_resnet

    def run(fused):
        net = get_resnet(1, 18, classes=4, thumbnail=True)
        net.initialize()
        tr = gluon.Trainer(net.collect_params(), name, dict(kw))
        tr._fused_opt = fused
        rng = np.random.default_rng(0)
        x = nd.array(rng.normal(size=(2, 3, 32, 32)).astype(np.float32))
        y = nd.array(np.array([0, 3], np.float32))
        loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
        for _ in range(2):
            with autograd.record():
                loss = loss_fn(net(x), y)
            loss.backward()
            tr.step(2)
        return {p.name: np.asarray(p.data()._data, np.float32)
                for p in tr._params}

    wf = run(True)
    np.random.seed(0)  # same auto-naming / init stream for the second net
    mx.random.seed(0)
    wp = run(False)
    assert wf.keys() != set()
    for (nf, a), (np_, b) in zip(sorted(wf.items()), sorted(wp.items())):
        np.testing.assert_allclose(a, b, atol=1e-6, err_msg=nf)


def test_fused_matches_per_param_multi_precision():
    """bf16 weights + fp32 masters: fused and per-param masters agree to
    <=1e-6 (f32)."""
    def mk(seed=3):
        rng = np.random.default_rng(seed)
        ps = []
        for i in range(4):
            p = Parameter("mp%d" % i, shape=(6, 3) if i % 2 else (8,))
            p.initialize()
            p.set_data(nd.array(rng.normal(size=p.shape).astype(np.float32)))
            p.cast("bfloat16")
            p.grad()._data = jnp.asarray(
                rng.normal(size=p.shape).astype(np.float32)).astype(
                jnp.bfloat16)
            ps.append(p)
        return ps

    kw = {"learning_rate": 0.05, "momentum": 0.9, "multi_precision": True}
    pf, pp = mk(), mk()
    tf = gluon.Trainer(pf, "sgd", dict(kw))
    tp = gluon.Trainer(pp, "sgd", dict(kw))
    tp._fused_opt = False
    tf.step(1)
    tp.step(1)
    for i in sorted(tf._states):
        assert "master" in tf._states[i] and "master" in tp._states[i]
        np.testing.assert_allclose(np.asarray(tf._states[i]["master"]),
                                   np.asarray(tp._states[i]["master"]),
                                   atol=1e-6)
    for a, b in zip(pf, pp):
        np.testing.assert_allclose(
            np.asarray(a.data()._data, np.float32),
            np.asarray(b.data()._data, np.float32), atol=1e-6)


@pytest.mark.parametrize("save_fused,load_fused", [(True, False),
                                                   (False, True)])
def test_save_load_states_across_layouts(tmp_path, save_fused, load_fused):
    """save_states under one update path, load_states under the other:
    the index-keyed state layout is identical, and training continues
    identically after the reload."""
    def mk_trainer(fused, seed=5):
        rng = np.random.default_rng(seed)
        ps = []
        for i in range(5):
            p = Parameter("s%d" % i, shape=(4, 3) if i % 2 else (6,))
            p.initialize()
            p.set_data(nd.array(rng.normal(size=p.shape).astype(np.float32)))
            p.grad()._data = jnp.asarray(
                rng.normal(size=p.shape).astype(np.float32))
            ps.append(p)
        tr = gluon.Trainer(ps, "adam", {"learning_rate": 0.01})
        tr._fused_opt = fused
        return tr, ps

    fname = str(tmp_path / "opt.states")
    tr_a, ps_a = mk_trainer(save_fused)
    tr_a.step(1)
    tr_a.step(1)
    tr_a.save_states(fname)

    tr_b, ps_b = mk_trainer(load_fused)
    tr_b.load_states(fname)
    assert tr_b._optimizer.num_update == tr_a._optimizer.num_update
    for i in sorted(tr_a._states):
        for a, b in zip(jax.tree_util.tree_leaves(tr_a._states[i]),
                        jax.tree_util.tree_leaves(tr_b._states[i])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-7)
    # continuing from the loaded state matches continuing in-place
    # (weights differ — only states/counts travel — so align them first)
    for a, b in zip(ps_a, ps_b):
        b.set_data(nd.array(np.asarray(a.data()._data)))
    tr_a.step(1)
    tr_b.step(1)
    for a, b in zip(ps_a, ps_b):
        np.testing.assert_allclose(np.asarray(a.data()._data),
                                   np.asarray(b.data()._data), atol=1e-6)


def test_row_sparse_leaf_keeps_per_param_path():
    """A lazy row-sparse grad leaf falls back per-param (1 rsp dispatch)
    while the dense rest still fuses into one dispatch."""
    rng = np.random.default_rng(7)
    emb = Parameter("emb", shape=(10, 4), grad_stype="row_sparse")
    emb.initialize()
    emb.set_data(nd.array(rng.normal(size=(10, 4)).astype(np.float32)))
    emb.grad()._data = jnp.asarray(rng.normal(size=(10, 4)).astype(np.float32))
    dense = []
    for i in range(3):
        p = Parameter("d%d" % i, shape=(4, 4))
        p.initialize()
        p.set_data(nd.array(rng.normal(size=(4, 4)).astype(np.float32)))
        p.grad()._data = jnp.asarray(rng.normal(size=(4, 4)).astype(np.float32))
        dense.append(p)
    trainer = gluon.Trainer([emb] + dense, "sgd", {"learning_rate": 0.1})
    opt_mod.dispatch_counter.reset()
    trainer.step(1)
    assert opt_mod.dispatch_counter.count == 2  # 1 rsp + 1 fused


def test_weight_update_sharding_trainer_parity():
    """set_weight_update_sharding(mesh): same numbers as unsharded, and the
    optimizer state genuinely ends up sharded across replicas (ZeRO-1)."""
    mesh = parallel.make_mesh({"dp": 8})

    def mk(seed=9):
        rng = np.random.default_rng(seed)
        ps = []
        for i in range(3):
            p = Parameter("w%d" % i, shape=(16, 4) if i % 2 == 0 else (5,))
            p.initialize()
            p.set_data(nd.array(rng.normal(size=p.shape).astype(np.float32)))
            p.grad()._data = jnp.asarray(
                rng.normal(size=p.shape).astype(np.float32))
            ps.append(p)
        return ps

    pa, pb = mk(), mk()
    ta = gluon.Trainer(pa, "adam", {"learning_rate": 0.01})
    tb = gluon.Trainer(pb, "adam", {"learning_rate": 0.01})
    tb.set_weight_update_sharding(mesh)
    for _ in range(2):
        ta.step(1)
        tb.step(1)
    for a, b in zip(pa, pb):
        np.testing.assert_allclose(np.asarray(a.data()._data),
                                   np.asarray(b.data()._data), atol=1e-6)
    moment = jax.tree_util.tree_leaves(tb._states[0])[0]  # (16, 4) leaf
    assert moment.sharding.spec == P("dp")


def test_weight_update_sharding_compiled_step_parity():
    """build_train_step(shard_weight_update=True) == single-device step."""
    opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9)

    def loss_fn(params, batch, key):
        x, y = batch
        pred = jnp.tanh(x @ params["w"]) @ params["w2"] + params["b"]
        return jnp.mean((pred - y) ** 2)

    params = {"w": jax.random.normal(jax.random.PRNGKey(3), (16, 8)) * 0.3,
              "w2": jax.random.normal(jax.random.PRNGKey(4), (8, 1)) * 0.3,
              "b": jnp.zeros((1,))}
    init_states, _ = parallel.tree_optimizer_step(opt)
    states = init_states(params)
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 16))
    y = jax.random.normal(jax.random.PRNGKey(1), (16, 1))
    key = jax.random.PRNGKey(2)

    step1 = parallel.build_train_step(loss_fn, opt, donate=False)
    p1, s1, l1 = step1(dict(params), dict(states), jnp.int32(1), key, (x, y))

    mesh = parallel.make_mesh({"dp": 8})
    stepz = parallel.build_train_step(loss_fn, opt, mesh=mesh, donate=False,
                                      batch_spec=(P("dp"), P("dp")),
                                      shard_weight_update=True)
    batch = (parallel.shard_array(x, mesh, "dp"),
             parallel.shard_array(y, mesh, "dp"))
    pz, sz, lz = stepz(dict(params), dict(states), jnp.int32(1), key, batch)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(lz), rtol=1e-5)
    for k in p1:
        np.testing.assert_allclose(np.asarray(p1[k]), np.asarray(pz[k]),
                                   atol=1e-6, err_msg=k)
    # steady state: sharded states feed back in
    pz2, sz2, _ = stepz(pz, sz, jnp.int32(2), key, batch)
    p12, _, _ = step1(p1, s1, jnp.int32(2), key, (x, y))
    for k in p1:
        np.testing.assert_allclose(np.asarray(p12[k]), np.asarray(pz2[k]),
                                   atol=1e-6, err_msg=k)
    # the (16, 8) momentum is genuinely sharded over dp between steps
    assert sz["w"].sharding.spec == P("dp")


def test_kvstore_batched_push_fuses_and_matches():
    """A pushed key batch with a store-side optimizer updates in one fused
    dispatch and matches per-key pushes."""
    rng = np.random.default_rng(0)
    ws = [nd.array(rng.normal(size=(6, 4)).astype(np.float32))
          for _ in range(4)]
    gs = [nd.array(rng.normal(size=(6, 4)).astype(np.float32))
          for _ in range(4)]

    kv = mx.kvstore.create("device")
    kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.1, momentum=0.9))
    kv.init(list(range(4)), [w.copy() for w in ws])
    opt_mod.dispatch_counter.reset()
    kv.push(list(range(4)), gs)
    assert opt_mod.dispatch_counter.count == 1

    kv2 = mx.kvstore.create("device")
    kv2.set_optimizer(mx.optimizer.SGD(learning_rate=0.1, momentum=0.9))
    kv2.init(list(range(4)), [w.copy() for w in ws])
    for i in range(4):
        kv2.push(i, gs[i])
    for i in range(4):
        np.testing.assert_allclose(kv.pull(i).asnumpy(),
                                   kv2.pull(i).asnumpy(), atol=1e-6)


def test_lr_schedule_and_batch_size_do_not_retrace():
    """Changing lr / Trainer.step(batch_size) between steps must not grow
    the fused jit cache (lr/wd/rescale enter traced)."""
    rng = np.random.default_rng(0)
    ps = []
    for i in range(3):
        p = Parameter("r%d" % i, shape=(4, 4))
        p.initialize()
        p.grad()._data = jnp.asarray(rng.normal(size=(4, 4)).astype(np.float32))
        ps.append(p)
    trainer = gluon.Trainer(ps, "sgd", {"learning_rate": 0.1})
    trainer.step(2)
    f = trainer._optimizer._jit_fused[(None, True, False)]
    sizes = f._cache_size()
    trainer.set_learning_rate(0.01)
    trainer.step(4)  # different lr AND different batch_size rescale
    assert f._cache_size() == sizes


@pytest.mark.slow
def test_opt_step_bench_quick_speedup():
    """tools/opt_step_bench.py --quick: >=5x host step-loop reduction for
    the 160-tensor ResNet-50-sized case on CPU (acceptance criterion)."""
    import json
    import subprocess
    import sys
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "opt_step_bench.py"),
         "--quick", "--iters", "10"],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    rows = [json.loads(l) for l in out.stdout.splitlines()
            if l.startswith("{")]
    by_case = {r["case"]: r for r in rows}
    r50 = by_case["resnet50_sized"]
    assert r50["tensors"] == 160
    assert r50["fused_dispatches_per_step"] == 1.0
    assert r50["per_param_dispatches_per_step"] == 160.0
    assert r50["host_loop_speedup"] >= 5.0, r50
