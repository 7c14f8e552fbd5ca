"""Distributed: mesh, dp/fsdp train step, tp sharding, ring attention,
pipeline, kvstore (on the virtual 8-device CPU mesh)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, parallel
from mxnet_tpu.parallel import P


def test_mesh_creation():
    mesh = parallel.make_mesh({"dp": 2, "tp": 4})
    assert mesh.shape == {"dp": 2, "tp": 4}
    mesh2 = parallel.make_mesh({"dp": -1, "tp": 2})
    assert mesh2.shape["dp"] == 4


def test_ring_attention_matches_full():
    mesh = parallel.make_mesh({"sp": 8})
    B, H, T, D = 2, 2, 64, 8
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (jax.random.normal(kk, (B, H, T, D)) for kk in ks)
    ref = parallel.full_attention(q, k, v, causal=True)
    sh = lambda x: parallel.shard_array(x, mesh, None, None, "sp", None)
    out = parallel.ring_attention(sh(q), sh(k), sh(v), mesh, causal=True)
    assert float(jnp.abs(out - ref).max()) < 1e-4


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_gradients_match_full(causal):
    """sp-sharded BACKWARD parity: grads of ring attention w.r.t. q/k/v match
    dense attention (long-context training path)."""
    mesh = parallel.make_mesh({"sp": 8})
    B, H, T, D = 2, 2, 64, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q, k, v = (jax.random.normal(kk, (B, H, T, D)) for kk in ks[:3])
    ct = jax.random.normal(ks[3], (B, H, T, D))  # random cotangent

    def loss_ref(q, k, v):
        return jnp.sum(parallel.full_attention(q, k, v, causal=causal) * ct)

    def loss_ring(q, k, v):
        return jnp.sum(parallel.ring_attention(q, k, v, mesh, causal=causal) * ct)

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    sh = lambda x: parallel.shard_array(x, mesh, None, None, "sp", None)
    gs = jax.grad(loss_ring, argnums=(0, 1, 2))(sh(q), sh(k), sh(v))
    for a, b, name in zip(gr, gs, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=1e-3, err_msg=name)


def test_dp_train_step_matches_single_device():
    """Compiled dp step over 8 devices == single-device step (SURVEY §4)."""
    opt = mx.optimizer.SGD(learning_rate=0.1)

    def loss_fn(params, batch, key):
        x, y = batch
        pred = x @ params["w"] + params["b"]
        return jnp.mean((pred - y) ** 2)

    params = {"w": jnp.ones((4, 1)), "b": jnp.zeros((1,))}
    states = {"w": (), "b": ()}
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 4))
    y = jax.random.normal(jax.random.PRNGKey(1), (16, 1))
    key = jax.random.PRNGKey(2)

    step_single = parallel.build_train_step(loss_fn, opt, donate=False)
    p1, s1, l1 = step_single(params, states, jnp.int32(1), key, (x, y))

    mesh = parallel.make_mesh({"dp": 8})
    step_dp = parallel.build_train_step(loss_fn, opt, mesh=mesh, donate=False,
                                        batch_spec=(P("dp"), P("dp")))
    batch = (parallel.shard_array(x, mesh, "dp"), parallel.shard_array(y, mesh, "dp"))
    p8, s8, l8 = step_dp(dict(params), dict(states), jnp.int32(1), key, batch)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l8), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(p1["w"]), np.asarray(p8["w"]), rtol=1e-5)


def test_fsdp_param_sharding():
    mesh = parallel.make_mesh({"fsdp": 8})
    spec = parallel.tensor_parallel._fsdp_spec((16, 4), mesh)
    assert spec == P("fsdp", None) or spec == P(None, "fsdp")
    a = jnp.ones((16, 4))
    sharded = jax.device_put(a, jax.sharding.NamedSharding(mesh, spec))
    assert len(sharded.sharding.device_set) == 8


def test_tp_rules():
    mesh = parallel.make_mesh({"tp": 8})
    from mxnet_tpu.parallel.tensor_parallel import TRANSFORMER_RULES, spec_for

    assert spec_for("bert_layer0_qkv_weight", (24, 8), TRANSFORMER_RULES, mesh) == P("tp", None)
    assert spec_for("bert_layer0_attn_out_weight", (8, 24), TRANSFORMER_RULES, mesh) == P(None, "tp")
    assert spec_for("bert_ln_gamma", (7,), TRANSFORMER_RULES, mesh) == P()


def test_pipeline_matches_sequential():
    mesh = parallel.make_mesh({"pp": 8})

    def stage_fn(params, x):
        return jnp.tanh(x @ params["w"])

    per_stage = [{"w": jax.random.normal(jax.random.PRNGKey(i), (4, 4)) * 0.4}
                 for i in range(8)]
    stacked = parallel.stack_stage_params(per_stage)
    xs = jax.random.normal(jax.random.PRNGKey(99), (10, 2, 4))
    out = parallel.pipeline_apply(stage_fn, stacked, xs, mesh)
    ref = xs
    for p in per_stage:
        ref = jnp.tanh(ref @ p["w"])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_pipeline_1f1b_matches_single_device_grads():
    """1F1B schedule: loss AND stage-param grads == unpipelined jax.grad."""
    S, M = 4, 7  # n_micro not a multiple of stages, exercises cooldown
    mesh = parallel.make_mesh({"pp": 4}, devices=jax.devices()[:4])

    def stage_fn(params, x):
        return jnp.tanh(x @ params["w"] + params["b"])

    def loss_fn(y, t):
        return jnp.mean((y - t) ** 2)

    per_stage = [{"w": jax.random.normal(jax.random.PRNGKey(i), (4, 4)) * 0.4,
                  "b": jnp.zeros((4,))} for i in range(S)]
    stacked = parallel.stack_stage_params(per_stage)
    xs = jax.random.normal(jax.random.PRNGKey(99), (M, 2, 4))
    tg = jax.random.normal(jax.random.PRNGKey(7), (M, 2, 4))

    loss, grads = parallel.pipeline_train_step_1f1b(
        stage_fn, loss_fn, stacked, xs, tg, mesh)

    def ref_loss(stacked_params):
        def one(x, t):
            y = x
            for i in range(S):
                p = jax.tree_util.tree_map(lambda a: a[i], stacked_params)
                y = stage_fn(p, x=y)
            return loss_fn(y, t)

        return jnp.mean(jax.vmap(one)(xs, tg))

    ref_l, ref_g = jax.value_and_grad(ref_loss)(stacked)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(ref_l), rtol=1e-5)
    for k in ("w", "b"):
        np.testing.assert_allclose(np.asarray(grads[k]), np.asarray(ref_g[k]),
                                   atol=1e-5)


def test_moe_expert_parallel_matches_reference():
    from mxnet_tpu.parallel.expert_parallel import moe_ffn

    mesh = parallel.make_mesh({"ep": 8})
    T, C, H, E = 64, 16, 32, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (T, C))
    rw = jax.random.normal(ks[1], (C, E)) * 0.5
    w1 = jax.random.normal(ks[2], (E, C, H)) * 0.3
    w2 = jax.random.normal(ks[3], (E, H, C)) * 0.3
    xs = parallel.shard_array(x, mesh, "ep")
    y, aux = moe_ffn(xs, rw, w1, w2, mesh, capacity_factor=float(E))
    p = jax.nn.softmax(x @ rw, -1)
    e = jnp.argmax(p, -1)
    g = jnp.max(p, -1)
    ref = jnp.stack([g[t] * (jax.nn.relu(x[t] @ w1[e[t]]) @ w2[e[t]])
                     for t in range(T)])
    assert float(jnp.abs(np.asarray(y) - ref).max()) < 1e-4
    assert float(aux) > 0


def test_moe_expert_parallel_composed_with_dp():
    """ep × dp: tokens sharded over BOTH axes, each dp
    replica routing through its own ep all-to-all against dp-replicated
    experts — must match the unsharded per-token reference exactly (routing
    is per-token, capacity ample)."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from mxnet_tpu.parallel.expert_parallel import moe_ffn

    mesh = parallel.make_mesh({"dp": 2, "ep": 4})
    T, C, H, E = 64, 16, 32, 8
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    x = jax.random.normal(ks[0], (T, C))
    rw = jax.random.normal(ks[1], (C, E)) * 0.5
    w1 = jax.random.normal(ks[2], (E, C, H)) * 0.3
    w2 = jax.random.normal(ks[3], (E, H, C)) * 0.3
    xs = jax.device_put(x, NamedSharding(mesh, P(("dp", "ep"), None)))
    y, aux = moe_ffn(xs, rw, w1, w2, mesh, capacity_factor=float(E),
                     batch_axis="dp")
    p = jax.nn.softmax(x @ rw, -1)
    e = jnp.argmax(p, -1)
    g = jnp.max(p, -1)
    ref = jnp.stack([g[t] * (jax.nn.relu(x[t] @ w1[e[t]]) @ w2[e[t]])
                     for t in range(T)])
    assert float(jnp.abs(np.asarray(y) - ref).max()) < 1e-4
    assert float(aux) > 0


def test_kvstore_local_push_pull():
    kv = mx.kvstore.create("local")
    kv.init(3, nd.ones((2, 2)))
    kv.push(3, [nd.ones((2, 2)), nd.ones((2, 2)) * 2])  # aggregate list
    out = nd.zeros((2, 2))
    kv.pull(3, out=out)
    np.testing.assert_allclose(out.asnumpy(), np.full((2, 2), 4.0))


def test_kvstore_optimizer_update():
    kv = mx.kvstore.create("device")
    kv.init("w", nd.ones((2,)))
    kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.5))
    kv.push("w", nd.ones((2,)))
    out = nd.zeros((2,))
    kv.pull("w", out=out)
    np.testing.assert_allclose(out.asnumpy(), [0.5, 0.5])


def test_block_loss_fn_compiled_dp():
    """End-to-end: gluon BERT-ish block through build_train_step on a dp mesh."""
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8, activation="relu", in_units=4), gluon.nn.Dense(2, in_units=8))
    net.initialize()
    loss_block = gluon.loss.SoftmaxCrossEntropyLoss()
    opt = mx.optimizer.Adam()
    loss_fn, plist = parallel.block_loss_fn(net, loss_block)
    params = [p.data()._data for p in plist]
    _, apply_opt = parallel.tree_optimizer_step(opt)
    init_states, _ = parallel.tree_optimizer_step(opt)
    states = init_states(params)
    mesh = parallel.make_mesh({"dp": 8})
    step = parallel.build_train_step(loss_fn, opt, mesh=mesh,
                                     batch_spec=(P("dp"), P("dp")))
    x = jnp.asarray(np.random.randn(16, 4).astype(np.float32))
    y = jnp.asarray(np.random.randint(0, 2, 16).astype(np.float32))
    losses = []
    t = jnp.int32(1)
    key = jax.random.PRNGKey(0)
    for i in range(5):
        params, states, loss = step(params, states, t + i, key, (x, y))
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_sync_batchnorm_global_stats_under_dp():
    """SyncBatchNorm's claim (contrib/nn.py): under a dp-sharded jit the SPMD
    partitioner computes batch statistics over the FULL global batch. Give
    each of the 8 shards a different distribution and check the normalized
    output matches the global-batch oracle, NOT per-shard normalization."""
    from jax.sharding import NamedSharding
    from mxnet_tpu import _trace
    from mxnet_tpu.gluon.contrib.nn import SyncBatchNorm

    bn = SyncBatchNorm(in_channels=4)
    bn.initialize()
    plist = list(bn.collect_params().values())

    # shard i drawn around mean 2*i: per-shard mean differs wildly from global
    rng = np.random.default_rng(0)
    x = np.concatenate([
        rng.normal(loc=2.0 * i, scale=0.5, size=(2, 4)).astype(np.float32)
        for i in range(8)], axis=0)  # (16, 4)

    def fwd(param_arrays, xb):
        with _trace.trace_scope(jax.random.PRNGKey(0), True) as t:
            t.param_store = {id(p): a for p, a in zip(plist, param_arrays)}
            out = bn._call_traced(xb)
            upd = {i: t.state_updates.get(id(p)) for i, p in enumerate(plist)}
        return out, upd

    mesh = parallel.make_mesh({"dp": 8})
    xs = jax.device_put(jnp.asarray(x), NamedSharding(mesh, P("dp")))
    params = [p.data()._data for p in plist]
    out, upd = jax.jit(fwd, in_shardings=(None, NamedSharding(mesh, P("dp"))),
                       )(params, xs)
    out = np.asarray(out)

    gm = x.mean(axis=0)
    gv = x.var(axis=0)
    want_global = (x - gm) / np.sqrt(gv + 1e-5)
    np.testing.assert_allclose(out, want_global, rtol=2e-3, atol=2e-3)

    # per-shard normalization would differ enormously (shard means span 0..14)
    shard0 = x[:2]
    per_shard = (shard0 - shard0.mean(0)) / np.sqrt(shard0.var(0) + 1e-5)
    assert np.abs(out[:2] - per_shard).max() > 1.0

    # running-mean update reflects the GLOBAL batch mean
    momentum = 0.9
    names = [p.name for p in plist]
    mean_upd = [np.asarray(v) for i, v in sorted(upd.items())
                if v is not None and "running_mean" in names[i]]
    assert mean_upd, "BatchNorm recorded no running_mean update"
    np.testing.assert_allclose(mean_upd[0], (1 - momentum) * gm, rtol=2e-3,
                               atol=2e-3)


def test_ulysses_attention_matches_full():
    """All-to-all (Ulysses) sequence parallelism: forward + grads exactly
    match dense attention under a position-sensitive loss (a permutation of
    sequence positions cannot cancel)."""
    from mxnet_tpu.parallel.ulysses import ulysses_attention
    from mxnet_tpu.parallel import full_attention, make_mesh

    mesh = make_mesh({"sp": 8})
    B, H, T, D = 2, 8, 64, 16
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
               for _ in range(3))
    for causal in (False, True):
        out = ulysses_attention(q, k, v, mesh, causal=causal)
        ref = full_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    w = jnp.asarray(rng.normal(size=(1, H, T, D)), jnp.float32)
    g1 = jax.grad(lambda a, b, c: (ulysses_attention(a, b, c, mesh,
                                                     causal=True) * w).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda a, b, c: (full_attention(a, b, c, causal=True)
                                   * w).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_ulysses_rejects_indivisible_heads():
    from mxnet_tpu.parallel.ulysses import ulysses_attention
    from mxnet_tpu.parallel import make_mesh
    import pytest as _pytest

    mesh = make_mesh({"sp": 8})
    q = jnp.zeros((1, 4, 64, 8), jnp.float32)  # 4 heads < sp=8
    with _pytest.raises(ValueError, match="ring_attention"):
        ulysses_attention(q, q, q, mesh)


def test_kvstore_two_bit_gradient_compression():
    """2-bit compression with error feedback (ref:
    src/kvstore/gradient_compression.cc): pushes are ternarized to
    {-t, 0, +t} and the quantization error accumulates until it crosses
    the threshold."""
    import numpy as np

    from mxnet_tpu import kvstore, nd

    kv = kvstore.create("local")
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    kv.init("w", nd.array(np.zeros(4, np.float32)))

    # 0.7 ≥ t → +0.5 lands; residual keeps 0.2
    kv.push("w", nd.array(np.array([0.7, -0.7, 0.2, 0.0], np.float32)))
    out = kv.pull("w").asnumpy()
    np.testing.assert_allclose(out, [0.5, -0.5, 0.0, 0.0], atol=1e-6)

    # second push of 0.2: residual 0.2 + 0.2 = 0.4 < t → still 0...
    kv.push("w", nd.array(np.array([0.0, 0.0, 0.2, 0.0], np.float32)))
    np.testing.assert_allclose(kv.pull("w").asnumpy(),
                               [0.5, -0.5, 0.0, 0.0], atol=1e-6)
    # ...third push crosses: 0.4 + 0.2 = 0.6 ≥ t → +0.5 lands (error feedback)
    kv.push("w", nd.array(np.array([0.0, 0.0, 0.2, 0.0], np.float32)))
    np.testing.assert_allclose(kv.pull("w").asnumpy(),
                               [0.5, -0.5, 0.5, 0.0], atol=1e-6)

    import pytest
    with pytest.raises(ValueError):
        kv.set_gradient_compression({"type": "1bit"})


def test_trainer_wires_gradient_compression():
    """Trainer(compression_params=...) configures the kvstore's 2-bit
    compressor (ref: gluon/trainer.py)."""
    from mxnet_tpu import gluon, kvstore

    kv = kvstore.create("dist_sync")
    net = gluon.nn.Dense(4, in_units=3)
    net.initialize()
    gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1},
                  kvstore=kv,
                  compression_params={"type": "2bit", "threshold": 0.5})
    assert kv._compression is not None
    assert kv._compression["threshold"] == 0.5


def test_dp_tp_composed_2d_mesh_matches_single_device():
    """COMPOSED parallelism on one 2-D mesh {dp:2, tp:4}: batch sharded over
    dp, transformer-style params column/row sharded over tp — one train step
    must match the unsharded single-device step (dp psum + tp collectives
    both inserted by the partitioner in the SAME program)."""
    from mxnet_tpu.parallel import tensor_parallel as tp

    opt = mx.optimizer.SGD(learning_rate=0.1)
    U, H_, B = 8, 16, 8

    def loss_fn(params, batch, key):
        x, y = batch
        h = jnp.tanh(x @ params["ffn_1_weight"].T + params["ffn_1_bias"])
        out = h @ params["ffn_2_weight"].T
        return jnp.mean((out - y) ** 2)

    rng = np.random.default_rng(0)
    params = {
        "ffn_1_weight": jnp.asarray(rng.normal(size=(H_, U)) * 0.1,
                                    jnp.float32),
        "ffn_1_bias": jnp.zeros((H_,), jnp.float32),
        "ffn_2_weight": jnp.asarray(rng.normal(size=(U, H_)) * 0.1,
                                    jnp.float32),
    }
    states = {k: () for k in params}
    x = jnp.asarray(rng.normal(size=(B, U)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(B, U)), jnp.float32)
    key = jax.random.PRNGKey(0)

    step1 = parallel.build_train_step(loss_fn, opt, donate=False)
    p1, s1, l1 = step1(dict(params), dict(states), jnp.int32(1), key, (x, y))

    mesh = parallel.make_mesh({"dp": 2, "tp": 4})
    specs = {k: tp.spec_for(k, v.shape, tp.TRANSFORMER_RULES, mesh)
             for k, v in params.items()}
    assert specs["ffn_1_weight"] == P("tp", None)   # column parallel
    assert specs["ffn_2_weight"] == P(None, "tp")   # row parallel
    step2 = parallel.build_train_step(loss_fn, opt, mesh=mesh,
                                      param_spec=specs, donate=False,
                                      batch_spec=(P("dp"), P("dp")))
    names = sorted(params)
    placed = tp.shard_params([(k, params[k]) for k in names], mesh)
    sharded = dict(zip(names, placed))
    batch = parallel.shard_batch((x, y), mesh)
    p2, s2, l2 = step2(sharded, dict(states), jnp.int32(1), key, batch)

    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), rtol=1e-5)
    for k in params:
        np.testing.assert_allclose(np.asarray(p1[k]), np.asarray(p2[k]),
                                   rtol=1e-4, atol=1e-6)


def test_pipeline_interleaved_matches_sequential():
    """Interleaved virtual chunks: 16 global stages on 4 devices (v=4,
    Megatron assignment g%S) through the +1 ring — output matches applying
    all 16 stages sequentially, and gradients flow through the schedule."""
    S, v = 4, 4
    G = S * v
    mesh = parallel.make_mesh({"pp": S}, devices=jax.devices()[:S])

    def stage_fn(params, x):
        return jnp.tanh(x @ params["w"] + params["b"])

    per_stage = [{"w": jax.random.normal(jax.random.PRNGKey(i), (4, 4)) * 0.4,
                  "b": jnp.full((4,), 0.01 * i)} for i in range(G)]
    stacked = parallel.interleave_stage_params(per_stage, S)
    xs = jax.random.normal(jax.random.PRNGKey(50), (6, 2, 4))

    out = parallel.pipeline_apply_interleaved(stage_fn, stacked, xs, mesh,
                                              n_virtual=v)
    ref = xs
    for p in per_stage:
        ref = jnp.tanh(ref @ p["w"] + p["b"])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    # gradients through the interleaved schedule == sequential gradients
    def loss_pipe(st):
        y = parallel.pipeline_apply_interleaved(stage_fn, st, xs, mesh,
                                                n_virtual=v)
        return jnp.sum(y ** 2)

    def loss_seq(st):
        # st rows are in interleaved order: row d*v+j = global j*S+d
        y = xs
        for g in range(G):
            d, j = g % S, g // S
            p = jax.tree_util.tree_map(lambda a: a[d * v + j], st)
            y = stage_fn(p, y)
        return jnp.sum(y ** 2)

    g1 = jax.grad(loss_pipe)(stacked)
    g2 = jax.grad(loss_seq)(stacked)
    for k in ("w", "b"):
        np.testing.assert_allclose(np.asarray(g1[k]), np.asarray(g2[k]),
                                   atol=1e-4)


def test_gpt_tensor_parallel_forward_matches_replicated():
    """models/gpt.py's docstring claim: its param names follow
    TRANSFORMER_RULES, so the SAME model tp-shards without edits. Forward
    under a tp=4 mesh (qkv/ffn column+row sharded, vocab-sharded embedding)
    must match the replicated forward."""
    from jax.sharding import NamedSharding

    from mxnet_tpu import _trace
    from mxnet_tpu.models.gpt import gpt_nano
    from mxnet_tpu.parallel import tensor_parallel as tp

    net = gpt_nano()
    net.initialize()
    plist = list(net.collect_params().values())
    toks = jnp.asarray(np.random.RandomState(0).randint(0, 256, (2, 8)),
                       jnp.int32)

    def fwd(param_arrays, t):
        with _trace.trace_scope(jax.random.PRNGKey(0), False) as tc:
            tc.param_store = {id(p): a for p, a in zip(plist, param_arrays)}
            return net._call_traced(t)

    params = [p.data()._data for p in plist]
    ref = jax.jit(fwd)(params, toks)

    mesh = parallel.make_mesh({"tp": 4}, devices=jax.devices()[:4])
    specs = [tp.spec_for(p.name, p.data().shape, tp.TRANSFORMER_RULES, mesh)
             for p in plist]
    # the rules must actually bite: at least qkv + ffn sharded
    assert any(sp == P("tp", None) for sp in specs)
    assert any(sp == P(None, "tp") for sp in specs)
    placed = [jax.device_put(a, NamedSharding(mesh, sp))
              for a, sp in zip(params, specs)]
    with mesh:
        out = jax.jit(fwd, in_shardings=(
            [NamedSharding(mesh, sp) for sp in specs], None))(placed, toks)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_sequence_parallel_scope_gpt_matches_unsharded():
    """parallel.sequence_parallel_scope: the SAME gpt_nano, unmodified,
    runs its causal attention ring-sharded over sp=4 inside the scope —
    forward AND parameter gradients match the unsharded model."""
    from mxnet_tpu import _trace
    from mxnet_tpu.models.gpt import gpt_nano

    net = gpt_nano()
    net.initialize()
    plist = list(net.collect_params().values())
    toks = jnp.asarray(np.random.RandomState(2).randint(0, 256, (2, 8)),
                       jnp.int32)

    def loss(param_arrays, t):
        with _trace.trace_scope(jax.random.PRNGKey(0), False) as tc:
            tc.param_store = {id(p): a for p, a in zip(plist, param_arrays)}
            logits = net._call_traced(t)
        return (logits.astype(jnp.float32) ** 2).mean()

    params = [p.data()._data for p in plist]
    ref_l, ref_g = jax.value_and_grad(loss)(params, toks)

    mesh = parallel.make_mesh({"sp": 4}, devices=jax.devices()[:4])
    with parallel.sequence_parallel_scope(mesh, impl="ring"):
        sp_l, sp_g = jax.value_and_grad(loss)(params, toks)
    np.testing.assert_allclose(float(sp_l), float(ref_l), rtol=1e-5)
    worst = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                      - b.astype(jnp.float32))))
                for a, b in zip(sp_g, ref_g))
    assert worst < 2e-4, worst

    # ulysses impl too (heads=2, sp=2 divides)
    mesh2 = parallel.make_mesh({"sp": 2}, devices=jax.devices()[:2])
    with parallel.sequence_parallel_scope(mesh2, impl="ulysses"):
        u_l, _ = jax.value_and_grad(loss)(params, toks)
    np.testing.assert_allclose(float(u_l), float(ref_l), rtol=1e-5)


def test_dp_tp_pp_composed_3d_mesh_matches_reference():
    """FULL Megatron-style composition on ONE {dp:2, tp:2, pp:2} mesh:
    microbatch rows sharded over dp, stage weights column/row-split over tp
    (stage_fn closes with psum), stages over pp riding the 1F1B ring —
    loss AND stacked grads must match the unsharded single-device oracle."""
    from jax import lax

    S, M, MB, U, H_ = 2, 5, 4, 4, 8  # stages, microbatches, rows, widths
    mesh = parallel.make_mesh({"dp": 2, "tp": 2, "pp": 2})

    from mxnet_tpu.parallel.tensor_parallel import (psum_region_entry,
                                                    psum_region_exit)

    def stage_fn(params, x):
        x = psum_region_entry(x, "tp")  # Megatron `f`: dx sums over tp
        h = jnp.tanh(x @ params["w1"] + params["b1"])  # w1 cols over tp
        y = h @ params["w2"]                           # w2 rows over tp
        # Megatron `g`: psum fwd, identity bwd (raw lax.psum would double
        # the upstream grads under the per-rank redundant loss)
        return psum_region_exit(y, "tp") + params["b2"]

    def stage_fn_ref(params, x):
        h = jnp.tanh(x @ params["w1"] + params["b1"])
        return h @ params["w2"] + params["b2"]

    def loss_fn(y, t):
        return jnp.mean((y - t) ** 2)

    rng = np.random.default_rng(5)
    per_stage = [{
        "w1": jnp.asarray(rng.normal(size=(U, H_)) * 0.4, jnp.float32),
        "b1": jnp.zeros((H_,), jnp.float32),
        "w2": jnp.asarray(rng.normal(size=(H_, U)) * 0.4, jnp.float32),
        "b2": jnp.zeros((U,), jnp.float32),
    } for _ in range(S)]
    stacked = parallel.stack_stage_params(per_stage)
    xs = jnp.asarray(rng.normal(size=(M, MB, U)), jnp.float32)
    tg = jnp.asarray(rng.normal(size=(M, MB, U)), jnp.float32)

    param_spec = {"w1": P("pp", None, "tp"), "b1": P("pp", "tp"),
                  "w2": P("pp", "tp", None), "b2": P("pp")}
    loss, grads = parallel.pipeline_train_step_1f1b(
        stage_fn, loss_fn, stacked, xs, tg, mesh,
        batch_axis="dp", param_spec=param_spec)

    def ref_loss(stacked_params):
        def one(x, t):
            y = x
            for i in range(S):
                p = jax.tree_util.tree_map(lambda a: a[i], stacked_params)
                y = stage_fn_ref(p, y)
            return loss_fn(y, t)

        return jnp.mean(jax.vmap(one)(xs, tg))

    ref_l, ref_g = jax.value_and_grad(ref_loss)(stacked)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(ref_l), rtol=1e-5)
    for k in ("w1", "b1", "w2", "b2"):
        np.testing.assert_allclose(np.asarray(grads[k]), np.asarray(ref_g[k]),
                                   atol=2e-5)
