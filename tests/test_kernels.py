"""Pallas flash attention (interpret mode on CPU) + CTC loss."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import gluon, nd
from mxnet_tpu.ops.pallas.flash_attention import _flash_fwd
from mxnet_tpu.parallel import full_attention


def test_flash_attention_interpret_matches_reference():
    B, H, T, D = 1, 2, 256, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (B, H, T, D), jnp.float32) for kk in ks)
    for causal in (False, True):
        out = _flash_fwd(q, k, v, None, 1.0 / D ** 0.5, causal, 128, 128, interpret=True)
        ref = full_attention(q, k, v, causal=causal)
        assert float(jnp.abs(out - ref).max()) < 1e-4, causal


def test_flash_attention_backward_kernels_match_reference():
    """Pallas dq/dkv kernels (flash-2 recompute, no T×T residual) vs autodiff
    of the dense reference — the training path."""
    from mxnet_tpu.ops.pallas.flash_attention import flash_attention

    B, H, T, D = 1, 2, 256, 64
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q, k, v = (jax.random.normal(kk, (B, H, T, D), jnp.float32) for kk in ks[:3])
    ct = jax.random.normal(ks[3], (B, H, T, D), jnp.float32)
    for causal in (False, True):
        gq, gk, gv = jax.grad(
            lambda q_, k_, v_: jnp.sum(flash_attention(
                q_, k_, v_, causal=causal, block_q=128, block_k=128,
                interpret=True) * ct),
            argnums=(0, 1, 2))(q, k, v)
        rq, rk, rv = jax.grad(
            lambda q_, k_, v_: jnp.sum(
                full_attention(q_, k_, v_, causal=causal) * ct),
            argnums=(0, 1, 2))(q, k, v)
        for a, b, name in ((gq, rq, "dq"), (gk, rk, "dk"), (gv, rv, "dv")):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-3, rtol=1e-3,
                                       err_msg="%s causal=%s" % (name, causal))


def test_fused_layernorm_interpret_and_grad():
    from mxnet_tpu.ops.functional import LayerNorm
    from mxnet_tpu.ops.pallas.layernorm import fused_layernorm, _ln_bwd

    x = jax.random.normal(jax.random.PRNGKey(0), (64, 256), jnp.float32)
    g = jax.random.normal(jax.random.PRNGKey(1), (256,))
    b = jax.random.normal(jax.random.PRNGKey(2), (256,))
    out = fused_layernorm(x, g, b, interpret=True)
    ref = LayerNorm(x, g, b)
    assert float(jnp.abs(out - ref).max()) < 1e-4
    # analytic backward vs autodiff of the reference formulation
    dy = jax.random.normal(jax.random.PRNGKey(3), (64, 256))
    dx, dg, db = _ln_bwd(1e-5, True, (x, g), dy)
    rx, rg, rb = jax.grad(
        lambda x_, g_, b_: jnp.sum(LayerNorm(x_, g_, b_) * dy),
        argnums=(0, 1, 2))(x, g, b)
    assert float(jnp.abs(dx - rx).max()) < 1e-3
    assert float(jnp.abs(dg - rg).max()) < 1e-2
    assert float(jnp.abs(db - rb).max()) < 1e-2


def test_ctc_loss_brute_force():
    from mxnet_tpu.ops.ctc import CTCLoss

    rng = np.random.default_rng(0)
    T, V = 5, 4
    pred = jnp.asarray(rng.normal(size=(1, T, V)).astype(np.float32))
    label = jnp.asarray([[1, 2]], jnp.int32)
    loss = float(CTCLoss(pred, label)[0])

    lp = np.asarray(jax.nn.log_softmax(pred[0], axis=-1))

    def collapse(path):
        out, prev = [], None
        for s in path:
            if s != prev and s != 0:
                out.append(s)
            prev = s
        return out

    tot = -np.inf
    for path in itertools.product(range(V), repeat=T):
        if collapse(path) == [1, 2]:
            tot = np.logaddexp(tot, sum(lp[t, s] for t, s in enumerate(path)))
    assert abs(loss - (-tot)) < 1e-4


def test_ctc_gluon_block_and_grad():
    from mxnet_tpu import autograd

    loss_fn = gluon.loss.CTCLoss()
    pred = nd.array(np.random.randn(2, 8, 5).astype(np.float32))
    label = nd.array(np.array([[1, 2, 3], [2, 4, 4]], np.float32))
    pred.attach_grad()
    with autograd.record():
        loss = loss_fn(pred, label)
    assert loss.shape == (2,)
    assert np.isfinite(loss.asnumpy()).all()
    loss.backward()
    g = pred.grad.asnumpy()
    assert np.isfinite(g).all() and np.abs(g).sum() > 0


def test_fused_softmax_xent_interpret_and_grad():
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas.softmax_xent import softmax_xent

    rng = np.random.RandomState(3)
    N, V = 16, 256
    logits = jnp.asarray(rng.randn(N, V).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, V, N).astype(np.int32))

    loss = softmax_xent(logits, labels, True)  # interpret mode
    lp = jax.nn.log_softmax(logits)
    ref = -np.asarray(lp)[np.arange(N), np.asarray(labels)]
    np.testing.assert_allclose(np.asarray(loss), ref, rtol=1e-5)

    g = jax.grad(lambda lg: softmax_xent(lg, labels, True).sum())(logits)
    g_ref = jax.grad(lambda lg: -jnp.take_along_axis(
        jax.nn.log_softmax(lg), labels[:, None], axis=-1).sum())(logits)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=1e-5)


def test_fused_softmax_xent_unaligned_vocab():
    """Real vocabularies are not lane-aligned (BERT 30522, GPT-2 50257):
    the kernel pads V to a 128 multiple internally with a large-negative
    constant and slices the grad back — fwd and bwd must match the jnp
    reference exactly at an unaligned V."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas.softmax_xent import softmax_xent

    rng = np.random.RandomState(7)
    N, V = 8, 300  # 300 % 128 != 0
    logits = jnp.asarray(rng.randn(N, V).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, V, N).astype(np.int32))

    loss = softmax_xent(logits, labels, True)
    ref = -np.asarray(jax.nn.log_softmax(logits))[np.arange(N), np.asarray(labels)]
    np.testing.assert_allclose(np.asarray(loss), ref, rtol=1e-5)

    g = jax.grad(lambda lg: softmax_xent(lg, labels, True).sum())(logits)
    g_ref = jax.grad(lambda lg: -jnp.take_along_axis(
        jax.nn.log_softmax(lg), labels[:, None], axis=-1).sum())(logits)
    assert g.shape == (N, V)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=1e-5)


def test_gluon_softmax_ce_loss_routes_to_fused(monkeypatch):
    """User LM training must hit the pallas kernel.
    With the TPU gate forced open, gluon.loss.SoftmaxCrossEntropyLoss
    (sparse-label, from-logits) routes through softmax_xent_rows into the
    fused kernel (interpret mode stands in for hardware) and matches the
    log_softmax+pick formulation in value and gradient."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import autograd, nd
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
    from mxnet_tpu.ops import functional as OF
    from mxnet_tpu.ops.pallas import softmax_xent as SX

    monkeypatch.setattr(OF, "is_tpu_backend", lambda: True)
    seen = {}
    orig = SX.softmax_xent

    def spy(logits, labels, interpret=False):
        seen["shape"] = tuple(logits.shape)
        return orig(logits, labels, True)

    monkeypatch.setattr(SX, "softmax_xent", spy)

    rng = np.random.RandomState(11)
    B, T, V = 2, 3, 300  # unaligned V, 3-D logits like an LM head
    logits_np = rng.randn(B, T, V).astype(np.float32)
    labels_np = rng.randint(0, V, (B, T)).astype(np.float32)

    pred = nd.array(logits_np)
    label = nd.array(labels_np)
    pred.attach_grad()
    loss_fn = SoftmaxCrossEntropyLoss()
    with autograd.record():
        loss = loss_fn(pred, label)
    loss.backward()
    assert seen["shape"] == (B * T, V)  # fused path actually taken

    lp = jax.nn.log_softmax(jnp.asarray(logits_np), axis=-1)
    ref = -np.asarray(jnp.take_along_axis(
        lp, jnp.asarray(labels_np, jnp.int32)[..., None], axis=-1))[..., 0]
    np.testing.assert_allclose(loss.asnumpy(), ref.mean(axis=1), rtol=1e-5)
    assert np.isfinite(pred.grad.asnumpy()).all()
    assert np.abs(pred.grad.asnumpy()).sum() > 0


def test_fused_softmax_xent_bf16_logits():
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas.softmax_xent import softmax_xent

    rng = np.random.RandomState(4)
    logits = jnp.asarray(rng.randn(8, 128).astype(np.float32)).astype(jnp.bfloat16)
    labels = jnp.asarray(rng.randint(0, 128, 8).astype(np.int32))
    loss = softmax_xent(logits, labels, True)
    ref = -jax.nn.log_softmax(logits.astype(jnp.float32))[
        jnp.arange(8), labels]
    assert np.abs(np.asarray(loss) - np.asarray(ref)).max() < 0.05


def test_flash_attention_kv_valid_len():
    """Key-padding (prefix) masking inside the flash kernels — fwd + bwd
    match a densely masked reference, including a partially and a fully
    valid example."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas.flash_attention import flash_attention

    rng = np.random.RandomState(0)
    B, H, T, D = 2, 2, 256, 32
    q, k, v = (jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
               for _ in range(3))
    vl = jnp.asarray([100, 256], jnp.int32)

    def dense(q, k, v, causal=False):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
        mask = jnp.arange(T)[None, None, None, :] < vl[:, None, None, None]
        if causal:
            cm = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
            mask = mask & cm[None, None]
        s = jnp.where(mask, s, -1e30)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)

    for causal in (False, True):
        out = flash_attention(q, k, v, causal=causal, interpret=True,
                              kv_valid_len=vl)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(dense(q, k, v, causal)),
                                   rtol=2e-4, atol=2e-5)

    w = jnp.asarray(rng.randn(1, H, T, D).astype(np.float32))
    g1 = jax.grad(lambda a, b, c: (flash_attention(
        a, b, c, interpret=True, kv_valid_len=vl) * w).sum(),
        argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda a, b, c: (dense(a, b, c) * w).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)
    # grads of padded K/V positions must be exactly zero
    np.testing.assert_array_equal(np.asarray(g1[1][0, :, 100:, :]), 0.0)
    np.testing.assert_array_equal(np.asarray(g1[2][0, :, 100:, :]), 0.0)


def test_scaled_dot_attention_prefix_mask_matches_dense():
    """prefix_mask=True must be numerically identical to the explicit-mask
    reference path (on CPU both take the reference; the flag changes TPU
    routing only)."""
    import jax.numpy as jnp
    from mxnet_tpu.ops.attention import scaled_dot_attention

    rng = np.random.RandomState(1)
    B, H, T, D = 2, 2, 64, 16
    q, k, v = (jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
               for _ in range(3))
    vl = jnp.asarray([30, 64], jnp.int32)
    mask = (jnp.arange(T)[None, None, None, :]
            < vl[:, None, None, None]).astype(jnp.float32)
    a = scaled_dot_attention(q, k, v, mask)
    b = scaled_dot_attention(q, k, v, mask, prefix_mask=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_prefix_mask_to_valid_len_recovery():
    import jax.numpy as jnp
    from mxnet_tpu.ops.attention import _prefix_mask_to_valid_len

    vl = np.array([3, 7, 0], np.int32)
    T = 8
    # BERT shape (B,1,1,T) and full (B,H,Tq,Tk) prefix masks both recover
    m1 = (np.arange(T)[None, None, None, :] < vl[:, None, None, None])
    m4 = np.broadcast_to(m1, (3, 2, T, T))
    for m in (m1, m4):
        got = _prefix_mask_to_valid_len(jnp.asarray(m.astype(np.float32)))
        np.testing.assert_array_equal(np.asarray(got), vl)


def test_prefix_mask_routes_to_flash(monkeypatch):
    """With the TPU gate forced open, prefix_mask=True must route through
    the flash kernel with the recovered valid length (interpret mode stands
    in for hardware) and match the dense reference."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import attention as A
    from mxnet_tpu.ops.pallas import flash_attention as FA

    monkeypatch.setattr(A, "is_tpu_backend", lambda: True)
    monkeypatch.setattr(A, "_FLASH_MIN_LEN", 0)
    # a swept flash_blocks.json ships in-repo since r5 and its measured
    # MIN_LEN overrides the static gate — neutralize both gate sources
    monkeypatch.setattr(FA, "MIN_LEN", None)
    seen = {}
    orig = FA.flash_attention

    def spy(q, k, v, **kw):
        seen["kv_valid_len"] = kw.get("kv_valid_len")
        return orig(q, k, v, interpret=True,
                    **{k2: v2 for k2, v2 in kw.items() if k2 != "interpret"})

    monkeypatch.setattr(FA, "flash_attention", spy)

    rng = np.random.RandomState(2)
    B, H, T, D = 2, 2, 64, 16
    q, k, v = (jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
               for _ in range(3))
    vl = np.array([20, 64], np.int32)
    mask = jnp.asarray((np.arange(T)[None, None, None, :]
                        < vl[:, None, None, None]).astype(np.float32))
    out = A.scaled_dot_attention(q, k, v, mask, prefix_mask=True)
    assert seen["kv_valid_len"] is not None
    np.testing.assert_array_equal(np.asarray(seen["kv_valid_len"]), vl)
    ref = A._reference_attention(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_flash_attention_bf16_fwd_and_grads_match_oracle():
    """The bf16 MXU path (native-dtype operands, p/ds downcasts — the AMP
    train-step path): fwd + all three grads vs the f32 dense oracle, with
    bf16-appropriate tolerances. f32-input tests cannot see this path
    because its casts are no-ops there."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.pallas.flash_attention import flash_attention

    rng = np.random.default_rng(7)
    B, H, T, D = 2, 2, 256, 64
    q = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.bfloat16)
    vl = jnp.asarray([192, 256], jnp.float32)

    def oracle(q, k, v, causal, vl_):
        s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32) / np.sqrt(D),
                       k.astype(jnp.float32))
        if causal:
            cm = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
            s = jnp.where(cm[None, None], s, -1e30)
        if vl_ is not None:
            km = jnp.arange(T)[None, None, None, :] < vl_[:, None, None, None]
            s = jnp.where(km, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))

    for causal, kv in ((False, None), (True, None), (False, vl), (True, vl)):
        got = flash_attention(q, k, v, causal=causal, block_q=128,
                              block_k=128, interpret=True, kv_valid_len=kv)
        assert got.dtype == jnp.bfloat16
        want = oracle(q, k, v, causal, kv)
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
        assert err < 0.05, (causal, kv is not None, err)

        def f(args, causal=causal, kv=kv):
            return (flash_attention(*args, causal=causal, block_q=128,
                                    block_k=128, interpret=True,
                                    kv_valid_len=kv)
                    .astype(jnp.float32) ** 2).sum()

        def g(args, causal=causal, kv=kv):
            return (oracle(*args, causal, kv) ** 2).sum()

        gn = jax.grad(f)((q, k, v))
        go = jax.grad(g)((q, k, v))
        for a, b, nm in zip(gn, go, "qkv"):
            assert a.dtype == jnp.bfloat16, nm
            rel = float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                        - b.astype(jnp.float32)))
                        / (float(jnp.max(jnp.abs(b))) + 1e-9))
            assert rel < 0.08, (nm, causal, kv is not None, rel)


# ------------------------------------------------ kv_cache_write (PR 29)
def _vmap_dus(cache, update, index, live=None):
    """Today's per-slot write, the kernel's reference, bit for bit: the
    live rows as ``vmap(dynamic_update_slice)``, the other rows untouched."""
    zero = jnp.int32(0)
    written = jax.vmap(
        lambda c, u, i: jax.lax.dynamic_update_slice(c, u, (zero, i, zero))
    )(cache, update, jnp.asarray(index, jnp.int32))
    if live is None:
        return written
    return jnp.where((jnp.asarray(live) != 0).reshape(-1, 1, 1, 1), written,
                     cache)


# the first slot's position; the other three slots stay at 5, C - 2 and 64
_KV_POSITIONS = {
    "first": lambda C: 0,
    "last_lane_of_a_block": lambda C: 127,
    "first_lane_of_the_next_block": lambda C: 128,   # == C at C = 128
    "last": lambda C: C - 1,
    "at_capacity_clamps": lambda C: C,
    "far_past_capacity_clamps": lambda C: 3 * C + 7,
    "negative_counts_from_the_end": lambda C: -3,
    "far_negative_clamps_to_zero": lambda C: -2 * C,
}
# which of six slots hold a stream (PR 34), by what the decode step hands
# over: a length, 0 for a free slot. The slots stand at 5, C - 2, 64, 70
# (the block of 64), -3 and 3 * C + 7
_KV_LIVE = {
    "no_slot_live": [0, 0, 0, 0, 0, 0],
    "one_slot_live": [0, 0, 65, 0, 0, 0],
    "some_slots_live_not_side_by_side": [6, 0, 65, 0, 0, 1],
    "all_slots_live": [6, 1, 65, 71, 9, 1],
    "two_live_slots_in_one_block_index": [0, 0, 65, 71, 0, 0],
    "live_at_negative_and_clamped_positions": [0, 0, 0, 0, 9, 2],
}


@pytest.mark.parametrize("where", list(_KV_POSITIONS) + [
    "all_slots_equal", "more_slots_than_lanes"] + list(_KV_LIVE) + [
    "more_slots_than_lanes_a_third_live"])
@pytest.mark.parametrize("C", [128, 1024])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_kv_cache_write_interpret_matches_dynamic_update_slice(dtype, C,
                                                               where):
    """The Pallas K/V column write in interpret mode against
    ``vmap(dynamic_update_slice)``: the same bits at block edges, with the
    start clamped as ``dynamic_update_slice`` clamps it, with several slots
    at one position, and every other lane and slot untouched; told which
    slots are live, the same bits in those and no bit of the others
    changed, with none, one, some and all live (all live is the call that
    is not told)."""
    from mxnet_tpu.ops.pallas import kv_write

    S, H = (130, 1) if where.startswith("more_slots_than_lanes") else (4, 3)
    live = None
    if where in _KV_LIVE:
        S, live = 6, _KV_LIVE[where]
    D = 16 if dtype == jnp.bfloat16 else 8      # one sublane tile
    ks = jax.random.split(jax.random.PRNGKey(C), 2)
    cache = jax.random.normal(ks[0], (S, H, C, D), dtype)
    update = jax.random.normal(ks[1], (S, H, 1, D), dtype)
    if where == "all_slots_equal":
        index = [77] * S
    elif where.startswith("more_slots_than_lanes"):
        index = [(37 * i) % C for i in range(S)]
        if where.endswith("a_third_live"):
            live = [(i + 1) * (i % 3 == 0) for i in range(S)]
    elif live is not None:
        index = [5, C - 2, 64, 70, -3, 3 * C + 7]
    else:
        index = [_KV_POSITIONS[where](C), 5, C - 2, 64]
    assert kv_write.tiles(cache.shape, update.shape, dtype)
    told = () if live is None else (jnp.asarray(live, jnp.int32),)
    got = kv_write.kv_cache_write(cache, update, jnp.asarray(index, jnp.int32),
                                  *told, interpret=True)
    want = _vmap_dus(cache, update, index, live)
    assert got.dtype == want.dtype and got.shape == want.shape
    as_bits = lambda a: np.asarray(a.astype(jnp.float32)).view(np.uint32)
    np.testing.assert_array_equal(as_bits(got), as_bits(want))
    # and stated on its own: one column a live slot changed, nothing else did
    landed = np.clip([i + C if i < 0 else i for i in index], 0, C - 1)
    wrote = np.ones(S, bool) if live is None else np.asarray(live) != 0
    kept = np.ones((S, H, C, D), bool)
    kept[np.arange(S)[wrote], :, landed[wrote], :] = False
    np.testing.assert_array_equal(as_bits(got)[kept], as_bits(cache)[kept])
    np.testing.assert_array_equal(
        as_bits(got)[np.arange(S)[wrote], :, landed[wrote], :],
        as_bits(update)[wrote, :, 0, :])
    if where == "all_slots_live":
        untold = kv_write.kv_cache_write(
            cache, update, jnp.asarray(index, jnp.int32), interpret=True)
        np.testing.assert_array_equal(as_bits(got), as_bits(untold))


def _kv_gate_case(case):
    """(cache shape, update shape, index) of one way past the kernel."""
    S, H = 4, 2
    C, D, T = {"window_of_4": (256, 16, 4), "capacity_64": (64, 16, 1),
               "head_dim_128": (256, 128, 1),
               "head_dim_192": (256, 192, 1),
               "head_dim_off_the_sublane_tile": (256, 12, 1),
               }.get(case, (256, 16, 1))
    index = (jnp.int32(9) if case == "scalar_index"
             else jnp.arange(S, dtype=jnp.int32) * 15)
    return (S, H, C, D), (S, H, T, D), index


@pytest.mark.parametrize("case", [
    "scalar_index", "window_of_4", "capacity_64", "head_dim_192",
    "head_dim_off_the_sublane_tile", "under_a_mesh", "on_the_cpu",
    "tpu_and_tiles", "head_dim_128", "scalar_index_told_the_live_rows",
    "window_of_4_told_the_live_rows", "under_a_mesh_told_the_live_rows",
    "on_the_cpu_told_the_live_rows", "tpu_and_tiles_told_the_live_rows",
    "head_dim_128_told_the_live_rows"])
def test_cache_write_gate(monkeypatch, case):
    """``cache_write`` decides at trace time, from what it can see: only a
    per-slot index with one token a slot, shapes that tile, a TPU and no
    device mesh reach the kernel (head widths under a lane tile by the
    column path, whole lane tiles such as 128 by the row path); every other
    call keeps today's path. Told which rows are live, the kernel, the one
    ``dynamic_update_slice`` of a scalar index and the scatter agree: the
    live rows written, the bits of the others as they were."""
    from mxnet_tpu import parallel
    from mxnet_tpu.ops import attention as A
    from mxnet_tpu.ops.pallas import kv_write

    calls = []
    told = case.endswith("_told_the_live_rows")
    case = case.replace("_told_the_live_rows", "")
    reaches = case in ("tpu_and_tiles", "head_dim_128")

    def kernel(cache, update, index, live=None):
        calls.append(cache.shape)
        if not reaches:
            raise AssertionError("%s reached the kernel" % case)
        assert (live is not None) == told
        return kv_write_orig(cache, update, index, live, interpret=True)

    kv_write_orig = kv_write.kv_cache_write
    monkeypatch.setattr(kv_write, "kv_cache_write", kernel)
    if case != "on_the_cpu":
        monkeypatch.setattr(A, "is_tpu_backend", lambda: True)
    cshape, ushape, index = _kv_gate_case(case)
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    cache = jax.random.normal(ks[0], cshape, jnp.float32)
    update = jax.random.normal(ks[1], ushape, jnp.float32)
    live = (jnp.asarray([3, 0, 0, 1], jnp.int32),) if told else ()
    if case == "under_a_mesh":
        with parallel.use_mesh(parallel.make_mesh({"dp": -1})):
            got = A.cache_write(cache, update, index, *live)
    else:
        got = A.cache_write(cache, update, index, *live)
    want = _vmap_dus(cache, update, jnp.broadcast_to(index, cshape[:1]),
                     *live)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert len(calls) == (1 if reaches else 0)


@pytest.mark.parametrize("slots", [3, 5])
def test_server_tokens_same_with_the_kv_write_kernel(monkeypatch, slots):
    """``GenerativeServer``'s greedy tokens with the kernel forced on (the
    TPU gate open, interpret mode standing in for the chip) are the tokens
    it serves without it: gpt_nano's widths over a 128-token capacity, with
    every slot taken and with two that never hold a stream (the kernel is
    told which slots are live and leaves the others' pages as they lie)."""
    import mxnet_tpu as mx
    from mxnet_tpu.models.gpt import GPTModel
    from mxnet_tpu.ops import attention as A
    from mxnet_tpu.ops.pallas import decode_attention, kv_write

    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, 256, (n,)).astype(np.int32) for n in (5, 11, 3)]

    def serve():
        mx.random.seed(11)
        model = GPTModel(vocab_size=256, units=64, num_layers=2, num_heads=2,
                         max_length=128, dropout=0.0)
        model.initialize()
        with mx.serve.GenerativeServer(model, slots=slots,
                                       timeout_ms=120000.0) as srv:
            streams = [srv.submit(p, max_new_tokens=40 + 15 * i)
                       for i, p in enumerate(prompts)]
            tokens = [s.result(120) for s in streams]
            assert srv.cache.capacity == 128
        return tokens

    plain = serve()
    calls = []
    orig = kv_write.kv_cache_write

    def forced(cache, update, index, live):
        calls.append(cache.shape)
        return orig(cache, update, index, live, interpret=True)

    monkeypatch.setattr(A, "is_tpu_backend", lambda: True)
    monkeypatch.setattr(kv_write, "kv_cache_write", forced)
    # the write alone: the read keeps the dense path here (both kernels on:
    # test_server_tokens_same_with_the_decode_attention_kernel)
    monkeypatch.setattr(decode_attention, "tiles", lambda *shapes: False)
    with_kernel = serve()
    # K and V of two layers, in every decode program traced
    assert calls and len(calls) % 4 == 0
    assert set(calls) == {(slots, 2, 128, 32)}
    assert with_kernel == plain


# ------------------------------------------------ decode_attention (PR 32)
def _dense_over_lengths(q, k, v, lengths):
    """Today's read, the kernel's reference: the mask ``position < length``
    and the dense attention over every slot's whole buffer."""
    from mxnet_tpu.ops import attention as A

    C = k.shape[2]
    mask = (jnp.arange(C, dtype=jnp.int32).reshape(1, 1, 1, C)
            < jnp.asarray(lengths, jnp.int32).reshape(-1, 1, 1, 1))
    return A.scaled_dot_attention(q, k, v, mask)


# (slots, query heads, K/V heads, capacity, head width) of each layout at
# test size: the column path's blocks are 128 positions, the row path's 512
_DA_LAYOUTS = {"columns": (7, 3, 3, 384, None), "rows": (7, 4, 2, 1024, 128)}


def _da_lengths(case, S, C, block):
    if case == "edges":
        return [0, 1, block - 1, block, block + 1, C, C + 77][:S]
    if case == "lane_tile_edges":
        return [127, 128, 129, 0, 1, 255, 257][:S]
    return {"all_dead": [0] * S, "all_full": [C] * S,
            "more_slots_than_lanes": [(53 * i) % (C + 1) for i in range(S)],
            "sixteen_query_heads_a_group": [3, 0, C - 5, block + 9, 1, 0,
                                            C][:S]}[case]


@pytest.mark.parametrize("case", [
    "edges", "lane_tile_edges", "all_dead", "all_full",
    "more_slots_than_lanes", "sixteen_query_heads_a_group"])
@pytest.mark.parametrize("layout", list(_DA_LAYOUTS))
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_decode_attention_interpret_matches_the_dense_path(dtype, layout,
                                                           case):
    """The Pallas decode attention in interpret mode against the dense
    masked attention on the same lengths, both layouts: at block edges, with
    every slot dead (zeros) or full, with a length past the capacity (it
    clamps, as a ring past its wrap reads all of its ring), with more slots
    than lanes and with 16 query heads a K/V head; and what lies past a
    slot's length never reaches its output."""
    from mxnet_tpu.ops.pallas import decode_attention as K

    S, H, Hkv, C, D = _DA_LAYOUTS[layout]
    block = 128 if layout == "columns" else 512
    if D is None:
        D = 16 if dtype == jnp.bfloat16 else 8      # one sublane tile
    if case == "more_slots_than_lanes":
        S, H, Hkv, C = 130, 1, 1, block
    if case == "sixteen_query_heads_a_group":
        if layout == "columns":
            pytest.skip("the column path takes as many K/V as query heads")
        H = 16 * Hkv
    lengths = _da_lengths(case, S, C, block)
    ks = jax.random.split(jax.random.PRNGKey(C + S), 3)
    q = jax.random.normal(ks[0], (S, H, 1, D), dtype)
    k = jax.random.normal(ks[1], (S, Hkv, C, D), dtype)
    v = jax.random.normal(ks[2], (S, Hkv, C, D), dtype)
    assert K.tiles(q.shape, k.shape, dtype)
    run = jax.jit(lambda q, k, v, n: K.decode_attention(q, k, v, n,
                                                        interpret=True))
    got = run(q, k, v, jnp.asarray(lengths, jnp.int32))
    assert got.shape == q.shape and got.dtype == q.dtype
    f32 = lambda a: np.asarray(a.astype(jnp.float32))
    live = np.asarray(lengths) > 0
    want = _dense_over_lengths(q, k, v, lengths)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(f32(got)[live], f32(want)[live], atol=tol,
                               rtol=tol)
    # a dead slot reads nothing and gives zeros
    assert not f32(got)[~live].any()
    # large values past every length change no bit of the result
    past = (np.arange(C).reshape(1, 1, C, 1)
            >= np.minimum(lengths, C).reshape(-1, 1, 1, 1))
    foul = lambda a: jnp.where(past, jnp.asarray(3e4, dtype), a)
    again = run(q, foul(k), foul(v), jnp.asarray(lengths, jnp.int32))
    np.testing.assert_array_equal(f32(again), f32(got))


def _da_gate_case(case):
    """(q shape, cache shape, lengths) of one way to or past the kernel."""
    S, H, Hkv, C, D, T = {
        "window_of_4": (4, 2, 2, 256, 16, 4),
        "capacity_64": (4, 2, 2, 64, 16, 1),
        "head_dim_12": (4, 2, 2, 256, 12, 1),
        "head_dim_192": (4, 2, 2, 256, 192, 1),
        "grouped_heads_under_a_lane_tile": (4, 4, 2, 256, 16, 1),
        "tpu_and_tiles": (4, 2, 2, 256, 16, 1),
        "head_dim_128_grouped": (4, 4, 2, 256, 128, 1),
        "served_gpt2_large": (32, 20, 20, 1024, 64, 1),
        "served_ring_of_command_a_plus": (32, 128, 8, 4096, 128, 1),
        "served_page_of_command_a_plus": (32, 128, 8, 8192, 128, 1),
    }.get(case.replace("_row_path_opened", ""), (4, 2, 2, 256, 16, 1))
    lengths = (jnp.int32(9) if case == "scalar_length"
               else jnp.arange(S, dtype=jnp.int32) * 15 % (C + 1))
    return (S, H, T, D), (S, Hkv, C, D), lengths


@pytest.mark.parametrize("case", [
    "scalar_length", "window_of_4", "capacity_64", "head_dim_12",
    "head_dim_192", "grouped_heads_under_a_lane_tile", "under_a_mesh",
    "on_the_cpu", "tpu_and_tiles", "head_dim_128_grouped",
    "served_gpt2_large", "served_ring_of_command_a_plus",
    "served_page_of_command_a_plus", "head_dim_128_grouped_row_path_opened",
    "served_ring_of_command_a_plus_row_path_opened",
    "served_page_of_command_a_plus_row_path_opened"])
def test_cached_attention_gate(monkeypatch, case):
    """``cached_attention`` decides at trace time, from what it can see:
    only per-row lengths with one query token a row, shapes that tile, a TPU
    and no device mesh reach the kernel, by its column path (the row path,
    head widths of whole lane tiles, only where ``_DECODE_ROW_PATH`` is
    opened: it is shut); every other call builds today's mask from the
    lengths and takes today's dense path, bit for bit."""
    from mxnet_tpu import parallel
    from mxnet_tpu.ops import attention as A
    from mxnet_tpu.ops.pallas import decode_attention as K

    # the row path (head widths of whole lane tiles) is shut at this gate
    row_path = case in ("head_dim_128_grouped_row_path_opened",
                        "served_ring_of_command_a_plus_row_path_opened",
                        "served_page_of_command_a_plus_row_path_opened")
    monkeypatch.setattr(A, "_DECODE_ROW_PATH", row_path)
    reaches = case in ("tpu_and_tiles", "served_gpt2_large") or row_path
    calls = []

    def kernel(q, k_cache, v_cache, lengths, scale=None):
        calls.append((q.shape, k_cache.shape))
        if not reaches:
            raise AssertionError("%s reached the kernel" % case)
        return jnp.zeros_like(q)

    monkeypatch.setattr(K, "decode_attention", kernel)
    if case != "on_the_cpu":
        monkeypatch.setattr(A, "is_tpu_backend", lambda: True)
    qshape, cshape, lengths = _da_gate_case(case)
    if reaches or case.startswith("served_"):
        # the served shapes are only traced: nothing of their size is made
        spec = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        out = jax.eval_shape(       # a new function: no trace is reused
            lambda *args: A.cached_attention(*args), spec(qshape),
            spec(cshape), spec(cshape), lengths)
        assert out.shape == qshape
        assert calls == ([(qshape, cshape)] if reaches else [])
        return
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], qshape, jnp.float32)
    k = jax.random.normal(ks[1], cshape, jnp.float32)
    v = jax.random.normal(ks[2], cshape, jnp.float32)
    if case == "under_a_mesh":
        with parallel.use_mesh(parallel.make_mesh({"dp": -1})):
            got = A.cached_attention(q, k, v, lengths)
    else:
        got = A.cached_attention(q, k, v, lengths)
    # today's mask: row t of a slot sees the positions up to its start + t
    T, C = qshape[2], cshape[2]
    start = (lengths if lengths.ndim == 0
             else lengths.reshape(-1, 1, 1, 1)) - 1
    mask = (jnp.arange(C, dtype=jnp.int32).reshape(1, 1, 1, C)
            <= jnp.arange(T, dtype=jnp.int32).reshape(1, 1, T, 1) + start)
    want = A.scaled_dot_attention(q, k, v, mask)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not calls


def _serve_in_waves(make_model, prompts, new_tokens, slots):
    """Greedy tokens of ``prompts`` through a ``GenerativeServer`` of fewer
    slots than requests: later requests join as earlier ones retire, and
    between steps some slots are free."""
    import mxnet_tpu as mx

    mx.random.seed(11)
    model = make_model()
    model.initialize()
    with mx.serve.GenerativeServer(model, slots=slots,
                                   timeout_ms=300000.0) as srv:
        streams = [srv.submit(p, max_new_tokens=n)
                   for p, n in zip(prompts, new_tokens)]
        tokens = [s.result(300) for s in streams]
        capacity = srv.cache.capacity
    return tokens, capacity


@pytest.mark.parametrize("which", ["gpt", "cohere_moe"])
def test_server_tokens_same_with_the_decode_attention_kernel(monkeypatch,
                                                             which):
    """``GenerativeServer``'s greedy tokens with both decode kernels forced
    on (the TPU gate open, interpret mode standing in for the chip) are the
    tokens it serves on the dense path, with slots joining and retiring
    between steps and free slots beside live ones: a GPT on the column path,
    and ``cohere_moe_nano`` at head width 128 on the row path (opened for
    the test: it is shut at the gate), its rings shorter than the positions
    its streams reach."""
    from mxnet_tpu.models.cohere_moe import cohere_moe_nano
    from mxnet_tpu.models.gpt import GPTModel
    from mxnet_tpu.ops import attention as A
    from mxnet_tpu.ops.pallas import decode_attention as K
    from mxnet_tpu.ops.pallas import kv_write

    rng = np.random.RandomState(5)
    if which == "gpt":
        make = lambda: GPTModel(vocab_size=256, units=64, num_layers=2,
                                num_heads=2, max_length=256, dropout=0.0)
        sizes, new_tokens, buffers = (5, 11, 3, 40, 9), (70, 12, 130, 25, 6), \
            {(3, 2, 256, 32)}
    else:
        make = lambda: cohere_moe_nano(head_dim=128, sliding_window=128,
                                       max_length=256)
        sizes, new_tokens, buffers = (100, 7, 30, 60), (60, 20, 9, 70), \
            {(3, 2, 128, 128), (3, 2, 256, 128)}
    prompts = [rng.randint(0, 256, (n,)).astype(np.int32) for n in sizes]
    plain, capacity = _serve_in_waves(make, prompts, new_tokens, slots=3)
    assert capacity == 256
    calls = []
    attend, write = K.decode_attention, kv_write.kv_cache_write

    def forced(q, k_cache, v_cache, lengths, scale=None):
        calls.append(k_cache.shape)
        return attend(q, k_cache, v_cache, lengths, scale=scale,
                      interpret=True)

    monkeypatch.setattr(A, "is_tpu_backend", lambda: True)
    monkeypatch.setattr(A, "_DECODE_ROW_PATH", True)
    monkeypatch.setattr(K, "decode_attention", forced)
    monkeypatch.setattr(kv_write, "kv_cache_write",
                        lambda c, u, i, live: write(c, u, i, live,
                                                    interpret=True))
    with_kernel, _ = _serve_in_waves(make, prompts, new_tokens, slots=3)
    # one call a layer in every decode program traced, on these buffers
    assert calls and set(calls) == buffers
    assert with_kernel == plain


# ------------------------------------------------ retention_step (PR 35)
@pytest.mark.parametrize("live", [(1, 0, 1), (1, 1, 1), (0, 0, 0), None],
                         ids=["one_free", "all_live", "none_live", "untold"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_retention_step_interpret_matches_the_plain_lowering(dtype, live):
    """The step's kernel in interpret mode against ``ops/retention.py``'s
    ``jax.numpy`` step at the published head width (128: 65 rows of phi, 5
    blocks of 13) and grouping (5 queries a K/V head): the live slots'
    output, S and z; a free slot's state bit for bit, its output zeros."""
    from mxnet_tpu.ops import retention as R
    from mxnet_tpu.ops.pallas import retention_step as K

    rs = np.random.RandomState(0)
    slots, H, Hkv, D = 3, 10, 2, 128
    rows = R.phi_rows(D)
    q = jnp.asarray(rs.normal(0, 1, (slots, H, 1, D)), dtype)
    k = jnp.asarray(rs.normal(0, 1, (slots, Hkv, 1, D)), dtype)
    v = jnp.asarray(rs.normal(0, 1, (slots, Hkv, 1, D)), dtype)
    log_c = jnp.asarray(rs.uniform(-1, 0, (slots, Hkv)), jnp.float32)
    S = jnp.asarray(rs.normal(0, 1, (slots, Hkv, rows * D, D)), jnp.float32)
    z = jnp.asarray(rs.normal(3, 1, (slots, Hkv, rows, D)), jnp.float32)
    assert K.tiles(q.shape, k.shape)
    told = None if live is None else jnp.asarray(live, jnp.int32)
    o, S1, z1 = K.retention_step(q, k, v, log_c, S, z, told, interpret=True)
    want_o, want_S, want_z = R._step(q, k, v, log_c, S, z, told, 1e-6)
    assert o.dtype == dtype and S1.dtype == z1.dtype == jnp.float32
    for slot, on in enumerate(live or (1, 1, 1)):
        if on:
            np.testing.assert_allclose(
                np.asarray(o[slot], np.float32),
                np.asarray(want_o[slot], np.float32),
                atol=2e-2 if dtype == jnp.bfloat16 else 1e-4)
            np.testing.assert_allclose(np.asarray(S1[slot]),
                                       np.asarray(want_S[slot]), atol=1e-5)
            np.testing.assert_allclose(np.asarray(z1[slot]),
                                       np.asarray(want_z[slot]), atol=1e-5)
        else:
            assert not np.asarray(o[slot], np.float32).any()
            assert np.asarray(S1[slot]).tobytes() \
                == np.asarray(S[slot]).tobytes()
            assert np.asarray(z1[slot]).tobytes() \
                == np.asarray(z[slot]).tobytes()


def test_power_retention_gate(monkeypatch):
    """On a TPU one token a slot at head width 128 takes the kernel; more
    tokens, another head width, a mesh and the CPU take ``jax.numpy``."""
    from mxnet_tpu.ops import retention as R
    from mxnet_tpu.ops.pallas import retention_step as K

    calls = []
    monkeypatch.setattr(
        K, "retention_step",
        lambda q, k, v, log_c, S, z, live, eps: calls.append(q.shape)
        or R._step(q, k, v, log_c, S, z, live, eps))

    def run(D, T=1):
        q = jnp.ones((2, 10, T, D))
        k = v = jnp.ones((2, 2, T, D))
        R.power_retention(q, k, v, jnp.zeros((2, 2, T)),
                          R.zero_state(2, 2, D), jnp.ones((2,) + (T,) * (T > 1)))

    run(128)
    assert not calls                                  # the CPU
    monkeypatch.setattr(R, "is_tpu_backend", lambda: True)
    run(128)
    assert calls == [(2, 10, 1, 128)]
    run(16), run(128, T=4)
    assert len(calls) == 1
    monkeypatch.setattr(R, "under_mesh", lambda: True)
    run(128)
    assert len(calls) == 1


# --------------------------------------------- latent_attention (PR 37)
@pytest.mark.parametrize("lengths", [(0, 1, 512, 700, 1024), (1024,) * 5,
                                     (0,) * 5, (129, 0, 3, 0, 513)],
                         ids=["ragged", "full", "none_live", "sparse"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_latent_attention_interpret_matches_the_dense_lowering(dtype,
                                                               lengths):
    """The absorbed decode read in interpret mode against the op's dense
    masked lowering: 16 heads over a latent of 128 and a rotated part of 32,
    a buffer of two 512-row blocks; a slot of length 0 gives zeros."""
    from mxnet_tpu.ops import attention as A
    from mxnet_tpu.ops.pallas import latent_attention as K

    rs = np.random.RandomState(0)
    S, H, R, P, C = 5, 16, 128, 32, 1024
    ql = jnp.asarray(rs.normal(0, 1, (S, H, 1, R)), dtype)
    qp = jnp.asarray(rs.normal(0, 1, (S, H, 1, P)), dtype)
    c = jnp.asarray(rs.normal(0, 1, (S, 1, C, R)), dtype)
    pe = jnp.asarray(rs.normal(0, 1, (S, 1, C, P)), dtype)
    lens = jnp.asarray(lengths, jnp.int32)
    assert K.tiles(ql.shape, qp.shape, c.shape, pe.shape, dtype)
    got = K.latent_attention(ql, qp, c, pe, lens, 0.13, interpret=True)
    want = A.latent_attention(ql, qp, c, pe, lens, scale=0.13)
    assert got.shape == (S, H, 1, R) and got.dtype == dtype
    for slot, n in enumerate(lengths):
        if n:
            np.testing.assert_allclose(
                np.asarray(got[slot], np.float32),
                np.asarray(want[slot], np.float32),
                atol=2e-2 if dtype == jnp.bfloat16 else 2e-5)
        else:
            assert not np.asarray(got[slot], np.float32).any()


def test_latent_attention_gate(monkeypatch):
    """On a TPU per-row lengths and one token a row take the kernel where
    the shapes tile; more tokens, a scalar length, a rotated part of a whole
    lane tile, a mesh and the CPU take the dense lowering."""
    from mxnet_tpu.ops import attention as A
    from mxnet_tpu.ops.pallas import latent_attention as K

    calls = []
    monkeypatch.setattr(
        K, "latent_attention",
        lambda ql, qp, c, pe, lens, scale: calls.append(ql.shape)
        or jnp.zeros_like(ql))

    def run(T=1, P=16, lengths=(3, 5)):
        A.latent_attention(jnp.ones((2, 8, T, 128)), jnp.ones((2, 8, T, P)),
                           jnp.ones((2, 1, 256, 128)), jnp.ones((2, 1, 256, P)),
                           jnp.asarray(lengths), scale=0.1)

    run()
    assert not calls                                  # the CPU
    monkeypatch.setattr(A, "is_tpu_backend", lambda: True)
    run()
    assert calls == [(2, 8, 1, 128)]
    run(T=2), run(P=128), run(lengths=4)
    assert len(calls) == 1
    monkeypatch.setattr(A, "under_mesh", lambda: True)
    run()
    assert len(calls) == 1
    assert not K.tiles((2, 8, 1, 128), (2, 8, 1, 16), (2, 1, 200, 128),
                       (2, 1, 200, 16), jnp.float32)      # not whole tiles
    assert not K.tiles((2, 6, 1, 128), (2, 6, 1, 16), (2, 1, 256, 128),
                       (2, 1, 256, 16), jnp.float32)      # 6 heads


def test_flash_forward_takes_values_of_their_own_width():
    """The forward kernel in interpret mode at key width 48 and value width
    32 (latent attention's prefill: 192 and 128) against the dense path."""
    from mxnet_tpu.ops import attention as A
    from mxnet_tpu.ops.pallas.flash_attention import flash_attention

    rs = np.random.RandomState(1)
    q, k = (jnp.asarray(rs.normal(0, 1, (1, 2, 256, 48)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rs.normal(0, 1, (1, 2, 256, 32)), jnp.float32)
    got = flash_attention(q, k, v, causal=True, scale=0.13, block_q=128,
                          block_k=128, interpret=True)
    want = A._reference_attention(q, k, v, causal=True, scale=0.13)
    assert got.shape == (1, 2, 256, 32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_expanded_latent_attention_in_head_groups_is_the_whole(monkeypatch):
    """The heads go a group at a time: the same result as all at once (a
    group that does not divide them)."""
    from mxnet_tpu.ops import attention as A

    rs = np.random.RandomState(2)
    H, T, Rq, R, P, N, Dv = 32, 12, 24, 16, 8, 8, 8
    arr = lambda *s: jnp.asarray(rs.normal(0, 0.5, s), jnp.float32)
    args = (arr(1, T, Rq), arr(1, 1, T, R), arr(1, 1, T, P),
            arr(H * (N + P), Rq), arr(H * N, R), arr(H * Dv, R))
    kw = dict(heads=H, scale=0.2, inv_freq=(1.0, 0.1, 0.01, 0.001))
    groups = A.expanded_latent_attention(*args, **kw)
    monkeypatch.setattr(A, "_EXPAND_GROUP", H + 1)
    whole = A.expanded_latent_attention(*args, **kw)
    assert whole.shape == groups.shape == (1, H, T, Dv)
    np.testing.assert_allclose(np.asarray(groups), np.asarray(whole),
                               atol=1e-5)
