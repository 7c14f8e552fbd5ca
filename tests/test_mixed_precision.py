"""bf16 dtype-flow audits: every matmul in an AMP-converted train step must
run with bf16 operands (fp32 accumulation allowed) — f32×f32 dots mean a
leak that silently costs MXU throughput (found in r3: LayerNorm's affine
re-promoted activations, and the dense-attention backward ran entirely in
f32 until its custom VJP)."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import _trace, amp, nd


DOT_RE = re.compile(r'stablehlo\.dot_general\s+[^:]+:\s*'
                    r'\(tensor<([^>]+)>,\s*tensor<([^>]+)>\)'
                    r'\s*->\s*tensor<([^>]+)>')


def _dot_dtypes(txt):
    out = []
    for m in DOT_RE.finditer(txt):
        out.append(tuple(g.split("x")[-1] for g in m.groups()))
    return out


def test_layernorm_preserves_input_dtype():
    x = nd.array(np.random.randn(4, 8).astype(np.float32)).astype("bfloat16")
    g = nd.ones((8,))          # fp32 affine params (the AMP keep-list)
    b = nd.zeros((8,))
    y = nd.LayerNorm(x._data, g._data, b._data)
    assert y.dtype == jnp.bfloat16


def test_bert_train_step_has_no_f32_matmuls():
    from mxnet_tpu.models.bert import BERTModel
    from mxnet_tpu.parallel import tree_optimizer_step

    bert = BERTModel(vocab_size=512, units=128, hidden_size=256,
                     max_length=32, num_layers=2, num_heads=2, dropout=0.1)
    bert.initialize()
    amp.convert_hybrid_block(bert, "bfloat16")
    plist = list(bert.collect_params().values())
    opt = mx.optimizer.Adam(multi_precision=True)
    init_states, apply_opt = tree_optimizer_step(opt)

    def loss_fn(param_arrays, batch, key):
        tok, tt, vl, mp, mlm_y, nsp_y = batch
        with _trace.trace_scope(key, True) as t:
            t.param_store = {id(p): a for p, a in zip(plist, param_arrays)}
            _seq, _pooled, nsp_logits, mlm_logits = bert._call_traced(
                tok, tt, vl, mp)
        mlm_lp = jax.nn.log_softmax(mlm_logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(mlm_lp, mlm_y[..., None], axis=-1)
        nsp_lp = jax.nn.log_softmax(nsp_logits.astype(jnp.float32), axis=-1)
        return jnp.mean(nll) + jnp.mean(
            -jnp.take_along_axis(nsp_lp, nsp_y[:, None], axis=-1))

    params = [p.data()._data for p in plist]
    states = init_states(params)

    def step(params, states, t, key, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch, key)
        new_p, new_s = apply_opt(params, grads, states, jnp.float32(1e-4),
                                 jnp.float32(0.01), t)
        return new_p, new_s, loss

    rng = np.random.default_rng(0)
    B, S, M = 2, 32, 4
    batch = (jnp.asarray(rng.integers(0, 512, (B, S)), jnp.int32),
             jnp.zeros((B, S), jnp.int32),
             jnp.full((B,), S, jnp.float32),
             jnp.asarray(rng.integers(0, S, (B, M)), jnp.int32),
             jnp.asarray(rng.integers(0, 512, (B, M)), jnp.int32),
             jnp.asarray(rng.integers(0, 2, (B,)), jnp.int32))
    txt = jax.jit(step).lower(params, states, jnp.int32(1),
                              jax.random.PRNGKey(0), batch).as_text()
    dots = _dot_dtypes(txt)
    assert dots, "no dot_general found — lowering changed?"
    f32_dots = [d for d in dots if d[0] == "f32" and d[1] == "f32"]
    assert not f32_dots, (
        "f32xf32 matmuls leaked into the AMP train step (first 5): %s"
        % f32_dots[:5])

def test_loss_scaler_dynamic_fp16():
    """Upstream loss_scaler.py semantics: halve on overflow,
    double after scale_window clean steps, clamp at min/max."""
    from mxnet_tpu.amp import LossScaler

    s = LossScaler(init_scale=8.0, scale_factor=2.0, scale_window=2,
                   min_scale=1.0)
    assert s.update(overflow=True) == 4.0
    assert s.update(overflow=True) == 2.0
    # window=2 clean steps doubles back
    assert s.update(False) == 2.0
    assert s.update(False) == 4.0
    # overflow resets the clean-step counter
    s.update(False)
    assert s.update(overflow=True) == 2.0
    assert s.update(False) == 2.0
    assert s.update(False) == 4.0
    # min clamp
    for _ in range(10):
        s.update(overflow=True)
    assert s.loss_scale == 1.0


def test_loss_scaler_overflow_detection_and_unscale():
    import jax.numpy as jnp

    from mxnet_tpu import nd
    from mxnet_tpu.amp import LossScaler

    s = LossScaler(init_scale=4.0)
    loss = jnp.float32(2.0)
    assert float(s.scale(loss)) == 8.0

    good = [nd.array(np.ones((3,), np.float32)),
            nd.array(np.ones((2, 2), np.float32))]
    bad = good + [nd.array(np.array([1.0, np.inf], np.float32))]
    assert s.has_overflow(good) is False
    assert s.has_overflow(bad) is True
    assert s.has_overflow(nd.array(np.array([np.nan], np.float32))) is True

    un = s.unscale([g * 4.0 for g in good])
    for u, g in zip(un, good):
        np.testing.assert_allclose(u.asnumpy(), g.asnumpy(), rtol=1e-6)
