"""Plain reference of the ``bert-large`` configuration: BERT pretraining.

Devlin et al., arXiv:1810.04805: post-LN transformer encoder with learned
position embeddings, exact (erf) GELU, tanh pooler, MLM head (dense + GELU +
LayerNorm + decoder tied to the word embedding + bias) over the masked
positions, NSP head on the pooled first token; loss = mean MLM NLL + mean
NSP NLL. Adam (Kingma & Ba) with bias correction, no weight decay, fp32.

Straightforward ``jax.numpy`` in float32 with every matmul at ``highest``
precision: no kernels, no fusion tricks. It imports nothing of the program.

Departures, each because the configuration states it:
  * Dropout 0.1 at the three published sites (after the embedding
    LayerNorm, after the attention output projection, after the FFN). The
    masks are part of the input: site ``i`` (counted from 1 in forward
    order) keeps an element where ``bernoulli(fold_in(key, i), 0.9)`` is
    true, ``key`` being the step's key argument. No dropout on the attention
    probabilities (the program's model has none).
  * Weights start from values rounded to bfloat16 (the fp32 masters of a
    bf16 model), see ``lib/weights.py``.

``precision`` selects the arithmetic of the matmuls: "float32" (the
reference), or the control: "int8" (both operands of every matmul rounded to
127 levels of their largest magnitude) or "fp8" (rounded to float8 e4m3 after
scaling the largest magnitude to 448), straight-through gradient.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def param_specs(cfg):
    d, f, v = cfg["units"], cfg["hidden_size"], cfg["vocab_size"]
    specs = [("word_embed_weight", (v, d)),
             ("token_type_embed_weight", (cfg["token_type_vocab_size"], d)),
             ("position_weight", (cfg["max_length"], d)),
             ("embed_ln_gamma", (d,)), ("embed_ln_beta", (d,))]
    for i in range(cfg["num_layers"]):
        p = "layer%d_" % i
        specs += [(p + "qkv_weight", (3 * d, d)), (p + "qkv_bias", (3 * d,)),
                  (p + "attn_out_weight", (d, d)), (p + "attn_out_bias", (d,)),
                  (p + "ln1_gamma", (d,)), (p + "ln1_beta", (d,)),
                  (p + "ffn_1_weight", (f, d)), (p + "ffn_1_bias", (f,)),
                  (p + "ffn_2_weight", (d, f)), (p + "ffn_2_bias", (d,)),
                  (p + "ln2_gamma", (d,)), (p + "ln2_beta", (d,))]
    specs += [("pooler_weight", (d, d)), ("pooler_bias", (d,)),
              ("mlm_transform_weight", (d, d)), ("mlm_transform_bias", (d,)),
              ("mlm_ln_gamma", (d,)), ("mlm_ln_beta", (d,)),
              ("decoder_bias", (v,)),
              ("nsp_weight", (2, d)), ("nsp_bias", (2,))]
    return specs


@jax.custom_vjp
def _round_int8(x):
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-30
    return jnp.round(x / scale) * scale


_round_int8.defvjp(lambda x: (_round_int8(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _round_fp8(x):
    scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    return (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale


_round_fp8.defvjp(lambda x: (_round_fp8(x), None), lambda _, g: (g,))


def _operand(x, precision):
    if precision == "int8":
        return _round_int8(x)
    if precision == "fp8":
        return _round_fp8(x)
    if precision != "float32":
        raise ValueError("unknown precision %r" % (precision,))
    return x


def dense(x, w, b, precision):
    """x (..., in) @ w(out, in)^T + b."""
    y = jnp.einsum("...i,oi->...o", _operand(x, precision),
                   _operand(w, precision), precision=HI)
    return y + b


def layer_norm(x, gamma, beta, eps=1e-12):
    m = jnp.mean(x, -1, keepdims=True)
    v = jnp.mean((x - m) ** 2, -1, keepdims=True)
    return (x - m) / jnp.sqrt(v + eps) * gamma + beta


def gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / jnp.sqrt(2.0)))


def _dropout(x, keep_mask, rate):
    if keep_mask is None:
        return x
    return jnp.where(keep_mask, x / (1.0 - rate), 0.0)


def dropout_masks(cfg, key, batch, seq):
    """The keep-masks of one step, site by site in forward order, for the
    whole batch; None where the configuration has no dropout."""
    rate = cfg.get("dropout", 0.0)
    if not rate:
        return None
    shape = (batch, seq, cfg["units"])
    n = 1 + 2 * cfg["num_layers"]
    return [jax.random.bernoulli(jax.random.fold_in(key, i + 1), 1.0 - rate,
                                 shape) for i in range(n)]


def loss_sum(p, cfg, batch, masks, precision="float32"):
    """Sum over the rows of ``batch`` of (mean MLM NLL of the row + NSP NLL of
    the row). The step's loss is this over the batch size. ``masks`` are the
    rows' slices of :func:`dropout_masks`."""
    tok, tt, vl, mp, mlm_y, nsp_y = batch
    rate = cfg.get("dropout", 0.0)
    b, t = tok.shape
    d, h = cfg["units"], cfg["num_heads"]
    mk = (lambda i: masks[i]) if masks is not None else (lambda i: None)
    x = p["word_embed_weight"][tok] + p["token_type_embed_weight"][tt] \
        + p["position_weight"][:t][None]
    x = layer_norm(x, p["embed_ln_gamma"], p["embed_ln_beta"])
    x = _dropout(x, mk(0), rate)
    valid = jnp.arange(t)[None, None, None, :] < vl[:, None, None, None]
    for i in range(cfg["num_layers"]):
        q = "layer%d_" % i
        qkv = dense(x, p[q + "qkv_weight"], p[q + "qkv_bias"], precision)
        qkv = qkv.reshape(b, t, 3, h, d // h)
        qh, kh, vh = (jnp.transpose(qkv[:, :, j], (0, 2, 1, 3))
                      for j in range(3))
        s = jnp.einsum("bhqd,bhkd->bhqk", _operand(qh, precision),
                       _operand(kh, precision), precision=HI)
        s = jnp.where(valid, s / jnp.sqrt(float(d // h)), -1e30)
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", _operand(a, precision),
                       _operand(vh, precision), precision=HI)
        o = jnp.transpose(o, (0, 2, 1, 3)).reshape(b, t, d)
        o = dense(o, p[q + "attn_out_weight"], p[q + "attn_out_bias"],
                  precision)
        x = layer_norm(x + _dropout(o, mk(1 + 2 * i), rate),
                       p[q + "ln1_gamma"], p[q + "ln1_beta"])
        f = dense(gelu(dense(x, p[q + "ffn_1_weight"], p[q + "ffn_1_bias"],
                             precision)),
                  p[q + "ffn_2_weight"], p[q + "ffn_2_bias"], precision)
        x = layer_norm(x + _dropout(f, mk(2 + 2 * i), rate),
                       p[q + "ln2_gamma"], p[q + "ln2_beta"])
    pooled = jnp.tanh(dense(x[:, 0], p["pooler_weight"], p["pooler_bias"],
                            precision))
    nsp_logits = dense(pooled, p["nsp_weight"], p["nsp_bias"], precision)
    nsp_nll = -jnp.take_along_axis(jax.nn.log_softmax(nsp_logits, -1),
                                   nsp_y[:, None], -1)[:, 0]
    hm = jnp.take_along_axis(x, mp[:, :, None], axis=1)          # (b, P, d)
    hm = layer_norm(gelu(dense(hm, p["mlm_transform_weight"],
                               p["mlm_transform_bias"], precision)),
                    p["mlm_ln_gamma"], p["mlm_ln_beta"])
    logits = dense(hm, p["word_embed_weight"], p["decoder_bias"], precision)
    mlm_nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                                   mlm_y[:, :, None], -1)[:, :, 0]
    return jnp.sum(jnp.mean(mlm_nll, axis=1)) + jnp.sum(nsp_nll)


def adam_init(params):
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"m": zeros, "v": jax.tree_util.tree_map(jnp.zeros_like, params)}


@jax.jit
def adam_update(params, grads, state, t, lr, beta1=0.9, beta2=0.999,
                eps=1e-8):
    tm = jax.tree_util.tree_map
    m = tm(lambda m_, g: beta1 * m_ + (1 - beta1) * g, state["m"], grads)
    v = tm(lambda v_, g: beta2 * v_ + (1 - beta2) * g * g, state["v"], grads)
    c1, c2 = 1 - beta1 ** t, 1 - beta2 ** t
    new = tm(lambda p_, m_, v_: p_ - lr * (m_ / c1) / (jnp.sqrt(v_ / c2) + eps),
             params, m, v)
    return new, {"m": m, "v": v}


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision",
                                             "row_block"))
def _grad_step(params, batch, key, cfg_items, precision, row_block):
    """Loss and gradient of one step, accumulated over blocks of rows (a
    scan, so that one block's program is compiled once) so that the fp32
    activations fit beside whatever else the device holds."""
    cfg = dict(cfg_items)
    b, t = batch[0].shape
    blocks = b // row_block
    masks = dropout_masks(cfg, key, b, t)
    split = lambda x: x.reshape((blocks, row_block) + x.shape[1:])
    xs = (tuple(split(x) for x in batch),
          None if masks is None else [split(m) for m in masks])
    vg = jax.value_and_grad(loss_sum)

    def block(carry, x):
        rows, mrows = x
        l, g = vg(params, cfg, rows, mrows, precision)
        return (carry[0] + l, jax.tree_util.tree_map(jnp.add, carry[1], g)), None

    zero = (jnp.float32(0.0), jax.tree_util.tree_map(jnp.zeros_like, params))
    (loss, grads), _ = jax.lax.scan(block, zero, xs)
    inv = 1.0 / b
    return loss * inv, jax.tree_util.tree_map(lambda g: g * inv, grads)


@jax.jit
def _norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in tree.items()}


def train(cfg, params, batches, keys, lr, precision="float32", row_block=8,
          keep=()):
    """Follows ``len(batches)`` steps from ``params`` (dict name -> array of
    any float type; upcast here), step ``i`` with ``keys[i]`` (raw uint32
    key data). Returns per step the loss, and per leaf the norm of the first
    gradient and the norm of the parameters' change after the last step; for
    the one-dimensional leaves (biases, gains) the two vectors themselves,
    and the first gradient itself for the leaves named in ``keep``."""
    params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    start = params
    state = adam_init(params)
    cfg_items = tuple(sorted((k, v) for k, v in cfg.items()
                             if isinstance(v, (int, float, str))))
    losses, first_grad = [], None
    for i, batch in enumerate(batches):
        batch = tuple(jnp.asarray(x) for x in batch)
        loss, grads = _grad_step(params, batch, jnp.asarray(keys[i]),
                                 cfg_items, precision,
                                 min(row_block, batch[0].shape[0]))
        if first_grad is None:
            first_grad = {k: float(v) for k, v in _norms(grads).items()}
            first_vec = {k: np.asarray(g) for k, g in grads.items()
                         if g.ndim == 1 or k in keep}
        params, state = adam_update(params, grads, state,
                                    jnp.float32(i + 1), jnp.float32(lr))
        losses.append(float(loss))
    change = {k: float(v) for k, v in _norms(jax.tree_util.tree_map(
        jnp.subtract, params, start)).items()}
    change_vec = {k: np.asarray(params[k] - start[k]) for k in first_vec
                  if params[k].ndim == 1}
    return {"losses": losses, "first_grad_norm": first_grad,
            "change_norm": change, "first_grad_vector": first_vec,
            "change_vector": change_vec}
