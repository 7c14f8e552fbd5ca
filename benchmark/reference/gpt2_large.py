"""Plain reference of the ``gpt2-large`` configuration: GPT-2 forward pass.

Radford et al. 2019: learned token and position embeddings, pre-LN decoder
blocks (LayerNorm eps 1e-5, causal multi-head attention, exact GELU FFN of
4x width), final LayerNorm, LM head tied to the token embedding.

Straightforward ``jax.numpy`` in float32 with every matmul at ``highest``
precision, one full forward over prompt + served tokens: no cache, no
batching, no kernels. It imports nothing of the program. Weights are the
bfloat16-rounded values of ``lib/weights.py``, upcast layer by layer.

``precision`` selects the arithmetic of the matmuls: "float32" (the
reference), or the control: "int8" (both operands of every matmul rounded to
127 levels of their largest magnitude per row / per output channel) or "fp8"
(rounded to float8 e4m3 after scaling that magnitude to 448).
"""
import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def param_specs(cfg):
    d, f, v = cfg["units"], cfg["hidden"], cfg["vocab_size"]
    specs = [("word_embed_weight", (v, d)),
             ("pos_embed_weight", (cfg["max_length"], d))]
    for i in range(cfg["num_layers"]):
        p = "layer%d_" % i
        specs += [(p + "ln1_gamma", (d,)), (p + "ln1_beta", (d,)),
                  (p + "attn_qkv_weight", (3 * d, d)),
                  (p + "attn_qkv_bias", (3 * d,)),
                  (p + "attn_attn_out_weight", (d, d)),
                  (p + "attn_attn_out_bias", (d,)),
                  (p + "ln2_gamma", (d,)), (p + "ln2_beta", (d,)),
                  (p + "ffn_1_weight", (f, d)), (p + "ffn_1_bias", (f,)),
                  (p + "ffn_2_weight", (d, f)), (p + "ffn_2_bias", (d,))]
    specs += [("ln_f_gamma", (d,)), ("ln_f_beta", (d,))]
    return specs


def _round_int8(x, axis=-1):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0 + 1e-30
    return jnp.round(x / scale) * scale


def _round_fp8(x, axis=-1):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0 + 1e-30
    return (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale


def _operand(x, precision):
    if precision == "int8":
        return _round_int8(x)
    if precision == "fp8":
        return _round_fp8(x)
    if precision != "float32":
        raise ValueError("unknown precision %r" % (precision,))
    return x


def dense(x, w, b, precision):
    y = jnp.einsum("...i,oi->...o", _operand(x, precision),
                   _operand(w, precision), precision=HI)
    return y + b


def layer_norm(x, gamma, beta, eps=1e-5):
    m = jnp.mean(x, -1, keepdims=True)
    v = jnp.mean((x - m) ** 2, -1, keepdims=True)
    return (x - m) / jnp.sqrt(v + eps) * gamma + beta


def gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / jnp.sqrt(2.0)))


@functools.partial(jax.jit, static_argnames=("heads", "precision"))
def _block(x, lp, heads, precision):
    t, d = x.shape
    lp = {k: v.astype(jnp.float32) for k, v in lp.items()}
    h = layer_norm(x, lp["ln1_gamma"], lp["ln1_beta"])
    qkv = dense(h, lp["attn_qkv_weight"], lp["attn_qkv_bias"], precision)
    qkv = qkv.reshape(t, 3, heads, d // heads)
    q, k, v = (jnp.transpose(qkv[:, j], (1, 0, 2)) for j in range(3))
    s = jnp.einsum("hqd,hkd->hqk", _operand(q, precision),
                   _operand(k, precision), precision=HI)
    causal = jnp.arange(t)[None, :, None] >= jnp.arange(t)[None, None, :]
    s = jnp.where(causal, s / jnp.sqrt(float(d // heads)), -1e30)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,hkd->hqd", _operand(a, precision),
                   _operand(v, precision), precision=HI)
    o = jnp.transpose(o, (1, 0, 2)).reshape(t, d)
    x = x + dense(o, lp["attn_attn_out_weight"], lp["attn_attn_out_bias"],
                  precision)
    h = layer_norm(x, lp["ln2_gamma"], lp["ln2_beta"])
    f = dense(gelu(dense(h, lp["ffn_1_weight"], lp["ffn_1_bias"], precision)),
              lp["ffn_2_weight"], lp["ffn_2_bias"], precision)
    return x + f


@functools.partial(jax.jit, static_argnames=("count", "precision"))
def _head(x, start, gamma, beta, table, count, precision):
    # the rows are cut here, at a traced start, so that one program serves
    # every request with ``count`` served tokens wherever its prompt ends
    x = jax.lax.dynamic_slice_in_dim(x, start, count)
    x = layer_norm(x, gamma.astype(jnp.float32), beta.astype(jnp.float32))
    return jnp.einsum("ti,vi->tv", _operand(x, precision),
                      _operand(table.astype(jnp.float32), precision),
                      precision=HI)


def logits(cfg, params, tokens, first, count, precision="float32"):
    """Logits (count, vocab) that predict tokens[first:first+count], i.e. at
    positions first-1 .. first+count-2, from one full forward over
    ``tokens``. ``tokens`` are padded by the caller to a length the jit has
    seen."""
    tokens = jnp.asarray(tokens, jnp.int32)
    t = tokens.shape[0]
    x = params["word_embed_weight"][tokens].astype(jnp.float32) \
        + params["pos_embed_weight"][:t].astype(jnp.float32)
    for i in range(cfg["num_layers"]):
        pre = "layer%d_" % i
        lp = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
        x = _block(x, lp, cfg["num_heads"], precision)
    return _head(x, first - 1, params["ln_f_gamma"], params["ln_f_beta"],
                 params["word_embed_weight"], count, precision)


def served_logits(cfg, params, prompt, served, precision="float32",
                  pad_to=128):
    """Logits (len(served), vocab) at the positions that produced each served
    token, from one full forward over prompt + served, padded at the end
    (under a causal mask padding cannot reach back) to a multiple of
    ``pad_to`` so that few shapes compile."""
    import numpy as np

    n0, n = len(prompt), len(served)
    toks = np.zeros(-(-(n0 + n) // pad_to) * pad_to, np.int32)
    toks[:n0] = prompt
    toks[n0:n0 + n] = served
    return logits(cfg, params, toks, n0, n, precision)
