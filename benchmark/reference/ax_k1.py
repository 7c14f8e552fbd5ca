"""Plain reference of the ``ax-k1`` configuration: one chip's share of
``skt/A.X-K1`` (``model_type`` ``axk1``, the DeepSeek-V3 lineage's keys), as
the configuration file cuts it.

The equations, from the published ``config.json``:

- ``RMS(x; g) = x * rsqrt(mean(x^2) + 1e-6) * g``; no biases anywhere;
  ``x_0 = E[token]``; layer: ``x <- x + Attn(RMS(x; g1))``, then ``x <- x +
  FFN(RMS(x; g2))``; ``logits = RMS(x_L; gf) Whead^T`` (untied head);
- latent attention, 64 heads: ``c_q = RMS(h Wqa; gq)`` (1536), ``q = c_q
  Wqb``, a head being ``[q_nope (128) | q_pe (64)]``; ``[c | k_pe] = h Wkva``
  (512 | 64), ``c_kv = RMS(c; gkv)``, ``k_pe`` ONE vector a position for all
  heads; ``[k_nope | v] = c_kv Wkvb``, a head (128 | 128); ``q_pe`` and
  ``k_pe`` rotated; ``s = (q_nope . k_nope + q_pe . k_pe) * scale``, causal
  softmax, ``o_h = sum p v``, output ``concat(o_h) Wo``;
- ``scale = mscale^2 / sqrt(192)`` with ``mscale = 0.1 * mscale_all_dim *
  ln(factor) + 1``;
- rotary with YaRN over the 64: ``f_i = theta^(-2i/64)``; ``low = floor(64
  ln(orig / (beta_fast 2 pi)) / (2 ln theta))``, ``high = ceil(... beta_slow
  ...)``; ``ramp_i = clip((i - low) / (high - low), 0, 1)``; ``inv_i = f_i /
  factor * ramp_i + f_i (1 - ramp_i)``; the factor on cos and sin is
  ``mscale(factor, mscale) / mscale(factor, mscale_all_dim) = 1``; pairs
  ``(x[2i], x[2i+1])`` (``assumed``);
- layers ``0 .. first_k_dense - 1``: ``FFN(h) = (silu(h Wg) * (h Wu)) Wd`` at
  ``dense_hidden``; the others: ``s = sigmoid(h Wr)`` over all experts in
  float32, the ``experts_per_token`` largest (``topk_method`` "none": no
  groups, no bias: ``assumed``), ``w_e = routed_scaling_factor * s_e / sum of
  the chosen s``, ``MoE(h) = sum_e w_e FFN_e(h) + FFN_shared(h)``.

The EXPANDED form only: K and V of every head are built from the latent for
the whole sequence, no cache, no absorbed product, nothing of the program.

Departures from the published model, each also in the configuration's
``departures``: (1) only the experts ``first_expert .. first_expert +
experts_held - 1`` are held: the routed sum runs over the chosen experts
among them; (2) embedding and head hold ``vocab_size`` rows, the deployment's
slice; (3) ``num_layers`` layers, the dense one and the expert layers of one
pipeline stage. Layouts, not arithmetic: ``Wkvb`` is stored as its two halves
head by head (``kv_b_k`` (64 x 128, 512), ``kv_b_v`` likewise), the experts'
matrices ``(held, f, d)`` (the down matrix transposed against the published
``(d, f)``), and the shared expert under the dense FFN's names.

Straightforward ``jax.numpy`` in float32 with every matmul at ``highest``
precision, one full forward over prompt + served tokens. Weights are the
bfloat16-rounded values of ``lib/weights.py``, upcast where they are used. So
that a request of 16,384 tokens fits beside 8.3 GB of bfloat16 weights it
works in blocks: the heads ``HEADS`` at a time, their query rows ``ROWS`` at a
time, the experts one at a time, the dense FFN ``FFN_ROWS`` rows at a time.

``precision``: "float32" (the reference), or the control, "int8" / "fp8":
both operands of every matmul rounded as in ``gpt2_large.py``. The router,
the softmax and the RMSNorms stay float32 in the control too: the
configuration states them so. "altered" is the control of a gross fault, not
a precision: the float32 logits with every position's best token moved to its
neighbour in the vocabulary.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
ROWS = 512
HEADS = 8
FFN_ROWS = 2048


def param_specs(cfg):
    d, v, h = cfg["units"], cfg["vocab_size"], cfg["num_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    f, held = cfg["expert_hidden"], cfg["experts_held"]
    specs = [("word_embed_weight", (v, d)), ("lm_head_weight", (v, d))]
    for i in range(cfg["num_layers"]):
        p = "layer%d_" % i
        dense = i < cfg["first_k_dense"]
        inner = cfg["dense_hidden"] if dense \
            else cfg["num_shared_experts"] * f
        specs += [(p + "ln1_gamma", (d,)),
                  (p + "attn_q_a_weight", (rq, d)),
                  (p + "attn_q_a_norm_gamma", (rq,)),
                  (p + "attn_q_b_weight", (h * (nope + rope), rq)),
                  (p + "attn_kv_a_weight", (rkv + rope, d)),
                  (p + "attn_kv_a_norm_gamma", (rkv,)),
                  (p + "attn_kv_b_k_weight", (h * nope, rkv)),
                  (p + "attn_kv_b_v_weight", (h * dv, rkv)),
                  (p + "attn_o_weight", (d, h * dv)),
                  (p + "ln2_gamma", (d,)),
                  (p + "ffn_gate_weight", (inner, d)),
                  (p + "ffn_up_weight", (inner, d)),
                  (p + "ffn_down_weight", (d, inner))]
        if not dense:
            specs += [(p + "router_weight", (cfg["num_experts"], d)),
                      (p + "experts_gate_weight", (held, f, d)),
                      (p + "experts_up_weight", (held, f, d)),
                      (p + "experts_down_weight", (held, f, d))]
    specs += [("ln_f_gamma", (d,))]
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


def matmul(x, w, precision):
    """``x (..., i) @ w (o, i)^T``, both operands in ``precision``."""
    return jnp.einsum("...i,oi->...o", _operand(x, precision),
                      _operand(w.astype(jnp.float32), precision),
                      precision=HI)


def rms_norm(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * gamma.astype(jnp.float32)


def yarn(cfg):
    """(inverse frequencies (rope / 2,), low, high, softmax scale)."""
    dim, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    factor, orig = float(cfg["rope_factor"]), cfg["original_max_length"]

    def pair(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair(cfg["beta_fast"])), 0)
    high = min(math.ceil(pair(cfg["beta_slow"])), dim - 1)
    f = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv = f / factor * ramp + f * (1 - ramp)
    mscale = 0.1 * cfg["mscale_all_dim"] * math.log(factor) + 1.0 \
        if factor > 1 else 1.0
    scale = mscale ** 2 / math.sqrt(cfg["qk_nope_head_dim"] + dim)
    return inv, low, high, scale


def rotate(x, positions, inv):
    """Rotary positions over the whole last axis of ``x`` (T, ..., D), row t
    at ``positions[t]``, interleaved pairs ``(x[2i], x[2i+1])`` turning by
    ``positions[t] * inv[i]``."""
    ang = positions.astype(jnp.float32).reshape(
        (-1,) + (1,) * (x.ndim - 1)) * jnp.asarray(inv, jnp.float32)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                      x1 * jnp.sin(ang) + x2 * jnp.cos(ang)],
                     axis=-1).reshape(x.shape)


def attention(cfg, h, lp, precision):
    """All heads, ``HEADS`` at a time, their queries ``ROWS`` rows at a
    time; K and V of a group of heads built whole from the latent."""
    t, nh = h.shape[0], cfg["num_heads"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    rank, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    inv, _low, _high, scale = yarn(cfg)
    hb = min(HEADS, nh)
    c_q = rms_norm(matmul(h, lp["attn_q_a_weight"], precision),
                   lp["attn_q_a_norm_gamma"], eps)
    kv = matmul(h, lp["attn_kv_a_weight"], precision)
    c_kv = rms_norm(kv[:, :rank], lp["attn_kv_a_norm_gamma"], eps)
    k_pe = rotate(kv[:, rank:], jnp.arange(t), inv)              # (t, rope)
    wq = lp["attn_q_b_weight"].reshape(nh // hb, hb * (nope + rope), -1)
    wk = lp["attn_kv_b_k_weight"].reshape(nh // hb, hb * nope, rank)
    wv = lp["attn_kv_b_v_weight"].reshape(nh // hb, hb * dv, rank)

    def heads(w):
        wq_g, wk_g, wv_g = w
        k_nope = matmul(c_kv, wk_g, precision).reshape(t, hb, nope)
        v = matmul(c_kv, wv_g, precision).reshape(t, hb, dv)

        def rows_block(start):
            rows = start + jnp.arange(ROWS)
            q = matmul(jax.lax.dynamic_slice_in_dim(c_q, start, ROWS), wq_g,
                       precision).reshape(ROWS, hb, nope + rope)
            q_nope, q_pe = q[..., :nope], rotate(q[..., nope:], rows, inv)
            s = jnp.einsum("qhd,khd->hqk", _operand(q_nope, precision),
                           _operand(k_nope, precision), precision=HI) \
                + jnp.einsum("qhd,kd->hqk", _operand(q_pe, precision),
                             _operand(k_pe, precision), precision=HI)
            seen = rows[:, None] >= jnp.arange(t)[None, :]
            a = jax.nn.softmax(jnp.where(seen[None], s * scale, -1e30),
                               axis=-1)
            o = jnp.einsum("hqk,khd->qhd", _operand(a, precision),
                           _operand(v, precision), precision=HI)
            return o.reshape(ROWS, hb * dv)

        return jax.lax.map(rows_block, jnp.arange(0, t, ROWS))

    out = jax.lax.map(heads, (wq, wk, wv))          # (nh/hb, t/R, R, hb*dv)
    out = jnp.transpose(out, (1, 2, 0, 3)).reshape(t, nh * dv)
    return matmul(out, lp["attn_o_weight"], precision)


def gated_ffn(h, wg, wu, wd, precision):
    """``(silu(h Wg^T) * (h Wu^T)) Wd^T`` with ``wd`` (d, f), ``FFN_ROWS``
    rows at a time where there are more."""
    def ffn(x):
        a = jax.nn.silu(matmul(x, wg, precision)) * matmul(x, wu, precision)
        return matmul(a, wd, precision)

    t = h.shape[0]
    if t <= FFN_ROWS or t % FFN_ROWS:
        return ffn(h)
    return jax.lax.map(ffn, h.reshape(t // FFN_ROWS, FFN_ROWS, -1)) \
        .reshape(h.shape)


def route(cfg, h, router_weight):
    """(weights (T, k), experts (T, k)) over ALL experts, float32."""
    s = jax.nn.sigmoid(jnp.einsum(
        "ti,ei->te", h, router_weight.astype(jnp.float32), precision=HI))
    score, expert = jax.lax.top_k(s, cfg["experts_per_token"])
    return cfg["routed_scaling_factor"] * score \
        / jnp.sum(score, -1, keepdims=True), expert


def moe(cfg, h, lp, precision):
    weight, expert = route(cfg, h, lp["router_weight"])
    first, held = cfg["first_expert"], cfg["experts_held"]

    def one_expert(e, acc):
        # the weight with which each token chose expert first + e (0: not)
        w_e = jnp.sum(jnp.where(expert == first + e, weight, 0.0), axis=-1)
        y = gated_ffn(h, lp["experts_gate_weight"][e],
                      lp["experts_up_weight"][e],
                      jnp.transpose(lp["experts_down_weight"][e]), precision)
        return acc + w_e[:, None] * y

    routed = jax.lax.fori_loop(0, held, one_expert, jnp.zeros_like(h))
    return routed + gated_ffn(h, lp["ffn_gate_weight"], lp["ffn_up_weight"],
                              lp["ffn_down_weight"], precision)


@functools.partial(jax.jit, static_argnames=("cfg", "dense", "precision"))
def _layer(x, lp, cfg, dense, precision):
    cfg = dict(cfg)
    eps = cfg["rms_norm_eps"]
    x = x + attention(cfg, rms_norm(x, lp["ln1_gamma"], eps), lp, precision)
    h = rms_norm(x, lp["ln2_gamma"], eps)
    if dense:
        return x + gated_ffn(h, lp["ffn_gate_weight"], lp["ffn_up_weight"],
                             lp["ffn_down_weight"], precision)
    return x + moe(cfg, h, lp, precision)


@functools.partial(jax.jit, static_argnames=("count", "eps", "precision"))
def _head(x, start, gamma, table, count, eps, precision):
    x = jax.lax.dynamic_slice_in_dim(x, start, count)
    return matmul(rms_norm(x, gamma, eps), table, precision)


def _static(cfg):
    """The sizes as a hashable jit argument."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in cfg.items()))


def hidden(cfg, params, tokens, precision="float32"):
    """The residual stream (T, d) after the last layer; ``T % ROWS == 0``."""
    x = params["word_embed_weight"][jnp.asarray(tokens, jnp.int32)] \
        .astype(jnp.float32)
    for i in range(cfg["num_layers"]):
        pre = "layer%d_" % i
        lp = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
        x = _layer(x, lp, _static(cfg), i < cfg["first_k_dense"], precision)
    return x


def logits(cfg, params, tokens, first, count, precision="float32"):
    """Logits (count, vocab) that predict tokens[first:first+count], from
    one full forward over ``tokens`` (padded by the caller to a multiple of
    ``ROWS``)."""
    x = hidden(cfg, params, tokens, precision)
    return _head(x, first - 1, params["ln_f_gamma"],
                 params["lm_head_weight"], count, cfg["rms_norm_eps"],
                 precision)


def served_logits(cfg, params, prompt, served, precision="float32",
                  pad_to=None):
    """Logits (len(served), vocab) at the positions that produced each
    served token, from one full forward over prompt + served, padded at the
    end (a causal mask keeps padding from reaching back, and the experts
    work a token at a time) to a multiple of ``pad_to`` (``FFN_ROWS``, which
    ``ROWS`` divides)."""
    pad_to = pad_to or max(FFN_ROWS, ROWS)
    n0, n = len(prompt), len(served)
    toks = np.zeros(-(-(n0 + n) // pad_to) * pad_to, np.int32)
    toks[:n0] = prompt
    toks[n0:n0 + n] = served
    if precision == "altered":
        return jnp.roll(logits(cfg, params, toks, n0, n, "float32"), 1, -1)
    return logits(cfg, params, toks, n0, n, precision)
