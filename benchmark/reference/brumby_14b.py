"""Plain reference of the ``brumby-14b`` configuration: the language model of
``manifestai/Brumby-14B-Base`` (``model_type`` ``brumby``, "power retention
layers"), as the configuration file cuts it.

The equations. From the published ``config.json``: d 5120, 40 query heads
and 8 K/V heads of width 128 (query head h reads K/V head ``h // 5``), inner
width 17408, vocabulary 151936 with an untied head, ``rms_norm_eps`` 1e-6,
``rope_theta`` 1e6, no biases, all layers alike. What the config has no key
for is ``assumed`` in the configuration's file and marked (*) here.

- ``RMS(x; w) = x / sqrt(mean(x^2) + 1e-6) * w``; ``x_0 = E[token]``;
  ``logits = RMS(x_L; wf) Whead^T``;
- layer: ``h = RMS(x; w1)``; ``q = Wq h``, ``k = Wk h``, ``v = Wv h``,
  ``g = Wg h`` (one a K/V head *);
- QK-norm (*): ``q <- RMS(q; wq)``, ``k <- RMS(k; wk)`` over the 128 of a
  head, one gain vector for all heads;
- rotary (* kept) over the whole head, half-split pairs ``(x[i], x[i + 64])``
  at angle ``position * 1e6 ** (-2i / 128)``;
- power retention of degree 2 (*; Gelada, Buckman, Zhang, Bach, "Scaling
  Context Requires Rethinking Attention", arXiv:2507.04239), per K/V head and
  in float32: ``log c_t = log sigmoid(g_t)``; for j <= i ``A_ij = (q_i . k_j /
  sqrt(128))^2 * exp(sum_{l=j+1..i} log c_l)``; ``o_i = sum_j A_ij v_j /
  (sum_j A_ij + 1e-6)``;
- ``x <- x + Wo concat(o)``; ``h2 = RMS(x; w2)``; ``x <- x + Wdown (silu(Wgate
  h2) * (Wup h2))``.

The quadratic form as it stands: ``A`` from a cumulative sum of ``log c``
over the whole sequence, no state, no chunks, nothing of the program.
Straightforward ``jax.numpy`` in float32 with every matmul at ``highest``
precision, one full forward over prompt + served tokens. Weights are the
bfloat16-rounded values of ``lib/weights.py``, upcast where they are used. So
that it fits beside 6.4 GB of bfloat16 weights it works in blocks: the K/V
heads one at a time, query rows ``ROWS`` at a time.

Departure from the published model, also in the configuration's
``departures``: ``num_layers`` layers of the 40.

``precision``: "float32" (the reference), or the control, "int8" / "fp8":
both operands of every matmul rounded as in ``gpt2_large.py``; the gates'
logarithm, their running sum, the squares and the quotient stay float32 in
the control too: the configuration states them so, and the control is the
nearest precision below the rest. "altered" is the control of a gross fault,
not a precision: the float32 logits with every position's best token moved
to its neighbour in the vocabulary.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
ROWS = 512
EPS = 1e-6


def param_specs(cfg):
    d, f, v = cfg["units"], cfg["hidden"], cfg["vocab_size"]
    dh = cfg["head_dim"]
    hq, hk = cfg["num_heads"] * dh, cfg["num_kv_heads"] * dh
    specs = [("word_embed_weight", (v, d))]
    for i in range(cfg["num_layers"]):
        p = "layer%d_" % i
        specs += [(p + "ln1_gamma", (d,)),
                  (p + "ret_q_weight", (hq, d)),
                  (p + "ret_k_weight", (hk, d)),
                  (p + "ret_v_weight", (hk, d)),
                  (p + "ret_g_weight", (cfg["num_kv_heads"], d)),
                  (p + "ret_o_weight", (d, hq)),
                  (p + "ret_q_norm_gamma", (dh,)),
                  (p + "ret_k_norm_gamma", (dh,)),
                  (p + "ln2_gamma", (d,)),
                  (p + "ffn_gate_weight", (f, d)),
                  (p + "ffn_up_weight", (f, d)),
                  (p + "ffn_down_weight", (d, f))]
    specs += [("ln_f_gamma", (d,)), ("lm_head_weight", (v, d))]
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
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * gamma.astype(jnp.float32)


def rotate(x, positions, theta):
    """Rotary positions over the whole last axis of ``x`` (T, D), row t at
    ``positions[t]``, half-split pairs ``(x[i], x[i + D/2])``."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    x1, x2 = x[:, :d // 2], x[:, d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def retention(cfg, h, lp, precision):
    """All heads, one K/V head at a time, its queries ``ROWS`` rows at a
    time against every key."""
    t = h.shape[0]
    dh, group = cfg["head_dim"], cfg["num_heads"] // cfg["num_kv_heads"]
    theta, eps = float(cfg["rope_theta"]), cfg["rms_norm_eps"]
    at = jnp.arange(t)
    wq = lp["ret_q_weight"].reshape(cfg["num_kv_heads"], group * dh, -1)
    wk = lp["ret_k_weight"].reshape(cfg["num_kv_heads"], dh, -1)
    wv = lp["ret_v_weight"].reshape(cfg["num_kv_heads"], dh, -1)
    wg = lp["ret_g_weight"].reshape(cfg["num_kv_heads"], 1, -1)

    def kv_head(w):
        wq_g, wk_g, wv_g, wg_g = w
        k = rotate(rms_norm(matmul(h, wk_g, precision),
                            lp["ret_k_norm_gamma"], eps), at, theta)
        v = matmul(h, wv_g, precision)
        # running sum of log c: key j reaches query i under exp(L_i - L_j)
        L = jnp.cumsum(jax.nn.log_sigmoid(matmul(h, wg_g, precision)[:, 0]))

        def rows_block(start):
            rows = start + jnp.arange(ROWS)
            hb = jax.lax.dynamic_slice_in_dim(h, start, ROWS)
            q = rms_norm(matmul(hb, wq_g, precision).reshape(ROWS, group, dh),
                         lp["ret_q_norm_gamma"], eps)
            q = jax.vmap(lambda one: rotate(one, rows, theta),
                         in_axes=1, out_axes=1)(q)
            s = jnp.einsum("qgd,kd->gqk", _operand(q, precision),
                           _operand(k, precision), precision=HI)
            gap = rows[:, None] - at[None, :]
            Lq = jax.lax.dynamic_slice_in_dim(L, start, ROWS)
            a = jnp.where(gap >= 0, jnp.exp(jnp.minimum(
                Lq[:, None] - L[None, :], 0.0)), 0.0)[None] * s * s / dh
            o = jnp.einsum("gqk,kd->qgd", _operand(a, precision),
                           _operand(v, precision), precision=HI)
            o = o / (jnp.sum(a, axis=-1).T[..., None] + EPS)
            return o.reshape(ROWS, group * dh)

        return jax.lax.map(rows_block, jnp.arange(0, t, ROWS))  # (t/R,R,G*dh)

    out = jax.lax.map(kv_head, (wq, wk, wv, wg))       # (Hkv, t/R, R, G*dh)
    out = jnp.transpose(out, (1, 2, 0, 3)).reshape(t, -1)
    return matmul(out, lp["ret_o_weight"], precision)


def gated_ffn(h, wg, wu, wd, precision):
    a = jax.nn.silu(matmul(h, wg, precision)) * matmul(h, wu, precision)
    return matmul(a, wd, precision)


@functools.partial(jax.jit, static_argnames=("cfg", "precision"))
def _layer(x, lp, cfg, precision):
    cfg = dict(cfg)
    eps = cfg["rms_norm_eps"]
    x = x + retention(cfg, rms_norm(x, lp["ln1_gamma"], eps), lp, precision)
    return x + gated_ffn(rms_norm(x, lp["ln2_gamma"], eps),
                         lp["ffn_gate_weight"], lp["ffn_up_weight"],
                         lp["ffn_down_weight"], precision)


@functools.partial(jax.jit, static_argnames=("count", "eps", "precision"))
def _head(x, start, gamma, table, count, eps, precision):
    x = jax.lax.dynamic_slice_in_dim(x, start, count)
    return matmul(rms_norm(x, gamma, eps), table, precision)


def _static(cfg):
    """The sizes as a hashable jit argument."""
    return tuple(sorted(cfg.items()))


def hidden(cfg, params, tokens, precision="float32"):
    """The residual stream (T, d) after the last layer; ``T % ROWS == 0``."""
    x = params["word_embed_weight"][jnp.asarray(tokens, jnp.int32)] \
        .astype(jnp.float32)
    for i in range(cfg["num_layers"]):
        pre = "layer%d_" % i
        lp = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
        x = _layer(x, lp, _static(cfg), precision)
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
                  pad_to=2 * ROWS):
    """Logits (len(served), vocab) at the positions that produced each
    served token, from one full forward over prompt + served, padded at the
    end (key j reaches query i only for j <= i, so padding never reaches
    back) to a multiple of ``pad_to``."""
    n0, n = len(prompt), len(served)
    toks = np.zeros(-(-(n0 + n) // pad_to) * pad_to, np.int32)
    toks[:n0] = prompt
    toks[n0:n0 + n] = served
    if precision == "altered":
        return jnp.roll(logits(cfg, params, toks, n0, n, "float32"), 1, -1)
    return logits(cfg, params, toks, n0, n, precision)
