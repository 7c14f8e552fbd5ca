"""Plain reference of the ``command-a-plus`` configuration: one chip's share
of the language model of ``CohereLabs/command-a-plus-05-2026``
(``model_type`` ``cohere2_moe``), as the configuration file cuts it.

The equations, from the published ``config.json``:

- embedding ``x = E[token]``; head ``logits = LN_f(x) E^T`` (tied,
  ``logit_scale`` 1); LayerNorm with mean subtraction and a gain, no shift,
  eps 1e-5; no biases anywhere, no QK-norm;
- layer l, parallel block: ``h = LN_l(x)``; ``x <- x + Attn_l(h) + MoE_l(h)``;
- attention: 128 query heads and 8 K/V heads of width 128 (query head h
  reads K/V head ``h // 16``), scale ``1/sqrt(128)``; layers of type
  ``sliding_attention`` rotate q and k over the whole head (theta 50000,
  interleaved pairs ``(x[2i], x[2i+1])``) and let query i see key j iff
  ``0 <= i - j < sliding_window``; ``full_attention`` layers have no
  positional encoding and a causal mask;
- MoE: ``s = sigmoid(h Wr)`` over all 128 experts in float32, the 8 largest,
  ``w_e = s_e / sum of the chosen s``; ``FFN(h) = (silu(h Wg) * (h Wu)) Wd``;
  routed ``= sum_e w_e FFN_e(h)``; shared ``= mean of the 4 shared FFNs``;
  ``MoE(h) = routed + shared``.

Departures from the published model, each also in the configuration's
``departures``: (1) only the experts ``first_expert .. first_expert +
experts_held - 1`` are held: the routed sum runs over the chosen experts
among them (the part this chip contributes before the deployment's sum
across chips); (2) the embedding holds ``vocab_size`` rows, the deployment's
slice of the tied table, and the logits run over those rows; (3)
``num_layers`` layers, one whole period of ``layer_types``; (4) the vision
tower is not part of the configuration.

Straightforward ``jax.numpy`` in float32 with every matmul at ``highest``
precision, one full forward over prompt + served tokens: no cache, no
kernels, nothing of the program. Weights are the bfloat16-rounded values of
``lib/weights.py``, upcast where they are used. So that it fits beside 9.5
GB of bfloat16 weights it works in blocks: the experts one at a time, the
K/V heads one at a time, query rows ``ROWS`` at a time. The expert matrices
are stored ``(held, f, d)`` (the down matrix transposed against the
published ``(d, f)``) and the shared experts' side by side ``(4 f, d)``;
both are layouts, not arithmetic.

``precision``: "float32" (the reference), or the control, "int8" / "fp8":
both operands of every matmul rounded as in ``gpt2_large.py``. The router
stays float32 in the control too: the configuration states it so, and the
control is the nearest precision below the rest. "altered" is the control of
a gross fault, not a precision: the float32 logits with every position's
best token moved to its neighbour in the vocabulary, so that the comparison
reads what a served token that is simply another token reads.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
ROWS = 512
SLIDING = "sliding_attention"


def param_specs(cfg):
    d, f, v = cfg["units"], cfg["expert_hidden"], cfg["vocab_size"]
    hq = cfg["num_heads"] * cfg["head_dim"]
    hk = cfg["num_kv_heads"] * cfg["head_dim"]
    held, ns = cfg["experts_held"], cfg["num_shared_experts"]
    specs = [("word_embed_weight", (v, d))]
    for i in range(cfg["num_layers"]):
        p = "layer%d_" % i
        specs += [(p + "ln_gamma", (d,)),
                  (p + "attn_q_weight", (hq, d)),
                  (p + "attn_k_weight", (hk, d)),
                  (p + "attn_v_weight", (hk, d)),
                  (p + "attn_o_weight", (d, hq)),
                  (p + "router_weight", (cfg["num_experts"], d)),
                  (p + "experts_gate_weight", (held, f, d)),
                  (p + "experts_up_weight", (held, f, d)),
                  (p + "experts_down_weight", (held, f, d)),
                  (p + "shared_gate_weight", (ns * f, d)),
                  (p + "shared_up_weight", (ns * f, d)),
                  (p + "shared_down_weight", (d, ns * f))]
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


def layer_norm(x, gamma, eps):
    m = jnp.mean(x, -1, keepdims=True)
    v = jnp.mean((x - m) ** 2, -1, keepdims=True)
    return (x - m) / jnp.sqrt(v + eps) * gamma.astype(jnp.float32)


def rotate(x, positions, theta):
    """Rotary positions over the whole last axis of ``x`` (T, D), row t at
    ``positions[t]``, interleaved pairs ``(x[2i], x[2i+1])``."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    x1, x2 = x[:, 0::2], x[:, 1::2]
    return jnp.stack([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                      x1 * jnp.sin(ang) + x2 * jnp.cos(ang)],
                     axis=-1).reshape(x.shape)


def visible(rows, t, window):
    """(len(rows), t) mask: key j visible to the query at position i iff
    ``0 <= i - j`` and, under a window, ``i - j < window``."""
    gap = rows[:, None] - jnp.arange(t)[None, :]
    return (gap >= 0) if window is None else (gap >= 0) & (gap < window)


def attention(cfg, h, lp, window, precision):
    """All heads, one K/V head at a time, its queries ``ROWS`` rows at a
    time."""
    t = h.shape[0]
    dh, group = cfg["head_dim"], cfg["num_heads"] // cfg["num_kv_heads"]
    theta = float(cfg["rope_theta"])
    wq = lp["attn_q_weight"].reshape(cfg["num_kv_heads"], group * dh, -1)
    wk = lp["attn_k_weight"].reshape(cfg["num_kv_heads"], dh, -1)
    wv = lp["attn_v_weight"].reshape(cfg["num_kv_heads"], dh, -1)

    def kv_head(w):
        wq_g, wk_g, wv_g = w
        k, v = matmul(h, wk_g, precision), matmul(h, wv_g, precision)
        if window is not None:
            k = rotate(k, jnp.arange(t), theta)

        def rows_block(start):
            rows = start + jnp.arange(ROWS)
            hb = jax.lax.dynamic_slice_in_dim(h, start, ROWS)
            q = matmul(hb, wq_g, precision).reshape(ROWS, group, dh)
            if window is not None:
                q = jax.vmap(lambda one: rotate(one, rows, theta),
                             in_axes=1, out_axes=1)(q)
            s = jnp.einsum("qgd,kd->gqk", _operand(q, precision),
                           _operand(k, precision), precision=HI)
            s = jnp.where(visible(rows, t, window)[None],
                          s / jnp.sqrt(float(dh)), -1e30)
            a = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("gqk,kd->qgd", _operand(a, precision),
                           _operand(v, precision), precision=HI)
            return o.reshape(ROWS, group * dh)

        return jax.lax.map(rows_block, jnp.arange(0, t, ROWS))  # (t/R,R,G*dh)

    out = jax.lax.map(kv_head, (wq, wk, wv))           # (Hkv, t/R, R, G*dh)
    out = jnp.transpose(out, (1, 2, 0, 3)).reshape(t, -1)
    return matmul(out, lp["attn_o_weight"], precision)


def route(cfg, h, router_weight):
    """(weights (T, k), experts (T, k)) over ALL experts, float32."""
    s = jax.nn.sigmoid(jnp.einsum(
        "ti,ei->te", h, router_weight.astype(jnp.float32), precision=HI))
    score, expert = jax.lax.top_k(s, cfg["experts_per_token"])
    return score / jnp.sum(score, -1, keepdims=True), expert


def gated_ffn(h, wg, wu, wd_t, precision):
    """``(silu(h Wg^T) * (h Wu^T)) Wd`` with ``wd_t`` stored (f, d)."""
    a = jax.nn.silu(matmul(h, wg, precision)) * matmul(h, wu, precision)
    return matmul(a, jnp.transpose(wd_t), precision)


def moe(cfg, h, lp, precision):
    weight, expert = route(cfg, h, lp["router_weight"])
    first, held = cfg["first_expert"], cfg["experts_held"]

    def one_expert(e, acc):
        # the weight with which each token chose expert first + e (0: not)
        w_e = jnp.sum(jnp.where(expert == first + e, weight, 0.0), axis=-1)
        y = gated_ffn(h, lp["experts_gate_weight"][e],
                      lp["experts_up_weight"][e],
                      lp["experts_down_weight"][e], precision)
        return acc + w_e[:, None] * y

    routed = jax.lax.fori_loop(0, held, one_expert, jnp.zeros_like(h))
    ns, f = cfg["num_shared_experts"], cfg["expert_hidden"]
    shared = jnp.zeros_like(h)
    for s in range(ns):
        rows = slice(s * f, (s + 1) * f)
        shared = shared + gated_ffn(
            h, lp["shared_gate_weight"][rows], lp["shared_up_weight"][rows],
            jnp.transpose(lp["shared_down_weight"][:, rows]), precision)
    return routed + shared / ns


@functools.partial(jax.jit, static_argnames=("cfg", "window", "precision"))
def _layer(x, lp, cfg, window, precision):
    cfg = dict(cfg)
    h = layer_norm(x, lp["ln_gamma"], cfg["layer_norm_eps"])
    return x + attention(cfg, h, lp, window, precision) \
        + moe(cfg, h, lp, precision)


@functools.partial(jax.jit, static_argnames=("count", "eps", "precision"))
def _head(x, start, gamma, table, count, eps, precision):
    x = jax.lax.dynamic_slice_in_dim(x, start, count)
    return matmul(layer_norm(x, gamma, eps), table, precision)


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
        window = int(cfg["sliding_window"]) \
            if cfg["layer_types"][i] == SLIDING else None
        x = _layer(x, lp, _static(cfg), window, precision)
    return x


def logits(cfg, params, tokens, first, count, precision="float32"):
    """Logits (count, vocab) that predict tokens[first:first+count], from
    one full forward over ``tokens`` (padded by the caller to a multiple of
    ``ROWS``)."""
    x = hidden(cfg, params, tokens, precision)
    return _head(x, first - 1, params["ln_f_gamma"],
                 params["word_embed_weight"], count,
                 cfg["layer_norm_eps"], precision)


def served_logits(cfg, params, prompt, served, precision="float32",
                  pad_to=2 * ROWS):
    """Logits (len(served), vocab) at the positions that produced each
    served token, from one full forward over prompt + served, padded at the
    end (a causal mask keeps padding from reaching back, and the experts
    work a token at a time) to a multiple of ``pad_to``."""
    n0, n = len(prompt), len(served)
    toks = np.zeros(-(-(n0 + n) // pad_to) * pad_to, np.int32)
    toks[:n0] = prompt
    toks[n0:n0 + n] = served
    if precision == "altered":
        return jnp.roll(logits(cfg, params, toks, n0, n, "float32"), 1, -1)
    return logits(cfg, params, toks, n0, n, precision)
