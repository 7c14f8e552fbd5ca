"""Plain reference of the ``nemotron-3-super`` configuration: one chip's share
of ``nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16`` (``model_type``
``nemotron_h``), as the configuration file cuts it.

The equations, from the published ``config.json`` (``assumed`` in the
configuration says where a value is read, not given):

- ``RMS(x; g) = x * rsqrt(mean(x^2) + 1e-5) * g``; no biases but the conv's
  and the router's selection bias; ``x_0 = E[token]``; one layer a letter of
  the pattern, each ``x <- x + Mixer(RMS(x; g))``; ``logits = RMS(x_L; g_f)
  W_head^T`` (untied head);
- **M** (Mamba-2): ``[z | xBC | dt] = h W_in`` (8192 | 10240 | 128);
  ``xBC <- silu(conv4(xBC) + b_conv)``, a depthwise causal conv of width 4;
  split into ``x`` (128 heads x 64), ``B``, ``C`` (8 groups x 128), head h
  reading group ``h // 16``; ``dt = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``; ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`` (64 x 128 a
  head, float32); ``y_t = S_t C_t + D x_t``; ``y <- RMS_group(y * silu(z);
  g)`` with the statistics over groups of 1024 (8192 / n_groups); out ``y
  W_out``;
- **\\*** GQA: 32 query heads on 2 K/V heads of 128, causal softmax at scale
  ``1 / sqrt(128)``, no rotary, out ``W_o``;
- **E** LatentMoE: ``s = sigmoid(h W_r^T)`` over all 512 experts in
  float32; the top 22 of ``s + e_score_correction_bias``; ``w_e = 5 s_e /
  sum of the chosen s``; ``l = h W_fc1`` (1024); ``MoE(h) = (sum_e w_e
  relu(l W_up,e^T)^2 W_down,e^T) W_fc2 + relu(h W_su^T)^2 W_sd^T`` (shared
  width 5376, reading h).

The state-space layer runs as the SEQUENTIAL recurrence over positions, one
position at a time, not the chunked form the program's prefill uses, and
nothing of the program (no cache, no kernel) is imported.

Departures from the published model, each also in the configuration's
``departures``: (1) only the experts ``first_expert .. first_expert +
experts_held - 1`` are held: the routed sum runs over the chosen experts
among them; (2) embedding and head hold ``vocab_size`` rows, the
deployment's slice; (3) the pattern's layers are one pipeline stage of 11;
(4) no multi-token prediction module; (5) ``A_log`` and ``dt_bias`` are
seeded like every leaf, N(0, 0.02^2), so that the decay ``exp(dt A)`` lies
between about 0.1 and 0.8 a token and the state holds the last few tokens
only (the trained values keep it far longer). Layouts, not arithmetic: the conv's
weight is (channels, 4), the experts' matrices ``(held, f, latent)`` (the
down matrix transposed against the published ``(latent, f)``).

Straightforward ``jax.numpy`` in float32 with every matmul at ``highest``
precision, one full forward over prompt + served tokens. Weights are the
bfloat16-rounded values of ``lib/weights.py``, upcast matmul by matmul, so
that the reference fits beside 9.3 GB of bfloat16 weights: attention ``ROWS``
query rows at a time, the experts one at a time, the FFNs ``FFN_ROWS`` rows
at a time.

``precision``: "float32" (the reference), or the control, "int8" / "fp8":
both operands of every matmul rounded as in ``ax_k1.py``. The router, the
recurrence, the softmax and the RMSNorms stay float32 in the control too:
the configuration states them so. "bf16_state" is the control of the
recurrence alone: the matmuls in float32, and the state and the operands of
its update (the decay, ``dt x`` and ``B``) rounded to bfloat16 at every
position, as a state kept in bfloat16 would be. "altered" is the control
of a gross fault, not a precision: the float32 logits with every position's
best token moved to its neighbour in the vocabulary.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
ROWS = 512
FFN_ROWS = 2048


def param_specs(cfg):
    d, v = cfg["units"], cfg["vocab_size"]
    H, P, G, N = (cfg["mamba_heads"], cfg["mamba_head_dim"], cfg["n_groups"],
                  cfg["state"])
    inner = H * P
    channels = inner + 2 * G * N
    nh, nkv, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    E, held = cfg["num_experts"], cfg["experts_held"]
    lat, f, fs = cfg["moe_latent"], cfg["expert_hidden"], cfg["shared_hidden"]
    specs = [("word_embed_weight", (v, d)), ("lm_head_weight", (v, d))]
    for i, kind in enumerate(cfg["pattern"]):
        p = "layer%d_" % i
        specs.append((p + "norm_gamma", (d,)))
        if kind == "M":
            specs += [(p + "mixer_in_proj_weight", (inner + channels + H, d)),
                      (p + "mixer_conv_weight", (channels, cfg["conv"])),
                      (p + "mixer_conv_bias", (channels,)),
                      (p + "mixer_dt_bias", (H,)),
                      (p + "mixer_A_log", (H,)),
                      (p + "mixer_D", (H,)),
                      (p + "mixer_norm_gamma", (inner,)),
                      (p + "mixer_out_proj_weight", (d, inner))]
        elif kind == "*":
            specs += [(p + "attn_q_weight", (nh * hd, d)),
                      (p + "attn_k_weight", (nkv * hd, d)),
                      (p + "attn_v_weight", (nkv * hd, d)),
                      (p + "attn_o_weight", (d, nh * hd))]
        else:
            specs += [(p + "moe_router_weight", (E, d)),
                      (p + "moe_router_bias", (E,)),
                      (p + "moe_fc1_weight", (lat, d)),
                      (p + "moe_fc2_weight", (d, lat)),
                      (p + "moe_experts_up_weight", (held, f, lat)),
                      (p + "moe_experts_down_weight", (held, f, lat)),
                      (p + "moe_shared_up_weight", (fs, d)),
                      (p + "moe_shared_down_weight", (d, fs))]
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
    if precision not in ("float32", "bf16_state"):
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


def silu(x):
    return x * jax.nn.sigmoid(x)


def mamba(cfg, h, lp, precision):
    """The Mamba-2 mixer over the whole sequence, its recurrence one
    position at a time."""
    T = h.shape[0]
    H, P, G, N = (cfg["mamba_heads"], cfg["mamba_head_dim"], cfg["n_groups"],
                  cfg["state"])
    inner, K = H * P, cfg["conv"]
    proj = matmul(h, lp["mixer_in_proj_weight"], precision)
    z, xbc, dt = (proj[:, :inner], proj[:, inner:2 * inner + 2 * G * N],
                  proj[:, 2 * inner + 2 * G * N:])
    w = lp["mixer_conv_weight"].astype(jnp.float32)
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc])
    xbc = silu(lp["mixer_conv_bias"].astype(jnp.float32) + sum(
        padded[k:k + T] * w[:, k] for k in range(K)))
    x = xbc[:, :inner].reshape(T, H, P)
    B = xbc[:, inner:inner + G * N].reshape(T, G, N)
    C = xbc[:, inner + G * N:].reshape(T, G, N)
    dt = jax.nn.softplus(dt + lp["mixer_dt_bias"].astype(jnp.float32))
    A = -jnp.exp(lp["mixer_A_log"].astype(jnp.float32))
    if precision == "bf16_state":
        low = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
    else:
        low = lambda a: a

    def step(S, at):
        x_t, dt_t, B_t, C_t = at
        B_h = jnp.repeat(B_t, H // G, axis=0)                   # (H, N)
        C_h = jnp.repeat(C_t, H // G, axis=0)
        S = low(low(jnp.exp(dt_t * A))[:, None, None] * S
                + low(dt_t[:, None] * x_t)[:, :, None]
                * low(B_h)[:, None, :])
        return S, jnp.einsum("hpn,hn->hp", S, C_h, precision=HI)

    _S, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                         (x, dt, B, C))
    y = y + lp["mixer_D"].astype(jnp.float32)[:, None] * x
    g = (y.reshape(T, inner) * silu(z)).reshape(T, G, inner // G)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True)
                          + cfg["rms_norm_eps"])
    y = g.reshape(T, inner) * lp["mixer_norm_gamma"].astype(jnp.float32)
    return matmul(y, lp["mixer_out_proj_weight"], precision)


def attention(cfg, h, lp, precision):
    """Grouped heads, no rotary; the queries ``ROWS`` rows at a time."""
    T = h.shape[0]
    nh, nkv, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    q = matmul(h, lp["attn_q_weight"], precision).reshape(T, nh, hd)
    k = jnp.repeat(matmul(h, lp["attn_k_weight"], precision)
                   .reshape(T, nkv, hd), nh // nkv, axis=1)
    v = jnp.repeat(matmul(h, lp["attn_v_weight"], precision)
                   .reshape(T, nkv, hd), nh // nkv, axis=1)

    def rows_block(start):
        rows = start + jnp.arange(ROWS)
        qb = jax.lax.dynamic_slice_in_dim(q, start, ROWS)
        s = jnp.einsum("qhd,khd->hqk", _operand(qb, precision),
                       _operand(k, precision), precision=HI) / np.sqrt(hd)
        seen = rows[:, None] >= jnp.arange(T)[None, :]
        a = jax.nn.softmax(jnp.where(seen[None], s, -1e30), axis=-1)
        return jnp.einsum("hqk,khd->qhd", _operand(a, precision),
                          _operand(v, precision), precision=HI)

    out = jax.lax.map(rows_block, jnp.arange(0, T, ROWS)).reshape(T, nh * hd)
    return matmul(out, lp["attn_o_weight"], precision)


def relu2_ffn(h, w_up, w_down, precision):
    """``relu(h Wu^T)^2 Wd^T`` with ``w_down`` (d, f), ``FFN_ROWS`` rows at
    a time where there are more."""
    def ffn(x):
        return matmul(jnp.square(jax.nn.relu(matmul(x, w_up, precision))),
                      w_down, precision)

    t = h.shape[0]
    if t <= FFN_ROWS or t % FFN_ROWS:
        return ffn(h)
    return jax.lax.map(ffn, h.reshape(t // FFN_ROWS, FFN_ROWS, -1)) \
        .reshape(t, -1)


def route(cfg, h, router_weight, bias):
    """(weights (T, k), experts (T, k)) over ALL experts, float32: chosen
    by the scores plus the selection bias, weighed by the scores alone."""
    s = jax.nn.sigmoid(jnp.einsum(
        "ti,ei->te", h, router_weight.astype(jnp.float32), precision=HI))
    _biased, expert = jax.lax.top_k(s + bias.astype(jnp.float32),
                                    cfg["experts_per_token"])
    score = jnp.take_along_axis(s, expert, axis=-1)
    return cfg["routed_scaling_factor"] * score \
        / jnp.sum(score, -1, keepdims=True), expert


def moe(cfg, h, lp, precision):
    weight, expert = route(cfg, h, lp["moe_router_weight"],
                           lp["moe_router_bias"])
    first, held = cfg["first_expert"], cfg["experts_held"]
    latent = matmul(h, lp["moe_fc1_weight"], precision)

    def one_expert(e, acc):
        # the weight with which each token chose expert first + e (0: not)
        w_e = jnp.sum(jnp.where(expert == first + e, weight, 0.0), axis=-1)
        y = relu2_ffn(latent, lp["moe_experts_up_weight"][e],
                      jnp.transpose(lp["moe_experts_down_weight"][e]),
                      precision)
        return acc + w_e[:, None] * y

    routed = jax.lax.fori_loop(0, held, one_expert, jnp.zeros_like(latent))
    return matmul(routed, lp["moe_fc2_weight"], precision) \
        + relu2_ffn(h, lp["moe_shared_up_weight"],
                    lp["moe_shared_down_weight"], precision)


@functools.partial(jax.jit, static_argnames=("cfg", "kind", "precision"))
def _layer(x, lp, cfg, kind, precision):
    cfg = dict(cfg)
    h = rms_norm(x, lp["norm_gamma"], cfg["rms_norm_eps"])
    mixer = {"M": mamba, "*": attention, "E": moe}[kind]
    return x + mixer(cfg, h, lp, precision)


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
    for i, kind in enumerate(cfg["pattern"]):
        pre = "layer%d_" % i
        lp = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
        x = _layer(x, lp, _static(cfg), kind, precision)
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
    end (every layer is causal: padding never reaches back) to a multiple
    of ``pad_to`` (``FFN_ROWS``, which ``ROWS`` divides)."""
    pad_to = pad_to or max(FFN_ROWS, ROWS)
    n0, n = len(prompt), len(served)
    toks = np.zeros(-(-(n0 + n) // pad_to) * pad_to, np.int32)
    toks[:n0] = prompt
    toks[n0:n0 + n] = served
    if precision == "altered":
        return jnp.roll(logits(cfg, params, toks, n0, n, "float32"), 1, -1)
    return logits(cfg, params, toks, n0, n, precision)
