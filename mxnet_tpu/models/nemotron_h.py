"""Nemotron-H style hybrid decoder (``model_type`` ``nemotron_h``): Mamba-2
state-space mixers beside grouped-query attention, with latent experts, one
chip's share of an expert-parallel model.

The layers follow ``hybrid_override_pattern``, one letter a layer: ``M`` a
Mamba-2 mixer, ``*`` attention, ``E`` a mixture of experts (``-`` a dense
FFN is not built). Each layer is ONE mixer or ONE FFN, pre-norm:
``x <- x + Mixer(RMS(x))``; ``logits = RMS(x; g_f) W_head^T`` (untied).
RMS eps ``rms_norm_eps``; no biases but the conv's and the router's
selection bias.

- **M** (``F.mamba_mix``, ``ops/ssm.py``): ``[z | xBC | dt] = h W_in``
  (widths ``heads * head_dim``, that plus ``2 * groups * state``,
  ``heads``); ``xBC <- silu(causal_depthwise_conv(xBC) + b_conv)``, split
  into ``x`` (heads x head_dim), ``B`` and ``C`` (groups x state), head h
  reading group ``h // (heads / groups)``; ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)`` a head; ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t
  B_t^T`` (head_dim x state a head, float32); ``y_t = S_t C_t + D x_t``;
  ``y <- RMS_group(y * silu(z); g)`` by ``groups`` groups; out ``y W_out``.
  The prefill runs the chunked SSD scan (``chunk`` tokens), the decode step
  one token against the state (on a TPU the Pallas kernel ``ssd_step``).
- **\\*** GQA: ``num_heads`` query heads on ``num_kv_heads`` K/V heads of
  ``head_dim``, causal softmax at scale ``1 / sqrt(head_dim)``, NO rotary
  (Nemotron-H's attention carries no position embedding), output ``W_o``.
- **E** LatentMoE: ``s = sigmoid(h W_r^T)`` over all ``num_experts``
  (float32); the ``experts_per_token`` largest of ``s + bias`` chosen;
  ``w_e = routed_scaling_factor * s_e / sum of the chosen s``; ``l = h
  W_fc1`` (``moe_latent`` wide); ``MoE(h) = (sum_e w_e relu(l W_up,e^T)^2
  W_down,e^T) W_fc2 + relu(h W_su^T)^2 W_sd^T``: the router and the shared
  expert read ``h``, only the routed experts work in the latent.

**The share** is ``cohere_moe.py``'s: the model holds the experts
``first_expert .. first_expert + experts_held - 1``, routes over all and
computes its own experts' part (``F.expert_ffn``); the rest is whole.

**Served state.** Only the mixers keep a cache, so cache layer i is the i-th
``M`` or ``*`` layer; an ``E`` layer holds nothing. ``decode_state_spec()``
gives the cache a layout: an ``SSMPage`` (the conv's last ``conv - 1``
inputs and the state, a snapshot of fixed size a slot) for each ``M``, a
``PlainPage`` of ``num_kv_heads`` K/V heads for each ``*``. The prefill
hands over ``(conv, S)`` or ``(K, V)`` a cache layer. The spec names neither
``int8_pages`` nor ``multi_token`` (``decode_step`` takes one token a slot:
a window of more would need the state after every row of it), so the server
refuses ``quantize``, ``draft`` and ``prefill_chunk`` by name.
"""
from __future__ import annotations

import numpy as np

from .. import initializer as init_mod
from ..gluon import nn
from ..gluon.block import HybridBlock, param_value
from ..serve.kv_cache import PlainPage, SSMPage
from .brumby import _RMSNorm, _dense
from .cohere_moe import _GroupedAttention

__all__ = ["NemotronHModel", "nemotron_h_nano"]


class _Mamba(HybridBlock):
    def __init__(self, units, heads, head_dim, groups, state, conv, chunk,
                 eps, **kwargs):
        super().__init__(**kwargs)
        self._geo = dict(heads=heads, head_dim=head_dim, groups=groups,
                         state_size=state, eps=eps, chunk=chunk)
        inner = heads * head_dim
        channels = inner + 2 * groups * state
        normal = init_mod.Normal(0.02)
        with self.name_scope():
            self.in_proj = _dense(inner + channels + heads, units,
                                  "in_proj_")
            self.conv_weight = self.params.get(
                "conv_weight", shape=(channels, conv), init=normal)
            self.conv_bias = self.params.get(
                "conv_bias", shape=(channels,), init="zeros")
            self.dt_bias = self.params.get("dt_bias", shape=(heads,),
                                           init="zeros")
            self.A_log = self.params.get("A_log", shape=(heads,),
                                         init="zeros")
            self.D = self.params.get("D", shape=(heads,), init="ones")
            self.norm_gamma = self.params.get("norm_gamma", shape=(inner,),
                                              init="ones")
            self.out_proj = _dense(units, inner, "out_proj_")

    def forward_state(self, F, h, conv_state, S, live):
        """``h`` (B, T, C) from ``(conv_state, S)`` (None, None: rows that
        have seen nothing); ``live`` (B, T) or (B,). Returns (out (B, T,
        C), conv_state, S)."""
        y, conv_state, S = F.mamba_mix(
            self.in_proj(h), param_value(self.conv_weight),
            param_value(self.conv_bias), param_value(self.dt_bias),
            param_value(self.A_log), param_value(self.D),
            param_value(self.norm_gamma), conv_state, S, live, **self._geo)
        return self.out_proj(y), conv_state, S


class _LatentMoE(HybridBlock):
    def __init__(self, units, experts, **kwargs):
        super().__init__(**kwargs)
        self._e = experts
        normal = init_mod.Normal(0.02)
        latent, f, fs = experts["latent"], experts["hidden"], \
            experts["shared"]
        with self.name_scope():
            self.router = self.params.get(
                "router_weight", shape=(experts["count"], units),
                init=normal)
            # e_score_correction_bias: picks the experts, weighs nothing
            self.router_bias = self.params.get(
                "router_bias", shape=(experts["count"],), init="zeros")
            self.fc1 = _dense(latent, units, "fc1_")
            self.fc2 = _dense(units, latent, "fc2_")
            # the experts held, each matrix (held, f, latent)
            self.experts_up, self.experts_down = (
                self.params.get("experts_%s_weight" % n,
                                shape=(experts["held"], f, latent),
                                init=normal) for n in ("up", "down"))
            self.shared_up = self.params.get(
                "shared_up_weight", shape=(fs, units), init=normal)
            self.shared_down = self.params.get(
                "shared_down_weight", shape=(units, fs), init=normal)

    def forward_rows(self, F, h, live):
        """``h`` (B, T, C); ``live`` (B * T,). Returns (MoE(h), load)."""
        B, T, C = h.shape
        rows = F.reshape(h, shape=(B * T, C))
        e = self._e
        routed, load = F.expert_ffn(
            rows, param_value(self.router), None,
            param_value(self.experts_up), param_value(self.experts_down),
            live, first_expert=e["first"], top_k=e["top_k"],
            routed_scale=e["scale"], act="relu2",
            bias=param_value(self.router_bias), rows=self.fc1(rows))
        shared = F.relu2_ffn(rows, param_value(self.shared_up),
                             param_value(self.shared_down))
        return F.reshape(self.fc2(routed) + shared, shape=(B, T, C)), load


class _Layer(HybridBlock):
    """One residual layer: norm, then the mixer or FFN its letter names."""

    def __init__(self, kind, units, mamba, attention, experts, eps,
                 **kwargs):
        super().__init__(**kwargs)
        self.kind = kind
        with self.name_scope():
            self.norm = _RMSNorm(units, eps, prefix="norm_")
            if kind == "M":
                self.mixer = _Mamba(units, eps=eps, prefix="mixer_",
                                    **mamba)
            elif kind == "*":
                self.attn = _GroupedAttention(units, window=None, theta=None,
                                              prefix="attn_", **attention)
            else:
                self.moe = _LatentMoE(units, experts, prefix="moe_")

    def forward_kv(self, F, x, live_bt, live, plen):
        """The prefill: (x, what the cache keeps or None, load or None)."""
        h = self.norm(x)
        if self.kind == "M":
            out, conv, S = self.mixer.forward_state(F, h, None, None,
                                                    live_bt)
            return x + out, (conv, S), None
        if self.kind == "*":
            out, k, v = self.attn.forward_kv(F, h, plen)
            return x + out, (k, v), None
        out, load = self.moe.forward_rows(F, h, live)
        return x + out, None, load

    def step_cached(self, F, x, page, position, active):
        """The decode step: (x, the page written or None, load or None)."""
        h = self.norm(x)
        if self.kind == "M":
            out, conv, S = self.mixer.forward_state(F, h, page.conv, page.S,
                                                    active)
            return x + out, SSMPage(conv, S), None
        if self.kind == "*":
            out, page = self.attn.step_cached(F, h, page, position, active)
            return x + out, page, None
        out, load = self.moe.forward_rows(F, h, active)
        return x + out, None, load


class NemotronHModel(HybridBlock):
    """tokens (B, T) int -> logits (B, T, V) over the rows of the head held
    (``vocab_size``: the deployment's slice of embedding and head)."""

    def __init__(self, vocab_size=32768, units=4096, pattern="MEMEMEM*EME",
                 num_experts=512, experts_held=128, first_expert=0,
                 experts_per_token=22, moe_latent=1024, expert_hidden=2688,
                 shared_hidden=5376, mamba_heads=128, mamba_head_dim=64,
                 n_groups=8, state=128, conv=4, chunk=128, num_heads=32,
                 num_kv_heads=2, head_dim=128, routed_scaling_factor=5.0,
                 rms_norm_eps=1e-5, max_length=8192, **kwargs):
        super().__init__(**kwargs)
        if set(pattern) - set("ME*"):
            raise ValueError("pattern %r: one of M, E, * a layer" % pattern)
        if not 0 <= first_expert <= num_experts - experts_held:
            raise ValueError(
                "experts %d..%d are not among %d" % (
                    first_expert, first_expert + experts_held - 1,
                    num_experts))
        self._pattern, self._max_len = pattern, max_length
        self._heads, self._kv_heads, self._head_dim = \
            num_heads, num_kv_heads, head_dim
        self._held = experts_held
        inner = mamba_heads * mamba_head_dim
        self._ssm_sizes = dict(heads=mamba_heads, head_dim=mamba_head_dim,
                               state=state,
                               channels=inner + 2 * n_groups * state,
                               width=conv)
        mamba = dict(heads=mamba_heads, head_dim=mamba_head_dim,
                     groups=n_groups, state=state, conv=conv, chunk=chunk)
        attention = dict(heads=num_heads, kv_heads=num_kv_heads,
                         head_dim=head_dim)
        experts = dict(count=num_experts, held=experts_held,
                       first=first_expert, top_k=experts_per_token,
                       latent=moe_latent, hidden=expert_hidden,
                       shared=shared_hidden,
                       scale=float(routed_scaling_factor))
        normal = init_mod.Normal(0.02)
        with self.name_scope():
            self.word_embed = nn.Embedding(
                vocab_size, units, weight_initializer=normal,
                prefix="word_embed_")
            self.blocks = nn.HybridSequential(prefix="layers_")
            for i, kind in enumerate(pattern):
                self.blocks.add(_Layer(kind, units, mamba, attention,
                                       experts, rms_norm_eps,
                                       prefix="layer%d_" % i))
            self.ln_f = _RMSNorm(units, rms_norm_eps, prefix="ln_f_")
            self.lm_head = self.params.get(
                "lm_head_weight", shape=(vocab_size, units), init=normal)

    def _lm_logits(self, F, x):
        x = self.ln_f(x)
        B, T, C = x.shape
        return F.reshape(F.dot(F.reshape(x, shape=(B * T, C)),
                               F.transpose(param_value(self.lm_head))),
                         shape=(B, T, -1))

    def hybrid_forward(self, F, tokens, **_own):     # the head's matrix
        return self.forward_collect_kv(F, tokens)[0]

    # ----------------------------------------------- the served protocol
    def decode_state_spec(self):
        """The cache contract for ``serve.GenerativeServer``: a layout of
        one record and its sizes a cache layer (an ``SSMPage`` of
        ``mamba_heads`` heads of ``mamba_head_dim`` by ``state``, with the
        conv's channels and width, for each ``M``; a ``PlainPage`` of
        ``num_kv_heads`` K/V heads for each ``*``); ``routed`` (expert layers, experts held + 1)
        is the shape of the load array that prefill and step return as
        ``aux``."""
        layout = [(SSMPage, self._ssm_sizes) if kind == "M" else
                  (PlainPage, dict(heads=self._kv_heads,
                                   head_dim=self._head_dim))
                  for kind in self._pattern if kind != "E"]
        return {"layers": len(layout), "heads": self._heads,
                "kv_heads": self._kv_heads, "head_dim": self._head_dim,
                "page": layout,
                "routed": (self._pattern.count("E"), self._held + 1),
                "max_length": self._max_len,
                "dtype": np.dtype(self.word_embed.weight.data().dtype)}

    def forward_collect_kv(self, F, tokens, plen=None):
        """The prefill primitive. Without ``plen``: logits of every row.
        With ``plen`` (a traced scalar: the prompt's length inside its
        padded bucket): rows at or past it add nothing to a state, attend to
        nothing and route to no expert, and only row ``plen - 1`` goes
        through the head (logits (B, 1, V)). Returns (logits, [(conv, S) or
        (K, V) a cache layer], load (expert layers, held + 1))."""
        B, T = tokens.shape
        if T > self._max_len:
            raise ValueError("sequence length %d exceeds max_length=%d"
                             % (T, self._max_len))
        x = self.word_embed(tokens)
        positions = F.arange(0, T, dtype="int32")
        live_bt = F.broadcast_to(F.reshape(
            positions < (T if plen is None else plen), shape=(1, T)),
            shape=(B, T))
        live = F.reshape(live_bt, shape=(B * T,))
        kept, loads = [], []
        for blk in self.blocks:
            x, page, load = blk.forward_kv(F, x, live_bt, live, plen)
            if page is not None:
                kept.append(page)
            if load is not None:
                loads.append(load)
        if plen is not None:
            x = F.take(x, F.reshape(plen - 1, shape=(1,)), axis=1)
        return self._lm_logits(F, x), kept, \
            F.stack(*loads) if loads else None

    def decode_step(self, F, tokens, state, valid_len, active):
        """One token a slot (``tokens`` (B, 1)) at per-slot positions
        ``valid_len`` over ``state``, one record a cache layer; ``active``
        (B,) marks the live slots (a free slot's state and page stay as they
        were, and it routes nowhere). Returns (logits (B, 1, V), the state
        written, load)."""
        if tokens.shape[1] != 1:
            raise ValueError("NemotronHModel.decode_step takes one token a "
                             "slot, got %d" % tokens.shape[1])
        x = self.word_embed(tokens)                            # (B, 1, C)
        pages, new, loads = iter(state), [], []
        for blk in self.blocks:
            page = next(pages) if blk.kind != "E" else None
            x, page, load = blk.step_cached(F, x, page, valid_len, active)
            if page is not None:
                new.append(page)
            if load is not None:
                loads.append(load)
        return self._lm_logits(F, x), new, \
            F.stack(*loads) if loads else None


def nemotron_h_nano(vocab_size=256, **kwargs):
    """Test-scale config: the pattern ``MEM*E`` at width 64, 8 Mamba heads
    of 16 in 2 groups of state 128, 4 query / 2 K/V heads of 16, 8 experts
    of 32 top-2 in a latent of 48 + 1 shared of 64."""
    cfg = dict(units=64, pattern="MEM*E", num_experts=8, experts_held=8,
               experts_per_token=2, moe_latent=48, expert_hidden=32,
               shared_hidden=64, mamba_heads=8, mamba_head_dim=16,
               n_groups=2, state=128, conv=4, chunk=16, num_heads=4,
               num_kv_heads=2, head_dim=16, routed_scaling_factor=2.5,
               max_length=128)
    cfg.update(kwargs)
    return NemotronHModel(vocab_size=vocab_size, **cfg)
