"""DeepSeek-V3 lineage decoder (A.X-K1, ``model_type`` ``axk1``): latent
attention and routed experts, one chip's share of an expert-parallel model.

Pre-norm residual blocks, RMSNorm, no biases: ``x <- x + Attn(N1(x))``,
``x <- x + FFN(N2(x))``. The first ``first_k_dense`` layers have a dense
SwiGLU FFN, the others a mixture: a float32 sigmoid router over
``num_experts`` picks ``experts_per_token`` of them with weights normalised
over the picked and scaled by ``routed_scaling_factor``, beside
``num_shared_experts`` shared gated FFNs (side by side: one FFN of their
widths' sum). The head is its own matrix.

**Latent attention.** Queries go through a low rank: ``c_q = RMSNorm(h
W_qa)``, ``q = c_q W_qb``, a head being ``[q_nope | q_pe]``. Keys and values
come from ONE compressed row a position: ``[c | k_pe] = h W_kva``, ``c_kv =
RMSNorm(c)``; ``k_pe`` is shared by all heads; ``k_nope = c_kv W_uk^T`` and
``v = c_kv W_uv^T`` a head (the published ``W_kvb`` stored as its two halves:
a layout). ``q_pe`` and ``k_pe`` are rotated (interleaved pairs) with YaRN's
blend of stretched and unstretched frequencies (:func:`yarn_inv_freq`), and
the scores ``(q_nope . k_nope + q_pe . k_pe) * scale`` carry YaRN's
``mscale^2`` in their scale (:func:`attention_scale`).

The same mathematics in two orders. The prefill EXPANDS
(``F.expanded_latent_attention``): it builds K (nope and rotated part side
by side) and V of every head from the rows, a group of heads at a time for a
long prompt, and runs ``scaled_dot_attention`` (the flash kernel from 1,024
tokens, at key width nope + rope and value width ``v_head_dim``). The decode step ABSORBS: ``q_lat
= q_nope W_uk``, scores ``q_lat . c_kv + q_pe . k_pe`` against the cached
rows, ``ctx = sum p c_kv``, ``o = ctx W_uv^T`` (``F.latent_attention``), so
nothing per head is ever cached or rebuilt.

**The share** is ``cohere_moe.py``'s: the model is told which experts it
holds, routes over all and computes its own experts' part (``F.expert_ffn``);
attention, router and shared expert are whole.

**Served state.** ``decode_state_spec()`` names ``serve.kv_cache.LatentPage``
under ``"page"``: a layer's pool is ``c_kv`` (after its norm) and ``k_pe``
(after its rotation), (slots, 1, capacity, width) each. The spec names
neither ``int8_pages`` nor ``multi_token`` (``decode_step`` takes one token a
slot), so the server refuses ``quantize``, ``draft`` and ``prefill_chunk`` by
name.
"""
from __future__ import annotations

import math

import numpy as np

from .. import initializer as init_mod
from ..gluon import nn
from ..gluon.block import HybridBlock, param_value
from ..serve.kv_cache import LatentPage
from .brumby import _RMSNorm, _dense

__all__ = ["LatentMoEModel", "latent_moe_nano", "yarn_inv_freq",
           "attention_scale"]


def yarn_correction_range(dim, theta, original_max, beta_fast, beta_slow):
    """(low, high): the pairs of a rotary of width ``dim`` between which
    YaRN blends: below ``low`` a pair turns more than ``beta_fast`` times
    over the original positions and keeps its frequency, above ``high`` it
    turns less than ``beta_slow`` times and is stretched."""
    def pair(turns):
        return dim * math.log(original_max / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    return (max(math.floor(pair(beta_fast)), 0),
            min(math.ceil(pair(beta_slow)), dim - 1))


def yarn_inv_freq(dim, theta, factor, original_max, beta_fast=32,
                  beta_slow=1):
    """The ``dim / 2`` inverse frequencies of a YaRN-stretched rotary:
    ``f_i / factor * ramp_i + f_i * (1 - ramp_i)`` with ``f_i = theta ** (-2i
    / dim)`` and ``ramp`` rising from 0 at ``low`` to 1 at ``high``."""
    f = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    low, high = yarn_correction_range(dim, theta, original_max, beta_fast,
                                      beta_slow)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return f / factor * ramp + f * (1 - ramp)


def attention_scale(head_width, factor, mscale_all_dim):
    """``mscale^2 / sqrt(head_width)`` with ``mscale = 0.1 * mscale_all_dim
    * ln(factor) + 1``: the softmax scale under YaRN."""
    mscale = 0.1 * mscale_all_dim * math.log(factor) + 1.0 \
        if factor > 1 else 1.0
    return mscale ** 2 / math.sqrt(head_width)


class _LatentAttention(HybridBlock):
    def __init__(self, units, heads, q_rank, kv_rank, nope, rope, v_dim,
                 inv_freq, scale, eps, **kwargs):
        super().__init__(**kwargs)
        self._heads, self._nope, self._rope, self._v = heads, nope, rope, v_dim
        self._rank = kv_rank
        self._inv_freq, self._scale = inv_freq, scale
        normal = init_mod.Normal(0.02)
        with self.name_scope():
            self.q_a = _dense(q_rank, units, "q_a_")
            self.q_a_norm = _RMSNorm(q_rank, eps, prefix="q_a_norm_")
            self.q_b = _dense(heads * (nope + rope), q_rank, "q_b_")
            self.kv_a = _dense(kv_rank + rope, units, "kv_a_")
            self.kv_a_norm = _RMSNorm(kv_rank, eps, prefix="kv_a_norm_")
            # W_kvb's two halves, head by head: W_uk and W_uv, which the
            # absorbed step multiplies from the other side
            self.kv_b_k = self.params.get(
                "kv_b_k_weight", shape=(heads * nope, kv_rank), init=normal)
            self.kv_b_v = self.params.get(
                "kv_b_v_weight", shape=(heads * v_dim, kv_rank), init=normal)
            self.o = _dense(units, heads * v_dim, "o_")

    def _rotate(self, F, x, positions):
        return F.rotary(x, positions, inv_freq=self._inv_freq)

    def _queries(self, F, h, positions):
        """(q_nope (B, H, T, nope), q_pe (B, H, T, rope) rotated)."""
        B, T, _ = h.shape
        q = F.transpose(
            F.reshape(self.q_b(self.q_a_norm(self.q_a(h))),
                      shape=(B, T, self._heads, self._nope + self._rope)),
            axes=(0, 2, 1, 3))
        return (F.slice_axis(q, axis=3, begin=0, end=self._nope),
                self._rotate(F, F.slice_axis(q, axis=3, begin=self._nope,
                                             end=None), positions))

    def _rows(self, F, h, positions):
        """What the page keeps of ``h`` (B, T, C): (c_kv (B, 1, T, rank)
        after its norm, k_pe (B, 1, T, rope) after its rotation)."""
        kv = F.expand_dims(self.kv_a(h), axis=1)
        c_kv = self.kv_a_norm(F.slice_axis(kv, axis=3, begin=0,
                                           end=self._rank))
        return c_kv, self._rotate(
            F, F.slice_axis(kv, axis=3, begin=self._rank, end=None),
            positions)

    def _by_head(self, F, w, width):
        return F.reshape(param_value(w),
                         shape=(self._heads, width, self._rank))

    def _merge(self, F, out):
        B, H, T, D = out.shape
        return self.o(F.reshape(F.transpose(out, axes=(0, 2, 1, 3)),
                                shape=(B, T, H * D)))

    def forward_kv(self, F, h, plen=None):
        """Whole-sequence attention, EXPANDED: K and V of every head built
        from the rows (``F.expanded_latent_attention``; rows at and past
        ``plen``, where given, attend to nothing and are zeros); also
        returns the rows, as the page keeps them."""
        c_kv, k_pe = self._rows(F, h, F.arange(0, h.shape[1], dtype="int32"))
        out = F.expanded_latent_attention(
            self.q_a_norm(self.q_a(h)), c_kv, k_pe,
            param_value(self.q_b.weight), param_value(self.kv_b_k),
            param_value(self.kv_b_v), heads=self._heads, scale=self._scale,
            inv_freq=self._inv_freq, q_valid_len=plen)
        return self._merge(F, out), c_kv, k_pe

    def step_cached(self, F, h, page, position, active):
        """One token a row (``h`` (B, 1, C)) at per-row ``position`` (B,)
        against this layer's ``LatentPage``, ABSORBED: the token's row is
        written at its position (nothing for a row where ``active`` is 0),
        and the queries, taken through ``W_uk``, read the first ``position
        + 1`` rows (none where not active) by ``F.latent_attention``; its
        result, a mix of latent rows a head, goes through ``W_uv``."""
        at = F.reshape(position, shape=(-1, 1))
        q_nope, q_pe = self._queries(F, h, at)
        c_kv, k_pe = self._rows(F, h, at)
        page = LatentPage(F.cache_write(page.c_kv, c_kv, position, active),
                          F.cache_write(page.k_pe, k_pe, position, active))
        q_lat = F.einsum(q_nope, self._by_head(F, self.kv_b_k, self._nope),
                         equation="bhtd,hdr->bhtr")
        ctx = F.latent_attention(q_lat, q_pe, page.c_kv, page.k_pe,
                                 (position + 1) * active, scale=self._scale)
        out = F.einsum(ctx, self._by_head(F, self.kv_b_v, self._v),
                       equation="bhtr,hdr->bhtd")
        return self._merge(F, out), page


class _Block(HybridBlock):
    """One residual block: norm, latent attention, norm, and a dense SwiGLU
    (``experts`` None) or routed experts beside the shared ones."""

    def __init__(self, units, attention, hidden, eps, experts=None, **kwargs):
        super().__init__(**kwargs)
        self._experts = experts
        normal = init_mod.Normal(0.02)
        with self.name_scope():
            self.ln1 = _RMSNorm(units, eps, prefix="ln1_")
            self.attn = _LatentAttention(units, eps=eps, prefix="attn_",
                                         **attention)
            self.ln2 = _RMSNorm(units, eps, prefix="ln2_")
            # the dense FFN, or the shared experts side by side
            self.ffn_gate, self.ffn_up = (
                self.params.get("ffn_%s_weight" % n, shape=(hidden, units),
                                init=normal) for n in ("gate", "up"))
            self.ffn_down = self.params.get(
                "ffn_down_weight", shape=(units, hidden), init=normal)
            if experts is not None:
                f, held = experts["hidden"], experts["held"]
                self.router = self.params.get(
                    "router_weight", shape=(experts["count"], units),
                    init=normal)
                self.experts_gate, self.experts_up, self.experts_down = (
                    self.params.get("experts_%s_weight" % n,
                                    shape=(held, f, units), init=normal)
                    for n in ("gate", "up", "down"))

    def _ffn(self, F, x, live):
        B, T, C = x.shape
        rows = F.reshape(self.ln2(x), shape=(B * T, C))
        y = F.gated_ffn(rows, param_value(self.ffn_gate),
                        param_value(self.ffn_up), param_value(self.ffn_down))
        load = None
        if self._experts is not None:
            e = self._experts
            routed, load = F.expert_ffn(
                rows, param_value(self.router),
                param_value(self.experts_gate), param_value(self.experts_up),
                param_value(self.experts_down), live,
                first_expert=e["first"], top_k=e["top_k"],
                routed_scale=e["scale"])
            y = routed + y
        return x + F.reshape(y, shape=(B, T, C)), load

    def forward_kv(self, F, x, live, plen=None):
        a, c_kv, k_pe = self.attn.forward_kv(F, self.ln1(x), plen)
        x, load = self._ffn(F, x + a, live)
        return x, c_kv, k_pe, load

    def step_cached(self, F, x, page, position, live):
        a, page = self.attn.step_cached(F, self.ln1(x), page, position, live)
        x, load = self._ffn(F, x + a, live)
        return x, page, load


class LatentMoEModel(HybridBlock):
    """tokens (B, T) int -> logits (B, T, V) over the rows of the head held
    (``vocab_size``: the deployment's slice of embedding and head)."""

    def __init__(self, vocab_size=20480, units=7168, num_layers=6,
                 num_heads=64, q_lora_rank=1536, kv_lora_rank=512,
                 qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                 dense_hidden=18432, first_k_dense=1, expert_hidden=2048,
                 num_experts=192, experts_held=12, first_expert=0,
                 experts_per_token=8, num_shared_experts=1,
                 routed_scaling_factor=2.5, rope_theta=10000.0,
                 rope_factor=32.0, original_max_length=4096, beta_fast=32,
                 beta_slow=1, mscale_all_dim=1.0, max_length=16384,
                 rms_norm_eps=1e-6, **kwargs):
        super().__init__(**kwargs)
        if not 0 <= first_expert <= num_experts - experts_held:
            raise ValueError(
                "experts %d..%d are not among %d" % (
                    first_expert, first_expert + experts_held - 1,
                    num_experts))
        if not 0 <= first_k_dense <= num_layers:
            raise ValueError("first_k_dense %d of %d layers"
                             % (first_k_dense, num_layers))
        self._max_len, self._heads = max_length, num_heads
        self._widths = (kv_lora_rank, qk_rope_head_dim)
        self._held = experts_held
        self._routed_layers = num_layers - first_k_dense
        attention = dict(
            heads=num_heads, q_rank=q_lora_rank, kv_rank=kv_lora_rank,
            nope=qk_nope_head_dim, rope=qk_rope_head_dim, v_dim=v_head_dim,
            inv_freq=tuple(float(f) for f in yarn_inv_freq(
                qk_rope_head_dim, float(rope_theta), float(rope_factor),
                original_max_length, beta_fast, beta_slow)),
            scale=attention_scale(qk_nope_head_dim + qk_rope_head_dim,
                                  float(rope_factor), mscale_all_dim))
        experts = dict(count=num_experts, held=experts_held,
                       first=first_expert, top_k=experts_per_token,
                       hidden=expert_hidden,
                       scale=float(routed_scaling_factor))
        normal = init_mod.Normal(0.02)
        with self.name_scope():
            self.word_embed = nn.Embedding(
                vocab_size, units, weight_initializer=normal,
                prefix="word_embed_")
            self.blocks = nn.HybridSequential(prefix="layers_")
            for i in range(num_layers):
                dense = i < first_k_dense
                self.blocks.add(_Block(
                    units, attention,
                    dense_hidden if dense
                    else num_shared_experts * expert_hidden, rms_norm_eps,
                    experts=None if dense else experts,
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
        """The cache contract for ``serve.GenerativeServer``: layer i's pool
        is a ``LatentPage``, one "head" whose ``head_dim`` is the pair
        (``kv_lora_rank``, ``qk_rope_head_dim``); ``routed`` (expert layers,
        experts held + 1) is the shape of the load array that prefill and
        step return as ``aux``."""
        return {"layers": len(self.blocks), "heads": self._heads,
                "kv_heads": 1, "head_dim": self._widths, "page": LatentPage,
                "routed": (self._routed_layers, self._held + 1),
                "max_length": self._max_len,
                "dtype": np.dtype(self.word_embed.weight.data().dtype)}

    def forward_collect_kv(self, F, tokens, plen=None):
        """The prefill primitive, the EXPANDED path. Without ``plen``:
        logits of every row. With ``plen`` (a traced scalar: the prompt's
        length inside its padded bucket): rows at or past it attend to
        nothing (their attention is zeros, and the flash kernel does not
        compute it) and route to no expert, and only row ``plen - 1`` goes
        through the head (logits (B, 1, V)). Returns (logits, [(c_kv, k_pe)
        a layer: the rows the page keeps], load (expert layers, held +
        1))."""
        B, T = tokens.shape
        if T > self._max_len:
            raise ValueError("sequence length %d exceeds max_length=%d"
                             % (T, self._max_len))
        x = self.word_embed(tokens)
        rows = F.arange(0, B * T, dtype="int32") % T
        live = F.ones_like(rows) if plen is None else rows < plen
        kept, loads = [], []
        for blk in self.blocks:
            x, c_kv, k_pe, load = blk.forward_kv(F, x, live, plen)
            kept.append((c_kv, k_pe))
            if load is not None:
                loads.append(load)
        if plen is not None:
            x = F.take(x, F.reshape(plen - 1, shape=(1,)), axis=1)
        return self._lm_logits(F, x), kept, \
            F.stack(*loads) if loads else None

    def decode_step(self, F, tokens, state, valid_len, active):
        """One token a slot (``tokens`` (B, 1)) at per-slot positions
        ``valid_len`` over ``state``, one ``LatentPage`` a layer, the
        ABSORBED path; ``active`` (B,) marks the live slots (a free slot
        writes and reads nothing and routes nowhere). Returns (logits
        (B, 1, V), the state written, load)."""
        if tokens.shape[1] != 1:
            raise ValueError("LatentMoEModel.decode_step takes one token a "
                             "slot, got %d" % tokens.shape[1])
        x = self.word_embed(tokens)                            # (B, 1, C)
        new, loads = [], []
        for blk, page in zip(self.blocks, state):
            x, page, load = blk.step_cached(F, x, page, valid_len, active)
            new.append(page)
            if load is not None:
                loads.append(load)
        return self._lm_logits(F, x), new, \
            F.stack(*loads) if loads else None


def latent_moe_nano(vocab_size=256, **kwargs):
    """Test-scale config: one dense layer and two expert layers, 4 heads of
    16 + 8 (value 16) over a latent of 32, 8 experts top-2 + 1 shared, YaRN
    factor 4 over 32 original positions."""
    cfg = dict(units=64, num_layers=3, num_heads=4, q_lora_rank=48,
               kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
               v_head_dim=16, dense_hidden=96, expert_hidden=32,
               num_experts=8, experts_held=8, experts_per_token=2,
               rope_factor=4.0, original_max_length=32, max_length=128)
    cfg.update(kwargs)
    return LatentMoEModel(vocab_size=vocab_size, **cfg)
