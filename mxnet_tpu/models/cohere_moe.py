"""Cohere2-MoE style decoder: one chip's share of an expert-parallel model.

The block is parallel (``x <- x + Attn(h) + MoE(h)`` from ONE bias-free
LayerNorm ``h = LN(x)``), attention has grouped K/V heads (query head h
reads K/V head ``h // (heads // kv_heads)``) and alternates per
``layer_types``: ``"sliding_attention"`` layers rotate q and k (interleaved
pairs, ``rope_theta``) and see the last ``sliding_window`` positions,
``"full_attention"`` layers carry no positional encoding at all and see
everything before them. Every layer's FFN is a mixture: a float32 sigmoid
router over ``num_experts`` picks ``experts_per_token`` of them with
weights normalised over the picked, and ``num_shared_experts`` shared gated
FFNs are averaged and added. The head is tied to the token embedding.

**The share.** The model is told which experts it holds
(``first_expert .. first_expert + experts_held - 1``): it routes over all
``num_experts`` and computes its own experts' part (``F.expert_ffn``), as
one chip of an expert-parallel deployment does before the parts are summed
across chips; attention and the shared experts are whole. With
``experts_held == num_experts`` it is the whole model.

**Served state.** ``decode_state_spec()`` gives ``serve.GenerativeServer``
the cache geometry layer by layer: ``kv_heads`` K/V heads a buffer, and
``windows``: a ring of ``sliding_window`` positions for the window layers
(written at ``position % ring length``), a full page for the others. A ring
holds exactly the positions its layer may see, so one mask (``slot <=
position``) serves both kinds. ``routed`` is the shape of the experts'
load, which prefill and step return as their ``aux`` (pad rows and free
slots route nowhere). The spec names neither ``int8_pages`` nor
``multi_token`` (``decode_step`` takes plain pages and one token a slot),
so the server refuses ``quantize``, ``draft`` and ``prefill_chunk`` by name.
"""
from __future__ import annotations

import numpy as np

from .. import initializer as init_mod
from ..gluon import nn
from ..gluon.block import HybridBlock, param_value
from ..serve.kv_cache import PlainPage

__all__ = ["CohereMoEModel", "cohere_moe_nano"]

SLIDING, FULL = "sliding_attention", "full_attention"


class _LayerNormNoBias(HybridBlock):
    """LayerNorm with mean subtraction and a gain, no shift (the Cohere
    norm): ``F.LayerNorm`` (and its kernel) with a zero shift."""

    def __init__(self, units, eps, **kwargs):
        super().__init__(**kwargs)
        self._eps = eps
        with self.name_scope():
            self.gamma = self.params.get("gamma", shape=(units,), init="ones")

    def hybrid_forward(self, F, x, gamma):
        return F.LayerNorm(x, gamma, F.zeros_like(gamma), eps=self._eps)


def _dense(units, in_units, prefix):
    return nn.Dense(units, use_bias=False, flatten=False, in_units=in_units,
                    prefix=prefix)


class _GroupedAttention(HybridBlock):
    def __init__(self, units, heads, kv_heads, head_dim, window, theta,
                 **kwargs):
        super().__init__(**kwargs)
        assert heads % kv_heads == 0
        self._heads, self._kv, self._d = heads, kv_heads, head_dim
        self._window, self._theta = window, theta   # window None: full, NoPE
        with self.name_scope():
            self.q = _dense(heads * head_dim, units, "q_")
            self.k = _dense(kv_heads * head_dim, units, "k_")
            self.v = _dense(kv_heads * head_dim, units, "v_")
            self.o = _dense(units, heads * head_dim, "o_")

    def _split(self, F, y, n):
        B, T, _ = y.shape
        return F.transpose(F.reshape(y, shape=(B, T, n, self._d)),
                           axes=(0, 2, 1, 3))

    def _qkv(self, F, h, positions):
        q = self._split(F, self.q(h), self._heads)
        k = self._split(F, self.k(h), self._kv)
        v = self._split(F, self.v(h), self._kv)
        if self._window is not None:
            q = F.rotary(q, positions, theta=self._theta)
            k = F.rotary(k, positions, theta=self._theta)
        return q, k, v

    def _merge(self, F, out):
        B, H, T, D = out.shape
        return self.o(F.reshape(F.transpose(out, axes=(0, 2, 1, 3)),
                                shape=(B, T, H * D)))

    def forward_kv(self, F, h):
        """Whole-sequence attention; also returns K (rotated where the layer
        rotates) and V, (B, kv_heads, T, D), as the cache holds them."""
        T = h.shape[1]
        q, k, v = self._qkv(F, h, F.arange(0, T, dtype="int32"))
        out = F.scaled_dot_attention(q, k, v, causal=True,
                                     window=self._window)
        return self._merge(F, out), k, v

    def step_cached(self, F, h, page, position, active):
        """One token a row (``h`` (B, 1, C)) at per-row ``position`` (B,)
        against this layer's ``PlainPage`` (B, kv_heads, L, D): the new K/V
        go to slot ``position % L`` (a ring wraps; a full page is longer
        than any position), and every slot at or before the position is live: a
        wrapped ring holds exactly the window. So a row reads the first
        ``min(position + 1, L)`` slots of its buffer, and none where
        ``active`` (B,) 0/1 is 0; such a row writes nothing either
        (``F.cache_write`` is told ``active``). The read is
        ``F.cached_attention``, which
        today lowers to the dense masked attention over rows x L on every
        backend: head width 128 would take the row path of the Pallas
        kernel ``decode_attention`` (blocks of 512 slots, fetched once for
        the 16 query heads of a group, and only those that hold a live
        slot), which is built, tested and shut at the op's gate
        (``ops/attention.py: _DECODE_ROW_PATH`` says why)."""
        L = page.k.shape[2]
        q, k, v = self._qkv(F, h, F.reshape(position, shape=(-1, 1)))
        at = position % L
        page = PlainPage(F.cache_write(page.k, k, at, active),
                         F.cache_write(page.v, v, at, active))
        lengths = F.minimum(position + 1, L) * active
        out = F.cached_attention(q, page.k, page.v, lengths)
        return self._merge(F, out), page


class _MoEBlock(HybridBlock):
    """One parallel block: norm, attention, routed and shared experts."""

    def __init__(self, units, heads, kv_heads, head_dim, window, theta,
                 expert_hidden, num_experts, experts_held, first_expert,
                 experts_per_token, num_shared, eps, **kwargs):
        super().__init__(**kwargs)
        self._first, self._top_k = first_expert, experts_per_token
        self._shared_scale = 1.0 / num_shared
        f, normal = expert_hidden, init_mod.Normal(0.02)
        with self.name_scope():
            self.ln = _LayerNormNoBias(units, eps, prefix="ln_")
            self.attn = _GroupedAttention(units, heads, kv_heads, head_dim,
                                          window, theta, prefix="attn_")
            self.router = self.params.get(
                "router_weight", shape=(num_experts, units), init=normal)
            # the experts held, each matrix (held, f, units): a block of an
            # expert's inner rows is contiguous for the grouped kernel
            self.experts_gate, self.experts_up, self.experts_down = (
                self.params.get("experts_%s_weight" % n,
                                shape=(experts_held, f, units), init=normal)
                for n in ("gate", "up", "down"))
            # the shared experts side by side: their average is one gated
            # FFN of num_shared x f inner rows, scaled by 1 / num_shared
            self.shared_gate, self.shared_up = (
                self.params.get("shared_%s_weight" % n,
                                shape=(num_shared * f, units), init=normal)
                for n in ("gate", "up"))
            self.shared_down = self.params.get(
                "shared_down_weight", shape=(units, num_shared * f),
                init=normal)

    def _moe(self, F, h, live):
        B, T, C = h.shape
        rows = F.reshape(h, shape=(B * T, C))
        routed, load = F.expert_ffn(
            rows, param_value(self.router),
            param_value(self.experts_gate), param_value(self.experts_up),
            param_value(self.experts_down), live,
            first_expert=self._first, top_k=self._top_k)
        shared = F.gated_ffn(
            rows, param_value(self.shared_gate), param_value(self.shared_up),
            param_value(self.shared_down))
        return (F.reshape(routed, shape=(B, T, C)),
                F.reshape(shared, shape=(B, T, C)), load)

    def _sum(self, F, x, attn, routed, shared):
        # one rounding for the four terms, not three
        y = (x.astype("float32") + attn.astype("float32")
             + routed.astype("float32")
             + shared.astype("float32") * self._shared_scale)
        return y.astype(x.dtype)

    def forward_kv(self, F, x, live):
        h = self.ln(x)
        a, k, v = self.attn.forward_kv(F, h)
        routed, shared, load = self._moe(F, h, live)
        return self._sum(F, x, a, routed, shared), k, v, load

    def step_cached(self, F, x, page, position, live):
        h = self.ln(x)
        a, page = self.attn.step_cached(F, h, page, position, live)
        routed, shared, load = self._moe(F, h, live)
        return self._sum(F, x, a, routed, shared), page, load


class CohereMoEModel(HybridBlock):
    """tokens (B, T) int -> logits (B, T, V) over the rows of the embedding
    held (``vocab_size``: the deployment's slice of the tied table)."""

    def __init__(self, vocab_size=32768, units=4096, num_layers=4,
                 num_heads=128, num_kv_heads=8, head_dim=128,
                 expert_hidden=4096, num_experts=128, experts_held=16,
                 first_expert=0, experts_per_token=8, num_shared_experts=4,
                 layer_types=None, sliding_window=4096, rope_theta=50000.0,
                 max_length=8192, layer_norm_eps=1e-5, **kwargs):
        super().__init__(**kwargs)
        if layer_types is None:
            layer_types = [FULL if i % 4 == 3 else SLIDING
                           for i in range(num_layers)]
        if len(layer_types) != num_layers or \
                set(layer_types) - {SLIDING, FULL}:
            raise ValueError("layer_types needs one of %r, %r a layer, got "
                             "%r" % (SLIDING, FULL, layer_types))
        if not 0 <= first_expert <= num_experts - experts_held:
            raise ValueError(
                "experts %d..%d are not among %d" % (
                    first_expert, first_expert + experts_held - 1,
                    num_experts))
        self._units, self._max_len = units, max_length
        self._heads, self._kv_heads, self._head_dim = \
            num_heads, num_kv_heads, head_dim
        self._windows = [int(sliding_window) if t == SLIDING else None
                         for t in layer_types]
        self._held = experts_held
        with self.name_scope():
            self.word_embed = nn.Embedding(
                vocab_size, units, weight_initializer=init_mod.Normal(0.02),
                prefix="word_embed_")
            self.blocks = nn.HybridSequential(prefix="layers_")
            for i, window in enumerate(self._windows):
                self.blocks.add(_MoEBlock(
                    units, num_heads, num_kv_heads, head_dim, window,
                    float(rope_theta), expert_hidden, num_experts,
                    experts_held, first_expert, experts_per_token,
                    num_shared_experts, layer_norm_eps,
                    prefix="layer%d_" % i))
            self.ln_f = _LayerNormNoBias(units, layer_norm_eps,
                                         prefix="ln_f_")

    def _check_len(self, end):
        if end > self._max_len:
            raise ValueError("sequence length %d exceeds max_length=%d"
                             % (end, self._max_len))

    def _lm_logits(self, F, x):
        x = self.ln_f(x)
        B, T, C = x.shape
        w = param_value(self.word_embed.weight)              # tied head
        return F.reshape(F.dot(F.reshape(x, shape=(B * T, C)),
                               F.transpose(w)), shape=(B, T, -1))

    def hybrid_forward(self, F, tokens):
        return self.forward_collect_kv(F, tokens)[0]

    # ----------------------------------------------- the served protocol
    def decode_state_spec(self):
        """The cache contract for ``serve.GenerativeServer``: layer i's K
        and V buffers are (slots, ``kv_heads``, L_i, ``head_dim``) with L_i
        the capacity, or ``min(capacity, windows[i])`` for a ring;
        ``routed`` (layers, experts held + 1) is the shape of the load
        array that prefill and step return as ``aux``."""
        return {"layers": len(self.blocks), "heads": self._heads,
                "kv_heads": self._kv_heads, "head_dim": self._head_dim,
                "windows": list(self._windows),
                "routed": (len(self.blocks), self._held + 1),
                "max_length": self._max_len,
                "dtype": np.dtype(self.word_embed.weight.data().dtype)}

    def forward_collect_kv(self, F, tokens, plen=None):
        """The prefill primitive. Without ``plen``: logits of every row.
        With ``plen`` (a traced scalar: the prompt's length inside its
        padded bucket): rows at or past it route to no expert, and only row
        ``plen - 1`` goes through the head (logits (B, 1, V)). Returns
        (logits, [(K, V) a layer], load (layers, held + 1))."""
        B, T = tokens.shape
        self._check_len(T)
        x = self.word_embed(tokens)
        rows = F.arange(0, B * T, dtype="int32") % T
        live = F.ones_like(rows) if plen is None else rows < plen
        kvs, loads = [], []
        for blk in self.blocks:
            x, k, v, load = blk.forward_kv(F, x, live)
            kvs.append((k, v))
            loads.append(load)
        if plen is not None:
            x = F.take(x, F.reshape(plen - 1, shape=(1,)), axis=1)
        return self._lm_logits(F, x), kvs, F.stack(*loads)

    def decode_step(self, F, tokens, state, valid_len, active):
        """One token a slot (``tokens`` (B, 1)) at per-slot positions
        ``valid_len`` over ``state``, one ``PlainPage`` a layer; ``active``
        (B,) marks the live slots (a free slot routes nowhere). Returns
        (logits (B, 1, V), the state written, load)."""
        x = self.word_embed(tokens)                            # (B, 1, C)
        new, loads = [], []
        for blk, page in zip(self.blocks, state):
            x, page, load = blk.step_cached(F, x, page, valid_len, active)
            new.append(page)
            loads.append(load)
        return self._lm_logits(F, x), new, F.stack(*loads)


def cohere_moe_nano(vocab_size=256, **kwargs):
    """Test-scale config: 4 layers (three window-16 rings to one full
    page), 8 query / 2 K/V heads of width 16, 8 experts top-2 + 2 shared."""
    cfg = dict(units=64, num_layers=4, num_heads=8, num_kv_heads=2,
               head_dim=16, expert_hidden=32, num_experts=8, experts_held=8,
               experts_per_token=2, num_shared_experts=2, sliding_window=16,
               max_length=128)
    cfg.update(kwargs)
    return CohereMoEModel(vocab_size=vocab_size, **cfg)
