"""Brumby style decoder: power retention in the place of attention.

Every layer is ``x <- x + Wo Ret(RMS(x))``, ``x <- x + SwiGLU(RMS(x))``.
``Ret`` projects to grouped heads (query head h reads K/V head ``h // (heads
// kv_heads)``) and one gate a K/V head, normalises q and k over the head
width (RMSNorm with one gain vector for all heads), rotates them (whole
head, half-split pairs, ``rope_theta``), and reads power retention of degree
2 under the gate ``c = sigmoid(g)``: ``F.power_retention``, whose docstring
(``ops/retention.py``) has the equations. No biases; the head is its own
matrix.

**Served state.** A layer keeps no K and V by position: its cache is the
retention's state, ``S`` and ``z`` of a fixed size a slot whatever the
context. ``decode_state_spec()`` names ``serve.kv_cache.StatePage`` under
``"page"``; the prefill hands over (S, z) a layer after the prompt's last
token in the place of (K, V), and the step updates the live slots' state in
place (a free slot's is not touched). The spec names neither ``int8_pages``
nor ``multi_token`` (``decode_step`` takes one token a slot: a window of
more would need the state of every row of it, for the roll-back), so the
server refuses ``quantize``, ``draft`` and ``prefill_chunk`` by name.
"""
from __future__ import annotations

import numpy as np

from .. import initializer as init_mod
from ..gluon import nn
from ..gluon.block import HybridBlock, param_value
from ..serve.kv_cache import StatePage

__all__ = ["BrumbyModel", "brumby_nano"]


class _RMSNorm(HybridBlock):
    def __init__(self, units, eps, **kwargs):
        super().__init__(**kwargs)
        self._eps = eps
        with self.name_scope():
            self.gamma = self.params.get("gamma", shape=(units,), init="ones")

    def hybrid_forward(self, F, x, gamma):
        return F.rms_norm(x, gamma, eps=self._eps)


def _dense(units, in_units, prefix):
    return nn.Dense(units, use_bias=False, flatten=False, in_units=in_units,
                    prefix=prefix)


class _Retention(HybridBlock):
    def __init__(self, units, heads, kv_heads, head_dim, theta, eps,
                 **kwargs):
        super().__init__(**kwargs)
        assert heads % kv_heads == 0
        self._heads, self._kv, self._d = heads, kv_heads, head_dim
        self._theta = theta
        with self.name_scope():
            self.q = _dense(heads * head_dim, units, "q_")
            self.k = _dense(kv_heads * head_dim, units, "k_")
            self.v = _dense(kv_heads * head_dim, units, "v_")
            self.g = _dense(kv_heads, units, "g_")
            self.o = _dense(units, heads * head_dim, "o_")
            self.q_norm = _RMSNorm(head_dim, eps, prefix="q_norm_")
            self.k_norm = _RMSNorm(head_dim, eps, prefix="k_norm_")

    def _split(self, F, y, n):
        B, T, _ = y.shape
        return F.transpose(F.reshape(y, shape=(B, T, n, self._d)),
                           axes=(0, 2, 1, 3))

    def forward_state(self, F, h, positions, state, live):
        """``h`` (B, T, C) at ``positions`` ((T,) or per row (B, T)) from
        ``state`` ((S, z), or None: rows that have seen nothing); ``live``
        marks the rows that are tokens. Returns (out (B, T, C), S, z)."""
        q = self.q_norm(self._split(F, self.q(h), self._heads))
        k = self.k_norm(self._split(F, self.k(h), self._kv))
        v = self._split(F, self.v(h), self._kv)
        q = F.rotary(q, positions, theta=self._theta, pairing="half")
        k = F.rotary(k, positions, theta=self._theta, pairing="half")
        # the gate a K/V head, float32 from here on: log sigmoid(g)
        log_c = -F.Activation(-F.transpose(self.g(h), axes=(0, 2, 1))
                              .astype("float32"), act_type="softrelu")
        out, S, z = F.power_retention(q, k, v, log_c, state, live)
        B, H, T, D = out.shape
        return self.o(F.reshape(F.transpose(out, axes=(0, 2, 1, 3)),
                                shape=(B, T, H * D))), S, z


class _Block(HybridBlock):
    def __init__(self, units, heads, kv_heads, head_dim, hidden, theta, eps,
                 **kwargs):
        super().__init__(**kwargs)
        normal = init_mod.Normal(0.02)
        with self.name_scope():
            self.ln1 = _RMSNorm(units, eps, prefix="ln1_")
            self.ret = _Retention(units, heads, kv_heads, head_dim, theta,
                                  eps, prefix="ret_")
            self.ln2 = _RMSNorm(units, eps, prefix="ln2_")
            self.ffn_gate, self.ffn_up = (
                self.params.get("ffn_%s_weight" % n, shape=(hidden, units),
                                init=normal) for n in ("gate", "up"))
            self.ffn_down = self.params.get(
                "ffn_down_weight", shape=(units, hidden), init=normal)

    def forward_state(self, F, x, positions, state, live):
        a, S, z = self.ret.forward_state(F, self.ln1(x), positions, state,
                                          live)
        x = x + a
        B, T, C = x.shape
        y = F.gated_ffn(F.reshape(self.ln2(x), shape=(B * T, C)),
                        param_value(self.ffn_gate), param_value(self.ffn_up),
                        param_value(self.ffn_down))
        return x + F.reshape(y, shape=(B, T, C)), S, z


class BrumbyModel(HybridBlock):
    """tokens (B, T) int -> logits (B, T, V)."""

    def __init__(self, vocab_size=151936, units=5120, num_layers=40,
                 num_heads=40, num_kv_heads=8, head_dim=128, hidden=17408,
                 rope_theta=1000000.0, rms_norm_eps=1e-6, max_length=32768,
                 **kwargs):
        super().__init__(**kwargs)
        self._max_len = max_length
        self._heads, self._kv_heads, self._head_dim = \
            num_heads, num_kv_heads, head_dim
        normal = init_mod.Normal(0.02)
        with self.name_scope():
            self.word_embed = nn.Embedding(
                vocab_size, units, weight_initializer=normal,
                prefix="word_embed_")
            self.blocks = nn.HybridSequential(prefix="layers_")
            for i in range(num_layers):
                self.blocks.add(_Block(
                    units, num_heads, num_kv_heads, head_dim, hidden,
                    float(rope_theta), rms_norm_eps, prefix="layer%d_" % i))
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
        """The cache contract for ``serve.GenerativeServer``: layer i's
        pool is a ``StatePage``, (S, z) of ``kv_heads`` heads a slot."""
        return {"layers": len(self.blocks), "heads": self._heads,
                "kv_heads": self._kv_heads, "head_dim": self._head_dim,
                "page": StatePage, "max_length": self._max_len,
                "dtype": np.dtype(self.word_embed.weight.data().dtype)}

    def forward_collect_kv(self, F, tokens, plen=None):
        """The prefill primitive. Without ``plen``: logits of every row.
        With ``plen`` (a traced scalar: the prompt's length inside its
        padded bucket): rows at or past it add nothing to the state, and
        only row ``plen - 1`` goes through the head (logits (B, 1, V)).
        Returns (logits, [(S, z) a layer: the state after the prompt],
        None)."""
        B, T = tokens.shape
        if T > self._max_len:
            raise ValueError("sequence length %d exceeds max_length=%d"
                             % (T, self._max_len))
        x = self.word_embed(tokens)
        positions = F.arange(0, T, dtype="int32")
        live = None if plen is None else \
            F.broadcast_to(F.reshape(positions < plen, shape=(1, T)),
                           shape=(B, T))
        states = []
        for blk in self.blocks:
            x, S, z = blk.forward_state(F, x, positions, None, live)
            states.append((S, z))
        if plen is not None:
            x = F.take(x, F.reshape(plen - 1, shape=(1,)), axis=1)
        return self._lm_logits(F, x), states, None

    def decode_step(self, F, tokens, state, valid_len, active=None):
        """One token a slot (``tokens`` (B, 1)) at per-slot positions
        ``valid_len`` over ``state``, one ``StatePage`` a layer; ``active``
        (B,) marks the live slots: a free slot's state stays as it was.
        Returns (logits (B, 1, V), the state written, None)."""
        if tokens.shape[1] != 1:
            raise ValueError("BrumbyModel.decode_step takes one token a "
                             "slot, got %d" % tokens.shape[1])
        x = self.word_embed(tokens)                            # (B, 1, C)
        positions = F.reshape(valid_len, shape=(-1, 1))
        new = []
        for blk, page in zip(self.blocks, state):
            x, S, z = blk.forward_state(F, x, positions, (page.S, page.z),
                                       active)
            new.append(StatePage(S, z))
        return self._lm_logits(F, x), new, None


def brumby_nano(vocab_size=256, **kwargs):
    """Test-scale config: 2 layers, 10 query / 2 K/V heads of width 16 (a
    state of 9 x 16 x 16 a head), inner width 96."""
    cfg = dict(units=64, num_layers=2, num_heads=10, num_kv_heads=2,
               head_dim=16, hidden=96, max_length=128)
    cfg.update(kwargs)
    return BrumbyModel(vocab_size=vocab_size, **cfg)
