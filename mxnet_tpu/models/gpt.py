"""GPT-style decoder-only language model (ref: gluon-nlp
src/gluonnlp/model/transformer.py GPT2Model / scripts/text_generation).

TPU-first details: pre-LN blocks with the causal ``F.scaled_dot_attention``
seam — at seq >= 256 on TPU this is the causal pallas flash kernel with its
block-skipping for the masked upper triangle (O(T) memory, ~half the score
FLOPs); weight-tied LM head (one MXU matmul against the embedding table);
KV-cached incremental decode for generation over FIXED-CAPACITY caches:
``init_cache`` allocates (B, H, capacity, D) buffers once and every step
writes in place via ``F.cache_write`` with attention masked to the live
prefix, so no shape ever changes across decode steps (the old growing
(B, H, t, D) time axis retraced any compiled consumer every token —
graphlint GL007). ``prefill`` fills the cache from the whole prompt in ONE
forward pass; ``decode_step`` is the per-slot-position step the
``serve.GenerativeServer`` continuous-batching scheduler traces into one
fused program. All widths multiples of 128 at base size for MXU tiling;
param names follow parallel.tensor_parallel.TRANSFORMER_RULES so the model
shards over a (dp, tp, sp) mesh without edits.
"""
from __future__ import annotations

import numpy as np

from .. import initializer as init_mod
from ..base import next_pow2
from ..gluon import nn
from ..gluon.block import HybridBlock, param_value
from ..serve.kv_cache import Int8Page, PlainPage

__all__ = ["GPTModel", "gpt2_small", "gpt_nano"]


class _CausalSelfAttention(HybridBlock):
    def __init__(self, units, num_heads, dropout=0.0, **kwargs):
        super().__init__(**kwargs)
        assert units % num_heads == 0
        self._units = units
        self._heads = num_heads
        with self.name_scope():
            self.qkv = nn.Dense(3 * units, flatten=False, in_units=units,
                                prefix="qkv_")
            self.attn_out = nn.Dense(units, flatten=False, in_units=units,
                                     prefix="attn_out_")
            self.dropout = nn.Dropout(dropout) if dropout else None

    def _qkv_heads(self, F, x):
        B, T, C = x.shape
        H = self._heads
        h = F.reshape(self.qkv(x), shape=(B, T, 3, H, C // H))
        h = F.transpose(h, axes=(2, 0, 3, 1, 4))  # (3, B, H, T, D)
        q = F.squeeze(F.slice_axis(h, axis=0, begin=0, end=1), axis=0)
        k = F.squeeze(F.slice_axis(h, axis=0, begin=1, end=2), axis=0)
        v = F.squeeze(F.slice_axis(h, axis=0, begin=2, end=3), axis=0)
        return q, k, v

    def _merge_heads(self, F, out):
        B, H, T, D = out.shape
        return F.reshape(F.transpose(out, axes=(0, 2, 1, 3)),
                         shape=(B, T, H * D))

    def forward_kv(self, F, x):
        """Causal self-attention that also returns the projected per-head
        K/V (B, H, T, D) — prefill writes them into the decode cache in one
        shot instead of re-projecting token by token."""
        q, k, v = self._qkv_heads(F, x)
        out = F.scaled_dot_attention(q, k, v, causal=True)
        out = self.attn_out(self._merge_heads(F, out))
        if self.dropout is not None:
            out = self.dropout(out)
        return out, k, v

    def hybrid_forward(self, F, x):
        return self.forward_kv(F, x)[0]

    def step_cached(self, F, x, page, start, lengths=None):
        """Decode against one layer's fixed-capacity ``page`` (a record of
        ``serve.kv_cache``): ``x`` (B, T, C) holds the next T tokens (T=1
        in steady-state decode), whose K/V are written IN PLACE at time
        offset ``start``; row ``t`` then attends to the positions ``[0,
        lengths + t)``, ``lengths`` by default ``start + 1``. ``start`` is a
        python int (uniform imperative decode) or a (B,) per-slot position
        vector (continuous batching, where the server hands ``lengths``
        with 0 for a free slot). The page's type decides the arithmetic, at
        trace time:

        - a ``PlainPage`` is written by ``F.cache_write`` (its docstring
          says what each kind of ``start`` lowers to; with per-slot
          lengths and T = 1 it is told them, and a free slot's page is
          left as it lies) and read by
          ``F.cached_attention``: with per-slot lengths and T = 1 on a TPU
          the Pallas kernel ``decode_attention`` (this model's head widths
          take its column path: only the 128-position blocks that hold a
          slot's live positions are fetched, none for a free slot); with
          an int ``start``, T > 1 (speculative verify, chunked prefill),
          under a mesh or off the TPU the dense masked attention over all
          slots x capacity;
        - an ``Int8Page`` quantizes on write, and the fused write+read
          (``F.quant_cache_write_read``, running per-page-per-head scale)
          hands attention the fp32 pages directly from the
          pre-quantization values — no full-page int8→fp32 convert per
          layer per step (the hlolint GL024 churn the unfused
          quant_cache_write + dequant_cache pair pays). Every row reads
          densely through the mask ``position <= start + t``, whatever
          ``lengths`` says: the whole page is dequantized already.

        Cache shapes never change across steps — the whole point. Returns
        (out (B, T, C), the page written)."""
        q, k_new, v_new = self._qkv_heads(F, x)
        if isinstance(page, Int8Page):
            k_cache, k_scale, k_deq = F.quant_cache_write_read(
                page.k, page.k_scale, k_new, start)
            v_cache, v_scale, v_deq = F.quant_cache_write_read(
                page.v, page.v_scale, v_new, start)
            page = Int8Page(k_cache, k_scale, v_cache, v_scale)
            T, cap = x.shape[1], k_cache.shape[2]
            pos = F.reshape(F.arange(0, cap, dtype="int32"),
                            shape=(1, 1, 1, cap))
            rows = F.reshape(F.arange(0, T, dtype="int32"),
                             shape=(1, 1, T, 1))
            if isinstance(start, int):
                limit = rows + start
            else:  # (B,) per-slot positions
                limit = rows + F.reshape(start, shape=(-1, 1, 1, 1))
            out = F.scaled_dot_attention(q, k_deq, v_deq,
                                         F.lesser_equal(pos, limit))
        else:
            # a window of T > 1 (verify, chunk) is written in every row
            live = () if lengths is None or x.shape[1] > 1 else (lengths,)
            page = PlainPage(F.cache_write(page.k, k_new, start, *live),
                             F.cache_write(page.v, v_new, start, *live))
            if lengths is None:
                lengths = start + 1
            out = F.cached_attention(q, page.k, page.v, lengths)
        return self.attn_out(self._merge_heads(F, out)), page

    def step(self, x, cache):
        """One-token decode against the fixed-capacity ``(k, v, n)`` cache
        (eager path: generation loops in python, each step a fixed-shape
        program — position ``n`` advances, shapes don't)."""
        from .. import nd

        ks, vs, n = cache
        out, page = self.step_cached(nd, x, PlainPage(ks, vs), n)
        return out, (page.k, page.v, n + 1)


class _GPTBlock(HybridBlock):
    """Pre-LN residual block (GPT-2 layout, unlike BERT's post-LN)."""

    def __init__(self, units, hidden, heads, dropout, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.ln1 = nn.LayerNorm(in_channels=units, prefix="ln1_")
            self.attn = _CausalSelfAttention(units, heads, dropout,
                                             prefix="attn_")
            self.ln2 = nn.LayerNorm(in_channels=units, prefix="ln2_")
            self.ffn_1 = nn.Dense(hidden, flatten=False, in_units=units,
                                  prefix="ffn_1_")
            self.act = nn.Activation("gelu")
            self.ffn_2 = nn.Dense(units, flatten=False, in_units=hidden,
                                  prefix="ffn_2_")
            self.dropout = nn.Dropout(dropout) if dropout else None

    def _ffn(self, x):
        h = self.ffn_2(self.act(self.ffn_1(self.ln2(x))))
        if self.dropout is not None:
            h = self.dropout(h)
        return x + h

    def forward_kv(self, F, x):
        a, k, v = self.attn.forward_kv(F, self.ln1(x))
        return self._ffn(x + a), k, v

    def hybrid_forward(self, F, x):
        return self.forward_kv(F, x)[0]

    def step_cached(self, F, x, page, start, lengths=None):
        a, page = self.attn.step_cached(F, self.ln1(x), page, start, lengths)
        return self._ffn(x + a), page

    def step(self, x, cache):
        ks, vs, n = cache
        from .. import nd

        out, page = self.step_cached(nd, x, PlainPage(ks, vs), n)
        return out, (page.k, page.v, n + 1)


class GPTModel(HybridBlock):
    """tokens (B, T) int → logits (B, T, V); LM head tied to the token
    embedding (one matmul against the table, the GPT-2 convention)."""

    def __init__(self, vocab_size=50257, units=768, num_layers=12,
                 num_heads=12, max_length=1024, hidden=None, dropout=0.1,
                 **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self._max_len = max_length
        hidden = hidden or 4 * units
        with self.name_scope():
            self.word_embed = nn.Embedding(
                vocab_size, units, weight_initializer=init_mod.Normal(0.02),
                prefix="word_embed_")
            self.pos_embed = nn.Embedding(
                max_length, units, weight_initializer=init_mod.Normal(0.01),
                prefix="pos_embed_")
            self.drop = nn.Dropout(dropout) if dropout else None
            self.blocks = nn.HybridSequential(prefix="layers_")
            for i in range(num_layers):
                self.blocks.add(_GPTBlock(units, hidden, num_heads, dropout,
                                          prefix="layer%d_" % i))
            self.ln_f = nn.LayerNorm(in_channels=units, prefix="ln_f_")

    def _check_len(self, end):
        if end > self._max_len:
            raise ValueError(
                "sequence length %d exceeds max_length=%d (the positional "
                "embedding table)" % (end, self._max_len))

    def _embed(self, F, tokens, position0=0):
        T = tokens.shape[1]
        self._check_len(position0 + T)
        x = self.word_embed(tokens)
        pw = param_value(self.pos_embed.weight)
        x = x + F.slice_axis(pw, axis=0, begin=position0,
                             end=position0 + T)
        if self.drop is not None:
            x = self.drop(x)
        return x

    def _lm_logits(self, F, x):
        x = self.ln_f(x)
        w = param_value(self.word_embed.weight)          # (V, C) tied head
        B, T, C = x.shape
        logits = F.dot(F.reshape(x, shape=(B * T, C)), F.transpose(w))
        return F.reshape(logits, shape=(B, T, -1))

    def hybrid_forward(self, F, tokens):
        x = self._embed(F, tokens)
        x = self.blocks(x)
        return self._lm_logits(F, x)

    # --------------------------------------------------- fixed-cap caches
    def decode_state_spec(self):
        """Cache-shape contract for external decode schedulers
        (serve.GenerativeServer): per layer, K/V buffers are
        (slots, heads, capacity, head_dim) of ``dtype``; what
        :meth:`decode_step` takes beside plain pages and one token a slot:
        ``int8_pages`` (the attention layer handles an ``Int8Page``) and
        ``multi_token`` (K > 1: speculative verify, chunked prefill)."""
        H = self.blocks[0].attn._heads
        return {"layers": len(self.blocks), "heads": H,
                "head_dim": self._units // H, "max_length": self._max_len,
                "dtype": np.dtype(self.word_embed.weight.data().dtype),
                "int8_pages": True, "multi_token": True}

    def init_cache(self, batch_size, capacity=None, dtype=None):
        """Fixed-capacity decode cache: per layer ``(k, v, n)`` with k/v
        (B, H, capacity, D) zero buffers written in place by ``step`` and
        ``n`` the live length attention masks to. Shapes never change
        across decode steps, so every compiled consumer traces ONCE (the
        old growing (B, H, t, D) time axis was a per-token retrace —
        graphlint GL007). ``capacity`` defaults to ``max_length``; dtype
        defaults to the parameter dtype (bf16-cast models cache in bf16)."""
        from .. import nd

        cap = int(capacity if capacity is not None else self._max_len)
        self._check_len(cap)
        if dtype is None:
            dtype = self.word_embed.weight.data().dtype
        H = self.blocks[0].attn._heads
        D = self._units // H
        return [(nd.zeros((batch_size, H, cap, D), dtype=dtype),
                 nd.zeros((batch_size, H, cap, D), dtype=dtype), 0)
                for _ in range(len(self.blocks))]

    def forward_collect_kv(self, F, tokens, plen=None):
        """Forward pass that also returns every layer's projected K/V —
        the prefill primitive: one whole-prompt dispatch yields both the
        next-token logits and the complete cache contents. ``plen`` (the
        prompt's length inside its padded bucket) changes nothing for a
        dense causal model: every row's logits come back and the caller
        cuts row ``plen - 1``. Returns (logits (B, T, V), [(K, V) a layer],
        None: nothing for the host to read behind the tokens)."""
        x = self._embed(F, tokens)
        kvs = []
        for blk in self.blocks:
            x, k, v = blk.forward_kv(F, x)
            kvs.append((k, v))
        return self._lm_logits(F, x), kvs, None

    def prefill(self, tokens, caches):
        """Whole-prompt cache fill: ONE forward pass computes every
        position's K/V and writes them into the fixed-capacity caches at
        offset 0 (vs. the old token-by-token loop — T dispatch rounds and
        a growing cache shape). Returns (last-position logits (B, V),
        updated caches)."""
        from .. import nd

        B, T = tokens.shape
        self._check_len(T)
        logits, kvs, _ = self.forward_collect_kv(nd, tokens)
        new = [(nd.cache_write(kc, k, 0), nd.cache_write(vc, v, 0), T)
               for (k, v), (kc, vc, _n) in zip(kvs, caches)]
        last = nd.reshape(nd.slice_axis(logits, axis=1, begin=T - 1, end=T),
                          shape=(B, -1))
        return last, new

    def step(self, tokens, caches, position):
        """One decode step: tokens (B, 1) → logits (B, V), updated caches.
        ``position`` indexes into the fixed capacity axis; shapes are
        step-invariant."""
        from .. import nd

        self._check_len(position + 1)
        x = self.word_embed(tokens)
        pw = param_value(self.pos_embed.weight)
        x = x + nd.slice_axis(pw, axis=0, begin=position, end=position + 1)
        new_caches = []
        for blk, (ks, vs, _n) in zip(self.blocks, caches):
            x, page = blk.step_cached(nd, x, PlainPage(ks, vs), position)
            new_caches.append((page.k, page.v, position + 1))
        x = self.ln_f(x)
        w = param_value(self.word_embed.weight)
        logits = nd.dot(nd.reshape(x, shape=(x.shape[0], self._units)),
                        nd.transpose(w))
        return logits, new_caches

    def decode_step(self, F, tokens, state, valid_len, active=None):
        """The served decode step over PER-SLOT positions: tokens (B, K) int
        — each slot's current input token, followed for K > 1 by K-1 more
        (the drafted tokens of a speculative verify, a chunk of a prompt),
        occupying positions ``valid_len .. valid_len+K-1`` of that slot's
        page; ``state`` one page record a layer (``serve.kv_cache``; the
        attention layer decides the arithmetic from its type);
        ``valid_len`` (B,) — tokens already cached per slot; ``active``
        (B,) 0/1 marks the live slots (all, where it is not given): a free
        slot reads nothing of its page, and what it computes is finite and
        discarded by the caller. Row j's K/V is written at ``valid_len+j``
        and attends to the live prefix plus the rows before it, so
        logits[:, j] scores the token at position valid_len+j+1, and row 0
        of any K has the bits of the K = 1 call. Cache rollback after a
        rejected draft is the caller's job and is free: advancing
        ``valid_len`` by only the accepted length masks the dead suffix,
        and the next window overwrites it in place. Returns (logits
        (B, K, V), the state written, None). Pure and F-generic:
        serve.GenerativeServer traces it (with sampling fused behind it)
        into ONE cached XLA program per kind of step."""
        K = tokens.shape[1]
        x = self.word_embed(tokens)                        # (B, K, C)
        pos = F.expand_dims(valid_len, axis=1)
        if K > 1:
            pos = pos + F.reshape(F.arange(0, K, dtype="int32"),
                                  shape=(1, -1))
        x = x + F.take(param_value(self.pos_embed.weight), pos)
        lengths = valid_len + 1
        if active is not None:
            lengths = lengths * active
        new = []
        for blk, page in zip(self.blocks, state):
            x, page = blk.step_cached(F, x, page, valid_len, lengths)
            new.append(page)
        w = param_value(self.word_embed.weight)            # (V, C) tied head
        return F.dot(self.ln_f(x), F.transpose(w)), new, None

    def generate(self, prompt, max_new_tokens=16, use_cache=True):
        """Greedy decode. prompt (B, T0) int → (B, T0 + max_new) int.
        The cached path prefills the whole prompt in ONE forward pass and
        keeps argmax on-device between steps (no host sync in the loop);
        ``use_cache=False`` re-forwards the whole sequence each step
        (the O(T²) parity oracle the cached path is tested against)."""
        from .. import nd

        toks = prompt
        if use_cache:
            B, T0 = prompt.shape
            self._check_len(T0 + max_new_tokens)
            cap = min(self._max_len, next_pow2(T0 + max_new_tokens))
            caches = self.init_cache(B, capacity=cap)
            logits, caches = self.prefill(prompt, caches)
            new = []
            for i in range(max_new_tokens):
                nxt = nd.reshape(nd.argmax(logits, axis=-1),
                                 shape=(-1, 1)).astype(prompt.dtype)
                new.append(nxt)
                if i + 1 < max_new_tokens:
                    logits, caches = self.step(nxt, caches, T0 + i)
            return nd.concat(toks, *new, dim=1)
        for _ in range(max_new_tokens):
            logits = self(toks)
            nxt = nd.reshape(
                nd.argmax(nd.slice_axis(logits, axis=1,
                                        begin=toks.shape[1] - 1,
                                        end=toks.shape[1]), axis=-1),
                shape=(-1, 1)).astype(prompt.dtype)
            # intentional O(T²) growth: this is the oracle, not the product
            toks = nd.concat(toks, nxt, dim=1)  # graphlint: disable=GL007
        return toks


def gpt2_small(vocab_size=50257, **kwargs):
    """GPT-2 124M config (12 x 768, ctx 1024)."""
    return GPTModel(vocab_size=vocab_size, units=768, num_layers=12,
                    num_heads=12, max_length=1024, **kwargs)


def gpt_nano(vocab_size=256, **kwargs):
    """Test-scale config."""
    kwargs.setdefault("dropout", 0.0)
    return GPTModel(vocab_size=vocab_size, units=64, num_layers=2,
                    num_heads=2, max_length=64, **kwargs)
