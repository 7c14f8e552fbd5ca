"""Draft proposers for speculative decode (serve.GenerativeServer).

Speculative decoding amortizes the target model over k tokens per verify
dispatch: a cheap DRAFT proposes k-1 tokens per slot, the target scores
the whole window in ONE wide ``decode_step`` dispatch (K = spec_k), and the
longest sampled-prefix-equals-drafted-prefix is accepted (the first
mismatching row's sample IS the resample — for a deterministic draft the
proposal distribution is one-hot, so "sample y ~ p, accept iff y == d,
else emit y" is exactly the standard rejection-sampling identity:
accept w.p. p(d), residual norm(max(p - q, 0)) = p with d masked out).
Greedy requests therefore emit BYTE-IDENTICAL streams to plain greedy
decode, and sampled requests emit the same per-(seed, position) tokens as
the plain path — each emitted token is sampled at its own sequence
position with the slot key folded exactly as the plain step would.

Two drafts, one protocol (``join``/``propose``/``release``/``warm``):

* ``NGramDraft`` — HOST-side order-n pattern matcher over each stream's
  own prompt+generated history. Zero extra dispatches: a speculation
  round is ONE verify dispatch. The right draft when prompts are
  repetitive (code, logs, templated text) or when no small model exists.
* ``ModelDraft`` — a smaller ``GPTModel``-API model with its OWN paged
  KV cache mirroring the target's slots/capacity. One multi-step dispatch
  per round: k single-token steps UNROLLED inside one traced program
  (the k-th step re-decodes the last proposal purely to write its K/V —
  the draft cache would otherwise hold a hole at ``valid+k-1`` after a
  full accept). Draft rollback is the same trick as the target's: the
  shared ``valid_len`` masks rejected positions and the next window
  overwrites them in place.

Both drafts keep every shape fixed — the k-window, the caches, the slot
batch — so steady-state speculation is exactly ``1 + dispatches_per_round``
dispatches per round with ZERO retrace (``engine.decode_compile_counter``
flat, ``engine.verify_dispatch_counter`` counting verify dispatches at the
call site; tests/test_speculative.py pins both with the watchdog armed).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from .. import _trace, engine
from .batcher import ServeError
from .kv_cache import PagedKVCache, write_prompt

__all__ = ["NGramDraft", "ModelDraft", "ngram_propose"]


def ngram_propose(history, n, order=3):
    """Propose ``n`` continuation tokens for one stream by suffix matching:
    find the most recent earlier occurrence of the last ``m`` tokens
    (longest m ≤ order first) and propose the token that followed it;
    extend iteratively. Falls back to repeating the last token — a wrong
    proposal only costs acceptance rate, never correctness (the verify
    pass emits its own sample on mismatch)."""
    out = []
    h = list(history)   # caller passes python ints; copy only for append
    for _ in range(n):
        nxt = None
        for m in range(min(order, len(h) - 1), 0, -1):
            ctx = h[-m:]
            for i in range(len(h) - m - 1, -1, -1):
                if h[i:i + m] == ctx:
                    nxt = h[i + m]
                    break
            if nxt is not None:
                break
        if nxt is None:
            nxt = h[-1] if h else 0
        out.append(nxt)
        h.append(nxt)
    return out


class NGramDraft:
    """Host-side n-gram draft: proposes from each stream's own history
    (prompt + generated tokens, which already ends with the slot's current
    input token). No device state, no dispatches — ``dispatches_per_round``
    is 0, so a speculation round costs exactly ONE (verify) dispatch."""

    needs_history = True
    dispatches_per_round = 0

    def __init__(self, order=3):
        self.order = int(order)
        self._server = None

    def bind(self, server):
        self._server = server

    def ensure_capacity(self):
        pass

    def join(self, slot, stream, padded, t0_len):
        pass

    def release(self, slot):
        pass

    def warm(self, tp_buckets=()):
        pass

    def propose(self, histories, k):
        """(slots, k-1) int32 host proposals; rows with no history (free
        slots) propose zeros — the verify mask ignores them."""
        slots = len(histories)
        out = np.zeros((slots, max(0, k - 1)), np.int32)
        if k <= 1:
            return out
        for s, h in enumerate(histories):
            if h:
                out[s] = ngram_propose(h, k - 1, self.order)
        return out

    # ----------------------------------------------- snapshot interface
    def export_executables(self):
        return []

    def preload_executable(self, kind, tp, capacity, compiled):
        raise ServeError("NGramDraft has no compiled programs (kind %r)"
                         % kind)


class ModelDraft:
    """Device draft: a smaller model speaking the same fixed-capacity
    decode protocol (``decode_state_spec``/``forward_collect_kv``/
    ``decode_step``) with its own slot-paged KV cache mirroring the
    target server's slots and capacity buckets. The draft model must share
    the target's vocabulary and cover its ``max_length``."""

    needs_history = False
    dispatches_per_round = 1

    def __init__(self, model):
        self.model = model
        self._plist = list(model.collect_params().values())
        self._spec = model.decode_state_spec()
        self._server = None
        self.cache = None
        self._step_fns = {}   # capacity -> k-unrolled propose program
        self._fill_fns = {}   # (tp, capacity) -> whole-prompt cache fill

    def bind(self, server):
        self._server = server
        if self._spec["max_length"] < server.cache.max_capacity:
            raise ServeError(
                "draft max_length=%d < target max_length=%d — the draft "
                "must cover every target position it speculates at"
                % (self._spec["max_length"], server.cache.max_capacity))
        self.cache = PagedKVCache(
            self._spec["layers"], self._spec["heads"],
            self._spec["head_dim"], server.slots, server.cache.max_capacity,
            dtype=self._spec["dtype"])

    def ensure_capacity(self):
        """Mirror the target cache's capacity bucket (same pow2, so the
        draft migrates exactly when the target does)."""
        self.cache.ensure_capacity(self._server.cache.capacity)

    def release(self, slot):
        pass

    # -------------------------------------------------------- programs
    def _step_fn(self, capacity):
        fn = self._step_fns.get(capacity)
        if fn is not None:
            return fn
        model, plist = self.model, self._plist
        k = self._server.spec_k

        def pure(params, state, valid, toks):
            # trace-time bump: the zero-steady-state-retrace proof covers
            # the draft program too (tests/test_speculative.py)
            engine.decode_compile_counter.bump()
            props = []
            x = toks
            with _trace.trace_scope(jax.random.PRNGKey(0), False) as t:
                t.param_store = {id(p): a for p, a in zip(plist, params)}
                # k UNROLLED greedy steps in one dispatch: steps 0..k-2
                # propose d_1..d_{k-1}; step k-1 re-decodes d_{k-1} only
                # to write its K/V at valid+k-1 (else a full accept next
                # round would attend over a hole) — its argmax is dropped
                for j in range(k):
                    logits, state, _aux = model.decode_step(
                        _trace.F, x[:, None], state, valid + j)
                    x = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
                    if j < k - 1:
                        props.append(x)
            if props:
                drafts = jnp.stack(props, axis=1)
            else:
                drafts = jnp.zeros((toks.shape[0], 0), jnp.int32)
            return state, drafts

        fn = self._server._jit(pure, donate=(1,),
                               hint="draftstep@c%d" % capacity)
        self._step_fns[capacity] = fn
        return fn

    def _fill_fn(self, tp, capacity):
        fn = self._fill_fns.get((tp, capacity))
        if fn is not None:
            return fn
        model, plist = self.model, self._plist

        def pure(params, state, tokens, slot):
            engine.decode_compile_counter.bump()
            with _trace.trace_scope(jax.random.PRNGKey(0), False) as t:
                t.param_store = {id(p): a for p, a in zip(plist, params)}
                _logits, kvs, _aux = model.forward_collect_kv(_trace.F,
                                                              tokens)
            # the whole bucket counts as live: the target's valid_len masks
            return write_prompt(state, kvs, tokens.shape[1], slot)

        fn = self._server._jit(pure, donate=(1,),
                               hint="draftfill@t%dc%d" % (tp, capacity))
        self._fill_fns[(tp, capacity)] = fn
        return fn

    # ------------------------------------------------------- scheduling
    def join(self, slot, stream, padded, t0_len):
        """Fill the draft's page for a joining stream: one whole-prompt
        dispatch (the draft is small by design — chunking it would cost
        more in round trips than it saves). The draft has no prefix cache;
        a target prefix hit still pays this one small fill. Positions
        beyond the prompt hold stale garbage masked by the shared
        ``valid_len`` and overwritten by later windows."""
        self.ensure_capacity()
        engine.dispatch_counter.bump()
        fn = self._fill_fn(padded.shape[1], self.cache.capacity)
        params = [p.data()._data for p in self._plist]
        self.cache.state = fn(params, self.cache.state, jnp.asarray(padded),
                              jnp.int32(slot))

    def propose(self, histories, k):
        """(slots, k-1) device proposals via ONE k-unrolled dispatch,
        positions taken from the TARGET's valid_len (the shared notion of
        the live prefix — draft rollback is implicit in it)."""
        srv = self._server
        engine.dispatch_counter.bump()
        fn = self._step_fn(self.cache.capacity)
        params = [p.data()._data for p in self._plist]
        self.cache.state, drafts = fn(params, self.cache.state,
                                      srv.cache.valid, srv._tok)
        return drafts

    def warm(self, tp_buckets=()):
        """Compile the draft programs ahead of traffic (fill per prompt
        bucket + the k-unrolled step at the current capacity)."""
        self.ensure_capacity()
        params = [p.data()._data for p in self._plist]
        for tp in tp_buckets:
            fn = self._fill_fn(int(tp), self.cache.capacity)
            self.cache.state = fn(
                params, self.cache.state,
                jnp.zeros((1, int(tp)), jnp.int32), jnp.int32(0))
        fn = self._step_fn(self.cache.capacity)
        self.cache.state, _d = fn(params, self.cache.state,
                                  self._server.cache.valid,
                                  self._server._tok)

    # ----------------------------------------------- snapshot interface
    def export_executables(self):
        """Draft programs for the snapshot manifest (kinds ``draftstep``/
        ``draftfill``) — a warm replica speculates with zero compiles."""
        out = []
        for cap, fn in sorted(self._step_fns.items()):
            c = fn.compiled_for()
            if c is not None:
                out.append({"key": "draftstep@c%d" % cap,
                            "kind": "draftstep", "tp": 0,
                            "capacity": int(cap), "compiled": c})
        for (tp, cap), fn in sorted(self._fill_fns.items()):
            c = fn.compiled_for()
            if c is not None:
                out.append({"key": "draftfill@t%dc%d" % (tp, cap),
                            "kind": "draftfill", "tp": int(tp),
                            "capacity": int(cap), "compiled": c})
        return out

    def preload_executable(self, kind, tp, capacity, compiled):
        if kind == "draftstep":
            fn = self._step_fn(capacity)
        elif kind == "draftfill":
            fn = self._fill_fn(tp, capacity)
        else:
            raise ServeError("unknown draft program kind %r" % kind)
        fn.adopt(compiled)
