"""Paged KV-cache state for continuous-batching generative decode.

The decode-side analogue of ``executor_pool``'s pad-to-bucket discipline
(TVM-style fixed compiled shapes, arXiv 1802.04799) applied to the KV cache:
instead of a per-request cache tensor whose time axis grows every token —
a new aval per step, so every compiled consumer retraces (graphlint GL007)
— all in-flight requests share per-layer ``(slots, heads, capacity,
head_dim)`` buffers (a sliding-window layer's is a ring of its window's
length: ``windows``). Each request owns one SLOT page; its tokens are
written in place at its own ``valid_len`` position by the ``cache_write``
op and attention masks to the live prefix, so **no shape ever changes
across decode steps**. What that write lowers to depends on the call
(``ops/attention.py: cache_write``): a prefill or an inject writes a whole
window at a scalar offset, one ``lax.dynamic_update_slice``; the decode
step writes one token per slot at per-slot positions, on a TPU the Pallas
kernel ``kv_cache_write`` (one pass over the 128-position blocks the
positions fall in), elsewhere ``vmap(dynamic_update_slice)``, a
``scatter`` that XLA runs as a serial loop over the slots.

Capacity is bucketed in powers of two: when an admitted request needs more
room than the current bucket, the buffers are zero-padded up to the next
bucket (one rare migration dispatch) and the decode program for that
capacity compiles once — the same log2-many-programs bound the executor
pool gives batch sizes. Buffers are donated to the decode program on TPU
backends (they are pure carried state; XLA updates them in place), the
same donation discipline as ``executor_pool``.

``PrefixCache`` is the prompt-caching layer: completed prefills are keyed
by the token-prefix hash; a hit replays the stored K/V pages into the new
request's slot (one tiny inject dispatch) instead of re-running the
whole-prompt forward.
"""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from ..base import BoundedCache, env_cap, next_pow2


class CacheError(RuntimeError):
    """Misuse of the paged cache (capacity/slot exhaustion)."""


class PagedKVCache:
    """Slot-paged fixed-capacity KV cache shared by all in-flight requests.

    Holds the device-side carried state of the decode loop — per-layer K/V
    buffers plus the per-slot ``valid_len`` vector — and the host-side slot
    bookkeeping (which request owns which page). The compiled prefill/
    decode programs take these arrays as (donated) inputs and return the
    updated ones; the server writes them back via :meth:`update`.

    Parameters
    ----------
    layers, heads, head_dim : int
        Per-layer buffer geometry (``model.decode_state_spec()``);
        ``heads`` are the K/V heads a buffer holds (fewer than the query
        heads under grouped-query attention).
    slots : int
        Number of request pages — the padded decode batch size.
    max_capacity : int
        Hard ceiling on the time axis (the model's ``max_length``).
    dtype : np.dtype
        K/V element dtype (the model's parameter dtype; bf16 models
        cache in bf16).
    quantize : bool
        Store pages as int8 with per-page-per-head fp32 scales
        (``k_scale``/``v_scale``, (slots, H, 1, 1) per layer): ~0.5× the
        bf16 page bytes. Pages quantize on write (``quant_cache_write``'s
        running-max scale) and dequantize on read inside the decode
        program; capacity buckets, donation and the one-dispatch step are
        unchanged. Scale buffers are capacity-independent, so migrations
        only pad the int8 pages.
    windows : None or list
        The geometry layer by layer: ``None`` for a full page (time axis =
        the capacity bucket), or the length of a RING for a sliding-window
        layer, whose time axis is ``min(capacity, window)``: the model
        writes position p at ``p % length``, so a ring never holds more
        than its window and never grows past it. Not with ``quantize``.
    """

    def __init__(self, layers, heads, head_dim, slots, max_capacity,
                 dtype=np.float32, quantize=False, windows=None):
        self.layers = int(layers)
        self.windows = [None if w is None else int(w)
                        for w in (windows or [None] * self.layers)]
        if len(self.windows) != self.layers:
            raise CacheError("%d windows for %d layers"
                             % (len(self.windows), self.layers))
        if quantize and any(w is not None for w in self.windows):
            raise CacheError("int8 pages keep one running scale a page: "
                             "not for window rings")
        self.heads = int(heads)
        self.head_dim = int(head_dim)
        self.slots = int(slots)
        self.max_capacity = int(max_capacity)
        self.quantize = bool(quantize)
        self.dtype = np.dtype(np.int8) if self.quantize else np.dtype(dtype)
        # what a non-quantized cache of the model's dtype would cost per
        # element — the denominator of the bytes-saved accounting
        self._ref_itemsize = np.dtype(dtype).itemsize
        self.capacity = 0
        self.k = None     # list[L] of (slots, H, layer_length, D) jax arrays
        self.v = None
        self.k_scale = None  # list[L] of (slots, H, 1, 1) fp32 (quantized)
        self.v_scale = None
        self.valid = jnp.zeros((self.slots,), jnp.int32)
        self._free = list(range(self.slots))
        self._owner = [None] * self.slots
        self.migrations = 0  # capacity-bucket growths (rare by design)

    # ---------------------------------------------------------- capacity
    def capacity_bucket(self, need):
        """Pow2 capacity bucket for ``need`` tokens, clamped to the model's
        max length (positions beyond it have no embedding)."""
        if need > self.max_capacity:
            raise CacheError(
                "request needs %d cache positions but the model's "
                "max_length is %d" % (need, self.max_capacity))
        return min(self.max_capacity, next_pow2(need))

    def layer_length(self, layer, capacity=None):
        """Time axis of ``layer``'s buffers at a capacity bucket (the
        current one by default): the bucket, or a ring's own length."""
        cap = self.capacity if capacity is None else int(capacity)
        w = self.windows[layer]
        return cap if w is None else min(cap, w)

    def page_lengths(self, tp):
        """Positions a prompt of bucket ``tp`` leaves in each layer: ``tp``,
        or the whole ring where the prompt is longer."""
        return [min(int(tp), self.layer_length(i))
                for i in range(self.layers)]

    def page_bytes(self, tp, itemsize=None):
        """Bytes of the K and V page of a prompt of bucket ``tp``, as the
        prefix store keeps it."""
        itemsize = self.dtype.itemsize if itemsize is None else itemsize
        return 2 * sum(self.page_lengths(tp)) * self.heads * self.head_dim \
            * itemsize

    def ensure_capacity(self, need):
        """Grow the buffers to the bucket that fits ``need`` (zero-padding
        the time axis — one migration dispatch per layer, then the decode
        program for the new capacity compiles once). Returns True when a
        migration happened — live programs for the old capacity stay
        cached, so shrinking traffic never re-migrates."""
        cap = self.capacity_bucket(need)
        if cap <= self.capacity and self.k is not None:
            return False
        if self.k is None:
            shapes = [(self.slots, self.heads, self.layer_length(i, cap),
                       self.head_dim) for i in range(self.layers)]
            self.k = [jnp.zeros(shape, self.dtype) for shape in shapes]
            self.v = [jnp.zeros(shape, self.dtype) for shape in shapes]
            if self.quantize:
                sshape = (self.slots, self.heads, 1, 1)
                self.k_scale = [jnp.zeros(sshape, jnp.float32)
                                for _ in range(self.layers)]
                self.v_scale = [jnp.zeros(sshape, jnp.float32)
                                for _ in range(self.layers)]
        else:
            # a ring that has reached its window stays as it is: until then
            # no position has wrapped, and padding keeps p % length == p
            def grow(a, i):
                more = self.layer_length(i, cap) - a.shape[2]
                return jnp.pad(a, ((0, 0), (0, 0), (0, more), (0, 0))) \
                    if more else a

            self.k = [grow(k, i) for i, k in enumerate(self.k)]
            self.v = [grow(v, i) for i, v in enumerate(self.v)]
            # scale buffers are (slots, H, 1, 1) — capacity-independent
            self.migrations += 1
        self.capacity = cap
        return True

    # ------------------------------------------------------------- slots
    def acquire(self, owner):
        """Claim a free page for ``owner``; None when fully booked (the
        scheduler leaves the request in the admission queue)."""
        if not self._free:
            return None
        slot = self._free.pop(0)
        self._owner[slot] = owner
        return slot

    def release(self, slot):
        """Free a page between decode steps — pure host bookkeeping: the
        next prefill overwrites the page from offset 0 and ``valid_len``
        masks everything stale, so no device-side scrub is needed (and no
        recompile: the batch layout is padded, not reshaped)."""
        self._owner[slot] = None
        self._free.append(slot)

    def owner(self, slot):
        return self._owner[slot]

    @property
    def active_slots(self):
        return [i for i, o in enumerate(self._owner) if o is not None]

    @property
    def num_active(self):
        return self.slots - len(self._free)

    def active_mask(self, exclude=()):
        """(slots,) int32 mask of live pages — a traced input of the decode
        program (free slots sample nothing and their valid_len holds), so
        join/leave between steps never changes a shape. ``exclude`` drops
        acquired-but-not-yet-decodable pages (chunked prefill in flight):
        the slot is owned, so admission can't reuse it, but decode must
        treat it as free until its final chunk lands."""
        return np.asarray([0 if (o is None or i in exclude) else 1
                           for i, o in enumerate(self._owner)], np.int32)

    def update(self, k, v, valid, k_scale=None, v_scale=None):
        """Install the arrays a compiled step returned (the old buffers
        were donated on TPU — they must not be touched again)."""
        self.k, self.v, self.valid = list(k), list(v), valid
        if k_scale is not None:
            self.k_scale = list(k_scale)
        if v_scale is not None:
            self.v_scale = list(v_scale)

    # ------------------------------------------------------------ accounting
    def nbytes(self):
        """Live page-buffer bytes (K + V + scales) — the measured side of
        the quantized-cache acceptance ratio."""
        if self.k is None:
            return 0
        total = sum(int(a.nbytes) for a in self.k)
        total += sum(int(a.nbytes) for a in self.v)
        if self.quantize:
            total += sum(int(a.nbytes) for a in self.k_scale)
            total += sum(int(a.nbytes) for a in self.v_scale)
        return total

    def nbytes_unquantized(self, itemsize=None):
        """What the SAME geometry would cost unquantized — the denominator
        of the ≤ 0.55× bytes acceptance check. ``itemsize`` defaults to the
        model dtype's (pass 2 to compare against a bf16 cache)."""
        if self.k is None:
            return 0
        elems = 2 * self.slots * self.heads * self.head_dim \
            * sum(self.layer_length(i) for i in range(self.layers))
        return elems * (self._ref_itemsize if itemsize is None else itemsize)


def _host(stack):
    """A page stack as host arrays: one array, or a tuple of one a layer."""
    return tuple(np.asarray(a) for a in stack) \
        if isinstance(stack, (tuple, list)) else np.asarray(stack)


class PrefixCache:
    """Prompt/prefix cache: token-prefix hash → finished prefill state.

    Entries hold host-side copies ``(k_stack, v_stack, prompt_len,
    last_logits)`` with ``k_stack``/``v_stack`` of shape (layers, heads,
    padded_prompt_len, head_dim) — exact dtypes (bf16 stays bf16); where
    the layers' pages differ in length (window rings beside full pages) a
    stack is a tuple of one (heads, length, head_dim) array a layer. A hit
    skips the whole-prompt forward: the stored pages are injected into the
    request's slot by a tiny compiled program and the first token is
    sampled from the stored logits with the request's own key/temperature
    (two requests sharing a prompt can still sample differently).

    Bounded (``MXNET_PREFIX_CACHE_CAP``, default 32 prompts): entries are
    full KV pages, the one cache in this subsystem where eviction is about
    host RAM, not compiled-program count.
    """

    def __init__(self, cap=None):
        self._store = BoundedCache(env_cap("MXNET_PREFIX_CACHE_CAP", 32)
                                   if cap is None else cap)
        self.hits = 0
        self.misses = 0

    @staticmethod
    def key(tokens):
        return tuple(int(t) for t in np.asarray(tokens).ravel())

    def get(self, tokens):
        ent = self._store.get(self.key(tokens))
        if ent is None:
            self.misses += 1
        else:
            self.hits += 1
        return ent

    def put(self, tokens, k_stack, v_stack, prompt_len, last_logits):
        self._store[self.key(tokens)] = (
            _host(k_stack), _host(v_stack), int(prompt_len),
            np.asarray(last_logits))

    def __len__(self):
        return len(self._store)
