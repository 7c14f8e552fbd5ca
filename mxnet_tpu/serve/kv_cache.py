"""Paged KV-cache state for continuous-batching generative decode.

The decode-side analogue of ``executor_pool``'s pad-to-bucket discipline
(TVM-style fixed compiled shapes, arXiv 1802.04799) applied to the KV cache:
instead of a per-request cache tensor whose time axis grows every token —
a new aval per step, so every compiled consumer retraces (graphlint GL007)
— all in-flight requests share per-layer ``(slots, heads, capacity,
head_dim)`` buffers (a sliding-window layer's is a ring of its window's
length: ``windows``). Each request owns one SLOT page; its tokens are
written in place at its own ``valid_len`` position by the ``cache_write``
op and attention reads the live prefix (``cached_attention``), so **no
shape ever changes across decode steps**. What that write lowers to depends
on the call (``ops/attention.py: cache_write``): a prefill or an inject
writes a whole window at a scalar offset, one
``lax.dynamic_update_slice``; the decode
step writes one token per live slot at per-slot positions, on a TPU the
Pallas kernel ``kv_cache_write`` (one pass over the 128-position blocks the
live slots' positions fall in: a free slot's page is not touched),
elsewhere ``vmap(dynamic_update_slice)``, a ``scatter`` that XLA runs as
a serial loop over the slots (there a free slot writes back what it read).
A free slot's page is next written by the join that takes it (the
prefill's or the inject's ``write_prompt``, from position 0), and until
then no program reads it. What the read
lowers to (``ops/attention.py: cached_attention``): the decode step hands
per-slot lengths, 0 for a free slot, and on a TPU the Pallas kernel
``decode_attention`` fetches only the blocks that hold a slot's live
positions (128 positions a block, with the capacity on the lanes, for head
widths under 128), so a step reads what its streams hold, not slots x
capacity, and a released page costs nothing until it is taken again; head
widths of whole lane tiles (the kernel's row path is shut at the op's
gate), speculative verify and chunked prefill (more than one query token a
slot), the int8 pages, meshes and the CPU read through a mask, densely.

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

Not every model keeps K and V by position. ``StatePage`` is the record of a
layer whose cache is a recurrent state of fixed size a slot (power
retention, ``ops/retention.py``): the same pool of slots, carried by the
same programs, with no time axis. Its prompt write and read-out move a
snapshot of the state, which is what the prefix store then keeps.

Nor does every model that keeps positions keep K and V a head.
``LatentPage`` is the record of a layer with latent (compressed K/V)
attention: ONE row a position whatever the head count, the normalised
latent ``c_kv`` (512 values) and the rotated key ``k_pe`` (64) that all
heads share, as two buffers (slots, 1, capacity, width), so that both tile
for ``cache_write`` (``c_kv``: whole lane tiles, the kernel's row path;
``k_pe``: under one lane tile, its column path, the capacity on the
lanes). The prefill expands a prompt's rows to per-head K and V for its own
attention and hands over the rows; the decode step reads them through
``latent_attention`` (``ops/attention.py``), on a TPU a Pallas kernel that
fetches only the blocks that hold a slot's live positions, once for all
heads. Its prompt write, read-out and the prefix store's entries are those
rows: 1,152 bytes a position and layer in bfloat16 where 64 heads of K (192)
and V (128) would take 40,960.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from ..base import BoundedCache, env_cap, next_pow2


class CacheError(RuntimeError):
    """Misuse of the paged cache (capacity/slot exhaustion)."""


# ------------------------------------------------------------- page records
# One layer's pool of pages is a RECORD whose type says its format. The
# cache's state is a list of them, one a layer: a pytree that the serving
# programs carry (donated) without looking inside. What knows a format: the
# record here (allocation, growth, and the traced operations that move a
# prompt's or a slot's page in and out of the pool) and the model's
# attention layer (the write and read of a decode step, chosen at trace time
# from the record's type). A new format is a new record with these methods
# and a model whose ``decode_state_spec()`` names it under ``"page"``.
@functools.lru_cache(maxsize=None)
def _zero():
    # ONE device scalar for every start index of every program (a program
    # then carries one constant for them), made at the first use and not at
    # import: importing this module starts no backend (a fleet's router
    # stays off the chip). The first use is inside a trace: made eagerly.
    with jax.ensure_compile_time_eval():
        return jnp.int32(0)


def _slot_start(slot):
    zero = _zero()
    return (slot, zero, zero, zero)


def _take(a, slot):
    return jax.lax.dynamic_slice(a, _slot_start(slot), (1,) + a.shape[1:])


def _put(pool, slot, page):
    """``pool`` with ``slot``'s page replaced by ``page`` (a pool of one
    slot, as ``take_slot`` gave it), leaf by leaf."""
    at = _slot_start(slot)
    return type(pool)(*(jax.lax.dynamic_update_slice(a, p, at)
                        for a, p in zip(pool, page)))


def _pad_time(a, more):
    return jnp.pad(a, ((0, 0), (0, 0), (0, more), (0, 0))) if more else a


# what a record with a time axis (its first buffer (slots, heads, length,
# width)) answers the cache's and the scheduler's questions with
def _prompt_length(page, tp):
    """Positions a prompt of bucket ``tp`` leaves in the page: ``tp``, or
    the whole ring where the prompt is longer."""
    return min(int(tp), page[0].shape[2])


def _plain_bytes(page, itemsize):
    """What K and V of the page's geometry cost at ``itemsize`` bytes an
    element."""
    return 2 * page.k.size * itemsize


def _kvread_tag(pages, contexts):
    """``kvread=<share>`` of a traced step's span: the 128-position blocks
    of K and V that hold a live position of a stream (``contexts``: the
    tokens each live slot has cached, this step's included), over the blocks
    the pool holds, which is how much of the pool the step's attention has
    to read."""
    blocks = lambda n: -(-n // 128)
    lengths = [page[0].shape[2] for page in pages]
    held = sum(blocks(min(n, length)) for n in contexts
               for length in lengths)
    pool = pages[0][0].shape[0] * sum(blocks(length) for length in lengths)
    return "kvread=%.3f" % (held / pool)


class PlainPage(NamedTuple):
    """K and V of one layer in the model's dtype: ``k``, ``v`` (slots,
    heads, length, head_dim), ``length`` the capacity bucket or a ring's
    own length."""

    k: Any
    v: Any

    @classmethod
    def zeros(cls, slots, heads, length, head_dim, dtype):
        shape = (slots, heads, length, head_dim)
        return cls(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))

    def grow(self, more):
        """The time axis zero-padded by ``more`` positions."""
        return PlainPage(_pad_time(self.k, more), _pad_time(self.v, more))

    def _rows(self, kv, plen):
        """A prompt's K or V (1, H, tp, D) as this layer keeps it: as it
        is, or, where the prompt's bucket is longer than the layer's ring,
        the ring's rows: slot j holds the last position p < plen with
        p % length == j."""
        length = self.k.shape[2]
        if kv.shape[2] > length:
            j = jnp.arange(length, dtype=jnp.int32)
            kv = jnp.take(kv, jnp.clip(
                plen - 1 - (plen - 1 - j) % length, 0, kv.shape[2] - 1),
                axis=2)
        return kv.astype(self.k.dtype)

    def write_prompt(self, k, v, plen, slot):
        """The pool with a prompt's ``k``, ``v`` (1, H, tp, D; ``plen``
        live positions) written into ``slot``'s page from position 0."""
        at = _slot_start(slot)
        return PlainPage(*(
            jax.lax.dynamic_update_slice(a, self._rows(new, plen), at)
            for a, new in zip(self, (k, v))))

    def read_prompt(self, slot, n):
        """The first ``n`` positions of ``slot``'s page as the prefix store
        keeps them: (k, v), each (H, n, D)."""
        H, D = self.k.shape[1], self.k.shape[3]
        return tuple(jax.lax.dynamic_slice(
            a, _slot_start(slot), (1, H, n, D))[0] for a in self)

    def prompt_bytes(self, n):
        """Bytes of what :meth:`read_prompt` returns."""
        H, D = self.k.shape[1], self.k.shape[3]
        return 2 * H * n * D * self.k.dtype.itemsize

    def take_slot(self, slot, fresh):
        """``slot``'s page as a pool of one slot (``fresh``: a traced flag,
        the slot starts a new stream; a plain page carries nothing over)."""
        return PlainPage(*(_take(a, slot) for a in self))

    put_slot = _put
    prompt_length = _prompt_length
    plain_bytes = _plain_bytes
    step_tag = staticmethod(_kvread_tag)


class Int8Page(NamedTuple):
    """K and V of one layer as int8 with one float32 scale a page and head:
    ``k``, ``v`` (slots, heads, capacity, head_dim) int8, ``k_scale``,
    ``v_scale`` (slots, heads, 1, 1). A decode step keeps a running max
    (``ops/attention.py: quant_cache_write_read``); a prompt's write sets the
    scale afresh, which is what lets a slot be taken again."""

    k: Any
    k_scale: Any
    v: Any
    v_scale: Any

    @classmethod
    def zeros(cls, slots, heads, length, head_dim, dtype):
        # four buffers of their own: a donated argument is handed over once
        page = lambda: jnp.zeros((slots, heads, length, head_dim), jnp.int8)
        scale = lambda: jnp.zeros((slots, heads, 1, 1), jnp.float32)
        return cls(page(), scale(), page(), scale())

    def grow(self, more):
        # the scales are capacity-independent: only the int8 pages pad
        return self._replace(k=_pad_time(self.k, more),
                             v=_pad_time(self.v, more))

    def write_prompt(self, k, v, plen, slot):
        # a fresh scale, not a running max, with positions >= plen masked
        # out of the amax (pad garbage must not inflate it)
        tp = k.shape[2]
        maskf = (jnp.arange(tp) < plen).astype(jnp.float32).reshape(
            (1, 1, tp, 1))

        def quantize(a):
            a = a.astype(jnp.float32) * maskf
            amax = jnp.max(jnp.abs(a), axis=(2, 3), keepdims=True)
            scale = jnp.maximum(amax / 127.0, 1e-8)
            return (jnp.clip(jnp.round(a / scale), -127, 127).astype(
                jnp.int8), scale)

        at = _slot_start(slot)
        return Int8Page(*(
            jax.lax.dynamic_update_slice(a, new, at)
            for a, new in zip(self, quantize(k) + quantize(v))))

    def read_prompt(self, slot, n):
        # the store's entries are fp whatever the pool's format: dequantised
        # on the way out, and write_prompt re-derives the same scale from
        # the page's largest element on the way back in
        H, D = self.k.shape[1], self.k.shape[3]
        at = _slot_start(slot)

        def deq(a, scale):
            page = jax.lax.dynamic_slice(a, at, (1, H, n, D))
            return (page.astype(jnp.float32) * _take(scale, slot))[0]

        return deq(self.k, self.k_scale), deq(self.v, self.v_scale)

    def prompt_bytes(self, n):
        H, D = self.k.shape[1], self.k.shape[3]
        return 2 * H * n * D * 4

    def take_slot(self, slot, fresh):
        # a fresh stream must not inherit the running max of the one that
        # held the slot before it
        def scale(s):
            s = _take(s, slot)
            return jnp.where(fresh, jnp.zeros_like(s), s)

        return Int8Page(_take(self.k, slot), scale(self.k_scale),
                        _take(self.v, slot), scale(self.v_scale))

    put_slot = _put
    prompt_length = _prompt_length
    plain_bytes = _plain_bytes
    step_tag = staticmethod(_kvread_tag)


class StatePage(NamedTuple):
    """The recurrent state of one layer, one a slot, of the same size
    whatever the context: ``S`` (slots, heads, rows x head_dim, head_dim)
    and ``z`` (slots, heads, rows, head_dim) in float32, as
    ``ops/retention.py`` lays them out (``zero_state``). There is no time
    axis: the capacity bucket names the programs and bounds the positions,
    and sizes nothing. What moves in and out of the pool is a SNAPSHOT: a
    prefill hands over the state after the prompt's last token, the prefix
    store keeps that state whole, and a join overwrites all of its slot's,
    which is what lets a slot be taken again."""

    S: Any
    z: Any

    # what read_prompt moves is the whole state, not a prompt's positions
    snapshot = True

    @classmethod
    def zeros(cls, slots, heads, length, head_dim, dtype):
        from ..ops.retention import zero_state

        return cls(*zero_state(slots, heads, head_dim))

    def grow(self, more):
        return self

    def write_prompt(self, S, z, plen, slot):
        """The pool with the state after a prompt (``S``, ``z`` of one
        slot, as the model's prefill or the prefix store gives them) in
        ``slot``'s place."""
        return _put(self, slot, StatePage(S, z))

    def read_prompt(self, slot, n):
        """``slot``'s state as the prefix store keeps it: (S, z) without
        the slot axis."""
        return tuple(_take(a, slot)[0] for a in self)

    def prompt_length(self, tp):
        return 0

    def prompt_bytes(self, n):
        """Bytes of what :meth:`read_prompt` returns: one slot's state."""
        return sum(a.nbytes // a.shape[0] for a in self)

    def plain_bytes(self, itemsize):
        return sum(a.nbytes for a in self)

    def take_slot(self, slot, fresh):
        # a fresh stream starts from nothing
        return StatePage(*(jnp.where(fresh, 0.0, _take(a, slot))
                           for a in self))

    put_slot = _put

    @staticmethod
    def step_tag(pages, contexts):
        """``state=<MB>`` of a traced step's span: the state its live slots
        hold, which the step reads and writes once, whatever their
        contexts."""
        return "state=%.1f" % (1e-6 * len(contexts) * sum(
            page.prompt_bytes(0) for page in pages))


class LatentPage(NamedTuple):
    """The compressed rows of one layer with latent attention: ``c_kv``
    (slots, 1, length, rank), the latent after its norm, and ``k_pe``
    (slots, 1, length, rope), the key all heads share after its rotation,
    in the model's dtype. One row a position whatever the head count; the
    cache is told the two widths as its ``head_dim`` (``(rank, rope)``) and
    one "head"."""

    c_kv: Any
    k_pe: Any

    @classmethod
    def zeros(cls, slots, heads, length, head_dim, dtype):
        return cls(*(jnp.zeros((slots, 1, length, width), dtype)
                     for width in head_dim))

    def grow(self, more):
        return LatentPage(*(_pad_time(a, more) for a in self))

    def write_prompt(self, c_kv, k_pe, plen, slot):
        """The pool with a prompt's rows (``c_kv``, ``k_pe`` (1, 1, tp,
        width); ``plen`` live positions) written into ``slot``'s page from
        position 0."""
        at = _slot_start(slot)
        return LatentPage(*(
            jax.lax.dynamic_update_slice(a, new.astype(a.dtype), at)
            for a, new in zip(self, (c_kv, k_pe))))

    def read_prompt(self, slot, n):
        """The first ``n`` positions of ``slot``'s page as the prefix store
        keeps them: (c_kv, k_pe), each (1, n, width)."""
        return tuple(jax.lax.dynamic_slice(
            a, _slot_start(slot), (1, 1, n, a.shape[3]))[0] for a in self)

    def prompt_bytes(self, n):
        """Bytes of what :meth:`read_prompt` returns."""
        return sum(n * a.shape[3] * a.dtype.itemsize for a in self)

    def plain_bytes(self, itemsize):
        return sum(a.size for a in self) * itemsize

    def take_slot(self, slot, fresh):
        return LatentPage(*(_take(a, slot) for a in self))

    put_slot = _put
    prompt_length = _prompt_length
    step_tag = staticmethod(_kvread_tag)


def write_prompt(state, kvs, plen, slot):
    """The state with a prompt's K/V (``kvs``: one (k, v) a layer, each
    (1, H, tp, D)) written into ``slot``'s page of every layer. Traced."""
    return [page.write_prompt(k, v, plen, slot)
            for page, (k, v) in zip(state, kvs)]


def read_prompt(state, slot, lengths):
    """``slot``'s page read out as the prefix store keeps it: (k_stack,
    v_stack), each one stacked array where every layer's page has the same
    length, else (window rings beside full pages) one array a layer. The
    two stacks are whatever the record keeps two of: K and V, or a
    :class:`StatePage`'s ``S`` and ``z``.
    ``lengths``: ``PagedKVCache.page_lengths`` of the prompt's bucket.
    Traced."""
    pack = jnp.stack if len(set(lengths)) == 1 else tuple
    ks, vs = zip(*(page.read_prompt(slot, n)
                   for page, n in zip(state, lengths)))
    return pack(ks), pack(vs)


def stored_kvs(k_stack, v_stack):
    """A prefix entry's stacks as :func:`write_prompt` takes them."""
    return [(k_stack[i][None], v_stack[i][None])
            for i in range(len(k_stack))]


def take_slot(state, slot, fresh):
    """Every layer's page of ``slot`` as a state of one slot. Traced."""
    return [page.take_slot(slot, fresh) for page in state]


def put_slot(state, slot, pages):
    """The state with ``slot``'s pages replaced by ``pages``. Traced."""
    return [page.put_slot(slot, p) for page, p in zip(state, pages)]


class PagedKVCache:
    """Slot-paged fixed-capacity KV cache shared by all in-flight requests.

    Holds the device-side carried state of the decode loop — ``state``, one
    page record a layer (:class:`PlainPage`, :class:`Int8Page` or the
    model's own), plus the per-slot ``valid_len`` vector — and the host-side
    slot bookkeeping (which request owns which page). The compiled prefill/
    decode programs take ``state`` and ``valid`` as (donated) inputs and
    return the updated ones; the server writes them back via :meth:`update`.

    Parameters
    ----------
    layers, heads, head_dim : int
        Per-layer buffer geometry (``model.decode_state_spec()``);
        ``heads`` are the K/V heads a buffer holds (fewer than the query
        heads under grouped-query attention). ``head_dim`` is handed to the
        page record as it is: a :class:`LatentPage` takes the pair of its
        two widths.
    slots : int
        Number of request pages — the padded decode batch size.
    max_capacity : int
        Hard ceiling on the time axis (the model's ``max_length``).
    dtype : np.dtype
        The model's parameter dtype (bf16 models cache in bf16).
    quantize : bool
        Store pages as :class:`Int8Page`: ~0.5× the bf16 page bytes. Pages
        quantize on write and dequantize on read inside the decode program;
        capacity buckets, donation and the one-dispatch step are unchanged.
    windows : None or list
        The geometry layer by layer: ``None`` for a full page (time axis =
        the capacity bucket), or the length of a RING for a sliding-window
        layer, whose time axis is ``min(capacity, window)``: the model
        writes position p at ``p % length``, so a ring never holds more
        than its window and never grows past it. Not with ``quantize``.
    page : None or type
        The page record of a model that keeps its own format
        (``decode_state_spec()["page"]``: :class:`StatePage`, a recurrent
        state of fixed size, :class:`LatentPage`, compressed rows in the
        place of K and V, or the model's own); by default
        :class:`Int8Page` with ``quantize``, else :class:`PlainPage`.
    """

    def __init__(self, layers, heads, head_dim, slots, max_capacity,
                 dtype=np.float32, quantize=False, windows=None, page=None):
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
        self.head_dim = tuple(int(d) for d in head_dim) \
            if np.ndim(head_dim) else int(head_dim)
        self.slots = int(slots)
        self.max_capacity = int(max_capacity)
        self.dtype = np.dtype(dtype)
        self.page = page or (Int8Page if quantize else PlainPage)
        # the record's word on what its read-out is (StatePage: the state)
        self.snapshots = bool(getattr(self.page, "snapshot", False))
        self.capacity = 0
        self.state = None     # list[L] of page records, once allocated
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
        """Positions a prompt of bucket ``tp`` leaves in each layer, by the
        layer's record: ``tp``, the whole ring where the prompt is longer,
        0 for a record without a time axis."""
        return [page.prompt_length(tp) for page in self.state]

    def page_bytes(self, tp):
        """Bytes of the K and V page of a prompt of bucket ``tp``, as the
        prefix store keeps it."""
        return sum(page.prompt_bytes(n)
                   for page, n in zip(self.state, self.page_lengths(tp)))

    def ensure_capacity(self, need):
        """Grow the buffers to the bucket that fits ``need`` (zero-padding
        the time axis — one migration dispatch per layer, then the decode
        program for the new capacity compiles once). Returns True when a
        migration happened — live programs for the old capacity stay
        cached, so shrinking traffic never re-migrates."""
        cap = self.capacity_bucket(need)
        if cap <= self.capacity and self.state is not None:
            return False
        if self.state is None:
            self.state = [
                self.page.zeros(self.slots, self.heads,
                                self.layer_length(i, cap), self.head_dim,
                                self.dtype) for i in range(self.layers)]
        else:
            # a ring that has reached its window stays as it is: until then
            # no position has wrapped, and padding keeps p % length == p
            self.state = [
                page.grow(self.layer_length(i, cap) - self.layer_length(i))
                for i, page in enumerate(self.state)]
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
        recompile: the batch layout is padded, not reshaped). The decode
        step gives a free slot the length 0: its page is not read."""
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

    def update(self, state, valid):
        """Install the state a compiled step returned (the old buffers
        were donated on TPU — they must not be touched again)."""
        self.state, self.valid = list(state), valid

    # ------------------------------------------------------------ accounting
    def nbytes(self):
        """Live page-buffer bytes (every leaf of the state: K, V, scales) —
        the measured side of the quantized-cache acceptance ratio."""
        return sum(int(a.nbytes) for a in jax.tree_util.tree_leaves(
            self.state))

    def nbytes_unquantized(self, itemsize=None):
        """What the SAME geometry would cost unquantized — the denominator
        of the ≤ 0.55× bytes acceptance check. ``itemsize`` defaults to the
        model dtype's (pass 2 to compare against a bf16 cache)."""
        if self.state is None:
            return 0
        itemsize = self.dtype.itemsize if itemsize is None else itemsize
        return sum(page.plain_bytes(itemsize) for page in self.state)


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
