"""Hierarchical allreduce: reduce-scatter on the fast axis, cross the slow
axis with only the scattered shard, all-gather back (Goyal-style two-level
allreduce; arXiv 1810.11112).

TPU topology gives two very different wires: ICI inside a slice (fast,
all-to-all capable) and DCN between slices/hosts (slow, per-host NICs).
A flat allreduce moves the full gradient over both; the hierarchy moves
the full gradient only over ICI and 1/ici_size of it over DCN:

    1. ``lax.psum_scatter`` within ``ici_axis``: each device ends up owning
       the ici-group sum of one 1/ici_size shard;
    2. the shard — optionally compressed — crosses ``dcn_axis``
       (``lax.psum``), or hops through the existing ``DistKVStore``
       dist_sync path when ``dcn='kvstore'`` (the ps-lite-shaped wire);
    3. ``lax.all_gather`` within ``ici_axis`` rebuilds the full reduced
       vector on every device.

Compression (the DCN-bandwidth lever) is *functional* error feedback:
the residual enters the program as an input and leaves as an output —
what quantization dropped this step is re-added next step, so small
gradients accumulate until they cross the representable range instead of
being lost (the 2-bit kvstore scheme generalized to fp16/int8).

Two reduction modes, one program shape:

* ``stacked``: input ``(W, n)`` — one row per worker, W = dcn*ici — the
  multi-worker sum the kvstore 'device' mode computes with ``_aggregate``;
  every collective does real cross-worker math (the dryrun-provable mode).
* replicated: input ``(n,)`` identical on every device (one local worker,
  e.g. a single-process Trainer) — the same data movement runs, scaled so
  the result is exact; on multi-host deployments the DCN leg is where the
  cross-process sum happens.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..base import _jit_backed
from ..parallel.mesh import shard_map


def _make_codec(compression):
    """compression dict -> (quantize, dequantize) pure fns for one shard.

    quantize(acc) -> (payload, new_residual); dequantize(payload) -> f32.
    ``acc`` is grad-shard + carried residual; the pair must satisfy
    acc == dequantize(payload) + new_residual exactly (error feedback)."""
    if compression is None:
        return None
    ctype = compression.get("type", "2bit")
    if ctype == "fp16":
        def quant(acc):
            q = acc.astype(jnp.float16)
            return q, acc - q.astype(jnp.float32)

        return quant, lambda q: q.astype(jnp.float32)
    if ctype == "int8":
        def quant(acc):
            # per-shard symmetric scale; a zero shard keeps scale 1 so the
            # division stays finite and the payload is exactly zero
            scale = jnp.maximum(jnp.max(jnp.abs(acc)) / 127.0, 1e-30)
            q = jnp.clip(jnp.round(acc / scale), -127, 127).astype(jnp.int8)
            deq = q.astype(jnp.float32) * scale
            return (q, scale), acc - deq

        def deq(payload):
            q, scale = payload
            return q.astype(jnp.float32) * scale

        return quant, deq
    if ctype == "2bit":
        t = float(compression.get("threshold", 0.5))

        def quant(acc):
            # same ternary {-t, 0, +t} scheme as kvstore._two_bit_quantize
            q = jnp.where(acc >= t, t,
                          jnp.where(acc <= -t, -t, jnp.zeros((), acc.dtype)))
            return q, acc - q

        return quant, lambda q: q
    raise ValueError("unsupported dist compression type %r "
                     "(fp16 / int8 / 2bit)" % (ctype,))


class HierarchicalAllreduce:
    """Two-level gradient reduction strategy over a named mesh.

    mesh:       jax Mesh carrying ``ici_axis`` (and ``dcn_axis`` if any)
    ici_axis:   fast axis (intra-slice ICI) — reduce-scatter / all-gather
    dcn_axis:   slow axis (cross-slice / cross-host); None = single level
    compression: None or {'type': 'fp16'|'int8'|'2bit', ...} applied to the
                DCN-crossing shard with error-feedback residuals
    average:    divide the stacked sum by W (mean semantics)
    dcn:        'jit' keeps the slow-axis psum inside the bucket program
                (one dispatch per bucket); 'kvstore' routes the scattered
                shard through ``DistKVStore`` push/pull — the existing
                dist_sync wire — at three dispatches per bucket
    """

    def __init__(self, mesh, ici_axis="dp", dcn_axis=None, compression=None,
                 average=False, dcn="jit"):
        if dcn not in ("jit", "kvstore"):
            raise ValueError("dcn must be 'jit' or 'kvstore', got %r" % dcn)
        self.mesh = mesh
        self.ici_axis = ici_axis
        self.dcn_axis = dcn_axis
        self.compression = dict(compression) if compression else None
        self.average = bool(average)
        self.dcn = dcn
        self.ici_size = int(mesh.shape[ici_axis])
        self.dcn_size = int(mesh.shape[dcn_axis]) if dcn_axis else 1
        self._codec = _make_codec(self.compression)
        self._kv = None
        self._progs = {}
        # cache-key identity: everything that changes the traced program
        self.key = ("hier", tuple(sorted(mesh.shape.items())), ici_axis,
                    dcn_axis, dcn,
                    tuple(sorted(self.compression.items()))
                    if self.compression else None, self.average)

    @property
    def world(self):
        return self.ici_size * self.dcn_size

    @property
    def needs_host_hop(self):
        return self.dcn == "kvstore"

    # ------------------------------------------------------------- layout
    def pad_to(self, n):
        """Bucket payloads pad to a multiple of the ici size so the
        reduce-scatter tiles evenly; deterministic in n (bucket-layout
        determinism is the zero-retrace contract)."""
        m = self.ici_size
        return ((n + m - 1) // m) * m

    def residual_init(self, n_pad):
        """Error-feedback state for one bucket: per-device shard residuals,
        laid out (dcn, ici, n_pad/ici) and sharded so each device owns its
        own row. None when compression is off (no state to carry)."""
        if self._codec is None:
            return None
        ns = n_pad // self.ici_size
        z = jnp.zeros((self.dcn_size, self.ici_size, ns), jnp.float32)
        return jax.device_put(z, NamedSharding(self.mesh,
                                               self._residual_spec()))

    def _residual_spec(self):
        return P(self.dcn_axis, self.ici_axis, None)

    # ----------------------------------------------------- traced bodies
    def _scaled_dcn_sum(self, x, stacked):
        """Cross the slow axis. Replicated mode divides by the group size
        (identical copies sum to size*x); stacked rows are distinct."""
        if self.dcn_axis is None:
            return x
        s = lax.psum(x, self.dcn_axis)
        return s if stacked else s / self.dcn_size

    def _local_stage1(self, x, residual, stacked):
        """reduce-scatter within ici (+ optional compress): one device's
        view. Returns (payload, new_residual) — payload is what crosses
        the slow axis."""
        rs = lax.psum_scatter(x, self.ici_axis, tiled=True)
        if not stacked:
            rs = rs / self.ici_size   # identical copies summed
        if self._codec is None:
            return rs, None
        quant, _ = self._codec
        acc = rs + residual[0, 0]
        payload, new_res = quant(acc)
        return payload, new_res[None, None]

    def _local_stage2(self, payload, stacked):
        """dequantize + slow-axis sum + ici all-gather: one device's view."""
        if self._codec is not None:
            _, deq = self._codec
            payload = deq(payload)
        d = self._scaled_dcn_sum(payload, stacked)
        out = lax.all_gather(d, self.ici_axis, tiled=True)
        if self.average and stacked:
            out = out / self.world
        return out

    def fused_body(self, stacked):
        """The whole exchange as one shard_map-able body
        ``(vec, residual) -> (out, new_residual)`` for ``dcn='jit'`` —
        embedded by the bucketer inside ONE jitted bucket program."""
        def body(x, residual):
            if stacked:
                x = x[0]              # my worker's row
            payload, new_res = self._local_stage1(x, residual, stacked)
            out = self._local_stage2(payload, stacked)
            return out, new_res

        return body

    def _wrap(self, body, stacked, with_residual, n_outs=2):
        in_vec = P((self.dcn_axis, self.ici_axis)
                   if self.dcn_axis else self.ici_axis, None) \
            if stacked else P()
        specs = [in_vec] + ([self._residual_spec()] if with_residual else [])
        r_spec = self._residual_spec()
        outs = tuple([P()] + [r_spec] * (n_outs - 1)) if n_outs > 1 else P()
        return shard_map(body, mesh=self.mesh, in_specs=tuple(specs),
                         out_specs=outs)

    # ---------------------------------------------------- standalone API
    def reduce(self, vec, residual=None, stacked=False):
        """Reduce one padded flat vector outside the bucketer (tests, the
        kvstore-DCN leg). ``vec``: (n_pad,) replicated, or (W, n_pad)
        stacked. Returns (out (n_pad,), new_residual)."""
        from ..engine import dist_compile_counter

        if self.needs_host_hop:
            return self._reduce_kvstore(vec, residual, stacked)
        key = ("fused", int(vec.shape[-1]), bool(stacked),
               residual is not None)
        prog = self._progs.get(key)
        if prog is None:
            body = self.fused_body(stacked)
            if residual is None:
                def nores(x):
                    # in-trace bump: fires at trace time only, the exact
                    # retrace proof (serve counter discipline)
                    dist_compile_counter.bump(note="dist:%s" % (key,))
                    out, _ = body(x, jnp.zeros((1, 1, 1), jnp.float32))
                    return out

                wrapped = self._wrap(nores, stacked, with_residual=False,
                                     n_outs=1)
                prog = _jit_backed(wrapped, tier="jit", hint="dist_reduce")
            else:
                def withres(x, r):
                    dist_compile_counter.bump(note="dist:%s" % (key,))
                    return body(x, r)

                wrapped = self._wrap(withres, stacked, with_residual=True)
                prog = _jit_backed(wrapped, tier="jit", hint="dist_reduce")
            self._progs[key] = prog
        if residual is None:
            return prog(vec), None
        return prog(vec, residual)

    # ------------------------------------------------- kvstore DCN hop
    def _kvstore(self):
        if self._kv is None:
            from ..kvstore import create as kv_create

            self._kv = kv_create("dist_sync")
        return self._kv

    def _reduce_kvstore(self, vec, residual, stacked):
        """Three-dispatch variant: stage-1 program (reduce-scatter +
        compress), a host hop of the *scattered shard only* through the
        DistKVStore dist_sync path (the cross-process sum on multi-host
        deployments; degenerate single-process it exercises the same
        wire), stage-2 program (dequantize + slow-axis sum + all-gather)."""
        from ..engine import dist_compile_counter
        from ..ndarray import NDArray

        n_pad = int(vec.shape[-1])
        key1 = ("kv1", n_pad, bool(stacked), residual is not None)
        prog1 = self._progs.get(key1)
        if prog1 is None:
            def stage1(x, r):
                dist_compile_counter.bump(note="dist:%s" % (key1,))
                if stacked:
                    x = x[0]
                payload, new_res = self._local_stage1(x, r, stacked)
                if self._codec is not None:
                    _, deq = self._codec
                    payload = deq(payload)   # host hop carries f32 shards
                else:
                    new_res = jnp.zeros((1, 1, 1), jnp.float32)
                return payload[None, None], new_res

            in_vec = P((self.dcn_axis, self.ici_axis)
                       if self.dcn_axis else self.ici_axis, None) \
                if stacked else P()
            r_spec = self._residual_spec()
            # BOTH outputs carry the per-device shard layout: the payload
            # is the sharded thing that crosses the wire
            prog1 = self._progs[key1] = _jit_backed(
                shard_map(stage1, mesh=self.mesh, in_specs=(in_vec, r_spec),
                          out_specs=(r_spec, r_spec)),
                tier="jit", hint="dist_kv_stage1")
        key2 = ("kv2", n_pad, bool(stacked))
        prog2 = self._progs.get(key2)
        if prog2 is None:
            def stage2(shards):
                dist_compile_counter.bump(note="dist:%s" % (key2,))
                # NOTE: codec already applied in stage 1 (the kvstore wire
                # carries the dequantized shard) — stage 2 is sum + gather
                d = self._scaled_dcn_sum(shards[0, 0], stacked)
                out = lax.all_gather(d, self.ici_axis, tiled=True)
                if self.average and stacked:
                    out = out / self.world
                return out

            prog2 = self._progs[key2] = _jit_backed(
                shard_map(stage2, mesh=self.mesh,
                          in_specs=(self._residual_spec(),), out_specs=P()),
                tier="jit", hint="dist_kv_stage2")
        if residual is None:
            residual = jnp.zeros(
                (self.dcn_size, self.ici_size, n_pad // self.ici_size),
                jnp.float32)
            residual = jax.device_put(
                residual, NamedSharding(self.mesh, self._residual_spec()))
            keep_res = False
        else:
            keep_res = True
        # stage 1 output spec: per-device shard rows (same layout as the
        # residual) — the sharded thing that crosses the wire
        shards, new_res = prog1(vec, residual)
        kv = self._kvstore()
        kvkey = "dist_shard_%d_%d" % (n_pad, int(stacked))
        # push/pull through the dist_sync store: cross-process allreduce of
        # the scattered shard only (ps-lite wire shape, DCN payload / ici)
        if kvkey in kv._store:
            kv._store[kvkey]._data = jnp.zeros_like(shards)
        else:
            kv.init(kvkey, NDArray(jnp.zeros_like(shards)))
        kv.push(kvkey, NDArray(shards))
        pulled = kv.pull(kvkey)
        reduced = jax.device_put(
            pulled._data, NamedSharding(self.mesh, self._residual_spec()))
        out = prog2(reduced)
        return out, (new_res if keep_res else None)


class FlatAllreduce:
    """The serialized baseline: one single-level psum over the replica
    axes, no hierarchy, no compression — what ``tools/dist_bench.py``
    measures the overlapped hierarchy against."""

    def __init__(self, mesh, axes=("dp",), average=False):
        self.mesh = mesh
        self.axes = tuple(axes)
        self.average = bool(average)
        self.world = 1
        for a in self.axes:
            self.world *= int(mesh.shape[a])
        self._codec = None
        self.dcn_axis = None
        self.key = ("flat", tuple(sorted(mesh.shape.items())), self.axes,
                    self.average)

    @property
    def needs_host_hop(self):
        return False

    def pad_to(self, n):
        return n

    def residual_init(self, n_pad):
        return None

    def fused_body(self, stacked):
        def body(x, residual):
            if stacked:
                out = lax.psum(x[0], self.axes)
                if self.average:
                    out = out / self.world
            else:
                out = x
            return out, residual

        return body

    def _wrap(self, body, stacked, with_residual, n_outs=2):
        in_vec = P(self.axes if len(self.axes) > 1 else self.axes[0],
                   None) if stacked else P()
        outs = (P(), P()) if n_outs > 1 else P()
        specs = (in_vec, P()) if with_residual else (in_vec,)
        return shard_map(body, mesh=self.mesh, in_specs=specs, out_specs=outs)
