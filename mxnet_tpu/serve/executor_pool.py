"""Bucketed compiled-executor pool — the serving analogue of CachedOp.

MXNet's model server runs ``Module.predict`` over a bound executor; every
new batch size rebinds (re-plans memory, re-launches kernel chains). The
TPU-native version pre-compiles the model's pure inference function at a
fixed set of batch-size *buckets* (the TVM-style "ahead-of-time compiled
shapes" discipline, arXiv 1802.04799) and pads each request batch up to the
smallest fitting bucket — the μ-cuDNN micro-batch decomposition idea
(arXiv 1804.04806) applied to request coalescing. Steady-state inference is
then ONE cached XLA dispatch per batch with zero retrace:

* ``engine.serve_compile_counter`` bumps inside the traced body, so it
  fires exactly when XLA re-traces — warmup compiles every bucket up
  front, and a steady request stream must not bump it again (the same
  proof-hook discipline as ``bulk_compile_counter``/``tape_compile_counter``);
* padded input buffers are donated to XLA on TPU backends (they are
  per-request scratch, so the output can reuse their HBM — "donated output
  reuse"); params are never donated (they serve the next request);
* multi-replica: parameters are placed once per device and batches are
  round-robined over replicas by the caller (server.py) — whole-batch
  replication, the inference-side complement of ``split_and_load``.

``symbol_infer_fn`` adapts a Symbol graph (Module / SymbolBlock) into the
pool's ``fn(params, *inputs)`` shape; hybridized gluon blocks hand off via
``HybridBlock.serving_fn()``.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from .. import engine
from ..base import is_tpu_backend, next_pow2  # noqa: F401  (re-export)


class PoolError(RuntimeError):
    """Misuse of the executor pool (shape/bucket mismatch)."""


class BucketedExecutor:
    """Compiled inference executors over a fixed bucket set.

    Parameters
    ----------
    fn : callable
        Pure ``fn(param_arrays, *inputs) -> output or list`` (eval mode).
    params_fn : callable
        Zero-arg callable returning the CURRENT list of parameter arrays —
        read per dispatch so a reloaded checkpoint serves without a pool
        rebuild (same shapes/dtypes = same compiled programs, no retrace).
    buckets : tuple of int or None
        Allowed padded batch sizes. None = power-of-two auto-bucketing:
        any request stream compiles at most log2(max_batch) programs
        instead of one per distinct size.
    devices : list or None
        Replica devices. None = current placement, single replica.
    donate : bool or None
        Donate the (padded, per-request) input buffers to XLA. Default: on
        for TPU backends, off elsewhere (CPU donation is a no-op + warning).
    """

    def __init__(self, fn, params_fn, buckets=None, devices=None,
                 donate=None, name="pool", batch_axis=0, pad=True):
        if batch_axis != 0:
            raise PoolError("bucketing is defined on batch axis 0")
        self.name = name
        self.buckets = tuple(sorted(set(int(b) for b in buckets))) \
            if buckets else None
        # pad=False: exact-signature mode — every batch size is its own
        # "bucket" (no zero-row padding). For callers that cannot declare
        # which inputs carry a batch axis (SymbolBlock's general graphs):
        # still one cached program per signature instead of a per-call
        # evaluation walk, but padding semantics are never assumed.
        self._pad = bool(pad)
        self._params_fn = params_fn
        self._devices = list(devices) if devices else [None]
        self._placed = {}   # replica idx -> (param-identity token, arrays)
        self._rr = 0
        self._in_dtypes = None   # captured at first dispatch / warmup
        self._row_outputs = None  # per-output: leading dim == bucket?

        def traced(params, *xs):
            # executes at TRACE time only: one bump per program build is the
            # zero-retrace proof tests/test_serve.py asserts. A snapshot
            # warm start (serve.load(snapshot=True)) never traces at all —
            # deserialized executables are adopted directly — so this
            # counter reads 0 from process start to first request there.
            engine.serve_compile_counter.bump()
            out = fn(params, *xs)
            return list(out) if isinstance(out, (list, tuple)) else [out]

        if donate is None:
            donate = is_tpu_backend()
        self._donate = bool(donate)
        # per-signature AOT dispatch (cache.AotFn): explicit lower/compile
        # per bucket so every bucket program has an exportable executable
        # handle (Tier B snapshots) and a persistent disk tier under it
        # (Tier A) — jax.jit's internal cache can do neither. One wrapper
        # per (replica, donating): a Compiled is specialized to its
        # arguments' device placement, so replicas cannot share one.
        self._aots = {}
        self._fn = traced

    # ------------------------------------------------------------ buckets
    def pick_bucket(self, n):
        """Smallest configured bucket that fits ``n`` rows (power-of-two
        round-up in auto mode). Larger-than-max requests are the batcher's
        job to split; a direct caller gets a typed error."""
        if n <= 0:
            raise PoolError("empty batch")
        if not self._pad:
            return n
        if self.buckets is None:
            return next_pow2(n)
        for b in self.buckets:
            if n <= b:
                return b
        raise PoolError("batch of %d rows exceeds the largest bucket %d"
                        % (n, self.buckets[-1]))

    @property
    def max_bucket(self):
        return self.buckets[-1] if self.buckets else None

    @property
    def num_replicas(self):
        return len(self._devices)

    # ------------------------------------------------------------ params
    def _replica_params(self, r):
        cur = self._params_fn()
        token = tuple(map(id, cur))
        dev = self._devices[r]
        hit = self._placed.get(r)
        if hit is not None and hit[0] == token:
            return hit[1]
        arrs = list(cur) if dev is None else jax.device_put(list(cur), dev)
        self._placed[r] = (token, arrs)
        return arrs

    def next_replica(self):
        r = self._rr % len(self._devices)
        self._rr += 1
        return r

    # ------------------------------------------------------------ dispatch
    def _prepare(self, inputs, bucket):
        """Host-side pad-to-bucket: numpy concat+zeros (no device ops), one
        transfer per input. Dtypes are pinned to the first-seen signature so
        a stray float64 request can never force a retrace."""
        if self._in_dtypes is None:
            self._in_dtypes = [np.asarray(x).dtype for x in inputs]
        prepped = []
        for x, dt in zip(inputs, self._in_dtypes):
            x = np.asarray(x, dtype=dt)
            n = x.shape[0]
            if n != bucket:
                pad = np.zeros((bucket - n,) + x.shape[1:], dtype=dt)
                x = np.concatenate([x, pad], axis=0)
            prepped.append(x)
        return prepped

    def _dispatch(self, inputs, replica, donate_ok=True):
        """One cached-program call. ``donate_ok`` is False when the inputs
        are caller-owned buffers (run_device without padding) — donating
        those would invalidate arrays the caller still holds."""
        dev = self._devices[replica]
        params = self._replica_params(replica)
        xs = [jnp.asarray(x) if dev is None else jax.device_put(x, dev)
              for x in inputs]
        engine.dispatch_counter.bump()
        return self._exec_for(replica, len(xs), donate_ok)(params, *xs)

    def _exec_for(self, replica, n_inputs, donate_ok):
        """The AOT wrapper a dispatch routes through: one per (replica,
        donating) — the donating variant on TPU (padded inputs are
        per-request scratch), the plain one elsewhere / for caller-owned
        buffers."""
        donating = bool(self._donate and donate_ok)
        aot = self._aots.get((replica, donating))
        if aot is None:
            from ..cache import AotFn

            aot = self._aots[(replica, donating)] = AotFn(
                self._fn,
                donate_argnums=(tuple(range(1, 1 + n_inputs))
                                if donating else ()),
                tier="serve",
                hint="%s:r%d%s" % (self.name, replica,
                                   ":donated" if donating else ""))
        return aot

    def run(self, inputs, n_real=None, replica=None, traces=None):
        """Execute a coalesced batch: pad to bucket, one cached dispatch,
        host-gather, slice off the pad rows. ``inputs`` share leading batch
        dim; returns a list of numpy outputs with ``n_real`` rows each
        (row-aligned outputs only — others returned whole).

        ``traces``: the coalesced requests' RequestTraces — each gets the
        shared ``pad`` (host pad-to-bucket) and ``dispatch`` (compiled
        call + host gather) spans closed, three clock reads per BATCH."""
        import time as _time

        n = int(np.asarray(inputs[0]).shape[0])
        n_real = n if n_real is None else int(n_real)
        bucket = self.pick_bucket(n)
        if replica is None:
            replica = self.next_replica()
        from .. import profiler
        t_pad0 = _time.perf_counter() if traces else None
        prepped = self._prepare(inputs, bucket)
        t_disp0 = _time.perf_counter() if traces else None
        if profiler.is_running():
            with profiler.serve_scope(bucket, n_real):
                outs = self._dispatch(prepped, replica)
        else:
            outs = self._dispatch(prepped, replica)
        # host gather: what the caller (a serving response) needs, and
        # what closes the dispatch span
        outs = [np.asarray(o) for o in outs]
        if traces:
            t_done = _time.perf_counter()
            for tr in traces:
                tr.add_span("pad", t_pad0, t_disp0, bucket=bucket)
                tr.add_span("dispatch", t_disp0, t_done, bucket=bucket,
                            rows=n_real, replica=replica)
        if self._row_outputs is None:
            self._row_outputs = [o.ndim >= 1 and o.shape[0] == bucket
                                 for o in outs]
        return [o[:n_real] if row else o
                for o, row in zip(outs, self._row_outputs)]

    def run_device(self, inputs, n_real=None, replica=None):
        """Device-resident variant for framework callers (SymbolBlock
        inference, Module.predict): inputs/outputs stay jax arrays — pad
        and slice are tiny XLA ops bracketing the same cached bucket
        program, no host round-trip. Never donates (unpadded inputs are
        caller-owned buffers)."""
        n = int(inputs[0].shape[0]) if getattr(inputs[0], "ndim", 0) >= 1 \
            else 1
        n_real = n if n_real is None else int(n_real)
        bucket = self.pick_bucket(n)
        if replica is None:
            replica = self.next_replica()
        if self._in_dtypes is None:
            self._in_dtypes = [np.dtype(x.dtype) for x in inputs]
        prepped = []
        for x, dt in zip(inputs, self._in_dtypes):
            if x.dtype != dt:
                x = x.astype(dt)
            if n != bucket:
                pad = jnp.zeros((bucket - n,) + tuple(x.shape[1:]), dt)
                x = jnp.concatenate([x, pad], axis=0)
            prepped.append(x)
        from .. import profiler
        if profiler.is_running():
            with profiler.serve_scope(bucket, n_real):
                outs = self._dispatch(prepped, replica, donate_ok=False)
        else:
            outs = self._dispatch(prepped, replica, donate_ok=False)
        if self._row_outputs is None:
            self._row_outputs = [getattr(o, "ndim", 0) >= 1
                                 and o.shape[0] == bucket for o in outs]
        return [o[:n_real] if row and bucket != n_real else o
                for o, row in zip(outs, self._row_outputs)]

    @property
    def row_aligned(self):
        """True when every output carries the batch on axis 0 (known after
        the first dispatch/warmup) — the precondition for slicing padded
        rows off per request."""
        return self._row_outputs is not None and all(self._row_outputs)

    def warmup(self, input_specs, buckets=None):
        """Compile every (bucket, replica) program up front with zero-filled
        inputs. ``input_specs``: per input, (sample_shape, dtype) — shapes
        WITHOUT the batch dim. After warmup, serving is dispatch-only:
        ``engine.serve_compile_counter`` stays flat."""
        bs = buckets or self.buckets
        if bs is None:
            raise PoolError("warmup needs an explicit bucket list in "
                            "auto-bucket mode")
        self._in_dtypes = [np.dtype(dt) for _, dt in input_specs]
        for b in bs:
            zeros = [np.zeros((b,) + tuple(shape), dtype=dt)
                     for shape, dt in input_specs]
            for r in range(len(self._devices)):
                self.run(zeros, n_real=b, replica=r)
        return self

    # ------------------------------------------------ snapshot interface
    def _bucket_sig(self, aot, bucket, input_specs):
        """Call signature of a bucket dispatch, computed from shape specs
        (no arrays, no trace): (params, *padded_inputs)."""
        params = [jax.ShapeDtypeStruct(tuple(p.shape), p.dtype)
                  for p in self._params_fn()]
        xs = [jax.ShapeDtypeStruct((int(bucket),) + tuple(shape),
                                   np.dtype(dt))
              for shape, dt in input_specs]
        return aot.sig_of(params, *xs)

    def export_executables(self, input_specs, buckets):
        """Every warmed bucket's compiled executable, tagged for the
        snapshot manifest: [{key, bucket, donating, compiled}]. Replica 0
        only — a snapshot-warmed replica is a fresh single-device process
        (the horizontal-autoscale unit); extra replicas compile lazily."""
        out = []
        for donating in (False, True):
            aot = self._aots.get((0, donating))
            if aot is None:
                continue
            for b in buckets:
                c = aot.compiled_for(self._bucket_sig(aot, b, input_specs))
                if c is not None:
                    out.append({"key": "b%d_d%d" % (b, int(donating)),
                                "bucket": int(b),
                                "donating": bool(donating),
                                "compiled": c})
        return out

    def preload_executables(self, entries, input_specs):
        """Adopt deserialized bucket executables (snapshot warm start): no
        trace, no compile. Entries that don't match the live signature are
        caught at first dispatch (AotFn recompiles with one warning)."""
        for e in entries:
            aot = self._exec_for(0, len(input_specs),
                                 donate_ok=e["donating"])
            aot.adopt(e["compiled"],
                      self._bucket_sig(aot, e["bucket"], input_specs))

    def export_state(self):
        """Host-side pool state a snapshot must carry so a warm start
        needs no proving dispatch (warmup also exists to learn these)."""
        return {"in_dtypes": [str(np.dtype(dt)) for dt in self._in_dtypes]
                if self._in_dtypes else None,
                "row_outputs": self._row_outputs,
                "donate": self._donate}

    def restore_state(self, state):
        if state.get("in_dtypes"):
            self._in_dtypes = [np.dtype(d) for d in state["in_dtypes"]]
        if state.get("row_outputs") is not None:
            self._row_outputs = [bool(r) for r in state["row_outputs"]]
        if state.get("donate") is not None:
            # the exporter's donation decision rode into the executables;
            # dispatch must route the same way or warm start would retrace
            self._donate = bool(state["donate"])


def symbol_infer_fn(outputs, input_names, param_names=None):
    """Adapt a Symbol graph to the pool's ``fn(params, *inputs)`` contract.

    Returns ``(fn, param_names)`` for the EVAL-mode clone of the graph, or
    ``(None, None)`` when the eval graph still draws randomness at run time
    (mode='always' dropout etc.) — those need fresh noise per call and must
    stay on the per-call evaluation path.
    """
    from ..symbol import (Group, _graph_has_rng, _ir_infer_runner,
                          _with_training)

    combined = outputs[0] if len(outputs) == 1 else Group(list(outputs))
    ev = _with_training(combined, False)
    if _graph_has_rng(ev):
        return None, None
    # prefer the unified-IR runner: the pass-optimized graph (CSE/fold/
    # cast-sink/DCE, mxnet_tpu.ir) compiles per bucket instead of the raw
    # per-call evaluation walk; graphs the IR can't represent (control
    # flow, multi-output ops) keep the legacy _build_fn closure
    ir_runner = _ir_infer_runner(ev)
    if ir_runner is not None:
        inner, names = ir_runner
    else:
        inner, names = ev._build_fn()
    input_names = list(input_names)
    if param_names is None:
        param_names = [n for n in names if n not in input_names]
    order = []  # positional plan: ('p', i) from params, ('x', i) from inputs
    for n in names:
        if n in input_names:
            order.append(("x", input_names.index(n)))
        else:
            order.append(("p", param_names.index(n)))

    def fn(params, *xs):
        vals = [params[i] if kind == "p" else xs[i] for kind, i in order]
        return inner(*vals)

    return fn, list(param_names)
