"""Driver of a training window: the fused whole-step program.

The entry is ``parallel.build_train_step(loss_fn, opt)`` on the
configuration's model, built the way ``chip_smoke.py`` builds its training
step (bf16 parameters through ``amp.convert_hybrid_block``, fp32 masters in the
optimizer state, MLM through ``softmax_xent_rows`` + NSP). Set-up builds that
one object, drives its first four steps through the window's own feed (a pool
of distinct seeded host batches, one ``device_put`` a step), and hands the
same object to the window. In the window step i+1 is dispatched before loss i
is read; it closes on a host read of the last loss.

What ``correct`` compares, after the window, against the plain reference's
first three steps: each step's loss; the norm of the first gradient as the
optimizer got it (from Adam's first moment after one step) and the norm of
the parameters' change after three steps, both by the worst leaf; and the
direction of that first gradient (1 - cosine to the reference's) in the
median leaf of those kept whole, which is the number that a lower precision
moves (PERF.md gives the readings).
"""
import os
import shutil
import sys
import time

import numpy as np

from lib import build, loadgen, trace_reduce, weights
from lib.log import note

CHECK_STEPS = 3
SLICE_S = 3.0           # traced slice in the middle of the window


class Run:
    def __init__(self, cell, config, reference, seed, seconds, trace, devices,
                 t_process_start, scratch, control=None):
        self.cell, self.config, self.reference = cell, config, reference
        self.sizes, self.traffic = config["sizes"], cell["traffic"]
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.devices, self.t0 = devices, t_process_start
        self.trace_dir = os.path.join(scratch, "trace")
        self.control = control

    # ------------------------------------------------------------- set-up
    def set_up(self):
        import jax
        import jax.numpy as jnp

        import mxnet_tpu as mx
        from mxnet_tpu import _trace, amp, parallel
        from mxnet_tpu.ops.functional import softmax_xent_rows

        note("imports done")
        net = build.construct(self.config)
        amp.convert_hybrid_block(net, "bfloat16")
        plist = list(net.collect_params().values())
        self.specs = self.reference.param_specs(self.sizes)
        self.names = build.install_weights(
            self.config, plist,
            weights.make(self.seed, self.specs, jnp.bfloat16))
        self.order = [self.names[p.name] for p in plist]
        note("model built, weights made from the seed")
        o = self.config["optimizer"]
        opt = mx.optimizer.create(o["name"], **o["kwargs"])
        self.beta1 = float(getattr(opt, "beta1", 0.9))

        def loss_fn(param_arrays, batch, key):
            tok, tt, vl, mp, mlm_y, nsp_y = batch
            with _trace.trace_scope(key, True) as t:
                t.param_store = {id(p): a
                                 for p, a in zip(plist, param_arrays)}
                _seq, _pooled, nsp_logits, mlm_logits = net._call_traced(
                    tok, tt, vl, mp)
            nsp_lp = jax.nn.log_softmax(nsp_logits.astype(jnp.float32), -1)
            nsp_nll = -jnp.take_along_axis(nsp_lp, nsp_y[:, None], axis=-1)
            return (jnp.mean(softmax_xent_rows(mlm_logits, mlm_y))
                    + jnp.mean(nsp_nll))

        self.step = parallel.build_train_step(loss_fn, opt)
        params = [p.data()._data for p in plist]
        states = parallel.tree_optimizer_step(opt)[0](params)
        # committed to the device like everything the step returns, so that
        # its first call and all later ones are one signature and the step
        # compiles once
        params = jax.device_put(params, self.devices[0])
        states = jax.device_put(states, self.devices[0])
        self._put = lambda b: jax.device_put(b, self.devices[0])
        self.params, self.states = params, states

        pool = int(self.traffic["batch_pool"])
        self.batches = loadgen.train_batches(
            self.traffic, self.seed, self.sizes["vocab_size"], pool)
        base = jax.random.fold_in(weights.seed_key(self.seed), 7)
        self.key_data = [np.asarray(jax.random.fold_in(base, i))
                         for i in range(pool)]
        self.keys = [jax.device_put(k, self.devices[0])
                     for k in self.key_data]
        self.global_batch = int(self.traffic["batch"])

        # leaves whose first gradient is kept whole: the one-dimensional
        # ones, and every leaf of the first, the middle and the last layer
        layers = self.sizes["num_layers"]
        kept = tuple("layer%d_" % i for i in (0, layers // 2, layers - 1))
        self.keep = [n for n in self.order if n.startswith(kept)]
        self.small = [i for i, p in enumerate(plist)
                      if len(p.shape) == 1 or self.order[i] in self.keep]
        self.flat = [i for i, p in enumerate(plist) if len(p.shape) == 1]
        moment = lambda s: s["state"][0] if isinstance(s, dict) else s[0]
        latest = lambda s, p: s["master"] if isinstance(s, dict) else p

        @jax.jit
        def moment_norms(states):
            ms = [s["state"][0] if isinstance(s, dict) else s[0]
                  for s in states]
            return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
                m.astype(jnp.float32)))) for m in ms])

        @jax.jit
        def change_norms(states, params, start):
            now = [s["master"] if isinstance(s, dict) else p
                   for s, p in zip(states, params)]
            return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
                a.astype(jnp.float32) - start[n].astype(jnp.float32))))
                for a, n in zip(now, self.order)])

        # the first steps, through the window's own call and feed
        note("step built, optimizer state made, batches made")
        self.first_losses, self.i = [], 0
        for _ in range(CHECK_STEPS + 1):
            loss = self._feed()
            self.first_losses.append(float(loss))
            note("step %d done" % self.i)
            if self.i == 1:
                self.grad_norms = np.asarray(moment_norms(self.states)) \
                    / (1.0 - self.beta1)
                self.grad_vectors = {
                    self.order[k]: np.asarray(moment(self.states[k]),
                                              np.float32) / (1.0 - self.beta1)
                    for k in self.small}
            if self.i == CHECK_STEPS:
                start = weights.make(self.seed, self.specs, jnp.bfloat16)
                self.change_norms = np.asarray(
                    change_norms(self.states, self.params, start))
                self.change_vectors = {
                    self.order[k]: np.asarray(
                        latest(self.states[k], self.params[k]), np.float32)
                    - np.asarray(start[self.order[k]], np.float32)
                    for k in self.flat}
                del start

    def _feed(self):
        """One step of the timed path: the next host batch of the pool to
        the device, the step dispatched; returns its loss (not yet read)."""
        import jax.numpy as jnp

        j = self.i % len(self.batches)
        self.i += 1
        self.params, self.states, loss = self.step(
            self.params, self.states, jnp.int32(self.i), self.keys[j],
            self._put(self.batches[j]))
        return loss

    # ------------------------------------------------------------- window
    def window(self):
        import jax

        state, marks = ("before" if self.trace else "off"), {}
        slice_s = min(SLICE_S, self.seconds / 3.0)
        t_open = time.perf_counter()
        steps, pending = 0, self._feed()
        while True:
            elapsed = time.perf_counter() - t_open
            if state == "before" and elapsed >= self.seconds / 3.0:
                shutil.rmtree(self.trace_dir, ignore_errors=True)
                trace_reduce.start(self.trace_dir)
                with jax.profiler.TraceAnnotation(trace_reduce.MARK_START):
                    marks["start"] = time.perf_counter()
                state = "tracing"
            elif state == "tracing" and \
                    time.perf_counter() - marks["start"] >= slice_s:
                with jax.profiler.TraceAnnotation(trace_reduce.MARK_END):
                    marks["end"] = time.perf_counter()
                jax.profiler.stop_trace()
                state = "done"
            if state == "tracing":
                with jax.profiler.TraceAnnotation("bench[train_step]"):
                    nxt = self._feed()       # step i+1 is dispatched ...
            else:
                nxt = self._feed()
            last = float(pending)            # ... before loss i is read
            steps += 1
            pending = nxt
            if state in ("off", "done") and \
                    time.perf_counter() - t_open >= self.seconds:
                break
        last = float(pending)                # the window closes on this read
        steps += 1
        t_close = time.perf_counter()
        if not np.isfinite(last):
            raise SystemExit("benchmark: the loss is not finite at step %d"
                             % self.i)
        record = {
            "attempted": steps, "failed": 0,
            "scalars": {"setup_s": t_open - self.t0,
                        "window_s": t_close - t_open,
                        "samples_done": steps * self.global_batch,
                        "steps_done": steps},
            "samples": {}, "sizes": self.sizes, "traffic": self.traffic,
            "trace": None,
        }
        if self.trace:
            record["trace"] = trace_reduce.reduce_dir(self.trace_dir)
            shutil.rmtree(self.trace_dir, ignore_errors=True)
        return record

    def free(self):
        self.params = self.states = self.step = None

    # -------------------------------------------------------------- check
    def check(self):
        import jax.numpy as jnp

        limits = self.cell["limits"]
        start = weights.make(self.seed, self.specs, jnp.bfloat16)
        def follow(precision):
            batches = self.batches[:CHECK_STEPS]
            if precision == "half-batch":
                # a fault, not a precision: half of the batch left out, the
                # mean taken over the rest
                batches = [tuple(x[:len(x) // 2] for x in b) for b in batches]
                precision = "float32"
            lr = self.config["optimizer"]["kwargs"]["learning_rate"]
            if precision == "state-unchanged":
                # a fault: the step returns its state as it got it
                lr, precision = 0.0, "float32"
            return self.reference.train(
                self.sizes, start, batches,
                self.key_data[:CHECK_STEPS], precision=precision, lr=lr,
                row_block=int(self.traffic.get("reference_row_block", 8)),
                keep=tuple(self.keep))

        ref = follow("float32")
        got = (self.first_losses, self.grad_norms, self.change_norms,
               self.grad_vectors, self.change_vectors)
        if self.control:
            # the control: the reference in the lower precision stands in
            # for the program
            low = follow(self.control)
            got = (low["losses"],
                   [low["first_grad_norm"][n] for n in self.order],
                   [low["change_norm"][n] for n in self.order],
                   low["first_grad_vector"], low["change_vector"])
        return compare(ref, self.order, limits, *got)

    def close(self):
        shutil.rmtree(self.trace_dir, ignore_errors=True)


def worst_leaf_gap(got, ref, keep=None):
    """The widest gap between the program's norm and the reference's over
    the leaves, against the reference's norm of that leaf or of the median
    leaf, whichever is larger."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    floor = np.median(ref)
    gap = np.abs(got - ref) / np.maximum(ref, floor)
    if keep is not None:
        gap = gap[keep]
    return float(np.max(gap)), int(np.argmax(gap))


def live_norms(order, norms, vectors, ref_norms, ref_grad_vectors,
               ref_vectors):
    """The norms of both sides with, in the one-dimensional leaves, only
    the elements counted whose gradient in the reference is not nought to
    rounding (a thousandth of the leaf's median element or more): a fused
    bias holds the key's bias, which softmax leaves without a gradient, and
    such elements move under Adam by round-off alone."""
    got, ref = np.array(norms, np.float64), np.array(ref_norms, np.float64)
    for k, name in enumerate(order):
        if name in ref_vectors:
            g = np.abs(ref_grad_vectors[name])
            live = g >= 1e-3 * np.median(g)
            got[k] = np.linalg.norm(np.asarray(vectors[name])[live])
            ref[k] = np.linalg.norm(np.asarray(ref_vectors[name])[live])
    return got, ref


def compare(ref, order, limits, losses, grad_norms, change_norms,
            grad_vectors, change_vectors):
    """name -> (value, limit) of every number compared."""
    out = {}
    for i, want in enumerate(ref["losses"]):
        gap, key = abs(losses[i] - want) / abs(want), "loss%d_rel_gap" % (i + 1)
        if key in limits:
            out[key] = (gap, limits[key])
        else:
            # a loss that neither the control nor a fault moves has no upper
            # reading and could only fail sound runs: shown, not compared
            print("%s %.6g (not compared)" % (key, gap), file=sys.stderr)
    grad_norms, g_ref = live_norms(
        order, grad_norms, grad_vectors,
        [ref["first_grad_norm"][n] for n in order],
        ref["first_grad_vector"], ref["first_grad_vector"])
    change_norms, c_ref = live_norms(
        order, change_norms, change_vectors,
        [ref["change_norm"][n] for n in order],
        ref["first_grad_vector"], ref["change_vector"])
    gap, at = worst_leaf_gap(grad_norms, g_ref)
    print("grad1: worst leaf %s (reference norm %.3g, median leaf %.3g)"
          % (order[at], g_ref[at], np.median(g_ref)), file=sys.stderr)
    out["grad1_worst_leaf_gap"] = (gap, limits["grad1_worst_leaf_gap"])
    # a leaf whose gradient is nought to rounding in the reference moves
    # under Adam by round-off alone: left out of the change by this rule
    moved = g_ref >= 1e-3 * np.median(g_ref)
    # the direction of the first gradient, leaf by leaf of those kept whole:
    # 1 - cosine to the reference's, the dead elements left out as above
    turn = {}
    for name, g in ref["first_grad_vector"].items():
        g = np.asarray(g, np.float64).ravel()
        live = np.abs(g) >= 1e-3 * np.median(np.abs(g))
        mine = np.asarray(grad_vectors[name], np.float64).ravel()[live]
        if np.linalg.norm(g[live]) >= 1e-3 * np.median(g_ref):
            turn[name] = 1.0 - float(mine @ g[live]) / (
                np.linalg.norm(mine) * np.linalg.norm(g[live]) + 1e-300)
    worst = max(turn, key=turn.get)
    print("grad1 direction: worst leaf %s turns %.3g (not compared: one "
          "noisy leaf of the program reads above the control)"
          % (worst, turn[worst]), file=sys.stderr)
    out["grad1_median_leaf_turn"] = (float(np.median(list(turn.values()))),
                                     limits["grad1_median_leaf_turn"])
    gap, at = worst_leaf_gap(change_norms, c_ref, moved)
    kept = [n for n, m in zip(order, moved) if m]
    print("change3: worst leaf %s; %d leaves left out: %s"
          % (kept[at], len(order) - len(kept),
             [n for n, m in zip(order, moved) if not m][:6]), file=sys.stderr)
    out["change3_worst_leaf_gap"] = (gap, limits["change3_worst_leaf_gap"])
    return out
