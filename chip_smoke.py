#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user calls, in ONE
process (a chip belongs to one process at a time), on one TPU chip:

  phase A  trainer takes steps: BERT-base (12 x 768, vocab 30522) at batch
           64 x seq 128, bf16 compute + fp32 master weights, MLM + NSP loss,
           Adam, the fused ``parallel.build_train_step`` program; then the
           same model through the gluon user path (hybridize, autograd.record,
           backward, Trainer.step);
  phase B  server answers requests: GPT-2 small (12 x 768, vocab 50257,
           context 1024) in bf16 under ``serve.GenerativeServer`` with 8
           slots, prompts of 16..600 tokens, greedy, checked against
           ``GPTModel.generate`` and against a second identical wave;
  phase C  imperative sanity: ``nd.*`` ops and one autograd pass on
           ``mx.tpu()`` against NumPy.

``--chips 4`` runs ONLY the multi-chip phase and what it is compared with:
the phase-A step over a ``{"dp": 4}`` mesh against the same steps on one
device, and one ``dist.attach`` trainer against the plain one.

Weights and data are random, made from ``--seed``. Every check that fails
raises; nothing is caught. The last line of stdout is one JSON object,
``{"ok": true, "device": {...}}``; everything else worth reading is printed
before it. On anything but a TPU the script exits non-zero at once.

The phases are functions that take their sizes as arguments:
tests/test_chip_smoke.py calls them at tiny sizes on the CPU.
"""
import argparse
import json
import sys
import time

_T0 = time.perf_counter()

# A greedy token of the server is held to the reference only while the
# reference's own top-2 logit margin stays above this: both sides compute in
# bf16 (8 mantissa bits) through 12 layers, in differently shaped programs
# (padded flash prefill vs exact-length dense), and seeded random weights put
# the top two of 50257 logits within a few hundredths of each other.
BF16_MARGIN_TOL = 0.05
# |loss(4-chip mesh) - loss(1 device)| at every step, about 1 % of a loss
# near 11: same seed, same global batch, bf16 compute; each device rounds its
# own partial gradients to bf16 before the all-reduce sums them, the mesh
# program takes the XLA formulations where the single device takes the Pallas
# kernels, and Adam's first steps (update ~ lr * sign(g)) amplify both.
DP_LOSS_TOL = 0.1


def say(msg):
    print("[chip_smoke] %7.1fs %s" % (time.perf_counter() - _T0, msg),
          flush=True)


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def _fmt(xs):
    return " ".join("%.4f" % x for x in xs)


# ------------------------------------------------------------------ phase A
def build_bert_step(make_model, seed, mesh=None):
    """The BERT pretraining step as bench.py builds it — bf16 params, fp32
    masters in the Adam state, MLM through ``softmax_xent_rows`` + NSP —
    compiled by ``parallel.build_train_step`` (over ``mesh`` when given).
    Returns (net, plist, step, params, states)."""
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import _trace, amp, parallel
    from mxnet_tpu.ops.functional import softmax_xent_rows

    mx.random.seed(seed)
    net = make_model()
    net.initialize()
    amp.convert_hybrid_block(net, "bfloat16")
    plist = list(net.collect_params().values())
    opt = mx.optimizer.Adam(learning_rate=1e-4, multi_precision=True)

    def loss_fn(param_arrays, batch, key):
        tok, tt, vl, mp, mlm_y, nsp_y = batch
        with _trace.trace_scope(key, True) as t:
            t.param_store = {id(p): a for p, a in zip(plist, param_arrays)}
            _seq, _pooled, nsp_logits, mlm_logits = net._call_traced(
                tok, tt, vl, mp)
        nsp_lp = jax.nn.log_softmax(nsp_logits.astype(jnp.float32), axis=-1)
        nsp_nll = -jnp.take_along_axis(nsp_lp, nsp_y[:, None], axis=-1)
        return (jnp.mean(softmax_xent_rows(mlm_logits, mlm_y))
                + jnp.mean(nsp_nll))

    step = parallel.build_train_step(loss_fn, opt, mesh=mesh)
    params = [p.data()._data for p in plist]
    states = parallel.tree_optimizer_step(opt)[0](params)
    return net, plist, step, params, states


def make_bert_batch(seed, vocab, batch, seq, masked):
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.integers(0, vocab, (batch, seq)), jnp.int32),
            jnp.zeros((batch, seq), jnp.int32),
            jnp.full((batch,), seq, jnp.float32),
            jnp.asarray(rng.integers(0, seq, (batch, masked)), jnp.int32),
            jnp.asarray(rng.integers(0, vocab, (batch, masked)), jnp.int32),
            jnp.asarray(rng.integers(0, 2, (batch,)), jnp.int32))


def run_steps(step, params, states, batch, steps, seed, kernels=()):
    """Compile ``step`` once (ahead of time, so its text can be read), take
    ``steps`` steps on the repeated ``batch``; returns (params, states,
    losses, the clock at the first loss). Fails unless every name in
    ``kernels`` is in the compiled program."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    key = jax.random.PRNGKey(seed)
    compiled = step.lower(params, states, jnp.int32(1), key, batch).compile()
    text = compiled.as_text()
    for name in kernels:
        check(name in text, "the compiled train step does not contain the "
              "%r kernel: a gate chose the jnp branch" % name)
    losses, first_at = [], None
    for i in range(steps):
        params, states, loss = compiled(params, states, jnp.int32(i + 1),
                                        key, batch)
        losses.append(float(loss))          # host read closes the step
        first_at = first_at or time.perf_counter()
    check(np.all(np.isfinite(losses)), "non-finite loss: %r" % (losses,))
    return params, states, losses, first_at


def phase_train(make_model, vocab, batch, seq, masked, steps, gluon_batch,
                seed, check_kernels=True):
    import numpy as np

    from mxnet_tpu import autograd, engine, gluon, nd

    t_phase = time.perf_counter()
    net, plist, step, params, states = build_bert_step(make_model, seed)
    say("A: model built: %d parameter tensors, %.1f M parameters"
        % (len(plist), sum(int(np.prod(p.shape)) for p in plist) / 1e6))
    data = make_bert_batch(seed, vocab, batch, seq, masked)
    kernels = ("layernorm_fwd", "softmax_xent_fwd", "softmax_xent_bwd") \
        if check_kernels else ()
    params, states, losses, first_at = run_steps(
        step, params, states, data, steps, seed, kernels)
    say("A: seconds_to_first_train_step %.1f (model build, compile and "
        "step 1, from the start of the phase)" % (first_at - t_phase))
    say("A: fused step, batch %d x seq %d, %d steps on one batch, losses %s"
        % (batch, seq, steps, _fmt(losses)))
    if kernels:
        say("A: the compiled step contains %s" % ", ".join(kernels))
    check(losses[-1] < losses[0],
          "loss did not fall on a repeated batch: %r" % (losses,))

    # the step donated the arrays the Parameters held: hand the trained ones
    # back (the contract the fused update and dist use), then drive the same
    # model through the gluon user path
    for p, a in zip(plist, params):
        p.data()._data = a
    del params, states
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 1e-4, "multi_precision": True})
    xent = gluon.loss.SoftmaxCrossEntropyLoss()
    tok, tt, vl, mp, mlm_y, nsp_y = (
        nd.array(np.asarray(a)[:gluon_batch], dtype=a.dtype) for a in data)
    watched = plist[-1]
    before = watched.data().asnumpy().astype(np.float32)
    d0 = engine.dispatch_counter.count
    glosses = []
    for _ in range(2):
        with autograd.record():
            _seq, _pooled, nsp_logits, mlm_logits = net(tok, tt, vl, mp)
            loss = (xent(mlm_logits, mlm_y).mean()
                    + xent(nsp_logits, nsp_y).mean())
        loss.backward()
        trainer.step(gluon_batch)
        glosses.append(float(loss.asnumpy()))
    say("A: gluon path (hybridize, record, backward, Trainer.step), batch "
        "%d, losses %s, %d dispatches"
        % (gluon_batch, _fmt(glosses), engine.dispatch_counter.count - d0))
    check(np.all(np.isfinite(glosses)),
          "non-finite gluon loss: %r" % (glosses,))
    after = watched.data().asnumpy().astype(np.float32)
    check(not np.array_equal(before, after),
          "Trainer.step left %s unchanged" % watched.name)
    return {"losses": losses, "gluon_losses": glosses}


# ------------------------------------------------------------------ phase B
def reference_tokens(model, prompt, new_tokens):
    """Greedy tokens of ``GPTModel.generate`` on ``prompt`` and, for each,
    the reference's own two best tokens and the margin between their logits
    (teacher-forced over the same tokens through the public prefill/step
    pair generate is made of)."""
    import numpy as np

    from mxnet_tpu import nd
    from mxnet_tpu.base import next_pow2

    t0 = len(prompt)
    ids = nd.array(np.asarray(prompt, np.int32)[None], dtype="int32")
    out = model.generate(ids, max_new_tokens=new_tokens).asnumpy()[0]
    toks = [int(t) for t in out[t0:]]
    cap = min(model.decode_state_spec()["max_length"],
              next_pow2(t0 + new_tokens))
    logits, caches = model.prefill(ids, model.init_cache(1, capacity=cap))
    best2, margins = [], []
    for i, tok in enumerate(toks):
        row = logits.asnumpy().astype(np.float32)[0]
        second, first = np.argsort(row)[-2:]
        best2.append((int(first), int(second)))
        margins.append(float(row[first] - row[second]))
        if i + 1 < len(toks):
            logits, caches = model.step(
                nd.array([[tok]], dtype="int32"), caches, t0 + i)
    return toks, best2, margins


def phase_serve(make_model, vocab, prompt_lens, new_tokens, slots, seed,
                compare, flash_len=None):
    """``compare``: indices into ``prompt_lens`` of the requests held to
    ``GPTModel.generate``. ``flash_len``: the prefill bucket whose compiled
    program must contain the flash kernel (None = do not look)."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import engine, serve
    from mxnet_tpu.base import next_pow2

    t_phase = time.perf_counter()
    mx.random.seed(seed)
    model = make_model()
    model.initialize()
    model.cast("bfloat16")
    model.hybridize()
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, vocab, (n,)).astype(np.int32)
               for n in prompt_lens]
    buckets = {}
    for n in prompt_lens:                    # one length per pow2 bucket
        buckets[next_pow2(n)] = max(n, buckets.get(next_pow2(n), 0))
    srv = serve.GenerativeServer(model, slots=slots, timeout_ms=600000.0)
    srv.warmup(prompt_buckets=sorted(buckets.values()),
               max_tokens=max(prompt_lens) + new_tokens)
    c_warm = engine.decode_compile_counter.count
    say("B: model built and warmed: prompt buckets %s, capacity %d, %d "
        "programs traced" % (sorted(buckets), srv.cache.capacity, c_warm))

    def wave():
        streams = [srv.submit(p, max_new_tokens=new_tokens) for p in prompts]
        first = next(iter(streams[0]))
        t_first = time.perf_counter()
        out = [s.result(timeout_s=600.0) for s in streams]
        check(out[0][0] == first, "stream and result disagree")
        return out, t_first

    with srv:
        wave1, t_first = wave()
        c1 = engine.decode_compile_counter.count
        wave2, _ = wave()
        c2 = engine.decode_compile_counter.count
        stats = srv.stats()
        texts = {e["key"]: e["compiled"].as_text()
                 for e in srv.export_executables()} \
            if flash_len is not None else {}
    say("B: seconds_to_first_token %.1f (model build, warm-up compiles and "
        "prefill, from the start of the phase)" % (t_first - t_phase))
    for n, toks in zip(prompt_lens, wave1):
        check(len(toks) == new_tokens and all(0 <= t < vocab for t in toks),
              "request of %d tokens answered %r" % (n, toks))
    say("B: %d requests of %s prompt tokens, %d new tokens each, all "
        "resolved; first request: %s ..."
        % (len(prompts), list(prompt_lens), new_tokens, wave1[0][:8]))
    say("B: compiles: %d in warm-up, %d in wave 1, %d in wave 2; prefix "
        "hits %s, prefills %s; host clock: ttft p50 %s ms, gap between "
        "tokens p50 %s ms"
        % (c_warm, c1 - c_warm, c2 - c1, stats.get("prefix_hits"),
           stats.get("prefills"), stats.get("ttft_p50_ms"),
           stats.get("itl_p50_ms")))
    check(wave2 == wave1, "the second wave of the same prompts gave other "
          "tokens: %r vs %r" % (wave2, wave1))
    check(c2 == c1, "%d programs compiled in the second wave" % (c2 - c1))
    if flash_len is not None:
        key = "prefill@t%dc%d" % (flash_len, srv.cache.capacity)
        check("flash_fwd" in texts[key],
              "%s does not contain the flash kernel" % key)
        step_key = "decode@c%d" % srv.cache.capacity
        check("layernorm_fwd" in texts[step_key],
              "%s does not contain the LayerNorm kernel" % step_key)
        say("B: %s contains flash_fwd, %s contains layernorm_fwd"
            % (key, step_key))

    for i in compare:
        ref, best2, margins = reference_tokens(model, prompts[i], new_tokens)
        got = wave1[i]
        # equal while the reference's top two are clearly apart; at the
        # first near-tie the server may pick either of the two, and from
        # there the two sequences are free to part
        held = next((j for j, m in enumerate(margins)
                     if m < BF16_MARGIN_TOL), len(ref))
        say("B: request %d (%d prompt tokens) vs GPTModel.generate: %d of "
            "%d tokens equal; held equal for the first %d (top-2 margins "
            "%s), then to the reference's top two"
            % (i, prompt_lens[i], sum(a == b for a, b in zip(ref, got)),
               len(ref), held,
               " ".join("%.3f" % m for m in margins[:held + 1])))
        check(got[:held] == ref[:held]
              and (held == len(ref) or got[held] in best2[held]),
              "request %d: server %r, reference %r, top two %r, margins %r"
              % (i, got, ref, best2, margins))
    return {"tokens": wave1, "compiles_wave2": c2 - c1}


# ------------------------------------------------------------------ phase C
def phase_imperative(ctx, seed):
    """A dozen ``nd.*`` ops and one autograd pass on ``ctx`` against NumPy;
    every result lives on ``ctx``'s device."""
    import numpy as np

    from mxnet_tpu import autograd, nd

    rng = np.random.default_rng(seed)
    xa = rng.standard_normal((64, 128)).astype(np.float32)
    xb = rng.standard_normal((64, 128)).astype(np.float32)
    xw = rng.standard_normal((128, 32)).astype(np.float32)
    a, b, w = (nd.array(x, ctx=ctx) for x in (xa, xb, xw))
    e = np.exp(xa - xa.max(-1, keepdims=True))
    cases = {
        "add": (a + b, xa + xb),
        "mul_scalar": (a * 2.5, xa * 2.5),
        "dot": (nd.dot(a, w), xa @ xw),
        "relu": (nd.relu(a), np.maximum(xa, 0)),
        "exp": (nd.exp(a), np.exp(xa)),
        "tanh": (nd.tanh(a), np.tanh(xa)),
        "softmax": (nd.softmax(a, axis=-1), e / e.sum(-1, keepdims=True)),
        "sum_axis": (nd.sum(a, axis=1), xa.sum(1)),
        "mean": (nd.mean(a), xa.mean()),
        "max": (nd.max(a, axis=0), xa.max(0)),
        "transpose": (nd.transpose(a), xa.T),
        "concat": (nd.concat(a, b, dim=1), np.concatenate([xa, xb], 1)),
        "reshape_slice": (nd.reshape(a, shape=(128, 64))[3:7],
                          xa.reshape(128, 64)[3:7]),
        "argmax": (nd.argmax(a, axis=1), xa.argmax(1)),
    }
    device = ctx.jax_device()
    # a float32 matmul runs at the MXU's default precision on the chip: one
    # bf16 pass, so a 128-term dot of unit normals is off by up to ~0.1
    mxu = dict(rtol=2e-2, atol=0.25)
    for name, (got, want) in cases.items():
        tol = mxu if name == "dot" else dict(rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(got.asnumpy(), want, err_msg=name, **tol)
        check(got._data.devices() == {device},
              "%s landed on %s, not %s" % (name, got._data.devices(), device))
    x = nd.array(0.1 * xa, ctx=ctx)
    x.attach_grad()
    with autograd.record():
        y = nd.sum(nd.tanh(nd.dot(x, w)) ** 2)
    y.backward()
    t = np.tanh(0.1 * xa @ xw)
    np.testing.assert_allclose(x.grad.asnumpy(), (2 * t * (1 - t * t)) @ xw.T,
                               err_msg="autograd", **mxu)
    check(x.grad._data.devices() == {device}, "the gradient left the device")
    say("C: %d nd ops and one record/backward agree with NumPy on %s"
        % (len(cases), device))
    return {"ops": len(cases)}


# ------------------------------------------------------- --chips 4 phases
def phase_train_dp(make_model, vocab, batch, seq, masked, steps, seed,
                   devices):
    """The phase-A step over a ``dp`` mesh of ``devices``, batch sharded and
    parameters replicated, against the same steps on ``devices[0]``."""
    import jax
    import numpy as np

    from mxnet_tpu import parallel

    n = len(devices)
    mesh = parallel.make_mesh({"dp": n}, devices=devices)
    data = make_bert_batch(seed, vocab, batch, seq, masked)

    _net, _plist, step, params, states = build_bert_step(make_model, seed,
                                                         mesh=mesh)
    params = parallel.replicate_params(params, mesh)
    states = parallel.replicate_params(states, mesh)
    sharded = parallel.shard_batch(data, mesh)
    for x in sharded:
        held = {s.device for s in x.addressable_shards}
        check(held == set(devices) and all(
            s.data.shape[0] == batch // n for s in x.addressable_shards),
            "batch shards sit on %s" % sorted(d.id for d in held))
    params, states, mesh_losses, _ = run_steps(step, params, states, sharded,
                                               steps, seed)
    for a in jax.tree_util.tree_leaves((params, states)):
        check(a.sharding.device_set == set(devices),
              "a trained array lives on %s only"
              % sorted(d.id for d in a.sharding.device_set))
    say("dp: %d steps over mesh %s, every device holds a batch shard of %d "
        "rows and the replicated parameters; losses %s"
        % (steps, dict(mesh.shape), batch // n, _fmt(mesh_losses)))
    del params, states

    _net, _plist, step, params, states = build_bert_step(make_model, seed)
    _p, _s, one_losses, _ = run_steps(
        step, params, states, jax.device_put(data, devices[0]), steps, seed)
    say("dp: the same %d steps on device %d alone: losses %s"
        % (steps, devices[0].id, _fmt(one_losses)))
    diff = float(np.max(np.abs(np.asarray(mesh_losses)
                               - np.asarray(one_losses))))
    say("dp: max |loss difference| %.5f (tolerance %g)" % (diff, DP_LOSS_TOL))
    check(diff <= DP_LOSS_TOL, "mesh and single-device losses differ by %g"
          % diff)
    check(mesh_losses[-1] < mesh_losses[0], "loss did not fall: %r"
          % (mesh_losses,))
    return {"mesh_losses": mesh_losses, "single_losses": one_losses}


def phase_dist_attach(devices, seed, steps=3):
    """One gluon Trainer wired into ``dist.attach`` over a ``dp`` mesh of
    ``devices`` against the plain Trainer: dist is placement, not math."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, dist, gluon, nd, parallel
    from mxnet_tpu.gluon import nn

    xs = np.random.RandomState(seed + 1).randn(steps, 16, 8).astype(np.float32)
    ys = np.random.RandomState(seed + 2).randn(steps, 16, 1).astype(np.float32)

    def train(attach):
        mx.random.seed(seed)
        net = nn.Sequential()
        net.add(nn.Dense(32, activation="relu", in_units=8),
                nn.Dense(1, in_units=32))
        net.initialize()
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.1})
        if attach:
            dist.attach(tr, parallel.make_mesh({"dp": len(devices)},
                                               devices=devices),
                        ici_axis="dp", bucket_mb=0.001)
        losses = []
        for s in range(steps):
            x, y = nd.array(xs[s]), nd.array(ys[s])
            with autograd.record():
                loss = ((net(x) - y) ** 2).mean()
            loss.backward()
            tr.step(16)
            losses.append(float(loss.asnumpy()))
        if attach:
            dist.detach(tr)
        return losses

    plain, attached = train(False), train(True)
    diff = float(np.max(np.abs(np.asarray(plain) - np.asarray(attached))))
    say("dist.attach: %d steps, losses %s attached vs %s plain, max "
        "difference %.2e" % (steps, _fmt(attached), _fmt(plain), diff))
    check(diff <= 1e-4, "dist.attach changed the losses by %g" % diff)
    return {"attached": attached, "plain": plain}


# --------------------------------------------------------------------- main
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases A, B, C on one chip (default). 4: only "
                         "the data-parallel phase and what it is compared "
                         "with, on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    # the first thing: look at the device
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print("chip_smoke: needs a TPU, found platform %r (%d device(s))"
              % (dev.platform, len(devices)), file=sys.stderr)
        return 1
    if len(devices) != args.chips:
        print("chip_smoke: --chips %d but jax reports %d TPU device(s)"
              % (args.chips, len(devices)), file=sys.stderr)
        return 1
    say("device: %s x %d (jax %s)" % (dev.device_kind, len(devices),
                                      jax.__version__))

    import mxnet_tpu as mx
    from mxnet_tpu import engine
    from mxnet_tpu.cache import enable_compile_cache
    from mxnet_tpu.models.bert import bert_base
    from mxnet_tpu.models.gpt import gpt2_small

    say("compile cache: %s" % enable_compile_cache())
    say("host engine: %s" % engine.host_engine_kind())
    say("default context: %s" % mx.current_context())

    def bert():
        return bert_base(max_length=128)

    if args.chips == 4:
        phase_train_dp(bert, vocab=30522, batch=64, seq=128, masked=20,
                       steps=5, seed=args.seed, devices=devices)
        phase_dist_attach(devices, seed=args.seed)
    else:
        phase_train(bert, vocab=30522, batch=64, seq=128, masked=20,
                    steps=6, gluon_batch=8, seed=args.seed)
        say("A: peak HBM %s" % _peak_hbm(dev))
        phase_serve(lambda: gpt2_small(dropout=0.0), vocab=50257,
                    prompt_lens=(16, 30, 100, 120, 250, 400, 500, 600),
                    new_tokens=32, slots=8, seed=args.seed, compare=(0, 7),
                    flash_len=1024)
        say("B: peak HBM %s" % _peak_hbm(dev))
        phase_imperative(mx.tpu(), seed=args.seed)
    say("peak HBM %s; done in %.1f s"
        % (_peak_hbm(dev), time.perf_counter() - _T0))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


def _peak_hbm(device):
    from mxnet_tpu.profiler import device_memory_summary

    peak = device_memory_summary(device).get("peak_bytes_in_use")
    return "not reported" if peak is None else "%.2f GiB" % (peak / 2 ** 30)


if __name__ == "__main__":
    sys.exit(main())
