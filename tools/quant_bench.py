#!/usr/bin/env python
"""Quantized-decode microbench: int8 serving end to end vs the bf16 path.

The PERF.md §3 artifact. Two rows:

**Row 1 — gpt_nano quality/structure.** Trains a gpt_nano on a synthetic
next-token task (increment mod vocab — a few seconds on CPU; random-init
logit gaps are too small for a meaningful top-1 agreement number), then
decodes the same mixed-length request set through
``serve.GenerativeServer`` three ways (fp32 / bf16 / ``quantize="int8"``)
in interleaved stream passes. This row pins the structural contract: ONE
fused dispatch per pure decode step, zero steady-state retrace
(``engine.decode_compile_counter`` armed under the watchdog), int8 KV
pages at ~0.5x the bf16 page bytes, and the quality numbers vs the fp32
oracle — top-1 token agreement and mean-abs logit error.

**Row 2 — wide-model throughput.** The tokens/s claim is pinned here, at
a width where the memory-bandwidth lever actually engages. At gpt_nano
width (units=64) the whole decode step is compute-trivial and the
quantize/dequantize elementwise traffic dominates the saved matmul work,
so int8 runs slightly behind bf16 — reported honestly on row 1. From
K>=256 the int8 MXU path wins outright (matmul microbench: 306us vs
377us at K=256; 5.3ms vs 25.6ms at K=1024, where bf16 CPU emulation
collapses), so row 2 times the COMPILED DECODE STEP PROGRAM (stable to
~3%; end-to-end server ticks on a shared CI host swing 25-40% with
turbo/thermal drift) on a units=256 GPT at full slot occupancy, int8 vs
bf16 in alternating blocks, and the speedup >= 1.0 assertion lives
there.

Run: python tools/quant_bench.py [--quick] [--json PATH]

--quick pins the CPU backend and the tiny models (the CI mode; wired as
``python bench.py quant --smoke`` and committed to
tools/quant_bench_quick.json, which tests/test_counter_baseline.py and
tests/test_quant.py hold to the one-dispatch/zero-retrace/KV-ratio/
agreement/throughput contract).
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def train_model(steps=120, batch=8, seqlen=32, lr=3e-3, vocab=256, seed=0):
    """gpt_nano trained on tokens[i+1] = (tokens[i] + 1) % vocab — enough
    signal that fp32 top-1 decisions have real margins."""
    import numpy as np

    from mxnet_tpu import autograd, gluon, nd
    from mxnet_tpu.models.gpt import gpt_nano

    rng = np.random.default_rng(seed)
    m = gpt_nano(vocab_size=vocab)
    m.initialize()
    m.hybridize()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(m.collect_params(), "adam",
                            {"learning_rate": lr})
    last = None
    for _ in range(steps):
        start = rng.integers(0, vocab, size=(batch, 1))
        seq = (start + np.arange(seqlen + 1)) % vocab
        x = nd.array(seq[:, :-1], dtype="int32")
        y = nd.array(seq[:, 1:].astype(np.float32))
        with autograd.record():
            logits = m(x)
            L = loss_fn(logits.reshape(-1, vocab), y.reshape(-1)).mean()
        L.backward()
        trainer.step(1)
        last = float(np.asarray(L._data))
    return m, last


def clone_params(src, dst):
    """Copy parameters between two same-architecture instances (global
    names differ by auto-numbered prefixes — zip construction order)."""
    for ps, pd in zip(src.collect_params().values(),
                      dst.collect_params().values()):
        pd.set_data(ps.data())


class DecodeSide:
    """One server under measurement. Sides are measured in INTERLEAVED
    stream passes (A, B, C, A, B, C, ...) with a median-of-ticks rate:
    per-side sequential runs on a shared CI host read turbo/thermal drift
    as a 20-40%% 'speedup' of whichever side ran first."""

    def __init__(self, name, model, prompts, slots, quantize=None):
        import mxnet_tpu as mx

        self.name = name
        self.quantize = quantize
        self.prompts = prompts
        self.srv = mx.serve.GenerativeServer(
            model, slots=slots, max_wait_ms=1.0,
            max_queue=max(64, len(prompts)), timeout_ms=120000.0,
            quantize=quantize)
        self.srv.warmup(prompt_buckets=(4, 8, 16), max_tokens=32)
        self.ticks = []
        self.pure_disp = self.pure_steps = 0
        self.toks = None

    def stream_pass(self, max_new):
        """One full pass over the request set; pure-decode-tick
        accounting (ticks that admit a join pay prefill dispatches and
        are excluded from the rate)."""
        import time

        from mxnet_tpu import engine

        srv = self.srv
        streams = [srv.submit(p, max_new_tokens=max_new)
                   for p in self.prompts]
        time.sleep(0.05)
        while not all(s.done() for s in streams):
            joins0 = srv.metrics.prefills + (srv.prefix.hits
                                             if srv.prefix else 0)
            engine.dispatch_counter.reset()
            t0 = time.perf_counter()
            n = srv.step()
            dt = time.perf_counter() - t0
            joins1 = srv.metrics.prefills + (srv.prefix.hits
                                             if srv.prefix else 0)
            if n and joins1 == joins0 and srv._flight is not None:
                # steady state: one step sent ahead, the one before it read
                self.pure_disp += engine.dispatch_counter.count
                self.pure_steps += 1
                self.ticks.append(n / dt)
            elif n == 0:
                time.sleep(0.001)
        self.toks = [s.result(10) for s in streams]

    def record(self, recompiles):
        srv = self.srv
        stats = srv.stats()
        ticks = sorted(self.ticks)
        return {
            "tokens_per_sec": round(ticks[len(ticks) // 2], 1) if ticks
            else 0.0,
            "dispatches_per_step": round(
                self.pure_disp / max(self.pure_steps, 1), 2),
            "steady_state_recompiles": recompiles,
            "kv_cache_bytes": stats["kv_cache_bytes"],
            "kv_bytes_vs_bf16": round(
                srv.cache.nbytes()
                / srv.cache.nbytes_unquantized(itemsize=2), 4),
        }


def decode_sides(sides, max_new, iters=3):
    """Interleaved measurement of all sides with the retrace watchdog
    ARMED after every side's warmup: a steady-state decode retrace would
    both bump ``engine.decode_compile_counter`` and fire a structured
    warning."""
    from mxnet_tpu import engine
    from mxnet_tpu.observability import watchdog

    engine.decode_compile_counter.reset()
    watchdog.arm()
    try:
        for _ in range(iters):
            for side in sides:
                side.stream_pass(max_new)
    finally:
        watchdog.disarm()
    recompiles = engine.decode_compile_counter.count
    recs = {s.name: s.record(recompiles) for s in sides}
    for s in sides:
        s.srv.stop()
    return recs


def logit_mae(fp_model, q_model, prompts):
    """Mean-abs error + top-1 agreement of next-token logits on held-out
    prompts (the direct, decode-independent quality probe)."""
    import numpy as np

    from mxnet_tpu import nd

    maes, agree = [], []
    for p in prompts:
        x = nd.array(np.asarray(p)[None], dtype="int32")
        lf = np.asarray(fp_model(x)._data)[0, -1]
        lq = np.asarray(q_model(x)._data)[0, -1]
        maes.append(float(np.abs(lf - lq).mean()))
        agree.append(int(lf.argmax()) == int(lq.argmax()))
    return float(np.mean(maes)), float(np.mean(agree))


def _time_decode_steps(srv, n):
    """Median per-step latency (us) of the compiled decode program at
    full slot occupancy, driving the real cache-donation update between
    steps — the stable measurement (end-to-end server ticks swing with
    host drift). One program invocation per step by construction; the
    dispatch-counter pin lives on the gpt_nano row, whose real server
    loop bumps ``engine.dispatch_counter``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    fn = srv._decode_fn(srv.cache.capacity)
    params = [p.data()._data for p in srv._plist]
    active = jnp.asarray(np.ones(srv.slots, np.int32))
    keys = jnp.asarray(np.tile(
        np.asarray(jax.random.PRNGKey(0), np.uint32), (srv.slots, 1)))
    temps = jnp.asarray(np.zeros(srv.slots, np.float32))

    def step():
        out = fn(params, srv.cache.state, srv.cache.valid, srv._tok, active,
                 keys, temps)
        state, valid, srv._tok = out
        srv.cache.update(state, valid)
        return out

    jax.block_until_ready(step())  # first call outside the timed region
    ticks = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(step())
        ticks.append(time.perf_counter() - t0)
    ticks.sort()
    return ticks[len(ticks) // 2] * 1e6


def run_wide(units=256, slots=8, mode="int8", steps=30, seed=0):
    """Throughput row: int8 vs bf16 at a width where the bandwidth lever
    engages. Random init is fine here — quality is pinned on the trained
    gpt_nano row; this row prices the compiled decode step."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import engine
    from mxnet_tpu.models.gpt import GPTModel
    from mxnet_tpu.observability import watchdog

    def build(quantize=None, cast=None):
        m = GPTModel(vocab_size=256, units=units, num_layers=2,
                     num_heads=2, max_length=64, dropout=0.0)
        m.initialize()
        if cast:
            m.cast(cast)
        m.hybridize()
        srv = mx.serve.GenerativeServer(
            m, slots=slots, max_wait_ms=1.0, timeout_ms=120000.0,
            quantize=quantize)
        srv.warmup(prompt_buckets=(8,), max_tokens=32)
        return srv

    bf_srv = build(cast="bfloat16")
    q_srv = build(quantize=mode)
    engine.decode_compile_counter.reset()
    watchdog.arm()
    try:
        # alternating half-blocks so host-clock drift cannot favour a side
        half = max(steps // 2, 5)
        bf_a = _time_decode_steps(bf_srv, half)
        q_a = _time_decode_steps(q_srv, half)
        bf_b = _time_decode_steps(bf_srv, half)
        q_b = _time_decode_steps(q_srv, half)
    finally:
        watchdog.disarm()
    recompiles = engine.decode_compile_counter.count
    bf_us = (bf_a + bf_b) / 2.0
    q_us = (q_a + q_b) / 2.0
    kv_ratio = (q_srv.cache.nbytes()
                / q_srv.cache.nbytes_unquantized(itemsize=2))
    kv_bytes = q_srv.cache.nbytes()
    bf_srv.stop()
    q_srv.stop()
    return {
        "case": "gpt_wide(units=%d) decode step (%s vs bf16)"
                % (units, mode),
        "quantize": mode,
        "units": units,
        "slots": slots,
        "timing": "compiled decode-step program, median of %d "
                  "alternating-block steps per side" % (2 * max(steps // 2, 5)),
        "bf16_step_us": round(bf_us, 1),
        "quant_step_us": round(q_us, 1),
        "bf16_tokens_per_sec": round(slots / (bf_us / 1e6), 1),
        "quant_tokens_per_sec": round(slots / (q_us / 1e6), 1),
        "speedup_vs_bf16": round(bf_us / q_us, 2),
        "steady_state_recompiles": recompiles,
        "kv_cache_bytes": kv_bytes,
        "kv_bytes_vs_bf16": round(kv_ratio, 4),
    }


def run(quick, max_new=16, requests=12, slots=8, mode="int8", seed=0):
    import numpy as np

    from mxnet_tpu.models.gpt import gpt_nano

    t0 = time.perf_counter()
    fp_model, final_loss = train_model(seed=seed)
    train_s = time.perf_counter() - t0
    q_model = gpt_nano()
    q_model.initialize()
    q_model.hybridize()
    clone_params(fp_model, q_model)
    # the throughput baseline the lever is priced against: bf16 weights
    # AND a bf16 KV cache (the pre-quantization serving configuration)
    bf_model = gpt_nano()
    bf_model.initialize()
    clone_params(fp_model, bf_model)
    bf_model.cast("bfloat16")
    bf_model.hybridize()

    rng = np.random.default_rng(seed + 1)
    prompts = [rng.integers(0, 256, size=(int(l),)).astype(np.int32)
               for l in rng.integers(3, 12, size=requests)]

    sides = [DecodeSide("fp32", fp_model, prompts, slots),
             DecodeSide("bf16", bf_model, prompts, slots),
             DecodeSide("quant", q_model, prompts, slots, quantize=mode)]
    recs = decode_sides(sides, max_new)
    fp32, bf16, quant = recs["fp32"], recs["bf16"], recs["quant"]
    fp_toks, quant_toks = sides[0].toks, sides[2].toks

    # quality vs the fp32 oracle (the bf16 side is the throughput bar)
    same = total = 0
    for a, b in zip(fp_toks, quant_toks):
        same += sum(1 for x, y in zip(a, b) if x == y)
        total += len(a)
    mae, head_agree = logit_mae(fp_model, q_model, prompts)

    return {
        "case": "gpt_nano quantized decode (%s)" % mode,
        "quantize": mode,
        "requests": requests,
        "max_new_tokens": max_new,
        "slots": slots,
        "train_steps": 120,
        "train_final_loss": round(final_loss, 4),
        "train_s": round(train_s, 1),
        "bf16_tokens_per_sec": bf16["tokens_per_sec"],
        "fp32_tokens_per_sec": fp32["tokens_per_sec"],
        "quant_tokens_per_sec": quant["tokens_per_sec"],
        "speedup_vs_bf16": round(quant["tokens_per_sec"]
                                 / bf16["tokens_per_sec"], 2),
        "speedup_vs_fp32": round(quant["tokens_per_sec"]
                                 / fp32["tokens_per_sec"], 2),
        "dispatches_per_step": quant["dispatches_per_step"],
        "bf16_dispatches_per_step": bf16["dispatches_per_step"],
        "steady_state_recompiles": quant["steady_state_recompiles"],
        "kv_cache_bytes": quant["kv_cache_bytes"],
        "kv_bytes_vs_bf16": quant["kv_bytes_vs_bf16"],
        "top1_agreement": round(same / max(total, 1), 4),
        "logit_mae": round(mae, 5),
        "next_token_head_agreement": round(head_agree, 4),
        "parity": "top-1 token agreement vs the fp32 oracle server; "
                  "tokens/s here is informational (units=64 is below the "
                  "width where int8 pays for its quantize/dequantize "
                  "traffic) — the >=bf16 throughput pin is the wide row",
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CPU backend + tiny model (the CI mode)")
    ap.add_argument("--mode", choices=("int8", "e4m3", "e5m2"),
                    default="int8")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--wide-units", type=int, default=256,
                    help="width of the throughput row's model")
    ap.add_argument("--json", default=None, metavar="PATH")
    args = ap.parse_args(argv)

    if args.quick:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    if args.quick:
        jax.config.update("jax_platforms", "cpu")

    rec = run(args.quick, max_new=args.max_new, requests=args.requests,
              slots=args.slots, mode=args.mode)
    print(json.dumps(rec), flush=True)
    wide = run_wide(units=args.wide_units, slots=args.slots,
                    mode=args.mode)
    print(json.dumps(wide), flush=True)
    if args.json:
        meta = {"quick": args.quick, "mode": "quant",
                "platform": jax.devices()[0].platform,
                "timing": "row 1 (gpt_nano): end-to-end mixed-length "
                          "concurrent streams on a trained model — pins "
                          "dispatch/retrace/KV/agreement; row 2 (wide): "
                          "compiled decode-step program timing — pins "
                          "tokens/s >= bf16 where the bandwidth lever "
                          "engages",
                "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                             time.gmtime())}
        with open(args.json, "w") as f:
            json.dump({"config": meta, "rows": [rec, wide]}, f, indent=1)
            f.write("\n")
        print("wrote %s" % args.json)
    return 0


if __name__ == "__main__":
    sys.exit(main())
