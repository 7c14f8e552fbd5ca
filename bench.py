#!/usr/bin/env python
"""Benchmarks over the BASELINE.md configs, single TPU chip.

Default (headline) mode matches BASELINE.md config #2: BERT-base pretraining,
seq 128, bf16 compute + fp32 master weights, MLM (20 masked positions) + NSP
loss, Adam. The entire step — forward, backward, optimizer — is ONE
donated-buffer XLA program (the path MXNet approximates with fused optimizer
kernels + CachedOp; see SURVEY.md §3.4).

Modes: bert (default) | bert512 | resnet50 | lstm | ssd512 | nmt | all.
Prints one JSON line per mode: {"metric", "value", "unit", "vs_baseline", ...}
with the platform, device kind and device count it ran on. Without a TPU and
without ``--cpu`` it exits non-zero: a measurement never falls back to the
CPU and never replays a stored number. Everything runs in this one process
(a chip belongs to one process at a time).
"""
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

_REPO = os.path.dirname(os.path.abspath(__file__))


def _log(msg):
    print("[bench] %.1fs %s" % (time.perf_counter() - _T0, msg),
          file=sys.stderr, flush=True)


_T0 = time.perf_counter()

# Per-chip peaks keyed by jax's ``device_kind``. A kind that is not here is an
# error, not a default: MFU against the wrong peak is a wrong number.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "source": "Google Cloud documentation, \"TPU v5e\": 197 TFLOP/s "
                  "bf16 per chip",
    },
}


def _peak_bf16_flops(device_kind):
    try:
        return PEAKS[device_kind]["bf16_flops"]
    except KeyError:
        raise SystemExit(
            "no peak FLOP/s on record for device kind %r (known: %s) — add it "
            "to bench.PEAKS with its source" % (device_kind, sorted(PEAKS)))


BASELINE_SAMPLES_PER_SEC = 250.0  # MXNet+A100 BERT-base phase-1 (BASELINE.md)

# 64 won the 2026-08-01 chip batch sweep (tools/batch_sweep_r5.jsonl:
# 32→1260 samples/s @0.447 MFU, 64→1443 @0.512, 128→1300, 256→1199)
BATCH = 64
SEQ = 128
MASKED = 20
VOCAB = 30522


def _xent_mean(logits, labels):
    """Mean NLL over (rows, vocab) logits through the registry op the gluon
    loss uses (``softmax_xent_rows``): on TPU it gates into the fused pallas
    softmax-xent kernel (ops/pallas/softmax_xent.py — loss + logsumexp in ONE
    VMEM pass, backward reuses the saved lse), lane-aligning V internally; on
    the CPU (``--cpu``) it takes the jnp path (kernel parity is pinned in
    tests). The bench measures what real training gets."""
    from mxnet_tpu.ops.functional import softmax_xent_rows
    return jnp.mean(softmax_xent_rows(logits, labels))


def build(seq=SEQ, remat=False):
    # batch/mask sizes come from make_batch via the jit trace; only the
    # max sequence length specializes the model itself
    import mxnet_tpu as mx
    from mxnet_tpu import _trace, amp
    from mxnet_tpu.models.bert import bert_base
    from mxnet_tpu.parallel import tree_optimizer_step

    bert = bert_base(dropout=0.1, max_length=seq)
    bert.initialize()
    amp.convert_hybrid_block(bert, "bfloat16")

    plist = list(bert.collect_params().values())
    opt = mx.optimizer.Adam(learning_rate=1e-4, multi_precision=True)
    init_states, apply_opt = tree_optimizer_step(opt)

    def loss_fn(param_arrays, batch, key):
        tok, tt, vl, mp, mlm_y, nsp_y = batch
        with _trace.trace_scope(key, True) as t:
            t.param_store = {id(p): a for p, a in zip(plist, param_arrays)}
            seq, pooled, nsp_logits, mlm_logits = bert._call_traced(tok, tt, vl, mp)
        # NSP stays on jnp: 2-class logits are lane-hostile for a pallas
        # block and cost nothing either way
        nsp_lp = jax.nn.log_softmax(nsp_logits.astype(jnp.float32), axis=-1)
        nsp_nll = -jnp.take_along_axis(nsp_lp, nsp_y[:, None], axis=-1)
        return _xent_mean(mlm_logits, mlm_y) + jnp.mean(nsp_nll)

    params = [p.data()._data for p in plist]
    states = init_states(params)
    if remat:
        # rematerialize activations during backward to buy larger batches
        # (the --batch sweep). remat is the POLICY string: 'dots' (default)
        # saves matmul outputs — cheap to store, expensive to recompute —
        # and recomputes only the elementwise tail, the standard TPU LLM
        # recipe; 'full' (--remat=full) saves nothing (~2x forward FLOPs),
        # kept for the memory-extreme comparison
        # bool True (programmatic callers) means the default policy
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if remat in (True, "dots") else None)
        loss_fn = jax.checkpoint(loss_fn, policy=policy)

    # donate params+opt state: step i+1 overwrites step i's buffers in place
    # instead of allocating a second copy of every weight/moment in HBM
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, states, t, key, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch, key)
        new_p, new_s = apply_opt(params, grads, states, jnp.float32(1e-4),
                                 jnp.float32(0.01), t)
        return new_p, new_s, loss

    return step, params, states


def make_batch(rng, batch=BATCH, seq=SEQ, masked=MASKED):
    tok = jnp.asarray(rng.integers(0, VOCAB, (batch, seq)), jnp.int32)
    tt = jnp.zeros((batch, seq), jnp.int32)
    vl = jnp.full((batch,), seq, jnp.float32)
    mp = jnp.asarray(rng.integers(0, seq, (batch, masked)), jnp.int32)
    mlm_y = jnp.asarray(rng.integers(0, VOCAB, (batch, masked)), jnp.int32)
    nsp_y = jnp.asarray(rng.integers(0, 2, (batch,)), jnp.int32)
    return tok, tt, vl, mp, mlm_y, nsp_y


RESNET_BATCH = 128
RESNET_BASELINE_IMG_PER_SEC = 2900.0  # MXNet+A100 ResNet-50 (BASELINE.md)

# BERT phase-2 config (seq 512): exercises the pallas flash-attention path
# (seq 128 dispatches to dense XLA attention below _FLASH_MIN_LEN). Baseline
# derived from BASELINE.md's phase-1 250 samples/s/chip by FLOP ratio:
# per-sample FLOPs scale ~5.1x from seq 128→512 (linear in tokens plus the
# quadratic attention term), so 250 / 5.1 ≈ 49 samples/s/chip.
BERT512_BATCH = 16
BERT512_SEQ = 512
BERT512_MASKED = 80
BERT512_BASELINE = 49.0

LSTM_BATCH = 32
LSTM_BPTT = 35
LSTM_VOCAB = 10000
LSTM_BASELINE_TOK_PER_SEC = 45000.0  # MXNet+A100 LSTM PTB (BASELINE.md)

SSD_BATCH = 32
SSD_BASELINE_IMG_PER_SEC = 230.0  # MXNet+A100 SSD-512 VGG16 (BASELINE.md)

NMT_BATCH = 32
NMT_SRC_LEN = 64
NMT_TGT_LEN = 64
NMT_VOCAB = 32000
NMT_BASELINE_TOK_PER_SEC = 110000.0  # MXNet+A100 Transformer base (BASELINE.md)


def _bert_train_flops_per_sample(seq, masked, layers=12, d=768, ffn=3072,
                                 vocab=VOCAB):
    """Analytic fwd+bwd FLOPs for one BERT-base pretraining sample.

    Matmul fwd FLOPs/token/layer: qkv+out projections (4·d²) + FFN (2·d·ffn),
    ×2 for multiply-add. Attention fwd/token/layer: QKᵀ + PV = 4·seq·d.
    MLM head runs on `masked` positions only: transform d² + tied decoder d·V.
    Training total ≈ 3× forward (backward ≈ 2× forward). Used for the reported
    MFU against the v5e bf16 peak; ±few-% approximation (bias/LN/softmax
    excluded)."""
    per_tok_layer = 2 * (4 * d * d + 2 * d * ffn) + 4 * seq * d
    fwd = seq * layers * per_tok_layer + masked * 2 * (d * d + d * vocab)
    return 3.0 * fwd


def build_resnet():
    """Secondary bench (BASELINE.md config #1): ResNet-50 ImageNet training
    throughput — `python bench.py resnet50`."""
    import mxnet_tpu as mx
    from mxnet_tpu import _trace, amp
    from mxnet_tpu.gluon.model_zoo.vision import get_resnet
    from mxnet_tpu.parallel import tree_optimizer_step

    # BENCH_RESNET_S2D=1: MLPerf-style space-to-depth conv0 (identical math,
    # checkpoint-compatible; see model_zoo _S2DStem). Exploratory — runs
    # with it set are NOT persisted until it becomes the default.
    net = get_resnet(1, 50, classes=1000,
                     stem_s2d=bool(os.environ.get("BENCH_RESNET_S2D")))
    net.initialize()
    # one tiny eager forward materializes deferred param shapes
    from mxnet_tpu import nd as _nd
    net(_nd.array(np.zeros((1, 3, 224, 224), np.float32)))
    amp.convert_hybrid_block(net, "bfloat16")
    plist = list(net.collect_params().values())
    opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9,
                           multi_precision=True)
    init_states, apply_opt = tree_optimizer_step(opt)

    def loss_fn(param_arrays, batch, key):
        x, y = batch
        # entry cast: bf16 activations flow the whole trunk (BatchNorm keeps
        # x's dtype, applying its fp32 stats cast-to-input)
        x = x.astype(jnp.bfloat16)
        with _trace.trace_scope(key, True) as t:
            t.param_store = {id(p): a for p, a in zip(plist, param_arrays)}
            logits = net._call_traced(x)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return jnp.mean(-jnp.take_along_axis(lp, y[:, None], axis=-1))

    params = [p.data()._data for p in plist]
    states = init_states(params)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, states, t, key, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch, key)
        new_p, new_s = apply_opt(params, grads, states, jnp.float32(0.1),
                                 jnp.float32(1e-4), t)
        return new_p, new_s, loss

    return step, params, states


def make_resnet_batch(rng, batch=RESNET_BATCH):
    # fp32 input: amp's block-boundary cast rules put the convs in bf16
    # against bf16-cast weights (fp32 masters live in the optimizer)
    x = jnp.asarray(rng.normal(size=(batch, 3, 224, 224)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 1000, (batch,)), jnp.int32)
    return x, y


def _fused_train_step(net, opt, traced_loss, lr, wd):
    """Shared builder: one donated-buffer jit program for fwd+bwd+optimizer
    over a HybridBlock, mirroring build()/build_resnet()."""
    from mxnet_tpu import _trace
    from mxnet_tpu.parallel import tree_optimizer_step

    plist = list(net.collect_params().values())
    init_states, apply_opt = tree_optimizer_step(opt)

    def loss_fn(param_arrays, batch, key):
        with _trace.trace_scope(key, True) as t:
            t.param_store = {id(p): a for p, a in zip(plist, param_arrays)}
            return traced_loss(batch)

    params = [p.data()._data for p in plist]
    states = init_states(params)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, states, t, key, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch, key)
        new_p, new_s = apply_opt(params, grads, states, jnp.float32(lr),
                                 jnp.float32(wd), t)
        return new_p, new_s, loss

    return step, params, states


def build_lstm():
    """BASELINE.md config #3: LSTM PTB LM, batch 32, bptt 35 —
    `python bench.py lstm`. tokens/s = batch·bptt / step-time."""
    import mxnet_tpu as mx
    from mxnet_tpu import amp
    from mxnet_tpu.models.lstm_lm import lstm_ptb

    net = lstm_ptb(vocab_size=LSTM_VOCAB, tie_weights=True, dropout=0.5)
    net.initialize()
    amp.convert_hybrid_block(net, "bfloat16")
    opt = mx.optimizer.SGD(learning_rate=1.0, multi_precision=True)

    def traced_loss(batch):
        tokens, labels = batch  # (T, N) each
        logits = net._call_traced(tokens)  # (T, N, V)
        return _xent_mean(logits, labels)

    return _fused_train_step(net, opt, traced_loss, lr=1.0, wd=0.0)


def make_lstm_batch(rng, batch=LSTM_BATCH, bptt=LSTM_BPTT):
    tokens = jnp.asarray(rng.integers(0, LSTM_VOCAB, (bptt, batch)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, LSTM_VOCAB, (bptt, batch)), jnp.int32)
    return tokens, labels


def build_ssd():
    """BASELINE.md config #4: SSD-512 VGG16, batch 32 —
    `python bench.py ssd512`. The multibox target assignment (anchor
    matching + hard-negative mining) runs ON DEVICE inside the same jit
    program as fwd+bwd (ops/detection.py), where MXNet does it in a CUDA
    kernel chain."""
    import mxnet_tpu as mx
    from mxnet_tpu import amp, nd as _nd
    from mxnet_tpu.models.ssd import SSDLoss, ssd_512

    net = ssd_512(num_classes=20)
    net.initialize()
    net(_nd.array(np.zeros((1, 3, 512, 512), np.float32)))  # materialize shapes
    amp.convert_hybrid_block(net, "bfloat16")
    loss_blk = SSDLoss(20)
    opt = mx.optimizer.SGD(learning_rate=1e-3, momentum=0.9, wd=5e-4,
                           multi_precision=True)

    def traced_loss(batch):
        x, labels = batch
        x = x.astype(jnp.bfloat16)
        cls_preds, box_preds, anchors = net._call_traced(x)
        per_img = loss_blk._call_traced(cls_preds.astype(jnp.float32),
                                        box_preds.astype(jnp.float32),
                                        labels, anchors)
        return jnp.mean(per_img)

    return _fused_train_step(net, opt, traced_loss, lr=1e-3, wd=5e-4)


def make_ssd_batch(rng, batch=SSD_BATCH, num_boxes=8):
    x = jnp.asarray(rng.normal(size=(batch, 3, 512, 512)), jnp.float32)
    cls = rng.integers(0, 20, (batch, num_boxes, 1)).astype(np.float32)
    lo = rng.uniform(0.0, 0.7, (batch, num_boxes, 2)).astype(np.float32)
    wh = rng.uniform(0.1, 0.3, (batch, num_boxes, 2)).astype(np.float32)
    boxes = np.concatenate([lo, np.minimum(lo + wh, 1.0)], axis=-1)
    labels = jnp.asarray(np.concatenate([cls, boxes], axis=-1))
    return x, labels


def build_nmt():
    """BASELINE.md config #5: Transformer NMT WMT En-De base —
    `python bench.py nmt`. tokens/s counts source+target tokens per step
    (the gluonnlp training-log convention the baseline number uses)."""
    import mxnet_tpu as mx
    from mxnet_tpu import amp
    from mxnet_tpu.models.transformer import transformer_base

    net = transformer_base(NMT_VOCAB, NMT_VOCAB, max_len=128, dropout=0.1)
    net.initialize()
    amp.convert_hybrid_block(net, "bfloat16")
    opt = mx.optimizer.Adam(learning_rate=1e-4, multi_precision=True)

    def traced_loss(batch):
        src, tgt, labels = batch
        logits = net._call_traced(src, tgt)  # (B, T_tgt, V)
        return _xent_mean(logits, labels)

    return _fused_train_step(net, opt, traced_loss, lr=1e-4, wd=0.0)


def make_nmt_batch(rng, batch=NMT_BATCH, src_len=NMT_SRC_LEN,
                   tgt_len=NMT_TGT_LEN):
    src = jnp.asarray(rng.integers(4, NMT_VOCAB, (batch, src_len)), jnp.int32)
    tgt = jnp.asarray(rng.integers(4, NMT_VOCAB, (batch, tgt_len)), jnp.int32)
    labels = jnp.asarray(rng.integers(4, NMT_VOCAB, (batch, tgt_len)), jnp.int32)
    return src, tgt, labels


def _build_on_host(thunk):
    """Run model construction on the host CPU backend, then ship state to the
    accelerator in ONE device_put.

    Param init and the eager shape-materialization warmup (resnet/ssd) are
    hundreds of tiny one-off ops, each of which would compile its own program
    for the chip before the first timed step. None of that work needs the
    TPU — the jitted train step is the only hot path — so it runs pinned to
    the host CPU backend and the finished params/opt-state cross to the
    device once (keeping step-1 buffer donation valid).

    BOTH scopes are required: the mxnet_tpu Context scope places `nd.array`
    factory outputs, but parameter/optimizer init is raw jnp/jax.random
    compute that only honors jax's own default-device setting."""
    from mxnet_tpu import context as _ctx
    try:
        cpu_dev = jax.local_devices(backend="cpu")[0]
    except RuntimeError:  # no cpu backend registered: build on the default
        _log("cpu backend unavailable; building on the default device")
        return thunk()
    with _ctx.cpu(), jax.default_device(cpu_dev):
        step, params, states = thunk()
    # context-layer resolution, not jax.devices()[0]: under multi-controller
    # jax that global list leads with host 0's device (context.py:50)
    dev = _ctx.current_context().jax_device()
    if dev.platform != "cpu":
        params, states = jax.device_put((params, states), dev)
    return step, params, states


# XLA cost-analysis train FLOPs per unit for the non-bert modes
# (backend-independent: flops depend on the model math, not the lowering; the
# bert modes keep their closed-form analytic count, which agrees with cost
# analysis within 4%).
COST_FLOPS_PER_UNIT = {
    "resnet50": 23.52e9,   # per image
    "lstm": 60.36e6,       # per token
    "ssd512": 330.0e9,     # per image
    "nmt": 187.9e6,        # per token
}


# mode -> (step, params, states, batch, units_per_step, metric, unit,
#          baseline, train FLOPs per unit, resolved_batch)
def _mode_spec(mode, rng, smoke=False, batch_override=None, remat=False):
    def _b(default):
        return batch_override or (default)

    if mode == "bert":
        b = _b(4 if smoke else BATCH)
        step, params, states = _build_on_host(lambda: build(remat=remat))
        return (step, params, states, make_batch(rng, b), b,
                "bert_base_pretrain_samples_per_sec_per_chip", "samples/s",
                BASELINE_SAMPLES_PER_SEC,
                _bert_train_flops_per_sample(SEQ, MASKED), b)
    if mode == "bert512":
        b = _b(2 if smoke else BERT512_BATCH)
        step, params, states = _build_on_host(
            lambda: build(seq=BERT512_SEQ, remat=remat))
        return (step, params, states,
                make_batch(rng, b, BERT512_SEQ, BERT512_MASKED), b,
                "bert_base_seq512_train_samples_per_sec_per_chip", "samples/s",
                BERT512_BASELINE,
                _bert_train_flops_per_sample(BERT512_SEQ, BERT512_MASKED), b)
    if mode == "resnet50":
        b = _b(2 if smoke else RESNET_BATCH)
        step, params, states = _build_on_host(build_resnet)
        return (step, params, states, make_resnet_batch(rng, b), b,
                "resnet50_train_images_per_sec_per_chip", "images/s",
                RESNET_BASELINE_IMG_PER_SEC, COST_FLOPS_PER_UNIT["resnet50"], b)
    if mode == "lstm":
        b = _b(4 if smoke else LSTM_BATCH)
        step, params, states = _build_on_host(build_lstm)
        return (step, params, states, make_lstm_batch(rng, b), b * LSTM_BPTT,
                "lstm_ptb_train_tokens_per_sec_per_chip", "tokens/s",
                LSTM_BASELINE_TOK_PER_SEC, COST_FLOPS_PER_UNIT["lstm"], b)
    if mode == "ssd512":
        b = _b(1 if smoke else SSD_BATCH)
        step, params, states = _build_on_host(build_ssd)
        return (step, params, states, make_ssd_batch(rng, b), b,
                "ssd512_vgg16_train_images_per_sec_per_chip", "images/s",
                SSD_BASELINE_IMG_PER_SEC, COST_FLOPS_PER_UNIT["ssd512"], b)
    if mode == "nmt":
        b = _b(2 if smoke else NMT_BATCH)
        src_len = 16 if smoke else NMT_SRC_LEN
        tgt_len = 16 if smoke else NMT_TGT_LEN
        step, params, states = _build_on_host(build_nmt)
        return (step, params, states, make_nmt_batch(rng, b, src_len, tgt_len),
                b * (src_len + tgt_len),
                "transformer_nmt_train_tokens_per_sec_per_chip", "tokens/s",
                NMT_BASELINE_TOK_PER_SEC, COST_FLOPS_PER_UNIT["nmt"], b)
    raise SystemExit("unknown mode %r" % mode)


MODES = ("bert", "bert512", "resnet50", "lstm", "ssd512", "nmt")


def _make_key():
    """Step RNG key. Default is the 'rbg' generator: threefry (jax's
    default) burns real ALU time producing dropout bits — material at 12
    layers x several dropout sites per step on TPU — while rbg uses the
    hardware RNG instruction. BENCH_PRNG=threefry opts back out (the
    training numerics are dropout noise either way)."""
    impl = os.environ.get("BENCH_PRNG", "rbg")
    if impl == "threefry":
        return "threefry", jax.random.PRNGKey(0)
    return impl, jax.random.key(0, impl=impl)


def run_mode(mode, smoke=False, iters=None, batch_override=None, remat=False):
    rng = np.random.default_rng(0)
    _log("building model + train step (%s)..." % mode)
    (step, params, states, batch, units, metric, unit, baseline,
     flops_per_unit, resolved_batch) = _mode_spec(mode, rng, smoke,
                                                  batch_override, remat)
    prng_impl, key = _make_key()

    # warmup / compile. Timing is closed by a HOST READ of the final loss:
    # step i+1 consumes step i's params, so fetching loss_N forces the
    # entire chain to have executed.
    _log("compiling fused train step (cached in %s afterwards)..."
         % jax.config.jax_compilation_cache_dir)
    params, states, loss = step(params, states, jnp.int32(1), key, batch)
    float(loss)
    _log("compile + first step done; timing...")

    # only the bert builds thread jax.checkpoint; other modes must not
    # claim remat in the record. Keep the POLICY string intact ("x and y"
    # would collapse it to the boolean y).
    remat = remat if mode in ("bert", "bert512") else False
    iters = iters or (3 if smoke else 50)
    t0 = time.perf_counter()
    for i in range(iters):
        params, states, loss = step(params, states, jnp.int32(i + 2), key, batch)
    final_loss = float(loss)
    dt = time.perf_counter() - t0
    _log("timed %d iters in %.2fs (loss %.4f)" % (iters, dt, final_loss))
    assert np.isfinite(final_loss)

    per_sec = units * iters / dt
    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    rec = {
        # a --cpu run shows that the step runs; its rate is no device metric
        # and does not carry the device metric's name
        "metric": metric if on_chip else "cpu_smoke/" + metric,
        "value": round(per_sec, 2),
        "unit": unit,
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "iters": iters,
        # the resolved literal, never the string "default": a later change
        # of a default constant must not silently re-label an old record
        "batch": resolved_batch,
        "remat": bool(remat),
        "remat_policy": ("dots" if remat is True else remat) or None,
        "prng": prng_impl,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
    }
    # MFU is a statement about the chip: never on the CPU, and not in smoke
    # (the flops/unit constants assume full bench shapes — nmt smoke shrinks
    # src/tgt 64->16, whose attention flops differ)
    if on_chip:
        rec["vs_baseline"] = round(per_sec / baseline, 4)
    if on_chip and not smoke:
        rec["mfu"] = round(per_sec * flops_per_unit
                           / _peak_bf16_flops(dev.device_kind), 4)
    from mxnet_tpu.profiler import device_memory_summary
    mem = device_memory_summary()
    # in_use is per-mode accurate (this mode's buffers are live here);
    # the peak is PROCESS-lifetime — in `all` mode it covers every mode
    # run so far, hence the explicit name
    if mem.get("bytes_in_use"):
        rec["hbm_gb_in_use"] = round(mem["bytes_in_use"] / 2**30, 3)
    if mem.get("peak_bytes_in_use"):
        rec["hbm_process_peak_gb"] = round(
            mem["peak_bytes_in_use"] / 2**30, 3)
    if mode == "resnet50" and os.environ.get("BENCH_RESNET_S2D"):
        rec["stem"] = "s2d"  # exploratory config, tagged
    print(json.dumps(rec), flush=True)

    prof_dir = os.environ.get("BENCH_PROFILE_DIR")
    if prof_dir:
        os.makedirs(prof_dir, exist_ok=True)
        with jax.profiler.trace(os.path.join(prof_dir, mode)):
            for i in range(3):
                params, states, loss = step(
                    params, states, jnp.int32(1000 + i), key, batch)
            float(loss)
        _log("profile trace written under %s/%s" % (prof_dir, mode))


# host-dispatch microbenches (tools/*_bench.py) — separate from the MODES
# table: they measure host dispatch overhead, not model throughput.
# --smoke/--cpu run the CPU-pinned --quick variant.
TOOL_MODES = {
    "optstep": "opt_step_bench.py",
    "imperative": "imperative_bench.py",
    "autograd": "autograd_bench.py",
    "serve": "serve_bench.py",
    "decode": "serve_bench.py",
    # replica spin-up cold vs snapshot-warm (cache Tier B); the parent
    # stays off jax and each measurement is a child of its own
    "coldstart": "serve_bench.py",
    # speculative draft/verify decode + chunked prefill vs the plain
    # continuous-batching path
    "specdecode": "serve_bench.py",
    # unified graph IR: CSE/DCE node shrink + host-loop time on a
    # repeated-subexpression chain (mxnet_tpu.ir)
    "ir": "ir_bench.py",
    # overlapped bucketed hierarchical gradient exchange vs the serialized
    # flat baseline (mxnet_tpu.dist)
    "dist": "dist_bench.py",
    # int8 quantized decode: dispatch/retrace/KV/agreement on a trained
    # gpt_nano + step-program throughput vs bf16 (mxnet_tpu.quant)
    "quant": "quant_bench.py",
    # cost-model-driven autotune search vs DEFAULT_PASSES on the pinned
    # const-island scenarios (mxnet_tpu.ir.tune)
    "tune": "tune_bench.py",
    # multi-process replica fleet: kill -9 drill, SLO autoscale p99,
    # zero-downtime hot swap, warm spawn, prefix migration
    # (mxnet_tpu.serve.fleet). The router stays off the accelerator; the
    # workers are the processes that take chips.
    "fleet": "fleet_bench.py",
}


def _run_tool(mode, flags, quick):
    import importlib.util
    tool = TOOL_MODES[mode]
    spec = importlib.util.spec_from_file_location(
        tool[:-3], os.path.join(_REPO, "tools", tool))
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    argv = ["--quick"] if quick else []
    if mode in ("decode", "coldstart", "specdecode"):
        argv += ["--mode", mode]
    if iters := next((f.split("=", 1)[1] for f in flags
                      if f.startswith("--iters=")), None):
        # dist_bench counts training steps, fleet_bench counts requests per
        # wave — neither times fixed iterations
        argv += [{"dist": "--steps",
                  "fleet": "--requests"}.get(mode, "--iters"), iters]
    return m.main(argv)


def main():
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    flags = {a for a in sys.argv[1:] if a.startswith("--")}
    smoke = "--smoke" in flags
    cpu = "--cpu" in flags
    remat = "dots" if "--remat" in flags else False
    for f in flags:
        if f.startswith("--remat="):
            remat = f.split("=", 1)[1]
            if remat not in ("dots", "full"):
                # same convention as the mode check: a typo must abort
                # loudly, never run one policy while recording another
                raise SystemExit("--remat= takes dots or full, got %r"
                                 % remat)
    if cpu:
        jax.config.update("jax_platforms", "cpu")
    mode = args[0] if args else "bert"
    if mode in TOOL_MODES:
        raise SystemExit(_run_tool(mode, flags, quick=smoke or cpu))
    if mode != "all" and mode not in MODES:
        raise SystemExit("unknown mode %r (choose from %s or 'all')"
                         % (mode, ", ".join(MODES)))
    iters = None
    batch_override = None
    for f in flags:
        if f.startswith("--iters="):
            iters = int(f.split("=", 1)[1])
        if f.startswith("--batch="):
            batch_override = int(f.split("=", 1)[1])
            if batch_override < 1:
                raise SystemExit("--batch must be >= 1")

    # the first touch of the backend: everything that needs the chip runs
    # in this process from here on
    devs = jax.devices()
    _log("devices: %s" % (devs,))
    if devs[0].platform != "tpu" and not cpu:
        raise SystemExit(
            "bench.py measures on a TPU and found platform %r — run it on "
            "the chip, or pass --cpu for a CPU smoke that reports no device "
            "metric" % devs[0].platform)
    from mxnet_tpu.cache import enable_compile_cache
    enable_compile_cache()

    if mode == "all":
        # a failing mode is logged and skipped rather than aborting the
        # remaining measurements; the exit code still says it failed
        failed = []
        for m in MODES:
            try:
                run_mode(m, smoke=smoke, iters=iters,
                         batch_override=batch_override, remat=remat)
            except Exception as e:
                _log("mode %s FAILED: %r — continuing with remaining modes"
                     % (m, e))
                failed.append(m)
        if failed:
            raise SystemExit("modes failed: %s" % ",".join(failed))
    else:
        run_mode(mode, smoke=smoke, iters=iters,
                 batch_override=batch_override, remat=remat)


if __name__ == "__main__":
    main()
