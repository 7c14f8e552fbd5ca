#!/usr/bin/env python
"""Flash-attention block-size sweep on the real chip.

Times forward and forward+backward for a grid of (block_q, block_k) at the
given sequence lengths, against the dense XLA reference. Output guides the
default block sizes in ops/pallas/flash_attention.py.

Run: python tools/flash_sweep.py [--seq 512 2048] [--iters 20]
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# a slope is only trusted when it exceeds this multiple of the spread
# across the wall(N) repeats (the difference of two best-of-3 minima can be
# pure host jitter)
NOISE_FLOOR_MULT = 2.0


def time_fn(fn, *args, iters=20):
    """Time fn by running `iters` data-chained applications inside ONE jit.

    A python loop of enqueues pays one host dispatch per call, which swamps
    a µs-scale kernel. Chaining iteration i+1's operand on iteration i's
    output inside a lax.scan keeps the loop on the device and makes
    elision/reordering impossible; the final np.asarray host readback closes
    the timing.
    """
    def step(x0, _):
        out = fn(x0, *args[1:])
        # full-tensor probe: a single-element slice would let XLA dead-code
        # the rest of the dense (non-pallas) kernel
        probe = sum(jnp.sum(l).astype(jnp.float32)
                    for l in jax.tree_util.tree_leaves(out))
        return x0 + (probe * 1e-30).astype(x0.dtype), ()

    def wall(n, repeats=3):
        looped = jax.jit(lambda x0: lax.scan(step, x0, None, length=n)[0])
        np.asarray(looped(args[0]).ravel()[:1])  # compile + warm
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            np.asarray(looped(args[0]).ravel()[:1])  # readback = completion
            times.append(time.perf_counter() - t0)
        return min(times), max(times) - min(times)

    # slope timing: wall(2N) - wall(N) cancels the fixed dispatch + readback
    # latency (would swamp a µs-scale seq-128 kernel).
    # The slope must not only be positive but exceed a NOISE FLOOR — a
    # multiple of the spread across the wall() repeats: a small
    # positive slope that is just the difference of two jittery best-of-3
    # minima would otherwise be recorded and win its block bucket in
    # apply_winners. Retry once, then refuse rather than record a bogus row.
    for attempt in range(2):
        w1, spread1 = wall(iters)
        w2, spread2 = wall(2 * iters)
        slope = w2 - w1
        floor = NOISE_FLOOR_MULT * max(spread1, spread2)
        if slope > max(floor, 0.0):
            return slope / iters * 1e3
    raise RuntimeError(
        "slope %.3g s below noise floor %.3g s (= %g x repeat spread) "
        "twice — jitter, not a timing; config not timed"
        % (slope, floor, NOISE_FLOOR_MULT))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, nargs="+",
                    default=[128, 256, 512, 2048])
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--causal", action="store_true",
                    help="causal masking (default off — the BERT bench path "
                         "is bidirectional)")
    ap.add_argument("--valid-len", type=int, default=0,
                    help="exercise the kv_valid_len key-padding path with "
                         "this per-example length (0 = no padding mask)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write structured sweep results to PATH "
                         "(committed as the evidence artifact for the "
                         "default block-size choice)")
    ap.add_argument("--apply", action="store_true",
                    help="after the sweep, write the per-seq winners into "
                         "mxnet_tpu/ops/pallas/flash_blocks.json so "
                         "flash_attention's BLOCK_DEFAULTS picks them up")
    ap.add_argument("--apply-from", default=None, metavar="SWEEP_JSON",
                    help="skip measuring; fold an existing sweep artifact "
                         "into flash_blocks.json and exit")
    args = ap.parse_args()
    if args.apply_from:
        with open(args.apply_from) as f:
            data = json.load(f)
        return apply_winners(data["rows"], source=os.path.basename(
            args.apply_from), measured_at=data.get("config", {}).get(
            "measured_at"))
    rows = []

    # a sweep can be cut by its time limit: flush each row as a JSON line so
    # a cut costs only the in-flight config. Truncated at start + removed on
    # success: stale rows from an aborted run must not fold into this run's
    # buckets
    partial = (args.json + ".partial") if args.json else None
    if partial:
        open(partial, "w").close()

    def flush_row(row):
        rows.append(row)
        if partial:
            with open(partial, "a") as f:
                f.write(json.dumps(row) + "\n")

    from mxnet_tpu.ops.attention import _reference_attention
    from mxnet_tpu.ops.pallas.flash_attention import flash_attention

    for T in args.seq:
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
        shape = (args.batch, args.heads, T, args.dim)
        q = jax.random.normal(k1, shape, jnp.bfloat16)
        k = jax.random.normal(k2, shape, jnp.bfloat16)
        v = jax.random.normal(k3, shape, jnp.bfloat16)
        causal = args.causal
        vl = (jnp.full((args.batch,), args.valid_len, jnp.float32)
              if args.valid_len else None)
        mask = (None if vl is None else
                (jnp.arange(T)[None, None, None, :] < vl[:, None, None, None]))

        def dense_fwd(q, k, v):
            return _reference_attention(q, k, v, mask, causal=causal)

        def dense_grad(q, k, v):
            # differentiate w.r.t. ALL of q/k/v: default argnums=0 would let
            # XLA dead-code-eliminate the dk/dv two-thirds of the backward
            gs = jax.grad(lambda *a: dense_fwd(*a).astype(jnp.float32).sum(),
                          argnums=(0, 1, 2))(q, k, v)
            return sum(g.astype(jnp.float32).sum() for g in gs)

        print("== seq %d (B%d H%d D%d bf16, causal=%s, vl=%s) ==" %
              (T, args.batch, args.heads, args.dim, causal,
               args.valid_len or "-"), flush=True)
        try:
            ms_f = time_fn(jax.jit(dense_fwd), q, k, v, iters=args.iters)
            ms_b = time_fn(jax.jit(dense_grad), q, k, v, iters=args.iters)
            print("dense xla          fwd %7.3f ms   fwd+bwd %7.3f ms"
                  % (ms_f, ms_b), flush=True)
            flush_row({"seq": T, "kernel": "dense", "fwd_ms": round(ms_f, 3),
                       "fwd_bwd_ms": round(ms_b, 3)})
        except Exception as e:
            print("dense xla failed:", e)

        from mxnet_tpu.ops.pallas.flash_attention import \
            _largest_divisor_block

        for bq in (128, 256, 512):
            for bk in (128, 256, 512):
                if bq > T or bk > T:
                    continue
                # flash_attention shrinks non-divisor blocks; skip labels
                # that would silently re-measure another row's config
                if (_largest_divisor_block(T, bq) != bq
                        or _largest_divisor_block(T, bk) != bk):
                    continue

                def flash_fwd(q, k, v, bq=bq, bk=bk):
                    return flash_attention(q, k, v, causal=causal,
                                           block_q=bq, block_k=bk,
                                           kv_valid_len=vl)

                def flash_grad(q, k, v, bq=bq, bk=bk):
                    gs = jax.grad(lambda *a: flash_fwd(*a).astype(
                        jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)
                    return sum(g.astype(jnp.float32).sum() for g in gs)

                try:
                    ms_f = time_fn(jax.jit(flash_fwd), q, k, v,
                                   iters=args.iters)
                    ms_b = time_fn(jax.jit(flash_grad), q, k, v,
                                   iters=args.iters)
                    print("flash bq=%3d bk=%3d fwd %7.3f ms   fwd+bwd %7.3f ms"
                          % (bq, bk, ms_f, ms_b), flush=True)
                    flush_row({"seq": T, "kernel": "flash", "block_q": bq,
                               "block_k": bk, "fwd_ms": round(ms_f, 3),
                               "fwd_bwd_ms": round(ms_b, 3)})
                except Exception as e:
                    print("flash bq=%3d bk=%3d FAILED: %s" % (bq, bk, e))

    if args.json:
        meta = {"batch": args.batch, "heads": args.heads, "dim": args.dim,
                "causal": args.causal, "valid_len": args.valid_len,
                "iters": args.iters,
                "platform": jax.devices()[0].platform,
                "timing": "slope-chained-v2",
                "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                             time.gmtime())}
        with open(args.json, "w") as f:
            json.dump({"config": meta, "rows": rows}, f, indent=1)
            f.write("\n")
        print("wrote %d rows to %s" % (len(rows), args.json))
        if partial and os.path.exists(partial):
            os.remove(partial)  # the full artifact supersedes the crash log
    if args.apply:
        return apply_winners(
            rows, source=os.path.basename(args.json or "sweep"),
            measured_at=time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()))


def apply_winners(rows, source, measured_at=None):
    """Pick the fastest (block_q, block_k) per swept seq by fwd+bwd time and
    write them into the package block-table artifact. Bucket keys are the
    swept seqs themselves; the smallest seq's winner also becomes the 0
    (catch-all) row so shorter sequences inherit the nearest tuning."""
    from mxnet_tpu.ops.pallas import flash_attention as fa
    winners = {}
    for r in rows:
        if r.get("kernel") != "flash" or "fwd_bwd_ms" not in r:
            continue
        seq = int(r["seq"])
        if seq not in winners or r["fwd_bwd_ms"] < winners[seq]["fwd_bwd_ms"]:
            winners[seq] = r
    if not winners:
        print("no flash rows to apply; leaving flash_blocks.json untouched")
        return 1
    blocks = {str(s): [w["block_q"], w["block_k"]]
              for s, w in winners.items()}
    blocks["0"] = blocks[str(min(winners))]
    # measured flash-vs-dense crossover: the gate is a single threshold
    # (seq >= min_len), so the only SOUND value is the start of a suffix of
    # swept seqs where flash wins consistently — taking the first isolated
    # win would install a measured-slower kernel at larger seqs. When no
    # consistent winning suffix exists, no min_len is written and the gate
    # keeps its static guess (the sweep output still shows the full
    # picture; the headline bert runs at seq 128 — whether it flashes
    # should be hardware's call).
    dense = {}
    for r in rows:
        if r.get("kernel") == "dense" and "fwd_bwd_ms" in r:
            s = int(r["seq"])
            dense[s] = min(dense.get(s, float("inf")), r["fwd_bwd_ms"])
    compared = [s for s in sorted(winners) if s in dense]
    min_len = None
    for s in compared:
        if all(winners[t]["fwd_bwd_ms"] < dense[t]
               for t in compared if t >= s):
            min_len = s
            break
    if compared and min_len is None:
        print("flash beat dense at no consistent seq suffix %s; "
              "min_len not written (static gate stays)" % (compared,))
    # write through the SHARED artifact writer (also used by
    # ir.tune.tune_flash_blocks) so the two tuning paths cannot diverge
    # on format; it validates, writes atomically, and reloads the live
    # table
    try:
        import jax
        backend = jax.default_backend()
    except Exception:
        backend = None
    fa.write_block_artifact(
        {int(s): b for s, b in blocks.items()},
        source=source,
        swept_at=measured_at,
        tuned_by="tools/flash_sweep.py --apply",
        backend=backend,
        min_len=min_len,
        note="winners by min fwd_bwd_ms per seq; written by "
             "tools/flash_sweep.py --apply")
    print("applied block winners to %s: %s" % (fa._BLOCKS_ARTIFACT, blocks))
    return 0


if __name__ == "__main__":
    sys.exit(main() or 0)
