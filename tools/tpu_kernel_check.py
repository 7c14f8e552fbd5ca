#!/usr/bin/env python
"""On-hardware (non-interpret) numerics check for every pallas kernel.

The pytest suite runs the kernels in interpret mode on the CPU mesh
(tests/conftest.py pins JAX_PLATFORMS=cpu), which validates math but not
Mosaic lowering/tiling (tests/test_tpu_compile.py compiles them for a
described chip, which proves lowering but runs nothing). This script runs
the same checks on the real TPU chip:

    python tools/tpu_kernel_check.py [--json PATH]

Exits 0 and prints PASS lines on success; nonzero on numeric mismatch.
--json writes a structured record of every check (name, max error, tolerance,
platform, timestamp) — the committable evidence artifact that the
non-interpret Mosaic lowering ran on hardware.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def log(msg):
    print("[tpu-kernel-check] %s" % msg, flush=True)


def main():
    json_path = None
    argv = sys.argv[1:]
    if "--json" in argv:
        i = argv.index("--json")
        if i + 1 >= len(argv):
            print("usage: tpu_kernel_check.py [--json PATH]", file=sys.stderr)
            return 2
        json_path = argv[i + 1]

    t0 = time.time()
    devs = jax.devices()
    log("devices: %s (%.1fs)" % (devs, time.time() - t0))
    if devs[0].platform != "tpu":
        log("no TPU present; nothing to check")
        return 1

    rows = []

    def record(name, err, tol):
        ok = err < tol
        rows.append({"check": name, "max_err": float("%.3e" % err),
                     "tol": tol, "pass": bool(ok)})
        log("%s %s (maxerr %.2e, tol %g)"
            % (name, "PASS" if ok else "FAIL", err, tol))
        return ok

    def record_rel(name, err, xla_err, margin=1.5, floor=1e-5):
        """Oracle-relative criterion: on real MXUs BOTH flash and XLA's
        dense attention run default-precision matmuls, whose rounding
        against a precision=HIGHEST oracle reaches ~1e-2 (causal f32,
        measured r5) — an absolute tolerance can only be wrong on one
        side. The invariant that matters: the kernel is no less accurate
        than what XLA itself does at the same dtype."""
        tol = max(xla_err * margin, floor)
        ok = err <= tol
        rows.append({"check": name, "max_err": float("%.3e" % err),
                     "xla_default_err": float("%.3e" % xla_err),
                     "tol": float("%.3e" % tol), "pass": bool(ok),
                     "criterion": "flash_err <= max(%.1fx XLA-default err, "
                                  "%g) vs precision=HIGHEST oracle"
                                  % (margin, floor)})
        log("%s %s (maxerr %.2e vs XLA-default %.2e, tol %.2e)"
            % (name, "PASS" if ok else "FAIL", err, xla_err, tol))
        return ok

    from mxnet_tpu.ops.pallas.flash_attention import (BLOCK_DEFAULTS,
                                                      flash_attention)
    from mxnet_tpu.ops.pallas.layernorm import fused_layernorm
    from mxnet_tpu.ops.pallas.softmax_xent import softmax_xent
    from mxnet_tpu.parallel import full_attention
    from mxnet_tpu.ops.functional import LayerNorm

    # flash attention fwd + bwd (non-interpret Mosaic lowering)
    B, H, T, D = 2, 4, 512, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v = (jax.random.normal(kk, (B, H, T, D), jnp.float32) for kk in ks[:3])
    ct = jax.random.normal(ks[3], (B, H, T, D), jnp.float32)
    for causal in (False, True):
        def fl_fwd(a, b, c, causal=causal):
            return flash_attention(a, b, c, causal=causal)

        def xla_fwd(a, b, c, causal=causal):
            return full_attention(a, b, c, causal=causal)

        def fl_loss(a, b, c, causal=causal):
            return jnp.sum(flash_attention(a, b, c, causal=causal) * ct)

        def xla_loss(a, b, c, causal=causal):
            return jnp.sum(full_attention(a, b, c, causal=causal) * ct)

        with jax.default_matmul_precision("highest"):
            oracle = jax.jit(xla_fwd)(q, k, v)
            g_oracle = jax.jit(jax.grad(xla_loss, argnums=(0, 1, 2)))(q, k, v)
        out = jax.jit(fl_fwd)(q, k, v)
        ref = jax.jit(xla_fwd)(q, k, v)
        record_rel("flash_fwd_causal=%s" % causal,
                   float(jnp.abs(out - oracle).max()),
                   float(jnp.abs(ref - oracle).max()))

        grads = jax.jit(jax.grad(fl_loss, argnums=(0, 1, 2)))(q, k, v)
        refs = jax.jit(jax.grad(xla_loss, argnums=(0, 1, 2)))(q, k, v)
        for g, r, o, name in zip(grads, refs, g_oracle, ("dq", "dk", "dv")):
            record_rel("flash_bwd_%s_causal=%s" % (name, causal),
                       float(jnp.abs(g - o).max()),
                       float(jnp.abs(r - o).max()))

    # key-padding (kv_valid_len) path — the BERT bench configuration
    from mxnet_tpu.ops.attention import _reference_attention
    vl = jnp.asarray([384.0, 512.0], jnp.float32)
    mask = jnp.arange(T)[None, None, None, :] < vl[:, None, None, None]

    def xla_vl(a, b, c):
        return _reference_attention(a, b, c, mask)

    with jax.default_matmul_precision("highest"):
        oracle = jax.jit(xla_vl)(q, k, v)
    out = jax.jit(lambda a, b, c: flash_attention(a, b, c, kv_valid_len=vl))(q, k, v)
    ref = jax.jit(xla_vl)(q, k, v)
    record_rel("flash_fwd_kv_valid_len",
               float(jnp.abs(out - oracle).max()),
               float(jnp.abs(ref - oracle).max()))

    # fused layernorm
    x = jax.random.normal(jax.random.PRNGKey(1), (256, 1024), jnp.float32)
    g = jax.random.normal(jax.random.PRNGKey(2), (1024,))
    b = jax.random.normal(jax.random.PRNGKey(3), (1024,))
    out = jax.jit(fused_layernorm)(x, g, b)
    ref = LayerNorm(x, g, b)
    record("fused_layernorm", float(jnp.abs(out - ref).max()), 1e-3)

    # fused softmax cross-entropy fwd + bwd, at the bench's real vocab width
    rng = np.random.RandomState(3)
    logits = jnp.asarray(rng.randn(128, 30522).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, 30522, 128).astype(np.int32))
    loss = jax.jit(lambda lg: softmax_xent(lg, labels))(logits)
    ref = -jax.nn.log_softmax(logits)[jnp.arange(128), labels]
    record("softmax_xent_fwd_V30522", float(jnp.abs(loss - ref).max()), 1e-4)

    dx = jax.jit(jax.grad(lambda lg: softmax_xent(lg, labels).mean()))(logits)
    dref = jax.grad(
        lambda lg: (-jax.nn.log_softmax(lg)[jnp.arange(128), labels]).mean())(logits)
    record("softmax_xent_bwd_V30522", float(jnp.abs(dx - dref).max()), 1e-6)

    ok = all(r["pass"] for r in rows)
    log("%s ON %s" % ("ALL PALLAS KERNELS PASS" if ok else "FAILURES PRESENT",
                      devs[0].platform))
    if json_path:
        art = {"platform": devs[0].platform,
               "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
               "block_defaults": {str(k): list(vv)
                                  for k, vv in BLOCK_DEFAULTS.items()},
               "all_pass": ok, "checks": rows}
        with open(json_path, "w") as f:
            json.dump(art, f, indent=1)
            f.write("\n")
        log("wrote %d checks to %s" % (len(rows), json_path))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
