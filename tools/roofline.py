"""No-hardware roofline report over the six bench train steps.

For each bench mode the jitted train step is LOWERED AND COMPILED (never
executed), and XLA's cost analysis plus the optimized HLO text yield:

- flops per step / per sample
- HBM bytes accessed per step, arithmetic intensity (flops/byte)
- the v5e roofline ceiling MFU implied by that intensity
  (peak 197 Tflop/s bf16, 819 GB/s HBM: critical intensity ~241 flops/byte)
- the top-K non-matmul output-byte sinks (fusions, copies, reduces ... —
  the things worth attacking with pallas or layout changes)

Caveats, recorded in the artifact: by default the analysis compiles for the
HOST CPU backend (no chip needed), so TPU-gated pallas kernels appear as
their jnp fallbacks — byte counts for those paths are an UPPER
bound (the kernels exist to shrink them) — and XLA:CPU fusion choices can
differ from XLA:TPU. Flops, which depend on the model math and not the
backend, transfer directly.

Usage: python tools/roofline.py [--modes bert,lstm] [--smoke]
                                [--json PATH]
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

# --backend must be honored BEFORE jax is imported (it reads the env then).
# 'tpu' compiles on the machine with the chip against its own XLA:TPU
# backend — nothing executes, but fusion choices and cost analysis are the
# chip's own.
_BACKEND = "cpu"
for _i, _a in enumerate(sys.argv):
    if _a == "--backend" and _i + 1 < len(sys.argv):
        _BACKEND = sys.argv[_i + 1]
    elif _a.startswith("--backend="):
        _BACKEND = _a.split("=", 1)[1]
os.environ["JAX_PLATFORMS"] = "tpu,cpu" if _BACKEND == "tpu" else "cpu"

import jax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import bench  # noqa: E402

V5E_PEAK_FLOPS = bench.PEAKS["TPU v5 lite"]["bf16_flops"]   # 197e12
V5E_HBM_BYTES_PER_S = 819e9                     # v5e HBM bandwidth
CRITICAL_INTENSITY = V5E_PEAK_FLOPS / V5E_HBM_BYTES_PER_S

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1,
                "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
                "c64": 8, "c128": 16}

# opcodes that ARE the matmul/conv work (or bookkeeping), not byte sinks
_NOT_SINK = {"dot", "convolution", "custom-call", "parameter", "constant",
             "get-tuple-element", "tuple", "bitcast"}

_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?(%?[\w.\-]+)\s+=\s+(\(?[a-z0-9]+\[)")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype, dims):
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _line_opcode(line):
    # `%name = f32[2,3]{1,0} fusion(...), kind=kLoop` → "fusion"
    after = line.split(" = ", 1)[1]
    # skip the (possibly tuple) shape token
    depth, i = 0, 0
    while i < len(after):
        c = after[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
        elif c == " " and depth == 0:
            break
        i += 1
    op = after[i:].strip().split("(", 1)[0].strip()
    return op


def top_sinks(hlo_text, k=5):
    """Top-k instructions by OUTPUT bytes, excluding matmul/conv/bookkeeping.
    Output bytes is the HBM write cost of the instruction; for fusions it is
    exactly what the fusion materializes. Only instructions that actually
    write buffers are counted: the ENTRY computation plus loop bodies —
    fusion-computation internals stay in registers."""
    sinks = []
    counted_scope = False
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if stripped.endswith("{") and "(" in stripped:
            # a computation header: `ENTRY %main (...) -> ... {` or
            # `%fused_computation.1 (...) -> ... {` or `%body.2 (...) {`
            head = stripped.split("(", 1)[0]
            counted_scope = (stripped.startswith("ENTRY")
                             or "while" in head or "body" in head
                             or "cond" in head)
            continue
        if not counted_scope or " = " not in line:
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name = m.group(1)
        try:
            op = _line_opcode(line)
        except IndexError:
            continue
        if op in _NOT_SINK or not op:
            continue
        shape_part = line.split(" = ", 1)[1]
        first = _SHAPE_RE.search(shape_part)
        if not first:
            continue
        out_bytes = _shape_bytes(first.group(1), first.group(2))
        kind = ""
        km = re.search(r"kind=(\w+)", line)
        if km:
            kind = km.group(1)
        sinks.append({"name": name.lstrip("%"), "op": op, "kind": kind,
                      "out_bytes": out_bytes,
                      "shape": "%s[%s]" % (first.group(1), first.group(2))})
    sinks.sort(key=lambda s: -s["out_bytes"])
    return sinks[:k]


# Known sink shapes → the mitigation that already exists in this repo. The
# CPU-lowered HLO shows the jnp fallback paths; on TPU these sinks are
# removed (pallas) or fused away (XLA:TPU elementwise fusion).
def _is_attention_scores(shape):
    """(B,H,T,T) with small H and lane-scale T — NOT a square conv map
    (whose channel dim is large and spatial dims < 128)."""
    m = re.match(r"[a-z0-9]+\[(\d+),(\d+),(\d+),(\d+)\]$", shape)
    return bool(m) and m.group(3) == m.group(4) \
        and int(m.group(2)) <= 16 and int(m.group(3)) >= 128


_MITIGATIONS = (
    (_is_attention_scores,
     "dense attention scores: on TPU the flash kernel "
     "(ops/pallas/flash_attention.py) never materializes (B,H,T,T)"),
    (re.compile(r"f32\[\d+,(30522|30592|50257|50304|32000|10000)\]$").search,
     "LM log-probs: on TPU softmax_xent_rows gates into the fused pallas "
     "kernel (one HBM pass, lse-reusing backward)"),
    (re.compile(r"f32\[(30522|50257|10000),\d+\]$").search,
     "embedding-table optimizer math: XLA:TPU fuses the whole Adam chain "
     "into one kernel; the unfused chain is an XLA:CPU artifact"),
)


def aggregate_sinks(hlo_text, k=5):
    """Same-shape sink chains grouped: total bytes, op histogram, and the
    repo mitigation if one applies. The instruction list double-counts a
    buffer that a chain of unfused elementwise ops rewrites; this view
    answers 'which BUFFER is the problem'."""
    groups = {}
    for s in top_sinks(hlo_text, k=10 ** 6):
        g = groups.setdefault(s["shape"], {"shape": s["shape"],
                                           "total_bytes": 0, "count": 0,
                                           "ops": {}})
        g["total_bytes"] += s["out_bytes"]
        g["count"] += 1
        g["ops"][s["op"]] = g["ops"].get(s["op"], 0) + 1
    out = sorted(groups.values(), key=lambda g: -g["total_bytes"])[:k]
    for g in out:
        for match, note in _MITIGATIONS:
            if match(g["shape"]):
                g["mitigation"] = note
                break
    return out


def analyze_mode(mode, smoke=False, save_hlo=None):
    rng = np.random.default_rng(0)
    (step, params, states, batch, units, metric, unit, baseline,
     _flops_per_unit, _batch_n) = bench._mode_spec(mode, rng, smoke=smoke)
    import jax.numpy as jnp

    key = jax.random.PRNGKey(0)
    t0 = time.time()
    lowered = step.lower(params, states, jnp.int32(1), key, batch)
    compiled = lowered.compile()
    hlo_text = compiled.as_text()  # many MB; regenerate once, not thrice
    if save_hlo:
        # the optimized text carries the backend's OWN fusion names — the
        # join key tools/profile_hlo_map.py uses to turn a captured
        # xplane's "fusion.2248 took 2.1ms" into "which op, what shape"
        os.makedirs(save_hlo, exist_ok=True)
        with open(os.path.join(save_hlo, "hlo_%s_%s.txt"
                               % (_BACKEND, mode)), "w") as f:
            f.write(hlo_text)
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    ai = flops / byts if byts else float("inf")
    # roofline: attainable flops/s = min(peak, AI * BW)
    ceiling_mfu = min(1.0, ai * V5E_HBM_BYTES_PER_S / V5E_PEAK_FLOPS)
    rec = {
        "mode": mode,
        "units_per_step": units,
        "flops_per_step": flops,
        "flops_per_unit": flops / units,
        "hbm_bytes_per_step": byts,
        "arithmetic_intensity": round(ai, 2),
        "ceiling_mfu_v5e": round(ceiling_mfu, 4),
        "bound": "compute" if ai >= CRITICAL_INTENSITY else "memory",
        "top_non_matmul_sinks": top_sinks(hlo_text),
        "sink_buffers": aggregate_sinks(hlo_text),
        "analysis_seconds": round(time.time() - t0, 1),
    }
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--modes", default=",".join(bench.MODES))
    ap.add_argument("--save-hlo", default=None, metavar="DIR",
                    help="save each mode's optimized HLO text to DIR "
                         "(join key for tools/profile_hlo_map.py)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes (CI); the committed artifact uses "
                    "the real bench shapes")
    ap.add_argument("--json", default=None, help="artifact output path")
    ap.add_argument("--backend", default="cpu", choices=["cpu", "tpu"],
                    help="tpu = compile (never execute) against the real "
                         "XLA:TPU backend, on the machine with the chip; "
                         "cpu = needs no chip")
    args = ap.parse_args(argv)
    if args.backend != _BACKEND:
        # argparse accepted a spelling (abbreviation, main(argv=...)) that
        # the import-time env scan missed — the backend pin happens before
        # jax import, so it cannot be fixed up here; refuse loudly instead
        # of silently generating a CPU artifact labeled tpu
        raise SystemExit(
            "--backend must be passed on the command line as "
            "'--backend %s' or '--backend=%s' (import-time env pin saw %r)"
            % (args.backend, args.backend, _BACKEND))

    if _BACKEND == "tpu":
        backend_note = (
            "tpu-compiled (XLA:TPU fusion + cost analysis — the chip's own "
            "view; pallas kernels are opaque custom-calls whose internal "
            "HBM traffic cost analysis cannot see, so bytes on those paths "
            "are a lower bound)")
        ceiling_note = (
            "ceilings derive from XLA:TPU's own 'bytes accessed'; they are "
            "the roofline for THIS compiled program (a lower-traffic "
            "rewrite can raise them)")
    else:
        backend_note = ("cpu-lowered (pallas-gated kernels appear as jnp "
                        "fallbacks; bytes for those paths are an upper bound)")
        ceiling_note = (
            "XLA:CPU 'bytes accessed' counts the weakly-fused "
            "CPU pipeline's traffic, so these ceilings are NOT "
            "upper bounds for TPU (bert512 MEASURED 0.276 MFU "
            "on hardware vs the 0.11 cpu-derived ceiling). Use "
            "them to RANK modes/sinks; the true TPU roofline "
            "needs the TPU-compiled HLO (--backend tpu on the chip).")
    out = {
        "generated_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "backend": backend_note,
        "ceiling_caveat": ceiling_note,
        "v5e_peak_bf16_flops": V5E_PEAK_FLOPS,
        "v5e_hbm_bytes_per_s": V5E_HBM_BYTES_PER_S,
        "critical_intensity_flops_per_byte": round(CRITICAL_INTENSITY, 1),
        "smoke": bool(args.smoke),
        "modes": {},
    }
    for mode in args.modes.split(","):
        mode = mode.strip()
        if not mode:
            continue
        print("[roofline] analyzing %s..." % mode, flush=True)
        try:
            out["modes"][mode] = analyze_mode(mode, smoke=args.smoke,
                                  save_hlo=args.save_hlo)
        except Exception as e:  # record the failure, keep going
            out["modes"][mode] = {"mode": mode, "error": repr(e)}
        m = out["modes"][mode]
        if "error" not in m:
            print("[roofline] %s: %.1f Gflop/step, %.2f GB/step, AI=%.1f, "
                  "ceiling MFU=%.2f (%s-bound)"
                  % (mode, m["flops_per_step"] / 1e9,
                     m["hbm_bytes_per_step"] / 2**30,
                     m["arithmetic_intensity"], m["ceiling_mfu_v5e"],
                     m["bound"]), flush=True)
        else:
            print("[roofline] %s FAILED: %s" % (mode, m["error"]), flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1, sort_keys=True)
        print("[roofline] wrote %s" % args.json)
    else:
        print(json.dumps(out))
    return out


if __name__ == "__main__":
    main()
