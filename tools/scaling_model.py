#!/usr/bin/env python
"""Analytic ICI scaling model + measured collective inventory.

Real multi-chip hardware is unavailable in this harness, so the BASELINE.md
row "8->256 chip scaling efficiency (BERT) = 0.90" cannot be measured. This
tool produces the next-best evidence, in two grounded halves:

1. **Measured structure** — compile the REAL composed dp x tp x pp 1F1B train
   step (parallel/pipeline.py, the same program the multichip dryrun runs) on
   a virtual 8-device CPU mesh and parse the post-GSPMD HLO for its
   collectives: kind, byte volume, participant-group size. This pins the
   communication pattern of the actual program — not a paper model of it.

2. **Analytic ICI time** — scale BERT-base data-parallel pretraining (the
   BASELINE row's config) over a TPU v5e 2D torus: ring all-reduce of the
   fp32 gradients vs per-chip step compute at the measured MFU (falls back
   to 0.40 when no BENCH_RESULTS.json record exists). Gradient all-reduce
   overlaps the backward pass (XLA's latency-hiding scheduler issues async
   collectives; the scaling-book dp recipe), so the exposed time is
   (1 - overlap) * t_allreduce; both the overlapped (0.9) and worst-case
   (0.0) curves are emitted.

Run:  python tools/scaling_model.py [--json tools/scaling_model_r5.json]
The committed JSON is the artifact SURVEY.md / the bench story cite.
"""
import argparse
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# ----------------------------------------------------------------- constants
V5E = {
    "peak_bf16_flops": 197e12,      # per chip
    "ici_link_gbytes": 45.0,        # per link, per direction (2D torus)
    "torus_axes": 2,                # v5e: 2D torus, one ring per axis
    "hop_latency_s": 1e-6,
}

BERT_PARAMS = 110e6                 # BERT-base
GRAD_BYTES = BERT_PARAMS * 4        # fp32 grads all-reduced per step
BATCH_PER_CHIP = 32                 # BASELINE.md bench config
DEFAULT_MFU = 0.40

# BERT-base shape constants for the tp activation-collective terms
BERT_LAYERS = 12
BERT_HIDDEN = 768
BERT_SEQ = 128

# v5e pod boundary + cross-pod DCN (per-host NICs; v5e hosts hold 8 chips).
# DCN numbers are deployment-dependent — these are deliberately conservative
# and recorded in the artifact as assumptions.
POD_CHIPS = 256
CHIPS_PER_HOST = 8
DCN_GBYTES_PER_HOST = 12.5          # ~100 Gb/s per host, conservative


def _bert_flops_per_sample():
    import bench
    return bench._bert_train_flops_per_sample(bench.SEQ, bench.MASKED)


def measured_mfu():
    try:
        with open(os.path.join(REPO, "BENCH_RESULTS.json")) as f:
            results = json.load(f)
        for mode in ("bert", "bert512"):
            if results.get(mode, {}).get("mfu"):
                return float(results[mode]["mfu"]), mode
    except (OSError, ValueError):
        pass
    return DEFAULT_MFU, "assumed"


# ------------------------------------------------------- 1. HLO collectives
_COLL = re.compile(
    r"=\s+(?P<shape>\(?[a-z0-9]+\[[^=]*?)\s+"
    r"(?P<kind>all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)(?:-start)?\(")
_SHAPE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_GROUPS = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_PAIRS = re.compile(r"source_target_pairs=\{")

_DT_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
             "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
             "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}


def _shape_bytes(txt):
    total = 0
    for dt, dims in _SHAPE.findall(txt):
        if dt not in _DT_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DT_BYTES[dt]
    return total


def parse_hlo_collectives(hlo_text):
    """Inventory of collectives in compiled HLO: kind -> count, total bytes,
    and participant-group sizes seen."""
    inv = {}
    for line in hlo_text.splitlines():
        m = _COLL.search(line)
        if not m:
            continue
        kind = m.group("kind")
        nbytes = _shape_bytes(m.group("shape"))
        g = _GROUPS.search(line)
        gsize = len(g.group(1).split(",")) if g else None
        rec = inv.setdefault(kind, {"count": 0, "bytes": 0, "group_sizes": []})
        rec["count"] += 1
        rec["bytes"] += nbytes
        if gsize and gsize not in rec["group_sizes"]:
            rec["group_sizes"].append(gsize)
    return inv


def composed_step_inventory():
    """Compile the real dp2 x tp2 x pp2 composed 1F1B step (tiny shapes) and
    inventory its collectives. Must run on a >=8-device mesh."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from mxnet_tpu import parallel
    from mxnet_tpu.parallel.tensor_parallel import (psum_region_entry,
                                                    psum_region_exit)

    S, M, MB, U, H = 2, 5, 4, 4, 8
    mesh = parallel.make_mesh({"dp": 2, "tp": 2, "pp": 2})

    def stage_fn(params, x):
        x = psum_region_entry(x, "tp")
        h = jnp.tanh(x @ params["w1"] + params["b1"])
        y = h @ params["w2"]
        return psum_region_exit(y, "tp") + params["b2"]

    def loss_fn(y, t):
        return jnp.mean((y - t) ** 2)

    rng = np.random.default_rng(0)
    per_stage = [{
        "w1": jnp.asarray(rng.normal(size=(U, H)) * 0.4, jnp.float32),
        "b1": jnp.zeros((H,), jnp.float32),
        "w2": jnp.asarray(rng.normal(size=(H, U)) * 0.4, jnp.float32),
        "b2": jnp.zeros((U,), jnp.float32),
    } for _ in range(S)]
    stacked = parallel.stack_stage_params(per_stage)
    xs = jnp.asarray(rng.normal(size=(M, MB, U)), jnp.float32)
    tg = jnp.asarray(rng.normal(size=(M, MB, U)), jnp.float32)
    param_spec = {"w1": P("pp", None, "tp"), "b1": P("pp", "tp"),
                  "w2": P("pp", "tp", None), "b2": P("pp")}

    def step(stacked, xs, tg):
        return parallel.pipeline_train_step_1f1b(
            stage_fn, loss_fn, stacked, xs, tg, mesh,
            batch_axis="dp", param_spec=param_spec)

    lowered = jax.jit(step).lower(stacked, xs, tg)
    hlo = lowered.compile().as_text()
    return parse_hlo_collectives(hlo), {"mesh": {"dp": 2, "tp": 2, "pp": 2},
                                        "stages": S, "microbatches": M,
                                        "mb_rows": MB, "width": U}


# ------------------------------------------------- 2. analytic weak scaling
def allreduce_time(nbytes, n_chips, axes=None):
    """Bidirectional ring all-reduce over a 2D torus: XLA splits the
    reduction across both torus axes, so the effective bandwidth is
    axes * per-link-per-direction; volume factor is the standard
    2*(n-1)/n."""
    axes = axes or V5E["torus_axes"]
    bw = axes * V5E["ici_link_gbytes"] * 1e9
    ring = max(2, round(n_chips ** (1.0 / axes)))
    return (2.0 * nbytes * (n_chips - 1) / n_chips / bw
            + 2 * (ring - 1) * V5E["hop_latency_s"])


def dcn_allreduce_time(nbytes, n_chips):
    """Cross-pod hierarchical all-reduce: the intra-pod ICI phase is already
    modeled by allreduce_time; past one pod the inter-pod phase moves the
    full gradient once over each pod's aggregate DCN (ring over pods,
    2(P-1)/P volume factor)."""
    if n_chips <= POD_CHIPS:
        return 0.0
    pods = (n_chips + POD_CHIPS - 1) // POD_CHIPS
    pod_dcn_bw = (POD_CHIPS // CHIPS_PER_HOST) * DCN_GBYTES_PER_HOST * 1e9
    return 2.0 * nbytes * (pods - 1) / pods / pod_dcn_bw


def tp_collective_time(tp, batch_per_chip=BATCH_PER_CHIP):
    """Megatron tensor parallelism: 4 activation all-reduces per transformer
    layer per step (f/g in forward, their adjoints in backward), each of
    (B_replica, T, H) bf16 riding ONE torus axis's ring. Weak scaling keeps
    the per-CHIP batch fixed, so a tp group's replica batch — and the
    all-reduced activation — is tp * batch_per_chip samples (per-chip
    compute stays t_c: each chip does 1/tp of the replica's matmuls). These
    sit on the critical path — unlike the grad all-reduce they cannot
    overlap the backward."""
    if tp <= 1:
        return 0.0
    act_bytes = tp * batch_per_chip * BERT_SEQ * BERT_HIDDEN * 2
    return BERT_LAYERS * 4 * allreduce_time(act_bytes, tp, axes=1)


def pp_bubble_overhead(stages, microbatches):
    """1F1B steady-state bubble: step time inflates by (S-1)/M of the
    compute (GPipe/1F1B fill+drain; interleaving with v virtual chunks
    divides this by v — modeled at v=1, the pessimistic case)."""
    if stages <= 1:
        return 0.0
    return (stages - 1) / microbatches


def strategy_step_time(n, overlap, t_compute, tp=1, pp=1, pp_microbatches=32):
    """Step time for dp x tp x pp at n chips: compute (+ pp bubble),
    critical-path tp collectives, exposed dp grad all-reduce (params shard
    1/(tp*pp) per dp ring; the pp stages / tp shards reduce concurrently on
    disjoint links), and the cross-pod DCN phase, which overlaps like the
    ICI phase. The DCN term keys on TOTAL chips n: the dp replicas span
    every pod the job occupies even when tp/pp shrink the dp count."""
    dp = n // (tp * pp)
    if dp < 1:
        return None
    t_pp = t_compute * pp_bubble_overhead(pp, pp_microbatches)
    t_tp = tp_collective_time(tp)
    grad_shard = GRAD_BYTES / (tp * pp)
    t_ar = allreduce_time(grad_shard, dp) + dcn_allreduce_time(grad_shard, n)
    exposed = max(0.0, (1.0 - overlap) * t_ar)
    return {"dp": dp, "tp": tp, "pp": pp,
            "t_compute_ms": round(t_compute * 1e3, 3),
            "t_pp_bubble_ms": round(t_pp * 1e3, 3),
            "t_tp_collectives_ms": round(t_tp * 1e3, 3),
            "t_dp_allreduce_ms": round(t_ar * 1e3, 3),
            "t_exposed_ms": round(exposed * 1e3, 3),
            "t_step_ms": round((t_compute + t_pp + t_tp + exposed) * 1e3, 3)}


def required_overlap_for(target_eff, chips, mfu):
    """The smallest overlap fraction at which the 8->chips[-1] weak-scaling
    efficiency reaches target_eff (same formulas as bert_dp_curve) — the
    model's honest statement of what the 0.90 BASELINE row DEPENDS on when
    the worst case misses it. Returns None if even full overlap misses."""
    for i in range(101):
        ov = i / 100.0
        curve, _ = bert_dp_curve(chips, mfu, overlap=ov)
        if curve[-1]["efficiency_vs_%d" % chips[0]] >= target_eff:
            return ov
    return None


def bert_dp_curve(chips, mfu, overlap):
    """Weak scaling (fixed BATCH_PER_CHIP) of BERT-base pure-dp pretraining:
    per-chip compute is constant; the dp gradient all-reduce grows with the
    (n-1)/n volume factor and ring latency. efficiency(N) is throughput per
    chip at N vs at chips[0]."""
    flops = _bert_flops_per_sample() * BATCH_PER_CHIP
    t_compute = flops / (V5E["peak_bf16_flops"] * mfu)
    rows = []
    for n in chips:
        t_ar = allreduce_time(GRAD_BYTES, n) + dcn_allreduce_time(GRAD_BYTES, n)
        exposed = max(0.0, (1.0 - overlap) * t_ar)
        rows.append({"chips": n, "t_compute_ms": round(t_compute * 1e3, 3),
                     "t_allreduce_ms": round(t_ar * 1e3, 3),
                     "t_exposed_ms": round(exposed * 1e3, 3),
                     "t_step_ms": round((t_compute + exposed) * 1e3, 3)})
    t0 = rows[0]["t_step_ms"]
    for r in rows:
        r["efficiency_vs_%d" % chips[0]] = round(t0 / r["t_step_ms"], 4)
    return rows, t_compute


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=os.path.join(
        REPO, "tools", "scaling_model_r5.json"))
    ap.add_argument("--skip-hlo", action="store_true",
                    help="analytic curve only (no 8-device compile)")
    args = ap.parse_args(argv)

    # the virtual CPU mesh of tests/conftest.py, set before jax is imported
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (flags +
                                   " --xla_force_host_platform_device_count=8")

    mfu, mfu_src = measured_mfu()
    chips = [8, 16, 32, 64, 128, 256]
    chips_xpod = chips + [512, 1024]
    curve_overlap, t_c = bert_dp_curve(chips_xpod, mfu, overlap=0.9)
    curve_worst, _ = bert_dp_curve(chips_xpod, mfu, overlap=0.0)

    # dp x tp x pp strategy table at the pod boundary: the tp activation
    # all-reduces are critical-path and the pp bubble inflates compute, so
    # at BERT-base scale pure dp should win — the point of carrying the
    # terms is that the model CAN now say so (and can fail a target).
    strategies = {}
    for name, tp, pp in (("dp", 1, 1), ("dp_tp8", 8, 1), ("dp_pp4", 1, 4),
                         ("dp_tp8_pp4", 8, 4)):
        row = strategy_step_time(POD_CHIPS, 0.0, t_c, tp=tp, pp=pp)
        if row is not None:
            strategies[name] = row

    worst_eff = curve_worst[len(chips) - 1]["efficiency_vs_8"]  # 256 chips
    need = required_overlap_for(0.90, chips, mfu)
    baseline = {
        "claim": "8->256 scaling efficiency 0.90 (BASELINE.md)",
        "model_prediction_overlap0.9":
            curve_overlap[len(chips) - 1]["efficiency_vs_8"],
        "model_prediction_overlap0.0": worst_eff,
        "met_under_worst_case": bool(worst_eff >= 0.90),
    }
    if not baseline["met_under_worst_case"]:
        baseline["honest_statement"] = (
            "the 0.90 row is NOT met at zero overlap (%0.3f): it depends on "
            "the async grad all-reduce overlapping the backward pass; the "
            "model needs overlap >= %.2f. The scaling-book dp recipe and "
            "XLA's latency-hiding scheduler make that plausible but it is "
            "UNMEASURED until a multi-chip profile exists."
            % (worst_eff, need))
    if need is not None:
        baseline["required_overlap_for_0.90"] = need

    # Sensitivity: the 0.90 row gets HARDER as MFU improves (faster compute
    # exposes the same all-reduce). At the round's MFU targets the worst
    # case fails and the row depends on overlap — the model can now say so
    # instead of only ever validating.
    baseline["mfu_sensitivity_worst_case"] = {}
    for m in sorted({round(mfu, 4), 0.40, 0.50, 0.60}):
        c, _ = bert_dp_curve(chips, m, overlap=0.0)
        e = c[-1]["efficiency_vs_8"]
        entry = {"efficiency_8_to_256": e, "meets_0.90": bool(e >= 0.90)}
        if e < 0.90:
            entry["required_overlap"] = required_overlap_for(0.90, chips, m)
        baseline["mfu_sensitivity_worst_case"]["mfu_%s" % m] = entry

    baseline["structural_note"] = (
        "intra-pod the worst case cannot fall much below ~0.95 at ANY mfu: "
        "ring all-reduce time saturates with the 2(n-1)/n factor, so "
        "t_ar(8) is already ~88%% of t_ar(256) and the 8->256 RATIO stays "
        "flat even with zero overlap. The axes on which the row can "
        "actually fail are cross-pod DCN bandwidth (see dcn_sensitivity) "
        "and the latency-bound small-tensor regime, not intra-pod ICI "
        "bandwidth.")
    # cross-pod: at what DCN bandwidth does 8->1024 fall below 0.90?
    global DCN_GBYTES_PER_HOST
    saved_dcn = DCN_GBYTES_PER_HOST
    baseline["dcn_sensitivity_8_to_1024_worst_case"] = {}
    try:
        for bw in (25.0, 12.5, 5.0, 2.0):
            DCN_GBYTES_PER_HOST = bw
            c, _ = bert_dp_curve(chips_xpod, mfu, overlap=0.0)
            e = c[-1]["efficiency_vs_8"]
            baseline["dcn_sensitivity_8_to_1024_worst_case"][
                "dcn_%sGBps_per_host" % bw] = {
                    "efficiency": e, "meets_0.90": bool(e >= 0.90)}
    finally:
        DCN_GBYTES_PER_HOST = saved_dcn

    out = {
        "constants": dict(V5E, bert_params=BERT_PARAMS,
                          grad_bytes=GRAD_BYTES,
                          batch_per_chip=BATCH_PER_CHIP,
                          pod_chips=POD_CHIPS,
                          dcn_gbytes_per_host=DCN_GBYTES_PER_HOST),
        "mfu": {"value": mfu, "source": mfu_src},
        "assumptions": [
            "weak scaling: fixed per-chip batch %d" % BATCH_PER_CHIP,
            "fp32 gradient all-reduce rides a bidirectional ring per torus "
            "axis (2 axes on v5e); volume factor 2(n-1)/n",
            "overlap=0.9: XLA's latency-hiding scheduler overlaps the async "
            "grad all-reduce with the backward pass (dp recipe, "
            "jax-ml.github.io/scaling-book); overlap=0.0 is the no-overlap "
            "worst case; the overlap is UNMEASURED (needs a multi-chip "
            "profile) — required_overlap_for_0.90 states the dependency",
            "past %d chips the inter-pod phase rides DCN at %.1f GB/s per "
            "host (conservative), hierarchical ring over pods"
            % (POD_CHIPS, DCN_GBYTES_PER_HOST),
            "tp: 4 critical-path activation all-reduces per layer "
            "(Megatron f/g + adjoints) on one torus axis; dp grad volume "
            "shards 1/(tp*pp)",
            "pp: 1F1B bubble (S-1)/M at M=32 microbatches, v=1 (interleaved "
            "v>1 shrinks it)",
        ],
        "bert_dp_weak_scaling_overlap0.9": curve_overlap,
        "bert_dp_weak_scaling_overlap0.0": curve_worst,
        "strategy_table_256_worst_case": strategies,
        "baseline_row": baseline,
    }

    if not args.skip_hlo:
        inv, cfg = composed_step_inventory()
        out["composed_step_collectives"] = {
            "config": cfg,
            "inventory": inv,
            "note": "parsed from the compiled post-GSPMD HLO of the real "
                    "dp2xtp2xpp2 1F1B step on the 8-device virtual mesh; "
                    "bytes are the tiny dryrun shapes (structure, not scale)",
        }

    with open(args.json, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")
    print("wrote %s" % args.json)
    print("predicted 8->256 efficiency: %.3f (overlap 0.9) / %.3f (worst)"
          % (out["baseline_row"]["model_prediction_overlap0.9"],
             out["baseline_row"]["model_prediction_overlap0.0"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
