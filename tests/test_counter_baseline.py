"""CI counter-baseline gate (ISSUE 8 satellite): replay the quick bench
scenarios — optstep / imperative / autograd / serve / decode — and assert
the dispatch/compile counters match the committed ``tools/*_bench_quick
.json`` artifacts. Timing columns are host-dependent and excluded; the
COUNTER columns (dispatches per step/iter, steady-state recompiles) are
the repo's one-dispatch story and must never regress: a change that turns
1 dispatch/step into 2 fails here even if every parity test still passes.

The replays reuse the bench tools' own scenario builders (imported from
tools/) at reduced iteration counts — counter columns are deterministic
per iteration, so fewer iterations measure the identical value.
"""
import importlib.util
import json
import os
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import engine, gluon

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, "tools")


def _tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(TOOLS, "%s.py" % name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _artifact(name):
    with open(os.path.join(TOOLS, name)) as fh:
        return json.load(fh)


def _row(artifact, case):
    rows = {r["case"]: r for r in artifact["rows"]}
    assert case in rows, "artifact row %r missing (have %s)" \
        % (case, sorted(rows))
    return rows[case]


# ------------------------------------------------------------- optstep
def test_optstep_dispatch_counters_match_artifact():
    art = _artifact("opt_step_bench_quick.json")
    bench = _tool("opt_step_bench")
    for case, n_tensors in (("resnet50_sized", 160), ("bert_sized", 200)):
        row = _row(art, case)
        tr, ps = bench.build_trainer(n_tensors, quick=True,
                                     optimizer=row["optimizer"], fused=True)
        _ms, disp = bench.time_loop(tr, ps, iters=3)
        assert disp == row["fused_dispatches_per_step"], \
            "%s: fused step now takes %.1f dispatches (baseline %.1f)" \
            % (case, disp, row["fused_dispatches_per_step"])


# ---------------------------------------------------------- imperative
def test_imperative_dispatch_counters_match_artifact():
    art = _artifact("imperative_bench_quick.json")
    bench = _tool("imperative_bench")
    for case, n_ops in (("chain50", 50), ("chain15", 15)):
        row = _row(art, case)
        _ms, disp, _out = bench.run_case(case, n_ops, "lazy", iters=5,
                                         quick=True)
        assert disp == row["lazy_dispatches_per_iter"], \
            "%s: lazy chain now takes %.1f dispatches/iter (baseline %.1f)" \
            % (case, disp, row["lazy_dispatches_per_iter"])


# ------------------------------------------------------------ autograd
def test_autograd_dispatch_counters_match_artifact():
    art = _artifact("autograd_bench_quick.json")
    bench = _tool("autograd_bench")
    for case, n_ops in (("chain50", 50), ("chain15", 15)):
        row = _row(art, case)
        _ms, disp, recompiles, _g = bench.run_case(n_ops, "compiled",
                                                   iters=5, quick=True)
        assert disp == row["compiled_dispatches_per_iter"], \
            "%s: record→backward now takes %.1f dispatches/iter " \
            "(baseline %.1f)" % (case, disp,
                                 row["compiled_dispatches_per_iter"])
        assert recompiles == row["steady_state_tape_recompiles"], \
            "%s: %d steady-state tape recompiles (baseline %d)" \
            % (case, recompiles, row["steady_state_tape_recompiles"])


# ------------------------------------------------------------ graph IR
def test_ir_counters_and_node_shrink_match_artifact():
    """The unified-IR gate: the repeated-subexpression chain must keep
    lowering to 1 dispatch/iter with zero steady-state recompiles, AND
    the pass pipeline must keep shrinking it to the committed node
    counts — a pass regression that stops CSE/DCE from firing fails
    here even though parity tests still pass."""
    art = _artifact("ir_bench_quick.json")
    bench = _tool("ir_bench")
    for case, reps in (("cse_chain12", 12), ("cse_chain4", 4)):
        row = _row(art, case)
        _ms, disp, recompiles, build, pdelta, _out = bench.run_case(
            case, reps, "lazy", iters=5, quick=True)
        assert disp == row["lazy_dispatches_per_iter"], \
            "%s: IR-lowered chain now takes %.1f dispatches/iter " \
            "(baseline %.1f)" % (case, disp,
                                 row["lazy_dispatches_per_iter"])
        assert recompiles == row["steady_state_recompiles"], \
            "%s: %d steady-state recompiles (baseline %d)" \
            % (case, recompiles, row["steady_state_recompiles"])
        for col in ("nodes_captured", "nodes_canonical", "nodes_final"):
            assert build[col] == row[col], \
                "%s: %s now %d (baseline %d) — pass pipeline changed " \
                "shape" % (case, col, build[col], row[col])
        assert pdelta["cse"] == row["cse_rewrites"]
        assert pdelta["dce"] == row["dce_nodes_removed"]


# --------------------------------------------------------------- serve
def test_serve_dispatch_counters_match_artifact():
    art = _artifact("serve_bench_quick.json")
    row = _row(art, "mlp64")
    bench = _tool("serve_bench")
    rng = np.random.default_rng(0)
    net = bench.build_model(features=64)
    samples = [rng.normal(size=(64,)).astype(np.float32)
               for _ in range(row["requests_per_iter"])]
    srv = mx.serve.ModelServer(net, [((64,), "float32")],
                               buckets=tuple(row["buckets"]),
                               max_wait_ms=row["max_wait_ms"],
                               max_queue=4096, timeout_ms=30000.0)
    with srv:
        handles = [srv.submit(s) for s in samples]   # warmup wave
        for h in handles:
            h.result(30)
        best_disp = float("inf")
        engine.serve_compile_counter.reset()
        # min over repeats: counters are deterministic per perfectly
        # coalesced wave; scheduler jitter can only split batches (more
        # dispatches), so the min is the comparable baseline figure
        # (5 waves: 3 still flaked ~1/6 on a loaded host — observed on
        # pristine HEAD too, the jitter is the batcher's, not the IR's)
        for _ in range(5):
            engine.dispatch_counter.reset()
            handles = [srv.submit(s) for s in samples]
            for h in handles:
                h.result(30)
            best_disp = min(best_disp, engine.dispatch_counter.count)
        recompiles = engine.serve_compile_counter.count
    assert best_disp == row["served_dispatches_per_iter"], \
        "serving a %d-request wave now takes %.1f dispatches (baseline " \
        "%.1f)" % (row["requests_per_iter"], best_disp,
                   row["served_dispatches_per_iter"])
    assert recompiles == row["steady_state_recompiles"], \
        "%d steady-state bucket recompiles (baseline %d)" \
        % (recompiles, row["steady_state_recompiles"])


# -------------------------------------------------------------- decode
def test_decode_dispatch_counters_match_artifact():
    from mxnet_tpu.models.gpt import gpt_nano

    art = _artifact("serve_decode_bench_quick.json")
    row = _row(art, "gpt_nano decode")
    rng = np.random.default_rng(0)
    m = gpt_nano()
    m.initialize()
    m.hybridize()
    prompts = [rng.integers(0, 256, size=(int(l),)).astype(np.int32)
               for l in rng.integers(3, 12, size=row["requests"])]
    srv = mx.serve.GenerativeServer(m, slots=row["slots"], max_wait_ms=1.0,
                                    max_queue=max(64, row["requests"]),
                                    timeout_ms=120000.0)
    srv.warmup(prompt_buckets=(4, 8, 16), max_tokens=32)
    try:
        streams = [srv.submit(p, max_new_tokens=8) for p in prompts]
        srv._batcher.start()
        time.sleep(0.05)  # admission handover
        engine.decode_compile_counter.reset()
        pure_disp = pure_steps = 0
        t0 = time.time()
        while not all(s.done() for s in streams) and time.time() - t0 < 120:
            # dispatches/step is measured over PURE decode ticks only —
            # a tick that admits joins also pays prefill/inject — and in
            # steady state: the tick that reads a stretch's last step sends
            # none behind it and leaves none in flight (the same accounting
            # tools/serve_bench.py --mode decode uses)
            joins0 = srv.metrics.prefills + (srv.prefix.hits
                                             if srv.prefix else 0)
            engine.dispatch_counter.reset()
            n = srv.step()
            joins1 = srv.metrics.prefills + (srv.prefix.hits
                                             if srv.prefix else 0)
            if n and joins1 == joins0 and srv._flight is not None:
                pure_disp += engine.dispatch_counter.count
                pure_steps += 1
            elif n == 0:
                time.sleep(0.001)
        assert pure_steps > 0
        for s in streams:
            assert len(s.result(10)) == 8
        dps = pure_disp / pure_steps
        recompiles = engine.decode_compile_counter.count
    finally:
        srv.stop()
    assert dps == row["dispatches_per_step"], \
        "decode now takes %.2f dispatches per token step (baseline %.2f)" \
        % (dps, row["dispatches_per_step"])
    assert recompiles == row["steady_state_recompiles"], \
        "%d steady-state decode recompiles (baseline %d)" \
        % (recompiles, row["steady_state_recompiles"])


# --------------------------------------------------------------- quant
def test_quant_decode_counters_match_artifact():
    """Quantized-decode gate: the int8 serving path must keep the same
    one-fused-dispatch/zero-retrace counters as the committed artifact,
    and the int8 paged-KV byte ratio is deterministic per cache geometry
    — a cache or decoder change that splits the quantized step or grows
    the pages fails here even with parity intact."""
    from mxnet_tpu.models.gpt import gpt_nano

    art = _artifact("quant_bench_quick.json")
    row = _row(art, "gpt_nano quantized decode (int8)")
    rng = np.random.default_rng(0)
    m = gpt_nano()
    m.initialize()
    m.hybridize()
    prompts = [rng.integers(0, 256, size=(int(l),)).astype(np.int32)
               for l in rng.integers(3, 12, size=8)]
    srv = mx.serve.GenerativeServer(m, slots=row["slots"], max_wait_ms=1.0,
                                    max_queue=64, timeout_ms=120000.0,
                                    quantize=row["quantize"])
    srv.warmup(prompt_buckets=(4, 8, 16), max_tokens=32)
    try:
        streams = [srv.submit(p, max_new_tokens=8) for p in prompts]
        srv._batcher.start()
        time.sleep(0.05)
        engine.decode_compile_counter.reset()
        pure_disp = pure_steps = 0
        t0 = time.time()
        while not all(s.done() for s in streams) and time.time() - t0 < 120:
            joins0 = srv.metrics.prefills + (srv.prefix.hits
                                             if srv.prefix else 0)
            engine.dispatch_counter.reset()
            n = srv.step()
            joins1 = srv.metrics.prefills + (srv.prefix.hits
                                             if srv.prefix else 0)
            if n and joins1 == joins0 and srv._flight is not None:
                pure_disp += engine.dispatch_counter.count
                pure_steps += 1
            elif n == 0:
                time.sleep(0.001)
        assert pure_steps > 0
        for s in streams:
            assert len(s.result(10)) == 8
        dps = pure_disp / pure_steps
        recompiles = engine.decode_compile_counter.count
        ratio = round(srv.cache.nbytes()
                      / srv.cache.nbytes_unquantized(itemsize=2), 4)
    finally:
        srv.stop()
    assert dps == row["dispatches_per_step"], \
        "quantized decode now takes %.2f dispatches per token step " \
        "(baseline %.2f)" % (dps, row["dispatches_per_step"])
    assert recompiles == row["steady_state_recompiles"], \
        "%d steady-state quantized-decode recompiles (baseline %d)" \
        % (recompiles, row["steady_state_recompiles"])
    assert ratio == row["kv_bytes_vs_bf16"], \
        "int8 KV pages now %.4fx bf16 bytes (baseline %.4fx)" \
        % (ratio, row["kv_bytes_vs_bf16"])


# ---------------------------------------------------------------- dist
def test_dist_exchange_counters_match_artifact():
    """The overlapped-exchange gate: bucket dispatches per step and
    steady-state bucket-program builds are deterministic per (model,
    bucket cap) — a bucketer change that splits buckets differently or
    retraces in steady state fails here even with parity intact."""
    art = _artifact("dist_bench_quick.json")
    row = _row(art, "mlp_6x256_w8")
    bench = _tool("dist_bench")
    for mode, col in (("overlapped", "overlapped_buckets_per_step"),
                      ("serialized", "serialized_buckets_per_step")):
        _losses, _ms, counters = bench.run_mode(mode, steps=4,
                                                bucket_mb=row["bucket_mb"])
        assert counters["buckets_per_step"] == row[col], \
            "%s: %.1f bucket dispatches/step (baseline %.1f)" \
            % (mode, counters["buckets_per_step"], row[col])
        assert counters["steady_state_bucket_builds"] == \
            row["steady_state_bucket_builds"], \
            "%s: %d steady-state bucket builds (baseline %d)" \
            % (mode, counters["steady_state_bucket_builds"],
               row["steady_state_bucket_builds"])


# ---------------------------------------------------------- specdecode
def test_specdecode_artifact_pins():
    """Speculative-decode gate (ISSUE 17): the committed artifact must
    keep its acceptance numbers — tokens/s >= 1.5x plain at accept
    >= 0.6 on the pinned latency-regime scenario, chunked-prefill
    victim ITL p95 >= 2x better than whole-prompt prefill, and the
    structural columns the speedup rests on (ONE verify dispatch per
    round, zero steady-state recompiles). Wall-clock is measured by
    tools/serve_bench.py --mode specdecode with the paired-step method;
    re-timing it here would flake on a loaded CI host. The LIVE replay
    of the 1-verify-per-round / zero-retrace / exact-parity contract is
    tests/test_speculative.py::
    test_spec_steady_state_dispatch_budget_watchdog_armed."""
    art = _artifact("serve_specdecode_bench_quick.json")
    row = _row(art, "nano GPT latency-regime specdecode (ngram draft, k=4)")
    assert row["speedup"] >= 1.5, \
        "committed specdecode speedup %.2f below the 1.5x acceptance bar" \
        % row["speedup"]
    assert min(row["speedup_all_reps"]) >= 1.5, \
        "a paired rep fell below the 1.5x bar: %r" % row["speedup_all_reps"]
    assert row["accept_rate"] >= 0.6
    assert row["chunked_itl_p95_improvement"] >= 2.0, \
        "committed chunked-prefill ITL improvement %.2fx below the 2x bar" \
        % row["chunked_itl_p95_improvement"]
    assert row["dispatches_per_round"] == 1
    assert row["steady_state_recompiles"] == 0
    assert row["verify_dispatches"] == row["spec_rounds"]
    assert 1.0 <= row["tokens_per_verify_dispatch"] <= row["spec_k"]


# --------------------------------------------------------------- fleet
def test_fleet_artifact_pins():
    """Fleet gate (ISSUE 20): the committed artifact must keep the
    acceptance counters — kill -9 mid-wave costs zero failed requests
    beyond the victim's in-flight (and those are retried), autoscale-out
    actually landed a second replica AND improved p99 by eliminating
    sheds, hot-swap mid-traffic dropped zero requests with zero torn
    (neither-old-nor-new) outputs, a snapshot-warm spawn reached its
    first request with zero compiles under an armed watchdog, and a
    retired replica's prefix entries migrated and HIT on the session's
    next turn. Wall-clock columns are context, not gated. The live
    replays are tests/test_fleet.py (kill -9, swap rejections) — this
    file stays cheap, subprocess spawns belong there."""
    art = _artifact("fleet_bench_quick.json")

    row = _row(art, "kill9_drill")
    assert row["failed"] == 0, \
        "committed kill -9 drill lost %d requests" % row["failed"]
    assert row["ok"] == row["requests"]
    assert row["workers_lost"] == 1 and row["workers_left"] == 1

    row = _row(art, "scale_out_p99")
    assert row["autoscaled"] is True and row["workers_after"] == 2, \
        "committed scale-out row never actually autoscaled"
    assert row["failed"] == 0
    assert row["shed_retries_before"] > 0, \
        "single replica never shed — the scenario measured nothing"
    assert row["shed_retries_after"] == 0, \
        "the scaled pair still sheds (%d)" % row["shed_retries_after"]
    assert row["p99_after_ms"] < row["p99_before_ms"], \
        "autoscale-out did not improve p99 (%.1f -> %.1f ms)" \
        % (row["p99_before_ms"], row["p99_after_ms"])

    row = _row(art, "hot_swap_mid_traffic")
    assert row["dropped"] == 0 and row["mixed_outputs"] == 0, \
        "hot swap dropped %d / tore %d responses" \
        % (row["dropped"], row["mixed_outputs"])
    assert row["old_model_responses"] > 0 and \
        row["new_model_responses"] > 0
    assert row["replicas_swapped"] == 2 and row["swap_epochs"] == [1, 1]

    row = _row(art, "warm_spawn")
    assert row["warm_compiles"] == 0, \
        "snapshot-warm spawn compiled %d programs" % row["warm_compiles"]
    assert row["watchdog_armed"] is True and row["watchdog_retraces"] == 0
    assert row["first_request_ok"] is True

    row = _row(art, "session_affinity")
    assert row["prefix_hits_on_pinned"] >= 1
    assert row["migrated_entries"] == 1
    assert row["hit_on_migrated_prefix"] == 1, \
        "the migrated prefix entry was not hit after retirement"
    assert row["tokens_stable_across_migration"] is True


# ------------------------------------------------- artifact sanity gate
@pytest.mark.parametrize("name,counter_cols", [
    ("opt_step_bench_quick.json", ["fused_dispatches_per_step"]),
    ("imperative_bench_quick.json", ["lazy_dispatches_per_iter"]),
    ("autograd_bench_quick.json", ["compiled_dispatches_per_iter",
                                   "steady_state_tape_recompiles"]),
    ("serve_bench_quick.json", ["served_dispatches_per_iter",
                                "steady_state_recompiles"]),
    ("serve_decode_bench_quick.json", ["dispatches_per_step",
                                       "steady_state_recompiles"]),
    ("ir_bench_quick.json", ["lazy_dispatches_per_iter",
                             "steady_state_recompiles", "nodes_captured",
                             "nodes_canonical", "nodes_final",
                             "cse_rewrites", "dce_nodes_removed"]),
    ("dist_bench_quick.json", ["overlapped_buckets_per_step",
                               "serialized_buckets_per_step",
                               "overlapped_dispatches_per_step",
                               "steady_state_bucket_builds",
                               "loss_trajectory_max_diff"]),
    # row-specific quant columns (dispatches_per_step, top1_agreement on
    # the nano row; speedup_vs_bf16 >= 1 on the wide row) are pinned in
    # tests/test_quant.py::test_quant_bench_artifact_pins
    ("quant_bench_quick.json", ["steady_state_recompiles",
                                "kv_bytes_vs_bf16",
                                "kv_cache_bytes"]),
    # flops/bytes/peak-HBM gate columns: replayed exactly by
    # tests/test_costs.py::test_cost_gate_replay_matches_committed_artifact
    ("cost_report_quick.json", ["tier", "programs", "flops",
                                "bytes_accessed", "peak_hbm_bytes"]),
    # per-scenario lint gate rows: replayed + asserted clean by
    # tests/test_hlolint.py::test_pinned_scenarios_lint_ci_clean
    ("hlolint_quick.json", ["tier", "programs", "findings", "suppressed"]),
    # speedup/accept/ITL-improvement bars + the 1-dispatch-per-round
    # contract are pinned above in
    # test_specdecode_counters_and_artifact_pins
    # fleet acceptance counters (failed, autoscaled, mixed_outputs,
    # warm_compiles, migrated hits) are pinned above in
    # test_fleet_artifact_pins; rows carry disjoint columns so the
    # shared sanity gate only checks presence per-case there
    ("serve_specdecode_bench_quick.json", ["spec_rounds",
                                           "verify_dispatches",
                                           "dispatches_per_round",
                                           "tokens_per_verify_dispatch",
                                           "accept_rate",
                                           "steady_state_recompiles",
                                           "chunked_itl_p95_improvement"]),
    # speedup bar + ledger direction + zero-retrace are pinned (and the
    # deterministic columns replayed) by tests/test_tune.py::
    # test_tune_bench_artifact_pins_and_replay
    ("tune_bench_quick.json", ["candidates", "candidates_pruned",
                               "candidates_timed", "speedup",
                               "ledger_bytes_improved",
                               "ledger_peak_hbm_improved",
                               "steady_state_recompiles"]),
])
def test_committed_artifacts_carry_counter_columns(name, counter_cols):
    """The gate only works while the artifacts keep their counter columns —
    a bench refactor that drops one would silently disable the baseline."""
    art = _artifact(name)
    for r in art["rows"]:
        for col in counter_cols:
            assert col in r, "%s row %r lost counter column %r" \
                % (name, r.get("case"), col)
