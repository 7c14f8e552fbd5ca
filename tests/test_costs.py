"""Cost attribution (ISSUE 13): every program built through the
``base._jit_backed`` funnel records a CostProfile — deterministic XLA
flops / bytes-accessed / peak-HBM columns keyed by the comp-cache's
content hash — surfaced through ``observability.snapshot()["costs"]``
and Prometheus, with ``jax.named_scope`` provenance stamped from IR node
ops and gluon block names into the optimized-HLO metadata. The committed
``tools/cost_report_quick.json`` pins the pinned-bench columns: the last
tests here replay it in a fresh process and assert EXACT equality — the
deterministic CPU perf-regression gate.
"""
import copy
import importlib.util
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.observability import costs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, "tools")


def _tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(TOOLS, "%s.py" % name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _new_profiles(before):
    costs.materialize()
    return {k: p for k, p in costs.profiles().items() if k not in before}


def _mark():
    costs.materialize()
    return set(costs.profiles())


def _subprocess(argv, **env_extra):
    """Fresh-interpreter run. ``close_fds=False`` keeps the posix_spawn
    fast path (forking this heavily-threaded jax parent has crashed
    children with malloc-arena corruption under full-suite load), and a
    signal-death (rc < 0) gets ONE retry — a wrong RESULT never does.

    ``JAX_COMPILATION_CACHE_DIR`` is stripped: where the session runs
    with one set, a child deserializing executables written under a
    different XLA config dies with SIGSEGV/SIGABRT before main(). Cost
    capture happens at trace time, so the replay gate loses nothing by
    running cache-less."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    for _ in range(2):
        r = subprocess.run([sys.executable] + argv, cwd=REPO, env=env,
                           capture_output=True, text=True, timeout=300,
                           close_fds=False)
        if r.returncode >= 0:
            return r
    return r


# ------------------------------------------------------ funnel coverage
def test_every_funnel_tier_records_a_profile():
    """bulk (lazy imperative window), tape (compiled autograd), hybrid
    (gluon forward), jit (fused optimizer step): each capture path lands
    a non-zero CostProfile under its own tier, with the comp-cache-shaped
    16-hex content key."""
    before = _mark()
    # bulk: a lazy chain flushed by asnumpy
    a = nd.array(np.ones((8, 8), np.float32))
    ((a * 2.0 + 1.0) @ a).asnumpy()
    # tape: the compiled autograd program. A chain length no other test or
    # pinned bench uses: profiles are content-addressed and the tape cache is
    # per process, so a chain another file of this xdist worker already
    # compiled (15 and 50 are the bench's) would record nothing new here
    _tool("autograd_bench").run_case(13, "compiled", iters=2, quick=True)
    # hybrid: a gluon forward
    net = mx.gluon.nn.Dense(5)
    net.initialize()
    net.hybridize()
    net(nd.array(np.ones((2, 3), np.float32))).asnumpy()
    # jit: the fused optimizer step
    bench = _tool("opt_step_bench")
    tr, ps = bench.build_trainer(20, quick=True, optimizer="sgd", fused=True)
    bench.time_loop(tr, ps, iters=2)

    new = _new_profiles(before)
    tiers = {p["tier"] for p in new.values()}
    assert {"bulk", "tape", "hybrid", "jit"} <= tiers, \
        "missing funnel tiers: got %s" % sorted(tiers)
    for k, p in new.items():
        assert k == "%s:%s" % (p["tier"], p["key"])
        assert len(p["key"]) == 16 and int(p["key"], 16) >= 0
        assert p["flops"] >= 0 and p["bytes_accessed"] > 0
        assert p["peak_hbm_bytes"] > 0
    fused = [p for p in new.values()
             if p["tier"] == "jit" and p["hint"] == "fused_step"]
    assert fused and fused[0]["flops"] > 0


def test_serve_and_decode_tiers_record_profiles():
    """One serve bucket and one gpt_nano decode step report non-zero
    profiles (the AotFn path records eagerly at compile time)."""
    from mxnet_tpu.models.gpt import gpt_nano

    net = mx.gluon.nn.Dense(4)
    net.initialize()
    net.hybridize()
    net(nd.array(np.ones((2, 3), np.float32)))  # materialize shapes
    before = _mark()
    srv = mx.serve.ModelServer(net, [((3,), "float32")], buckets=(4,),
                               max_wait_ms=0.5, timeout_ms=30000.0,
                               name="costs:mlp")
    with srv:
        srv.predict(np.ones((2, 3), np.float32))
    m = gpt_nano()
    m.initialize()
    m.hybridize()
    gsrv = mx.serve.GenerativeServer(m, slots=2, max_wait_ms=1.0,
                                     max_queue=8, timeout_ms=60000.0,
                                     name="costs:gpt")
    gsrv.warmup(prompt_buckets=(4,), max_tokens=8)
    try:
        new = _new_profiles(before)
        serve_rows = [p for p in new.values() if p["tier"] == "serve"]
        decode_rows = [p for p in new.values() if p["tier"] == "decode"]
        assert serve_rows and any(p["flops"] > 0 for p in serve_rows)
        assert decode_rows and any(
            p["flops"] > 0 and p["hint"].startswith("step@")
            for p in decode_rows)
        # the ledger sees both live servers with exact cache bytes
        led = costs.hbm_ledger()["servers"]
        assert led["costs:mlp"]["params_bytes"] > 0
        assert led["costs:gpt"]["kv_cache_bytes"] == gsrv.cache.nbytes()
        assert led["costs:gpt"]["total_bytes"] >= \
            led["costs:gpt"]["params_bytes"] + led["costs:gpt"]["kv_cache_bytes"]
    finally:
        gsrv.stop()


def test_program_keys_stable_within_process():
    """Rebuilding the SAME program dedups onto one profile (builds += 1)
    instead of minting a new key — the key is content-addressed, not
    object-addressed."""
    bench = _tool("opt_step_bench")
    tr, ps = bench.build_trainer(20, quick=True, optimizer="sgd", fused=True)
    bench.time_loop(tr, ps, iters=2)
    costs.materialize()
    first = {k: p["builds"] for k, p in costs.profiles().items()
             if p["tier"] == "jit" and p["hint"] == "fused_step"}
    tr2, ps2 = bench.build_trainer(20, quick=True, optimizer="sgd",
                                   fused=True)
    bench.time_loop(tr2, ps2, iters=2)
    costs.materialize()
    after = {k: p["builds"] for k, p in costs.profiles().items()
             if p["tier"] == "jit" and p["hint"] == "fused_step"}
    assert set(after) == set(first), \
        "rebuild minted new keys: %s" % sorted(set(after) - set(first))
    assert any(after[k] > first[k] for k in first)


def test_program_keys_stable_across_processes():
    """The same hybrid forward lowers to the same content key in two
    fresh interpreters — profiles from different workers/days join."""
    code = (
        "import numpy as np\n"
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu import nd\n"
        "from mxnet_tpu.observability import costs\n"
        "net = mx.gluon.nn.Dense(5)\n"
        "net.initialize()\n"
        "net.hybridize()\n"
        "net(nd.array(np.ones((2, 3), np.float32))).asnumpy()\n"
        "costs.materialize()\n"
        "ks = sorted(k for k, p in costs.profiles().items()\n"
        "            if p['tier'] == 'hybrid')\n"
        "print('KEYS=' + ','.join(ks))\n")
    outs = []
    for _ in range(2):
        r = _subprocess(["-c", code])
        assert r.returncode == 0, r.stderr
        outs.append([l for l in r.stdout.splitlines()
                     if l.startswith("KEYS=")][0])
    assert outs[0] == outs[1] and outs[0] != "KEYS="


# ----------------------------------------------------------- provenance
def test_named_scope_provenance_registry_op():
    """_trace.F stamps the registry op name: the lowered module's debug
    form carries FullyConnected in its location metadata, so optimized
    HLO ``op_name=`` keeps the op name end to end."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import _trace

    def fwd(a):
        return _trace.F.FullyConnected(a, jnp.ones((4, 3)), jnp.zeros((4,)))

    lowered = jax.jit(fwd).lower(np.ones((2, 3), np.float32))
    asm = lowered.compiler_ir().operation.get_asm(enable_debug_info=True)
    assert "FullyConnected" in asm
    # the DEFAULT lowered text (what the comp-cache digests) must NOT
    # change with scope names — content keys stay stable
    assert "named_scope" not in lowered.as_text()


def test_named_scope_provenance_ir_node_op():
    """build_runner wraps each node call in jax.named_scope(node.op):
    graph provenance survives into the debug-form lowering."""
    import jax

    from mxnet_tpu.ir.graph import Graph, Node, build_runner

    node = Node("MyScopedOp", lambda x: x * 2.0 + 1.0, {}, (), specs=(-1,))
    g = Graph(nodes=[node], leaf_sigs=(0,), outputs=(0,))
    run = build_runner(g)
    lowered = jax.jit(lambda lv: run(lv)).lower(
        (np.ones((3,), np.float32),))
    asm = lowered.compiler_ir().operation.get_asm(enable_debug_info=True)
    assert "MyScopedOp" in asm


def test_profile_hlo_map_prefers_op_name_metadata():
    """The profile joiner names sinks from metadata op_name= instead of
    opcode-only categorization, with the no-metadata fallback intact."""
    phm = _tool("profile_hlo_map")
    hlo = (
        "ENTRY %main (p0: f32[8,8]) -> f32[8,8] {\n"
        "  %p0 = f32[8,8]{1,0} parameter(0)\n"
        '  %d = f32[8,8]{1,0} fusion(%p0), kind=kOutput, '
        'calls=%fused_dot, metadata={op_name='
        '"jit(step)/jit(main)/dense0/FullyConnected/dot_general" '
        'source_file="x.py"}\n'
        "  %c = f32[8,8]{1,0} copy(%d)\n"
        "}\n"
        "%fused_dot (a: f32[8,8]) -> f32[8,8] {\n"
        "  %a = f32[8,8]{1,0} parameter(0)\n"
        "  ROOT %dd = f32[8,8]{1,0} dot(%a, %a)\n"
        "}\n")
    instrs, comp_ops = phm.parse_hlo(hlo)
    assert instrs["d"]["op_name"] == "dense0/FullyConnected/dot_general"
    assert "op_name" not in instrs["c"]          # fallback row
    out = phm.join({"d": 2.0, "c": 1.0}, instrs, comp_ops, top=5)
    assert out["named_ops"] == 1
    assert out["scope_ms"] == {"dense0/FullyConnected": 2.0}
    assert out["category_ms"]["matmul/conv"] == 2.0
    assert out["category_ms"]["copy/layout"] == 1.0
    # weak fusion-root metadata must not demote a matmul fusion
    rec = {"opcode": "fusion", "calls": "%f",
           "op_name": "blk/broadcast_in_dim"}
    assert phm.categorize(rec, {"dot": 1}) == "matmul/conv"


# ----------------------------------------------------------- HBM ledger
def test_hbm_ledger_int8_kv_half_of_bf16():
    """The quantized decode server's ledger reports the EXACT int8 page
    bytes (scales included): ~0.50x what the same geometry costs in
    bf16 — the memory side of the quantized-serving acceptance."""
    from mxnet_tpu.models.gpt import gpt_nano

    m = gpt_nano()
    m.initialize()
    m.hybridize()
    srv = mx.serve.GenerativeServer(m, slots=2, max_wait_ms=1.0,
                                    max_queue=8, timeout_ms=60000.0,
                                    quantize="int8", name="costs:gpt8")
    srv.warmup(prompt_buckets=(4,), max_tokens=8)
    try:
        row = costs.hbm_ledger()["servers"]["costs:gpt8"]
        assert row["kv_cache_bytes"] == srv.cache.nbytes() > 0
        ratio = row["kv_cache_bytes"] / srv.cache.nbytes_unquantized(
            itemsize=2)
        # int8 pages + fp32 scale planes: ~0.50x bf16, and never past the
        # 0.55x quantized-serving acceptance bound (tests/test_quant.py)
        assert round(ratio, 1) == 0.5 and ratio <= 0.55, ratio
    finally:
        srv.stop()


# ------------------------------------------------- snapshot / prometheus
def test_snapshot_and_prometheus_round_trip():
    from mxnet_tpu import observability

    net = mx.gluon.nn.Dense(3)
    net.initialize()
    net.hybridize()
    net(nd.array(np.ones((2, 2), np.float32))).asnumpy()
    snap = observability.snapshot()
    sec = snap["costs"]
    assert sec["enabled"] is True
    assert sec["pending"] == 0          # snapshot materializes first
    assert sec["profiles"] and sec["totals"]
    for tier, tot in sec["totals"].items():
        assert tot["programs"] >= 1 and tot["bytes_accessed"] > 0
    assert json.loads(json.dumps(snap))  # JSON-clean
    text = observability.prometheus()
    assert 'mxtpu_costs_program_flops{program="' in text
    assert 'mxtpu_costs_program_peak_hbm_bytes{program="' in text
    assert "mxtpu_costs_enabled 1" in text


def test_histogram_empty_percentiles_and_prom_sum_count():
    """Satellite: empty-ring percentiles are None (absent samples), a
    populated histogram exports Prometheus ``_sum``/``_count`` counter
    series, and snapshot under concurrent observe never tears count/sum."""
    from mxnet_tpu import observability
    from mxnet_tpu.observability import registry

    h = registry.histogram("costs_test_lat_ms")
    empty = h.snapshot()
    assert empty["count"] == 0
    assert empty["p50"] is None and empty["p95"] is None \
        and empty["p99"] is None
    for v in (1.0, 2.0, 3.0):
        h.observe(v)
    text = observability.prometheus()
    assert "mxtpu_metrics_histograms_costs_test_lat_ms_sum 6" in text
    assert "mxtpu_metrics_histograms_costs_test_lat_ms_count 3" in text
    assert ("# TYPE mxtpu_metrics_histograms_costs_test_lat_ms_count "
            "counter") in text

    stop = threading.Event()

    def pump():
        while not stop.is_set():
            h.observe(1.0)

    t = threading.Thread(target=pump, daemon=True)
    t.start()
    try:
        deadline = time.time() + 0.5
        while time.time() < deadline:
            s = h.snapshot()
            # every observation adds exactly 1.0: a torn read shows a
            # count/sum mismatch beyond the 3 seed values
            assert abs((s["sum"] - 6.0) - (s["count"] - 3)) < 1e-6, s
    finally:
        stop.set()
        t.join(1.0)


# ---------------------------------------------------------- kill switch
def test_kill_switch_disables_collection():
    code = (
        "import numpy as np\n"
        "from mxnet_tpu import base\n"
        "from mxnet_tpu.observability import costs\n"
        "assert costs.enabled() is False\n"
        "f = base._jit_backed(lambda a: a + 1)\n"
        "assert type(f).__name__ != '_TrackedJit', type(f)\n"
        "f(np.ones((2,), np.float32))\n"
        "costs.materialize()\n"
        "assert costs.profiles() == {}, costs.profiles()\n"
        "print('KILLED_OK')\n")
    r = _subprocess(["-c", code], MXNET_COST_ATTRIBUTION="0")
    assert r.returncode == 0, r.stderr
    assert "KILLED_OK" in r.stdout


# ------------------------------------------------------------- CI gate
def test_cost_gate_replay_matches_committed_artifact(tmp_path):
    """THE gate: re-run the pinned bench programs in a fresh process and
    assert the flops / bytes-accessed / peak-HBM columns equal the
    committed artifact exactly. A rewrite pass, fusion change, or capture
    regression that alters any pinned program's cost fails here on CPU,
    no TPU required. Regenerate intentionally with
    ``python tools/cost_report.py --quick --json tools/cost_report_quick
    .json``."""
    cr = _tool("cost_report")
    with open(os.path.join(TOOLS, "cost_report_quick.json")) as fh:
        baseline = json.load(fh)
    out = str(tmp_path / "replay.json")
    r = _subprocess([os.path.join(TOOLS, "cost_report.py"), "--quick",
                     "--json", out])
    assert r.returncode == 0, r.stderr
    with open(out) as fh:
        replay = json.load(fh)
    problems = cr.compare(baseline, replay)
    assert problems == [], "cost regression vs committed artifact:\n  " \
        + "\n  ".join(problems)


def test_seeded_inflation_fails_exactly_that_gate():
    """A 2x flops inflation in any ONE capture path trips its own
    scenario's gate and no other — the failure names the path."""
    cr = _tool("cost_report")
    with open(os.path.join(TOOLS, "cost_report_quick.json")) as fh:
        baseline = json.load(fh)
    for case in [r["case"] for r in baseline["rows"]]:
        inflated = copy.deepcopy(baseline)
        for row in inflated["rows"]:
            if row["case"] == case:
                row["flops"] = row["flops"] * 2
        problems = cr.compare(baseline, inflated)
        assert problems, case
        assert all(p.startswith(case + ":") for p in problems), problems
        assert any("flops" in p for p in problems), problems
