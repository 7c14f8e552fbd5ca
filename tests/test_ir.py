"""mxnet_tpu.ir — the unified typed graph IR under all three captures.

Proves the ISSUE-9 acceptance criteria:

* identical math captured via the bulk window, the autograd tape, and a
  Symbol graph lowers to ONE shared compiled program (single canonical
  cache entry; counter-asserted in-process AND from a fresh process);
* round-trip parity ≤ 1e-6 (incl. bf16) for every capture's IR lowering
  vs its pre-IR path;
* each rewrite pass does its one job (CSE merges duplicate
  subexpressions, folding pre-evaluates constant islands, DCE drops
  unused branches, cast-sinking preserves parity, the donation annotator
  marks safe leaves) — unit-tested on hand-built graphs;
* zero steady-state retrace across all three captures with the
  observability watchdog ARMED;
* pass-pipeline determinism: the same graph produces a byte-identical
  canonical key.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, engine, nd
from mxnet_tpu import base
from mxnet_tpu import ir
from mxnet_tpu import symbol as S
from mxnet_tpu.base import OP_REGISTRY
from mxnet_tpu.ir import graph as irgraph, lower as irlower, passes as irpasses
from mxnet_tpu.observability import watchdog

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def _reset_ir_state():
    # a lazy window an earlier test of this process left pending (ops never
    # read back) would flush, and compile, at the next sync point: inside
    # the test that counts compiles. Flush it before the counters reset.
    engine.flush()
    base._BULK_CACHE.clear()
    base._TAPE_CACHE.clear()
    base._IR_CACHE.clear()
    irlower.reset_stats()
    for c in (engine.bulk_compile_counter, engine.tape_compile_counter,
              engine.symbol_compile_counter):
        c.reset()


def _mlp_arrays(rng, dtype=np.float32):
    X = rng.normal(size=(4, 8)).astype(dtype)
    W1 = rng.normal(size=(8, 16)).astype(dtype)
    B1 = rng.normal(size=(16,)).astype(dtype)
    W2 = rng.normal(size=(16, 3)).astype(dtype)
    B2 = rng.normal(size=(3,)).astype(dtype)
    return X, W1, B1, W2, B2


def _mlp_nd(x, w1, b1, w2, b2):
    a = x @ w1
    b = a + b1
    c = b.relu()
    d = c @ w2
    e = d + b2
    return [a, b, c, d, e]


def _mlp_sym():
    vs = {n: S.var(n) for n in ("x", "w1", "b1", "w2", "b2")}
    sa = S.Symbol("matmul", [vs["x"], vs["w1"]], {})
    sb = S.Symbol("add", [sa, vs["b1"]], {})
    sc = S.Symbol("relu", [sb], {})
    sd = S.Symbol("matmul", [sc, vs["w2"]], {})
    se = S.Symbol("add", [sd, vs["b2"]], {})
    return S.Group([sa, sb, sc, sd, se])


# ===================================================== cross-capture dedup


def test_cross_capture_single_program(rng):
    """The tentpole: the same MLP built via bulk window, autograd tape,
    and Symbol graph shares ONE compiled program — one canonical cache
    entry, ONE total compile across the three capture counters."""
    _reset_ir_state()
    X, W1, B1, W2, B2 = _mlp_arrays(rng)
    arrs = [nd.array(a) for a in (X, W1, B1, W2, B2)]

    # 1. bulk window (all intermediates kept live → same output set as
    #    the tape capture, whose tape pins every recorded output)
    with engine.bulk(32):
        keep = _mlp_nd(*arrs)
        r_bulk = keep[-1].asnumpy()

    # 2. autograd tape capture: flush happens at the read, with every
    #    recorded output alive on the tape
    with autograd.record():
        keep2 = _mlp_nd(*arrs)
    r_tape = keep2[-1].asnumpy()
    autograd._st().tape = []

    # 3. Symbol graph of the same math, same output order
    outs = _mlp_sym().eval(x=X, w1=W1, b1=B1, w2=W2, b2=B2)
    r_sym = outs[-1].asnumpy()

    np.testing.assert_allclose(r_bulk, r_tape, atol=1e-6)
    np.testing.assert_allclose(r_bulk, r_sym, atol=1e-6)
    total = (engine.bulk_compile_counter.count
             + engine.tape_compile_counter.count
             + engine.symbol_compile_counter.count)
    assert total == 1, "3 captures compiled %d programs (want 1)" % total
    assert irlower.program_count() == 1
    assert len(base._IR_CACHE) == 1  # single canonical entry, not 3


def test_cross_capture_single_program_fresh_process():
    """Acceptance: counter-asserted from a FRESH process (no warm state
    from other tests)."""
    script = r"""
import numpy as np
from mxnet_tpu import autograd, engine, nd, symbol as S
from mxnet_tpu.ir import lower as irlower
import mxnet_tpu.base as base

rng = np.random.default_rng(0)
X = rng.normal(size=(4, 8)).astype(np.float32)
W = rng.normal(size=(8, 3)).astype(np.float32)
B = rng.normal(size=(3,)).astype(np.float32)
x, w, bb = nd.array(X), nd.array(W), nd.array(B)

with engine.bulk(16):
    a = x @ w; b = a + bb; c = b.relu()
    keep = [a, b, c]
    r1 = c.asnumpy()
with autograd.record():
    a2 = x @ w; b2 = a2 + bb; c2 = b2.relu()
r2 = c2.asnumpy()
autograd._st().tape = []
vx, vw, vb = S.var('x'), S.var('w'), S.var('b')
sa = S.Symbol('matmul', [vx, vw], {})
sb = S.Symbol('add', [sa, vb], {})
sc = S.Symbol('relu', [sb], {})
r3 = S.Group([sa, sb, sc]).eval(x=X, w=W, b=B)[-1].asnumpy()
assert np.allclose(r1, r2, atol=1e-6) and np.allclose(r1, r3, atol=1e-6)
total = (engine.bulk_compile_counter.count
         + engine.tape_compile_counter.count
         + engine.symbol_compile_counter.count)
assert total == 1, "fresh process: %d compiles across captures" % total
assert irlower.program_count() == 1
assert len(base._IR_CACHE) == 1
print("OK")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", script], cwd=REPO,
                          capture_output=True, text=True, env=env,
                          timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "OK" in proc.stdout


# ========================================================= capture parity


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_bulk_lowering_parity_vs_eager(rng, dtype):
    X = rng.normal(size=(16, 16)).astype(np.float32)
    A = np.full((16, 16), 0.7, np.float32)
    x, a = nd.array(X, dtype=dtype), nd.array(A, dtype=dtype)
    with engine.bulk(32):
        lazy = (((x * a).tanh() + x) * a - x).sum().asnumpy()
    with engine.bulk(0):
        eager = (((x * a).tanh() + x) * a - x).sum().asnumpy()
    np.testing.assert_allclose(np.float32(lazy), np.float32(eager),
                               atol=1e-6)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_tape_lowering_parity_vs_eager_walk(rng, dtype):
    X = rng.normal(size=(8, 8)).astype(np.float32)
    A = np.full((8, 8), 0.9, np.float32)

    def step(dup):
        x = nd.array(X, dtype=dtype)
        a = nd.array(A, dtype=dtype)
        x.attach_grad()
        with autograd.record():
            # `dup` seeds a CSE-mergeable duplicate — exercised in fp32
            # only: merging reassociates the cotangent sum, which is
            # exact in fp32 here but one-ulp different in bf16 (an
            # optimizing compiler's prerogative; values, not math, move)
            loss = (((x * a).tanh() + x * a).sum() if dup
                    else ((x * a).tanh() + x).sum())
        loss.backward()
        return np.float32(np.asarray(x.grad._data))

    dup = dtype == "float32"
    g_ir = step(dup)
    prev = autograd.set_tape_compile(False)
    try:
        g_eager = step(dup)
    finally:
        autograd.set_tape_compile(prev)
    np.testing.assert_allclose(g_ir, g_eager, atol=1e-6)


def test_tape_grad_req_add_parity(rng):
    X = rng.normal(size=(6, 6)).astype(np.float32)
    x = nd.array(X)
    x.attach_grad(grad_req="add")
    for _ in range(2):
        with autograd.record():
            loss = (x * x).sum()
        loss.backward()
    # two accumulated backward passes: grad = 2 * (2x)
    np.testing.assert_allclose(np.asarray(x.grad._data), 4 * X, atol=1e-5)


def test_symbol_lowering_parity_vs_legacy_eval(rng):
    X = rng.normal(size=(4, 8)).astype(np.float32)
    W = rng.normal(size=(8, 3)).astype(np.float32)
    y = S.Symbol("relu", [S.Symbol("matmul", [S.var("x"), S.var("w")], {})],
                 {})
    r_ir = y.eval(x=X, w=W)[0].asnumpy()
    # legacy path: the per-symbol jitted _build_fn closure
    fn, names = y._build_fn()
    import jax

    r_legacy = np.asarray(jax.jit(fn)(*[{"x": X, "w": W}[n] for n in names]))
    np.testing.assert_allclose(r_ir, r_legacy, atol=1e-6)


def test_intermediate_grad_targets_survive_cse(rng):
    """Two IDENTICAL intermediate subexpressions, both grad targets: CSE
    must not merge the probe-injection sites (pinned nodes) — each must
    receive its own cotangent."""
    X = rng.normal(size=(4, 4)).astype(np.float32)
    A = np.full((4, 4), 0.5, np.float32)
    x, a = nd.array(X), nd.array(A)
    with autograd.record():
        u = x * a
        v = x * a          # structurally identical to u
        u.attach_grad()
        v.attach_grad()
        loss = (u + 2 * v).sum()
    loss.backward()
    np.testing.assert_allclose(np.asarray(u.grad._data),
                               np.ones((4, 4), np.float32), atol=1e-6)
    np.testing.assert_allclose(np.asarray(v.grad._data),
                               2 * np.ones((4, 4), np.float32), atol=1e-6)


def test_executor_ir_forward_backward_parity(rng):
    X = rng.normal(size=(4, 8)).astype(np.float32)
    W = rng.normal(size=(8, 3)).astype(np.float32)
    y = S.Symbol("matmul", [S.var("x"), S.var("w")], {})
    ex = y.bind(args={"x": nd.array(X), "w": nd.array(W)},
                args_grad={"x": nd.zeros((4, 8)), "w": nd.zeros((8, 3))})
    out = ex.forward(is_train=True)[0].asnumpy()
    np.testing.assert_allclose(out, X @ W, atol=1e-5)
    ex.backward()
    np.testing.assert_allclose(np.asarray(ex.grad_dict["w"].asnumpy()),
                               X.T @ np.ones((4, 3), np.float32), atol=1e-5)


# ====================================================== per-pass unit tests


def _node_fns():
    return (OP_REGISTRY["multiply"].fn, OP_REGISTRY["tanh"].fn,
            OP_REGISTRY["add"].fn)


def _sig(shape=(4,), dt=np.float32):
    return irgraph._sig_id((np.dtype(dt), tuple(shape)))


def test_cse_merges_duplicate_subexpressions():
    mul, tanh, add = _node_fns()
    b = ir.GraphBuilder()
    lx = b.leaf("x", sig_id=_sig())
    la = b.leaf("a", sig_id=_sig())
    n1 = b.add("multiply", mul, {}, (), (lx, la))
    n2 = b.add("tanh", tanh, {}, (), (n1,))
    n3 = b.add("multiply", mul, {}, (), (lx, la))   # duplicate of n1
    n4 = b.add("tanh", tanh, {}, (), (n3,))         # duplicate of n2
    n5 = b.add("add", add, {}, (), (n2, n4))
    g = b.build((n5,))
    opt = ir.PassManager(("cse", "dce")).run(g)
    assert opt.n_nodes == 3  # mul, tanh, add — duplicates merged
    x = np.arange(4, dtype=np.float32)
    a = np.full(4, 0.5, np.float32)
    out = ir.build_runner(opt)([x, a])[0]
    np.testing.assert_allclose(np.asarray(out), 2 * np.tanh(x * a),
                               atol=1e-6)


def test_fold_preevaluates_constant_islands():
    add, mul = OP_REGISTRY["add"].fn, OP_REGISTRY["multiply"].fn
    cfn = OP_REGISTRY["_const"].fn
    from mxnet_tpu.base import _freeze

    b = ir.GraphBuilder()
    lx = b.leaf("x", sig_id=_sig())
    c2 = b.add("_const", cfn, {"value": 2.0}, _freeze({"value": 2.0}), ())
    c3 = b.add("_const", cfn, {"value": 3.0}, _freeze({"value": 3.0}), ())
    s = b.add("add", add, {}, (), (c2, c3))        # constant island: 5.0
    y = b.add("multiply", mul, {}, (), (lx, s))
    g = b.build((y,))
    opt = ir.PassManager(("fold", "dce")).run(g)
    assert opt.n_nodes == 2  # baked constant + multiply
    assert any(n.op == "_ir_const" for n in opt.nodes)
    x = np.arange(4, dtype=np.float32)
    out = ir.build_runner(opt)([x])[0]
    np.testing.assert_allclose(np.asarray(out), x * 5.0, atol=1e-6)


def test_dce_drops_unused_branch():
    mul, tanh, _ = _node_fns()
    b = ir.GraphBuilder()
    lx = b.leaf("x", sig_id=_sig())
    la = b.leaf("a", sig_id=_sig())
    live = b.add("tanh", tanh, {}, (), (lx,))
    dead = b.add("multiply", mul, {}, (), (lx, la))   # unused branch
    b.add("tanh", tanh, {}, (), (dead,))              # also dead
    g = b.build((live,))
    opt = ir.PassManager(("dce",)).run(g)
    assert opt.n_nodes == 1
    assert len(opt.leaf_sigs) == 1  # leaf 'a' dropped with its branch
    x = np.arange(4, dtype=np.float32)
    out = ir.build_runner(opt)([x])[0]
    np.testing.assert_allclose(np.asarray(out), np.tanh(x), atol=1e-6)


def test_cast_sink_collapses_bf16_roundtrip(rng):
    """bf16 → f32 → bf16 (the AMP/checkpoint boundary round trip)
    collapses to the source value — parity EXACT, nodes removed."""
    _reset_ir_state()
    X = rng.normal(size=(8, 8)).astype(np.float32)
    x = nd.array(X, dtype="bfloat16")
    with engine.bulk(16):
        y = x.astype("float32").astype("bfloat16").tanh()
        lazy = np.float32(y.asnumpy())
    build = irlower.stats()["builds"]["last_build"]
    assert build["nodes_final"] < build["nodes_captured"], \
        "cast round trip survived the pass pipeline"
    with engine.bulk(0):
        eager = np.float32(x.astype("float32").astype("bfloat16")
                           .tanh().asnumpy())
    np.testing.assert_array_equal(lazy, eager)  # parity-exact rewrites


def test_donation_annotator_marks_safe_leaves():
    mul, tanh, _ = _node_fns()
    b = ir.GraphBuilder()
    lx = b.leaf("x", sig_id=_sig())   # used once, output aval matches
    la = b.leaf("a", sig_id=_sig())   # used twice: not donatable
    n1 = b.add("multiply", mul, {}, (), (lx, la), sig=_sig())
    n2 = b.add("multiply", mul, {}, (), (n1, la), sig=_sig())
    g = b.build((n2,))
    opt = ir.PassManager(("donation",)).run(g)
    assert opt.meta["donatable_leaves"] == (0,)


def test_pass_stats_registered_in_observability():
    snap = mx.observability.snapshot()
    assert "ir" in snap
    for k in ("cache", "interner", "builds", "passes"):
        assert k in snap["ir"]
    assert set(snap["ir"]["passes"]) == set(irpasses.PASS_STATS)
    # eviction counters surfaced for the canonical cache
    assert "evictions" in snap["ir"]["cache"]
    assert "evictions" in snap["caches"]["ir"]


# ============================================== retrace + key determinism


def test_zero_retrace_steady_state_with_watchdog_armed(rng):
    """Acceptance: all three captures re-running warmed topologies under
    the ARMED watchdog produce zero retrace events."""
    X, W1, B1, W2, B2 = _mlp_arrays(rng)
    arrs = [nd.array(a) for a in (X, W1, B1, W2, B2)]
    xg = nd.array(X)
    xg.attach_grad()
    sym = _mlp_sym()

    def bulk_step():
        with engine.bulk(32):
            keep = _mlp_nd(*arrs)
            return keep[-1].asnumpy()

    def tape_step():
        with autograd.record():
            loss = (xg * xg).sum()
        loss.backward()
        return float(loss._data)

    def sym_step():
        return sym.eval(x=X, w1=W1, b1=B1, w2=W2, b2=B2)[-1].asnumpy()

    bulk_step(), tape_step(), sym_step()  # warm
    watchdog.reset_events()
    watchdog.arm()
    try:
        for _ in range(3):
            bulk_step()
            tape_step()
            sym_step()
        assert len(watchdog.events) == 0, watchdog.events
    finally:
        watchdog.disarm()
        watchdog.reset_events()


def _twin_graph():
    mul, tanh, add = _node_fns()
    b = ir.GraphBuilder()
    lx = b.leaf("x", sig_id=_sig((3, 3)))
    la = b.leaf("a", sig_id=_sig((3, 3)))
    n1 = b.add("multiply", mul, {}, (), (lx, la))
    n2 = b.add("tanh", tanh, {}, (), (n1,))
    n3 = b.add("add", add, {}, (), (n2, lx))
    return b.build((n3,))


def test_canonical_key_determinism():
    g1, g2 = _twin_graph(), _twin_graph()
    k1 = ir.canonical_key(ir.canonicalize(g1).graph)
    k2 = ir.canonical_key(ir.canonicalize(g2).graph)
    assert k1 == k2 and isinstance(k1, str) and len(k1) == 64
    # a materially different graph keys differently
    mul, tanh, add = _node_fns()
    b = ir.GraphBuilder()
    lx = b.leaf("x", sig_id=_sig((3, 3)))
    la = b.leaf("a", sig_id=_sig((3, 3)))
    n1 = b.add("add", add, {}, (), (lx, la))
    g3 = b.build((n1,))
    assert ir.canonical_key(ir.canonicalize(g3).graph) != k1


def test_pass_pipeline_determinism():
    o1 = ir.PassManager().run(_twin_graph())
    o2 = ir.PassManager().run(_twin_graph())
    assert [n.ident() for n in o1.nodes] == [n.ident() for n in o2.nodes]
    assert o1.outputs == o2.outputs and o1.leaf_sigs == o2.leaf_sigs
    assert ir.canonical_key(ir.canonicalize(o1).graph) == \
        ir.canonical_key(ir.canonicalize(o2).graph)


def test_single_shared_interner():
    """Satellite: the duplicated per-capture signature interning collapsed
    into ONE bounded table in ir.graph — ndarray's hot-loop names are
    aliases of the same objects."""
    from mxnet_tpu import ndarray as ndm

    assert ndm._sig_id is irgraph._sig_id
    assert ndm._SIG_IDS is irgraph._SIG_IDS
    assert ndm._SIG_LIST is irgraph._SIG_LIST
    assert ndm._AVAL_CACHE is irgraph._AVAL_CACHE
    snap = mx.observability.snapshot()
    assert snap["caches"]["sig_intern"]["entries"] == len(irgraph._SIG_IDS)


def test_bounded_cache_counts_evictions():
    c = base.BoundedCache(2)
    c["a"], c["b"], c["c"] = 1, 2, 3
    assert len(c) == 2 and c.evictions == 1


# ==================================================== fallbacks stay alive


def test_stochastic_symbol_falls_back(rng):
    """A graph that draws randomness at run time cannot lower through
    the IR — eval still works via the legacy path, drawing fresh noise."""
    X = rng.normal(size=(64, 64)).astype(np.float32)
    y = S.Symbol("Dropout", [S.var("x")], {"p": 0.5, "training": True})
    out = y.eval(x=X)[0].asnumpy()
    assert S._ir_skeleton_of(y) is False
    assert out.shape == X.shape


def test_control_flow_symbol_falls_back(rng):
    X = rng.normal(size=(4,)).astype(np.float32)
    x = S.var("x")
    pred = S.Symbol("sum", [x], {})
    y = S.cond(pred > 0, x * 2.0, x * 3.0)
    out = y.eval(x=X)[0].asnumpy()
    want = X * 2.0 if X.sum() > 0 else X * 3.0
    np.testing.assert_allclose(out, want, atol=1e-6)
    assert S._ir_skeleton_of(y) is False


def test_opaque_tape_node_falls_back_to_eager_walk(rng):
    """autograd.Function on the path keeps the eager backward walk."""
    X = rng.normal(size=(4,)).astype(np.float32)

    class Double(autograd.Function):
        def forward(self, x):
            return x * 2

        def backward(self, dy):
            return dy * 2

    x = nd.array(X)
    x.attach_grad()
    with autograd.record():
        loss = (Double()(x) * x).sum()
    loss.backward()
    np.testing.assert_allclose(np.asarray(x.grad._data), 4 * X, atol=1e-5)
