"""mxnet_tpu.ir.tune — cost-model-driven autotuning over the typed IR.

The TVM thesis (arXiv 1802.04799) applied to this stack: schedules are
*searched*, not hand-authored. Every knob that decides real step time —
the PassManager configuration (pass ordering, quant placement,
cast-sink on/off, the constant-fold size cap), the per-graph donation
policy, the imperative bulk watermark (``MXNET_ENGINE_BULK_SIZE``),
serve bucket sets, and the flash-attention block tables — becomes a
candidate space this module searches with two instruments the repo
already trusts:

* the **costs ledger** (observability.costs, PR 13): every candidate is
  compiled once and its deterministic flops / bytes-accessed / peak-HBM
  columns prune the space BEFORE anything is timed, so the search
  measures only plausibly-winning configs (μ-cuDNN's decompose-to-fit
  parameters are workload-dependent, arXiv 1804.04806 — but most of a
  grid is dominated and never worth a stopwatch);
* **paired-step timing** (PERF.md §2): run-level A/B on a
  shared box swings ±50%, so the objective interleaves ONE step per arm
  and takes the median of per-pair deltas — contention hits both sides
  of every pair.

Winners persist to a JSON store keyed by ``ir.graph.canonical_key``
(``MXNET_TUNE_STORE``, or ``<MXNET_COMP_CACHE_DIR>/tuned.json`` so the
tuned configs ride the comp-cache to every replica; in-memory when
neither is set). ``ir.lower.prepare`` consults :func:`pass_manager_for`
before falling back to ``DEFAULT_PASSES`` — tuning is paid once per
topology and a fresh process reloads the winner with ZERO re-search
(tests pin this with the retrace watchdog armed).

Every candidate the search may emit is parity-gated at ≤1e-6 against
the DEFAULT_PASSES output on deterministic example inputs; ``quant`` —
the one pass that intentionally changes numerics — is excluded from the
default space and only enters via ``include_quant=True``, where the
same gate applies (so it only survives on graphs it cannot touch).
"""
from __future__ import annotations

import json
import os
import threading
import time

import numpy as np

from .. import base
from ..observability import costs as _costs
from . import graph as _g
from . import passes as _p

__all__ = ["search", "candidate_configs", "rank_candidates",
           "paired_step_ms", "pass_manager_for", "install", "lookup",
           "fit_buckets", "tune_buckets", "tune_bulk_watermark",
           "tune_flash_blocks", "flash_block_candidates", "store_path",
           "get_store", "reset_store", "stats", "reset_stats"]

TUNED_BY = "mxnet_tpu.ir.tune"

# fixed-key search telemetry (GL006: bounded by construction) — the
# observability "tune" collector and tools/diagnose.py read this via
# stats()
_STATS = {
    "searches": 0,          # search() invocations this process
    "candidates": 0,        # configs probed (compiled for cost columns)
    "pruned": 0,            # dominated by the cost ledger — never timed
    "timed": 0,             # survivors measured with paired steps
    "parity_rejects": 0,    # candidates discarded for output mismatch
    "installs": 0,          # winners written to the store
    "store_hits": 0,        # lower-path lookups that found a tuned config
    "store_misses": 0,      # lookups that fell back to DEFAULT_PASSES
    "last_search": None,    # summary dict of the most recent search()
}

_lock = threading.Lock()


def _utcnow():
    return time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())


# ------------------------------------------------------------------ store


def store_path():
    """Resolved tuned-config store path, or None (in-memory only).
    ``MXNET_TUNE_STORE`` wins; otherwise the store lives inside the
    persistent comp-cache directory so tuned configs ship with the
    compiled executables they pair with."""
    p = os.environ.get("MXNET_TUNE_STORE")
    if p:
        return p
    cc = os.environ.get("MXNET_COMP_CACHE_DIR")
    if cc:
        return os.path.join(cc, "tuned.json")
    return None


class TunedStore:
    """Persistent ``key -> record`` map of tuning winners.

    Keys are namespaced: ``graph:<canonical sha>`` (PassManager
    configs), ``engine:bulk_size``, ``serve:buckets:<server name>``,
    ``flash:blocks``. Records always carry ``tuned_by`` / ``swept_at``
    / ``backend`` provenance next to the config itself. Writes are
    atomic (tmp + ``os.replace``) so a crashed search never leaves a
    half-written store; loads are lazy and a malformed file degrades to
    empty with a warning (tuning must never break lowering)."""

    VERSION = 1

    def __init__(self, path=None):
        self.path = path
        self._entries = None
        self._lock = threading.Lock()

    def _load(self):
        if self._entries is not None:
            return self._entries
        entries = {}
        if self.path and os.path.exists(self.path):
            try:
                with open(self.path) as f:
                    raw = json.load(f)
                entries = dict(raw.get("entries", {}))
            except Exception as e:
                import warnings

                warnings.warn("ignoring malformed tuned-config store %s "
                              "(%s); starting empty" % (self.path, e))
        self._entries = entries
        return entries

    def _save(self):
        if not self.path:
            return
        d = os.path.dirname(self.path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"version": self.VERSION, "entries": self._entries},
                      f, indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, self.path)

    def get(self, key):
        with self._lock:
            return self._load().get(key)

    def put(self, key, record):
        with self._lock:
            self._load()[key] = record
            self._save()

    def keys(self):
        with self._lock:
            return sorted(self._load())

    def __len__(self):
        with self._lock:
            return len(self._load())


_store = None


def get_store():
    global _store
    with _lock:
        if _store is None:
            _store = TunedStore(store_path())
        return _store


def reset_store():
    """Test hook: drop the in-process store handle so the next access
    re-resolves the path (e.g. after changing ``MXNET_TUNE_STORE``)."""
    global _store
    with _lock:
        _store = None


def reset_stats():
    with _lock:
        for k in _STATS:
            _STATS[k] = None if k == "last_search" else 0


def stats():
    """The observability "tune" collector / diagnose "Autotuning"
    section payload."""
    with _lock:
        out = dict(_STATS)
    st = get_store()
    out["store"] = {"path": st.path, "entries": len(st),
                    "keys": st.keys()[:16]}
    return out


# ------------------------------------------------- lower-path integration


def lookup(key):
    """Raw store record for canonical graph ``key``, or None."""
    return get_store().get("graph:" + key)


def pass_manager_for(key):
    """The tuned :class:`~mxnet_tpu.ir.passes.PassManager` for canonical
    graph ``key``, or None to fall back to ``DEFAULT_PASSES``. This is
    the hook ``ir.lower.prepare`` consults on every entry build — a hit
    means the search already ran (this process or any process sharing
    the store) and lowering replays the winner with zero re-search."""
    rec = lookup(key)
    with _lock:
        if rec is None:
            _STATS["store_misses"] += 1
        else:
            _STATS["store_hits"] += 1
    if rec is None:
        return None
    try:
        return _p.PassManager.from_config(rec["config"])
    except Exception:
        return None  # stale/foreign record: DEFAULT_PASSES, never a crash


def install(key, config, objective=None, cost=None, tuned_by=None):
    """Persist a winning config for canonical graph ``key`` and evict
    the live IR-cache entry so the NEXT lowering of this topology
    rebuilds with the tuned config (one retrace at install time, zero
    after — the watchdog-armed contract tests pin)."""
    rec = {"config": dict(config),
           "tuned_by": tuned_by or (TUNED_BY + ".search"),
           "swept_at": _utcnow(),
           "backend": _backend_name()}
    if objective:
        rec["objective"] = objective
    if cost:
        rec["cost"] = cost
    get_store().put("graph:" + key, rec)
    base._IR_CACHE.pop(key, None)
    with _lock:
        _STATS["installs"] += 1
    return rec


def _backend_name():
    try:
        import jax

        return jax.default_backend()
    except Exception:
        return None


# -------------------------------------------------------- candidate space


def candidate_configs(include_quant=False):
    """Deterministic candidate list over the PassManager surface: pass
    orderings (fold before/after CSE, cast-sink placement), pass subsets
    (cast-sink off, donation off), and the constant-fold size cap (the
    ``MXNET_IR_FOLD_MAX_ELEMS`` axis — larger caps bake bigger constant
    islands into the program; XLA refuses to pre-evaluate expensive ops
    like ``dot`` over constants, so this is a real lever, measured in
    tools/tune_bench.py). ``quant`` only enters on request: it is the
    one pass that intentionally changes numerics, and the parity gate
    will reject it anywhere it actually fires."""
    orderings = [
        ("cse", "fold", "cast_sink", "dce", "donation"),
        ("fold", "cse", "cast_sink", "dce", "donation"),
        ("cse", "cast_sink", "fold", "dce", "donation"),
        ("cse", "fold", "dce", "donation"),        # cast_sink off
        ("cse", "fold", "cast_sink", "dce"),       # donation off
    ]
    if include_quant:
        orderings.append(
            ("cse", "fold", "cast_sink", "dce", "donation", "quant"))
    caps = (None, 262144, 1048576)  # None = the process default (65536)
    out = []
    for cap in caps:
        for o in orderings:
            cfg = {"passes": list(o)}
            if cap is not None:
                cfg["fold_max_elems"] = cap
            out.append(cfg)
    return out


def config_key(cfg):
    """Stable string identity of a config (ranking tiebreak, dedupe)."""
    return json.dumps(cfg, sort_keys=True)


def example_leaves(cgraph, seed=0):
    """Deterministic example inputs for a canonical graph's leaves —
    the values every candidate is parity-checked and timed on. Array
    leaves only: scalar-typed or untyped leaves make the probe program
    ambiguous, and every graph the capture layers lower has array
    leaves."""
    rs = np.random.RandomState(seed)
    vals = []
    for sid in cgraph.leaf_sigs:
        sig = None if sid is None else _g.sig_value(sid)
        if type(sig) is not tuple:
            raise ValueError(
                "tune.search needs array-typed leaves (got %r)" % (sig,))
        dt, shape = np.dtype(sig[0]), sig[1]
        if dt.kind in "iu":
            vals.append(rs.randint(0, 8, size=shape).astype(dt))
        elif dt.kind == "b":
            vals.append((rs.rand(*shape) > 0.5))
        else:
            vals.append(rs.standard_normal(shape).astype(dt))
    return vals


class _Probe:
    """One candidate, compiled once: the optimized graph, its AOT
    executable, the cost-ledger columns, and the outputs on the example
    inputs (the parity evidence and the timing operands)."""

    __slots__ = ("config", "compiled", "args", "cost", "outputs",
                 "n_nodes")

    def __init__(self, config, compiled, args, cost, outputs, n_nodes):
        self.config = config
        self.compiled = compiled
        self.args = args
        self.cost = cost
        self.outputs = outputs
        self.n_nodes = n_nodes

    def step(self):
        import jax

        jax.block_until_ready(self.compiled(*self.args))


def _probe(cgraph, pm, leaves, config):
    """Compile one candidate AOT and read its cost columns. Probe
    programs are throwaway search artifacts — deliberately NOT routed
    through the persistent funnel (they must not crowd real programs
    out of the comp-cache), so the direct jit is intentional."""
    import jax

    final, leaf_sel, _ = _p.optimize(cgraph, pm)
    run = _g.build_runner(final)

    def fwd(*leaf_vals):
        return run(leaf_vals)

    args = [leaves[li] for li in leaf_sel]
    avals = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args]
    jfn = jax.jit(fwd)  # graphlint: disable=GL008
    compiled = jfn.lower(*avals).compile()
    cost = _costs._analyze(compiled)
    outputs = [np.asarray(o) for o in compiled(*args)]
    return _Probe(config, compiled, args, cost, outputs, final.n_nodes)


def _parity_ok(base_outs, cand_outs, tol=1e-6):
    if len(base_outs) != len(cand_outs):
        return False
    for a, b in zip(base_outs, cand_outs):
        if a.shape != b.shape or a.dtype != b.dtype:
            return False
        if not np.allclose(np.asarray(a, np.float64),
                           np.asarray(b, np.float64), rtol=tol, atol=tol):
            return False
    return True


def rank_candidates(rows):
    """Deterministic cost-model ranking: ascending (bytes_accessed,
    flops, peak_hbm_bytes), config-key tiebreak. Pure — same ledger
    columns in, same order out, in any process (the pruning-determinism
    test contract)."""
    return sorted(rows, key=lambda r: (
        float(r["cost"]["bytes_accessed"]), float(r["cost"]["flops"]),
        float(r["cost"]["peak_hbm_bytes"]), r["config_key"]))


def _cost_plausible(cand_cost, base_cost):
    """Ledger gate: a candidate is worth a stopwatch only if it strictly
    improves at least one first-order column — bytes accessed (the
    memory-bound proxy), flops, or peak HBM."""
    return (cand_cost["bytes_accessed"] < base_cost["bytes_accessed"]
            or cand_cost["flops"] < base_cost["flops"]
            or cand_cost["peak_hbm_bytes"] < base_cost["peak_hbm_bytes"])


# ------------------------------------------------------------- the search


def paired_step_ms(fn_a, fn_b, pairs=5):
    """PERF.md paired-step objective: interleave ONE step per arm so
    shared-box contention hits both sides of every pair; report the
    median per-arm step wall and the median per-pair delta (a - b, ms).
    Callers warm both arms first (compiles must never land in a pair)."""
    deltas, a_ms, b_ms = [], [], []
    for _ in range(max(1, int(pairs))):
        t0 = time.perf_counter()
        fn_a()
        t1 = time.perf_counter()
        fn_b()
        t2 = time.perf_counter()
        a, b = (t1 - t0) * 1e3, (t2 - t1) * 1e3
        a_ms.append(a)
        b_ms.append(b)
        deltas.append(a - b)

    def med(v):
        return sorted(v)[len(v) // 2]

    return {"a_ms": round(med(a_ms), 6), "b_ms": round(med(b_ms), 6),
            "delta_ms": round(med(deltas), 6)}


def search(raw_graph, pairs=5, top_k=3, include_quant=False,
           install_winner=True, configs=None):
    """Search the PassManager space for one graph and (optionally)
    install the winner.

    Flow: canonicalize → probe DEFAULT_PASSES (the baseline) → probe
    each candidate config (one AOT compile each, outputs parity-gated
    at ≤1e-6) → prune everything the cost ledger says is not plausibly
    faster → paired-step time the top ``top_k`` survivors against the
    baseline → the fastest strict improvement (wall AND ledger
    direction) is installed under ``graph:<canonical key>``.

    Returns a report dict; ``report["winner"]`` is None when nothing
    beat the baseline (the store is then left untouched — DEFAULT_PASSES
    was already optimal for this topology)."""
    canon = _g.canonicalize(raw_graph)
    cgraph = canon.graph
    key = _g.canonical_key(cgraph)
    leaves = example_leaves(cgraph)
    baseline = _probe(cgraph, _p.PassManager(), leaves,
                      {"passes": list(_p.DEFAULT_PASSES)})
    rows = []
    parity_rejects = 0
    cand_cfgs = list(configs) if configs is not None \
        else candidate_configs(include_quant)
    for cfg in cand_cfgs:
        try:
            probe = _probe(cgraph, _p.PassManager.from_config(cfg),
                           leaves, cfg)
        except Exception:
            continue  # config not buildable for this graph: skip, no crash
        if not _parity_ok(baseline.outputs, probe.outputs):
            parity_rejects += 1
            continue
        rows.append({"config": cfg, "config_key": config_key(cfg),
                     "cost": probe.cost, "probe": probe,
                     "n_nodes": probe.n_nodes})
    plausible = [r for r in rows
                 if _cost_plausible(r["cost"], baseline.cost)]
    timed = rank_candidates(plausible)[:max(0, int(top_k))]
    pruned = len(rows) - len(timed)
    # warm both arms (jit probes already executed once in _probe, but an
    # explicit warm step keeps any lazy backend work out of pair 0)
    baseline.step()
    results = []
    for r in timed:
        r["probe"].step()
        t = paired_step_ms(baseline.step, r["probe"].step, pairs=pairs)
        results.append({
            "config": r["config"], "config_key": r["config_key"],
            "cost": {k: r["cost"][k] for k in
                     ("flops", "bytes_accessed", "peak_hbm_bytes")},
            "baseline_step_ms": t["a_ms"], "tuned_step_ms": t["b_ms"],
            "delta_ms": t["delta_ms"],
        })
    # winner: fastest measured, but only a STRICT improvement on both
    # instruments — wall (median per-pair delta > 0) and the ledger
    # direction the acceptance contract asserts (bytes or peak HBM)
    winner = None
    for res in sorted(results, key=lambda r: (-r["delta_ms"],
                                              r["config_key"])):
        cc = res["cost"]
        if res["delta_ms"] > 0 and (
                cc["bytes_accessed"] < baseline.cost["bytes_accessed"]
                or cc["peak_hbm_bytes"] < baseline.cost["peak_hbm_bytes"]):
            winner = res
            break
    report = {
        "key": key,
        "baseline_cost": {k: baseline.cost[k] for k in
                          ("flops", "bytes_accessed", "peak_hbm_bytes")},
        "candidates": len(rows) + parity_rejects,
        "parity_rejects": parity_rejects,
        "pruned": pruned,
        "timed": results,
        "pairs": pairs,
        "winner": winner,
    }
    with _lock:
        _STATS["searches"] += 1
        _STATS["candidates"] += len(rows) + parity_rejects
        _STATS["pruned"] += pruned
        _STATS["timed"] += len(results)
        _STATS["parity_rejects"] += parity_rejects
        _STATS["last_search"] = {
            "key": key[:16], "candidates": report["candidates"],
            "pruned": pruned, "timed": len(results), "pairs": pairs,
            "winner": None if winner is None else winner["config_key"],
        }
    if winner is not None and install_winner:
        install(key, winner["config"],
                objective={"baseline_step_ms": winner["baseline_step_ms"],
                           "tuned_step_ms": winner["tuned_step_ms"],
                           "delta_ms": winner["delta_ms"],
                           "pairs": pairs},
                cost={"baseline": report["baseline_cost"],
                      "tuned": winner["cost"]})
    return report


# -------------------------------------------------------- serve buckets


def fit_buckets(size_counts, max_buckets=6, max_size=None):
    """Optimal bucket set for a measured request-size histogram:
    minimize total PAD ROWS (the waste ServeMetrics' per-bucket
    histograms surface) with at most ``max_buckets`` buckets, boundaries
    chosen from the observed sizes. Deterministic DP — same histogram,
    same buckets, any process. ``max_size`` (e.g. the current largest
    bucket) is always covered so retuning never shrinks admissible
    requests. Replaces the blind pow2 default when real traffic says
    otherwise."""
    counts = {int(s): int(c) for s, c in dict(size_counts).items()
              if int(s) > 0 and int(c) > 0}
    if max_size is not None:
        counts.setdefault(int(max_size), 0)
    if not counts:
        raise ValueError("fit_buckets needs a non-empty size histogram")
    sizes = sorted(counts)
    n = len(sizes)
    k = min(max(1, int(max_buckets)), n)
    # prefix sums for O(1) segment pad cost: covering sizes[j..i] with
    # bucket sizes[i] pads (sizes[i] - s) rows for each request of size s
    pc = [0] * (n + 1)
    psc = [0] * (n + 1)
    for i, s in enumerate(sizes):
        pc[i + 1] = pc[i] + counts[s]
        psc[i + 1] = psc[i] + counts[s] * s

    def seg(j, i):
        return sizes[i] * (pc[i + 1] - pc[j]) - (psc[i + 1] - psc[j])

    INF = float("inf")
    dp = [[INF] * (k + 1) for _ in range(n)]
    back = [[-1] * (k + 1) for _ in range(n)]
    for i in range(n):
        dp[i][1] = seg(0, i)
        for b in range(2, k + 1):
            for j in range(1, i + 1):
                c = dp[j - 1][b - 1]
                if c == INF:
                    continue
                c += seg(j, i)
                if c < dp[i][b]:
                    dp[i][b] = c
                    back[i][b] = j - 1
    best_b = min(range(1, k + 1), key=lambda b: (dp[n - 1][b], b))
    buckets = []
    i, b = n - 1, best_b
    while i >= 0 and b >= 1:
        buckets.append(sizes[i])
        i, b = back[i][b], b - 1
        if b == 0:
            break
    return tuple(sorted(buckets))


def tune_buckets(server, max_buckets=6, apply=True, install_record=True):
    """Fit a bucket set to a live server's measured request-size
    histogram (ServeMetrics), optionally rebuild the server on it
    (``ModelServer.retune_buckets`` — new pool, warm compile, batcher
    rewire), and persist the winner under ``serve:buckets:<name>``."""
    hist = server.metrics.request_rows()
    if not hist:
        raise ValueError(
            "no request-size history on %r — serve traffic (or replay a "
            "trace) before tuning buckets" % server.name)
    buckets = fit_buckets(hist, max_buckets=max_buckets,
                          max_size=server.buckets[-1])
    before = tuple(server.buckets)
    pad_before = _pad_rows(hist, before)
    pad_after = _pad_rows(hist, buckets)
    if install_record:
        get_store().put("serve:buckets:" + server.name, {
            "config": {"buckets": list(buckets)},
            "tuned_by": TUNED_BY + ".tune_buckets",
            "swept_at": _utcnow(), "backend": _backend_name(),
            "objective": {"pad_rows_before": pad_before,
                          "pad_rows_after": pad_after,
                          "requests": sum(hist.values())},
        })
    if apply and buckets != before:
        server.retune_buckets(buckets)
    return {"buckets": buckets, "before": before,
            "pad_rows_before": pad_before, "pad_rows_after": pad_after}


def _pad_rows(hist, buckets):
    bs = sorted(buckets)
    total = 0
    for s, c in hist.items():
        b = next((x for x in bs if x >= s), bs[-1])
        total += max(0, b - s) * c
    return total


# ------------------------------------------------------- bulk watermark


def tune_bulk_watermark(candidates=(0, 5, 15, 30, 60), rounds=8,
                        chain=24, shape=(64, 64), apply=False,
                        install_record=True):
    """Search the imperative bulk-window watermark
    (``MXNET_ENGINE_BULK_SIZE``) on a representative fusible op chain.
    Round-robin interleaved (one step per candidate per round — the
    paired-step discipline generalized to N arms), median step wall per
    candidate. The winner persists under ``engine:bulk_size``;
    ``apply=True`` also calls ``engine.set_bulk_size`` on it."""
    from .. import engine
    from .. import ndarray as nd

    candidates = tuple(dict.fromkeys(int(c) for c in candidates))

    def step(size):
        prev = engine.set_bulk_size(size)
        try:
            x = nd.ones(shape)
            for _ in range(chain):
                x = x * 1.0009765625 + 0.5
            x.asnumpy()
        finally:
            engine.set_bulk_size(prev)

    for c in candidates:  # warm: compile each watermark's window splits
        step(c)
    walls = {c: [] for c in candidates}
    for _ in range(max(1, int(rounds))):
        for c in candidates:
            t0 = time.perf_counter()
            step(c)
            walls[c].append((time.perf_counter() - t0) * 1e3)
    medians = {c: round(sorted(v)[len(v) // 2], 6)
               for c, v in walls.items()}
    winner = min(candidates, key=lambda c: (medians[c], c))
    if install_record:
        get_store().put("engine:bulk_size", {
            "config": {"bulk_size": winner},
            "tuned_by": TUNED_BY + ".tune_bulk_watermark",
            "swept_at": _utcnow(), "backend": _backend_name(),
            "objective": {"medians_ms": {str(c): medians[c]
                                         for c in candidates},
                          "rounds": rounds, "chain": chain},
        })
    if apply:
        engine.set_bulk_size(winner)
    return {"winner": winner, "medians_ms": medians}


# --------------------------------------------------- flash block tables

# VMEM is ~16 MB/core (pallas guide); candidates whose working set —
# Q-block resident + streamed K/V blocks (double-buffered) + fp32
# row-stat and accumulator scratch — exceeds a conservative budget are
# pruned before any kernel runs
_VMEM_BUDGET_BYTES = 12 * 2 ** 20
_FLASH_GRID = (128, 256, 512)


def flash_block_candidates(seq, head_dim, dtype_bytes=2,
                           vmem_budget=_VMEM_BUDGET_BYTES):
    """(block_q, block_k) candidates for one sequence length, pruned by
    the VMEM footprint model — the cost-model stage of the flash search
    (no hardware needed, deterministic)."""
    from ..ops.pallas import flash_attention as fa

    cands = []
    for bq in _FLASH_GRID:
        for bk in _FLASH_GRID:
            if bq > seq or bk > seq:
                continue
            # labels must time what they claim: skip non-divisor blocks
            # the kernel entry would silently shrink onto another label
            if fa._largest_divisor_block(seq, bq) != bq \
                    or fa._largest_divisor_block(seq, bk) != bk:
                continue
            footprint = (
                bq * head_dim * dtype_bytes          # resident Q block
                + 2 * 2 * bk * head_dim * dtype_bytes  # K+V, double-buffered
                + 2 * bq * fa.LANES * 4              # m/l row stats (fp32)
                + bq * head_dim * 4)                 # fp32 accumulator
            if footprint > vmem_budget:
                continue
            cands.append((bq, bk))
    return sorted(cands)


def tune_flash_blocks(seqs=(128, 256, 512, 2048), batch=1, heads=4,
                      dim=128, pairs=5, interpret=False, apply=False,
                      vmem_budget=_VMEM_BUDGET_BYTES):
    """Search flash-attention (block_q, block_k) per sequence bucket and
    write the winners through the SAME artifact writer flash_sweep uses
    (``flash_attention.write_block_artifact``) — retiring the hand-run
    table. TPU-gated: off-TPU the Pallas kernels only run under
    ``interpret=True`` (tests use tiny shapes there); timings from the
    interpreter are for plumbing only and are labelled as such."""
    import jax
    import jax.numpy as jnp

    from ..ops.pallas import flash_attention as fa

    if not interpret and _backend_name() != "tpu":
        raise RuntimeError(
            "flash block tuning needs a TPU backend (pass interpret=True "
            "only for plumbing tests — interpreter timings are not "
            "schedule evidence)")
    winners = {}
    rows = []
    for seq in seqs:
        cands = flash_block_candidates(seq, dim,
                                       vmem_budget=vmem_budget)
        if not cands:
            continue
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
        shape = (batch, heads, seq, dim)
        q = jax.random.normal(k1, shape, jnp.bfloat16)
        k = jax.random.normal(k2, shape, jnp.bfloat16)
        v = jax.random.normal(k3, shape, jnp.bfloat16)
        best = None
        for bq, bk in cands:
            def step(bq=bq, bk=bk):
                jax.block_until_ready(fa.flash_attention(
                    q, k, v, block_q=bq, block_k=bk,
                    interpret=interpret))

            step()  # warm (compile) outside the pairs
            if best is None:
                t0 = time.perf_counter()
                step()
                ms = (time.perf_counter() - t0) * 1e3
                best = {"blocks": (bq, bk), "ms": ms, "step": step}
                rows.append({"seq": seq, "block_q": bq, "block_k": bk,
                             "ms": round(ms, 4)})
                continue
            t = paired_step_ms(best["step"], step, pairs=pairs)
            rows.append({"seq": seq, "block_q": bq, "block_k": bk,
                         "ms": t["b_ms"]})
            if t["delta_ms"] > 0:  # incumbent median-slower: replace
                best = {"blocks": (bq, bk), "ms": t["b_ms"], "step": step}
        winners[seq] = best["blocks"]
    if not winners:
        raise ValueError("no timeable (seq, block) candidates")
    blocks = {s: list(winners[s]) for s in winners}
    blocks[0] = blocks[min(winners)]
    result = {"winners": {str(s): list(b) for s, b in winners.items()},
              "rows": rows, "interpret": interpret}
    if apply:
        result["artifact"] = fa.write_block_artifact(
            blocks,
            source="ir.tune.tune_flash_blocks",
            swept_at=_utcnow(),
            tuned_by=TUNED_BY + ".tune_flash_blocks"
            + (" (interpret — plumbing only)" if interpret else ""),
            backend=_backend_name())
    return result
