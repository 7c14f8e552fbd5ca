"""mxnet_tpu.observability — unified runtime telemetry.

One registry absorbs every signal the repo already proves its dispatch
story with — the engine ``DispatchCounter``s (dispatch + the
bulk/tape/serve/decode compile counters and comp-cache hit/miss/
deserialize), the serve/generative latency rings, the bounded program
caches, the profiler record buffer — and exports them two ways from one
``snapshot()``:

* ``observability.snapshot()`` — stable JSON; ``tools/diagnose.py``
  renders its human report from this dict and ``--json`` emits it
  verbatim;
* ``observability.prometheus()`` — Prometheus text exposition, served by
  the opt-in ``/metrics`` endpoint (``ModelServer``/``GenerativeServer``
  ``metrics_port=``, http.py).

Per-request tracing (tracing.py) threads a trace-id from ``submit()``
through queue → coalesce → pad → dispatch → (decode) token steps; the
retrace watchdog (watchdog.py) turns the zero-steady-state-retrace test
contract into a runtime alarm. The old names all still work —
``engine.dispatch_counter``, ``serve.stats()``, ``ServeMetrics`` — the
registry reads them, it does not replace them.
"""
from __future__ import annotations

from . import costs  # noqa: F401
from . import watchdog  # noqa: F401
from .http import MetricsHTTPServer  # noqa: F401
from .registry import (Counter, Gauge, Histogram,  # noqa: F401
                       MetricsRegistry, render_prometheus)
from .tracing import (RequestTrace, new_trace, set_tracing,  # noqa: F401
                      tracing_enabled)

__all__ = ["registry", "snapshot", "prometheus", "MetricsRegistry",
           "Counter", "Gauge", "Histogram", "RequestTrace", "new_trace",
           "set_tracing", "tracing_enabled", "arm_watchdog",
           "disarm_watchdog", "MetricsHTTPServer", "enable_op_telemetry",
           "op_telemetry_enabled", "note_compile", "render_prometheus",
           "device_section", "costs"]

# the process-wide default registry (module-level by design: it is the
# blessed home for metric state — graphlint GL009 polices ad-hoc metric
# state anywhere else)
registry = MetricsRegistry()

arm_watchdog = watchdog.arm
disarm_watchdog = watchdog.disarm

# compile accounting (fed by cache.AotFn around lower/compile): cumulative
# XLA compile wall-time + count — the "is this replica compiling under
# traffic" gauge the watchdog's per-event warnings aggregate into
_compiles_total = registry.counter(
    "compiles_total", "explicit lower/compile builds (cache.AotFn)")
_compile_seconds = registry.counter(
    "compile_seconds_total", "wall-clock seconds spent in lower/compile")


def note_compile(seconds):
    _compiles_total.inc()
    _compile_seconds.inc(float(seconds))


# ---------------------------------------------------------- op telemetry
# per-op-name dispatch counts from the imperative hot loop. Off by default:
# ndarray.invoke reads ONE precomputed module boolean (the _prof_on trick);
# when on, the cost is one dict increment per op into this registry-owned
# dict (bounded by len(OP_REGISTRY)).
_op_counts = {}


def enable_op_telemetry(on=True):
    """Count imperative dispatches per op name (``snapshot()['ops']``).
    Returns the previous state."""
    from .. import ndarray as _nd

    prev = _nd._obs_on
    _nd._obs_counts = _op_counts
    _nd._obs_on = bool(on)
    return prev


def op_telemetry_enabled():
    from .. import ndarray as _nd

    return _nd._obs_on


# ------------------------------------------------------------- collectors
def _collect_engine():
    from .. import engine

    return {
        "dispatch": engine.dispatch_counter.count,
        "bulk_compile": engine.bulk_compile_counter.count,
        "tape_compile": engine.tape_compile_counter.count,
        "tape_cache_hit": engine.tape_cache_hit_counter.count,
        "symbol_compile": engine.symbol_compile_counter.count,
        "serve_compile": engine.serve_compile_counter.count,
        "decode_compile": engine.decode_compile_counter.count,
        "comp_cache_hit": engine.comp_cache_hit_counter.count,
        "comp_cache_miss": engine.comp_cache_miss_counter.count,
        "comp_cache_deserialize": engine.comp_cache_deserialize_counter.count,
        "dist_bucket": engine.dist_bucket_counter.count,
        "dist_compile": engine.dist_compile_counter.count,
    }


def _collect_caches():
    from .. import base
    from ..autograd import tape_compile_enabled
    from ..ir import graph as irgraph

    return {
        "jit": {"entries": len(base._JIT_CACHE), "cap": base._JIT_CACHE.cap,
                "evictions": base._JIT_CACHE.evictions},
        "bulk": {"entries": len(base._BULK_CACHE),
                 "cap": base._BULK_CACHE.cap,
                 "evictions": base._BULK_CACHE.evictions},
        "tape": {"entries": len(base._TAPE_CACHE),
                 "cap": base._TAPE_CACHE.cap,
                 "evictions": base._TAPE_CACHE.evictions,
                 "compile_enabled": tape_compile_enabled()},
        "ir": {"entries": len(base._IR_CACHE), "cap": base._IR_CACHE.cap,
               "evictions": base._IR_CACHE.evictions},
        "aval": {"entries": len(irgraph._AVAL_CACHE),
                 "cap": irgraph._AVAL_CACHE.cap,
                 "evictions": irgraph._AVAL_CACHE.evictions},
        "sig_intern": {"entries": len(irgraph._SIG_IDS),
                       "cap": irgraph._SIG_INTERN_CAP},
    }


def _collect_comp_cache():
    from .. import cache

    return cache.stats()


def _collect_serve():
    from .. import serve

    return serve.stats()


def _collect_profiler():
    from .. import profiler

    return {
        "running": profiler.is_running(),
        "records": profiler.num_records(),
        "records_cap": profiler.record_cap(),
        "records_dropped": profiler.records_dropped(),
    }


def _collect_ops():
    # copy under the GIL: the hot loop mutates this dict lock-free
    return {"enabled": op_telemetry_enabled(), "dispatches": dict(_op_counts)}


def _collect_ir():
    # unified graph IR (mxnet_tpu.ir): canonical-cache occupancy +
    # evictions, the shared signature interner, build tallies, and the
    # per-pass node/edge delta counters — tools/diagnose.py's "Graph IR"
    # section renders this dict
    from ..ir import lower as irlower

    return irlower.stats()


def _collect_dist():
    # distributed gradient exchange (mxnet_tpu.dist) + resilience events.
    # The registry counters are get-or-create so the section is complete
    # (zeros) even before the first stall/save/restore; the subsystem
    # stats only appear once mxnet_tpu.dist has actually been imported —
    # a collector must never force-load the package it observes.
    import sys

    from .. import engine

    out = {
        "bucket_dispatches": engine.dist_bucket_counter.count,
        "bucket_compiles": engine.dist_compile_counter.count,
        "heartbeat_stalls": registry.counter(
            "dist_heartbeat_stalls",
            "device round-trips exceeding the heartbeat timeout").value,
        "checkpoint_saves": registry.counter(
            "dist_checkpoint_saves", "sharded checkpoint writes").value,
        "checkpoint_restores": registry.counter(
            "dist_checkpoint_restores", "sharded checkpoint restores").value,
        "elastic_recoveries": registry.counter(
            "dist_elastic_recoveries",
            "mesh re-formations after a replica loss").value,
    }
    d = sys.modules.get("mxnet_tpu.dist")
    if d is not None:
        out.update(d.stats())
    else:
        out["subsystem"] = "not loaded"
    return out


def _collect_quant():
    # quantized inference (mxnet_tpu.quant): swap/calibration tallies from
    # the quantization module's fixed-key stats table. Like dist, the
    # subsystem detail only appears once the module has actually been
    # imported — a collector must never force-load the package it
    # observes.
    import sys

    q = sys.modules.get("mxnet_tpu.quantization")
    if q is None:
        return {"subsystem": "not loaded"}
    return q.stats()


def _collect_costs():
    # per-program cost attribution (costs.py): drains any parked lowered
    # handles (the one place the lazy path pays its explicit compiles),
    # then reports bounded profiles + per-tier totals + the live-server
    # HBM ledger
    return costs.snapshot_section()


def _collect_concurrency():
    # racecheck runtime stage (analysis.concurrency): lock-order graph
    # size, deadlock cycles, race reports. Brief form — stacks stay in
    # concurrency.runtime_stats(verbose=True) / tools/diagnose.py
    from ..analysis import concurrency

    return concurrency.runtime_stats()


def _collect_hlolint():
    # program-level StableHLO lint (analysis.hlolint, ISSUE 18): ranked
    # findings over every program captured at the costs seam. Drains the
    # lazy cost path first so the corpus is complete at scrape time, and
    # joins the cost ledger so findings rank by real bytes
    if costs.enabled():
        costs.materialize()
    from ..analysis import hlolint

    return hlolint.snapshot_section(costs.profiles())


def _collect_tune():
    # IR autotuner (ir.tune): search telemetry + tuned-config store
    # shape. Same never-force-load rule as dist/quant — tuning telemetry
    # only appears once something actually imported the tuner.
    import sys

    t = sys.modules.get("mxnet_tpu.ir.tune")
    if t is None:
        return {"subsystem": "not loaded"}
    return t.stats()


registry.register_collector("engine", _collect_engine)
registry.register_collector("concurrency", _collect_concurrency)
registry.register_collector("costs", _collect_costs)
registry.register_collector("hlolint", _collect_hlolint)
registry.register_collector("dist", _collect_dist)
registry.register_collector("quant", _collect_quant)
registry.register_collector("caches", _collect_caches)
registry.register_collector("comp_cache", _collect_comp_cache)
registry.register_collector("serve", _collect_serve)
registry.register_collector("profiler", _collect_profiler)
registry.register_collector("ops", _collect_ops)
registry.register_collector("ir", _collect_ir)
registry.register_collector("tune", _collect_tune)
registry.register_collector("watchdog", watchdog.snapshot)
registry.register_collector(
    "tracing", lambda: {"enabled": tracing_enabled()})


def device_section():
    """HBM live-buffer gauges from the XLA client's own accounting
    (authoritative on TPU — jax owns the HBM pool). Separate from the
    collector set because a device probe initialises the backend, which
    takes the chip for this process (``diagnose.py --no-device``)."""
    from .. import profiler

    try:
        stats = profiler.device_memory_summary()
    except Exception as e:
        return {"error": "%s: %s" % (type(e).__name__, e)}
    return {"hbm_bytes_in_use": stats.get("bytes_in_use"),
            "hbm_peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "hbm_bytes_limit": stats.get("bytes_limit")}


def snapshot(device=False):
    """The stable JSON telemetry snapshot: registry metrics + every
    collector section. ``device=True`` adds the HBM gauges (it initialises
    the backend — opt in)."""
    snap = registry.snapshot()
    if device:
        snap["device"] = device_section()
    return snap


def prometheus(device=False):
    """Prometheus text exposition of :func:`snapshot` — the ``/metrics``
    payload."""
    return render_prometheus(snapshot(device=device))
