#!/usr/bin/env python3
"""Runs one cell of the benchmark once and prints one JSON line.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Knows no model, cell or metric by name. ``--workload x`` resolves to
``workloads/x.json``, which names a configuration (``configs/<c>.json``, with
its plain reference ``reference/<module>.py`` and its operation and byte
counts ``counts/<module>.py``) and a driver (``drivers/<d>.py``, one per kind
of measured window). The metrics the cell reports are those that
``BENCHMARK.json`` gives it (``metrics/<m>.json``, each naming a reader
``readers/<r>.py``). A name that resolves to no file is an error that names
the missing path.

Needs a TPU with as many chips as the cell asks for, and exits non-zero
without one. ``--cpu-rehearsal`` is the one exception: it shrinks the
configuration and the traffic through their ``tiny`` blocks, forces the CPU
backend, walks the same code, and prints a line whose metrics are withheld
and whose ``device`` says ``rehearsal``: it can never be read as a
measurement.
"""
import argparse
import gc
import importlib.util
import json
import os
import sys
import time

T_PROCESS_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from lib import log  # noqa: E402
from lib.log import note  # noqa: E402

log.T0 = T_PROCESS_START


def load_json(kind, name):
    path = os.path.join(HERE, kind, name + ".json")
    if not os.path.isfile(path):
        raise SystemExit("benchmark: %s %r needs the file %s, which is not "
                         "there" % (kind, name, os.path.relpath(path, ROOT)))
    with open(path) as f:
        return json.load(f)


def load_module(kind, name):
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit("benchmark: %s %r needs the file %s, which is not "
                         "there" % (kind, name, os.path.relpath(path, ROOT)))
    spec = importlib.util.spec_from_file_location(
        "benchmark_%s_%s" % (kind, name.replace("-", "_").replace(".", "_")),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def merged(base, over):
    out = dict(base)
    for k, v in (over or {}).items():
        out[k] = merged(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


def cell_metrics(cell_name, benchmark):
    """The names of the metrics ``BENCHMARK.json`` gives a cell: every
    metric whose entry lists the cell under ``workloads``; an end-to-end
    metric without the list; a per-layer metric without it that moves an
    end-to-end metric the cell reports."""
    listed = lambda m: cell_name in m.get("workloads", [cell_name])
    e2e = [m["name"] for m in benchmark["end_to_end"] if listed(m)]
    per = [m["name"] for m in benchmark["per_layer"]
           if listed(m) and ("workloads" in m or m["moves"] in e2e)]
    return {"end_to_end": e2e, "per_layer": per}


def resolve(cell_name, rehearsal, benchmark=None):
    """The cell and its configuration, each from its own file; the cell's
    metrics as ``BENCHMARK.json`` (``benchmark``, read from the root of the
    checkout where not given) lists them, each with its own file; and the
    configuration's module of operation and byte counts."""
    cell = load_json("workloads", cell_name)
    config = load_json("configs", cell["config"])
    if rehearsal:
        config = merged(config, {"sizes": config.get("tiny", {})})
        cell = merged(cell, cell.get("tiny", {}))
    if benchmark is None:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            benchmark = json.load(f)
    metrics = {group: [(m, load_json("metrics", m)) for m in names]
               for group, names in cell_metrics(cell_name, benchmark).items()}
    if "counts" not in config:
        raise SystemExit("benchmark: configuration %r names no module of "
                         "counts (\"counts\": a file under benchmark/counts/)"
                         % cell["config"])
    return cell, config, metrics, load_module("counts", config["counts"])


def override(cell, config, items):
    """``--set traffic.rate_per_s=10`` / ``--set config.sizes.units=256``."""
    for item in items:
        path, _, value = item.partition("=")
        keys = path.split(".")
        at = cell
        if keys[0] == "config":
            at, keys = config, keys[1:]
        for k in keys[:-1]:
            at = at[k]
        at[keys[-1]] = json.loads(value)


def read_metrics(specs, record):
    out = {}
    for name, spec in specs:
        reader = load_module("readers", spec["reader"])
        value = reader.read(record, spec.get("params", {}))
        if value is None:          # nothing to read here: leave it out
            continue
        out[name] = {"value": float(value), "unit": spec["unit"]}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="walk the code on the CPU at the tiny sizes; "
                         "prints no device metric")
    ap.add_argument("--control", default=None, metavar="PRECISION",
                    help="put the reference, computed in this lower "
                         "precision, in the program's place for the "
                         "comparison: `correct` has to come out false. Never "
                         "part of a measurement")
    ap.add_argument("--set", action="append", default=[], metavar="KEY=JSON",
                    help="override one value of the cell's file by its "
                         "dotted key, e.g. traffic.rate_per_s=10 (or of the "
                         "configuration's, config.sizes.num_layers=4): for "
                         "the builder's rate sweeps and control readings. "
                         "Never part of a measurement")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "mxnet_tpu")):
        raise SystemExit("benchmark: the system under test (mxnet_tpu/) is "
                         "not in %s" % ROOT)
    cell, config, metrics, counts = resolve(args.workload, args.cpu_rehearsal)
    override(cell, config, args.set)
    chips = int(cell["chips"])

    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_count"
                                   "=%d" % chips)
    import jax

    devices = jax.devices()
    if args.cpu_rehearsal:
        devices = devices[:chips]
    elif devices[0].platform != "tpu" or len(devices) != chips:
        print("benchmark: cell %s needs %d TPU chip(s); jax reports %d "
              "device(s) of platform %r" % (args.workload, chips,
                                            len(devices),
                                            devices[0].platform),
              file=sys.stderr)
        return 1
    # JAX's persistent compilation cache: the directory the environment
    # names (JAX_COMPILATION_CACHE_DIR) where it names one, otherwise the
    # fixed <checkout>/.jax_cache; never one set here. Only the environment's
    # cap on its size is lifted, and every program is kept however quickly it
    # compiled: the first run of a cell compiles, every later one finds each
    # program there. (Under the chip machine's cap of 192 MiB the 200 MB
    # train step was never kept and every run compiled it again, 195 s: my
    # chip runs, PR 27.)
    from mxnet_tpu.cache import enable_compile_cache
    note("compile cache: %s" % enable_compile_cache())
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    driver = load_module("drivers", cell["driver"])
    reference = load_module("reference", config["reference"])
    run = driver.Run(cell=cell, config=config, reference=reference,
                     seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), devices=devices,
                     t_process_start=T_PROCESS_START,
                     scratch=os.path.join(ROOT, ".bench_scratch"),
                     control=args.control)
    try:
        run.set_up()               # build, weights from the seed, warm-up
        note("set-up done")
        record = run.window()      # the measured window (and the trace)
        note("window closed")
        stats = [d.memory_stats() or {} for d in devices]
        record["memory_peak_bytes"] = max(
            int(s.get("peak_bytes_in_use", 0)) for s in stats)
        run.free()                 # the program's state leaves the device
        # a model is a cycle of blocks and parameters: only a collection
        # lets go of its arrays, and the reference needs their room
        gc.collect()
        note("the program's state is freed: %.2f GB in use on the device"
             % (max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
                    for d in devices) / 1e9))
        checks = run.check()       # the plain reference, after the window
        note("reference compared")
    finally:
        run.close()

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": record["memory_peak_bytes"]}
    record["device_kind"] = devices[0].device_kind
    if args.cpu_rehearsal:
        # the readers are walked against the first kind on record; what they
        # read is withheld below
        from lib import peaks
        record["device_kind"] = next(iter(peaks.PEAKS))
    record["chips"] = len(devices)
    # what the readers count with: the configuration's own module, beside
    # the driver's sizes and traffic
    record["counts"] = counts
    group = "per_layer" if args.trace else "end_to_end"
    if args.trace:
        device["busy_s"] = record["trace"].busy_s
        device["window_s"] = record["trace"].window_s
    values = read_metrics(metrics[group], record)
    if args.cpu_rehearsal:
        # the readers are walked, their CPU readings are withheld
        print("rehearsal: read and withheld %s; found nothing for %s"
              % (sorted(values),
                 sorted({m for m, _spec in metrics[group]} - set(values))),
              file=sys.stderr)
        device["platform"] = "rehearsal"
        values = {}
    compared = {k: {"value": v, "limit": lim}
                for k, (v, lim) in checks.items()}
    # a number that is not a number is not within its limit
    correct = bool(checks) and all(v <= lim for v, lim in checks.values())
    for k, (v, lim) in checks.items():
        print("compared %s %.6g limit %.6g %s"
              % (k, v, lim, "ok" if v <= lim else "FAIL"), file=sys.stderr)
    line = {"correct": correct, "attempted": int(record["attempted"]),
            "failed": int(record["failed"]), "metrics": values,
            "device": device}
    if args.trace and not args.cpu_rehearsal:
        line["breakdown"] = record["trace"].breakdown()
    if args.set or args.control:
        # a line made with an override or a control says so itself
        line["not_a_measurement"] = {"set": args.set,
                                     "control": args.control}
    line["compared"] = compared
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
