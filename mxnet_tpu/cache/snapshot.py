"""Tier B — AOT serving snapshots (``serve.snapshot`` / ``serve.load``).

The TVM ``export_library`` idea (arXiv 1802.04799) applied to a whole
server: one artifact bundles

* the checkpoint (``checkpoint.save_for_serving`` layout for ModelServer;
  ``save_parameters`` for a generative model),
* the serving config (buckets + input specs, or slots/top_k/eos/capacity
  + warmed prompt buckets),
* the **serialized executables** of every warmed program — bucket
  dispatches for ModelServer; prefill/decode/inject/extract buckets for
  GenerativeServer.

``serve.load(prefix, snapshot=True)`` rebuilds the server by
*deserializing* those executables: no trace, no XLA compile —
``engine.serve_compile_counter`` / ``decode_compile_counter`` read 0 from
process start to the first served request. That is the horizontal-
autoscale story: a new replica is warm in seconds (process spawn + param
load + executable deserialize), not compile-minutes.

Robustness (never a crash): a truncated, stale-jaxlib, or wrong-key entry
is skipped with ONE warning and that program falls back to a lazy
recompile; a manifest from a different jax/jaxlib/backend loads params
and config but no executables (full warmup path).

Layout, for ``prefix = "export/m"``::

    m-snapshot.json     manifest (config + executable index)
    m-symbol.json       ModelServer: exported graph
    m-0000.params       checkpoint (dtype-exact npz)
    m-exec/<key>.mxc    one serialized executable per warmed program
"""
from __future__ import annotations

import json
import os
import warnings

import numpy as np

from .store import (CompCacheStore, fingerprint, load_compiled_entry,
                    pack_entry, serialize_compiled)

# 2: the generative programs take the cache's state as one pytree of page
# records (serve/kv_cache.py), not lists of K and V buffers; an executable
# of format 1 would be called with the wrong argument tree
# 3: the generative programs no longer donate the per-slot input tokens (the
# decode loop reads a step's tokens after they have gone into the next
# program); an executable of format 2 would delete them under the host
FORMAT = 3


def _warn(msg):
    warnings.warn(msg, RuntimeWarning, stacklevel=3)


def _exec_dir(prefix):
    return prefix + "-exec"


def _manifest_path(prefix):
    return prefix + "-snapshot.json"


def _write_exec(prefix, key, compiled):
    """Serialize one executable into the artifact; returns the manifest
    file entry or None when the backend can't serialize (the manifest
    then simply lists fewer programs — load warms those lazily)."""
    packed = serialize_compiled(compiled)
    if packed is None:
        _warn("executable %r could not be serialized on this backend — "
              "snapshot will recompile it on load" % key)
        return None
    fname = key.replace("@", "_") + ".mxc"
    path = os.path.join(_exec_dir(prefix), fname)
    CompCacheStore.atomic_write(path, pack_entry(key, *packed))
    return {"file": os.path.join(os.path.basename(_exec_dir(prefix)),
                                 fname),
            "bytes": os.path.getsize(path)}


def _read_exec(prefix, entry, key):
    path = os.path.join(os.path.dirname(prefix) or ".", entry["file"])
    compiled, _fail = load_compiled_entry(path, key,
                                          origin="snapshot executable")
    return compiled


# ---------------------------------------------------------------- saving

def save_snapshot(server, prefix, input_names=None, epoch=0):
    """Write the AOT serving artifact for a ModelServer or
    GenerativeServer. Returns the manifest path."""
    from ..serve.decoder import GenerativeServer
    from ..serve.server import ModelServer

    os.makedirs(os.path.dirname(os.path.abspath(prefix)) or ".",
                exist_ok=True)
    if isinstance(server, ModelServer):
        manifest = _save_model_snapshot(server, prefix, input_names, epoch)
    elif isinstance(server, GenerativeServer):
        manifest = _save_generative_snapshot(server, prefix, epoch)
    else:
        raise TypeError("serve.snapshot takes a ModelServer or "
                        "GenerativeServer, got %r" % type(server).__name__)
    manifest.update(format=FORMAT, fingerprint=fingerprint(),
                    name=server.name, epoch=int(epoch))
    path = _manifest_path(prefix)
    CompCacheStore.atomic_write(
        path, (json.dumps(manifest, indent=1) + "\n").encode())
    return path


def _save_model_snapshot(server, prefix, input_names, epoch):
    from ..checkpoint import save_for_serving
    from ..gluon.block import SymbolBlock

    model = server.model
    if input_names is None:
        input_names = ([s.name for s in model._inputs]
                       if isinstance(model, SymbolBlock) else ("data",))
    input_names = list(input_names)
    save_for_serving(prefix, model, epoch=epoch, input_names=input_names)
    specs = [[list(shape), str(np.dtype(dt))] for shape, dt in server._specs]
    entries = server._pool.export_executables(server._specs, server.buckets)
    if not entries:
        _warn("snapshot of %r has no warmed bucket executables — did "
              "warmup run? load will compile everything" % server.name)
    execs = {}
    for e in entries:
        fe = _write_exec(prefix, e["key"], e["compiled"])
        if fe is not None:
            fe.update(bucket=e["bucket"], donating=e["donating"])
            execs[e["key"]] = fe
    # informational for model snapshots: the exported graph already bakes
    # the quantized ops in, so load never needs to re-apply it
    return {"kind": "model", "input_names": input_names,
            "input_specs": specs, "buckets": list(server.buckets),
            "quantize": getattr(server, "quantize", None),
            "pool_state": server._pool.export_state(),
            "executables": execs}


def _save_generative_snapshot(server, prefix, epoch):
    params_file = "%s-%04d.params" % (prefix, epoch)
    server.model.save_parameters(params_file)
    entries = server.export_executables()
    if not entries:
        _warn("snapshot of %r has no compiled decode programs — did "
              "warmup run? load will compile everything" % server.name)
    execs = {}
    for e in entries:
        fe = _write_exec(prefix, e["key"], e["compiled"])
        if fe is not None:
            fe.update(kind=e["kind"], tp=e["tp"], capacity=e["capacity"])
            execs[e["key"]] = fe
    return {"kind": "generative", "slots": server.slots,
            "top_k": server.top_k, "eos_id": server.eos_id,
            "capacity": int(server.cache.capacity),
            "prefix_cache": server.prefix is not None,
            "quantize": server._quantize,
            # speculative/chunked-prefill config: spec_k and prefill_chunk
            # are part of the compiled-program keys (window width / chunk
            # length) so load must rebuild the server with the same values;
            # the draft itself is CODE (like the model) and is passed to
            # load via draft= — "draft" here is informational
            "spec_k": server.spec_k,
            "prefill_chunk": server._prefill_chunk,
            "draft": (type(server._draft).__name__
                      if server._draft is not None else None),
            "prompt_buckets": sorted({tp for tp, _ in server._prefill_fns}),
            "executables": execs}


# --------------------------------------------------------------- loading

def load_manifest(prefix):
    with open(_manifest_path(prefix)) as fh:
        m = json.load(fh)
    if m.get("format") != FORMAT:
        raise ValueError("snapshot %r has format %r, this build reads %d"
                         % (prefix, m.get("format"), FORMAT))
    return m


def load_snapshot(prefix, model=None, **server_kwargs):
    """Rebuild a server from a snapshot artifact. ``model`` is required
    for generative snapshots (the decode protocol lives in code; params
    are loaded from the artifact). Extra kwargs go to the server
    constructor (queue/deadline knobs — they are process policy, not part
    of the artifact)."""
    manifest = load_manifest(prefix)
    fp = fingerprint()
    use_execs = manifest.get("fingerprint") == fp
    if not use_execs:
        _warn("snapshot %r was built by %r but this process is %r — "
              "loading checkpoint/config only, programs will recompile"
              % (prefix, manifest.get("fingerprint"), fp))
    if manifest["kind"] == "model":
        return _load_model_snapshot(prefix, manifest, use_execs,
                                    server_kwargs)
    if manifest["kind"] == "generative":
        return _load_generative_snapshot(prefix, manifest, model,
                                         use_execs, server_kwargs)
    raise ValueError("unknown snapshot kind %r" % manifest["kind"])


def _load_model_snapshot(prefix, manifest, use_execs, server_kwargs):
    from ..checkpoint import load_for_serving
    from ..serve.server import ModelServer

    block = load_for_serving(prefix, epoch=manifest.get("epoch", 0),
                             input_names=manifest["input_names"])
    specs = [(tuple(shape), dt) for shape, dt in manifest["input_specs"]]
    server_kwargs.setdefault("buckets", tuple(manifest["buckets"]))
    srv = ModelServer(block, specs, warmup=not use_execs, **server_kwargs)
    if not use_execs:
        return srv
    srv._pool.restore_state(manifest.get("pool_state") or {})
    entries = []
    for key, fe in sorted(manifest.get("executables", {}).items()):
        compiled = _read_exec(prefix, fe, key)
        if compiled is not None:
            entries.append({"bucket": fe["bucket"],
                            "donating": fe["donating"],
                            "compiled": compiled})
    srv._pool.preload_executables(entries, srv._specs)
    if not srv._pool.row_aligned:
        # incomplete artifact (hand-edited manifest?): fall back to the
        # proving warmup rather than serve with unknown output layout
        _warn("snapshot %r carried no pool state — running warmup" % prefix)
        srv.warmup()
    return srv


def _load_generative_snapshot(prefix, manifest, model, use_execs,
                              server_kwargs):
    from ..serve.decoder import GenerativeServer

    if model is None:
        raise TypeError(
            "generative snapshots need the model instance: "
            "serve.load(prefix, snapshot=True, model=my_model) — the "
            "decode protocol is code; only params/config/executables are "
            "in the artifact")
    quantize = manifest.get("quantize") or server_kwargs.pop("quantize",
                                                             None)
    if quantize:
        # the checkpoint holds the QUANTIZED parameter tree (qweight/
        # w_scale under structural names) — swap the layers first so
        # load_parameters finds matching slots, then load bit-exact (the
        # server ctor's re-quantize is an idempotent no-op on swapped
        # layers)
        from ..quantization import quantize_model

        params = model.collect_params()
        if any(p._data is None and p._deferred_init is None
               for p in params.values()):
            # bare skeleton (the usual serve.load(model=gpt_nano()) call):
            # QuantizedDense derives qweight from a materialized fp32
            # weight at swap time, so give the skeleton throwaway values —
            # load_parameters overwrites every slot bit-exactly below
            model.initialize()
        quantize_model(model, mode=quantize)
    model.load_parameters("%s-%04d.params" % (prefix,
                                              manifest.get("epoch", 0)))
    # window width / chunk length are baked into the exported programs —
    # rebuild with the artifact's values unless the caller overrides (the
    # override then recompiles, with AotFn's one-warning recovery)
    server_kwargs.setdefault("spec_k", manifest.get("spec_k", 4))
    server_kwargs.setdefault("prefill_chunk",
                             manifest.get("prefill_chunk"))
    srv = GenerativeServer(model, slots=manifest["slots"],
                           top_k=manifest["top_k"],
                           eos_id=manifest["eos_id"],
                           prefix_cache=manifest.get("prefix_cache", True),
                           quantize=quantize,
                           **server_kwargs)
    if manifest.get("draft") and srv._draft is None:
        _warn("snapshot %r was built with a %s draft but load got no "
              "draft= — speculative programs in the artifact are skipped "
              "and the server decodes plain" % (prefix, manifest["draft"]))
    # allocate the cache at the snapshot's capacity bucket up front — a
    # fresh zero alloc, NOT a migration dispatch — so the preloaded
    # programs (all specialized to this capacity) match from token one
    if manifest.get("capacity"):
        srv.cache.ensure_capacity(manifest["capacity"])
    if not use_execs:
        return srv
    for key, fe in sorted(manifest.get("executables", {}).items()):
        if fe["kind"] in ("verify", "draftstep", "draftfill") \
                and srv._draft is None:
            continue   # warned above: no draft, plain decode only
        if fe["kind"] == "chunk" and srv._prefill_chunk is None:
            continue   # chunking disabled by a caller override
        compiled = _read_exec(prefix, fe, key)
        if compiled is not None:
            srv.preload_executable(fe["kind"], fe["tp"], fe["capacity"],
                                   compiled)
    return srv
