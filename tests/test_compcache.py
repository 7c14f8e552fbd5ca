"""mxnet_tpu.cache — persistent cross-process compilation layer.

Tier A (disk executable store): hit/miss/deserialize counters through the
base.jitted / bulk / tape funnels, GC cap eviction, corruption and
version-mismatch robustness (poisoned fixtures under
tests/fixtures/compcache/), concurrent two-process writers.

Tier B (AOT serving snapshots): round-trip parity ≤1e-6 incl. bf16, and
the zero-compile warm-start contract asserted FROM A FRESH SUBPROCESS —
``serve_compile_counter`` / ``decode_compile_counter`` read 0 from process
start to the first served request/token.
"""
import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import base, cache, engine, gluon, nd
from mxnet_tpu.cache.store import load_compiled_entry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXDIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "fixtures", "compcache")
FEAT = 16


@pytest.fixture
def store(tmp_path):
    """A fresh enabled store; always detached afterwards so the suite's
    default zero-overhead jit path is restored."""
    st = cache.configure(str(tmp_path / "compcache"))
    engine.comp_cache_hit_counter.reset()
    engine.comp_cache_miss_counter.reset()
    engine.comp_cache_deserialize_counter.reset()
    yield st
    cache.disable()


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def _mlp(hidden=24, classes=10):
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(hidden, activation="relu"))
        net.add(gluon.nn.Dense(classes))
    net.initialize()
    net(nd.array(np.zeros((1, FEAT), np.float32)))
    net.hybridize()
    return net


def _clear_inproc_jit_caches():
    """Forget every in-process compiled program so the next dispatch must
    consult the disk tier (the same state a fresh process starts in)."""
    from mxnet_tpu import ndarray as ndm
    base._JIT_CACHE.clear()
    base._BULK_CACHE.clear()
    base._TAPE_CACHE.clear()
    base._IR_CACHE.clear()  # canonical IR programs (mxnet_tpu.ir.lower)
    ndm._FAST_JIT.clear()


# ===================================================== Tier A: disk store

def test_jitted_disk_hit_skips_compile(store, rng):
    """Same op, fresh in-process caches: second acquisition is a disk HIT
    + deserialize, not a recompile — the cross-process warm-start path,
    exercised in-process by clearing the memory caches."""
    x = nd.array(rng.normal(size=(4, 4)).astype(np.float32))
    _clear_inproc_jit_caches()
    ref = (x * 2 + 1).asnumpy()
    assert engine.comp_cache_miss_counter.count >= 1
    assert store.writes >= 1

    _clear_inproc_jit_caches()
    h0, d0 = (engine.comp_cache_hit_counter.count,
              engine.comp_cache_deserialize_counter.count)
    engine.comp_cache_miss_counter.reset()
    out = (x * 2 + 1).asnumpy()
    np.testing.assert_allclose(out, ref, atol=0)
    assert engine.comp_cache_hit_counter.count > h0
    assert engine.comp_cache_deserialize_counter.count > d0
    assert engine.comp_cache_miss_counter.count == 0


def test_bulk_and_tape_tiers_populate(store, rng):
    """The bulk window's composed program and the compiled tape backward
    land in their own store tiers."""
    from mxnet_tpu import autograd

    a = nd.array(rng.normal(size=(8,)).astype(np.float32))
    with engine.bulk(8):
        y = ((a * 2 + 1) * a - 3) * 2 + a
        _ = y.asnumpy()
    assert store.scan()["tiers"]["bulk"]["entries"] >= 1

    w = nd.array(rng.normal(size=(8,)).astype(np.float32))
    w.attach_grad()
    with autograd.record():
        loss = ((w * w) * 2).sum()
    loss.backward()
    assert store.scan()["tiers"]["tape"]["entries"] >= 1


def test_hybrid_tier_populates(store, rng):
    """The hybrid-block compiled forward routes through the funnel too."""
    net = _mlp()
    net(nd.array(rng.normal(size=(2, FEAT)).astype(np.float32)))
    assert store.scan()["tiers"]["hybrid"]["entries"] >= 1


def test_gc_cap_evicts_oldest(tmp_path):
    """Over-cap inserts evict oldest-mtime entries first; the store never
    exceeds the cap by more than the newest entry."""
    st = cache.configure(str(tmp_path / "small"), cap_bytes=1)
    try:
        for i in range(4):
            fn = cache.AotFn(lambda x: x * (i + 1.0), tier="jit",
                             hint="gc%d" % i)
            fn(jnp.ones((4, 4 + i)))  # distinct program per i
        snap = st.scan()
        # cap of 1 byte: every insert evicts the previous population
        assert st.evictions >= 3
        assert snap["entries"] <= 1
    finally:
        cache.disable()


def test_corrupt_store_entry_recompiles_with_warning(store, rng):
    """Overwrite a live entry with garbage: next acquisition warns ONCE,
    recompiles, and removes the bad file — never a crash."""
    fn = cache.AotFn(lambda x: x * 3 + 1, tier="jit", hint="corrupt")
    x = jnp.asarray(rng.normal(size=(5,)).astype(np.float32))
    ref = np.asarray(fn(x))
    files = [os.path.join(r, n) for r, _, ns in os.walk(store.directory)
             for n in ns if n.endswith(".mxc")]
    assert files
    with open(files[0], "wb") as fh:
        fh.write(b"\x80\x05garbage-not-a-pickle")
    fn2 = cache.AotFn(lambda x: x * 3 + 1, tier="jit", hint="corrupt")
    with pytest.warns(RuntimeWarning, match="corrupt"):
        out = np.asarray(fn2(x))
    np.testing.assert_allclose(out, ref, atol=0)
    assert store.corrupt == 1
    # the bad file was dropped and the recompile re-persisted a VALID
    # entry at the same digest — the store self-heals
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        compiled, fail = load_compiled_entry(
            files[0], os.path.splitext(os.path.basename(files[0]))[0])
    assert compiled is not None and fail is None


@pytest.mark.parametrize("fixture,kind,match", [
    ("truncated.mxc", "corrupt", "corrupt"),
    ("wrong_key.mxc", "wrong_key", "key mismatch"),
    ("stale_jaxlib.mxc", "stale", "built by"),
])
def test_poisoned_fixture_falls_back(fixture, kind, match):
    """Committed poisoned entries (truncated write, wrong-key file, stale
    jax/jaxlib): each loads as None with one typed warning — the caller
    recompiles, never crashes."""
    path = os.path.join(FIXDIR, fixture)
    with pytest.warns(RuntimeWarning, match=match):
        compiled, fail = load_compiled_entry(path, "b4_d0")
    assert compiled is None
    assert fail == kind


def test_concurrent_two_process_writers(tmp_path):
    """Two processes hammer the SAME store dir concurrently (shared and
    private programs). The atomic-write discipline must leave every entry
    readable; a third consumer then gets clean hits."""
    d = str(tmp_path / "shared")
    child = r"""
import sys
import jax.numpy as jnp
from mxnet_tpu import cache
cache.configure(sys.argv[1])
who = int(sys.argv[2])
for i in range(6):
    shared = cache.AotFn(lambda x: x * 2 + 1, tier="jit", hint="s%d" % i)
    shared(jnp.ones((3, 3 + i)))                    # same program both
    mine = cache.AotFn(lambda x: x * (who + 3.0), tier="bulk",
                       hint="p%d" % i)
    mine(jnp.ones((2, 2 + i)))                      # per-process program
print("OK")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    procs = [subprocess.Popen([sys.executable, "-c", child, d, str(w)],
                              stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, env=env, cwd=REPO,
                              text=True)
             for w in range(2)]
    for p in procs:
        out, err = p.communicate(timeout=600)
        assert p.returncode == 0 and "OK" in out, err
    # every entry on disk deserializes cleanly
    files = [os.path.join(r, n) for r, _, ns in os.walk(d)
             for n in ns if n.endswith(".mxc")]
    assert len(files) >= 6
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # any robustness warning = corruption
        for f in files:
            compiled, fail = load_compiled_entry(
                f, os.path.splitext(os.path.basename(f))[0])
            assert compiled is not None and fail is None, f
    # third consumer: the shared programs are pure disk hits
    st = cache.configure(d)
    try:
        engine.comp_cache_hit_counter.reset()
        engine.comp_cache_miss_counter.reset()
        fn = cache.AotFn(lambda x: x * 2 + 1, tier="jit", hint="s0")
        fn(jnp.ones((3, 3)))
        assert engine.comp_cache_hit_counter.count == 1
        assert engine.comp_cache_miss_counter.count == 0
    finally:
        cache.disable()


# ============================================ Tier B: serving snapshots

def _snapshot_server(net, tmp_path, buckets=(1, 2, 4)):
    srv = mx.serve.ModelServer(net, [((FEAT,), "float32")], buckets=buckets,
                               max_wait_ms=0.5, timeout_ms=10000.0)
    prefix = str(tmp_path / "snap")
    srv.snapshot(prefix)
    return srv, prefix


def test_snapshot_roundtrip_parity(rng, tmp_path):
    """snapshot → load(snapshot=True): identical outputs (≤1e-6) with ZERO
    serve compiles on the loaded side (in-process form; the subprocess
    test below proves the from-process-start contract)."""
    net = _mlp()
    srv, prefix = _snapshot_server(net, tmp_path)
    x = rng.normal(size=(3, FEAT)).astype(np.float32)
    with srv:
        ref = srv.predict(x)
    engine.serve_compile_counter.reset()
    srv2 = mx.serve.load(prefix, snapshot=True, max_wait_ms=0.5,
                         timeout_ms=10000.0)
    with srv2:
        out = srv2.predict(x)
    np.testing.assert_allclose(out, ref, atol=1e-6)
    assert engine.serve_compile_counter.count == 0


def test_snapshot_roundtrip_parity_bf16(rng, tmp_path):
    """bf16-cast model: the artifact's params npz is dtype-exact and the
    deserialized executables carry the bf16 signatures — reload neither
    upcasts nor recompiles."""
    net = _mlp()
    net.cast("bfloat16")
    srv, prefix = _snapshot_server(net, tmp_path, buckets=(2, 4))
    x = rng.normal(size=(2, FEAT)).astype(np.float32)
    with srv:
        ref = np.asarray(srv.predict(x), np.float32)
    engine.serve_compile_counter.reset()
    srv2 = mx.serve.load(prefix, snapshot=True, max_wait_ms=0.5,
                         timeout_ms=10000.0)
    for p in srv2.model.collect_params().values():
        assert np.dtype(p.data().dtype).name == "bfloat16"
    with srv2:
        out = np.asarray(srv2.predict(x), np.float32)
    np.testing.assert_allclose(out, ref, atol=1e-6)
    assert engine.serve_compile_counter.count == 0


def test_snapshot_zero_compile_warm_start_subprocess(rng, tmp_path):
    """THE acceptance check: a fresh process loads the snapshot and serves
    its first request with serve_compile_counter at 0 FROM PROCESS START
    (nothing in-process can leak in), with output parity vs the exporting
    process."""
    net = _mlp()
    srv, prefix = _snapshot_server(net, tmp_path)
    x = rng.normal(size=(3, FEAT)).astype(np.float32)
    with srv:
        ref = srv.predict(x)
    np.save(str(tmp_path / "x.npy"), x)
    child = r"""
import json, sys
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import engine
x = np.load(sys.argv[2])
srv = mx.serve.load(sys.argv[1], snapshot=True, max_wait_ms=0.5,
                    timeout_ms=10000.0)
with srv:
    out = srv.predict(x)
print(json.dumps({"serve_compiles": engine.serve_compile_counter.count,
                  "decode_compiles": engine.decode_compile_counter.count,
                  "out": np.asarray(out).ravel().tolist()}))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    r = subprocess.run([sys.executable, "-c", child, prefix,
                        str(tmp_path / "x.npy")],
                       capture_output=True, text=True, env=env, cwd=REPO,
                       timeout=600)
    assert r.returncode == 0, r.stderr
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["serve_compiles"] == 0, \
        "warm replica traced %d programs" % rec["serve_compiles"]
    assert rec["decode_compiles"] == 0
    np.testing.assert_allclose(np.asarray(rec["out"]).reshape(ref.shape),
                               ref, atol=1e-6)


def test_generative_snapshot_zero_compile_subprocess(tmp_path):
    """GenerativeServer snapshot: a fresh process reaches its first
    generated tokens with decode_compile_counter at 0 from process start
    (prefill/decode/inject/extract all deserialized), exact token parity."""
    from mxnet_tpu.models.gpt import gpt_nano

    m = gpt_nano()
    m.initialize()
    m.hybridize()
    srv = mx.serve.GenerativeServer(m, slots=4, timeout_ms=60000.0)
    srv.warmup(prompt_buckets=(4,), max_tokens=16)
    with srv:
        ref = srv.generate([1, 2, 3], max_new_tokens=6)
    prefix = str(tmp_path / "gsnap")
    srv.snapshot(prefix)
    child = r"""
import json, sys
import mxnet_tpu as mx
from mxnet_tpu import engine
from mxnet_tpu.models.gpt import gpt_nano
srv = mx.serve.load(sys.argv[1], snapshot=True, model=gpt_nano(),
                    timeout_ms=60000.0)
with srv:
    toks = srv.generate([1, 2, 3], max_new_tokens=6)
print(json.dumps({"decode_compiles": engine.decode_compile_counter.count,
                  "serve_compiles": engine.serve_compile_counter.count,
                  "tokens": toks}))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    r = subprocess.run([sys.executable, "-c", child, prefix],
                       capture_output=True, text=True, env=env, cwd=REPO,
                       timeout=600)
    assert r.returncode == 0, r.stderr
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["decode_compiles"] == 0, \
        "warm generative replica traced %d programs" % rec["decode_compiles"]
    assert rec["tokens"] == ref


def test_snapshot_corrupt_exec_falls_back(rng, tmp_path):
    """A truncated executable inside the artifact: load warns, that bucket
    recompiles lazily, results stay correct — degraded, never down."""
    net = _mlp()
    srv, prefix = _snapshot_server(net, tmp_path, buckets=(2, 4))
    x = rng.normal(size=(2, FEAT)).astype(np.float32)
    with srv:
        ref = srv.predict(x)
    victim = os.path.join(prefix + "-exec", "b2_d0.mxc")
    with open(os.path.join(FIXDIR, "truncated.mxc"), "rb") as fh:
        poison = fh.read()
    with open(victim, "wb") as fh:
        fh.write(poison)
    engine.serve_compile_counter.reset()
    with pytest.warns(RuntimeWarning, match="corrupt"):
        srv2 = mx.serve.load(prefix, snapshot=True, max_wait_ms=0.5,
                             timeout_ms=10000.0)
    with srv2:
        out = srv2.predict(x)
    np.testing.assert_allclose(out, ref, atol=1e-6)
    assert engine.serve_compile_counter.count == 1  # only the bad bucket


def test_snapshot_stale_fingerprint_falls_back(rng, tmp_path):
    """A manifest from a different jax/jaxlib: one warning, checkpoint +
    config still load, every program recompiles (full warmup path)."""
    net = _mlp()
    srv, prefix = _snapshot_server(net, tmp_path, buckets=(2,))
    x = rng.normal(size=(2, FEAT)).astype(np.float32)
    with srv:
        ref = srv.predict(x)
    mpath = prefix + "-snapshot.json"
    with open(mpath) as fh:
        manifest = json.load(fh)
    manifest["fingerprint"] = "mxc1|jax=0.0.0|jaxlib=0.0.0|cpu"
    with open(mpath, "w") as fh:
        json.dump(manifest, fh)
    engine.serve_compile_counter.reset()
    with pytest.warns(RuntimeWarning, match="built by"):
        srv2 = mx.serve.load(prefix, snapshot=True, max_wait_ms=0.5,
                             timeout_ms=10000.0)
    with srv2:
        out = srv2.predict(x)
    np.testing.assert_allclose(out, ref, atol=1e-6)
    assert engine.serve_compile_counter.count >= 1  # honest recompile


def test_snapshot_of_an_older_format_is_refused(tmp_path):
    """A manifest of another format (format 1: executables over lists of K
    and V buffers) is refused at the manifest, never loaded against the
    argument tree of this build's programs."""
    from mxnet_tpu.cache.snapshot import FORMAT

    _srv, prefix = _snapshot_server(_mlp(), tmp_path, buckets=(2,))
    mpath = prefix + "-snapshot.json"
    with open(mpath) as fh:
        manifest = json.load(fh)
    assert manifest["format"] == FORMAT
    manifest["format"] = FORMAT - 1
    with open(mpath, "w") as fh:
        json.dump(manifest, fh)
    with pytest.raises(ValueError, match="this build reads %d" % FORMAT):
        mx.serve.load(prefix, snapshot=True)


def test_snapshot_wrong_key_exec_falls_back(rng, tmp_path):
    """An exec file whose internal key disagrees with the manifest slot
    (mis-assembled artifact): that entry is rejected with a warning and
    recompiles; the rest of the snapshot stays warm."""
    net = _mlp()
    srv, prefix = _snapshot_server(net, tmp_path, buckets=(2, 4))
    x = rng.normal(size=(2, FEAT)).astype(np.float32)
    with srv:
        ref = srv.predict(x)
    # swap b2's file for b4's content: structurally valid, wrong key
    b2 = os.path.join(prefix + "-exec", "b2_d0.mxc")
    b4 = os.path.join(prefix + "-exec", "b4_d0.mxc")
    with open(b4, "rb") as fh:
        content = fh.read()
    with open(b2, "wb") as fh:
        fh.write(content)
    engine.serve_compile_counter.reset()
    with pytest.warns(RuntimeWarning, match="key mismatch"):
        srv2 = mx.serve.load(prefix, snapshot=True, max_wait_ms=0.5,
                             timeout_ms=10000.0)
    with srv2:
        out = srv2.predict(x)
    np.testing.assert_allclose(out, ref, atol=1e-6)
    assert engine.serve_compile_counter.count == 1


@pytest.mark.slow
def test_coldstart_bench_subprocess(tmp_path):
    """The shipped coldstart bench meets the ≥5× acceptance bar."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "serve_bench.py"),
         "--quick", "--mode", "coldstart",
         "--prefix", str(tmp_path / "cs")],
        capture_output=True, text=True, timeout=1200, env=env, cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["warm_serve_compiles"] == 0
    assert rec["speedup"] >= 5.0, rec
