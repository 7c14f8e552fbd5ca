"""bench.py's honesty about the device + the flash block-table artifact.

bench.py measures on a TPU: without one it fails unless ``--cpu`` asks for a
smoke run, and a CPU record never carries a device metric's name or an MFU.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bench(*argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run([sys.executable, os.path.join(REPO, "bench.py")]
                          + list(argv), capture_output=True, text=True,
                          timeout=600, env=env, cwd=REPO)


@pytest.mark.parametrize("mode", ["bert", "all"])
def test_bench_without_a_chip_fails_and_prints_no_metric(mode):
    r = _bench(mode)
    assert r.returncode != 0
    assert "found platform 'cpu'" in r.stderr
    assert not [l for l in r.stdout.splitlines() if l.startswith("{")]


def test_bench_cpu_smoke_record_is_not_a_device_metric():
    r = _bench("bert", "--cpu", "--smoke", "--iters=1")
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["metric"].startswith("cpu_smoke/")
    assert "mfu" not in rec and "vs_baseline" not in rec
    assert (rec["platform"], rec["device_kind"]) == ("cpu", "cpu")
    assert rec["device_count"] >= 1


def test_bench_fleet_without_cpu_refuses_before_it_spawns_a_worker():
    """Two workers cannot share what this host has (no TPU at all here): the
    bench says why and starts nothing."""
    r = _bench("fleet")
    assert r.returncode != 0
    assert "chip" in r.stderr and "--cpu" in r.stderr
    assert "WorkerGone" not in r.stderr and not r.stdout.strip()


def test_peaks_table_is_keyed_by_device_kind_and_refuses_unknown_kinds():
    sys.path.insert(0, REPO)
    import bench

    assert bench._peak_bf16_flops("TPU v5 lite") == 197e12
    assert all(p["source"] for p in bench.PEAKS.values())
    with pytest.raises(SystemExit, match="no peak"):
        bench._peak_bf16_flops("TPU v99")
    with pytest.raises(SystemExit, match="no peak"):
        bench._peak_bf16_flops("cpu")


def test_flash_block_artifact_roundtrip(tmp_path):
    """apply_winners picks min-fwd_bwd_ms per seq; the loader installs the
    table and the bucket scan serves the nearest lower bound."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import importlib

    from mxnet_tpu.ops.pallas import flash_attention as fa
    fs = importlib.import_module("flash_sweep")

    rows = [
        {"seq": 128, "kernel": "dense", "fwd_bwd_ms": 1.0},
        {"seq": 128, "kernel": "flash", "block_q": 128, "block_k": 128,
         "fwd_bwd_ms": 1.5},  # flash LOSES at 128
        {"seq": 512, "kernel": "dense", "fwd_bwd_ms": 9.0},
        {"seq": 512, "kernel": "flash", "block_q": 256, "block_k": 512,
         "fwd_bwd_ms": 5.0},
        {"seq": 512, "kernel": "flash", "block_q": 512, "block_k": 256,
         "fwd_bwd_ms": 4.0},
        {"seq": 2048, "kernel": "flash", "block_q": 128, "block_k": 512,
         "fwd_bwd_ms": 40.0},
    ]
    saved_path, saved_table = fa._BLOCKS_ARTIFACT, dict(fa.BLOCK_DEFAULTS)
    saved_min = fa.MIN_LEN
    try:
        fa._BLOCKS_ARTIFACT = str(tmp_path / "flash_blocks.json")
        assert fs.apply_winners(rows, source="unit") == 0
        assert fa._load_block_artifact()
        assert fa.BLOCK_DEFAULTS[512] == (512, 256)
        assert fa.BLOCK_DEFAULTS[2048] == (128, 512)
        assert fa.BLOCK_DEFAULTS[0] == (128, 128)  # smallest seq = catch-all
        assert fa._default_blocks(768) == (512, 256)
        assert fa._default_blocks(4096) == (128, 512)
        # measured crossover: flash lost at 128, won at 512 → the gate's
        # min length becomes 512, overriding attention's static guess
        assert fa.MIN_LEN == 512
        from mxnet_tpu.ops import attention as A
        assert A._flash_min_len() == 512
        # flash winning at no consistent suffix (loses at the largest
        # compared seq) → min_len NOT written; reload resets the stale one
        bad = [{"seq": 512, "kernel": "dense", "fwd_bwd_ms": 1.0},
               {"seq": 512, "kernel": "flash", "block_q": 256,
                "block_k": 512, "fwd_bwd_ms": 2.0}]
        assert fs.apply_winners(bad, source="unit") == 0
        assert fa._load_block_artifact()
        assert fa.MIN_LEN is None
        assert A._flash_min_len() == A._FLASH_MIN_LEN
        # malformed artifact leaves the installed table untouched — but
        # LOUDLY: a corrupted --apply output must not silently
        # revert benches to the untuned table
        (tmp_path / "flash_blocks.json").write_text("{broken")
        with pytest.warns(UserWarning, match="malformed"):
            assert not fa._load_block_artifact()
        assert fa.BLOCK_DEFAULTS[512] == (256, 512)  # last good table kept
        # an EXPLICIT path raises instead of warning: the caller asked for
        # that specific file
        with pytest.raises(ValueError, match="malformed"):
            fa._load_block_artifact(str(tmp_path / "flash_blocks.json"))
        with pytest.raises(FileNotFoundError):
            fa._load_block_artifact(str(tmp_path / "nope.json"))
    finally:
        fa._BLOCKS_ARTIFACT = saved_path
        fa.BLOCK_DEFAULTS = saved_table
        fa.MIN_LEN = saved_min


def test_shipped_flash_blocks_artifact_loads():
    """The in-repo artifact (interim since r5) must parse and carry the
    bench-evidenced gate: min_len 1024 keeps bert512 on the MEASURED-faster
    dense path until the corrected sweep overwrites the file. A corrupted
    commit here silently changes production attention routing."""
    from mxnet_tpu.ops.pallas import flash_attention as fa
    with open(fa._BLOCKS_ARTIFACT) as f:
        art = json.load(f)
    assert "0" in art["blocks"]  # catch-all bucket always present
    assert fa.MIN_LEN == art.get("min_len")
    assert fa.BLOCK_DEFAULTS[0] == tuple(art["blocks"]["0"])


def test_apply_winners_no_flash_rows_is_noop(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import importlib

    from mxnet_tpu.ops.pallas import flash_attention as fa
    fs = importlib.import_module("flash_sweep")
    saved_path = fa._BLOCKS_ARTIFACT
    try:
        fa._BLOCKS_ARTIFACT = str(tmp_path / "flash_blocks.json")
        assert fs.apply_winners([{"seq": 512, "kernel": "dense",
                                  "fwd_bwd_ms": 9.0}], source="unit") == 1
        assert not os.path.exists(fa._BLOCKS_ARTIFACT)
    finally:
        fa._BLOCKS_ARTIFACT = saved_path
