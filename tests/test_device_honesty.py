"""One honest way to find the device: no accelerator is never read as "use
the CPU instead", an id that does not exist never lands on another chip, and
the places that could give way in silence raise or say so."""
import os
import warnings

import jax
import pytest

import mxnet_tpu as mx
from mxnet_tpu import engine
from mxnet_tpu.base import MXNetError, is_tpu_backend

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("make", [mx.tpu, mx.gpu])
@pytest.mark.parametrize("device_id", [0, 1, 9])
def test_accelerator_context_raises_without_a_tpu(make, device_id):
    """Neither the CPU nor "the last device" stands in for a chip that is
    not there (8 virtual CPU devices exist in this process)."""
    ctx = make(device_id)
    with pytest.raises(MXNetError, match="does not exist"):
        ctx.jax_device()
    with pytest.raises(MXNetError):
        mx.nd.zeros((2,), ctx=ctx)


def test_no_accelerator_is_counted_on_the_cpu_backend():
    assert len(jax.devices()) == 8          # the virtual mesh is there
    assert mx.num_gpus() == 0 and mx.num_tpus() == 0
    assert mx.test_utils.list_gpus() == []
    assert not is_tpu_backend()
    assert mx.current_context() == mx.cpu(0)
    with pytest.raises(RuntimeError):
        mx.context.gpu_memory_info(0)


def test_cpu_context_ids_are_labels():
    assert mx.cpu(0).jax_device() == jax.devices()[0]
    assert mx.cpu(3).jax_device() == jax.devices()[3]
    assert mx.cpu(99).jax_device().platform == "cpu"


def test_compile_cache_helper_obeys_the_environment(monkeypatch, tmp_path):
    from mxnet_tpu.cache import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        # the variable is jax's to read: no directory is set in code
        assert jax.config.jax_compilation_cache_dir == before
        assert not os.path.exists(os.path.join(REPO, ".jax_cache", "x"))

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = os.path.join(REPO, ".jax_cache")
        assert enable_compile_cache() == fixed
        assert enable_compile_cache() == fixed      # never a moving name
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_failed_native_build_is_said_once_and_python_serves(monkeypatch,
                                                            tmp_path):
    (tmp_path / "Makefile").write_text(
        "all:\n\t@echo 'no compiler today' >&2; exit 1\n")
    monkeypatch.setattr(engine, "_lib_location",
                        lambda: (str(tmp_path), str(tmp_path / "libmxtpu.so")))
    monkeypatch.setattr(engine, "_build_attempted", False)
    with pytest.warns(RuntimeWarning, match="no compiler today"):
        so = engine.native_lib_path()
    assert not os.path.exists(so)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        engine.native_lib_path()                    # once per process
    monkeypatch.setattr(engine, "_lib", None)
    monkeypatch.setattr(engine, "_lib_tried", False)
    assert engine.host_engine_kind() == "python"
    eng = engine.NativeEngine(2)
    done = []
    eng.push(lambda: done.append(1))
    eng.wait_all()
    assert done == [1]


def test_native_helpers_build_from_the_committed_sources():
    """No .so is committed (.gitignore lists them); first use builds both
    from the .cc files."""
    import subprocess

    with open(os.path.join(REPO, ".gitignore")) as f:
        assert "src/engine_cc/*.so" in f.read().split()
    r = subprocess.run(["git", "ls-files", "src/engine_cc"], cwd=REPO,
                       capture_output=True, text=True)
    if r.returncode == 0 and r.stdout.strip():      # a git checkout
        assert not [f for f in r.stdout.split() if f.endswith(".so")]
    so = engine.native_lib_path()
    assert os.path.exists(so)
    assert len(mx.libinfo.find_lib_path()) == 2
