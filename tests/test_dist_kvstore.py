"""DistKVStore cross-host semantics via a real two-process jax.distributed
run on CPU (the DCN path; ref: tests/nightly/dist_sync_kvstore.py).

Each worker pushes rank+1; push semantics are a SUM, so both workers must
pull back 1+2=3 (a mean — the round-1 bug — would read 1.5)."""
import os
import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest

_WORKER = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    rank = int(sys.argv[1])
    jax.distributed.initialize(coordinator_address=sys.argv[2],
                               num_processes=2, process_id=rank)
    sys.path.insert(0, sys.argv[3])
    import numpy as np
    from mxnet_tpu import nd
    from mxnet_tpu.kvstore import DistKVStore

    kv = DistKVStore("dist_sync")
    kv.init("w", nd.array(np.zeros(4, np.float32)))
    kv.push("w", nd.array(np.full(4, float(rank + 1), np.float32)))
    out = kv.pull("w").asnumpy()
    np.testing.assert_allclose(out, np.full(4, 3.0))   # sum, not mean
    print("RANK%d_OK" % rank, flush=True)
""")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_dist_kvstore_push_sums_across_processes(tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    coord = "127.0.0.1:%d" % _free_port()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = [subprocess.Popen([sys.executable, str(script), str(r), coord,
                               repo],
                              stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, env=env, text=True)
             for r in range(2)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out)
    finally:
        for p in procs:
            p.kill()
    for r, (p, out) in enumerate(zip(procs, outs)):
        # capability gate (tracking: tier-1 straggler since PR 1): this
        # jaxlib's CPU backend refuses cross-process collectives outright
        # ("Multiprocess computations aren't implemented on the CPU
        # backend") — the DCN path can only be exercised on real multi-host
        # hardware, so the missing capability is a SKIP, not a failure.
        lowered = out.lower()
        if p.returncode != 0 and (
                ("distributed" in lowered and "unimplemented" in lowered)
                or "aren't implemented on the cpu backend" in lowered
                or "multiprocess computations" in lowered):
            pytest.skip("jax CPU cross-process collectives unavailable: %s"
                        % out.splitlines()[-1])
        assert p.returncode == 0, "rank %d failed:\n%s" % (r, out)
        assert "RANK%d_OK" % r in out
