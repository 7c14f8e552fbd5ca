"""A builder's tool for the chip: ``lib/weights.py: make`` at a size no
configuration of the benchmark has yet.

    python3 benchmark/tests/weights_at_size.py

Makes the weights of a spec list of 4.7 B parameters (one period of four
layers of a sparse-expert decoder cut to one chip: leaves up to
``(16, 4096, 4096)``, 45 leaves) from a seed above 2**31, then again from
another seed (nothing compiles the second time), and prints the seconds each
took, the bytes in use and the process's peak; then the two configurations
the benchmark has, three seeds each. The last line of standard output is one
JSON object with every reading. Never part of a measurement.
"""
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import run  # noqa: E402
from lib import weights  # noqa: E402

EXPERTS, WIDTH, VOCAB = 16, 4096, 32768


def big_specs():
    specs = [("embed_weight", (VOCAB, WIDTH))]
    for i in range(4):
        p = "layer%d_" % i
        specs += [(p + "q_weight", (4 * WIDTH, WIDTH)),
                  (p + "kv_weight", (2048, WIDTH)),
                  (p + "o_weight", (WIDTH, 4 * WIDTH)),
                  (p + "ln_gamma", (WIDTH,)),
                  (p + "router_weight", (128, WIDTH))]
        specs += [(p + kind + part, (n, WIDTH, WIDTH))
                  for kind, n in (("shared_", 4), ("expert_", EXPERTS))
                  for part in ("gate", "up", "down")]
    return specs


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("weights_at_size: needs a TPU, jax reports %r" % dev.platform,
              file=sys.stderr)
        return 1
    out = {"device": dev.device_kind}

    def timed(label, seed, specs):
        t0 = time.perf_counter()
        w = weights.make(seed, specs, jnp.bfloat16)
        jax.block_until_ready(w)
        took = time.perf_counter() - t0
        stats = dev.memory_stats()
        out[label] = {
            "seconds": took, "leaves": len(w),
            "parameters": sum(int(np.prod(v.shape)) for v in w.values()),
            "in_use_bytes": stats["bytes_in_use"],
            "peak_bytes": stats["peak_bytes_in_use"]}
        print(label, json.dumps(out[label]), flush=True)
        return w

    # the small ones first: a process's peak only ever rises
    for config in ("gpt2-large", "bert-large"):
        c = run.load_json("configs", config)
        specs = run.load_module("reference",
                                c["reference"]).param_specs(c["sizes"])
        for rep in range(3):
            timed("%s seed %d" % (config, 1234 + rep), 1234 + rep, specs)
    w = timed("4.7B first seed", 3000000001, big_specs())
    far = np.asarray(w["layer3_expert_down"][15, :64].astype(jnp.float32))
    out["std_of_a_far_corner"] = float(far.std())
    out["mean_of_a_gain"] = float(jnp.mean(
        w["layer0_ln_gamma"].astype(jnp.float32)))
    del w
    timed("4.7B second seed", 3000000002, big_specs())
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
