"""``counts/brumby.py`` (PR 35) against a hand-written sum at the published
sizes, and the new cell walked on the CPU at the tiny sizes."""
import json
import os

import numpy as np

import run

HERE = os.path.dirname(os.path.abspath(__file__))
brumby = run.load_module("counts", "brumby")
reference = run.load_module("reference", "brumby_14b")


def config():
    with open(os.path.join(HERE, "..", "configs", "brumby-14b.json")) as f:
        return json.load(f)


def test_brumby_counts_are_the_sums_the_issue_reckoned():
    cfg = config()
    sizes = cfg["sizes"]
    # every published width, and only the depth cut
    assert (sizes["units"], sizes["num_heads"], sizes["num_kv_heads"],
            sizes["head_dim"], sizes["hidden"], sizes["vocab_size"]) \
        == (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["vocab_size"]) \
        == (5120, 40, 8, 128, 17408, 151936)
    assert cfg["reduced"] == ["num_layers"] and sizes["num_layers"] == 5
    assert sizes["max_length"] == cfg["max_position_embeddings"]
    # a layer by hand: q and o 26.21 M each, k and v 10.49 M, the gate
    # 0.04 M, SwiGLU 267.39 M, four gains
    layer = 2 * 5120 * 5120 + 2 * 1024 * 5120 + 8 * 5120 \
        + 3 * 5120 * 17408 + 2 * 5120 + 2 * 128
    assert brumby.layer_params(sizes) == layer == 330352896       # 330.35 M
    held = 5 * layer + 2 * 151936 * 5120 + 5120
    assert brumby.params_held(sizes) == held                      # 3.207 B
    assert 3.207e9 < held < 3.208e9
    # ... which is every leaf of the reference, once
    assert held == sum(int(np.prod(s))
                       for _n, s in reference.param_specs(sizes))
    # a decode step reads the layers and the head, not the embedding
    assert brumby.decode_weight_bytes(sizes, 1) \
        == brumby.decode_weight_bytes(sizes, 26) \
        == 2 * (held - 151936 * 5120)                             # 4.86 GB
    # the state a stream holds: 8256 entries of phi by the 128 of v (and
    # one of z) a K/V head, float32, 5 layers; a token decoded reads and
    # writes it once, whatever its context: 338 MB of S and 2.6 MB of z
    assert brumby.state_entries(sizes) == 8256
    S, z = 5 * 8 * 8256 * 128 * 4, 5 * 8 * 8256 * 4
    assert brumby.state_bytes(sizes) == S + z
    assert brumby.kv_bytes(sizes, 1) == brumby.kv_bytes(sizes, 30000) \
        == brumby.retention_step_bytes(sizes, {}) == 2 * (S + z)
    assert round(2 * S / 1e6) == 338
    # the program's layout pads phi to 65 rows of 128: inside 9216
    from mxnet_tpu.ops import retention

    assert 8256 <= retention.phi_rows(128) * 128 <= 9216
    # FLOPs of a token: the matmuls twice their parameters, the state's
    # fold and read; the head once
    per = 2 * (layer - 2 * 5120 - 2 * 128) \
        + (3 * 8 + 2 * 40) * 8256 * 129
    assert brumby.forward_flops(sizes, 0, 7, heads=1) \
        == brumby.forward_flops(sizes, 900, 7, heads=1) \
        == 5 * 7 * per + 2 * 5120 * 151936


def test_the_new_cell_walks_on_the_cpu_at_the_tiny_sizes(capsys):
    assert run.main(["--workload", "brumby-14b.reasoning-decode", "--seed",
                     "2147483999", "--seconds", "6", "--trace", "1",
                     "--cpu-rehearsal"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 50
    assert line["device"]["platform"] == "rehearsal" and not line["metrics"]
