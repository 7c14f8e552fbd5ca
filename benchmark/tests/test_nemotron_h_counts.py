"""``counts/nemotron_h.py`` and the ``nemotron-3-super`` configuration
against a hand-written sum at the published widths, the two metrics of the
``ssd_step`` kernel on a hand-built trace, and the new cell walked on the CPU
at the tiny sizes."""
import json
import os

import numpy as np
import pytest

import run
from lib import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
counts = run.load_module("counts", "nemotron_h")
reference = run.load_module("reference", "nemotron_3_super")


def config():
    with open(os.path.join(HERE, "..", "configs",
                           "nemotron-3-super.json")) as f:
        return json.load(f)


def test_the_configuration_keeps_every_published_width():
    cfg = config()
    sizes = cfg["sizes"]
    assert (sizes["units"], sizes["mamba_heads"], sizes["mamba_head_dim"],
            sizes["n_groups"], sizes["state"], sizes["conv"],
            sizes["chunk"], sizes["num_heads"], sizes["num_kv_heads"],
            sizes["head_dim"], sizes["num_experts"],
            sizes["experts_per_token"], sizes["moe_latent"],
            sizes["expert_hidden"], sizes["shared_hidden"],
            sizes["routed_scaling_factor"], sizes["rms_norm_eps"]) \
        == (cfg["hidden_size"], cfg["mamba_num_heads"],
            cfg["mamba_head_dim"], cfg["n_groups"], cfg["ssm_state_size"],
            cfg["conv_kernel"], cfg["chunk_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["n_routed_experts"],
            cfg["num_experts_per_tok"], cfg["moe_latent_size"],
            cfg["moe_intermediate_size"],
            cfg["moe_shared_expert_intermediate_size"],
            cfg["routed_scaling_factor"], cfg["norm_eps"]) \
        == (4096, 128, 64, 8, 128, 4, 128, 32, 2, 128, 512, 22, 1024, 2688,
            5376, 5, 1e-5)
    assert sizes["mamba_heads"] * sizes["mamba_head_dim"] \
        == cfg["expand"] * cfg["hidden_size"]
    # one whole period: the first eleven layers of the published pattern
    assert sizes["pattern"] == cfg["hybrid_override_pattern"][:11] \
        == "MEMEMEM*EME"
    assert cfg["hybrid_override_pattern"].count("M") == 40 \
        and cfg["hybrid_override_pattern"].count("*") == 8
    assert cfg["reduced"] == ["num_layers", "experts_held", "vocab_size",
                              "max_length"]
    assert (sizes["experts_held"], sizes["first_expert"],
            sizes["vocab_size"], sizes["max_length"]) \
        == (128, 0, 32768, 8192)
    assert cfg["published"] == {"num_layers": 88, "num_experts": 512,
                                "vocab_size": 131072, "max_length": 262144}
    # the floors: a whole period, at least 8 routed experts, at least an
    # eighth of the vocabulary
    assert sizes["experts_held"] >= 8
    assert sizes["vocab_size"] * 8 >= cfg["vocab_size"]


def test_the_counts_are_the_sums_the_cut_was_made_by():
    """4.648 B parameters held, 4.26 MB of snapshot a slot and mixer, 1 KB
    of K/V a position: the numbers the cut was made by."""
    sizes = config()["sizes"]
    d = 4096
    mixer = d * (8192 + 10240 + 128) + 8192 * d + 10240 * 5 + 3 * 128 + 8192
    assert counts.mamba_params(sizes) == mixer                  # 109.6 M
    assert round(mixer / 1e6, 1) == 109.6
    attn = 2 * d * 4096 + 2 * d * 256
    assert counts.attention_params(sizes) == attn              # 35.7 M
    fixed = 512 * d + 512 + 2 * d * 1024 + 2 * d * 5376
    assert counts.moe_fixed_params(sizes) == fixed             # 54.5 M
    assert counts.expert_params(sizes) == 2 * 1024 * 2688      # 5.51 M
    held = 5 * mixer + attn + 5 * (fixed + 128 * 2 * 1024 * 2688) \
        + 11 * d + d + 2 * 32768 * d
    assert counts.params_held(sizes) == held
    assert round(held / 1e9, 3) == 4.648
    assert held == sum(int(np.prod(s))
                       for _n, s in reference.param_specs(sizes))
    # the snapshot: the float32 state and the bfloat16 conv window
    assert counts.snapshot_bytes(sizes) == 128 * 64 * 128 * 4 + 3 * 10240 * 2
    assert round(counts.snapshot_bytes(sizes) / 1e6, 2) == 4.26
    assert counts.kv_row_bytes(sizes) == 1024
    # a token decoded reads its rows and reads and writes every state once
    assert counts.kv_bytes(sizes, 100) - counts.kv_bytes(sizes, 0) \
        == 100 * 1024
    assert counts.kv_bytes(sizes, 0) == 2 * 5 * counts.snapshot_bytes(sizes)
    assert counts.ssd_step_bytes(sizes, {}) == 5 * (
        2 * 128 * 64 * 128 * 4 + (2 * 8192 + 128 + 2 * 1024) * 4)
    # the experts two matrices each, in the latent
    assert counts.moe_ffn_touched_bytes(sizes, 10, 0) \
        == 10 * 2 * 2 * 1024 * 2688
    # 32 live streams: 7.3 GB of weights (5.3 GB of it experts touched)
    w = counts.decode_weight_bytes(sizes, 32)
    experts = 5 * 2 * 128 * 2 * 1024 * 2688 \
        * counts.experts_touched(sizes, 32)
    assert 5.2e9 < experts < 5.4e9 and 7.2e9 < w < 7.4e9


def test_forward_flops_by_hand():
    sizes = config()["sizes"]
    d = 4096
    macs = 5 * (d * (8192 + 10240 + 128) + 8192 * d) \
        + (2 * d * 4096 + 2 * d * 256) \
        + 5 * (512 * d + 2 * d * 1024 + 2 * d * 5376
               + 22 * 128 / 512 * 2 * 1024 * 2688)
    mixer = 5 * (2 * 4 * 10240 + 5 * 128 * 64 * 128)
    attended = 3 * 10 + 6
    want = 2 * 3 * macs + 3 * mixer + 4 * 32 * 128 * attended \
        + 2 * d * 32768
    assert counts.forward_flops(sizes, 10, 3, heads=1) == pytest.approx(want)


def built(ops=(), modules=(), spans=(), lo=100.0, hi=110.0):
    at = lambda evs: [(n, s + lo, d) for n, s, d in evs]
    return trace_reduce.Trace({0: at(ops)}, {0: at(modules)}, at(spans),
                              lo, hi)


def test_the_ssd_step_metrics_on_a_built_trace():
    """``ssd_step_roofline.serve`` and ``ssd_step_share.serve`` with exact
    answers: the kernel's time in the steps (a prefill between them runs the
    chunked scan, no ``ssd_step``), the bytes once a decoded token; silent
    where the kernel is not on the path."""
    sizes = config()["sizes"]
    ops = [("ssd_step.1", 0.0, 0.002), ("fusion.2", 0.002, 0.008),
           ("fusion.9", 0.5, 0.100),            # the prefill's scan
           ("ssd_step.3", 1.0, 0.003), ("fusion.4", 1.003, 0.007)]
    modules = [("jit_pure_step_c8192", 0.0, 0.010),
               ("jit_pure_prefill_t1024c8192", 0.5, 0.100),
               ("jit_pure_step_c8192", 1.0, 0.010)]
    record = {"counts": counts, "sizes": sizes, "chips": 1,
              "device_kind": "TPU v5 lite", "traffic": {},
              "samples": {"slice_decode_context": [300] * 40},
              "trace": built(ops, modules)}
    read = lambda name: run.load_module(
        "readers", run.load_json("metrics", name)["reader"]).read(
        record, run.load_json("metrics", name)["params"])
    assert read("ssd_step_share.serve") == pytest.approx(100.0 * 0.005
                                                         / 0.020)
    spent = 0.002 + 0.003
    assert read("ssd_step_roofline.serve") == pytest.approx(
        100.0 * 40 * counts.ssd_step_bytes(sizes, {}) / 819e9 / spent)
    record["trace"] = built([("fusion.2", 0.0, 0.01)], modules)
    assert read("ssd_step_share.serve") is None
    assert read("ssd_step_roofline.serve") is None


def test_the_new_cell_walks_on_the_cpu_at_the_tiny_sizes(capsys):
    assert run.main(["--workload", "nemotron-3-super.short-chat", "--seed",
                     "2147483999", "--seconds", "6", "--trace", "1",
                     "--cpu-rehearsal"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 50
    assert line["device"]["platform"] == "rehearsal" and not line["metrics"]
