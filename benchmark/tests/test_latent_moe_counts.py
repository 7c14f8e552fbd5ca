"""``counts/latent_moe.py`` and the ``ax-k1`` configuration (PR 37) against a
hand-written sum at the published widths."""
import json
import os

import numpy as np
import pytest

import run

HERE = os.path.dirname(os.path.abspath(__file__))
counts = run.load_module("counts", "latent_moe")
reference = run.load_module("reference", "ax_k1")


def config():
    with open(os.path.join(HERE, "..", "configs", "ax-k1.json")) as f:
        return json.load(f)


def test_the_configuration_keeps_every_published_width():
    cfg = config()
    sizes = cfg["sizes"]
    assert (sizes["units"], sizes["num_heads"], sizes["q_lora_rank"],
            sizes["kv_lora_rank"], sizes["qk_nope_head_dim"],
            sizes["qk_rope_head_dim"], sizes["v_head_dim"],
            sizes["dense_hidden"], sizes["expert_hidden"],
            sizes["num_experts"], sizes["experts_per_token"],
            sizes["num_shared_experts"], sizes["routed_scaling_factor"],
            sizes["first_k_dense"], sizes["rms_norm_eps"]) \
        == (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["n_routed_experts"], cfg["num_experts_per_tok"],
            cfg["n_shared_experts"], cfg["routed_scaling_factor"],
            cfg["first_k_dense_replace"], cfg["rms_norm_eps"]) \
        == (7168, 64, 1536, 512, 128, 64, 128, 18432, 2048, 192, 8, 1, 2.5,
            1, 1e-6)
    yarn = cfg["rope_scaling"]
    assert (sizes["rope_theta"], sizes["rope_factor"],
            sizes["original_max_length"], sizes["beta_fast"],
            sizes["beta_slow"], sizes["mscale_all_dim"]) \
        == (cfg["rope_theta"], yarn["factor"],
            yarn["original_max_position_embeddings"], yarn["beta_fast"],
            yarn["beta_slow"], yarn["mscale_all_dim"])
    assert cfg["reduced"] == ["num_layers", "experts_held", "vocab_size",
                              "max_length"]
    assert (sizes["num_layers"], sizes["experts_held"], sizes["vocab_size"],
            sizes["max_length"]) == (6, 12, 20480, 16384)
    assert cfg["published"] == {"num_layers": 61, "num_experts": 192,
                                "vocab_size": 163840, "max_length": 131072}
    # the floors: the dense layer and at least 4 expert layers, at least 8
    # routed experts, at least an eighth of the vocabulary
    assert sizes["num_layers"] - sizes["first_k_dense"] >= 4
    assert sizes["experts_held"] >= 8
    assert sizes["vocab_size"] * 8 >= cfg["vocab_size"]
    # the rehearsal's size routes over experts held elsewhere, from a first
    # expert that is not 0, and serves past its original positions
    tiny = cfg["tiny"]
    assert tiny["experts_held"] < tiny["num_experts"] and tiny["first_expert"]
    assert tiny["max_length"] > tiny["original_max_length"]
    inv, low, high, scale = reference.yarn(sizes)
    assert (low, high) == (10, 23) and abs(scale - 0.130861) < 5e-7
    assert inv[0] == 1.0 and inv[31] == 10000.0 ** (-62 / 64) / 32


def test_latent_moe_counts_are_the_sums_the_issue_reckoned():
    sizes = config()["sizes"]
    # attention by hand: q_a 11.01 M, q_b 18.87 M, kv_a 4.13 M, kv_b 8.39 M,
    # o 58.72 M
    attn = 7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 * 64 * 256 \
        + 8192 * 7168
    assert counts.attention_params(sizes) == attn == 101122048   # 101.12 M
    expert = 3 * 7168 * 2048                                     # 44.04 M
    moe = attn + expert + 192 * 7168 + 12 * expert
    assert counts.moe_layer_params(sizes) == moe == 675020800
    layer_gains = 2 * 7168 + 1536 + 512
    assert counts.norm_params(sizes) == layer_gains
    assert moe + layer_gains == 675037184                        # 675.04 M
    dense = attn + 3 * 7168 * 18432
    assert dense + layer_gains == 497500160                      # 497.50 M
    gains = 6 * layer_gains + 7168
    held = dense + 5 * moe + 2 * 20480 * 7168 + gains
    assert counts.parameters(sizes) == held
    assert 4.166e9 < held < 4.167e9                              # 4.166 B
    # ... which is every leaf of the reference, once
    assert held == sum(int(np.prod(s))
                       for _n, s in reference.param_specs(sizes))
    # a page: 576 values of 2 bytes a position and layer, 6 layers
    assert counts.kv_bytes(sizes, 1) == 6 * 1152 == 6912
    assert counts.kv_bytes(sizes, 6000) == 6000 * 6912
    assert counts.kv_cache_write_bytes(sizes, {}) == 6912
    assert 32 * 16384 * 6912 == 3623878656                       # 3.62 GB
    # where 64 heads of K (192) and V (128) would take 35.6 x
    assert 64 * (192 + 128) * 2 / 1152 == pytest.approx(35.6, abs=0.06)
    # a decode step reads everything but the experts no token touched and
    # the rows of the embedding no token names
    every = 2 * (held - 20480 * 7168)
    few = counts.decode_weight_bytes(sizes, 1)
    many = counts.decode_weight_bytes(sizes, 4000)
    assert few < many <= every + 2 * 4000 * 7168
    assert many == pytest.approx(every + 2 * 4000 * 7168, rel=1e-6)
    one = 2 * expert
    assert few == pytest.approx(
        every - 5 * 12 * one * (1 - 8 / 192) + 2 * 7168, rel=1e-9)
    # the expert FFN's bytes: the matrices of the pairs hit and the rows
    assert counts.moe_ffn_touched_bytes(sizes, 30, 0) == 30 * one
    assert counts.moe_ffn_touched_bytes(sizes, 0, 16) \
        == 2 * 16 * 0.5 * 5 * 7168 * 2
    assert counts.moe_ffn_bytes(sizes, 12.0) == pytest.approx(
        5 * (12 * one * (1 - (1 - 8 / 192) ** 12)
             + 2 * 12 * 0.5 * 7168 * 2))
    # FLOPs: a token's matmuls twice their parameters (the experts expected
    # here, half an expert a token), and the EXPANDED form's scores
    macs = dense + 5 * (attn + 192 * 7168 + 1.5 * expert)
    assert counts.forward_flops(sizes, 0, 1, heads=0) \
        == 2 * macs + 6 * 2 * 64 * 320 * 1
    assert counts.forward_flops(sizes, 5999, 1, heads=1) \
        == 2 * macs + 6 * 2 * 64 * 320 * 6000 + 2 * 7168 * 20480
    prompt = counts.forward_flops(sizes, 0, 8192, heads=1)
    assert 25e12 < prompt < 35e12                  # the issue's 30 TFLOP
