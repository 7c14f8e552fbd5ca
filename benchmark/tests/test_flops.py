"""``counts/bert.py`` and ``counts/gpt.py`` against the repo's own count and
against hand counts."""
import json
import os

import run

HERE = os.path.dirname(os.path.abspath(__file__))
bert = run.load_module("counts", "bert")
gpt = run.load_module("counts", "gpt")


def sizes_of(config):
    with open(os.path.join(HERE, "..", "configs", config + ".json")) as f:
        return json.load(f)["sizes"]


def test_bert_train_flops_match_bench_py():
    import bench

    want = bench._bert_train_flops_per_sample(128, 20)      # BERT-base
    got = bert.train_flops_per_sample(128, 20, 12, 768, 3072, 30522)
    assert got == want
    cfg = {"num_layers": 12, "units": 768, "hidden_size": 3072,
           "vocab_size": 30522}
    assert bert.train_flops(cfg, {"seq": 128, "masked": 20}, 64) == 64 * want


def test_bert_large_is_about_245_gflop_a_sample():
    got = bert.train_flops(sizes_of("bert-large"),
                           {"seq": 128, "masked": 20}, 1)
    assert 2.3e11 < got < 2.6e11
    # 32 x 20 masked rows of 30522 bf16 logits
    assert bert.softmax_xent_fwd_bytes(
        sizes_of("bert-large"), {"batch": 32, "masked": 20}) \
        == 32 * 20 * 30522 * 2


def test_gpt2_layer_by_hand():
    d, ffn = 1280, 5120
    # one token attending to 100 positions: qkv 3 d^2, out d^2, ffn 2 d ffn
    # multiply-adds, then 100 d for QK^T and 100 d for PV
    by_hand = 2 * (3 * d * d + d * d + 2 * d * ffn) + 2 * (100 * d + 100 * d)
    assert gpt.layer_flops_per_token(d, ffn, 100) == by_hand
    cfg = {"units": d, "hidden": ffn, "num_layers": 1, "vocab_size": 50257}
    # the 100th token (position 99) alone, plus the LM head
    assert gpt.forward_flops(cfg, 99, 1, heads=1) == by_hand + 2 * d * 50257
    # a 3-token prefill attends to 1 + 2 + 3 positions
    three = sum(gpt.layer_flops_per_token(d, ffn, c) for c in (1, 2, 3))
    assert gpt.forward_flops(cfg, 0, 3, heads=0) == three


def test_gpt2_large_weights_are_774m_parameters():
    sizes = sizes_of("gpt2-large")
    n = gpt.decode_weight_bytes(sizes, 1) // gpt.BYTES_PER_EL
    assert 7.6e8 < n < 7.8e8          # 774 M less the position table
    assert gpt.decode_weight_bytes(sizes, 32) == gpt.decode_weight_bytes(
        sizes, 1)                     # a dense model reads all for one token
    assert gpt.kv_bytes(sizes, 1) == 2 * 36 * 1280 * 2
    assert gpt.kv_bytes(sizes, 300) == 300 * gpt.kv_bytes(sizes, 1)
    # a decoded token's write is its own column: K and V of one position in
    # 36 layers, whatever block the kernel moves around it (PERF.md section 5)
    assert gpt.kv_cache_write_bytes(sizes, {}) == 72 * 1280 * 2
