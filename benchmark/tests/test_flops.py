"""``lib/flops.py`` against the repo's own count and against hand counts."""
import json
import os

from lib import flops

HERE = os.path.dirname(os.path.abspath(__file__))


def test_bert_train_flops_match_bench_py():
    import bench

    want = bench._bert_train_flops_per_sample(128, 20)      # BERT-base
    got = flops.bert_train_flops_per_sample(128, 20, 12, 768, 3072, 30522)
    assert got == want
    cfg = {"num_layers": 12, "units": 768, "hidden_size": 3072,
           "vocab_size": 30522}
    assert flops.bert_train_flops(cfg, {"seq": 128, "masked": 20}, 64) \
        == 64 * want


def test_bert_large_is_about_245_gflop_a_sample():
    sizes = json.load(open(os.path.join(HERE, "..", "configs",
                                        "bert-large.json")))["sizes"]
    got = flops.bert_train_flops(sizes, {"seq": 128, "masked": 20}, 1)
    assert 2.3e11 < got < 2.6e11


def test_gpt2_layer_by_hand():
    d, ffn = 1280, 5120
    # one token attending to 100 positions: qkv 3 d^2, out d^2, ffn 2 d ffn
    # multiply-adds, then 100 d for QK^T and 100 d for PV
    by_hand = 2 * (3 * d * d + d * d + 2 * d * ffn) + 2 * (100 * d + 100 * d)
    assert flops.gpt_layer_flops_per_token(d, ffn, 100) == by_hand
    cfg = {"units": d, "hidden": ffn, "num_layers": 1, "vocab_size": 50257}
    # the 100th token (position 99) alone, plus the LM head
    assert flops.gpt_forward_flops(cfg, 99, 1, heads=1) \
        == by_hand + 2 * d * 50257
    # a 3-token prefill attends to 1 + 2 + 3 positions
    three = sum(flops.gpt_layer_flops_per_token(d, ffn, c) for c in (1, 2, 3))
    assert flops.gpt_forward_flops(cfg, 0, 3, heads=0) == three


def test_gpt2_large_weights_are_774m_parameters():
    sizes = json.load(open(os.path.join(HERE, "..", "configs",
                                        "gpt2-large.json")))["sizes"]
    n = flops.gpt_param_bytes(sizes, bytes_per_el=1)
    assert 7.6e8 < n < 7.8e8          # 774 M less the position table
    assert flops.gpt_kv_bytes_per_token(sizes) == 2 * 36 * 1280 * 2
