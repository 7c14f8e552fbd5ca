"""``counts/cohere_moe.py`` (PR 31) against the configuration's own sizes and
against the plain reference's own tally at the tiny sizes."""
import json
import os

import run

HERE = os.path.dirname(os.path.abspath(__file__))


def sizes_of(config):
    with open(os.path.join(HERE, "..", "configs", config + ".json")) as f:
        return json.load(f)["sizes"]


cohere = run.load_module("counts", "cohere_moe")
reference = run.load_module("reference", "command_a_plus")


def test_cohere_moe_weights_are_the_share_the_issue_reckoned():
    sizes = sizes_of("command-a-plus")
    # a decode step over so many tokens that every expert held is touched
    n = cohere.decode_weight_bytes(sizes, 10 ** 6) / cohere.BYTES_PER_EL
    specs = reference.param_specs(sizes)
    held = sum(int(__import__("numpy").prod(s)) for _n, s in specs)
    assert n == held                      # every leaf, once: 4.733 B
    assert 4.732e9 < n < 4.734e9
    # one live token touches 8/128 of the experts held, in expectation
    one = cohere.decode_weight_bytes(sizes, 1)
    assert abs((n * 2 - one) - 4 * (15 / 16) * cohere.expert_weight_bytes(
        sizes)) < 1
    # K and V: 8 heads x 128 x 2 B x 2 = 4 KB a position a layer; the three
    # window layers stop at 4096
    assert cohere.kv_bytes(sizes, 1) == 4 * 4096
    assert cohere.kv_bytes(sizes, 6000) == (3 * 4096 + 6000) * 4096
    assert cohere.kv_cache_write_bytes(sizes, {}) == 4 * 4096
    assert cohere.moe_ffn_bytes(sizes, 0) == 0


def test_cohere_moe_counts_against_the_references_own_tally():
    """At the tiny sizes: the positions a token attends to are the rows of
    the reference's own mask; the distinct experts a batch touches are, on
    average over draws, what the reference's router touches."""
    import jax.numpy as jnp
    import numpy as np

    cfg = json.load(open(os.path.join(HERE, "..", "configs",
                                      "command-a-plus.json")))
    sizes = dict(cfg["sizes"], **cfg["tiny"])
    t, w = 40, sizes["sliding_window"]
    for window in (None, w):
        seen = np.asarray(reference.visible(jnp.arange(t), t, window))
        assert [cohere.attended(p, window) for p in range(t)] \
            == seen.sum(axis=1).tolist()
        assert cohere._attended_sum(5, 30, window) == seen[5:35].sum()
    # forward_flops: the scores' part, by the mask
    q = sizes["num_heads"] * sizes["head_dim"]
    by_mask = sum(4 * q * int(np.asarray(reference.visible(
        jnp.arange(5, 35), 35, win)).sum()) for win in cohere.windows(sizes))
    assert cohere.forward_flops(sizes, 5, 30, heads=0) \
        == 4 * 30 * 2 * cohere.token_matmul_macs(sizes) + by_mask
    # experts touched: random routers over random rows, many draws
    rs = np.random.RandomState(0)
    live, draws, first, held = 3, 400, sizes["first_expert"], \
        sizes["experts_held"]
    touched = 0
    for _ in range(draws):
        h = jnp.asarray(rs.normal(size=(live, sizes["units"])), jnp.float32)
        wr = jnp.asarray(rs.normal(size=(sizes["num_experts"],
                                         sizes["units"])), jnp.float32)
        _w, expert = reference.route(sizes, h, wr)
        e = np.unique(np.asarray(expert))
        touched += int(((e >= first) & (e < first + held)).sum())
    want = cohere.experts_touched(sizes, live)       # 1 - (6/8)^3
    assert abs(touched / (draws * held) - want) < 0.03
