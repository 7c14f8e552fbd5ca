"""Operations and bytes of one chip's share of a Cohere2-MoE style decoder
(parallel block; grouped K/V heads; sliding-window layers with rotary
positions beside full-attention layers without; a sigmoid top-k router over
all experts, of which ``experts_held`` are here; averaged shared experts; LM
head tied to the slice of the embedding held), computed from shapes.

Every count is of the work the mathematics requires for the tokens
processed, whatever implements it: padding, dead cache capacity, the rows a
kernel moves around a column and recomputation are not counted. Where the
work depends on the router's choices it is the expectation under even
routing, which routers of seeded random weights give: a token picks
``experts_per_token`` of ``num_experts``, so it picks each expert with
probability ``k / E``. ``sizes`` is a configuration file's ``sizes``;
``BYTES_PER_EL`` is the width of the type the configuration serves in.
"""
BYTES_PER_EL = 2
SLIDING = "sliding_attention"


def windows(sizes):
    """Per layer: the sliding window, or None for full attention."""
    return [sizes["sliding_window"] if t == SLIDING else None
            for t in sizes["layer_types"]]


def attended(position, window):
    """Positions the token at ``position`` attends to, itself included."""
    return position + 1 if window is None else min(position + 1, window)


def _attended_sum(start, count, window):
    """Sum of :func:`attended` over positions start .. start+count-1."""
    total = count * start + count * (count + 1) // 2
    if window is not None:
        # positions p >= window each lose (p + 1 - window)
        first = max(start, window)
        n = start + count - first
        if n > 0:
            total -= n * (first + 1 - window) + n * (n - 1) // 2
    return total


def token_matmul_macs(sizes):
    """Multiply-adds of one token in one layer outside the attention scores:
    q, k, v and output projections, the router over all experts, the routed
    experts expected here (k x held / E of them) and the shared experts."""
    d, f = sizes["units"], sizes["expert_hidden"]
    q = sizes["num_heads"] * sizes["head_dim"]
    kv = sizes["num_kv_heads"] * sizes["head_dim"]
    here = sizes["experts_per_token"] * sizes["experts_held"] \
        / sizes["num_experts"]
    return 2 * d * q + 2 * d * kv + d * sizes["num_experts"] \
        + (here + sizes["num_shared_experts"]) * 3 * d * f


def forward_flops(sizes, start, count, heads=1):
    """Forward FLOPs to process ``count`` consecutive tokens at positions
    ``start .. start+count-1``, of which ``heads`` go through the tied LM
    head: per layer the token's matmuls, and QK^T and PV over the positions
    it attends to (a window layer: at most ``sliding_window``) in every
    query head."""
    q = sizes["num_heads"] * sizes["head_dim"]
    per_layer = len(sizes["layer_types"]) * count * 2 \
        * token_matmul_macs(sizes)
    scores = sum(4 * q * _attended_sum(start, count, w)
                 for w in windows(sizes))
    return per_layer + scores \
        + heads * 2 * sizes["units"] * sizes["vocab_size"]


def experts_touched(sizes, live_tokens):
    """Expected share of the experts held that ``live_tokens`` tokens
    touch: 1 - (1 - k/E)^live_tokens each."""
    miss = 1.0 - sizes["experts_per_token"] / sizes["num_experts"]
    return 1.0 - miss ** live_tokens


def expert_weight_bytes(sizes):
    """Gate, up and down matrices of all experts held, one layer."""
    return BYTES_PER_EL * sizes["experts_held"] * 3 * sizes["units"] \
        * sizes["expert_hidden"]


def decode_weight_bytes(sizes, live_tokens):
    """Bytes of the weights one decode step over ``live_tokens`` tokens has
    to read once: per layer the attention matrices, the shared experts, the
    router, the norm, and of the experts held those the live tokens are
    expected to touch; the final norm and the embedding slice (read whole by
    the head)."""
    d, f = sizes["units"], sizes["expert_hidden"]
    q = sizes["num_heads"] * sizes["head_dim"]
    kv = sizes["num_kv_heads"] * sizes["head_dim"]
    fixed = 2 * d * q + 2 * d * kv + sizes["num_shared_experts"] * 3 * d * f \
        + sizes["num_experts"] * d + d
    return len(sizes["layer_types"]) * (
        BYTES_PER_EL * fixed + expert_weight_bytes(sizes)
        * experts_touched(sizes, live_tokens)) \
        + BYTES_PER_EL * (d + sizes["vocab_size"] * d)


def kv_bytes(sizes, context):
    """K and V, over all layers, that a token decoded at ``context`` live
    positions has to read: a window layer keeps at most its window."""
    row = 2 * sizes["num_kv_heads"] * sizes["head_dim"] * BYTES_PER_EL
    return row * sum(context if w is None else min(context, w)
                     for w in windows(sizes))


def kv_cache_write_bytes(sizes, traffic):
    """HBM bytes the K/V write of one decoded token has to move: its own
    row, the K and the V of one position in every layer."""
    return kv_bytes(sizes, 1)


def moe_ffn_touched_bytes(sizes, experts_hit, tokens):
    """HBM bytes the grouped expert FFN has to move for steps that put picks
    on ``experts_hit`` (layer, expert) pairs in all and decoded ``tokens``
    tokens in all: one expert's three matrices a pair, and the routed rows
    in and out. The pairs are the program's own count (``xhit=`` on its
    step spans): routers of random weights are not quite even, so fewer
    experts are hit than :func:`experts_touched` expects."""
    rows = tokens * sizes["experts_per_token"] * sizes["experts_held"] \
        / sizes["num_experts"]
    return experts_hit * expert_weight_bytes(sizes) / sizes["experts_held"] \
        + 2 * rows * sizes["units"] * BYTES_PER_EL


def moe_ffn_bytes(sizes, live_tokens):
    """HBM bytes the grouped expert FFN of one decode step over
    ``live_tokens`` tokens has to move, all layers: the matrices of the
    experts touched, and the routed rows in and out (k x held / E a token)."""
    rows = live_tokens * sizes["experts_per_token"] * sizes["experts_held"] \
        / sizes["num_experts"]
    return len(sizes["layer_types"]) * (
        expert_weight_bytes(sizes) * experts_touched(sizes, live_tokens)
        + 2 * rows * sizes["units"] * BYTES_PER_EL)
