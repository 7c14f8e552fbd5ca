"""Operations and bytes of one chip's share of a DeepSeek-V3 lineage decoder
(latent attention: queries through a low rank, K and V expanded from one
compressed row a position; ``first_k_dense`` dense SwiGLU layers, then a
sigmoid top-k router over all experts, of which ``experts_held`` are here,
beside shared experts; an untied head over the slice of the vocabulary
held), computed from shapes.

Every count is of the work the mathematics requires for the tokens
processed, whatever implements it: padding, dead cache capacity, the rows a
kernel moves around a row and recomputation are not counted. Where the work
depends on the router's choices it is the expectation under even routing: a
token picks each expert with probability ``k / E``.

**Which form is counted.** The expanded and the absorbed form of latent
attention compute the same function. Their projections cost the same: a
token's ``q_nope W_uk`` and ``ctx W_uv`` are the H x rank x (nope + v)
multiply-adds of expanding that one token's K and V. Their scores differ:
against a position the expanded form spends nope + rope + v (320)
multiply-adds a head, the absorbed form 2 x rank + rope (1,088), which it
pays to read 1,152 bytes a position in the place of 40,960. ``forward_flops``
counts the EXPANDED form for a decoded token as for a prefilled one: it is
the least arithmetic the mathematics needs, and what the absorbed step spends
beyond it is the implementation's trade, like recomputation. The bytes
(``kv_bytes``) are the compressed row's: the least a token has to read of a
position, whichever form reads it.

``sizes`` is a configuration file's ``sizes``; ``BYTES_PER_EL`` is the width
of the type the configuration serves in.
"""
BYTES_PER_EL = 2


def expert_layers(sizes):
    return sizes["num_layers"] - sizes["first_k_dense"]


def attention_params(sizes):
    """q_a, q_b, kv_a, kv_b (both halves) and o of one layer."""
    d, h = sizes["units"], sizes["num_heads"]
    rq, rkv = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    nope, rope, dv = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                      sizes["v_head_dim"])
    return d * rq + rq * h * (nope + rope) + d * (rkv + rope) \
        + rkv * h * (nope + dv) + h * dv * d


def ffn_params(sizes, width):
    return 3 * sizes["units"] * width


def moe_layer_params(sizes):
    """Attention, shared experts, router and the experts held, one layer."""
    return attention_params(sizes) \
        + ffn_params(sizes, sizes["num_shared_experts"]
                     * sizes["expert_hidden"]) \
        + sizes["num_experts"] * sizes["units"] \
        + sizes["experts_held"] * ffn_params(sizes, sizes["expert_hidden"])


def norm_params(sizes):
    """Gains of one layer: the block's two norms and the latents' two."""
    return 2 * sizes["units"] + sizes["q_lora_rank"] + sizes["kv_lora_rank"]


def parameters(sizes):
    """Parameters held: the dense layers, the expert layers, the final norm,
    embedding and head."""
    dense = attention_params(sizes) + ffn_params(sizes, sizes["dense_hidden"])
    return sizes["first_k_dense"] * dense \
        + expert_layers(sizes) * moe_layer_params(sizes) \
        + sizes["num_layers"] * norm_params(sizes) + sizes["units"] \
        + 2 * sizes["vocab_size"] * sizes["units"]


def routed_here(sizes):
    """Routed experts a token is expected to find here: k x held / E."""
    return sizes["experts_per_token"] * sizes["experts_held"] \
        / sizes["num_experts"]


def token_matmul_macs(sizes, dense):
    """Multiply-adds of one token in one layer outside the attention scores:
    the attention's projections (its own K and V expanded once, or, the same
    count, its queries and its mixed rows taken through W_kvb's halves) and
    the dense FFN, or the router over all experts, the routed experts
    expected here and the shared ones."""
    d, f = sizes["units"], sizes["expert_hidden"]
    if dense:
        return attention_params(sizes) + 3 * d * sizes["dense_hidden"]
    return attention_params(sizes) + d * sizes["num_experts"] \
        + (routed_here(sizes) + sizes["num_shared_experts"]) * 3 * d * f


def forward_flops(sizes, start, count, heads=1):
    """Forward FLOPs to process ``count`` consecutive tokens at positions
    ``start .. start+count-1``, of which ``heads`` go through the LM head:
    per layer the token's matmuls, and the EXPANDED form's scores and values
    over the positions it attends to in every head (nope + rope for q.k,
    v_head_dim for p.v: see the module's docstring)."""
    n_dense, n_moe = sizes["first_k_dense"], expert_layers(sizes)
    macs = n_dense * token_matmul_macs(sizes, True) \
        + n_moe * token_matmul_macs(sizes, False)
    attended = count * start + count * (count + 1) // 2
    per_position = sizes["num_heads"] * (
        sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
        + sizes["v_head_dim"])
    return 2 * count * macs \
        + sizes["num_layers"] * 2 * per_position * attended \
        + heads * 2 * sizes["units"] * sizes["vocab_size"]


def experts_touched(sizes, live_tokens):
    """Expected share of the experts held that ``live_tokens`` tokens
    touch: 1 - (1 - k/E)^live_tokens each."""
    miss = 1.0 - sizes["experts_per_token"] / sizes["num_experts"]
    return 1.0 - miss ** live_tokens


def expert_weight_bytes(sizes):
    """Gate, up and down matrices of all experts held, one layer."""
    return BYTES_PER_EL * sizes["experts_held"] \
        * ffn_params(sizes, sizes["expert_hidden"])


def decode_weight_bytes(sizes, live_tokens):
    """Bytes of the weights one decode step over ``live_tokens`` tokens has
    to read once: per layer the attention matrices and the gains, the dense
    FFN or the shared experts, the router and, of the experts held, those the
    live tokens are expected to touch; the final norm, the head (read whole)
    and the live tokens' rows of the embedding."""
    d = sizes["units"]
    fixed = sizes["num_layers"] * (attention_params(sizes)
                                   + norm_params(sizes)) \
        + sizes["first_k_dense"] * ffn_params(sizes, sizes["dense_hidden"]) \
        + expert_layers(sizes) * (
            ffn_params(sizes, sizes["num_shared_experts"]
                       * sizes["expert_hidden"])
            + sizes["num_experts"] * d) \
        + d + sizes["vocab_size"] * d + live_tokens * d
    return BYTES_PER_EL * fixed + expert_layers(sizes) \
        * expert_weight_bytes(sizes) * experts_touched(sizes, live_tokens)


def kv_bytes(sizes, context):
    """The compressed rows, over all layers, that a token decoded at
    ``context`` live positions has to read: c_kv and k_pe of each."""
    return sizes["num_layers"] * BYTES_PER_EL * context \
        * (sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"])


def kv_cache_write_bytes(sizes, traffic):
    """HBM bytes the page write of one decoded token has to move: its own
    row, c_kv and k_pe of one position in every layer."""
    return kv_bytes(sizes, 1)


def moe_ffn_touched_bytes(sizes, experts_hit, tokens):
    """HBM bytes the grouped expert FFN has to move for steps that put picks
    on ``experts_hit`` (layer, expert) pairs in all and decoded ``tokens``
    tokens in all: one expert's three matrices a pair, and the routed rows
    in and out in every expert layer. The pairs are the program's own count
    (``xhit=`` on its step spans)."""
    rows = tokens * routed_here(sizes) * expert_layers(sizes)
    return experts_hit * expert_weight_bytes(sizes) / sizes["experts_held"] \
        + 2 * rows * sizes["units"] * BYTES_PER_EL


def moe_ffn_bytes(sizes, live_tokens):
    """HBM bytes the grouped expert FFN of one decode step over
    ``live_tokens`` tokens has to move, all expert layers: the matrices of
    the experts touched, and the routed rows in and out."""
    rows = live_tokens * routed_here(sizes)
    return expert_layers(sizes) * (
        expert_weight_bytes(sizes) * experts_touched(sizes, live_tokens)
        + 2 * rows * sizes["units"] * BYTES_PER_EL)
