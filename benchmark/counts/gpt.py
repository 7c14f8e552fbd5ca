"""Operations and bytes of a GPT-2 style decoder (learned positions, dense
causal attention over every earlier token, FFN, LM head tied to the token
embedding), computed from shapes.

Every count is of the work the mathematics requires for the tokens
processed, whatever implements it: padding, dead cache capacity and
recomputation are not counted, so a later change that stops doing them
cannot make a count stale. ``sizes`` is a configuration file's ``sizes``;
``BYTES_PER_EL`` is the width of the type the configuration serves in.
"""
BYTES_PER_EL = 2


def layer_flops_per_token(d, ffn, context):
    """Forward FLOPs of one decoder layer for one token that attends to
    ``context`` positions (itself included): qkv + output projections
    (4 d^2) and FFN (2 d ffn) as multiply-adds, plus QK^T and PV over the
    context (4 context d)."""
    return 2 * (4 * d * d + 2 * d * ffn) + 4 * context * d


def forward_flops(sizes, start, count, heads=1):
    """Forward FLOPs to process ``count`` consecutive tokens at positions
    ``start .. start+count-1`` (causal: token at position p attends to p+1
    positions), of which ``heads`` go through the tied LM head (a prefill
    needs one: the last position)."""
    d, ffn = sizes["units"], sizes["hidden"]
    # sum over p of (p + 1) for p in [start, start + count)
    ctx_sum = count * start + count * (count + 1) // 2
    per_layer = count * 2 * (4 * d * d + 2 * d * ffn) + 4 * d * ctx_sum
    return sizes["num_layers"] * per_layer \
        + heads * 2 * d * sizes["vocab_size"]


def decode_weight_bytes(sizes, live_tokens):
    """Bytes of the weights one decode step over ``live_tokens`` tokens has
    to read once: every layer's matrices, biases and norms, the final norm
    and the tied embedding (read whole by the LM head), however many tokens
    are live (a dense model touches every weight for one token). Position
    embeddings are gathered, not read. ``live_tokens`` belongs to the name's
    fixed signature: the reader passes it and is a file no later PR edits,
    and a model that reads only the experts its live tokens choose needs
    it."""
    d, ffn = sizes["units"], sizes["hidden"]
    per_layer = (3 * d * d + 3 * d) + (d * d + d) + (d * ffn + ffn) \
        + (ffn * d + d) + 4 * d
    return BYTES_PER_EL * (sizes["num_layers"] * per_layer + 2 * d
                           + sizes["vocab_size"] * d)


def kv_bytes(sizes, context):
    """K and V, over all layers, that a token decoded at ``context`` live
    positions has to read: every layer keeps every position."""
    return context * 2 * sizes["num_layers"] * sizes["units"] * BYTES_PER_EL


def kv_cache_write_bytes(sizes, traffic):
    """HBM bytes the K/V write of one decoded token has to move: its own
    column, the K and the V of one position in every layer, written once.
    Slots that decode nothing and the rest of the block that a kernel moves
    around the column are not work the token needs."""
    return kv_bytes(sizes, 1)
