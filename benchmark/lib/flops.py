"""Operations and bytes the algorithm needs, computed from shapes.

Every count is of the work the mathematics requires for the samples and
tokens processed, whatever implements it: padding, dead cache capacity and
recomputation are not counted, so a later change that stops doing them
cannot make a count stale. ``cfg`` is a configuration file's ``sizes``.
"""


# --------------------------------------------------------------- BERT train
def bert_train_flops_per_sample(seq, masked, layers, d, ffn, vocab):
    """Forward + backward FLOPs of one BERT pretraining sample (copied from
    ``bench._bert_train_flops_per_sample``): per token and layer the qkv and
    output projections (4 d^2) and the FFN (2 d ffn), times 2 for
    multiply-add, plus attention's QK^T and PV (4 seq d); the MLM head on the
    masked positions only (transform d^2, tied decoder d V); backward is
    twice forward. Bias, LayerNorm, softmax, pooler and NSP are left out
    (under 1 %)."""
    per_tok_layer = 2 * (4 * d * d + 2 * d * ffn) + 4 * seq * d
    fwd = seq * layers * per_tok_layer + masked * 2 * (d * d + d * vocab)
    return 3.0 * fwd


def bert_train_flops(cfg, traffic, samples):
    return samples * bert_train_flops_per_sample(
        traffic["seq"], traffic["masked"], cfg["num_layers"], cfg["units"],
        cfg["hidden_size"], cfg["vocab_size"])


def softmax_xent_fwd_bytes(cfg, traffic, bytes_per_el=2):
    """HBM bytes one step's softmax cross-entropy forward has to move: the
    logits of every masked position, read once (batch x masked rows of
    ``vocab_size``, in the type they are computed in); labels and the two
    columns it writes are a few thousand bytes and left out."""
    return traffic["batch"] * traffic["masked"] * cfg["vocab_size"] \
        * bytes_per_el


# ------------------------------------------------------------------ GPT serve
def gpt_layer_flops_per_token(d, ffn, context):
    """Forward FLOPs of one decoder layer for one token that attends to
    ``context`` positions (itself included): qkv + output projections
    (4 d^2) and FFN (2 d ffn) as multiply-adds, plus QK^T and PV over the
    context (4 context d)."""
    return 2 * (4 * d * d + 2 * d * ffn) + 4 * context * d


def gpt_forward_flops(cfg, start, count, heads=1):
    """Forward FLOPs to process ``count`` consecutive tokens at positions
    ``start .. start+count-1`` (causal: token at position p attends to p+1
    positions), of which ``heads`` go through the tied LM head (a prefill
    needs one: the last position)."""
    d, ffn = cfg["units"], cfg["hidden"]
    # sum over p of (p + 1) for p in [start, start + count)
    ctx_sum = count * start + count * (count + 1) // 2
    per_layer = count * 2 * (4 * d * d + 2 * d * ffn) + 4 * d * ctx_sum
    return cfg["num_layers"] * per_layer + heads * 2 * d * cfg["vocab_size"]


def gpt_param_bytes(cfg, bytes_per_el=2):
    """Bytes of the weights a decode step has to read once: every layer's
    matrices, biases and norms, the final norm and the tied embedding (read
    whole by the LM head). Position embeddings are gathered, not read."""
    d, ffn = cfg["units"], cfg["hidden"]
    per_layer = (3 * d * d + 3 * d) + (d * d + d) + (d * ffn + ffn) \
        + (ffn * d + d) + 4 * d
    return bytes_per_el * (cfg["num_layers"] * per_layer + 2 * d
                           + cfg["vocab_size"] * d)


def gpt_kv_bytes_per_token(cfg, bytes_per_el=2):
    """K and V of one cached token over all layers."""
    return 2 * cfg["num_layers"] * cfg["units"] * bytes_per_el
