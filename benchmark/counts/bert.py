"""Operations and bytes of BERT pretraining (encoder layers, MLM head on the
masked positions tied to the word embedding), computed from shapes.

Every count is of the work the mathematics requires for the samples
processed, whatever implements it: padding and recomputation are not
counted. ``sizes`` is a configuration file's ``sizes``, ``traffic`` a cell's.
"""
BYTES_PER_EL = 2


def train_flops_per_sample(seq, masked, layers, d, ffn, vocab):
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


def train_flops(sizes, traffic, samples):
    return samples * train_flops_per_sample(
        traffic["seq"], traffic["masked"], sizes["num_layers"],
        sizes["units"], sizes["hidden_size"], sizes["vocab_size"])


def softmax_xent_fwd_bytes(sizes, traffic):
    """HBM bytes one step's softmax cross-entropy forward has to move: the
    logits of every masked position, read once (batch x masked rows of
    ``vocab_size``, in the type they are computed in); labels and the two
    columns it writes are a few thousand bytes and left out."""
    return traffic["batch"] * traffic["masked"] * sizes["vocab_size"] \
        * BYTES_PER_EL
