"""Operations and bytes of a Brumby style decoder (RMSNorm, grouped heads
with one gate a K/V head, power retention of degree 2 in the place of
attention, SwiGLU, an untied LM head), computed from shapes.

Every count is of the work the mathematics requires for the tokens
processed, whatever implements it: padding, a layout's spare entries, dead
slots and recomputation are not counted. The retention is counted in its
recurrent form, which is the mechanism: a state of ``state_entries`` rows
(the symmetric square of a head: 128 x 129 / 2 = 8256) by the head's width,
folded and read once a token, whatever the context. ``sizes`` is a
configuration file's ``sizes``; ``BYTES_PER_EL`` is the width of the type
the configuration serves its weights in, ``STATE_BYTES_PER_EL`` that of the
state (float32, as the configuration states).
"""
BYTES_PER_EL = 2
STATE_BYTES_PER_EL = 4


def state_entries(sizes):
    """Entries of the symmetric square of one head's key."""
    return sizes["head_dim"] * (sizes["head_dim"] + 1) // 2


def layer_params(sizes):
    """Parameters of one layer: q, k, v, gate and output projections, the
    SwiGLU's three matrices, two norms and the two QK-norm gains."""
    d, f, dh = sizes["units"], sizes["hidden"], sizes["head_dim"]
    q, kv = sizes["num_heads"] * dh, sizes["num_kv_heads"] * dh
    return 2 * d * q + 2 * d * kv + d * sizes["num_kv_heads"] + 3 * d * f \
        + 2 * d + 2 * dh


def params_held(sizes):
    """Every parameter the configuration holds: the layers, the embedding,
    the head and the final norm."""
    d = sizes["units"]
    return sizes["num_layers"] * layer_params(sizes) \
        + 2 * sizes["vocab_size"] * d + d


def state_bytes(sizes):
    """Bytes of the state, S and z, that one stream holds over all layers."""
    return sizes["num_layers"] * sizes["num_kv_heads"] * state_entries(sizes) \
        * (sizes["head_dim"] + 1) * STATE_BYTES_PER_EL


def forward_flops(sizes, start, count, heads=1):
    """Forward FLOPs to process ``count`` consecutive tokens, of which
    ``heads`` go through the LM head: per layer the token's matmuls, the
    fold of ``phi(k) v^T`` and ``phi(k)`` into the state under the gate (a
    multiply-add and the gate's multiply an entry) and the read of ``phi(q)``
    against S and z in every query head. ``start`` does not matter: the
    state's size does not depend on the context."""
    d, dh = sizes["units"], sizes["head_dim"]
    matmuls = 2 * (layer_params(sizes) - 2 * d - 2 * dh)
    state = state_entries(sizes) * (dh + 1)
    retention = 3 * sizes["num_kv_heads"] * state \
        + 2 * sizes["num_heads"] * state
    return sizes["num_layers"] * count * (matmuls + retention) \
        + heads * 2 * d * sizes["vocab_size"]


def decode_weight_bytes(sizes, live_tokens):
    """Bytes of the weights one decode step has to read once, however many
    tokens are live: every layer's matrices and gains, the final norm and
    the head. The embedding is gathered, not read."""
    d = sizes["units"]
    return BYTES_PER_EL * (sizes["num_layers"] * layer_params(sizes) + d
                           + sizes["vocab_size"] * d)


def kv_bytes(sizes, context):
    """What stands in the place of K and V: the state, over all layers, that
    a token decoded has to read and write once, whatever its context and
    whatever the program pads."""
    return 2 * state_bytes(sizes)


def retention_step_bytes(sizes, traffic):
    """HBM bytes the step's kernel has to move for one decoded token: its
    stream's state in and out, every layer."""
    return kv_bytes(sizes, 1)
