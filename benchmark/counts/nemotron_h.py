"""Operations and bytes of one chip's share of a Nemotron-H style hybrid
(Mamba-2 mixers, grouped-query attention without rotary, LatentMoE: relu^2
experts in a latent between two projections, a sigmoid router with a
selection bias over all experts, of which ``experts_held`` are here, beside
one shared relu^2 expert; an untied head over the slice of the vocabulary
held), computed from shapes. The layers are ``sizes["pattern"]``, one
letter a layer: ``M`` mixer, ``*`` attention, ``E`` experts.

Every count is of the work the mathematics requires for the tokens
processed, whatever implements it: padding, dead cache capacity, dead slots
and recomputation are not counted. Where the work depends on the router's
choices it is the expectation under even routing: a token picks each expert
with probability ``k / E``. The mixer is counted in its recurrent form,
which is the mechanism: a state of ``mamba_head_dim x state`` a head, folded
and read once a token, whatever the context.

``sizes`` is a configuration file's ``sizes``; ``BYTES_PER_EL`` is the width
of the type the configuration serves its weights, activations and pages in,
``STATE_BYTES_PER_EL`` that of the SSM's state (float32, as the
configuration states).
"""
BYTES_PER_EL = 2
STATE_BYTES_PER_EL = 4


def layers(sizes, kind):
    return sizes["pattern"].count(kind)


def inner(sizes):
    return sizes["mamba_heads"] * sizes["mamba_head_dim"]


def conv_channels(sizes):
    """Channels of the conv: x, B and C."""
    return inner(sizes) + 2 * sizes["n_groups"] * sizes["state"]


def mamba_matmul_params(sizes):
    """The input projection ([z | xBC | dt]) and the output projection."""
    d = sizes["units"]
    return d * (inner(sizes) + conv_channels(sizes) + sizes["mamba_heads"]) \
        + inner(sizes) * d


def mamba_params(sizes):
    """One mixer: its projections, the conv's weight and bias, dt_bias,
    A_log and D a head, the gated norm's gain."""
    return mamba_matmul_params(sizes) \
        + conv_channels(sizes) * (sizes["conv"] + 1) \
        + 3 * sizes["mamba_heads"] + inner(sizes)


def attention_params(sizes):
    """q, k, v and o of one attention layer."""
    d, hd = sizes["units"], sizes["head_dim"]
    return 2 * d * sizes["num_heads"] * hd + 2 * d * sizes["num_kv_heads"] * hd


def moe_fixed_params(sizes):
    """One expert layer outside its routed experts: the router and its
    selection bias, the two latent projections, the shared expert."""
    d, E = sizes["units"], sizes["num_experts"]
    return E * d + E + 2 * d * sizes["moe_latent"] \
        + 2 * d * sizes["shared_hidden"]


def expert_params(sizes):
    """One routed expert: up and down, in the latent."""
    return 2 * sizes["moe_latent"] * sizes["expert_hidden"]


def params_held(sizes):
    """Every parameter the configuration holds: the layers with their
    norms, the experts held, the final norm, embedding and head."""
    d = sizes["units"]
    return layers(sizes, "M") * mamba_params(sizes) \
        + layers(sizes, "*") * attention_params(sizes) \
        + layers(sizes, "E") * (moe_fixed_params(sizes)
                                + sizes["experts_held"]
                                * expert_params(sizes)) \
        + len(sizes["pattern"]) * d + d + 2 * sizes["vocab_size"] * d


def ssm_state_bytes(sizes):
    """The SSM's state of one slot and mixer: heads x head_dim x state."""
    return sizes["mamba_heads"] * sizes["mamba_head_dim"] * sizes["state"] \
        * STATE_BYTES_PER_EL


def snapshot_bytes(sizes):
    """One slot's snapshot of one mixer: the state and the conv's last
    ``conv - 1`` inputs."""
    return ssm_state_bytes(sizes) \
        + (sizes["conv"] - 1) * conv_channels(sizes) * BYTES_PER_EL


def kv_row_bytes(sizes):
    """K and V of one position, all attention layers."""
    return layers(sizes, "*") * 2 * sizes["num_kv_heads"] \
        * sizes["head_dim"] * BYTES_PER_EL


def routed_here(sizes):
    """Routed experts a token is expected to find here: k x held / E."""
    return sizes["experts_per_token"] * sizes["experts_held"] \
        / sizes["num_experts"]


def forward_flops(sizes, start, count, heads=1):
    """Forward FLOPs to process ``count`` consecutive tokens at positions
    ``start .. start+count-1``, of which ``heads`` go through the LM head:
    per token every matmul twice its parameters (the routed experts
    expected here), the conv (a multiply-add a weight), the mixer's fold
    (decay, outer product, add: 3 an entry of the state) and read (2 an
    entry), and the attention's scores and values over the positions each
    token attends to."""
    d = sizes["units"]
    macs = layers(sizes, "M") * mamba_matmul_params(sizes) \
        + layers(sizes, "*") * attention_params(sizes) \
        + layers(sizes, "E") * (sizes["num_experts"] * d
                                + 2 * d * sizes["moe_latent"]
                                + 2 * d * sizes["shared_hidden"]
                                + routed_here(sizes) * expert_params(sizes))
    state = sizes["mamba_heads"] * sizes["mamba_head_dim"] * sizes["state"]
    mixer = 2 * sizes["conv"] * conv_channels(sizes) + 5 * state
    attended = count * start + count * (count + 1) // 2
    return 2 * count * macs + layers(sizes, "M") * count * mixer \
        + layers(sizes, "*") * 4 * sizes["num_heads"] * sizes["head_dim"] \
        * attended + heads * 2 * d * sizes["vocab_size"]


def experts_touched(sizes, live_tokens):
    """Expected share of the experts held that ``live_tokens`` tokens
    touch: 1 - (1 - k/E)^live_tokens each."""
    miss = 1.0 - sizes["experts_per_token"] / sizes["num_experts"]
    return 1.0 - miss ** live_tokens


def decode_weight_bytes(sizes, live_tokens):
    """Bytes of the weights one decode step over ``live_tokens`` tokens has
    to read once: every mixer, attention layer and expert layer outside its
    routed experts, the norms, the final norm, the head (read whole), the
    live tokens' rows of the embedding and, of the experts held, those the
    live tokens are expected to touch."""
    d = sizes["units"]
    fixed = layers(sizes, "M") * mamba_params(sizes) \
        + layers(sizes, "*") * attention_params(sizes) \
        + layers(sizes, "E") * moe_fixed_params(sizes) \
        + len(sizes["pattern"]) * d + d + sizes["vocab_size"] * d \
        + live_tokens * d
    return BYTES_PER_EL * fixed + layers(sizes, "E") * BYTES_PER_EL \
        * sizes["experts_held"] * expert_params(sizes) \
        * experts_touched(sizes, live_tokens)


def kv_bytes(sizes, context):
    """What a token decoded at ``context`` live positions has to read and
    write of its cache: the attention layers' K and V rows, and every
    mixer's state and conv window read once and written once, whatever the
    context."""
    return kv_row_bytes(sizes) * context \
        + 2 * layers(sizes, "M") * snapshot_bytes(sizes)


def kv_cache_write_bytes(sizes, traffic):
    """HBM bytes the K/V write of one decoded token has to move: its own
    row, the K and the V of one position in every attention layer."""
    return kv_row_bytes(sizes)


def ssd_step_bytes(sizes, traffic):
    """HBM bytes the mixer's step kernel has to move for one decoded token,
    all mixers: the state read once and written once, and what it reads of
    the token (dt x, the decay, B and C) and writes (y), float32."""
    H, P = sizes["mamba_heads"], sizes["mamba_head_dim"]
    token = (2 * H * P + H + 2 * sizes["n_groups"] * sizes["state"]) \
        * STATE_BYTES_PER_EL
    return layers(sizes, "M") * (2 * ssm_state_bytes(sizes) + token)


def moe_ffn_touched_bytes(sizes, experts_hit, tokens):
    """HBM bytes the grouped expert FFN has to move for steps that put picks
    on ``experts_hit`` (layer, expert) pairs in all and decoded ``tokens``
    tokens in all: one expert's two matrices a pair, and the routed rows in
    and out, in the latent, in every expert layer. The pairs are the
    program's own count (``xhit=`` on its step spans)."""
    rows = tokens * routed_here(sizes) * layers(sizes, "E")
    return experts_hit * BYTES_PER_EL * expert_params(sizes) \
        + 2 * rows * sizes["moe_latent"] * BYTES_PER_EL


def moe_ffn_bytes(sizes, live_tokens):
    """HBM bytes the grouped expert FFN of one decode step over
    ``live_tokens`` tokens has to move, all expert layers: the matrices of
    the experts touched, and the routed rows in and out."""
    rows = live_tokens * routed_here(sizes)
    return layers(sizes, "E") * (
        BYTES_PER_EL * sizes["experts_held"] * expert_params(sizes)
        * experts_touched(sizes, live_tokens)
        + 2 * rows * sizes["moe_latent"] * BYTES_PER_EL)
