"""Bytes a Nemotron-H decode step has to move, from the source's own keys:
the numerators of ``nemotron_stream_floor_share.serve``,
``nemotron_experts_roofline_share.serve`` and
``nemotron_ssm_update_roofline_share.serve``.  Kept with the benchmark
(beside ``moe_cost.py``, ``ssm_cost.py``, ``lfm2_cost.py`` and
``exaone_cost.py``) so no PR that claims a gain can change it.

Why none of those fits this source: ``nemotron_h`` names its layers by a
letter each of ``hybrid_override_pattern`` (``M`` a Mamba-2 mixer, ``*``
attention, ``E`` experts) and a layer is that one sublayer alone; B and C
come in ``n_groups`` groups, so the convolution is ``I + 2 G N`` wide; an
expert is two matrices (``mlp_hidden_act`` relu2: no gate) of
``hidden_size x moe_intermediate_size``, the shared one of
``moe_shared_expert_intermediate_size``; it holds a share of each experts
layer (``n_routed_experts`` held of ``n_routed_experts_published``) and a
slice of the vocabulary under an untied head.  Keys read here:
``hybrid_override_pattern``, ``hidden_size``, ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``mamba_num_heads``,
``mamba_head_dim``, ``ssm_state_size``, ``n_groups``, ``conv_kernel``,
``moe_intermediate_size``, ``moe_shared_expert_intermediate_size``,
``n_shared_experts``, ``n_routed_experts_published``, ``vocab_size``.

Only what must move is counted: each weight once, the experts *hit* and not
the experts held, each live lane's state once in and once out, K and V as
many blocks as the attention fetched (the step's span says: a block is the
least a paged cache can fetch), this step's rows of the embedding, nothing
of activations, the convolution's windows, the block norms or the selection
biases, and nothing twice.  So a share of the peak computed from these
cannot pass 100%.
"""

STATE_BYTES_PER_VALUE = 4       # the state is float32 wherever it lives


def layers_of(config, letter):
    return config["hybrid_override_pattern"].count(letter)


def mamba_weight_bytes(config, bytes_per_value=2):
    """One mamba layer's mixer: in_proj ``[H, 2 I + 2 G N + heads]``, the
    depthwise convolution and its bias over ``I + 2 G N`` channels,
    ``dt_bias``, ``A_log`` and ``D`` a head, the gated norm over ``I``,
    out_proj ``[I, H]``."""
    h, heads = config["hidden_size"], config["mamba_num_heads"]
    inner = heads * config["mamba_head_dim"]
    conv_dim = inner + 2 * config["n_groups"] * config["ssm_state_size"]
    return (h * (inner + conv_dim + heads)
            + conv_dim * (config["conv_kernel"] + 1)
            + 3 * heads + inner + inner * h) * bytes_per_value


def attention_weight_bytes(config, bytes_per_value=2):
    """wq, wk, wv, wo of one attention layer."""
    h = config["hidden_size"]
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    return (2 * h * q + 2 * h * kv) * bytes_per_value


def expert_bytes(config, bytes_per_value=2):
    """One routed expert: up and down of ``hidden_size x
    moe_intermediate_size`` each."""
    return 2 * config["hidden_size"] * config["moe_intermediate_size"] \
        * bytes_per_value


def experts_layer_fixed_bytes(config, bytes_per_value=2):
    """What an experts layer reads whatever was hit: the router over all
    the published experts and the shared expert."""
    h = config["hidden_size"]
    return (h * config["n_routed_experts_published"]
            + config["n_shared_experts"] * 2 * h
            * config["moe_shared_expert_intermediate_size"]) \
        * bytes_per_value


def experts_hit_bytes_per_step(config, experts_hit_per_layer,
                               bytes_per_value=2):
    """The routed experts a step must read: in each experts layer every
    held expert that at least one token was routed to
    (``experts_hit_per_layer``: the mean over those layers), once, whole."""
    return layers_of(config, "E") * float(experts_hit_per_layer) \
        * expert_bytes(config, bytes_per_value)


def state_bytes_per_sequence_layer(config):
    return config["mamba_num_heads"] * config["mamba_head_dim"] \
        * config["ssm_state_size"] * STATE_BYTES_PER_VALUE


def state_traffic_bytes_per_step(config, live_lanes):
    """Each live lane's state in every mamba layer, read once and written
    once."""
    return 2 * float(live_lanes) * layers_of(config, "M") \
        * state_bytes_per_sequence_layer(config)


def kv_block_bytes(config, block_size, bytes_per_value=2):
    """K and V of one block of one attention layer."""
    return 2 * block_size * config["num_key_value_heads"] \
        * config["head_dim"] * bytes_per_value


def kv_floor_bytes_per_step(config, blocks_a_layer, block_size,
                            bytes_per_value=2):
    """K and V the step's attention fetched: ``blocks_a_layer`` (the span's
    ``kv_blocks_read``) in each attention layer."""
    return layers_of(config, "*") * float(blocks_a_layer) \
        * kv_block_bytes(config, block_size, bytes_per_value)


def weight_floor_bytes_per_step(config, experts_hit_per_layer, lanes,
                                bytes_per_value=2):
    """Weights one decode step must read: every mixer, every attention
    layer's projections, every router and shared expert, the held experts
    hit, the head, a row of the embedding a lane."""
    h = config["hidden_size"]
    return layers_of(config, "M") * mamba_weight_bytes(config, bytes_per_value) \
        + layers_of(config, "*") \
        * attention_weight_bytes(config, bytes_per_value) \
        + layers_of(config, "E") \
        * experts_layer_fixed_bytes(config, bytes_per_value) \
        + experts_hit_bytes_per_step(config, experts_hit_per_layer,
                                     bytes_per_value) \
        + h * config["vocab_size"] * bytes_per_value \
        + lanes * h * bytes_per_value


def stream_floor_bytes_per_step(config, experts_hit_per_layer, lanes,
                                state_lanes, blocks_a_layer, block_size):
    """Everything one decode step must move: the weights, the live lanes'
    state in and out, the K and V blocks fetched."""
    return weight_floor_bytes_per_step(config, experts_hit_per_layer, lanes) \
        + state_traffic_bytes_per_step(config, state_lanes) \
        + kv_floor_bytes_per_step(config, blocks_a_layer, block_size)
