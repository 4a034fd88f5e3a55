"""Bytes a Kimi-Linear decode step has to move, from the source's own keys:
the numerators of ``kimi_stream_floor_share.serve``,
``kimi_kda_state_roofline_share.serve`` and
``kimi_latent_attention_roofline_share.serve``.  Kept with the benchmark
(beside ``moe_cost.py``, ``ssm_cost.py``, ``lfm2_cost.py``,
``exaone_cost.py`` and ``nemotron_cost.py``) so no PR that claims a gain can
change it.

Why none of those fits this source: ``kimi_linear`` names its layers in two
1-indexed lists of ``linear_attn_config`` (``kda_layers``,
``full_attn_layers``); a KDA layer keeps a matrix state ``[head_dim, num_heads
x head_dim]`` a sequence and has low-rank decay and gate projections; a
full-attention layer is MLA, which caches ONE row of ``kv_lora_rank +
qk_rope_head_dim`` values a token and not K and V; every layer after
``first_k_dense_replace`` routes over ``num_experts_published`` experts of
three matrices, of which this chip holds ``num_experts``, beside a shared
one; the head is untied and whole.

Only what must move is counted: each weight once, the experts *hit* and not
the experts held, each live lane's state once in and once out, latent rows
as many blocks as the attention fetched and the values of a row (576, not
the 640 its pool holds it in), this step's rows of the embedding, nothing of
activations, the convolutions' windows, the norms or the selection biases,
and nothing twice.  So a share of the peak computed from these cannot pass
100%.
"""

STATE_BYTES_PER_VALUE = 4       # the state is float32 wherever it lives


def kda_layers(config):
    return len(config["linear_attn_config"]["kda_layers"])


def latent_layers(config):
    return len(config["linear_attn_config"]["full_attn_layers"])


def routed_layers(config):
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def kda_weight_bytes(config, bytes_per_value=2):
    """One KDA mixer: q, k, v, o ``H x I`` each; the decay's and the output
    gate's low-rank pairs ``H x D`` + ``D x I``; ``b_proj H x heads``; three
    depthwise convolutions ``I x K``; ``A_log`` a head, ``dt_bias`` a
    channel, ``o_norm`` ``D``."""
    linear = config["linear_attn_config"]
    h, heads, d = config["hidden_size"], linear["num_heads"], \
        linear["head_dim"]
    inner = heads * d
    return (4 * h * inner + 2 * (h * d + d * inner) + h * heads
            + 3 * inner * linear["short_conv_kernel_size"]
            + heads + inner + d) * bytes_per_value


def latent_weight_bytes(config, bytes_per_value=2):
    """One MLA mixer: ``q_proj``, ``kv_a``, its norm, ``kv_b``, ``o_proj``."""
    h, heads = config["hidden_size"], config["num_attention_heads"]
    nope, rope, dv, rank = config["qk_nope_head_dim"], \
        config["qk_rope_head_dim"], config["v_head_dim"], \
        config["kv_lora_rank"]
    return (h * heads * (nope + rope) + h * (rank + rope) + rank
            + rank * heads * (nope + dv) + heads * dv * h) * bytes_per_value


def expert_bytes(config, bytes_per_value=2):
    """One routed expert: gate, up and down of ``hidden_size x
    moe_intermediate_size`` each."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"] \
        * bytes_per_value


def routed_layer_fixed_bytes(config, bytes_per_value=2):
    """What a routed layer reads whatever was hit: the router over all the
    published experts and the shared expert."""
    h = config["hidden_size"]
    return (h * config["num_experts_published"]
            + config["num_shared_experts"] * 3 * h
            * config["moe_intermediate_size"]) * bytes_per_value


def dense_layer_bytes(config, bytes_per_value=2):
    return 3 * config["hidden_size"] * config["intermediate_size"] \
        * bytes_per_value


def experts_hit_bytes_per_step(config, experts_hit_per_layer,
                               bytes_per_value=2):
    """The routed experts a step must read: in each routed layer every held
    expert that at least one token was routed to (``experts_hit_per_layer``:
    the mean over those layers), once, whole."""
    return routed_layers(config) * float(experts_hit_per_layer) \
        * expert_bytes(config, bytes_per_value)


def state_bytes_per_sequence_layer(config):
    linear = config["linear_attn_config"]
    return linear["num_heads"] * linear["head_dim"] ** 2 \
        * STATE_BYTES_PER_VALUE


def state_traffic_bytes_per_step(config, live_lanes):
    """Each live lane's state in every KDA layer, read once and written
    once."""
    return 2 * float(live_lanes) * kda_layers(config) \
        * state_bytes_per_sequence_layer(config)


def latent_block_bytes(config, block_size, bytes_per_value=2):
    """The values of one block of one latent layer: a row a token."""
    return block_size * (config["kv_lora_rank"]
                         + config["qk_rope_head_dim"]) * bytes_per_value


def latent_floor_bytes_per_step(config, blocks_a_layer, block_size,
                                bytes_per_value=2):
    """The rows the step's latent attention fetched: ``blocks_a_layer`` (the
    span's ``latent_blocks_read``) in each latent layer."""
    return latent_layers(config) * float(blocks_a_layer) \
        * latent_block_bytes(config, block_size, bytes_per_value)


def weight_floor_bytes_per_step(config, experts_hit_per_layer, lanes,
                                bytes_per_value=2):
    """Weights one decode step must read: every mixer, the dense lead, every
    router and shared expert, the held experts hit, the head, a row of the
    embedding a lane."""
    h = config["hidden_size"]
    return kda_layers(config) * kda_weight_bytes(config, bytes_per_value) \
        + latent_layers(config) \
        * latent_weight_bytes(config, bytes_per_value) \
        + config["first_k_dense_replace"] \
        * dense_layer_bytes(config, bytes_per_value) \
        + routed_layers(config) \
        * routed_layer_fixed_bytes(config, bytes_per_value) \
        + experts_hit_bytes_per_step(config, experts_hit_per_layer,
                                     bytes_per_value) \
        + h * config["vocab_size"] * bytes_per_value \
        + lanes * h * bytes_per_value


def stream_floor_bytes_per_step(config, experts_hit_per_layer, lanes,
                                state_lanes, blocks_a_layer, block_size):
    """Everything one decode step must move: the weights, the live lanes'
    state in and out, the latent rows fetched."""
    return weight_floor_bytes_per_step(config, experts_hit_per_layer, lanes) \
        + state_traffic_bytes_per_step(config, state_lanes) \
        + latent_floor_bytes_per_step(config, blocks_a_layer, block_size)
