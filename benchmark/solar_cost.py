"""Bytes a Solar-Open2 decode step has to move, from the source's own keys:
the numerators of ``solar_stream_floor_share.serve``,
``solar_kda_state_roofline_share.serve``,
``solar_paged_attention_roofline_share.serve`` and
``solar_experts_roofline_share.serve``.  Kept with the benchmark (beside
``kimi_cost.py``, ``exaone_cost.py`` and the others) so no PR that claims a
gain can change it.

Why none of those fits this source: ``solar_open2`` names its softmax layers
in ``gqa_layers`` (0-indexed; every other layer is KDA) and has no dense
lead; a KDA layer keeps a matrix state ``[head_dim, num_heads x head_dim]`` a
sequence (64 heads: 4,194,304 B) and has low-rank decay and gate
projections; a softmax layer is grouped-query attention with a gate
projection as wide as its queries, caching K and V of
``num_key_value_heads`` heads; every layer routes over
``num_experts_published`` experts of three matrices, of which this chip holds
``n_routed_experts``, beside a shared one; the head is untied.

Only what must move is counted: each weight once, the experts *hit* and not
the experts held, each live lane's state once in and once out, K and V as
many blocks as the attention fetched, this step's rows of the embedding,
nothing of activations, the convolutions' windows, the norms or the
selection biases, and nothing twice.  So a share of the peak computed from
these cannot pass 100%.
"""

STATE_BYTES_PER_VALUE = 4       # the state is float32 wherever it lives


def gqa_layers(config):
    return sum(l < config["num_hidden_layers"] for l in config["gqa_layers"])


def kda_layers(config):
    return config["num_hidden_layers"] - gqa_layers(config)


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


def gqa_weight_bytes(config, bytes_per_value=2):
    """One gated softmax mixer: ``q``, ``gate`` and ``o`` of ``H x heads D``
    each, ``k`` and ``v`` of ``H x KV heads D``."""
    h, d = config["hidden_size"], config["head_dim"]
    return (3 * h * config["num_attention_heads"] * d
            + 2 * h * config["num_key_value_heads"] * d) * bytes_per_value


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
            + config["n_shared_experts"] * 3 * h
            * config["moe_intermediate_size"]) * bytes_per_value


def experts_hit_bytes_per_step(config, experts_hit_per_layer,
                               bytes_per_value=2):
    """The routed experts a step must read: in each layer every held expert
    that at least one token was routed to (``experts_hit_per_layer``: the
    mean over the layers), once, whole."""
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


def kv_block_bytes(config, block_size, bytes_per_value=2):
    """K and V of one block of one softmax layer."""
    return 2 * block_size * config["num_key_value_heads"] \
        * config["head_dim"] * bytes_per_value


def kv_floor_bytes_per_step(config, blocks_a_layer, block_size,
                            bytes_per_value=2):
    """The K and V the step's attention fetched: ``blocks_a_layer`` (the
    span's ``kv_blocks_read``) in each softmax layer."""
    return gqa_layers(config) * float(blocks_a_layer) \
        * kv_block_bytes(config, block_size, bytes_per_value)


def weight_floor_bytes_per_step(config, experts_hit_per_layer, lanes,
                                bytes_per_value=2):
    """Weights one decode step must read: every mixer, every router and
    shared expert, the held experts hit, the head, a row of the embedding a
    lane."""
    h = config["hidden_size"]
    return kda_layers(config) * kda_weight_bytes(config, bytes_per_value) \
        + gqa_layers(config) * gqa_weight_bytes(config, bytes_per_value) \
        + routed_layers(config) \
        * routed_layer_fixed_bytes(config, bytes_per_value) \
        + experts_hit_bytes_per_step(config, experts_hit_per_layer,
                                     bytes_per_value) \
        + h * config["vocab_size"] * bytes_per_value \
        + lanes * h * bytes_per_value


def stream_floor_bytes_per_step(config, experts_hit_per_layer, lanes,
                                state_lanes, blocks_a_layer, block_size):
    """Everything one decode step must move: the weights, the live lanes'
    state in and out, the K and V fetched."""
    return weight_floor_bytes_per_step(config, experts_hit_per_layer, lanes) \
        + state_traffic_bytes_per_step(config, state_lanes) \
        + kv_floor_bytes_per_step(config, blocks_a_layer, block_size)


def late_attrs(obs, needs, seconds=2.0):
    """The attributes of the window's last ``seconds`` of
    ``serving.decode_step`` spans that carry every key of ``needs``: the
    steps nearest the ones the runner profiles (it profiles the seconds
    *after* the window and records no span meanwhile, PERF.md section 7), so
    that a share's numerator and its kernel time are read at the same
    contexts as nearly as the harness lets them.  Contexts still grow
    between the two, so a numerator from here is if anything too small.  []
    where no span has them."""
    spans = [s for s in obs.get("decode_spans") or []
             if all(key in s.get("attrs", {}) for key in needs)]
    if not spans:
        return []
    end = max(s["ts"] for s in spans)
    return [s["attrs"] for s in spans if s["ts"] >= end - seconds * 1e6]


# the keys that tell this source's configuration from every other cell's
KEYS = ("gqa_layers", "linear_attn_config", "num_experts_published")


def profiled(obs):
    """Is ``obs`` a traced serving run of this source's configuration with a
    device profile to divide by (not another cell, the parent of the PR that
    added this, or a CPU rehearsal)?"""
    config = obs.get("config") or {}
    return bool(obs.get("kind") == "serve" and obs.get("profile")
                and obs.get("peaks") and obs.get("traced_steps")
                and all(key in config for key in KEYS))


def kernel_seconds(obs, kernel):
    """The profile's seconds under the executions whose name starts with
    ``kernel``."""
    return sum(s for name, s in obs["profile"].get("op_seconds", {}).items()
               if name.lstrip("%").startswith(kernel))
