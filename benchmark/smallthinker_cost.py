"""Bytes a SmallThinker decode step has to move, from the source's own keys:
the numerators of ``smallthinker_stream_floor_share.serve`` and
``smallthinker_experts_roofline_share.serve``.  Kept with the benchmark
(beside ``moe_cost.py`` and ``exaone_cost.py``) so no PR that claims a gain
can change it.

Why neither of those fits this source: ``smallthinker`` names its experts
``moe_num_primary_experts`` of width ``moe_ffn_hidden_size``, has no shared
expert, no dense layer and no share (every expert of a layer is held), an
untied head over the whole vocabulary, a stream (``hidden_size``) narrower
than its query heads together, and keeps K and V by the layer's kind
(``sliding_window_layout``: 1, the last ``sliding_window_size`` positions;
0, the whole context).  Keys read here: ``hidden_size``,
``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
``moe_ffn_hidden_size``, ``moe_num_primary_experts``, ``vocab_size``,
``num_hidden_layers``, ``sliding_window_layout``.

Only what must move is counted, whatever implements the step: each weight
once, the experts *hit* and not the experts held, K and V as many blocks as
the attention fetched (the step's span says, by kind of layer: a block is
the least a paged cache can fetch), this step's rows of the embedding,
nothing of activations or norms, and nothing twice.  So a share of the peak
computed from these cannot pass 100%.
"""

KEYS = ("moe_num_primary_experts", "moe_ffn_hidden_size",
        "sliding_window_layout")


def attention_weight_bytes(config, bytes_per_value=2):
    """wq, wk, wv, wo of one layer."""
    h = config["hidden_size"]
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    return (2 * h * q + 2 * h * kv) * bytes_per_value


def expert_bytes(config, bytes_per_value=2):
    """One expert: gate, up and down projections of ``hidden_size x
    moe_ffn_hidden_size`` each."""
    return 3 * config["hidden_size"] * config["moe_ffn_hidden_size"] \
        * bytes_per_value


def experts_hit_bytes_per_step(config, experts_hit_per_layer,
                               bytes_per_value=2):
    """The experts a step's routing hit, read once in every layer
    (``experts_hit_per_layer``: the mean over the layers)."""
    return config["num_hidden_layers"] * float(experts_hit_per_layer) \
        * expert_bytes(config, bytes_per_value)


def weight_floor_bytes_per_step(config, experts_hit_per_layer, lanes,
                                bytes_per_value=2):
    """Weights one decode step must read: every layer's attention
    projections and router and every expert that at least one token was
    routed to, once, whole; the head; a row of the embedding a lane."""
    h = config["hidden_size"]
    n = config["num_hidden_layers"]
    return n * (attention_weight_bytes(config, bytes_per_value)
                + h * config["moe_num_primary_experts"] * bytes_per_value) \
        + experts_hit_bytes_per_step(config, experts_hit_per_layer,
                                     bytes_per_value) \
        + h * config["vocab_size"] * bytes_per_value \
        + lanes * h * bytes_per_value


def kv_block_bytes(config, block_size, bytes_per_value=2):
    """K and V of one block of one layer."""
    return 2 * block_size * config["num_key_value_heads"] \
        * config["head_dim"] * bytes_per_value


def kv_floor_bytes_per_step(config, global_blocks_a_layer,
                            window_blocks_all_layers, block_size,
                            bytes_per_value=2):
    """K and V the step's attention fetched: ``global_blocks_a_layer`` in
    each layer that attends its whole context (the span's
    ``kv_blocks_read``) and ``window_blocks_all_layers`` over the window
    layers (its ``kv_window_blocks_read``)."""
    full = sum(not w for w in config["sliding_window_layout"])
    return (full * float(global_blocks_a_layer)
            + float(window_blocks_all_layers)) \
        * kv_block_bytes(config, block_size, bytes_per_value)
