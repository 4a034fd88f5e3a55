"""Bytes a K-EXAONE decode step has to move, from the source's own keys:
the numerators of ``exaone_stream_floor_share.serve`` and
``paged_attention_roofline_share.serve``.  Kept with the benchmark (beside
``moe_cost.py`` and ``lfm2_cost.py``) so no PR that claims a gain can change
it.

Why neither of those fits this source: ``exaone_moe`` names its layers'
feed-forward one by one (``mlp_layer_types``), holds a share of each sparse
layer's experts (``num_experts`` held of ``num_experts_published``) beside a
shared expert every token passes through, has an untied head over a slice
of the vocabulary, and keeps K and V by the layer's kind: a
``full_attention`` layer's whole context, a ``sliding_attention`` layer's
last ``sliding_window`` positions.  Keys read here: ``hidden_size``,
``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
``intermediate_size``, ``moe_intermediate_size``, ``num_shared_experts``,
``num_experts_published``, ``vocab_size``, ``layer_types``,
``mlp_layer_types``.

Only what must move is counted: each weight once, the experts *hit* and not
the experts held, K and V as many blocks as the attention fetched (the
step's span says: a block is the least a paged cache can fetch), this
step's rows of the embedding, nothing of activations, norms or the biases,
and nothing twice.  So a share of the peak computed from these cannot pass
100%.
"""


def attention_weight_bytes(config, bytes_per_value=2):
    """wq, wk, wv, wo of one layer."""
    h = config["hidden_size"]
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    return (2 * h * q + 2 * h * kv) * bytes_per_value


def expert_bytes(config, bytes_per_value=2):
    """One routed (or shared) expert: gate, up and down projections of
    ``hidden_size x moe_intermediate_size`` each."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"] \
        * bytes_per_value


def sparse_layers(config):
    return sum(k == "sparse" for k in config["mlp_layer_types"])


def kv_block_bytes(config, block_size, bytes_per_value=2):
    """K and V of one block of one layer."""
    return 2 * block_size * config["num_key_value_heads"] \
        * config["head_dim"] * bytes_per_value


def weight_floor_bytes_per_step(config, experts_hit_per_layer, lanes,
                                bytes_per_value=2):
    """Weights one decode step must read: every layer's attention
    projections, the dense layers' MLP, and in each sparse layer the router,
    the shared expert and every held expert that at least one token was
    routed to (``experts_hit_per_layer``: the mean over the sparse layers),
    once, whole; the head; a row of the embedding a lane."""
    h = config["hidden_size"]
    n = len(config["mlp_layer_types"])
    dense = n - sparse_layers(config)
    per_sparse = (float(experts_hit_per_layer)
                  + config["num_shared_experts"]) \
        * expert_bytes(config, bytes_per_value) \
        + h * config["num_experts_published"] * bytes_per_value
    return n * attention_weight_bytes(config, bytes_per_value) \
        + dense * 3 * h * config["intermediate_size"] * bytes_per_value \
        + sparse_layers(config) * per_sparse \
        + h * config["vocab_size"] * bytes_per_value \
        + lanes * h * bytes_per_value


def kv_floor_bytes_per_step(config, global_blocks_a_layer,
                            window_blocks_all_layers, block_size,
                            bytes_per_value=2):
    """K and V the step's attention fetched: ``global_blocks_a_layer`` in
    each ``full_attention`` layer (the span's ``kv_blocks_read``) and
    ``window_blocks_all_layers`` over the ``sliding_attention`` layers (its
    ``kv_window_blocks_read``)."""
    full = sum(k == "full_attention" for k in config["layer_types"])
    return (full * float(global_blocks_a_layer)
            + float(window_blocks_all_layers)) \
        * kv_block_bytes(config, block_size, bytes_per_value)
