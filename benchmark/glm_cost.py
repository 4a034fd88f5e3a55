"""Bytes and operations a GLM-5 decode step needs, from the source's own
keys: the numerators of ``glm_stream_floor_share.serve``,
``glm_index_scores_roofline_share.serve``,
``glm_sparse_attention_roofline_share.serve`` and
``glm_experts_roofline_share.serve``.  Kept with the benchmark (beside
``dots_cost.py`` and the others) so no PR that claims a gain can change it.

Why none of those fits this source: its latent attention reads the rows a
learned indexer chooses (``index_topk`` of a lane's context at most) and not
a lane's blocks in order, every layer holds the indexer's weights and a pool
of its keys (``index_n_heads``, ``index_head_dim``) beside the latent rows,
and a head's key and value widths differ (``qk_nope_head_dim``,
``v_head_dim``).  The routed layer's keys are DeepSeek-V3's, as dots.vlm1's.

Only what must move is counted, whatever implements the step: each weight
once, the experts *hit* and not the experts held, an index key of every
position of every live block the scores walked (a block is the least a paged
cache can fetch), the values of every row attention read (576 a row, not the
640 its pool holds it in; the rows chosen, not the rows in context), this
step's rows of the embedding, nothing of activations, the norms or the
selection biases, and nothing twice.  The operations are the absorbed form's
over the rows read and the indexer's over the positions scored.  So a share
of a peak computed from these cannot pass 100%.
"""

KEYS = ("index_topk", "index_n_heads", "index_head_dim", "kv_lora_rank",
        "q_lora_rank", "num_experts_published")


def layers(config):
    return config["num_hidden_layers"]


def routed_layers(config):
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def latent_weight_bytes(config, bytes_per_value=2):
    """One MLA mixer: ``q_a``, its norm, ``q_b``, ``kv_a``, its norm,
    ``kv_b`` (a head's key part and its wider value), ``o_proj``."""
    h, heads = config["hidden_size"], config["num_attention_heads"]
    nope, rope, dv, rank = config["qk_nope_head_dim"], \
        config["qk_rope_head_dim"], config["v_head_dim"], \
        config["kv_lora_rank"]
    qr = config["q_lora_rank"]
    return (h * qr + qr + qr * heads * (nope + rope) + h * (rank + rope)
            + rank + rank * heads * (nope + dv) + heads * dv * h) \
        * bytes_per_value


def indexer_weight_bytes(config, bytes_per_value=2):
    """One layer's indexer: the index queries' projection from the
    compressed query, the key's from the stream with its LayerNorm's weight
    and bias, the heads' weights' projection."""
    h, qr = config["hidden_size"], config["q_lora_rank"]
    ih, idim = config["index_n_heads"], config["index_head_dim"]
    return (qr * ih * idim + h * idim + 2 * idim + h * ih) * bytes_per_value


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


def dense_layer_bytes(config, bytes_per_value=2):
    return 3 * config["hidden_size"] * config["intermediate_size"] \
        * bytes_per_value


def experts_hit_bytes_per_step(config, experts_hit_per_layer,
                               bytes_per_value=2):
    """The routed experts a step must read: in each routed layer every held
    expert that at least one token was routed to, once, whole."""
    return routed_layers(config) * float(experts_hit_per_layer) \
        * expert_bytes(config, bytes_per_value)


def index_block_bytes(config, block_size, bytes_per_value=2):
    """The index keys of one block of one layer: a key a token."""
    return block_size * config["index_head_dim"] * bytes_per_value


def index_floor_bytes_per_step(config, blocks_a_layer, block_size,
                               bytes_per_value=2):
    """The index keys the step's scores walked: ``blocks_a_layer`` (the
    span's ``index_blocks_read``) in each layer."""
    return layers(config) * float(blocks_a_layer) \
        * index_block_bytes(config, block_size, bytes_per_value)


def index_flops_per_step(config, blocks_a_layer, block_size):
    """The indexer's operations over those positions: every index head
    against every key walked, a multiply and an add a value, then a ReLU, a
    weight and an add a head (3 more a head a position)."""
    ih, idim = config["index_n_heads"], config["index_head_dim"]
    return layers(config) * float(blocks_a_layer) * block_size \
        * ih * (2 * idim + 3)


def latent_row_bytes(config, bytes_per_value=2):
    """The values of one latent row: ``[c | k_pe]``."""
    return (config["kv_lora_rank"] + config["qk_rope_head_dim"]) \
        * bytes_per_value


def selected_floor_bytes_per_step(config, rows_selected, lanes,
                                  bytes_per_value=2):
    """What attention over the chosen rows must move in every layer: the
    rows themselves (``rows_selected``: the span's ``latent_rows_selected``,
    summed over the lanes) and each live lane's absorbed queries and latent
    outputs, float32 as the kernel takes and gives them."""
    heads, rank = config["num_attention_heads"], config["kv_lora_rank"]
    width = rank + config["qk_rope_head_dim"]
    return layers(config) * (
        float(rows_selected) * latent_row_bytes(config, bytes_per_value)
        + float(lanes) * heads * (width + rank) * 4)


def selected_flops_per_step(config, rows_selected):
    """The operations of the absorbed attention over the chosen rows: every
    head against every row read, a multiply and an add a value of its score
    (``rank + rope`` values) and of its output (``rank``)."""
    rank, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    return layers(config) * float(rows_selected) \
        * config["num_attention_heads"] * 2 * (2 * rank + rope)


def weight_floor_bytes_per_step(config, experts_hit_per_layer, lanes,
                                bytes_per_value=2):
    """Weights one decode step must read: every mixer and indexer, the dense
    lead, every router and shared expert, the held experts hit, the head, a
    row of the embedding a lane."""
    h = config["hidden_size"]
    return layers(config) * (latent_weight_bytes(config, bytes_per_value)
                             + indexer_weight_bytes(config, bytes_per_value)) \
        + config["first_k_dense_replace"] \
        * dense_layer_bytes(config, bytes_per_value) \
        + routed_layers(config) \
        * routed_layer_fixed_bytes(config, bytes_per_value) \
        + experts_hit_bytes_per_step(config, experts_hit_per_layer,
                                     bytes_per_value) \
        + h * config["vocab_size"] * bytes_per_value \
        + lanes * h * bytes_per_value


def stream_floor_bytes_per_step(config, experts_hit_per_layer, lanes,
                                index_blocks_a_layer, rows_selected,
                                block_size):
    """Everything one decode step must move: the weights, the index keys
    its scores walked and the rows its attention read."""
    return weight_floor_bytes_per_step(config, experts_hit_per_layer, lanes) \
        + index_floor_bytes_per_step(config, index_blocks_a_layer,
                                     block_size) \
        + layers(config) * float(rows_selected) * latent_row_bytes(config)
