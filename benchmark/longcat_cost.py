"""Bytes and operations a LongCat-Flash decode step needs, from the source's
own keys: the numerators of ``longcat_stream_floor_share.serve``,
``longcat_latent_attention_roofline_share.serve`` and
``longcat_experts_roofline_share.serve``.  Kept with the benchmark (beside
``moe_cost.py``, ``exaone_cost.py``, ``nemotron_cost.py``, ``kimi_cost.py``,
``dots_cost.py``, ``smallthinker_cost.py`` and ``glm_cost.py``) so no PR that
claims a gain can change it.

Why none of those fits this source: a layer (``num_layers`` of them) is a
PAIR of sublayers, each an MLA mixer and a dense MLP of ``ffn_hidden_size``,
round one router of ``num_experts_published + zero_expert_num`` outputs, with
no shared expert and no dense lead; ``dots_cost`` counts one mixer and one
feed-forward a layer, a shared expert and a router as wide as its experts,
under DeepSeek-V3's keys.  The identity experts have no weights: a chosen
one costs no byte here.

Only what must move is counted: each weight once, the experts *hit* and not
the experts held, latent rows as many blocks as the attention fetched and
the values of a row (576, not the 640 its pool holds it in), this step's
rows of the embedding, nothing of activations, the norms or the selection
biases, and nothing twice.  The operations are the absorbed form's, whatever
implements it: a position costs a head ``2 (rank + rope)`` for its score and
``2 rank`` for its value.  So a share of a peak computed from these cannot
pass 100%.
"""


def layers(config):
    """The source's layers: pairs of sublayers."""
    return config["num_layers"]


def sublayers(config):
    return 2 * config["num_layers"]


def latent_weight_bytes(config, bytes_per_value=2):
    """One MLA mixer: ``q_a``, its norm, ``q_b``, ``kv_a``, its norm,
    ``kv_b``, ``o_proj``."""
    h, heads = config["hidden_size"], config["num_attention_heads"]
    nope, rope, dv, rank = config["qk_nope_head_dim"], \
        config["qk_rope_head_dim"], config["v_head_dim"], \
        config["kv_lora_rank"]
    qr = config["q_lora_rank"]
    return (h * qr + qr + qr * heads * (nope + rope) + h * (rank + rope)
            + rank + rank * heads * (nope + dv) + heads * dv * h) \
        * bytes_per_value


def dense_mlp_bytes(config, bytes_per_value=2):
    """One sublayer's gated MLP."""
    return 3 * config["hidden_size"] * config["ffn_hidden_size"] \
        * bytes_per_value


def router_bytes(config, bytes_per_value=2):
    """A layer's router, over the experts and the identity experts."""
    return config["hidden_size"] * (config["num_experts_published"]
                                    + config["zero_expert_num"]) \
        * bytes_per_value


def expert_bytes(config, bytes_per_value=2):
    """One routed expert: gate, up and down of ``hidden_size x
    expert_ffn_hidden_size`` each."""
    return 3 * config["hidden_size"] * config["expert_ffn_hidden_size"] \
        * bytes_per_value


def experts_hit_bytes_per_step(config, experts_hit_per_layer,
                               bytes_per_value=2):
    """The routed experts a step must read: in each layer every held expert
    that at least one token was routed to (``experts_hit_per_layer``: the
    mean over the layers), once, whole."""
    return layers(config) * float(experts_hit_per_layer) \
        * expert_bytes(config, bytes_per_value)


def latent_block_bytes(config, block_size, bytes_per_value=2):
    """The values of one block of one latent sublayer: a row a token."""
    return block_size * (config["kv_lora_rank"]
                         + config["qk_rope_head_dim"]) * bytes_per_value


def latent_floor_bytes_per_step(config, blocks_a_layer, block_size,
                                bytes_per_value=2):
    """The rows the step's latent attention fetched: ``blocks_a_layer`` (the
    span's ``latent_blocks_read``) in each sublayer."""
    return sublayers(config) * float(blocks_a_layer) \
        * latent_block_bytes(config, block_size, bytes_per_value)


def latent_flops_per_step(config, blocks_a_layer, block_size):
    """The operations of the absorbed attention over those rows: every head
    against every position fetched, a multiply and an add a value of its
    score (``rank + rope`` values) and of its output (``rank``).  A lane's
    last block counts whole though its context may end inside it: the time
    measured covers the whole block too (the kernel multiplies it)."""
    rank, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    return sublayers(config) * float(blocks_a_layer) * block_size \
        * config["num_attention_heads"] * 2 * (2 * rank + rope)


def weight_floor_bytes_per_step(config, experts_hit_per_layer, lanes,
                                bytes_per_value=2):
    """Weights one decode step must read: every sublayer's mixer and dense
    MLP, every router, the held experts hit, the head, a row of the
    embedding a lane."""
    h = config["hidden_size"]
    return sublayers(config) * (latent_weight_bytes(config, bytes_per_value)
                                + dense_mlp_bytes(config, bytes_per_value)) \
        + layers(config) * router_bytes(config, bytes_per_value) \
        + experts_hit_bytes_per_step(config, experts_hit_per_layer,
                                     bytes_per_value) \
        + h * config["vocab_size"] * bytes_per_value \
        + lanes * h * bytes_per_value


def stream_floor_bytes_per_step(config, experts_hit_per_layer, lanes,
                                blocks_a_layer, block_size):
    """Everything one decode step must move: the weights and the latent
    rows fetched."""
    return weight_floor_bytes_per_step(config, experts_hit_per_layer, lanes) \
        + latent_floor_bytes_per_step(config, blocks_a_layer, block_size)
