"""Bytes and operations a dots.vlm1 decode step needs, from the source's own
keys: the numerators of ``dots_stream_floor_share.serve``,
``dots_latent_attention_roofline_share.serve`` and
``dots_experts_roofline_share.serve``.  Kept with the benchmark (beside
``moe_cost.py``, ``exaone_cost.py``, ``nemotron_cost.py`` and
``kimi_cost.py``) so no PR that claims a gain can change it.

Why none of those fits this source: every layer is MLA (``kimi_cost`` counts
KDA mixers beside seven of them), its query goes through a low-rank pair
(``q_lora_rank``), and its 128 heads over one cached row of ``kv_lora_rank +
qk_rope_head_dim`` values put the attention at the chip's ridge, so its
least time is the larger of its bytes over the memory's rate and its
operations over the matrix unit's: this file counts both.  The keys are
DeepSeek-V3's (``n_routed_experts``, ``n_shared_experts``,
``num_experts_per_tok``), not Kimi-Linear's.

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
    return config["num_hidden_layers"]


def routed_layers(config):
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def latent_weight_bytes(config, bytes_per_value=2):
    """One MLA mixer: ``q_a``, its norm, ``q_b`` (or ``q_proj`` whole where
    ``q_lora_rank`` is null), ``kv_a``, its norm, ``kv_b``, ``o_proj``."""
    h, heads = config["hidden_size"], config["num_attention_heads"]
    nope, rope, dv, rank = config["qk_nope_head_dim"], \
        config["qk_rope_head_dim"], config["v_head_dim"], \
        config["kv_lora_rank"]
    qr = config["q_lora_rank"]
    query = h * qr + qr + qr * heads * (nope + rope) if qr \
        else h * heads * (nope + rope)
    return (query + h * (rank + rope) + rank
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
            + config["n_shared_experts"] * 3 * h
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


def latent_block_bytes(config, block_size, bytes_per_value=2):
    """The values of one block of one latent layer: a row a token."""
    return block_size * (config["kv_lora_rank"]
                         + config["qk_rope_head_dim"]) * bytes_per_value


def latent_floor_bytes_per_step(config, blocks_a_layer, block_size,
                                bytes_per_value=2):
    """The rows the step's latent attention fetched: ``blocks_a_layer`` (the
    span's ``latent_blocks_read``) in each layer."""
    return layers(config) * float(blocks_a_layer) \
        * latent_block_bytes(config, block_size, bytes_per_value)


def latent_flops_per_step(config, blocks_a_layer, block_size):
    """The operations of the absorbed attention over those rows: every head
    against every position fetched, a multiply and an add a value of its
    score (``rank + rope`` values) and of its output (``rank``).  A lane's
    last block counts whole though its context may end inside it: at
    contexts of thousands that is under 1% too many, and too many in a
    numerator only ever lowers a ceiling's distance, never past it (the
    time measured covers the whole block too: the kernel multiplies it)."""
    rank, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    return layers(config) * float(blocks_a_layer) * block_size \
        * config["num_attention_heads"] * 2 * (2 * rank + rope)


def weight_floor_bytes_per_step(config, experts_hit_per_layer, lanes,
                                bytes_per_value=2):
    """Weights one decode step must read: every mixer, the dense lead, every
    router and shared expert, the held experts hit, the head, a row of the
    embedding a lane."""
    h = config["hidden_size"]
    return layers(config) * latent_weight_bytes(config, bytes_per_value) \
        + config["first_k_dense_replace"] \
        * dense_layer_bytes(config, bytes_per_value) \
        + routed_layers(config) \
        * routed_layer_fixed_bytes(config, bytes_per_value) \
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
