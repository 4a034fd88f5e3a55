"""Bytes the routed-expert layers of an LFM2-MoE decode step have to read,
from the source's own keys: the numerator of
``moe_routed_stream_floor_share.serve``.  Kept with the benchmark (beside
``moe_cost.py``) so no PR that claims a gain can change it.

Why ``moe_cost.py`` does not fit this source: it reads ``intermediate_size``
as the width of one expert and ``num_hidden_layers`` as the layers that
route, which is what OLMoE's config.json means by them.  ``lfm2_moe`` gives
the experts a key of their own (``moe_intermediate_size``, 1536) and keeps
``intermediate_size`` (11776) for the dense MLP of its first
``num_dense_layers`` layers, which have no experts and are counted in
``num_hidden_layers``: ``moe_cost`` would count 9 layers of experts 7.7
times too wide.  Keys read here: ``hidden_size``,
``moe_intermediate_size``, ``num_hidden_layers``, ``num_dense_layers``.
"""


def expert_bytes(config, bytes_per_value=2):
    """One expert's weights: gate, up and down projections of
    ``hidden_size x moe_intermediate_size`` each."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"] \
        * bytes_per_value


def routed_layers(config):
    """Layers whose feed-forward is routed experts: all but the leading
    dense ones."""
    return config["num_hidden_layers"] - config["num_dense_layers"]


def routed_stream_floor_bytes_per_step(config, experts_hit_per_layer,
                                       bytes_per_value=2):
    """What one decode step must read of expert weights: in each routed
    layer, every expert that at least one token was routed to, once, whole.
    An expert no token chose need not be read; the routers, the mixers, the
    dense layer, norms and the embedding are not counted (this is the
    experts' floor, not the step's)."""
    return routed_layers(config) * float(experts_hit_per_layer) \
        * expert_bytes(config, bytes_per_value)
