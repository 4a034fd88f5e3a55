"""Bytes a decode step's state-space (Mamba-2) layers have to move, from
the configuration's keys: the numerator of ``ssm_stream_floor_share.serve``.
Kept with the benchmark (beside ``moe_cost.py`` and ``flops.py``) so no PR
that claims a gain can change them.

A mamba layer's mixer reads its weights once a step whatever the lanes, and
reads and writes each live lane's state once: the state ``S`` is
``mamba_n_heads x mamba_d_head x mamba_d_state`` float32 values a sequence
a layer (2,097,152 B at the published sizes), constant in the sequence's
length.  The convolution's window (three inputs a layer, 26,112 B in
bfloat16) is not counted, nor are attention, the MLPs, the norms outside
the mixer, the embedding and the head: this is the state-space mixers'
floor, not the step's.
"""

STATE_BYTES_PER_VALUE = 4       # the state is float32 wherever it lives


def mamba_layers(config):
    return sum(kind == "mamba" for kind in config["layer_types"])


def mixer_weight_bytes(config, bytes_per_value=2):
    """The weights of every mamba layer's mixer: in_proj ``[H, 2 I + 2 N +
    heads]``, the depthwise convolution and its bias over ``I + 2 N``
    channels, ``dt_bias``, ``A_log`` and ``D`` a head, the gated norm over
    ``I``, out_proj ``[I, H]`` (``I = heads x d_head``, ``N = d_state``,
    one group of B and C)."""
    h = config["hidden_size"]
    heads, n = config["mamba_n_heads"], config["mamba_d_state"]
    inner = heads * config["mamba_d_head"]
    conv_dim = inner + 2 * n * config["mamba_n_groups"]
    per_layer = h * (inner + conv_dim + heads) \
        + conv_dim * config["mamba_d_conv"] + conv_dim \
        + 3 * heads + inner + inner * h
    return mamba_layers(config) * per_layer * bytes_per_value


def state_bytes_per_sequence_layer(config):
    return config["mamba_n_heads"] * config["mamba_d_head"] \
        * config["mamba_d_state"] * STATE_BYTES_PER_VALUE


def state_traffic_bytes_per_step(config, live_lanes):
    """Each live lane's state in every mamba layer, read once and written
    once."""
    return 2 * float(live_lanes) * mamba_layers(config) \
        * state_bytes_per_sequence_layer(config)


def ssm_stream_bytes_per_step(config, live_lanes, bytes_per_value=2):
    """What one decode step's state-space mixers must move: their weights
    once, and the live lanes' state in and out."""
    return mixer_weight_bytes(config, bytes_per_value) \
        + state_traffic_bytes_per_step(config, live_lanes)
