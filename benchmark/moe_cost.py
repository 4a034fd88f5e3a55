"""Bytes a routed-expert decode step has to read, from its shapes: the
numerators of the expert layers' bandwidth metrics.  Kept with the benchmark
(beside ``flops.py``) so no PR that claims a gain can change them.
"""


def expert_bytes(config, bytes_per_value=2):
    """One expert's weights: gate, up and down projections of
    ``hidden_size x intermediate_size`` each."""
    return 3 * config["hidden_size"] * config["intermediate_size"] \
        * bytes_per_value


def expert_stream_bytes_per_step(config, experts_hit_per_layer,
                                 bytes_per_value=2):
    """What one decode step must read of expert weights: in each layer,
    every expert that at least one token was routed to, once, whole.  An
    expert no token chose need not be read; the router, attention, norms,
    embedding and head are not counted (this is the experts' floor, not the
    step's)."""
    return config["num_hidden_layers"] * float(experts_hit_per_layer) \
        * expert_bytes(config, bytes_per_value)
