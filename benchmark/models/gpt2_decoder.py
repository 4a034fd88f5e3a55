"""GPT-2-shaped weights for the repo's decoder (``serving/decode_model.py``),
made on the device in one jitted call from the seed, in float32 as they are
served, under the keys of ``init_decoder_params``.  Nothing is written to
disk: the pair goes to ``DecodeEngine.add_model`` as it is.
"""


def decoder_config(config):
    from paddle_tpu.serving.decode_model import DecoderConfig

    if config["n_embd"] % config["n_head"]:
        raise ValueError("n_embd must divide by n_head")
    return DecoderConfig(
        vocab=config["vocab_size"], layers=config["n_layer"],
        heads=config["n_head"],
        head_dim=config["n_embd"] // config["n_head"],
        ffn=config["n_inner"], max_seq=config["n_positions"])


def param_shapes(config):
    """name -> (shape, kind) with kind in normal | ones | zeros."""
    h, f = config["n_embd"], config["n_inner"]
    v, s = config["vocab_size"], config["n_positions"]
    shapes = {"embed": ((v, h), "normal"), "pos_embed": ((s, h), "normal"),
              "lnf_g": ((h,), "ones"), "lnf_b": ((h,), "zeros"),
              "head": ((h, v), "normal")}
    for l in range(config["n_layer"]):
        for name, shape, kind in (
                ("ln1_g", (h,), "ones"), ("ln1_b", (h,), "zeros"),
                ("wq", (h, h), "normal"), ("wk", (h, h), "normal"),
                ("wv", (h, h), "normal"), ("wo", (h, h), "normal"),
                ("ln2_g", (h,), "ones"), ("ln2_b", (h,), "zeros"),
                ("w1", (h, f), "normal"), ("b1", (f,), "zeros"),
                ("w2", (f, h), "normal"), ("b2", (h,), "zeros")):
            shapes["l%d_%s" % (l, name)] = (shape, kind)
    return shapes


def make_params(config, seed, device):
    """All weights in one jitted call on ``device``."""
    import jax
    import jax.numpy as jnp

    shapes = param_shapes(config)
    std = float(config["initializer_range"])

    def init(key):
        out = {}
        for i, (name, (shape, kind)) in enumerate(sorted(shapes.items())):
            if kind == "normal":
                out[name] = std * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
            else:
                out[name] = (jnp.ones if kind == "ones" else jnp.zeros)(
                    shape, jnp.float32)
        return out

    # a seed may need more than 31 bits
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                             int(seed) >> 31)
    with jax.default_device(device):
        return jax.jit(init)(key)
