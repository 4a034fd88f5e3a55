"""Operations a model's step needs, from its shapes: the numerators of the
utilisation metrics.  Kept with the benchmark so no PR that claims a gain
can change them.  Counted: the multiply-adds of every matrix multiplication
of the forward pass (x2), and twice that again for the backward pass.
Recomputed operations, element-wise work and the optimizer do not count.
"""


def bert_pretrain_train_flops_per_token(config, seq_len, mask_frac):
    """Forward + backward matmul operations per input token of the masked-LM
    pretraining step as ``models/bert_pretrain.py`` builds it.

    Differs from ``bench.py _bert_train_flops_per_seq`` (which this replaces)
    in one place: the vocabulary head and its transform run on the gathered
    mask positions only (``mask_frac`` of the tokens), as the program does,
    and not on every token.  bench.py charged the head for all of them,
    which read 22% high at seq 128."""
    h = config["hidden_size"]
    ffn = config["intermediate_size"]
    layers = config["num_hidden_layers"]
    vocab = config["vocab_size"]
    # per token, per layer: q, k, v, out projections + the two ffn matmuls,
    # + scores and the weighted sum over seq_len keys
    layer = 2 * (4 * h * h + 2 * h * ffn) + 2 * 2 * seq_len * h
    head = mask_frac * 2 * (h * h + h * vocab)
    return 3.0 * (layers * layer + head)
