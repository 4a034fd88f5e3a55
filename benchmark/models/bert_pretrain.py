"""BERT masked-LM pretraining step as a Fluid ``Program``: the builder of
``bench.build_bert_pretrain`` (bench.py:260), copied so that the benchmark
does not depend on a script outside its own directory, reading its sizes
from the configuration file.  The encoder itself is the program's own
(``paddle_tpu.models.bert``): it is the system under test.
"""

import numpy as np


def build_program(config, traffic):
    """-> (main, startup, loss) for one sequence length."""
    import paddle_tpu as fluid
    from paddle_tpu.models import bert

    if config["hidden_dropout_prob"] != config["attention_probs_dropout_prob"]:
        raise ValueError("the program's BERT has one dropout rate")
    cfg = bert.BertConfig(
        vocab_size=config["vocab_size"], hidden=config["hidden_size"],
        layers=config["num_hidden_layers"],
        heads=config["num_attention_heads"],
        ffn=config["intermediate_size"],
        max_pos=config["max_position_embeddings"],
        type_vocab=config["type_vocab_size"],
        dropout=config["hidden_dropout_prob"])
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        _inputs, seq_out = bert.bert_encoder(cfg, int(traffic["seq_len"]))
        mask_pos = fluid.layers.data("mask_pos", shape=[1], dtype="int64")
        mask_label = fluid.layers.data("mask_label", shape=[1],
                                       dtype="int64")
        flat = fluid.layers.reshape(seq_out, [-1, cfg.hidden])
        picked = fluid.layers.gather(flat, mask_pos)
        trans = fluid.layers.fc(picked, cfg.hidden, act="gelu")
        trans = fluid.layers.layer_norm(trans, begin_norm_axis=1)
        logits = fluid.layers.fc(trans, cfg.vocab_size)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, mask_label))
        opt = fluid.contrib.mixed_precision.decorate(
            fluid.optimizer.Adam(learning_rate=1e-4))
        opt.minimize(loss)
    return main, startup, loss


def masked_per_chip(config, traffic):
    return max(int(traffic["batch_per_chip"] * traffic["seq_len"]
                   * config["assumed"]["mask_frac"]), 1)


def make_batch(rng, config, traffic, chips):
    """One host batch (numpy) for ``chips`` chips.  Every feed's leading
    dimension divides by the chip count, so the executor splits it over a
    data-parallel mesh."""
    per = int(traffic["batch_per_chip"])
    batch = per * chips
    seq = int(traffic["seq_len"])
    n_mask = masked_per_chip(config, traffic)
    vocab = config["vocab_size"]
    # each chip's mask positions point into that chip's own tokens of the
    # global [batch * seq] axis, as a data-parallel input pipeline's would
    mask_pos = np.concatenate(
        [rng.integers(0, per * seq, (n_mask,)) + r * per * seq
         for r in range(chips)])
    return {
        "src_ids": rng.integers(0, vocab, (batch, seq, 1)).astype("int64"),
        "pos_ids": np.tile(np.arange(seq).reshape(1, seq, 1),
                           (batch, 1, 1)).astype("int64"),
        "sent_ids": np.zeros((batch, seq, 1), "int64"),
        "input_mask": np.ones((batch, seq, 1), "float32"),
        "mask_pos": mask_pos.astype("int64"),
        "mask_label": rng.integers(0, vocab,
                                   (n_mask * chips, 1)).astype("int64"),
    }


def tokens_per_step(traffic, chips):
    return int(traffic["batch_per_chip"]) * chips * int(traffic["seq_len"])


def flops_per_token(config, traffic):
    from benchmark import flops

    return flops.bert_pretrain_train_flops_per_token(
        config, int(traffic["seq_len"]), config["assumed"]["mask_frac"])


def expected_first_loss(config):
    return float(np.log(config["vocab_size"]))
