"""What a decoder family declares of itself.

A family the decode steps serve is ONE module, ``models/<arch>.py``, with
``token_logits`` (its block, under the contract ``serving/decode_model.py``
``_block`` states), ``init_params(cfg, seed)`` and ``FAMILY``, a
``DecoderFamily``: plain data, read by ``DecoderConfig`` to refuse what the
block does not compute and by the step makers to find what it routes.  A
module may also hold ``laid_out(cfg, params)``: the published parameters as
a decode step should hold them, where its block multiplies a weight in
another layout than the source publishes it in
(``decode_model.laid_out`` asks; a module without one is held as it is).  To
add a family: write that module, name it in ``decode_model.ARCHS``, and give
it a row in ``tests/decoder_families.py``; nothing else in ``serving/``
knows a family by name.
"""

import collections

__all__ = ["DecoderFamily"]


class DecoderFamily(collections.namedtuple(
        "DecoderFamily",
        ("kinds", "dtypes", "grouped_query", "routes", "expert_matrices",
         "dense_lead", "holds_share", "own_stream_width", "grouped_router",
         "rotated_latent", "shared_expert", "expert_gate", "selects"),
        defaults=(("f32", "bf16"), False, None, None, False, False, False,
                  False, False, False, "silu", False))):
    """``kinds``: the kinds of layer the block computes
    (``decode_model.LAYER_KINDS``: seven of them, of which a family names
    one to three).  ``dtypes``: the weight dtypes it is
    served in.  ``grouped_query``: its attention may have fewer KV heads than
    query heads.  ``routes``: where its feed-forward is routed experts: None
    (nowhere), ``"after_dense"`` (every layer after ``cfg.dense_layers``) or
    ``"experts_layers"`` (the layers of kind ``experts``).
    ``expert_matrices``: how many matrices an expert has, 3 (``wgate``,
    ``wup``, ``wdown``), 2 (``experts_up``, ``experts_down``) or None.
    ``dense_lead``: its first ``cfg.dense_layers`` layers may end in a gated
    MLP.  ``holds_share``: it may hold a share of each routed layer's experts
    (``cfg.experts_held`` from ``cfg.expert_first`` on).
    ``own_stream_width``: its stream (``cfg.hidden_size``) may be narrower
    than its query heads together.  ``grouped_router``: its router may
    choose ``cfg.topk_group`` of ``cfg.n_group`` groups of experts before it
    chooses experts (``exaone_moe.routed_part``).  ``rotated_latent``: its
    latent layers rotate the row's shared key and the query's last
    ``cfg.latent_rope`` values by position (``cfg.rope_scaling``: YaRN) and
    may compress the query (``cfg.q_rank``).  ``shared_expert``: beside its
    routed experts every token passes through a shared one of width
    ``cfg.shared_ffn``.  ``expert_gate``: the activation of a three-matrix
    expert's gate, as ``moe_experts.GATES`` names it.  ``selects``: its
    latent layers attend the ``cfg.index_topk`` positions a learned indexer
    scores highest (``cfg.index_heads`` heads of ``cfg.index_head_dim``,
    whose keys the cache holds in a pool beside each latent pool) and no
    others."""

    __slots__ = ()
