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
         "rotated_latent", "shared_expert", "expert_gate", "selects",
         "zero_experts", "scaled_latent", "neg_eigval",
         "residual_streams"),
        defaults=(("f32", "bf16"), False, None, None, False, False, False,
                  False, False, False, "silu", False, False, False,
                  False, False))):
    """``kinds``: the kinds of layer the block computes
    (``decode_model.LAYER_KINDS``: seven of them, of which a family names
    one to three, in any pairing the cache holds: a ``kda`` slot beside
    latent pools, or beside K/V pools).  ``dtypes``: the weight dtypes it is
    served in.  ``grouped_query``: its attention may have fewer KV heads than
    query heads.  ``routes``: where its feed-forward is routed experts: None
    (nowhere), ``"after_dense"`` (every layer after ``cfg.dense_layers``),
    ``"experts_layers"`` (the layers of kind ``experts``) or ``"pairs"``: the
    block is a *pair* of sublayers, each a mixer and a dense MLP, round ONE
    routed part that reads the stream behind the first sublayer's mixer and
    is added behind the second sublayer's MLP.  ``cfg.layers`` and
    ``cfg.layer_types`` then count sublayers (two a pair: an even number),
    sublayer ``2 i`` and ``2 i + 1`` are pair ``i``'s, each its own ``l`` to
    ``attend`` and its own pool in the cache, and ``cfg.routed_layers`` names
    the first sublayer of every pair, which holds the pair's router and
    experts.
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
    others.  ``zero_experts``: its router is wider than its experts by
    ``cfg.zero_experts`` identity experts, which return their input: a token
    that chooses one computes nothing for it, and its routed compute is the
    router's to decide.  ``scaled_latent``: its latent layers scale the
    projected query by ``cfg.latent_q_scale`` and the normed compressed K/V
    by ``cfg.latent_kv_scale``.  ``neg_eigval``: its ``kda`` layers' delta
    rule may allow negative eigenvalues (``cfg.kda_neg_eigval``: ``beta`` is
    twice the sigmoid, in (0, 2), so a write may turn about what the state
    holds along its key and not only shrink it).  ``residual_streams``: a
    token's stream is ``cfg.hc_mult`` vectors and not one, mixed round every
    sublayer by manifold-constrained hyper-connections
    (``models/hyper_connections.py``; ``cfg.hc_sinkhorn_iters``,
    ``cfg.hc_eps``, ``cfg.hc_clamp``)."""

    __slots__ = ()
