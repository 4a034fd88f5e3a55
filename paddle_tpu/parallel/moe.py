"""Mixture-of-Experts FFN with expert parallelism (EP) over a mesh axis.

NEW capability vs the reference (SURVEY.md §2.5: expert parallel ABSENT in
the 2019 codebase); the closest reference analog is the pserver-sharded
embedding (parameter_prefetch) — here the "sharded parameter" is the expert
stack and routing is data-dependent.

Design (Mesh-TensorFlow / Switch-style dispatch, XLA-friendly static
shapes):
  - top-k gating with renormalized combine weights
  - fixed expert capacity C = ceil(top_k * T / E * capacity_factor); tokens
    over capacity are dropped (their combine weight is 0) — the standard
    static-shape trade
  - dispatch/combine as einsums over a [T, E, C] one-hot tensor
  - EP: experts sharded over `axis_name`; token blocks exchanged with
    lax.all_to_all before and after the expert FFN (ICI all-to-all), the
    canonical EP schedule.

This is the trainer's routing.  Serving routes in ``models/olmoe.py``
(``_route``, then ``pallas_kernels/moe_experts.py``) and differs on purpose: there every token is
computed by exactly its chosen experts with the published softmax weights,
so a served logit can equal a reference's; capacity (and the dropped
tokens it implies), renormalised gates and the auxiliary loss are what a
trainer wants for static shapes and balanced load, and none of them is
part of a published model's forward pass.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["moe_ffn", "gating_dispatch"]


def _capacity(top_k, T, E, factor):
    try:
        return max(int(math.ceil(top_k * T / E * factor)), 1)
    except TypeError:
        # symbolic T during shape inference: capacity is internal only
        # (output stays [T, D]), any positive value works abstractly
        return 1


def gating_dispatch(x, gate_w, num_experts, top_k, capacity):
    """x [T, D] -> (dispatch [T, E, C] float 0/1, combine [T, E, C]),
    plus aux load-balancing loss (Switch-style)."""
    T = x.shape[0]
    E = num_experts
    logits = x @ gate_w                      # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)

    # top-k expert choice per token
    _, topk_idx = lax.top_k(probs, top_k)    # [T, k]
    onehot = jax.nn.one_hot(topk_idx, E, dtype=x.dtype)   # [T, k, E]
    gates = probs[:, None, :] * onehot       # [T, k, E] selected probs
    denom = jnp.sum(gates, axis=(1, 2), keepdims=True)
    gates = gates / jnp.maximum(denom, 1e-9)  # renormalize over chosen k

    # position of each (token, choice) within its expert queue: cumsum over
    # tokens, k-major so choice 0 claims slots first
    flat = onehot.transpose(1, 0, 2).reshape(top_k * T, E)   # [k*T, E]
    pos = jnp.cumsum(flat, axis=0) - flat                     # [k*T, E]
    pos = pos.reshape(top_k, T, E).transpose(1, 0, 2)         # [T, k, E]
    in_cap = pos < capacity
    slot = jnp.where(in_cap, pos, 0).astype(jnp.int32)

    keep = onehot * in_cap.astype(x.dtype)                    # [T, k, E]
    slot_oh = jax.nn.one_hot(slot, capacity, dtype=x.dtype)   # [T, k, E, C]
    dispatch = jnp.einsum("tke,tkec->tec", keep, slot_oh)
    combine = jnp.einsum("tke,tkec->tec", gates * keep, slot_oh)

    # aux loss: fraction of tokens per expert x mean gate prob (Switch eq.4)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(onehot[:, 0, :], axis=0)   # primary-choice load
    aux = jnp.sum(me * ce) * E
    return dispatch, combine, aux


def _expert_ffn(inp, w1, b1, w2, b2):
    """inp [E, C, D]; w1 [E, D, H]; w2 [E, H, D] -> [E, C, D]."""
    h = jnp.einsum("ecd,edh->ech", inp, w1) + b1[:, None, :]
    h = jax.nn.relu(h)
    return jnp.einsum("ech,ehd->ecd", h, w2) + b2[:, None, :]


def moe_ffn(x, gate_w, w1, b1, w2, b2, top_k=2, capacity_factor=1.25,
            axis_name=None):
    """MoE feed-forward. x [T, D] (flatten batch/seq first); returns
    (out [T, D], aux_loss scalar).

    Without `axis_name`: all experts local.  With `axis_name` (inside
    shard_map): tokens are sharded over the axis, experts too — w1/w2/b*
    are the LOCAL expert shard [E/n, ...]; gate_w is replicated and gating
    runs over the GLOBAL expert count inferred from gate_w's width."""
    D = x.shape[-1]
    if axis_name is None:
        E = w1.shape[0]
        C = _capacity(top_k, x.shape[0], E, capacity_factor)
        dispatch, combine, aux = gating_dispatch(x, gate_w, E, top_k, C)
        expert_in = jnp.einsum("tec,td->ecd", dispatch, x)
        expert_out = _expert_ffn(expert_in, w1, b1, w2, b2)
        out = jnp.einsum("tec,ecd->td", combine, expert_out)
        return out, aux

    n = lax.psum(1, axis_name)
    E_local = w1.shape[0]
    E = E_local * n
    Tl = x.shape[0]                       # local tokens
    # capacity per (expert, source-rank) block
    C = _capacity(top_k, Tl, E, capacity_factor)
    dispatch, combine, aux = gating_dispatch(x, gate_w, E, top_k, C)
    expert_in = jnp.einsum("tec,td->ecd", dispatch, x)    # [E, C, D]
    # exchange: rank r keeps expert block r; gathers that block from all
    # ranks -> [E_local, n*C, D] local expert batch
    blocks = expert_in.reshape(n, E_local, C, D)
    recv = lax.all_to_all(blocks, axis_name, split_axis=0, concat_axis=0,
                          tiled=False)                     # [n, El, C, D]
    local_in = recv.transpose(1, 0, 2, 3).reshape(E_local, n * C, D)
    local_out = _expert_ffn(local_in, w1, b1, w2, b2)
    back = local_out.reshape(E_local, n, C, D).transpose(1, 0, 2, 3)
    sent = lax.all_to_all(back, axis_name, split_axis=0, concat_axis=0,
                          tiled=False)                     # [n, El, C, D]
    expert_out = sent.reshape(E, C, D)
    out = jnp.einsum("tec,ecd->td", combine, expert_out)
    return out, lax.pmean(aux, axis_name)
