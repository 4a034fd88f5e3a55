"""Ring attention + Ulysses sequence parallelism (context parallelism).

Both operate on q/k/v laid out [B, H, S, D] with S sharded across a named
mesh axis.  They are written with differentiable collectives (lax.ppermute /
lax.all_to_all), so jax.grad produces the communication-correct backward —
the transpose of a ppermute ring is the reverse ring, which is exactly the
ring-attention backward schedule.

Ring schedule: at step t, rank r holds the K/V chunk originally owned by
rank (r - t) mod P; chunks move to the NEXT rank each step so the exchange
rides neighbor ICI links.  Softmax is accumulated online (same math as
pallas_kernels/flash_attention.py), so each chip never materializes more
than its local [Sq_local, Sk_local] score tile.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["ring_attention", "ulysses_attention",
           "make_ring_attention_sharded"]

_NEG_INF = -1e30


def _chunk_attn_update(q, kc, vc, sm_scale, m, l, acc, q_off, k_off, causal):
    """One online-softmax update of (m, l, acc) with a K/V chunk."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kc.astype(jnp.float32),
                   precision=lax.Precision.HIGHEST) * sm_scale
    if causal:
        Sq, Sk = q.shape[2], kc.shape[2]
        rows = q_off + lax.broadcasted_iota(jnp.int32, (Sq, Sk), 0)
        cols = k_off + lax.broadcasted_iota(jnp.int32, (Sq, Sk), 1)
        s = jnp.where((cols <= rows)[None, None], s, _NEG_INF)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m, m_cur)
    # fully-masked chunk: m_new stays -inf; keep exp() finite
    m_safe = jnp.where(m_new == _NEG_INF, 0.0, m_new)
    alpha = jnp.exp(jnp.where(m == _NEG_INF, _NEG_INF, m - m_safe))
    p = jnp.exp(s - m_safe)
    if causal:
        p = jnp.where((cols <= rows)[None, None], p, 0.0)
    l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * alpha + jnp.einsum(
        "bhqk,bhkd->bhqd", p, vc.astype(jnp.float32),
        precision=lax.Precision.HIGHEST)
    return m_new, l_new, acc_new


def ring_attention(q, k, v, axis_name, causal=False, sm_scale=None):
    """Per-shard ring attention; must run inside shard_map/pjit with the
    sequence dimension of q/k/v sharded over `axis_name`.

    q, k, v: [B, H, S_local, D] (the local sequence shard).
    Returns [B, H, S_local, D].
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    P = lax.psum(1, axis_name)
    r = lax.axis_index(axis_name)
    B, H, Sq, D = q.shape
    Sk = k.shape[2]

    m0 = jnp.full((B, H, Sq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Sq, 1), jnp.float32)
    acc0 = jnp.zeros((B, H, Sq, D), jnp.float32)
    perm = [(i, (i + 1) % P) for i in range(P)]

    def step(carry, t):
        m, l, acc, kc, vc = carry
        owner = (r - t) % P
        m, l, acc = _chunk_attn_update(
            q, kc, vc, sm_scale, m, l, acc,
            q_off=r * Sq, k_off=owner * Sk, causal=causal)
        # rotate chunks to the next rank (neighbor ICI exchange)
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        return (m, l, acc, kc, vc), None

    # last chunk is peeled out of the scan so the final (dead) rotation —
    # a full K+V neighbor transfer — is never issued
    (m, l, acc, kc, vc), _ = lax.scan(
        step, (m0, l0, acc0, k, v), jnp.arange(P - 1))
    owner_last = (r - (P - 1)) % P
    m, l, acc = _chunk_attn_update(
        q, kc, vc, sm_scale, m, l, acc,
        q_off=r * Sq, k_off=owner_last * Sk, causal=causal)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    return (acc / l_safe).astype(q.dtype)


def ulysses_attention(q, k, v, axis_name, causal=False, sm_scale=None,
                      attn_fn=None):
    """DeepSpeed-Ulysses sequence parallelism: all-to-all trades the
    sequence shard for a heads shard, dense attention runs locally over the
    FULL sequence with H/P heads, then the output is swapped back.

    q, k, v: [B, H, S_local, D] with H divisible by the axis size.
    attn_fn(q,k,v,causal,sm_scale): local attention over [B, H/P, S, D];
    defaults to the flash-attention entry (Pallas kernel on TPU).
    """
    P = lax.psum(1, axis_name)

    def seq2head(t):
        # [B, H, S/P, D] -> [B, H/P, S, D]
        return lax.all_to_all(t, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    def head2seq(t):
        return lax.all_to_all(t, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qh, kh, vh = seq2head(q), seq2head(k), seq2head(v)
    if attn_fn is None:
        from ..pallas_kernels import flash_attention as _fa

        out = _fa(qh, kh, vh, causal=causal, sm_scale=sm_scale)
    else:
        out = attn_fn(qh, kh, vh, causal=causal, sm_scale=sm_scale)
    return head2seq(out)


def make_ring_attention_sharded(mesh, axis_name="sp", causal=False,
                                sm_scale=None, impl="ring"):
    """Build a jittable global-view function: takes FULL [B, H, S, D]
    arrays, shards S over `axis_name` of `mesh`, and runs ring/ulysses
    attention under shard_map.  The convenience entry for model code and
    tests; inside a larger pjit program, call ring_attention directly in
    the shard_map'ed region."""
    from jax.sharding import PartitionSpec as P

    spec = P(None, None, axis_name, None)
    fn = ring_attention if impl == "ring" else ulysses_attention

    def per_shard(q, k, v):
        return fn(q, k, v, axis_name, causal=causal, sm_scale=sm_scale)

    return jax.shard_map(per_shard, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)
