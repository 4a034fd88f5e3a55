"""Paged attention for the decode serving path: the kernel that reads the
KV blocks where they lie, and the jnp path it is checked against.

The decode step (serving/decode_model.py) attends one query token per lane
against that lane's KV history, which lies scattered over the fixed-size
blocks of the layer's pool (serving/kv_cache.py: ``[num_blocks,
block_size, KH * D]``, KV heads folded) and is named by the lane's row of the
block table.

``paged_attention`` picks the path from what it can see, with no flag:

* **the kernel** (scope ``kv_read``), on a TPU backend, for a rank-3 pool
  in float32 or bfloat16 whose ``KH * D`` is a multiple of the 128 lanes and
  whose ``block_size`` is a multiple of the dtype's sublane tile.  One
  invocation walks the lanes; for each it loops over chunks up to
  ``context_lens[b]``, fetches the chunk's blocks from the pools in HBM by
  async copies steered by the scalar-prefetched table (double buffered,
  the next chunk or the next lane's first in flight while this one is
  reduced), and folds them into an online softmax.  **A chunk is as many
  of the lane's live blocks as are worth a wait**: its span is the fewest
  whole steps of ``CHUNK_TOKENS`` (128) positions that hold ``_CHUNK_BYTES``
  in the pools fetched (K and V rows of 1024 float32, or of 2048 or 1024
  bfloat16: 128 positions; 8 KV heads of 64: 256; 2 of 128, or one latent
  row of 640: 512, the most), less where the queries and outputs leave
  the buffers no room (``_chunk_positions``: a function of the shapes, one
  constant, no flag), and of a lane's last chunk only the blocks the lane
  holds are copied and waited for; the rows of a buffer no copy wrote are
  zeros or an earlier chunk's (finite: their probabilities are 0).  Work
  follows the live context: an idle lane (``context_lens`` 0) fetches
  nothing and returns zeros; nothing of the table's size is written, no
  block the table does not name is read and none is read twice.  **The
  walk has two bodies, and which one a chunk runs follows from the lane's
  count of chunks alone** (``_straight``, the one statement of the rule:
  ``straight_chunks_read`` counts by it for the step's span).  A chunk
  that is not a lane's last holds all its blocks, so all but a lane's last
  two chunks run *straight-line* (since PR 63): the next chunk's copies
  issued with no predicate and no loop, this chunk's waited for, then the
  scores, the softmax chain and the value product, one basic block in
  which the scheduler lays scalar issue, waits, matrix and vector work
  side by side; and the buffers' indices there are compile-time constants
  (a lane walks these chunks in pairs, first buffer then second, in one of
  two copies of the walk by the parity of the chunks fetched before it),
  since with a traced index the compiler must take a copy into one buffer
  and a load from the other for the same memory and keeps them in program
  order.  The lane's last two bodies (the one that fetches the last chunk,
  and the last chunk, which fetches the next lane's first) are *guarded*:
  each step of 8 copies under a predicate and the last few in a loop, a
  traced buffer index, so that the last chunk fetches its live blocks and
  no others (the latent form below fetches a lane's last block again
  there, which for K/V rows is bytes: 13-18% of GPT-2's fetch).  A ring of
  one chunk keeps the body it had.  What is left to take (ROADMAP Speed
  1(c)): a last chunk of one block still pays a whole chunk's arithmetic,
  and a fetch two chunks ahead is untried.
* **the gather** (scope ``kv_gather``) everywhere else: on the CPU tier,
  for the int8 residency (gather, dequantize), in a program XLA partitions
  over a mesh.  ``gather_blocks`` copies every slot of the padded table
  into contiguous history and ``masked_attention`` reduces it.

The pool's width is its own (``KH * D``, the KV heads it stores), and the
query may have more heads than that: with ``H`` query heads over ``KH`` KV
heads, query head ``r`` reads KV head ``r // (H // KH)`` (grouped-query
attention; ``H == KH`` is the multi-head case).  The scale of the scores is
an argument, ``1 / sqrt(D)`` unless the block says otherwise.

Both compute ``masked_attention``'s mathematics at its precision: head r's
scores are row r of a block-diagonal query against the folded rows, so
every dot is a plain 2-D matmul over ``KH * D``.  Against a bfloat16 pool
the query and the probabilities are rounded to bfloat16 and the MXU
accumulates in float32.  Against a float32 pool every product is float32:
each operand is split into three bfloat16 pieces and the six products
``Precision.HIGHEST`` keeps are taken, the pieces of the query (and of the
probabilities) stacked as rows so that each piece of K (and V) passes
through the MXU once.  Only the order of the softmax's sums differs.

A **window** layer (``window`` given) attends the last ``window`` positions
only, and its table is a *ring*: ``R`` slots a lane, position ``p`` in slot
``(p // block_size) % R`` (serving/kv_cache.py ``WindowRing``), so that a
sequence holds ``R`` blocks there whatever its length.  Entry ``j`` of the
ring's ``R * block_size`` rows then holds the newest position congruent to
``j``, ``age = (context_len - 1 - j) mod (R * block_size)`` tokens back, and
is attended iff ``age < min(window, context_len)`` (``ring_mask``); the
gather reads the ring as it lies.  The kernel takes a ring one of two ways,
by its length alone (``_ring_whole``: one rule for ``vmem_bytes``,
``chunk_positions``, ``attention_path`` and ``blocks_read``).  A ring no
longer than the longest chunk (``_MAX_CHUNK_TOKENS`` positions: a window of
128 in 9 blocks of 16) is ONE chunk, fetched whole and in the table's order
(a slot not held yet fetches block 0, masked).  A longer one (a window of
4,096: 257 blocks) is *walked in chunks* of the span ``_chunk_positions``
gives its rows, in the table's order, through the same two buffers and the
same two bodies as a global layer's context: chunk ``c`` holds entries
``[c * span, (c + 1) * span)`` of the ring and is masked by their age, and a
lane fetches the slots it holds and no others, ``min(ceil(context_len /
block_size), R)`` leading ones (under ``R * block_size`` positions the ring
has not wrapped and the slots past the context hold nothing; from there on
every slot, the one whose block has just left the window as block 0,
masked).  Nothing of the history before the window is read.

Where ``H / KH`` query heads share a KV head and ``D`` is a multiple of the
128 lanes, the query and the output cross the kernel's boundary compact
(``[B, H, D]``): the kernel repeats a lane's query under every KV head's
columns itself and folds the output's owned columns back, so VMEM holds
``B * H * D`` values of each and not ``B * H * KH * D`` (64 heads over 8 of
128: 1 MB for 32 lanes where the spread layout takes 8.4).

The **latent** form (``latent_attention``) serves absorbed multi-head latent
attention: a layer keeps ONE row a token, ``W`` wide (a compressed K/V of
``rank`` values and the key's shared part), in one pool ``[num_blocks,
block_size, W]``; every query head (``[B, H, W]``, the key's up-projection
already folded into it) scores all ``W`` columns of that one cached head,
and the value is the row's first ``rank`` columns, so a block is fetched
once and serves as K and as V (-> ``[B, H, rank]``).  ``W`` and ``rank`` are
whole 128-lane tiles: the cache rounds a row up to that and keeps the rest
zeros (576 values lie in rows of 640), since a kernel fetches whole tiles of
the pool's layout.  A kernel of its own (``_latent_kernel``,
``LATENT_KERNEL_NAME`` in a trace): one pool, one pair of chunk buffers, and
a chunk body other than the K/V form's.  The K/V form's guarded body (every
chunk's before PR 63, a lane's last two since) guards each step of 8
copies by a predicate and loops over the last few, so that a lane's last
chunk fetches its live blocks and no others; in a latent chunk the copies'
issue and waits, scalar work in the kernel's one instruction stream, then
stood beside the transfers and the arithmetic, overlapping neither (us a
chunk of 512 positions over bfloat16 rows of 640, copies alone | arithmetic
alone | the guarded whole: 0.93 | 0.57 | 1.31 at 32 query heads, 0.95 | 0.73
| 1.38 at 64, 0.87 | 1.09 | 1.75 at 128).  Here every chunk is ONE basic
block that the scheduler fills with scalar, vector and matrix work side by
side (1.11, 1.17 and 1.27 us), which takes two things.  No predicate: every
chunk issues all its copies, a slot past the lane's last block fetching
that block again (masked positions; no block the table does not name is
read, and of a lane's last chunk up to one chunk less a block is fetched
twice), and a chunk that nothing follows is fetched again for nobody and
waited out later.  And buffer indices that are compile-time constants: a
lane walks its chunks in pairs, first buffer then second, in one of two
copies of the walk chosen by the parity of the chunks fetched before it;
with a traced index the compiler must assume that a copy into one buffer
and a load from the other touch the same memory, and keeps them in program
order (1.5 us a chunk; 1.27 with constants: PERF.md section 6, PR 50).
From 64 heads on the body's gain shows in the step (dots.vlm1's cell at PR
50, LongCat-Flash's and GLM-5's at PR 62: a kernel call at LongCat's
contexts 480 -> 408 us); at Kimi-Linear's 32 the transfers bind, XLA's
fetches beside the kernel take back what it gains alone and the step reads
the same (its cell a tie, PRs 50 and 62), so the guarded latent body that
cell had kept went: one body, and no rule between two (PR 62).  A last chunk
of a shorter span of its own (whole steps of ``CHUNK_TOKENS``, in a copy of
the walk's end a span: 18 chunk bodies for 6) was built and measured at PR
62: 4% of the kernel alone, nothing LongCat-Flash's cell could show in three
pairs, and taken out again (PERF.md section 6, PR 62).  The same body takes
a selection's mask (below).

A latent layer that **selects** (a model whose ``index_topk`` is set)
attends the positions a learned indexer scores highest and no others, in
three calls.  ``index_scores`` scores every cached position of a lane: the
indexer's queries ``[B, J, E]`` against the lane's index keys, which lie in
a pool of their own beside the latent one (``[num_blocks, block_size, E]``,
on the same tables), a ReLU a head and the heads' weighted sum -> ``[B,
positions]`` float32, ``-inf`` past a lane's context.  A kernel
(``INDEX_KERNEL_NAME``) whose grid walks the lanes and which walks a lane's
live blocks in chunks of ``INDEX_CHUNK_TOKENS`` positions through two
buffers, every chunk's copies issued straight-line and a slot past the
lane's last block fetching that block again (masked), as the latent form's
straight-line body does; the gather of the padded table and
``dense_index_scores`` elsewhere.  ``choose`` takes the exact ``top_k`` of
those scores (ties to the lower position; no approximation: an approximate
set is another model); a lane at or under ``k`` positions chooses all it
has.  ``selected_latent_attention`` then reads the chosen rows alone, one of
three ways by the shapes (``selected_latent_path``; one constant,
``_WALK_POSITIONS_PER_CHOSEN``).  Where the table holds few positions a
chosen one (12,544 for 2,048), **the masked walk**: the latent kernel walks
the lane's own table and context under a mask of the chosen positions by
chunk, one more operand, a lane's chunks of it in VMEM, so that a position
counts where the context holds it AND it was chosen (``kv_read``): every
live block is fetched, none twice, and no row is gathered.  The walk wants
the chosen *set* and no list, so ``choose``'s result (a ``Choice``) gives it
the set ``by_chunk``: made from the scores by an exact threshold
(``chosen_by_chunk``: 46 counting passes, each a fusion over scores the
compiler keeps in VMEM), handed to ``selected_latent_attention`` in the
list's place (``chosen_for_read``; rank 3 where the list is rank 2), and the
list, which nothing then reads, is dropped by the compiler with its sort.
A stand-in for ``choose`` that returns a plain pair has its list laid out
instead (``_chunk_mask``: two one-hots contracted on the MXU, no scatter;
scope ``mask``).  Under a wider table (the
published 202,752 positions), **the row form**: the rows are gathered by
(block, offset) into contiguous blocks (scope ``kv_gather``: XLA's gather
costs by the count of its rows, 26 ns each) and the kernel's body runs over
them as over a lane's context (``kv_read``).  Elsewhere the whole table is
gathered and what was not chosen is masked (``masked_latent``'s
``chosen``), which is the same mathematics and what the unpaged loop
computes too.

``masked_attention`` is also the core of the UNPAGED reference loop in
decode_model.py: sharing it is what makes paged-vs-unpaged decode
bitwise-comparable on the CPU tier.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import adoption

__all__ = ["paged_attention", "paged_attention_reference",
           "paged_attention_checks", "attention_path", "blocks_read",
           "chunks_read", "straight_chunks_read", "blocks_refetched",
           "masked_attention", "gather_blocks", "ring_mask", "KERNEL_NAME",
           "latent_attention", "latent_attention_reference",
           "latent_attention_checks", "latent_path", "masked_latent",
           "LATENT_KERNEL_NAME", "chunk_positions",
           "latent_chunk_positions", "index_scores", "dense_index_scores",
           "index_scores_checks", "index_path", "choose", "Choice",
           "chosen_mask", "chosen_by_chunk", "chosen_for_read",
           "selected_latent_attention", "selected_latent_path",
           "INDEX_KERNEL_NAME", "INDEX_CHUNK_TOKENS"]

# the name the kernel's executions carry in a device trace
KERNEL_NAME = "paged_attention"
# ... and those of its latent form (one pool, the value the key's first
# columns): a name of its own, so that a reader of one never sums the other
LATENT_KERNEL_NAME = "latent_attention"
# ... and those of the kernel that scores a lane's cached index keys
INDEX_KERNEL_NAME = "index_scores"

_MASK = -1e30  # finite: a fully-masked lane softmaxes to uniform, not NaN

# the step of a chunk's span, in positions (whole blocks: 8 of 16 tokens):
# the MXU's 128 columns.  A chunk is a whole multiple of it, as many as make
# the chunk's fetch worth its wait (``_chunk_positions``); of a lane's last
# chunk only the blocks the lane holds are fetched.
CHUNK_TOKENS = 128
# bytes a chunk should hold before it is worth a wait.  What a chunk costs
# beside its bytes (the softmax's max, exp and rescale chain, once a chunk,
# and the issue of its copies) is paid once whatever the span, so rows of
# 1,024-2,048 B a position run 26-38% faster by the position at 512
# positions than at 128, while rows of 8,192 B (1 MiB in 128 positions)
# lose 2-4% at 256 (PERF.md section 6, PR 47: the copies-only,
# arithmetic-only and whole forms at spans of 128, 256 and 512 positions;
# and why K-EXAONE's 4,096 B, 17% faster at 256, stay at 128 for now)
_CHUNK_BYTES = 512 << 10
_MAX_CHUNK_TOKENS = 512

_SUBLANES = {"float32": 8, "bfloat16": 16}   # rows of a dtype's memory tile

# what the kernel may hold in VMEM: its four chunk buffers, the queries and
# the outputs.  Both serving cells hold 2.3-2.5 MB; Mosaic's scoped limit on
# a v5e is 16 MB and the matmuls' temporaries share it (a width of 8192 is
# refused at 18-20 MB, compiled for a described chip).
_VMEM_BUDGET = 8 << 20


def _own(heads, kv_heads, dtype):
    """[heads, kv_heads] 0/1: query head ``r`` owns KV head ``r // group``
    (``group = heads // kv_heads``; the identity when every query head has
    its own K and V)."""
    group = heads // kv_heads
    return (jnp.arange(heads)[:, None] // group
            == jnp.arange(kv_heads)[None, :]).astype(dtype)


def _in_window(ctx, entry, ring_len, window):
    """Is ``entry`` of a ring of ``ring_len`` rows attended from a context
    of ``ctx`` tokens?  It holds the newest position congruent to it,
    ``age`` tokens before the last one, which is inside the window, and
    written, iff ``age < min(window, ctx)``."""
    return (ctx - 1 - entry) % ring_len < jnp.minimum(window, ctx)


def ring_mask(context_lens, ring_len, window):
    """[B, ring_len] bool: which entries of a window layer's ring each lane
    attends (``_in_window``)."""
    return _in_window(context_lens.astype(jnp.int32)[:, None],
                      jnp.arange(ring_len, dtype=jnp.int32)[None, :],
                      ring_len, window)


def masked_attention(q, k, v, context_lens, scale=None, window=None,
                     chosen=None):
    """Single-token attention over a contiguous history: q [B, H, D],
    k/v [B, S, KH, D] with ``H`` a multiple of ``KH`` (grouped queries:
    query head ``r`` reads KV head ``r // (H // KH)``), context_lens [B]
    -> [B, H, D] (``v`` may be narrower than ``k``, ``[B, S, KH, Dv]``: the
    output is then ``[B, H, Dv]``).  Positions >= the context length are masked; scores are
    multiplied by ``scale`` (None: ``1 / sqrt(D)``).  With ``window`` the
    ``S`` rows are a ring (``ring_mask``) and the last ``window`` positions
    alone are attended; with ``chosen`` ([B, S] bool) only the positions it
    marks, among those the context holds.  Shared by the paged gather path
    AND the unpaged reference loop so the two stay bitwise-comparable.

    Both contractions run over the folded minor dimension KH * D, against
    a block-diagonal query: row r of ``qx`` holds head r's query in the D
    columns of its KV head and zeros elsewhere, so ``qx @ k`` is head r's
    scores and the owned blocks of ``p @ v`` are its output.  The history
    is then read as it lies in the pool ([S, KH * D] rows): splitting
    KH * D into heads on it costs a relayout of everything gathered
    wherever D is under the 128 lanes (D = 64: a second, padded copy of
    the history per layer), while the extra zero blocks are matmul work on
    a unit that is otherwise idle.  ``HIGHEST`` keeps the products
    float32, as the elementwise form they replace had them.

    The history's dtype is the matmuls' input dtype: against a float32
    pool nothing is cast; against a bf16 pool (a bf16 model's) the query
    and the probabilities are rounded to bf16, the history is read as it
    is stored, and both contractions accumulate in float32.  Scores,
    mask and softmax are float32 either way."""
    b, h, d = q.shape
    s, kh, dv = k.shape[1], k.shape[2], v.shape[3]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    dot = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    own = _own(h, kh, q.dtype)[None, :, :, None]
    qx = (q[:, :, None, :] * own).reshape(b, h, kh * d).astype(k.dtype)
    sc = dot("bhc,bsc->bhs", qx, k.reshape(b, s, kh * d)) * scale
    if window is None:
        pos = jnp.arange(s, dtype=jnp.int32)[None, :]
        seen = pos < context_lens[:, None].astype(jnp.int32)
    else:
        seen = ring_mask(context_lens, s, window)
    if chosen is not None:
        seen = seen & chosen
    sc = jnp.where(seen[:, None, :], sc, _MASK)
    p = jax.nn.softmax(sc, axis=-1)
    out = dot("bhs,bsc->bhc", p.astype(v.dtype), v.reshape(b, s, kh * dv))
    return (out.reshape(b, h, kh, dv) * own).sum(axis=2)


def gather_blocks(cache, block_tables):
    """A pool ``[num_blocks, block_size, ...]`` read through the tables
    ``[B, MAXB]`` as contiguous history ``[B, MAXB * block_size, ...]``.
    Entries < 0 are unused slots: clamped to block 0 here and masked by
    ``context_lens`` in the attention.  Every index is then in range,
    which ``mode="clip"`` lets the gather rely on: the default mode wraps
    it in a select over everything gathered."""
    bb, maxb = block_tables.shape
    got = jnp.take(cache, jnp.maximum(block_tables, 0), axis=0, mode="clip")
    return got.reshape((bb, maxb * cache.shape[1]) + cache.shape[2:])


def paged_attention_reference(q, k_cache, v_cache, block_tables,
                              context_lens, scale=None, window=None):
    """The jnp path: gather the table's blocks into contiguous K/V, then
    masked_attention.  q [B, H, D]; k_cache/v_cache the serving pool's
    [num_blocks, block_size, KH * D] (or [num_blocks, block_size, KH, D]):
    the heads are split after the gather, never on the pool.  With
    ``window`` the table is a window layer's ring."""
    bb, _h, d = q.shape
    with jax.named_scope("kv_gather"):
        k, v = (gather_blocks(c, block_tables).reshape(bb, -1, _kv_heads(
            c.shape, d), d) for c in (k_cache, v_cache))
    return masked_attention(q, k, v, context_lens, scale, window)


def _kv_heads(kv_shape, head_dim):
    """KV heads of a pool ``[num_blocks, block_size, KH * D]`` (or ``[...,
    KH, D]``): the pool's own width says how many heads it stores."""
    return kv_shape[2] if len(kv_shape) == 4 else kv_shape[2] // head_dim


# -- the shape rule ----------------------------------------------------------

def paged_attention_checks(q_shape, kv_shape, kv_dtype, ring=0):
    """Ordered (reason, ok) pairs for adoption.decide(): what the kernel
    needs of the query ``[B, H, D]`` and of a pool ``[num_blocks,
    block_size, KH * D]`` in ``kv_dtype``, ``H`` a multiple of ``KH``.
    ``ring`` is the slots of a window layer's ring table (one chunk, or
    walked in chunks: ``_ring_whole``); 0 for a layer that attends its whole
    context."""
    dims = tuple(q_shape) + tuple(kv_shape)
    static = all(isinstance(x, int) and x >= 0 for x in dims)
    rank = len(q_shape) == 3 and len(kv_shape) == 3
    tile = _SUBLANES.get(jnp.dtype(kv_dtype).name)
    # the pool's width is whole KV heads, and the query heads divide over
    # them evenly
    grouped = static and rank and q_shape[2] > 0 \
        and kv_shape[2] % q_shape[2] == 0 and kv_shape[2] > 0 \
        and q_shape[1] % (kv_shape[2] // q_shape[2]) == 0
    return [
        ("backend", adoption.interpret_mode()
         or jax.default_backend() == "tpu"),
        ("symbolic_shape", static),
        ("rank", rank),
        ("dtype", tile is not None),
        ("lanes", grouped and kv_shape[2] % 128 == 0),
        ("block_size", static and rank and tile is not None
         and kv_shape[1] > 0 and kv_shape[1] % tile == 0),
        ("empty", static and all(x > 0 for x in dims)),
        ("vmem", grouped and tile is not None
         and vmem_bytes(q_shape, kv_shape, kv_dtype, ring) <= _VMEM_BUDGET),
    ]


def vmem_bytes(q_shape, kv_shape, kv_dtype, ring=0):
    """What the kernel holds in VMEM for these shapes: the four chunk
    buffers (a window layer's chunk is its whole ring where ``_ring_whole``
    says so; any other chunk the span ``_chunk_positions`` gives these
    shapes, which the call uses too), and every lane's query and output in
    float32."""
    fetched = 2 * jnp.dtype(kv_dtype).itemsize * kv_shape[2]   # K and V
    held = _held_bytes(q_shape, kv_shape)
    span = ring * kv_shape[1] if _ring_whole(ring, kv_shape[1]) \
        else _chunk_positions(fetched, kv_shape[1], held)
    return 2 * span * fetched + held


def _ring_whole(ring, block_size):
    """Is a window layer's ring of ``ring`` slots ONE chunk of the kernel,
    fetched whole?  Where it is no longer than the longest chunk; a longer
    ring is walked in chunks, as a context is.  False for ``ring`` 0 (no
    ring: a layer that attends its whole context)."""
    return 0 < ring * block_size <= _MAX_CHUNK_TOKENS


def _held_bytes(q_shape, kv_shape):
    """Every lane's query and output as the kernel holds them, float32."""
    kv_heads = kv_shape[2] // q_shape[2]
    width = q_shape[2] if _compact(q_shape[1], kv_heads, q_shape[2]) \
        else kv_shape[2]
    return 2 * q_shape[0] * 4 * _query_rows(q_shape[1], kv_heads) * width


def _query_rows(heads, kv_heads):
    """Rows a lane's query (and output) takes in VMEM: one, broadcast over
    the heads, where each head has its own columns of the pool's width; a
    row a head, in whole sublane tiles, where ``heads // kv_heads`` query
    heads share a KV head's columns."""
    return 1 if heads == kv_heads else -(-heads // 16) * 16


def _compact(heads, kv_heads, head_dim):
    """Do the query and the output cross the kernel's boundary ``[rows,
    D]`` a lane, the kernel spreading them over the pool's width itself?
    Where query heads share a KV head and a head's ``D`` values fill whole
    128-lane tiles (the pieces are then joined and cut on tile borders)."""
    return heads != kv_heads and head_dim % 128 == 0


def attention_path(q_shape, kv_shape, kv_dtype, ring=0):
    """``"pallas"`` where the kernel would serve these shapes on this
    backend, else ``"gather"``: the same rule as ``paged_attention``,
    counted nowhere.  The engine names the step's path by it, in the
    executable's cache key and on the ``serving_prewarm`` event."""
    ok = all(ok for _reason, ok in
             paged_attention_checks(q_shape, kv_shape, kv_dtype, ring))
    return "pallas" if ok else "gather"


def blocks_read(context_lens, block_size, maxb, path, ring=False):
    """Blocks one layer's attention fetches for these lanes: every slot of
    the table on the gather path; on the kernel's, the blocks each lane
    holds (``ceil(context_len / block_size)``: a chunk fetches its live
    blocks and no others; what the latent form fetches twice, or for nobody,
    is ``blocks_refetched``'s to count, not this).
    For a window layer (``ring``: the table is its ring of
    ``maxb`` slots) a live lane's whole ring where the ring is one chunk
    (``_ring_whole``), and where it is walked in chunks the slots the lane
    holds, ``min(ceil(context_len / block_size), maxb)``: the same count as
    a context's (a host-side count for the step's span: ``context_lens`` is
    the numpy feed)."""
    if path != "pallas":
        return len(context_lens) * maxb
    if ring and _ring_whole(maxb, block_size):
        return int((context_lens > 0).sum()) * maxb
    return int((-(-context_lens // block_size)).clip(0, maxb).sum())


def _lane_chunks(context_lens, block_size, maxb, span):
    """Chunks of ``span`` positions that cover the blocks each lane holds."""
    held = (-(-context_lens // block_size)).clip(0, maxb)
    return -(-held // (span // block_size))


def chunks_read(context_lens, block_size, maxb, span):
    """``(chunks, full chunks)`` one layer's kernel walks for these lanes at
    ``span`` positions a chunk: a lane's chunks cover the blocks it holds
    (``blocks_read``), and a chunk is *full* when the lane sees every
    position of it (by positions, not blocks: a lane at 1,020 holds all the
    blocks of two chunks of 512 and sees 508 positions of the second).  All
    of a full chunk's copies and arithmetic are of use; of a lane's last
    chunk the arithmetic covers the whole span whatever part is seen, and
    the latent form pays a whole chunk's copies too.
    A host-side count for the step's span, as ``blocks_read``."""
    chunks = _lane_chunks(context_lens, block_size, maxb, span)
    return int(chunks.sum()), int(np.minimum(context_lens // span,
                                             chunks).sum())


def _straight(chunks):
    """Of a lane's ``chunks``, those the K/V kernel runs as its
    straight-line body: all but the last two (``max(chunks - 2, 0)``).  A
    chunk that is not the lane's last holds all its blocks, so its copies
    and waits need no guard; the body also fetches the chunk after it, so
    that one must hold all its own too.  The lane's last two bodies (the
    one that fetches the last chunk, and the last chunk, which fetches the
    next lane's first) keep the guarded copies.  The one statement of the
    rule: the kernel's walk (a traced count) and the step span's count (the
    numpy feed's) both read it here."""
    return (chunks - 2).clip(0)


def straight_chunks_read(context_lens, block_size, maxb, span):
    """Of the chunks one layer's K/V kernel walks for these lanes
    (``chunks_read``), those that run its straight-line body
    (``_straight``).  A host-side count for the step's span, as
    ``blocks_read``."""
    return int(_straight(
        _lane_chunks(context_lens, block_size, maxb, span)).sum())


def blocks_refetched(context_lens, block_size, maxb, span):
    """Blocks one layer's latent kernel fetches beyond those the lanes hold
    (``blocks_read``), at ``span`` positions a chunk.  Its body issues
    every copy of a chunk, a slot past the lane's last block fetching that
    block again (the rest of a lane's last chunk), and where nothing follows
    a live lane its last chunk is fetched once more, whole, for nobody.  A
    host-side count for the step's span, as ``blocks_read``."""
    per = span // block_size
    held = (-(-context_lens // block_size)).clip(0, maxb)
    live = held > 0
    ends = live & ~np.append(live[1:], False)     # nothing follows these
    return int((-held % per).sum()) + per * int(ends.sum())


def _chunk_positions(fetched, block_size, held):
    """Positions a chunk spans where one position costs ``fetched`` bytes
    in the pools the kernel reads (K and V rows, or the one latent row, in
    the pool's dtype): the fewest whole steps of ``CHUNK_TOKENS`` that hold
    ``_CHUNK_BYTES``, at most ``_MAX_CHUNK_TOKENS``, and no more than leave
    each pool's two buffers room in ``_VMEM_BUDGET`` beside the ``held``
    bytes of queries and outputs; one step, or one block, at the least."""
    step = CHUNK_TOKENS * fetched
    steps = min(-(-_CHUNK_BYTES // step), _MAX_CHUNK_TOKENS // CHUNK_TOKENS,
                (_VMEM_BUDGET - held) // (2 * step))
    return max(CHUNK_TOKENS * max(steps, 1), block_size)


def _chunk_blocks(block_size, maxb, fetched, held=0):
    """Blocks a chunk: ``_chunk_positions`` in whole blocks, capped by the
    table."""
    return max(1, min(_chunk_positions(fetched, block_size, held)
                      // block_size, maxb))


def chunk_positions(q_shape, kv_shape, kv_dtype, maxb, ring=0):
    """Positions one chunk of the kernel spans for these shapes and a table
    of ``maxb`` slots (a window layer's is its ring: the ring's length where
    it is one chunk, ``_ring_whole``, else the span its rows are worth, as a
    context's): what the engine puts on the ``serving_prewarm`` event beside
    the path's name."""
    if _ring_whole(ring, kv_shape[1]):
        return ring * kv_shape[1]
    maxb = ring or maxb
    fetched = 2 * jnp.dtype(kv_dtype).itemsize * kv_shape[2]
    return kv_shape[1] * _chunk_blocks(kv_shape[1], maxb, fetched,
                                       _held_bytes(q_shape, kv_shape))


# -- the kernel --------------------------------------------------------------

_NT = (((1,), (1,)), ((), ()))   # a [M, K] . b [N, K] -> [M, N]
_NN = (((1,), (0,)), ((), ()))   # a [M, K] . b [K, N] -> [M, N]


def _split3(x):
    """float32 -> three bfloat16 pieces whose sum is x to 2**-24."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _product(rows, x, dims):
    """``rows . x`` in ``x``'s precision.  bfloat16 ``x``: ``rows``
    rounded to bfloat16, one pass, float32 accumulation.  float32 ``x``:
    the six products of the operands' bfloat16 pieces that
    ``Precision.HIGHEST`` keeps (all but lo.lo, lo.mid, mid.lo), with the
    pieces of ``rows`` stacked so that each piece of ``x`` is contracted
    once; summed smallest first."""
    if x.dtype == jnp.bfloat16:
        return _dot(rows.astype(jnp.bfloat16), x, dims)
    n = rows.shape[0]
    r3 = jnp.concatenate(_split3(rows), axis=0)
    hi, mid, lo = _split3(x)
    by_hi = _dot(r3, hi, dims)
    by_mid = _dot(r3[:2 * n], mid, dims)
    by_lo = _dot(r3[:n], lo, dims)
    return ((by_lo + by_hi[2 * n:] + by_mid[n:])
            + (by_mid[:n] + by_hi[n:2 * n])) + by_hi[:n]


def _kernel(bt_ref, cl_ref, q_ref, *refs, heads, kv_heads, head_dim,
            block_size, maxb, per, scale, window, step, in_window, straights):
    """``window`` None: a lane's chunks cover positions ``[0,
    context_len)``.  Given: the table is a ring of ``maxb`` slots and
    ``ring_mask``'s rule says which of its rows are attended; with ``per ==
    maxb`` a live lane's one chunk is the ring as it lies, with ``per <
    maxb`` the lane's chunks cover the slots it holds, as they cover a
    context.

    A lane's chunks run one of two bodies, by their count alone
    (``_straight``): all but the last two ``straight``, one basic block with
    no predicate and constant buffer indices; the last two ``guarded``, whose
    copies are the blocks the lane holds and no others.  ``step`` is the
    blocks of ``CHUNK_TOKENS`` positions, ``in_window`` and ``straights``
    the module's ``_in_window`` and ``_straight`` as the call found them
    (``_kernel_call`` keys a traced kernel by all three)."""
    k_hbm, v_hbm, o_ref, kbuf, vbuf, sem = refs
    pools = ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1))
    lanes = cl_ref.shape[0]
    hd = kv_heads * head_dim             # the pool's width
    group = heads // kv_heads            # query heads a KV head
    rows = -(-heads // 16) * 16          # whole sublane tiles in any dtype
    span = per * block_size              # positions a chunk
    # a window layer's ring in one chunk, fetched whole; a longer ring is
    # walked as a context is
    whole = window is not None and per == maxb

    def blocks(b):
        """Blocks lane ``b`` holds: what its chunks fetch."""
        return jnp.minimum((cl_ref[b] + block_size - 1) // block_size, maxb)

    def chunks(b):
        if whole:
            return jnp.minimum(cl_ref[b], 1)
        return (blocks(b) + per - 1) // per

    def copies(slot, i, block):
        """Block ``block()`` of each pool into rows ``i`` of buffer
        ``slot``, a copy a pool (``block`` is called a pool: the ring's
        lowering reads its slot of the table once for K and once for V)."""
        at = pl.ds(i * block_size, block_size) if isinstance(i, int) \
            else pl.ds(pl.multiple_of(i * block_size, block_size), block_size)
        for pool, buf, which in pools:
            yield pltpu.make_async_copy(pool.at[block()], buf.at[slot, at],
                                        sem.at[which, slot])

    def wait_whole(slot):
        """For every copy of a chunk that holds all its blocks."""
        # a wait needs the copy's shape and semaphore, not its source
        for i in range(per):
            for dma in copies(slot, i, lambda: 0):
                dma.wait()

    if whole:
        # the ring is one chunk, fetched whole in the table's order: a slot
        # the lane does not hold yet (-1) fetches block 0, and ``ring_mask``
        # leaves its positions out
        def start(b, c, slot):
            def slot_of(i):
                j = jnp.minimum(c * per + i, maxb - 1)
                return jnp.maximum(bt_ref[b * maxb + j], 0)

            for i in range(per):
                for dma in copies(slot, i, functools.partial(slot_of, i)):
                    dma.start()

        def wait(b, c, slot):
            wait_whole(slot)
    else:
        # the guarded copies, for a chunk that may be a lane's last: the
        # lane's live blocks among its ``per`` (of a ring
        # walked in chunks: the slots it holds; one whose block left the
        # window names none and fetches block 0, masked): the last
        # chunk of a lane fetches the blocks the lane holds and no others,
        # and as many copies are waited for as were started.  A copy issued
        # from straight-line code costs a third of one issued from a loop
        # (the scalar core runs the kernel's one instruction stream), so
        # the blocks go by whole steps of ``CHUNK_TOKENS`` positions, each
        # under one predicate, and only the last step's few in a loop
        steps = per // step

        def named(b, c, i):
            """The block slot ``i`` of lane ``b``'s chunk ``c`` names."""
            return jnp.maximum(bt_ref[b * maxb + c * per + i], 0)

        def transfer(act, b, c, slot):
            n = jnp.minimum(blocks(b) - c * per, per)

            def one(i, _=None):
                # a wait needs the copy's shape and semaphore, not its source
                block = named(b, c, i) if act == "start" else 0
                for dma in copies(slot, i, lambda: block):
                    getattr(dma, act)()

            for k in range(steps):
                @pl.when(n >= (k + 1) * step)
                def _whole_step():
                    for i in range(k * step, (k + 1) * step):
                        one(i)

            jax.lax.fori_loop(jnp.minimum(n // step, steps) * step, n, one,
                              None)

        start = functools.partial(transfer, "start")
        wait = functools.partial(transfer, "wait")

        # rows no copy writes are still multiplied (by probabilities that
        # are 0): they have to be finite, so they start as zeros, and later
        # hold an earlier chunk's rows
        def clear():
            for _pool, buf, _which in pools:
                buf[...] = jnp.zeros(buf.shape, buf.dtype)

        clear()

    # row r of the mask covers the columns of query head r's KV head
    # (r // group; its own where group is 1) in the folded width
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, hd), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, hd), 0)
    if group > 1:
        row = row // group
    row = row * head_dim
    own = ((col >= row) & (col < row + head_dim)).astype(jnp.float32)

    def lane(b, g):
        """One lane; ``g`` counts the chunks fetched so far, so ``g % 2``
        is the buffer this lane's first chunk lies (or will lie) in."""
        n = chunks(b)
        ctx = cl_ref[b]

        # the lane before, when it had a chunk, fetched this one's first
        @pl.when((n > 0) & ((b == 0) | (chunks(jnp.maximum(b - 1, 0)) == 0)))
        def _first():
            start(b, 0, g % 2)

        # one row broadcast over the heads, or (grouped) a row a head
        # with its query in every KV head's columns: as it came, or (the
        # compact layout) repeated here
        qb = q_ref[b]
        if qb.shape[1] != hd:
            qb = jnp.concatenate([qb] * kv_heads, axis=1)
        qx = qb * own                                    # [rows, hd]

        def fold(c, carry, slot):
            """Chunk ``c``, as it lies in buffer ``slot``, into the sums."""
            m, l, acc = carry
            sc = _product(qx, kbuf[slot], _NT) * scale   # [rows, span]
            pos = c * span + jax.lax.broadcasted_iota(
                jnp.int32, (1, span), 1)
            if window is None:
                seen = pos < ctx
            elif whole:
                # c is 0 and span the ring's length
                seen = in_window(ctx, pos, span, window)
            else:
                # chunk c of the ring: its entries by age; the last chunk
                # may reach past the ring's end
                ring_len = maxb * block_size
                seen = in_window(ctx, pos, ring_len, window) \
                    & (pos < ring_len)
            sc = jnp.where(seen, sc, _MASK)
            m_new = jnp.maximum(m, jnp.max(sc, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(sc - m_new)
            l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
            value = vbuf[slot]
            acc = alpha * acc + _product(p, value, _NN)
            return m_new, l, acc

        def guarded(c, carry):
            """A chunk that may be the lane's last, or fetch it: the copies
            it starts and those it waits for are the blocks the lane holds,
            and what it fetches may be the next lane's first chunk."""
            slot = (g + c) % 2
            more = c + 1 < n
            nxt = jnp.minimum(b + 1, lanes - 1)

            @pl.when(more | ((b + 1 < lanes) & (chunks(nxt) > 0)))
            def _prefetch():
                start(jnp.where(more, b, nxt), jnp.where(more, c + 1, 0),
                      1 - slot)

            wait(b, c, slot)
            return fold(c, carry, slot)

        def straight(c, carry, slot):
            """A chunk that holds all its blocks and whose successor, in the
            same lane, holds all its own (``_straight``): no predicate, no
            loop and a buffer that is a constant, so one basic block in
            which the scheduler lays the next chunk's copies, this one's
            waits and the arithmetic side by side."""
            for i in range(per):
                block = named(b, c + 1, i)
                for dma in copies(1 - slot, i, lambda: block):
                    dma.start()
            wait_whole(slot)
            return fold(c, carry, slot)

        def walk(even, carry):
            """The lane's straight chunks, 0, 2, ... in buffer ``even``."""
            def pair(k, carry):
                return straight(2 * k + 1, straight(2 * k, carry, even),
                                1 - even)

            return jax.lax.fori_loop(
                first // 2 * 2, first,
                lambda c, carry: straight(c, carry, even),
                jax.lax.fori_loop(0, first // 2, pair, carry))

        carry = (jnp.full((rows, 1), _MASK, jnp.float32),
                 jnp.zeros((rows, 1), jnp.float32),
                 jnp.zeros((rows, hd), jnp.float32))
        first = 0                  # the first guarded chunk of the lane
        if maxb > 2 * per:
            # a lane may hold more than two chunks: all but its last two go
            # straight-line, in one of two copies of the walk by the buffer
            # its first chunk lies in
            first = straights(n)
            carry = jax.lax.cond(g % 2 == 0, functools.partial(walk, 0),
                                 functools.partial(walk, 1), carry)
        _m, l, acc = jax.lax.fori_loop(first, n, guarded, carry)
        # an idle lane has l == 0 and acc == 0: zeros out, not 0 / 0
        out = acc / jnp.where(l > 0, l, 1.0) * own
        if group == 1:
            # each column belongs to one row: fold the rows
            out = jnp.sum(out, axis=0, keepdims=True)
        elif o_ref.shape[2] != hd:
            # compact: a row's owned columns are its KV head's D; every
            # other piece is zeros
            out = sum(out[:, i * head_dim:(i + 1) * head_dim]
                      for i in range(kv_heads))
        o_ref[b] = out.astype(o_ref.dtype)
        return g + n

    jax.lax.fori_loop(0, lanes, lane, jnp.int32(0))


def _latent_kernel(bt_ref, cl_ref, q_ref, *refs, block_size, maxb, per,
                   scale, value_cols, masked=False):
    """The latent form: one pool, one pair of chunk buffers, a row's value
    its first ``value_cols`` columns, ``q_ref`` rows the query heads over
    the one cached head.  ``masked`` (a layer that selects): one more
    operand follows ``q_ref``, ``[lanes, chunks, span]`` int32 laid out by
    chunk, and a position counts where the context holds it AND its entry
    there is not 0.  A last SMEM cell given: the grid walks the lanes,
    ``q_ref``, ``o_ref`` and the mask are one lane's, and the count of
    chunks fetched so far passes from a grid step to the next there, as the
    chunk buffers and the copies in flight do in VMEM.

    Every chunk runs ONE straight-line body in which the scheduler lays the
    copies' issue, the matrix work and the softmax chain side by side.  Two
    things make that possible.  A chunk's copies need no predicate: a slot
    of the table past the lane's last block fetches that block again (rows
    the mask leaves out), and where nothing follows a chunk it is fetched
    again for nobody, waited out by the next lane that starts its own first
    chunk or by the kernel's end.  And the buffers' indices are compile-time
    constants: a lane walks its chunks in pairs, the first of a pair in one
    buffer and the second in the other, in one of two copies of the walk by
    the parity of the chunks fetched before it; with a traced index the
    compiler must take a copy into one buffer and a load from the other for
    the same memory, and keeps them in program order."""
    if masked:
        mask_ref, *refs = refs
    pool, o_ref, buf, sem, *fetched = refs
    lanes = cl_ref.shape[0]
    rows = q_ref.shape[1]
    span = per * block_size              # positions a chunk

    def blocks(b):
        """Blocks lane ``b`` holds."""
        return jnp.minimum((cl_ref[b] + block_size - 1) // block_size, maxb)

    def chunks(b):
        return (blocks(b) + per - 1) // per

    def copy(slot, i, block):
        return pltpu.make_async_copy(
            pool.at[block], buf.at[slot, pl.ds(i * block_size, block_size)],
            sem.at[slot])

    def start(b, c, slot):
        """Chunk ``c`` of live lane ``b`` into buffer ``slot``."""
        base, last = b * maxb, blocks(b) - 1
        for i in range(per):
            copy(slot, i,
                 bt_ref[base + jnp.minimum(c * per + i, last)]).start()

    def wait(slot):
        # a wait needs the copy's shape and semaphore, not its source
        for i in range(per):
            copy(slot, i, 0).wait()

    def lane(b, g):
        """One lane; ``g`` counts the chunks fetched so far, so ``g % 2``
        is the buffer this lane's first chunk lies (or will lie) in."""
        n = chunks(b)
        ctx = cl_ref[b]
        nxt = jnp.minimum(b + 1, lanes - 1)
        follows = (b + 1 < lanes) & (chunks(nxt) > 0)

        # the lane before, when it had a chunk, fetched this one's first
        @pl.when((n > 0) & ((b == 0) | (chunks(jnp.maximum(b - 1, 0)) == 0)))
        def _first():
            # the last live lane's last chunk fetched again for nobody
            pl.when(g > 0)(lambda: wait(g % 2))
            start(b, 0, g % 2)

        mine = 0 if fetched else b      # this lane's place in q_ref, o_ref
        qx = q_ref[mine]

        def chunk(c, carry, slot):
            m, l, acc = carry
            # what follows: the lane's next chunk, the next lane's first,
            # or (nothing) this one again
            more = c + 1 < n
            start(jnp.where(more | ~follows, b, nxt),
                  jnp.where(more, c + 1, jnp.where(follows, 0, c)), 1 - slot)
            wait(slot)
            sc = _product(qx, buf[slot], _NT) * scale    # [rows, span]
            pos = c * span + jax.lax.broadcasted_iota(
                jnp.int32, (1, span), 1)
            seen = pos < ctx
            if masked:
                seen = seen & (mask_ref[mine, pl.ds(c, 1), :] != 0)
            sc = jnp.where(seen, sc, _MASK)
            m_new = jnp.maximum(m, jnp.max(sc, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(sc - m_new)
            l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
            acc = alpha * acc + _product(p, buf[slot][:, :value_cols], _NN)
            return m_new, l, acc

        def walk(even):
            """The lane's chunks, 0, 2, ... in buffer ``even``."""
            def pair(k, carry):
                return chunk(2 * k + 1, chunk(2 * k, carry, even), 1 - even)

            carry = jax.lax.fori_loop(
                0, n // 2, pair, (jnp.full((rows, 1), _MASK, jnp.float32),
                                  jnp.zeros((rows, 1), jnp.float32),
                                  jnp.zeros((rows, value_cols), jnp.float32)))
            _m, l, acc = jax.lax.fori_loop(
                n // 2 * 2, n, lambda c, carry: chunk(c, carry, even), carry)
            # an idle lane has l == 0 and acc == 0: zeros out, not 0 / 0
            o_ref[mine] = (acc / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)

        for even in (0, 1):
            pl.when(g % 2 == even)(functools.partial(walk, even))
        return g + n

    def drain(g):
        """Wait out what the last live lane's last chunk fetched again."""
        pl.when(g > 0)(lambda: wait(g % 2))

    if fetched:
        count, = fetched
        b = pl.program_id(0)

        @pl.when(b == 0)
        def _none_yet():
            count[0] = 0

        count[0] = lane(b, count[0])
        pl.when(b == lanes - 1)(lambda: drain(count[0]))
    else:
        drain(jax.lax.fori_loop(0, lanes, lane, jnp.int32(0)))


@functools.lru_cache(maxsize=None)
def _kernel_call(q_shape, pool_shape, k_dtype, v_dtype, maxb, heads, head_dim,
                 per, scale, window, interpret, step, in_window, straights):
    """The kernel's call for one set of shapes, traced once: a model's
    layers (and a multi-token step's positions) call one ``jit`` whose
    jaxpr is inlined where it is called, so a step of 24 layers traces and
    lowers the kernel's seven chunk bodies once, not 24 times (0.7 s a
    call site on the chip's host with nothing cached).  Everything the
    kernel reads beside its operands is in the key, what a test or a chip
    check's control swaps in the module (``CHUNK_TOKENS`` as ``step``,
    ``_in_window``, ``_straight``) too: a swapped rule is another kernel."""
    bb, qrows, width = q_shape
    _nb, bs, hd = pool_shape
    whole = lambda i, bt, cl: (0, 0, 0)
    return jax.jit(pl.pallas_call(
        functools.partial(_kernel, heads=heads, kv_heads=hd // head_dim,
                          head_dim=head_dim, block_size=bs, maxb=maxb,
                          per=per, scale=scale, window=window, step=step,
                          in_window=in_window, straights=straights),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[pl.BlockSpec((bb, qrows, width), whole),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((bb, qrows, width), whole),
            scratch_shapes=[pltpu.VMEM((2, per * bs, hd), k_dtype),
                            pltpu.VMEM((2, per * bs, hd), v_dtype),
                            pltpu.SemaphoreType.DMA((2, 2))],
        ),
        out_shape=jax.ShapeDtypeStruct((bb, qrows, width), jnp.float32),
        name=KERNEL_NAME,
        interpret=interpret,
    ), inline=True)


def _paged_pallas(q, k_cache, v_cache, block_tables, context_lens,
                  scale=None, interpret=None, window=None):
    """q [B, H, D] against folded pools [num_blocks, block_size, KH * D]
    -> [B, H, D].  ``interpret`` None follows the backend; ``window``
    given, the table is a window layer's ring."""
    bb, h, d = q.shape
    _nb, bs, hd = k_cache.shape
    kh = hd // d
    maxb = block_tables.shape[1]
    per = chunk_positions(q.shape, k_cache.shape, k_cache.dtype, maxb,
                          ring=maxb if window is not None else 0) // bs
    if interpret is None:
        interpret = adoption.interpret()
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    qrows = _query_rows(h, kh)
    q = q.astype(jnp.float32)
    compact = _compact(h, kh, d)
    if kh == h:
        qx = q.reshape(bb, 1, hd)
    else:
        # a row a head; spread, the head's query repeated under every KV
        # head's columns (the kernel's mask keeps the one it owns); compact,
        # as it is, and the kernel repeats it
        qx = jnp.pad(q if compact else jnp.tile(q, (1, 1, kh)),
                     ((0, 0), (0, qrows - h), (0, 0)))
    out = _kernel_call(
        qx.shape, k_cache.shape, k_cache.dtype, v_cache.dtype, maxb, h, d,
        per, float(scale), window, interpret, max(1, CHUNK_TOKENS // bs),
        _in_window, _straight)(
            block_tables.astype(jnp.int32).reshape(-1),
            context_lens.astype(jnp.int32), qx, k_cache, v_cache)
    if kh == h:
        return out.reshape(bb, h, d)
    if compact:
        return out[:, :h]
    # row r holds head r's output in its KV head's columns, zeros elsewhere
    return out[:, :h].reshape(bb, h, kh, d).sum(axis=2)


def paged_attention(q, k_cache, v_cache, block_tables, context_lens,
                    scale=None, window=None):
    """The step's attention over a layer's pools: the kernel where the
    shape rule admits it (``adoption.decide`` counts the lowering under
    ``pallas_kernel_used_total`` / ``..._fallback_total{reason}``), the
    gather otherwise.  The pools' width says how many KV heads they store;
    ``scale`` None is ``1 / sqrt(D)``.  ``window`` given, the layer attends
    its last ``window`` positions and ``block_tables`` is its ring."""
    ring = block_tables.shape[1] if window is not None else 0
    use, _reason = adoption.decide(
        "paged_attention",
        paged_attention_checks(q.shape, k_cache.shape, k_cache.dtype, ring))
    if use:
        with jax.named_scope("kv_read"):
            return _paged_pallas(q, k_cache, v_cache, block_tables,
                                 context_lens, scale, window=window)
    return paged_attention_reference(q, k_cache, v_cache, block_tables,
                                     context_lens, scale, window)


# -- the latent form ---------------------------------------------------------

def masked_latent(q, rows, context_lens, scale, rank, chosen=None):
    """Absorbed latent attention over a contiguous history: q [B, H, W]
    against ``rows`` [B, S, W], one cached head whose value is its first
    ``rank`` columns -> [B, H, rank]; ``chosen`` [B, S] bool given, over the
    positions it marks alone.  ``masked_attention`` with that one
    head, so the gather path and the unpaged loop stay bitwise-comparable."""
    rows = rows[:, :, None, :]
    return masked_attention(q, rows, rows[..., :rank], context_lens, scale,
                            chosen=chosen)


def latent_attention_reference(q, pool, block_tables, context_lens, scale,
                               rank):
    """The jnp path: gather the table's blocks into contiguous rows, then
    ``masked_latent``."""
    with jax.named_scope("kv_gather"):
        rows = gather_blocks(pool, block_tables)
    return masked_latent(q, rows, context_lens, scale, rank)


def latent_vmem_bytes(q_shape, pool_shape, pool_dtype, rank):
    """What the latent kernel holds in VMEM: two chunk buffers of the span
    ``_chunk_positions`` gives these shapes (the call's too), and the
    queries and outputs of the lanes it holds at once, float32."""
    fetched = jnp.dtype(pool_dtype).itemsize * q_shape[2]     # the one row
    held = _latent_held_bytes(q_shape, pool_shape, pool_dtype, rank)
    return 2 * _chunk_positions(fetched, pool_shape[1], held) * fetched \
        + held


def _latent_lane_bytes(q_shape, rank):
    """One lane's query and output as the latent kernel holds them,
    float32."""
    _lanes, heads, padded = q_shape
    return 4 * (-(-heads // 16) * 16) * (padded + rank)


def _latent_lane_grid(q_shape, pool_shape, pool_dtype, rank):
    """Does the grid walk the lanes, a lane's query and output in VMEM at a
    time (the pipeline fetches the next lane's beside them)?  Where every
    lane's together would not leave the chunk buffers their full span in
    ``_VMEM_BUDGET``: 128 query heads of 640 + 512 values are 589,824 B a
    lane, 18.9e6 B at 32 lanes, and 64 heads half that a lane, 9.4e6 B at
    32 lanes and 18.9e6 at 64.  Where they do (32 heads: 4.7e6 B), one
    grid step holds them all and the lanes are a loop inside it."""
    fetched = jnp.dtype(pool_dtype).itemsize * q_shape[2]
    return q_shape[0] * _latent_lane_bytes(q_shape, rank) \
        + 2 * _chunk_positions(fetched, pool_shape[1], 0) * fetched \
        > _VMEM_BUDGET


def _latent_held_bytes(q_shape, pool_shape, pool_dtype, rank):
    """Queries and outputs the latent kernel holds, float32: every lane's,
    or (``_latent_lane_grid``) one lane's twice."""
    lanes = 2 if _latent_lane_grid(q_shape, pool_shape, pool_dtype, rank) \
        else q_shape[0]
    return lanes * _latent_lane_bytes(q_shape, rank)


def latent_chunk_positions(q_shape, pool_shape, pool_dtype, rank, maxb):
    """``chunk_positions`` of the latent form."""
    fetched = jnp.dtype(pool_dtype).itemsize * q_shape[2]
    return pool_shape[1] * _chunk_blocks(
        pool_shape[1], maxb, fetched,
        _latent_held_bytes(q_shape, pool_shape, pool_dtype, rank))


def latent_attention_checks(q_shape, pool_shape, pool_dtype, rank):
    """Ordered (reason, ok) pairs for adoption.decide(): what the latent
    kernel needs of the query ``[B, H, W]``, of the pool ``[num_blocks,
    block_size, W]`` in ``pool_dtype`` and of ``rank``, the leading columns
    of a row that are its value."""
    dims = tuple(q_shape) + tuple(pool_shape) + (rank,)
    static = all(isinstance(x, int) and x >= 0 for x in dims)
    rank3 = len(q_shape) == 3 and len(pool_shape) == 3
    tile = _SUBLANES.get(jnp.dtype(pool_dtype).name)
    shaped = static and rank3 and all(x > 0 for x in dims)
    return [
        ("backend", adoption.interpret_mode()
         or jax.default_backend() == "tpu"),
        ("symbolic_shape", static),
        ("rank", rank3),
        ("dtype", tile is not None),
        # the row, which the query's width is, and its value are whole
        # 128-lane tiles
        ("lanes", shaped and q_shape[2] == pool_shape[2]
         and pool_shape[2] % 128 == 0 and rank % 128 == 0
         and rank <= pool_shape[2]),
        ("block_size", static and rank3 and tile is not None
         and pool_shape[1] > 0 and pool_shape[1] % tile == 0),
        ("empty", shaped),
        ("vmem", shaped and tile is not None and latent_vmem_bytes(
            q_shape, pool_shape, pool_dtype, rank) <= _VMEM_BUDGET),
    ]


def latent_path(q_shape, pool_shape, pool_dtype, rank):
    """``"pallas"`` where the latent kernel would serve these shapes on this
    backend, else ``"gather"``: ``latent_attention``'s rule, counted
    nowhere."""
    ok = all(ok for _reason, ok in
             latent_attention_checks(q_shape, pool_shape, pool_dtype, rank))
    return "pallas" if ok else "gather"


@functools.lru_cache(maxsize=None)
def _latent_call(q_shape, mask_shape, pool_shape, pool_dtype, maxb, per,
                 scale, rank, lane_grid, interpret, kernel, product):
    """The latent kernel's call for one set of shapes, traced once, as
    ``_kernel_call`` is for the K/V form: a model's latent layers call one
    ``jit`` whose jaxpr is inlined where it is called, so a step of 40 such
    layers traces and lowers the kernel's straight-line body once, not 40
    times (a second a call site with nothing cached: 45 s of lowering
    Xing4.0's step here, 6 with this).  ``q_shape`` is the query as the
    kernel holds it (its rows filled up to 16s), ``mask_shape`` a selecting
    layer's mask or None.  Everything the kernel reads beside its operands
    is in the key, the kernel and its product too (``kernel``, ``product``:
    a test or a check that swaps either gets another call)."""
    bb, rows, width = q_shape
    bs = pool_shape[1]
    held = 1 if lane_grid else bb
    mine = (lambda i, bt, cl: (i, 0, 0)) if lane_grid \
        else (lambda i, bt, cl: (0, 0, 0))
    masks = () if mask_shape is None else (mask_shape,)
    del product                         # read by ``kernel`` from the module
    return jax.jit(pl.pallas_call(
        functools.partial(kernel, block_size=bs, maxb=maxb, per=per,
                          scale=scale, value_cols=rank,
                          masked=mask_shape is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bb // held,),
            in_specs=[pl.BlockSpec((held, rows, width), mine)]
            + [pl.BlockSpec((held,) + m[1:], mine) for m in masks]
            + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((held, rows, rank), mine),
            scratch_shapes=[pltpu.VMEM((2, per * bs, width), pool_dtype),
                            pltpu.SemaphoreType.DMA((2,))]
            + ([pltpu.SMEM((1,), jnp.int32)] if lane_grid else []),
        ),
        out_shape=jax.ShapeDtypeStruct((bb, rows, rank), jnp.float32),
        name=LATENT_KERNEL_NAME,
        interpret=interpret,
    ), inline=True)


def _latent_pallas(q, pool, block_tables, context_lens, scale, rank,
                   interpret=None, chosen=None):
    """q [B, H, W] against one pool [num_blocks, block_size, W] -> [B, H,
    rank].  ``chosen`` given (``_chunk_mask``'s [B, chunks, span] int32, of a
    layer that selects): over the positions it marks alone, of those a
    lane's context holds."""
    h = q.shape[1]
    bs = pool.shape[1]
    maxb = block_tables.shape[1]
    per = latent_chunk_positions(q.shape, pool.shape, pool.dtype, rank,
                                 maxb) // bs
    if interpret is None:
        interpret = adoption.interpret()
    rows = -(-h // 16) * 16
    qx = jnp.pad(q.astype(jnp.float32), ((0, 0), (0, rows - h), (0, 0)))
    # every lane's query and output in one grid step, or a lane a step
    lane_grid = _latent_lane_grid(q.shape, pool.shape, pool.dtype, rank)
    # a layer that selects hands its mask in after the query: a lane's
    # chunks of it, or every lane's, as the query's
    masks = () if chosen is None else (chosen,)
    out = _latent_call(
        qx.shape, None if chosen is None else tuple(chosen.shape),
        tuple(pool.shape), pool.dtype, maxb, per, float(scale), rank,
        lane_grid, interpret, _latent_kernel, _product)(
            block_tables.astype(jnp.int32).reshape(-1),
            context_lens.astype(jnp.int32), qx, *masks, pool)
    return out[:, :h]


def latent_attention(q, pool, block_tables, context_lens, scale, rank):
    """The step's attention over a latent layer's pool: q [B, H, W] (the
    absorbed query: its latent part and its shared-key part), ``pool``
    [num_blocks, block_size, W] -> [B, H, rank], the probabilities' sum of
    the rows' first ``rank`` columns.  The kernel where the shape rule
    admits it (``adoption.decide`` counts the lowering as
    ``latent_attention``), the gather otherwise."""
    use, _reason = adoption.decide(
        "latent_attention",
        latent_attention_checks(q.shape, pool.shape, pool.dtype, rank))
    if use:
        with jax.named_scope("kv_read"):
            return _latent_pallas(q, pool, block_tables, context_lens, scale,
                                  rank)
    return latent_attention_reference(q, pool, block_tables, context_lens,
                                      scale, rank)


# -- a latent layer that selects ---------------------------------------------

# positions a chunk of the index kernel spans: 64 blocks of 16 keys, 262,144
# B of 128 bfloat16 values a key in each of two buffers
INDEX_CHUNK_TOKENS = 1024

# by name, so that a check can score with it taken out
# (benchmark/tests/chip_check_glm.py): a head's activation
_index_act = jax.nn.relu


def dense_index_scores(qi, w, keys, context_lens):
    """The indexer's scores over a contiguous history: ``qi`` [B, J, E] its
    queries and ``w`` [B, J] their heads' weights (float32), ``keys`` [B, S,
    E] the cached index keys -> [B, S] float32, ``sum_j w_j relu(qi_j .
    key(s))``, ``-inf`` at and past ``context_lens``.  The keys' dtype is the
    products' input dtype (a bfloat16 pool rounds the queries once), the
    accumulation, the ReLU and the heads' sum float32.  Shared by the gather
    path and the unpaged loop."""
    sc = jnp.einsum("bjd,bsd->bjs", qi.astype(keys.dtype), keys,
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)
    out = jnp.sum(_index_act(sc) * w.astype(jnp.float32)[:, :, None], axis=1)
    pos = jnp.arange(keys.shape[1], dtype=jnp.int32)[None, :]
    return jnp.where(pos < context_lens.astype(jnp.int32)[:, None], out,
                     -jnp.inf)


def _index_chunk_blocks(block_size, maxb):
    return max(1, min(INDEX_CHUNK_TOKENS // block_size, maxb))


def index_scores_checks(q_shape, pool_shape, pool_dtype, maxb=1):
    """Ordered (reason, ok) pairs for adoption.decide(): what the index
    kernel needs of the queries ``[B, J, E]`` and of the pool ``[num_blocks,
    block_size, E]`` in ``pool_dtype``, under a table of ``maxb`` slots."""
    dims = tuple(q_shape) + tuple(pool_shape) + (maxb,)
    static = all(isinstance(x, int) and x >= 0 for x in dims)
    rank3 = len(q_shape) == 3 and len(pool_shape) == 3
    tile = _SUBLANES.get(jnp.dtype(pool_dtype).name)
    shaped = static and rank3 and all(x > 0 for x in dims)
    return [
        ("backend", adoption.interpret_mode()
         or jax.default_backend() == "tpu"),
        ("symbolic_shape", static),
        ("rank", rank3),
        ("dtype", tile is not None),
        # a key is whole 128-lane tiles, the queries' heads whole sublanes
        ("lanes", shaped and q_shape[2] == pool_shape[2]
         and pool_shape[2] % 128 == 0 and q_shape[1] % 8 == 0),
        ("block_size", static and rank3 and tile is not None
         and pool_shape[1] > 0 and pool_shape[1] % tile == 0),
        ("empty", shaped),
        # two chunk buffers, a lane's scores twice, its queries
        ("vmem", shaped and tile is not None
         and 2 * _index_chunk_blocks(pool_shape[1], maxb) * pool_shape[1]
         * pool_shape[2] * jnp.dtype(pool_dtype).itemsize
         + 8 * (maxb * pool_shape[1] + INDEX_CHUNK_TOKENS)
         + 8 * q_shape[1] * (q_shape[2] + 128) <= _VMEM_BUDGET),
    ]


def index_path(q_shape, pool_shape, pool_dtype, maxb=1):
    """``"pallas"`` where the index kernel would serve these shapes on this
    backend, else ``"gather"``: ``index_scores``'s rule, counted nowhere."""
    ok = all(ok for _reason, ok in
             index_scores_checks(q_shape, pool_shape, pool_dtype, maxb))
    return "pallas" if ok else "gather"


def _index_kernel(bt_ref, cl_ref, q_ref, w_ref, pool, o_ref, buf, sem, *,
                  block_size, maxb, per):
    """One lane a grid step: its queries ``q_ref`` [1, J, E] and weights
    ``w_ref`` [1, J, 1] against its live blocks of ``pool``, ``per`` blocks
    a chunk through the two buffers of ``buf``, the next chunk in flight
    while this one is scored -> ``o_ref`` [1, 1, positions] (whole chunks
    long).  Every chunk issues all its copies: a slot past the lane's last
    block fetches that block again, and its positions are masked."""
    b = pl.program_id(0)
    span = per * block_size
    ctx = cl_ref[b]
    held = jnp.minimum((ctx + block_size - 1) // block_size, maxb)
    n = (held + per - 1) // per

    def copy(slot, i, block):
        return pltpu.make_async_copy(
            pool.at[block], buf.at[slot, pl.ds(i * block_size, block_size)],
            sem.at[slot])

    def start(c, slot):
        base, last = b * maxb, held - 1
        for i in range(per):
            copy(slot, i,
                 bt_ref[base + jnp.minimum(c * per + i, last)]).start()

    def wait(slot):
        # a wait needs the copy's shape and semaphore, not its source
        for i in range(per):
            copy(slot, i, 0).wait()

    o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, o_ref.dtype)
    pl.when(n > 0)(lambda: start(0, 0))
    qx = q_ref[0]                         # [J, E]
    wx = w_ref[0]                         # [J, 1]

    def chunk(c, carry):
        slot = c % 2
        pl.when(c + 1 < n)(lambda: start(c + 1, 1 - slot))
        wait(slot)
        sc = _product(qx, buf[slot], _NT)                 # [J, span]
        out = jnp.sum(_index_act(sc) * wx, axis=0, keepdims=True)
        pos = c * span + jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)
        o_ref[0, :, pl.ds(pl.multiple_of(c * span, span), span)] = \
            jnp.where(pos < ctx, out, -jnp.inf)
        return carry

    jax.lax.fori_loop(0, n, chunk, None)


def _index_pallas(qi, w, pool, block_tables, context_lens, interpret=None):
    """qi [B, J, E], w [B, J] against one pool [num_blocks, block_size, E]
    -> [B, maxb * block_size] float32."""
    bb, heads, width = qi.shape
    bs = pool.shape[1]
    maxb = block_tables.shape[1]
    per = _index_chunk_blocks(bs, maxb)
    span = per * bs
    padded = -(-maxb * bs // span) * span
    if interpret is None:
        interpret = adoption.interpret()
    mine = lambda i, bt, cl: (i, 0, 0)
    out = pl.pallas_call(
        functools.partial(_index_kernel, block_size=bs, maxb=maxb, per=per),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bb,),
            in_specs=[pl.BlockSpec((1, heads, width), mine),
                      pl.BlockSpec((1, heads, 1), mine),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, 1, padded), mine),
            scratch_shapes=[pltpu.VMEM((2, span, width), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=jax.ShapeDtypeStruct((bb, 1, padded), jnp.float32),
        name=INDEX_KERNEL_NAME,
        interpret=interpret,
    )(block_tables.astype(jnp.int32).reshape(-1),
      context_lens.astype(jnp.int32), qi.astype(jnp.float32),
      w.astype(jnp.float32)[:, :, None], pool)
    return out[:, 0, :maxb * bs]


def index_scores(qi, w, pool, block_tables, context_lens):
    """The indexer's scores of every position a lane's table names: ``qi``
    [B, J, E], ``w`` [B, J], ``pool`` [num_blocks, block_size, E] -> [B,
    maxb * block_size] float32, ``-inf`` at and past a lane's context
    (``dense_index_scores``'s mathematics).  The kernel where the shape rule
    admits it (``adoption.decide`` counts the lowering as
    ``index_scores``), the gather of the padded table otherwise."""
    use, _reason = adoption.decide(
        "index_scores", index_scores_checks(
            qi.shape, pool.shape, pool.dtype, block_tables.shape[1]))
    if use:
        return _index_pallas(qi, w, pool, block_tables, context_lens)
    return dense_index_scores(qi, w, gather_blocks(pool, block_tables),
                              context_lens)


def _order_key(scores):
    """float32 -> the int32 that orders as it does in the plain total order
    (``-inf < ... < -0.0 < +0.0 < ... < +inf``, nothing canonicalised, which
    is how ``lax.top_k`` orders them on the CPU and on the TPU): the bits,
    the magnitude flipped where the sign is set."""
    bits = jax.lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.int32)
    return bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))


def chosen_by_chunk(scores, context_lens, k, chunks, span):
    """The ``k`` positions of largest score a lane as the masked walk reads
    them: ``scores`` [B, S] (``-inf`` at and past a lane's context) -> [B,
    chunks, span] int32, 1 at ``[c, j]`` where the lane chose position ``c *
    span + j``: ``chosen_mask``'s set of ``choose``'s result exactly, "of
    equal scores the lower position first", by a threshold and no sort.

    A lane's threshold ``t`` is its ``k``-th largest key (``_order_key``):
    the largest ``t`` with ``count(key >= t) >= k``, found bit by bit from
    the sign down, 32 counts.  Every position with ``key > t`` is chosen and,
    of those with ``key == t``, the ``need = k - count(key > t)`` lowest: the
    position of the ``need``-th is found bit by bit too (``count(key == t and
    position < p) < need``).  AND ``position < context_len``, so a lane at or
    under ``k`` positions chooses all it has.  Each count is one pass over
    the scores: a compare, a sum a lane.  The passes are written out, 46 of
    them for a table of 12,544 positions, so that each is a fusion of its own
    over operands the compiler keeps in VMEM (32 x 12,544 keys are 1.6e6 B):
    on the chip they come to 37 us a layer where the sort took 335 and a
    Pallas kernel holding 8 lanes a grid step 38-42 (PERF.md section 6, PR
    60), and a loop the compiler does not unroll was not tried."""
    lanes, length = scores.shape
    k = min(int(k), length)
    key = _order_key(scores)
    count = lambda hit: jnp.sum(hit.astype(jnp.int32), axis=1, keepdims=True)
    t = jnp.where(count(key >= 0) >= k, jnp.int32(0), jnp.int32(-2 ** 31))
    for bit in range(30, -1, -1):
        higher = t | jnp.int32(1 << bit)
        t = jnp.where(count(key >= higher) >= k, higher, t)
    tied = key == t
    need = k - count(key > t)
    pos = jax.lax.broadcasted_iota(jnp.int32, (lanes, length), 1)
    # ``p`` ends as the position of the need-th of the tied
    p = jnp.zeros((lanes, 1), jnp.int32)
    for bit in range((length - 1).bit_length() - 1, -1, -1):
        further = p | jnp.int32(1 << bit)
        p = jnp.where(count(tied & (pos < further)) < need, further, p)
    chosen = ((key > t) | (tied & (pos <= p))) \
        & (pos < context_lens.astype(jnp.int32)[:, None])
    return jnp.pad(chosen.astype(jnp.int32),
                   ((0, 0), (0, chunks * span - length))) \
        .reshape(lanes, chunks, span)


class Choice(tuple):
    """``choose``'s pair ``(positions, count)``, the list, which also
    remembers what it was chosen from: a reader that wants the chosen *set*
    and no list (the masked walk) asks for it ``by_chunk``, and the list it
    then never reads is dead code that the compiler drops with its sort.  A
    stand-in's plain pair cannot, and keeps the list."""

    def __new__(cls, positions, count, scores, context_lens, k):
        self = super().__new__(cls, (positions, count))
        self._chosen_from = (scores, context_lens, k)
        return self

    def by_chunk(self, chunks, span):
        """The set as ``_chunk_mask`` lays it out ([B, chunks, span] int32),
        from the scores by a threshold and no sort (``chosen_by_chunk``)."""
        return chosen_by_chunk(*self._chosen_from, chunks, span)


def choose(scores, context_lens, k):
    """The ``k`` positions of largest score a lane (exact; of equal scores
    the lower position first), ``scores`` [B, S] with ``-inf`` past the
    context -> (``positions`` [B, min(k, S)] int32, ``count`` [B] int32:
    the leading ``min(context_len, k)`` of a lane's row are chosen, what
    follows names positions past its context), a ``Choice``.  A lane at or
    under ``k`` positions chooses all it has."""
    k = min(int(k), scores.shape[1])
    _best, positions = jax.lax.top_k(scores, k)
    return Choice(positions.astype(jnp.int32),
                  jnp.minimum(context_lens.astype(jnp.int32), k),
                  scores, context_lens, k)


def chosen_mask(positions, count, length):
    """``choose``'s result as [B, length] bool: the positions chosen.  Of
    either form: the list (``positions`` [B, k], its leading ``count``), or
    the set by chunk that the masked walk is handed in the list's place
    (``positions`` [B, chunks, span], position ``c * span + j`` at ``[c,
    j]``)."""
    if positions.ndim == 3:
        flat = positions.reshape(positions.shape[0], -1)[:, :length] != 0
        return jnp.pad(flat, ((0, 0), (0, length - flat.shape[1])))
    lanes = jnp.arange(positions.shape[0], dtype=jnp.int32)[:, None]
    valid = jnp.arange(positions.shape[1], dtype=jnp.int32)[None, :] \
        < count[:, None]
    return jnp.zeros((positions.shape[0], length), bool) \
        .at[lanes, positions].set(valid)


# Positions of a lane's table a chosen position, up to which the selected
# read walks the table under a mask and past which it gathers the chosen
# rows.  The row form costs by the rows chosen whatever the lanes hold: XLA's
# gather and the kernel over the gathered rows, 25.6 ns a chosen row (10.08 ms
# over 6 layers of 65,536).  The walk costs by the positions the lanes hold,
# the whole table's at the worst: 2.81 ns a position with every lane at the
# table's end (the kernel 6.50 ms and the mask 0.26 over 6 layers of 32 x
# 12,544).  25.6 / 2.81 = 9.1: up to 9 the walk is never the worse form,
# whatever the lanes hold (PERF.md section 6, PR 57: the probe at GLM-5's
# shapes, served and ``--latent-read gathered``, contexts 12,300-12,499)
_WALK_POSITIONS_PER_CHOSEN = 9


def _chunk_mask(positions, count, chunks, span):
    """``choose``'s result as the latent kernel reads it: [B, chunks, span]
    int32, 1 at position ``c * span + j`` where a lane chose it (``chosen_mask``'s
    set, exactly).  No scatter: a position is a (row, column) of ``lo``
    columns, and the rows' one-hot is contracted against the columns' over
    the ``k`` choices on the MXU, the choices past ``count`` zeroed; a lane's
    valid choices are distinct, so an entry is 0 or 1 and bfloat16 holds it
    exactly."""
    lanes, k = positions.shape
    lo = CHUNK_TOKENS if span % CHUNK_TOKENS == 0 else span
    valid = jnp.arange(k, dtype=jnp.int32)[None, :] < count[:, None]
    row = (positions // lo)[:, :, None] == jnp.arange(
        chunks * span // lo, dtype=jnp.int32)[None, None, :]
    col = (positions % lo)[:, :, None] == jnp.arange(
        lo, dtype=jnp.int32)[None, None, :]
    hits = jnp.einsum("bkr,bkc->brc",
                      (row & valid[:, :, None]).astype(jnp.bfloat16),
                      col.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)
    return (hits > 0).astype(jnp.int32).reshape(lanes, chunks, span)


def _selected_checks(q_shape, pool_shape, pool_dtype, rank, k):
    """``latent_attention_checks`` for the kernel over ``k`` gathered rows a
    lane, which must be whole blocks (the row form)."""
    bs = pool_shape[1]
    whole = isinstance(k, int) and bs > 0 and k % bs == 0 and k > 0
    gathered = (q_shape[0] * max(k // max(bs, 1), 1), bs, pool_shape[2])
    return latent_attention_checks(q_shape, gathered, pool_dtype, rank) \
        + [("selection", whole)]


def _walk_checks(q_shape, pool_shape, pool_dtype, rank, k, maxb):
    """``latent_attention_checks`` for the kernel over a lane's own table of
    ``maxb`` slots under a mask of its ``k`` chosen positions (the masked
    walk): a table no longer than
    ``_WALK_POSITIONS_PER_CHOSEN`` positions a chosen one (``k`` None: the
    caller holds the set as a mask already, whatever its count), and room
    for the mask beside what the kernel holds."""
    base = latent_attention_checks(q_shape, pool_shape, pool_dtype, rank)
    shaped = all(ok for reason, ok in base if reason != "backend") \
        and isinstance(maxb, int) and maxb > 0 \
        and (k is None or (isinstance(k, int) and k > 0))
    if not shaped:
        return base + [("selection", False)]
    bs = pool_shape[1]
    span = latent_chunk_positions(q_shape, pool_shape, pool_dtype, rank, maxb)
    # a lane's chunks of the mask, 32-bit in whole sublane tiles: every
    # lane's, or (the grid walks the lanes) one lane's twice
    held = 2 if _latent_lane_grid(q_shape, pool_shape, pool_dtype, rank) \
        else q_shape[0]
    chunks = -(-maxb * bs // span)
    mask = held * 4 * span * (-(-chunks // 8) * 8)
    return base + [
        ("selection", k is None
         or maxb * bs <= _WALK_POSITIONS_PER_CHOSEN * k),
        ("mask_vmem", latent_vmem_bytes(q_shape, pool_shape, pool_dtype,
                                        rank) + mask <= _VMEM_BUDGET)]


def _selected_form(q_shape, pool_shape, pool_dtype, rank, k, maxb):
    """-> (``selected_latent_path``'s name, the checks ``adoption.decide``
    is given): the masked walk where its checks pass, else the row form's."""
    walk = _walk_checks(q_shape, pool_shape, pool_dtype, rank, k, maxb)
    if all(ok for _reason, ok in walk):
        return "pallas_masked", walk
    rows = _selected_checks(q_shape, pool_shape, pool_dtype, rank, k)
    return "pallas" if all(ok for _reason, ok in rows) else "gather", rows


def selected_latent_path(q_shape, pool_shape, pool_dtype, rank, k, maxb):
    """Which form ``selected_latent_attention`` takes for ``k`` chosen
    positions under a table of ``maxb`` slots: ``"pallas_masked"`` where the
    latent kernel walks a lane's live blocks under a mask of the chosen
    positions, ``"pallas"`` where it gathers the chosen rows and runs the
    kernel over them, ``"gather"`` where it gathers the whole table and
    masks.  From the shapes alone."""
    return _selected_form(q_shape, pool_shape, pool_dtype, rank, k, maxb)[0]


def _walk_layout(q_shape, pool_shape, pool_dtype, rank, maxb):
    """-> (chunks, span): how the masked walk's mask lies, ``span`` positions
    a chunk of the latent kernel over a table of ``maxb`` slots."""
    span = latent_chunk_positions(q_shape, pool_shape, pool_dtype, rank, maxb)
    return -(-maxb * pool_shape[1] // span), span


def chosen_for_read(choice, q_shape, pool_shape, pool_dtype, rank, maxb):
    """What ``selected_latent_attention`` is handed of ``choose``'s result
    for a read of these shapes -> (``positions``, ``count``).  Where the read
    is the masked walk and the choice can give its set by chunk (a
    ``Choice``; a stand-in's plain pair cannot), the mask ``[B, chunks,
    span]`` goes in the list's place, made from the scores with no sort;
    elsewhere the list as it is."""
    positions, count = choice
    if isinstance(choice, Choice) and selected_latent_path(
            q_shape, pool_shape, pool_dtype, rank, positions.shape[1],
            maxb) == "pallas_masked":
        return choice.by_chunk(*_walk_layout(q_shape, pool_shape, pool_dtype,
                                             rank, maxb)), count
    return positions, count


def selected_latent_attention(q, pool, block_tables, context_lens,
                              positions, count, scale, rank):
    """``latent_attention`` over the chosen positions alone (``choose``'s
    ``positions`` [B, k] and ``count`` [B]), one of three ways by the shapes
    (``selected_latent_path``).  The masked walk, where the table is short
    beside ``k``: the chosen positions laid out as a mask by chunk (scope
    ``mask``) and the kernel's body run over the lane's own table and
    context, a position counted where the context holds it and it was chosen
    (``kv_read``); ``positions`` of rank 3 are that mask already
    (``chosen_for_read``: [B, chunks, span], the walk's form by what it is
    handed) and nothing is laid out.  The row form, for a wider table: the
    chosen rows gathered by (block, offset) into ``k / block_size``
    contiguous blocks a lane (scope ``kv_gather``) and the kernel's body run
    over them in that order, a lane's ``count`` leading ones (``kv_read``).
    Elsewhere: the whole table gathered and what was not chosen masked."""
    bb = positions.shape[0]
    bs = pool.shape[1]
    maxb = block_tables.shape[1]
    laid_out = positions.ndim == 3
    k = None if laid_out else positions.shape[1]
    form, checks = ("pallas_masked", _walk_checks(
        q.shape, pool.shape, pool.dtype, rank, k, maxb)) if laid_out \
        else _selected_form(q.shape, pool.shape, pool.dtype, rank, k, maxb)
    use, _reason = adoption.decide("latent_attention", checks)
    if use and form == "pallas_masked":
        chosen = positions
        if not laid_out:
            with jax.named_scope("mask"):
                chosen = _chunk_mask(positions, count, *_walk_layout(
                    q.shape, pool.shape, pool.dtype, rank, maxb))
        with jax.named_scope("kv_read"):
            return _latent_pallas(q, pool, block_tables, context_lens, scale,
                                  rank, chosen=chosen)
    if use:
        with jax.named_scope("kv_gather"):
            blocks = jnp.take_along_axis(jnp.maximum(block_tables, 0),
                                         positions // bs, axis=1)
            # rows of the pool read as [num_blocks * block_size, W] (the
            # same bytes): one index a row gathers 6% faster than two
            rows = jnp.take(pool.reshape(-1, pool.shape[2]),
                            blocks * bs + positions % bs, axis=0,
                            mode="clip")                   # [B, k, W]
            rows = rows.reshape(bb * k // bs, bs, pool.shape[2])
            tables = jnp.arange(bb * k // bs, dtype=jnp.int32).reshape(bb, -1)
        with jax.named_scope("kv_read"):
            return _latent_pallas(q, rows, tables, count, scale, rank)
    with jax.named_scope("kv_gather"):
        rows = gather_blocks(pool, block_tables)
    return masked_latent(q, rows, context_lens, scale, rank,
                         chosen_mask(positions, count, rows.shape[1]))
