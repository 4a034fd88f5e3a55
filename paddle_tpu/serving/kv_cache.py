"""Paged KV-cache for autoregressive decode serving.

The decode engine owns a pool of fixed-size KV blocks (one K and one V
device array per layer, each ``[num_blocks, block_size, heads *
head_dim]``) and hands each admitted sequence a *block table* — the list
of physical blocks holding its history, grown one block per
``block_size`` generated tokens.  The physical layout is the point:
sequences of wildly different lengths all present the decode step with
the same static shapes (token ids, tables padded to
``max_seq // block_size`` slots, context lengths), so ONE
AOT-compiled step per lane bucket serves every mixture of lengths with
zero runtime XLA compiles, and a finished sequence returns its blocks to
the free list the same step it finishes.

``BlockAllocator`` is the host-side free list (LIFO for reuse locality;
all-or-nothing ``alloc`` so a half-admitted sequence never holds blocks).
It is *refcounted*: ``incref`` lets sequences share a block (prefix
caching), ``free`` decrements, and a ``seal``-ed block whose refcount
hits zero parks in an LRU *evictable* pool instead of the free list —
its content stays valid and revivable until ``alloc`` reclaims it under
pressure, so cache residency costs nothing when blocks are needed.
``PagedKVCache`` owns the device arrays as a donated carry: every decode
step consumes the current arrays and returns the updated ones
(``carry()``/``replace_carry()``), so the cache is updated in place on
device instead of being copied per token.  Two properties of the arrays
make "in place" hold on the TPU and not only in this sentence.  They are
per layer, so no write or read goes through a slice of a stacked array
(which the compiler materialises, a whole layer's pool per slice).  And
their minor dimension is ``heads * head_dim``, a multiple of the 128
lanes for every published width, where ``head_dim`` alone (64) pads to
128: the runtime then keeps the padded pool in another layout than the
step computes in, and converts the whole pool at the step's entry and
exit.  Heads are never split on the pool: the step's attention
(``pallas_kernels/paged_attention.py``) reads the folded rows where they
lie, a lane's live blocks at a time, and its jnp fallback reshapes only
what it has gathered.

Layers are of two kinds (``KVCacheConfig`` names them).  Attention layers
page K and V as above, ``heads`` being the KV heads the pool stores (fewer
than the query heads under grouped-query attention).  Recurrent layers
(state-space mixers) keep, a sequence, a state that is constant in its
length: the cache holds it in ``state_slots`` *slots* (one array per layer
and per ``(shape, dtype)`` the config lists, ``[state_slots,
*slot_layout(shape)]``: a slot is whole tiles of the chip's tiled HBM, so
a step moves it as one run of bytes),
``SlotAllocator`` hands a sequence one slot at admission for as long as it
holds blocks, and the step is told each lane's slot beside its block table
(slot 0, like block 0, is the idle lanes' scratch).  The slots ride in the
same donated carry after the K/V pools, and ``config.groups(carry)`` is the
one accessor that splits a carry by the description.  A slot cannot be
shared, trimmed or framed: prefix reuse, speculative roll-back and block
export stay with models whose every layer pages, and the engine declines
them for the others under counters.

Window layers (sliding-window attention) are a third kind.  They attend
the last ``window`` positions only, so their K and V live in pools of their
own, apart from the global layers' ``num_blocks``: ``window_slots`` *rings*
of ``window_ring = ceil(window / block_size) + 1`` blocks, the largest lane
bucket's lanes and a scratch, as the recurrent slots are sized.  A sequence
keeps a ``WindowRing`` there: position ``p`` lies in the block of ring slot
``(p // block_size) % window_ring``, taken from ``window_allocator`` when
the sequence reaches it and given back as soon as every position of it has
left the window, so a sequence of any length holds at most ``window_ring``
window blocks and the step is handed the ring as a second, short table.
What left the window is gone: a window layer's history cannot be matched,
published, exported or resumed from, and the engine declines those for such
a model as it does for recurrent state.

``PrefixCache`` is the content-addressed index over sealed blocks: a
per-model hash chain ``h_i = sha(h_{i-1}, block_token_ids)`` over *full*
prompt blocks keys each physical block, ``match`` revives the longest
cached prefix of a new prompt (capped at ``len(prompt) - 1`` tokens so
prefill always computes at least one tail token and never writes into a
shared block), and ``publish`` is first-publisher-wins.

Residency dtype (FLAGS_kv_cache_dtype, unless the model names its own:
``DecoderConfig.kv_dtype``): ``f32`` keeps bitwise parity with the
unpaged reference loop; ``bf16`` is a bf16 model's cache (K and V rounded
once at the write, half the bytes, and the unpaged loop keeps bf16 K/V
too, so parity stays bitwise); ``int8`` stores quantized blocks plus
per-(block, position, head) max-abs scales — the EQuARX
quantize-for-the-wire idiom (PAPERS.md arXiv 2506.17615) applied to
residency, ~4x the tokens per HBM byte.

Sizing is budget-gated (the MEM001/MEM003 satellite):
``plan_num_blocks`` fits the pool under ``FLAGS_hbm_budget_bytes`` after
the model's resident bytes and the recurrent layers' slots, and every live
cache registers its footprint (pools and slots) so
``core/world_analysis.check_memory`` counts engine-owned cache bytes in the
static per-replica peak estimate.
"""

import functools
import hashlib
import threading
import weakref
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from ..core import telemetry as _tm

__all__ = ["KVCacheConfig", "BlockAllocator", "SlotAllocator",
           "WindowRing", "PagedKVCache", "PrefixCache",
           "plan_num_blocks", "block_bytes", "latent_block_bytes",
           "index_block_bytes",
           "slot_bytes", "state_bytes", "slot_layout",
           "window_bytes",
           "engine_owned_kv_bytes",
           "engine_owned_resident_bytes", "register_resident_bytes",
           "quantize_kv", "dequantize_kv"]

# default pool size when neither FLAGS_kv_cache_blocks nor an HBM budget
# pins one (CPU-tier tests and demos)
_DEFAULT_BLOCKS = 64

# Machine-readable concurrency contracts (tools/threadlint.py enforces
# these; core/concurrency_analysis.py merges every module's registry).
# Index -> allocator: PrefixCache methods call into BlockAllocator while
# holding the index lock (match -> incref, publish -> seal), never the
# reverse — the allocator reaches the index only through on_evict, which
# fires AFTER the allocator lock is released.
LOCK_ORDER = (
    ("PrefixCache._lock", "BlockAllocator._lock"),
)

# Callbacks whose registration contract is "invoked with no owner lock
# held" — CC105 flags any invocation site that still holds one.
UNLOCKED_CALLBACKS = (
    "BlockAllocator.on_evict",
)


# residency dtype -> (payload dtype, payload bytes a value)
_PAYLOAD = {"f32": (jnp.float32, 4), "bf16": (jnp.bfloat16, 2),
            "int8": (jnp.int8, 1)}


class KVCacheConfig:
    """Static cache geometry, layers by kind.

    Attention layers: ``layers`` of them hold K and V, ``heads`` KV heads
    of ``head_dim`` (a pool row is ``heads * head_dim`` wide), paged in
    ``num_blocks`` blocks of ``block_size`` tokens in residency ``dtype``.

    Recurrent layers: ``state_layers`` of them hold, a sequence, one array
    of each ``(shape, dtype)`` of ``state_shapes`` (dtype ``f32`` |
    ``bf16``), constant in the sequence's length, in one of
    ``state_slots`` slots (slot 0 is the idle lanes' scratch, as block 0
    is).  A model of attention layers only has none.

    Window layers: ``window_layers`` of them hold K and V of the last
    ``window`` positions, same heads, block size and residency, in
    ``window_slots`` rings of ``window_ring`` blocks (ring 0's first block
    is the idle lanes' scratch).

    Latent layers: ``latent_layers`` of them hold ONE row a token,
    ``latent_width`` values wide (a compressed K/V and the key's shared
    part: what absorbed latent attention reads), in a pool each of the same
    ``num_blocks`` blocks on the same tables and allocator: a block id names
    a block in every layer's pool, whatever the layer's kind.  Their
    residency is ``f32`` | ``bf16``: a latent row has no heads for int8's
    scales to go by, and the kernel reads it as it lies.  A pool's rows are
    ``latent_row`` wide, the width rounded up to whole 128-lane tiles and
    the rest zeros: the only form in which a kernel can fetch a block from
    the pool where it lies (Mosaic fetches whole tiles of the chip's tiled
    HBM layout; asked for a 576-wide row XLA copies the whole pool into
    that layout, 640 wide, around every call: PERF.md section 6, PR 46).

    Index pools: ``index_layers`` of the latent layers (none, or every one:
    the layers of a model whose latent attention selects its positions)
    hold beside their latent pool a second one, the indexer's key of each
    token, ``index_width`` values held as they are (the published 128 are
    a whole 128-lane tile; a narrower pool is gathered, as a narrower
    latent row would be), ``[num_blocks, block_size, index_width]`` in the
    same residency.
    One block id names a block in both: whatever shares, frees, exports or
    adopts a block carries both rows of it."""

    __slots__ = ("layers", "heads", "head_dim", "block_size", "num_blocks",
                 "dtype", "state_layers", "state_shapes", "state_slots",
                 "window_layers", "window", "window_slots", "latent_layers",
                 "latent_width", "index_layers", "index_width")

    def __init__(self, layers, heads, head_dim, block_size, num_blocks,
                 dtype="f32", state_layers=0, state_shapes=(),
                 state_slots=0, window_layers=0, window=0, window_slots=0,
                 latent_layers=0, latent_width=0, index_layers=0,
                 index_width=0):
        if dtype not in _PAYLOAD:
            raise ValueError("kv_cache dtype must be f32|bf16|int8: %r"
                             % (dtype,))
        if latent_layers and (dtype == "int8" or latent_width < 1):
            raise ValueError(
                "a latent pool is f32|bf16 and latent_width >= 1 wide "
                "(int8 residency scales a head's values, and a latent row "
                "has no heads): %r, %r" % (dtype, latent_width))
        if index_layers and (index_layers != latent_layers
                             or index_width < 1):
            raise ValueError(
                "index pools lie one beside every latent pool, "
                "index_width >= 1 wide: %r of width %r beside %r latent "
                "layers" % (index_layers, index_width, latent_layers))
        if block_size <= 0 or num_blocks <= 1:
            raise ValueError("need block_size > 0 and num_blocks > 1 "
                             "(block 0 is the idle-lane scratch)")
        self.layers = int(layers)
        self.heads = int(heads)
        self.head_dim = int(head_dim)
        self.block_size = int(block_size)
        self.num_blocks = int(num_blocks)
        self.dtype = dtype
        self.state_layers = int(state_layers)
        self.state_shapes = tuple(
            (tuple(int(x) for x in shape), dt) for shape, dt in state_shapes)
        self.state_slots = int(state_slots)
        self.window_layers = int(window_layers)
        self.window = int(window)
        self.window_slots = int(window_slots)
        self.latent_layers = int(latent_layers)
        self.latent_width = int(latent_width)
        self.index_layers = int(index_layers)
        self.index_width = int(index_width)
        if self.window_layers and (self.window < 1 or self.window_slots <= 1):
            raise ValueError("window layers need window >= 1 and "
                             "window_slots > 1 (a ring a lane and the "
                             "scratch)")
        if any(dt not in ("f32", "bf16") for _shape, dt in self.state_shapes):
            raise ValueError("a recurrent state is f32|bf16: %r"
                             % (state_shapes,))
        if self.state_layers and (not self.state_shapes
                                  or self.state_slots <= 1):
            raise ValueError("recurrent layers need state_shapes and "
                             "state_slots > 1 (slot 0 is the idle-lane "
                             "scratch)")

    @property
    def kv_groups(self):
        """Groups of per-layer K/V arrays in a carry: K, V, and for int8
        residency their scales."""
        return 4 if self.dtype == "int8" else 2

    @property
    def window_ring(self):
        """Blocks of a sequence's ring in a window layer's pool: the window
        may straddle one block more than it fills."""
        if not self.window_layers:
            return 0
        return -(-self.window // self.block_size) + 1

    @property
    def window_blocks(self):
        """Blocks of a window layer's pool: a ring a slot."""
        return self.window_slots * self.window_ring

    def _cuts(self, carry):
        carry = list(carry)
        cut = self.kv_groups * self.layers
        first = cut + self.latent_layers + self.index_layers
        wcut = first + self.kv_groups * self.window_layers
        held = len(self.state_shapes) * self.state_layers
        if len(carry) != wcut + held:
            raise ValueError("a carry of %d arrays is not this cache's "
                             "(%d KV + %d latent + %d index + %d window + "
                             "%d state)"
                             % (len(carry), cut, self.latent_layers,
                                self.index_layers, wcut - first, held))
        return carry, cut, wcut

    def groups(self, carry):
        """A cache carry (or anything laid out like one) by what it holds
        -> ``(kv, state)``: ``kv`` the groups of ``layers`` per-layer
        pools (``[k, v]``, and ``[k, v, k_scales, v_scales]`` for int8
        residency), ``state`` one group of ``state_layers`` per-layer
        arrays for each entry of ``state_shapes``.  The latent layers'
        pools, their index pools and then the window layers' lie between
        the two (``latent_pools``, ``index_pools``, ``window_groups``)."""
        carry, cut, wcut = self._cuts(carry)
        kv = [carry[i:i + self.layers]
              for i in range(0, cut, self.layers or 1)]
        state = [carry[i:i + self.state_layers]
                 for i in range(wcut, len(carry), self.state_layers or 1)]
        return kv, state

    def window_groups(self, carry):
        """The window layers' pools of a carry, grouped as ``groups``'s
        ``kv`` is: ``window_layers`` arrays a group."""
        carry, cut, wcut = self._cuts(carry)
        return [carry[i:i + self.window_layers]
                for i in range(cut + self.latent_layers + self.index_layers,
                               wcut, self.window_layers or 1)]

    @property
    def latent_row(self):
        """Values a latent pool's row holds (``latent_row_of``)."""
        return latent_row_of(self.latent_width)

    def latent_pools(self, carry):
        """The latent layers' pools of a carry, one a layer."""
        carry, cut, _wcut = self._cuts(carry)
        return carry[cut:cut + self.latent_layers]

    @property
    def index_places(self):
        """Where the index pools lie in a carry: a ``range`` of its
        places (empty for a model whose latent attention does not
        select)."""
        at = self.kv_groups * self.layers + self.latent_layers
        return range(at, at + self.index_layers)

    def index_pools(self, carry):
        """The latent layers' index pools of a carry, one a layer."""
        carry, _cut, _wcut = self._cuts(carry)
        places = self.index_places
        return carry[places.start:places.stop]


def latent_row_of(width):
    """Values a latent pool's row holds for ``width`` values a token: the
    width rounded up to whole 128-lane tiles."""
    return -(-int(width) // 128) * 128


def _layer_block_bytes(config):
    """HBM bytes ONE block costs in one layer (K + V, + scales for
    int8)."""
    tok = config.heads * config.head_dim * _PAYLOAD[config.dtype][1]
    if config.dtype == "int8":
        tok += config.heads * 4                     # f32 scales
    return 2 * config.block_size * tok


def latent_block_bytes(config):
    """HBM bytes ONE block costs in one latent layer: a row a token, as the
    pool holds it (``latent_row`` wide)."""
    return config.block_size * config.latent_row \
        * _PAYLOAD[config.dtype][1]


def index_block_bytes(config):
    """HBM bytes ONE block costs in one index pool: an index key a
    token."""
    return config.block_size * config.index_width \
        * _PAYLOAD[config.dtype][1]


def block_bytes(config):
    """HBM bytes ONE block costs across all (global) attention layers, all
    latent layers and their index pools."""
    return config.layers * _layer_block_bytes(config) \
        + config.latent_layers * latent_block_bytes(config) \
        + config.index_layers * index_block_bytes(config)


def window_bytes(config):
    """HBM bytes of the window layers' pools: every ring, the scratch one
    included.  Fixed by the lane buckets, as the recurrent slots are."""
    return config.window_layers * config.window_blocks \
        * _layer_block_bytes(config)


def slot_layout(shape):
    """The shape one slot of ``shape`` values takes in its pool.  A matrix
    (a recurrent state, ``[N, I]``) lies as it is: its two minor dimensions
    are whole tiles of the chip's tiled HBM.  A flat array (a convolution's
    window, ``[(K - 1) * W]``) lies as rows of 128, ``[rows, 128]``, the
    last row's rest zeros nothing reads: held flat, ``[slots, values]``,
    the chip tiles the SLOTS with the values (16 bfloat16 rows a tile), a
    slot is one sublane of every tile and a write of it as many masked
    stores, 21 GB/s where a slot of whole tiles moves at the HBM's rate
    (PERF.md section 6, PR 65).  ``slot_bytes`` counts the values held,
    not the rest of the last row."""
    if len(shape) != 1:
        return tuple(shape)
    return (-(-shape[0] // 128), 128)


def slot_bytes(config):
    """HBM bytes ONE sequence's recurrent state costs across all recurrent
    layers: the values held (``slot_layout`` may round a flat array's last
    row up)."""
    per = 0
    for shape, dt in config.state_shapes:
        n = _PAYLOAD[dt][1]
        for x in shape:
            n *= x
        per += n
    return config.state_layers * per


def state_bytes(config):
    """HBM bytes of the recurrent layers' state pools: every slot, the
    scratch one included.  Fixed by the lane buckets, not by the pool's
    blocks."""
    return config.state_slots * slot_bytes(config)


def plan_num_blocks(config, model_resident_bytes=0, requested=None,
                    budget=None):
    """Budget-gated pool sizing -> (num_blocks, capped).

    ``requested`` (default FLAGS_kv_cache_blocks; <=0 = auto) asks for a
    pool size; ``budget`` (default FLAGS_hbm_budget_bytes; 0 = no gate)
    caps it at what fits beside the model's resident bytes.  A budget too
    small for even a 2-block pool raises — the engine must not start with
    a cache it cannot hold (FLAGS_hbm_budget_bytes gates cache sizing,
    not just the model)."""
    from .. import flags as _flags

    if requested is None:
        requested = int(_flags.flag("kv_cache_blocks") or 0)
    if budget is None:
        budget = int(_flags.flag("hbm_budget_bytes") or 0)
    per = block_bytes(config)
    if budget > 0:
        # the recurrent layers' slots and the window layers' rings are
        # held whatever the blocks: they come off the budget first
        model_resident_bytes = int(model_resident_bytes) \
            + state_bytes(config) + window_bytes(config)
        fit = int((budget - int(model_resident_bytes)) // per)
        if fit < 2:
            raise ValueError(
                "FLAGS_hbm_budget_bytes=%d leaves room for %d KV block(s) "
                "of %d bytes beside %d model-resident and state bytes; the decode "
                "cache needs >= 2 (shrink the model, raise the budget, or "
                "set FLAGS_kv_cache_dtype=int8)"
                % (budget, max(fit, 0), per, model_resident_bytes))
        if requested > 0:
            return min(requested, fit), fit < requested
        return fit, False
    return (requested if requested > 0 else _DEFAULT_BLOCKS), False


class _Quiet:
    """Telemetry that records nothing (``BlockAllocator(gauges=False)``)."""

    inc = set_gauge = staticmethod(lambda *args, **labels: None)


class BlockAllocator:
    """Refcounted host-side free list over physical block ids.

    ``reserve`` low ids never enter circulation (the cache reserves block
    0 as the idle-lane write scratch).  ``alloc`` is all-or-nothing: a
    request the pool cannot fully satisfy takes nothing (the engine
    sheds or preempts instead of deadlocking on a half-allocation).

    Sharing: ``alloc`` hands out blocks at refcount 1; ``incref`` takes
    another share (prefix-cache hits); ``free`` decrements and only a
    zero-ref block leaves circulation.  A ``seal``-ed block (content
    complete and content-addressed) parks in the LRU *evictable* pool at
    zero refs instead of the free list — still resident and revivable via
    ``incref``, but ``alloc`` reclaims evictable LRU-first once the free
    list runs dry (firing ``on_evict(block, tag)`` so the index forgets
    it).  ``reclaimable`` = free + evictable is what admission/shed
    decisions must budget against: a warm cache never causes a spurious
    shed."""

    def __init__(self, num_blocks, reserve=0, gauges=True):
        if num_blocks <= reserve:
            raise ValueError("num_blocks %d <= reserve %d"
                             % (num_blocks, reserve))
        self.num_blocks = int(num_blocks)
        self.reserve = int(reserve)
        # the process-wide kv_blocks_* gauges and kv_block_* counters are
        # the global pool's: a window layers' allocator keeps out of them
        # (its owner reports kv_pool_blocks{kind="window"})
        self._tm = _tm if gauges else _Quiet
        # LIFO: the most recently freed block is the next handed out, so a
        # churning batch keeps touching the same hot cache lines
        self._free = list(range(num_blocks - 1, reserve - 1, -1))
        self._owned = set()             # ids with refcount >= 1
        self._ref = {}                  # id -> refcount (keys == _owned)
        self._sealed = {}               # id -> content tag (in-use, sealed)
        self._evictable = OrderedDict()  # id -> tag; zero-ref, LRU order
        self.on_evict = None            # fn(block, tag) after a reclaim
        self._lock = threading.Lock()
        self.high_water = 0

    @property
    def capacity(self):
        return self.num_blocks - self.reserve

    @property
    def num_free(self):
        with self._lock:
            return len(self._free)

    @property
    def num_evictable(self):
        with self._lock:
            return len(self._evictable)

    @property
    def reclaimable(self):
        """Blocks an ``alloc`` could obtain right now: free list plus the
        zero-ref evictable pool (cached content it may reclaim)."""
        with self._lock:
            return len(self._free) + len(self._evictable)

    @property
    def in_use(self):
        with self._lock:
            return len(self._owned)

    def refcount(self, block):
        with self._lock:
            return self._ref.get(block, 0)

    def alloc(self, n):
        """n blocks or None (OOM — nothing is taken).  Prefers the free
        list; reclaims evictable cached blocks LRU-first only when the
        free list runs dry (cache residency is free until pressure)."""
        if n <= 0:
            return []
        evicted = []
        with self._lock:
            if n > len(self._free) + len(self._evictable):
                self._tm.inc("kv_block_oom_total")
                return None
            got = []
            while len(got) < n and self._free:
                got.append(self._free.pop())
            while len(got) < n:
                b, tag = self._evictable.popitem(last=False)   # LRU victim
                evicted.append((b, tag))
                got.append(b)
            for b in got:
                self._owned.add(b)
                self._ref[b] = 1
            self._note_high_water_locked()
            self._tm.inc("kv_block_alloc_total", n)
            self._tm.set_gauge("kv_blocks_in_use", len(self._owned))
            self._tm.set_gauge("kv_blocks_evictable", len(self._evictable))
            cb = self.on_evict
        # the index callback runs outside the allocator lock (it takes the
        # PrefixCache lock; the module-level LOCK_ORDER registry declares
        # the index -> allocator order and UNLOCKED_CALLBACKS declares
        # this fired-unlocked contract — threadlint CC101/CC105 enforce it)
        for b, tag in evicted:
            if cb is not None:
                cb(b, tag)
        return got

    def incref(self, block):
        """Take another share of ``block``.  True if it was in use
        (refcount bumped) or parked evictable (revived at refcount 1);
        False if it has already been reclaimed — the caller's index entry
        is stale."""
        with self._lock:
            if block in self._owned:
                self._ref[block] += 1
                return True
            tag = self._evictable.pop(block, None)
            if tag is None:
                return False
            self._owned.add(block)
            self._ref[block] = 1
            self._sealed[block] = tag        # stays sealed: re-parks at 0
            self._note_high_water_locked()
            self._tm.set_gauge("kv_blocks_in_use", len(self._owned))
            return True

    def seal(self, block, tag):
        """Mark an in-use block's content complete and content-addressed
        by ``tag``: at refcount zero it parks in the evictable pool
        (revivable) instead of returning to the free list."""
        with self._lock:
            if block not in self._owned:
                raise ValueError("seal of unallocated block %r" % (block,))
            self._sealed[block] = tag

    def free(self, blocks):
        """Drop one reference per block; a block released at refcount
        zero returns to the free list (or parks evictable when sealed).
        Double-free or a foreign id raises (an engine bug must be loud,
        not silent corruption)."""
        blocks = list(blocks)
        with self._lock:
            for b in blocks:
                if b not in self._owned:
                    raise ValueError("free of unallocated block %r" % (b,))
            released = 0
            for b in blocks:
                self._ref[b] -= 1
                if self._ref[b] > 0:
                    continue
                del self._ref[b]
                self._owned.discard(b)
                released += 1
                tag = self._sealed.pop(b, None)
                if tag is not None:
                    self._evictable[b] = tag     # newest = last (LRU front)
                else:
                    self._free.append(b)
            self._tm.inc("kv_block_free_total", released)
            self._tm.set_gauge("kv_blocks_in_use", len(self._owned))
            self._tm.set_gauge("kv_blocks_evictable", len(self._evictable))

    def discard_evictable(self, block):
        """Truly free a zero-ref evictable block (back to the free list,
        content dropped).  The disaggregated abort-reconciliation path:
        blocks a decode replica adopted for a request that died on the
        prefill half are parked evictable, and the cancel relay discards
        them instead of waiting for allocation pressure.  Returns False
        when the block is not currently evictable (already reclaimed, or
        revived by a matching sequence — in-use blocks are freed by their
        owner at finish)."""
        with self._lock:
            if block not in self._evictable:
                return False
            del self._evictable[block]
            self._free.append(block)
            self._tm.inc("kv_block_discard_total")
            self._tm.set_gauge("kv_blocks_evictable", len(self._evictable))
            return True

    def _note_high_water_locked(self):
        # evictable blocks still occupy physical pool slots
        occupied = len(self._owned) + len(self._evictable)
        self.high_water = max(self.high_water, occupied)

    def stats(self):
        with self._lock:
            return {"capacity": self.capacity, "free": len(self._free),
                    "in_use": len(self._owned),
                    "evictable": len(self._evictable),
                    "reclaimable": len(self._free) + len(self._evictable),
                    "high_water": self.high_water}


class SlotAllocator:
    """Host-side free list over the recurrent layers' state slots.  A
    sequence holds one slot exactly while it holds a lane's worth of
    blocks; slot 0 never enters circulation (the idle lanes' scratch).
    There are as many slots as the largest lane bucket has lanes, so a
    ``take`` that finds none, like a ``give`` of a slot not held, is an
    engine bug and raises."""

    def __init__(self, num_slots):
        self.num_slots = int(num_slots)
        self._free = list(range(self.num_slots - 1, 0, -1))
        self._held = set()
        self._lock = threading.Lock()

    @property
    def capacity(self):
        return self.num_slots - 1

    @property
    def in_use(self):
        with self._lock:
            return len(self._held)

    def take(self):
        with self._lock:
            if not self._free:
                raise RuntimeError(
                    "no recurrent-state slot free (%d held): more "
                    "sequences hold lanes than the largest bucket has"
                    % len(self._held))
            slot = self._free.pop()
            self._held.add(slot)
            return slot

    def give(self, slot):
        with self._lock:
            if slot not in self._held:
                raise ValueError("give of a state slot not held: %r"
                                 % (slot,))
            self._held.discard(slot)
            self._free.append(slot)


class WindowRing:
    """One sequence's blocks in the window layers' pools: ``table`` the
    ring handed to the step (physical block of each ring slot, -1 where the
    sequence holds none) and ``[lo, hi)`` the logical blocks held, block
    ``i`` (positions ``[i * block_size, (i + 1) * block_size)``) in slot ``i
    % len(table)``.  ``PagedKVCache.advance_ring`` / ``release_ring`` move
    it."""

    __slots__ = ("table", "lo", "hi")

    def __init__(self, slots):
        self.table = np.full(int(slots), -1, np.int32)
        self.lo = self.hi = 0

    @property
    def held(self):
        return self.hi - self.lo


class PrefixCache:
    """Content-addressed index of sealed full-prompt KV blocks.

    Keyed by a per-model hash chain ``h_i = sha(h_{i-1},
    block_token_ids)`` over *full* prompt blocks, so a block's key commits
    to its entire prefix — equal keys mean bitwise-equal token history.
    ``match`` revives the longest indexed prefix of a prompt (taking one
    reference per shared block on the caller's behalf) capped at
    ``len(prompt) - 1`` tokens: prefill always computes at least one tail
    token and every KV *write* lands in a private tail block — shared
    blocks are read-only by construction.  ``publish`` seals a
    freshly-filled block into the index, first-publisher-wins; the
    allocator's ``on_evict`` callback un-indexes reclaimed blocks."""

    def __init__(self, allocator, block_size, namespace=""):
        self.allocator = allocator
        self.block_size = int(block_size)
        self.namespace = str(namespace)
        self._seed = hashlib.sha256(
            ("kvprefix:%s" % namespace).encode()).digest()
        self._index = {}                 # hex digest -> physical block id
        self._lock = threading.Lock()
        # per-model cumulative token counts behind the advertised
        # prefix_cache_hit_rate{model=} gauge (1s __metrics__ republish)
        self.lookup_tokens = 0
        self.hit_tokens = 0
        allocator.on_evict = self._on_evict

    def chain(self, token_ids):
        """Hash chain over the full blocks of ``token_ids`` -> list of hex
        digests, one per full block."""
        bs = self.block_size
        out = []
        h = self._seed
        for j in range(len(token_ids) // bs):
            d = hashlib.sha256(h)
            d.update(b"".join(int(t).to_bytes(8, "little", signed=True)
                              for t in token_ids[j * bs:(j + 1) * bs]))
            h = d.digest()
            out.append(h.hex())
        return out

    def extend_chain(self, prev_hex, block_tokens):
        """One hash-chain step past an existing digest: ``prev_hex`` is
        the previous block's hex digest (None for the chain seed) and
        ``block_tokens`` the next block's token ids -> next hex digest.
        Lets a decoding sequence extend its prompt chain over generated
        tokens incrementally (live session migration) without rehashing
        the whole history per block boundary."""
        h = self._seed if prev_hex is None else bytes.fromhex(prev_hex)
        d = hashlib.sha256(h)
        d.update(b"".join(int(t).to_bytes(8, "little", signed=True)
                          for t in block_tokens))
        return d.hexdigest()

    def match_digests(self, digests):
        """Longest indexed prefix of a precomputed digest chain ->
        ``blocks`` with one reference taken per block (the resume-path
        twin of ``match``: the caller already knows the full-history
        chain — prompt ++ emitted tokens — and, unlike prefill, needs no
        one-token cap because the next fed token is already decided)."""
        blocks = []
        with self._lock:
            for d in digests:
                b = self._index.get(d)
                if b is None:
                    break
                if not self.allocator.incref(b):
                    self._index.pop(d, None)
                    break
                blocks.append(b)
        return blocks

    def match(self, prompt_ids):
        """Longest cached prefix -> ``(blocks, cached_tokens, hashes)``.

        ``blocks`` arrive with one reference taken per block (the caller
        frees them like any owned block); ``hashes`` is the full-prompt
        chain, reused by the caller when publishing the tail."""
        hashes = self.chain(prompt_ids)
        max_blocks = max(0, (len(prompt_ids) - 1) // self.block_size)
        blocks = []
        with self._lock:
            for j in range(min(len(hashes), max_blocks)):
                b = self._index.get(hashes[j])
                if b is None:
                    break
                if not self.allocator.incref(b):
                    # reclaimed under us without the callback having run
                    # yet — forget the stale entry and stop matching
                    self._index.pop(hashes[j], None)
                    break
                blocks.append(b)
        cached = len(blocks) * self.block_size
        with self._lock:
            self.lookup_tokens += len(prompt_ids)
            self.hit_tokens += cached
        _tm.inc("prefix_cache_lookup_tokens_total", len(prompt_ids))
        if cached:
            _tm.inc("prefix_cache_hit_tokens_total", cached)
        # namespace-labeled twins of the counters above: the 1s republish
        # derives a WINDOWED per-namespace hit rate from their series
        # deltas (prefix_cache_ns_hit_rate{namespace=}) so prefix-aware
        # routers can bias on recent affinity, not lifetime averages
        _tm.inc("prefix_cache_ns_lookup_tokens_total", len(prompt_ids),
                namespace=self.namespace)
        if cached:
            _tm.inc("prefix_cache_ns_hit_tokens_total", cached,
                    namespace=self.namespace)
        return blocks, cached, hashes

    def hit_rate(self):
        """Cumulative per-model hit fraction (0.0 before any lookup)."""
        with self._lock:
            if self.lookup_tokens <= 0:
                return 0.0
            return self.hit_tokens / float(self.lookup_tokens)

    def lookup(self, digest):
        """Physical block currently indexed under ``digest``, or None.
        Takes no reference — a routing/dedupe peek, not an acquisition."""
        with self._lock:
            return self._index.get(digest)

    def publish(self, block, digest):
        """Index a freshly-filled full-prompt ``block`` under ``digest``.
        First-publisher-wins: a duplicate digest leaves the block private
        and returns False."""
        with self._lock:
            if digest in self._index:
                return False
            self.allocator.seal(block, digest)
            self._index[digest] = block
            _tm.inc("prefix_cache_blocks_published_total")
            return True

    def forget(self, digest):
        """Un-index ``digest`` and truly free its block when it sits
        zero-ref in the evictable pool (the adopted-block abort path).
        A block revived in-use by a live sequence only loses its index
        entry — its owner frees it at finish.  Returns True when the
        entry existed."""
        with self._lock:
            b = self._index.pop(digest, None)
        if b is None:
            return False
        # outside our lock mirrors the on_evict ordering (index ->
        # allocator); discard_evictable is a no-op for in-use blocks
        self.allocator.discard_evictable(b)
        return True

    def _on_evict(self, block, tag):
        with self._lock:
            if self._index.get(tag) == block:
                del self._index[tag]
        _tm.inc("prefix_cache_evictions_total")

    def __len__(self):
        with self._lock:
            return len(self._index)


# live caches, summed into the MEM001 static peak estimate
_LIVE = weakref.WeakSet()

# engine-owned resident weights (target + draft decoder params), keyed by
# the owning object so the registration dies with its model entry
_LIVE_RESIDENT = weakref.WeakKeyDictionary()


def engine_owned_kv_bytes():
    """Total HBM bytes of every live PagedKVCache in this process, K/V
    pools and recurrent-state slots alike — world_analysis.check_memory
    folds this into MEM001/MEM003."""
    return sum(c.nbytes for c in list(_LIVE))


def register_resident_bytes(owner, nbytes):
    """Register `nbytes` of engine-owned resident weights (e.g. a decode
    model's target + draft params) against `owner` — the registration is
    weak, so it disappears with the owning model entry.  Folded into
    MEM001 beside the KV pool bytes."""
    _LIVE_RESIDENT[owner] = int(nbytes)


def engine_owned_resident_bytes():
    """Total engine-owned resident weight bytes (decoder params, incl.
    the speculative draft's) across live registrations."""
    return sum(_LIVE_RESIDENT.values())


def quantize_kv(x):
    """f32 [..., H, D] -> (int8 payload, f32 per-[..., H] max-abs scale).
    Symmetric round-to-nearest into [-127, 127]."""
    scale = jnp.max(jnp.abs(x), axis=-1) / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(x / safe[..., None]), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_kv(q, scale):
    return q.astype(jnp.float32) * scale[..., None]


@jax.jit
def _get_block(groups, block):
    """One block of every pool of ``groups`` (tuples of per-layer pools),
    stacked over the layers per group."""
    return [jnp.stack([jax.lax.dynamic_index_in_dim(c, block, 0, False)
                       for c in group]) for group in groups]


@functools.partial(jax.jit, donate_argnums=(0,))
def _set_block(carry, block, rows):
    """``pool[block] = rows`` for every pool of a carry and its rows, each
    pool in its own (donated) buffer: one block is written, none copied."""
    return tuple(jax.lax.dynamic_update_slice(
        c, r[None], (block,) + (0,) * r.ndim) for c, r in zip(carry, rows))


class PagedKVCache:
    """Engine-owned device arrays of what a model's layers keep between
    tokens, carried (donated) through the decode step: paged K/V pools for
    the attention layers and, for a model with recurrent layers, a slot a
    sequence for their state.  Block 0 and slot 0 are reserved: idle lanes
    in a partially-full bucket point their table and their slot at them,
    so their (masked, discarded) writes never touch a sequence's history.

    The carry is a flat tuple of per-layer arrays, one *group* after
    another, layer 0 (of its kind) first within a group: K then V (``2 *
    layers`` arrays of ``[num_blocks, block_size, heads * head_dim]``),
    for int8 residency the K then V scales after them (``4 * layers`` in
    all; a scale array is ``[num_blocks, block_size, heads]``), and then
    one group of ``state_layers`` arrays ``[state_slots,
    *slot_layout(shape)]`` for each entry of the config's ``state_shapes``.
    ``config.groups`` splits a carry back by that description.

    ``allocator`` hands out blocks and ``slots`` (None without recurrent
    layers) state slots.  A slot is constant in the sequence's length and
    cannot be shared, trimmed or snapshotted: prefix reuse, speculative
    roll-back and block export are for models whose every layer pages
    (attention layers' K and V, latent layers' rows, or both).

    The latent layers' pools (``[num_blocks, block_size, latent_row]``,
    one a layer, on ``allocator``'s blocks) follow the global K/V groups in
    the carry, their index pools (``[num_blocks, block_size,
    index_width]``, on the same blocks) follow them, and the window layers'
    pools follow those, in the K/V
    groups' order, ``[window_blocks, block_size, heads * head_dim]``
    each; ``window_allocator`` (None without window layers) hands out their
    blocks, one id for every window layer alike, through a sequence's
    ``WindowRing``."""

    def __init__(self, config):
        self.config = config
        self.allocator = BlockAllocator(config.num_blocks, reserve=1)
        self.slots = SlotAllocator(config.state_slots) \
            if config.state_layers else None
        self.window_allocator = BlockAllocator(
            config.window_blocks, reserve=1, gauges=False) \
            if config.window_layers else None

        def pools(num_blocks, layers):
            rows = (num_blocks, config.block_size)
            payload = rows + (config.heads * config.head_dim,)
            groups = [(payload, _PAYLOAD[config.dtype][0])] * 2
            if config.dtype == "int8":
                groups += [(rows + (config.heads,), jnp.float32)] * 2
            return tuple(jnp.zeros(shape, dtype) for shape, dtype in groups
                         for _ in range(layers))

        self._carry = pools(config.num_blocks, config.layers) \
            + tuple(jnp.zeros((config.num_blocks, config.block_size,
                               config.latent_row),
                              _PAYLOAD[config.dtype][0])
                    for _ in range(config.latent_layers)) \
            + tuple(jnp.zeros((config.num_blocks, config.block_size,
                               config.index_width),
                              _PAYLOAD[config.dtype][0])
                    for _ in range(config.index_layers)) \
            + pools(config.window_blocks, config.window_layers)
        self._carry += tuple(
            jnp.zeros((config.state_slots,) + slot_layout(shape),
                      _PAYLOAD[dt][0])
            for shape, dt in config.state_shapes
            for _ in range(config.state_layers))
        _LIVE.add(self)
        _tm.set_gauge("kv_cache_bytes", self.kv_nbytes)

    @property
    def kv_nbytes(self):
        """The K/V pools' bytes (the ``kv_cache_bytes`` gauge)."""
        return block_bytes(self.config) * self.config.num_blocks

    @property
    def nbytes(self):
        """Everything this cache holds on the device: the K/V pools, the
        window layers' (``window_bytes``) and the recurrent layers' slots
        (``state_bytes``)."""
        return self.kv_nbytes + window_bytes(self.config) \
            + state_bytes(self.config)

    def carry(self):
        """The current device arrays, in decode-step argument order."""
        return self._carry

    def replace_carry(self, new_carry):
        """Install the step's returned (donated) arrays."""
        if len(new_carry) != len(self._carry):
            raise ValueError("carry arity changed")
        self._carry = tuple(new_carry)

    def blocks_for_tokens(self, n_tokens):
        """How many blocks a sequence of n_tokens needs."""
        bs = self.config.block_size
        return max(1, -(-int(n_tokens) // bs))

    # -- sealed-block export/import (the disaggregated transfer unit) --------

    def _wire_groups(self):
        """What a block's export and import frame, a group of per-layer
        pools after another as they lie at the head of the carry: K then V
        (then their scales for int8) of the attention layers, then the
        latent layers' pools, a row a token, then their index pools, a key
        a token (a model with recurrent state or
        rings is refused both by the engine, before it gets here) ->
        [(the group's pools, one block of it on the wire)]: every layer's
        rows with the heads split, ``[layers, block_size, heads,
        head_dim]`` (scales ``[layers, block_size, heads]``; latent rows
        as the pool holds them, ``[latent_layers, block_size,
        latent_row]``; index keys ``[index_layers, block_size,
        index_width]``)."""
        c = self.config
        groups, _state = c.groups(self._carry)
        out = [(tuple(g), (c.layers, c.block_size, c.heads)
                + ((c.head_dim,) if i < 2 else ()))
               for i, g in enumerate(groups)] if c.layers else []
        if c.latent_layers:
            out.append((tuple(c.latent_pools(self._carry)),
                        (c.latent_layers, c.block_size, c.latent_row)))
        if c.index_layers:
            out.append((tuple(c.index_pools(self._carry)),
                        (c.index_layers, c.block_size, c.index_width)))
        return out

    def export_block(self, block):
        """Host copies of one physical block, one array per carry group:
        ``[k, v]`` for f32 and bf16 residency, ``[k, v, k_scales, v_scales]`` for
        int8, each stacked over the layers in its wire shape, then the
        latent layers' rows and then their index keys.  The wire
        payload IS the residency payload — prefill's compiled step is
        deterministic, so an adopted block is bitwise-identical to the
        one the decode replica would have computed itself."""
        import numpy as np

        groups = self._wire_groups()
        return [np.asarray(a).reshape(shape) for a, (_g, shape) in zip(
            _get_block(tuple(g for g, _shape in groups), block), groups)]

    def import_block(self, block, arrays):
        """Install transferred payloads into physical ``block``: one
        block-sized update of each (donated) carry array.  The caller
        must hold the engine step lock (the carry is swapped) and own the
        block at refcount 1.  Shape/dtype mismatch raises before anything
        is written — adopting a frame cut for different cache geometry
        would corrupt every sequence that later matches the digest."""
        import numpy as np

        groups = self._wire_groups()
        if len(arrays) != len(groups):
            raise ValueError(
                "kv import arity mismatch: %d arrays for a %s-dtype "
                "carry of %d" % (len(arrays), self.config.dtype,
                                 len(groups)))
        arrays = [np.asarray(a) for a in arrays]
        for (group, want_shape), a in zip(groups, arrays):
            want = group[0].dtype
            if tuple(a.shape) != want_shape or a.dtype != want:
                raise ValueError(
                    "kv import geometry mismatch: got %s%s, carry wants "
                    "%s%s (block_size/heads/head_dim/dtype must agree "
                    "across the disaggregated pair)"
                    % (a.dtype, tuple(a.shape), want, want_shape))
        framed = sum(len(group) for group, _shape in groups)
        self._carry = _set_block(
            self._carry[:framed], block,
            [a[l].reshape(c.shape[1:]) for (group, _s), a in zip(groups, arrays)
             for l, c in enumerate(group)]) + self._carry[framed:]

    # -- the window layers' rings --------------------------------------------

    def new_ring(self):
        """An empty ring for a sequence about to start (or replay from)
        position 0."""
        return WindowRing(self.config.window_ring)

    def advance_ring(self, ring, context_len):
        """Move a sequence's ring on for a step that attends from a context
        of ``context_len`` tokens (the position it writes included; a
        sequence's steps come one position after another from 0): give back
        every block whose positions have all left the window, take the
        block the write reaches.  -> blocks given back.  The pools hold a
        ring for every lane, so running out is a bug of the caller's (more
        sequences than lanes) and raises."""
        c = self.config
        lo = max(context_len - c.window, 0) // c.block_size
        hi = self.blocks_for_tokens(context_len)
        released = self._drop(ring, range(ring.lo, lo))
        ring.lo = max(ring.lo, lo)
        if hi > ring.hi:
            got = self.window_allocator.alloc(hi - ring.hi)
            if got is None:
                raise RuntimeError(
                    "no window block free (%d held): more sequences hold "
                    "rings than the largest bucket has lanes"
                    % self.window_allocator.in_use)
            for i, b in zip(range(ring.hi, hi), got):
                ring.table[i % len(ring.table)] = b
            ring.hi = hi
        return released

    def release_ring(self, ring):
        """Give back everything a ring holds (the sequence ended, or was
        preempted: its replay starts an empty ring at position 0)."""
        freed = self._drop(ring, range(ring.lo, ring.hi))
        ring.lo = ring.hi = 0
        return freed

    def _drop(self, ring, logical):
        slots = [i % len(ring.table) for i in logical]
        if slots:
            self.window_allocator.free([int(ring.table[j]) for j in slots])
            ring.table[slots] = -1
        return len(slots)

    # -- multi-token growth / rollback (the speculative-decode contract) -----

    def ensure_table(self, table, blocks, upto_tokens):
        """Grow a sequence's block table to cover positions
        ``[0, upto_tokens)`` with ONE all-or-nothing allocation: either
        every missing slot is filled (True) or nothing is taken (False —
        the engine preempts or sheds).  This is the multi-token append
        API: a k-token speculative write (and a k-token prefill chunk)
        reserves all the blocks it may touch in one call instead of one
        alloc per token."""
        need = self.blocks_for_tokens(upto_tokens)
        have = len(blocks)
        if need <= have:
            return True
        got = self.allocator.alloc(need - have)
        if got is None:
            return False
        for i, b in enumerate(got):
            table[have + i] = b
        blocks.extend(got)
        return True

    def trim_table(self, table, blocks, upto_tokens):
        """Rollback: free every block beyond the one holding position
        ``upto_tokens - 1`` and clear its table slot.  With paged tables
        a rejected speculation costs no copies — the over-allocated
        blocks return to the free list and ``context_lens`` truncation
        masks the stale writes.  Returns the number of blocks freed."""
        keep = self.blocks_for_tokens(upto_tokens) if upto_tokens > 0 else 0
        if len(blocks) <= keep:
            return 0
        extra = blocks[keep:]
        del blocks[keep:]
        table[keep:keep + len(extra)] = -1
        self.allocator.free(extra)
        return len(extra)
