"""Block-pooled paged KV cache: fixed-stride blocks + free-list allocator.

The paper's core claim is that fixed-size, branchless layouts turn decoding
into pure memory reads.  The serving KV cache applies that to generation
state: instead of one dense ``[B, H, cache_len, hd]`` tensor per batch
shape (which forces the scheduler to only merge shape-identical requests),
K/V live in a single device-resident pool of fixed-size blocks

    pool: [num_layers, num_blocks, num_kv_heads, block_size, head_dim]

and every request owns an ordered *block table* — a row of physical block
ids.  Addressing is pure arithmetic, exactly like a Bebop page record:

    token at logical position p of request r lives in
        block  = table[r][p // block_size]
        slot   = p %  block_size
        byte   = pool_base + block * BLOCK_STRIDE + slot * ROW_STRIDE

No pointer chasing, no per-request reshapes, no data-dependent control
flow on the read path — the paged-attention kernel receives the table as a
scalar-prefetch operand and turns it into fixed-stride DMA descriptors.

Like a Bebop page, a block's stride is forced to a 64-byte multiple
(:func:`aligned_block_size`), so every block starts on a cache-line/DMA
boundary regardless of head_dim/dtype.

Block 0 is reserved as the *null block*: padding entries in block tables
point at it, and masked/inactive batch rows write their garbage there.  It
is never handed to a request, so stale writes can never corrupt live data.

The :class:`BlockAllocator` is a refcounted free-list (LIFO for locality):
``alloc`` hands out fresh blocks at refcount 1, ``share`` lets another
owner take a reference to a resident block, and a block returns to the
free list exactly when its refcount reaches 0 — never earlier (a shared
block must survive its first owner), never later (capacity conservation).
Handing out a block that still has references is a hard invariant
(checked on every alloc), and releasing an owner drops *all* of its
references — the property the deadline-shedding path relies on (a shed
request must never leak pool capacity).

Refcounts are what make **prefix caching** nearly free on this layout:
because blocks are fixed-size, a prompt's content hash is a hash of whole
blocks (no variable-length boundary scan), so :class:`PrefixCache` keys
``(parent_block_hash, block_token_ids)`` chains to physical block ids.  A
new request's prompt is matched block-by-block against already-resident
prefixes; matched blocks are *shared* (a refcount, not a copy), the
partially-filled tail block is never shared, and a write into a block
that still has other readers triggers copy-on-write allocation of a
private block.  Finished requests' indexed blocks stay resident in an LRU
(the cache holds one reference of its own) so a hot system prompt
survives between requests; eviction reclaims the least-recently-used
unpinned block when the pool runs short.

The same fixed-stride layout is what makes the **swap tier** cheap: a
preempted request's KV state is a set of whole blocks, so paging it to
host memory is one bulk gather along the pool's block axis (contiguous
``[Hkv, bs, hd]`` strides per layer — the paper's "decode is memcpy"
claim applied to scheduling) and restoring it is one scatter into freshly
allocated blocks.  Swapping is refcount-aware: :meth:`PagedKVCache.swap_out`
images the victim's *content* to host and then drops only the victim's own
references — a prefix block shared with another live request stays
resident for that request and is never freed out from under it (its
refcount simply decreases by the victim's share).
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
from typing import Dict, Hashable, List, Optional, Sequence, Tuple

import numpy as np

_ALIGN = 64  # bytes; Bebop-page-style block alignment


class CacheOOM(RuntimeError):
    """The block pool cannot satisfy an allocation right now."""


def aligned_block_size(block_size: int, head_dim: int, dtype) -> int:
    """Round ``block_size`` up until a block row is 64B-aligned.

    One block holds ``block_size * head_dim`` elements per KV head; the
    block stride in bytes must be a multiple of 64 so fixed-stride
    addressing always lands on an aligned boundary (the same rule
    core/device.py applies to page columns).
    """
    itemsize = np.dtype(dtype).itemsize
    bs = max(int(block_size), 1)
    while (bs * head_dim * itemsize) % _ALIGN:
        bs += 1
    return bs


class BlockAllocator:
    """Refcounted free-list allocator over ``num_blocks`` fixed-size blocks.

    Block 0 is reserved (the null block) and never allocated.  Blocks are
    handed out LIFO so recently-freed (likely still-resident) blocks are
    reused first.

    Invariants (the ones ``tests/test_kv_cache.py``'s hypothesis property
    tests enforce — the docs and the tests tell the same story):

    * **No double assignment.**  ``alloc`` never hands out a block that
      still has references; hitting one raises ``AssertionError`` (a
      corrupt free list), restoring the free list first so even the
      failure path conserves capacity.
    * **Exact lifetime.**  A block returns to the free list exactly when
      its refcount reaches 0 — never earlier (a shared block must survive
      its first owner), never later (capacity conservation:
      ``num_free + live blocks == capacity`` at all times).
    * **All-or-nothing alloc.**  ``alloc(n)`` either records all ``n``
      blocks under the owner or raises :class:`CacheOOM` with no side
      effects.
    * **Wholesale release.**  ``free(owner)`` drops *every* reference the
      owner holds — the property the shedding and swap-out paths rely on
      (a retired or paged-out request must never leak pool capacity).
    """

    def __init__(self, num_blocks: int, *, reserved: int = 1):
        if num_blocks <= reserved:
            raise ValueError(f"need > {reserved} blocks, got {num_blocks}")
        self.num_blocks = num_blocks
        self.reserved = reserved
        self._free: List[int] = list(range(num_blocks - 1, reserved - 1, -1))
        self._refs: Dict[int, int] = {}
        self._owned: Dict[Hashable, List[int]] = {}

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def capacity(self) -> int:
        return self.num_blocks - self.reserved

    def blocks_of(self, owner: Hashable) -> List[int]:
        return list(self._owned.get(owner, ()))

    def refcount(self, block: int) -> int:
        """Outstanding references to ``block`` (0 = free)."""
        return self._refs.get(block, 0)

    def is_free(self, block: int) -> bool:
        return block in self._free

    def alloc(self, n: int, owner: Hashable) -> List[int]:
        """Take ``n`` fresh blocks (refcount 1) for ``owner``; all-or-nothing."""
        if n < 0:
            raise ValueError(f"negative block count {n}")
        if n > len(self._free):
            raise CacheOOM(
                f"{n} blocks requested, {len(self._free)} free "
                f"(capacity {self.capacity})")
        out = [self._free.pop() for _ in range(n)]
        bad = next((b for b in out if self._refs.get(b, 0)), None)
        if bad is not None:  # the invariant; corrupt free list if hit
            # all-or-nothing holds even on the invariant path: restore the
            # popped blocks (original order) before raising, so detecting
            # a corrupt free list doesn't ALSO leak pool capacity or leave
            # partially-recorded ownership behind
            self._free.extend(reversed(out))
            raise AssertionError(
                f"block {bad} double-assigned "
                f"({self._refs[bad]} refs outstanding -> {owner!r})")
        for b in out:
            self._refs[b] = 1
        self._owned.setdefault(owner, []).extend(out)
        return out

    def share(self, block: int, owner: Hashable) -> None:
        """Take one additional reference to a live block for ``owner``."""
        if self._refs.get(block, 0) <= 0:
            raise ValueError(f"block {block} is not allocated; cannot share")
        self._refs[block] += 1
        self._owned.setdefault(owner, []).append(block)

    def drop(self, owner: Hashable, block: int) -> bool:
        """Release ONE reference of ``owner`` on ``block``.

        Returns True when that was the last reference (the block is back
        on the free list).  The copy-on-write and LRU-eviction paths
        release single blocks; requests release wholesale via free().
        """
        blocks = self._owned.get(owner)
        if blocks is None or block not in blocks:
            raise ValueError(f"{owner!r} holds no reference to block {block}")
        blocks.remove(block)
        if not blocks:
            del self._owned[owner]
        return self._unref(block)

    def _unref(self, block: int) -> bool:
        left = self._refs[block] - 1
        if left:
            self._refs[block] = left
            return False
        del self._refs[block]  # refcount 0 <=> on the free list
        self._free.append(block)
        return True

    def free(self, owner: Hashable) -> int:
        """Drop EVERY reference held by ``owner`` (finish OR shed path).

        Only blocks whose refcount hits 0 return to the free list; blocks
        still shared with other requests (or pinned by the prefix cache)
        stay resident.  Returns the number of references released.
        """
        blocks = self._owned.pop(owner, [])
        for b in reversed(blocks):   # LIFO reuse: most recently used first
            self._unref(b)
        return len(blocks)


@dataclasses.dataclass(frozen=True)
class PagedLayout:
    """Static geometry of a paged pool (derived, never recomputed per call)."""

    num_layers: int
    num_blocks: int
    num_kv_heads: int
    block_size: int
    head_dim: int
    dtype: str
    blocks_per_seq: int   # block-table width M (= ceil(cache_len / bs))
    # window layers' KV rings: R blocks per batch row (0: no window
    # layers), in a pool of their own with ring_layers layers
    ring_blocks: int = 0
    ring_layers: int = 0
    rows: int = 0         # batch rows that own a ring
    ring_block_size: int = 0   # tokens per ring block

    @property
    def block_bytes(self) -> int:
        """Fixed per-layer block stride in bytes (the Bebop-page analogue)."""
        return (self.num_kv_heads * self.block_size * self.head_dim
                * np.dtype(self.dtype).itemsize)

    @property
    def tokens(self) -> int:
        return self.blocks_per_seq * self.block_size


#: the largest KV ring block of a window layer, in tokens
RING_BLOCK_TOKENS = 128


def ring_geometry(window: int, block_size: int, widest_step: int
                  ) -> Tuple[int, int]:
    """(tokens per ring block, ring blocks R) of a window layer's KV ring;
    (0, 0) without a window.

    A ring block is one grid step of the paged kernels, so it is as large
    as RING_BLOCK_TOKENS and the window allow (a whole number of pool
    blocks): 16-token blocks leave the kernels bound by their per-step
    cost.  A step reads back ``window`` positions from its first query
    while it writes up to ``widest_step`` new ones, so R blocks cover
    ``window + widest_step - 1`` positions and one block more: the ring
    never overwrites what the step still reads."""
    if not window:
        return 0, 0
    ring_bs = block_size * max(1, min(RING_BLOCK_TOKENS, window)
                               // block_size)
    return ring_bs, -(-(window + widest_step - 1) // ring_bs) + 1


def block_keys(tokens, block_size: int) -> List[bytes]:
    """Content-hash chain over the FULL blocks of a token row.

    ``key_i = H(key_{i-1} || tokens_of_block_i)``: a block's key commits
    to the entire prefix ending at its last token, so equal keys <=>
    equal (position, content) prefixes and matching is one flat dict
    probe per block.  Fixed-size blocks are what keep this branchless:
    the hash is a hash of whole blocks, never a variable-length boundary
    scan.  Only full blocks get keys — the partial tail is never shared.
    """
    toks = np.ascontiguousarray(np.asarray(tokens, np.int32).reshape(-1))
    keys: List[bytes] = []
    parent = b""
    for i in range(len(toks) // block_size):
        parent = hashlib.blake2b(
            parent + toks[i * block_size:(i + 1) * block_size].tobytes(),
            digest_size=16).digest()
        keys.append(parent)
    return keys


class PrefixCache:
    """Content-addressed index of full KV blocks + LRU retention.

    Maps chain keys (:func:`block_keys`) to resident physical blocks.
    The cache holds one reference of its own on every indexed block, so
    a finished request's prefix blocks stay out of the free list
    (refcount 1, "cached but unreferenced") until evicted — a hot system
    prompt survives between requests.  Eviction drops the
    least-recently-used indexed block whose only reference is the
    cache's; blocks pinned by live requests are skipped.

    Invariants (enforced by the property tests in
    ``tests/test_kv_cache.py``):

    * **Index implies resident.**  Every indexed block carries the
      cache's own reference, so ``lookup`` can only ever return blocks
      whose content is live in the pool.  This is also why swapped-out
      state can never satisfy a match: swap-out frees (or de-references)
      the victim's blocks, and a block leaves the index *before* it can
      be freed — there is no window where a key maps to absent content.
    * **First writer wins.**  ``register`` never remaps a key or
      re-indexes a block; an identical prompt that raced ahead keeps the
      index stable and the loser keeps its private copy.
    * **Leaf-first eviction.**  Only blocks with no indexed child are
      evicted, so a retained chain is always matchable from its head —
      eviction never strands reachable descendants.
    * **Pinned blocks survive.**  A block referenced by any live request
      (refcount > 1) is never evicted.
    """

    _OWNER = "<prefix-lru>"

    def __init__(self, allocator: BlockAllocator, *, max_blocks: int = 0):
        self.allocator = allocator
        self.max_blocks = max(0, int(max_blocks))  # 0 = pool-bounded
        self._index: Dict[bytes, int] = {}
        self._key_of: Dict[int, bytes] = {}
        self._parent: Dict[bytes, bytes] = {}      # chain linkage
        self._children: Dict[bytes, int] = {}      # indexed children per key
        self._lru: "collections.OrderedDict[int, None]" = \
            collections.OrderedDict()
        self.hits = 0        # blocks handed out via acquire()
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._index)

    @property
    def reclaimable(self) -> int:
        """Indexed blocks no live request references (evictable now)."""
        return sum(1 for b in self._lru if self.allocator.refcount(b) == 1)

    def lookup(self, keys: Sequence[bytes]) -> List[int]:
        """Longest indexed prefix of ``keys`` -> physical block ids."""
        out: List[int] = []
        for k in keys:
            b = self._index.get(k)
            if b is None:
                break
            out.append(b)
        return out

    def acquire(self, blocks: Sequence[int], owner: Hashable) -> None:
        """Reference matched blocks for ``owner`` and refresh recency."""
        for b in blocks:
            self.allocator.share(b, owner)
            self._lru.move_to_end(b)
        self.hits += len(blocks)

    def register(self, key: bytes, block: int,
                 parent: Optional[bytes] = None) -> bool:
        """Index a fully-written block under its chain key.

        First writer wins: if the key is already mapped (an identical
        prompt raced ahead) or the block is already indexed, nothing
        changes and the caller keeps its private copy.  ``parent`` is
        the chain key of the preceding block (None at the chain head);
        the linkage makes eviction leaf-first.
        """
        if key in self._index or block in self._key_of:
            return False
        self._index[key] = block
        self._key_of[block] = key
        if parent is not None:
            self._parent[key] = parent
            self._children[parent] = self._children.get(parent, 0) + 1
        self.allocator.share(block, self._OWNER)
        self._lru[block] = None
        self._lru.move_to_end(block)
        self.trim()
        return True

    def trim(self) -> int:
        """Enforce ``max_blocks``: evict unpinned entries over the cap.
        Called on register AND on request release — a block pinned by
        its writer at registration time only becomes evictable once
        that request drops its reference."""
        if not self.max_blocks or len(self._lru) <= self.max_blocks:
            return 0
        return self.evict(len(self._lru) - self.max_blocks)

    def evict(self, n: int) -> int:
        """Drop up to ``n`` unpinned blocks, LRU-ordered LEAF-first.

        Only blocks with no indexed child are candidates: lookup() walks
        chains from the head, so evicting a chain-head block would leave
        every retained descendant permanently unmatchable dead weight.
        Leaf-first eviction trims chains from the tail and keeps the
        matchable prefix resident.  Returns the number freed.
        """
        done = 0
        progress = True
        while done < n and progress:   # evicting a leaf may expose its
            progress = False           # parent as the next candidate
            for b in list(self._lru):
                if done >= n:
                    break
                if self.allocator.refcount(b) != 1:
                    continue  # pinned by a live request; not evictable
                key = self._key_of[b]
                if self._children.get(key, 0):
                    continue  # interior chain block; evict its tail first
                del self._index[key]
                del self._key_of[b]
                del self._lru[b]
                self._children.pop(key, None)
                parent = self._parent.pop(key, None)
                if parent is not None:
                    left = self._children[parent] - 1
                    if left:
                        self._children[parent] = left
                    else:
                        del self._children[parent]
                self.allocator.drop(self._OWNER, b)
                done += 1
                progress = True
        self.evictions += done
        return done


@dataclasses.dataclass
class _SwappedSeq:
    """Host-side image of one owner's paged KV state while preempted.

    ``host_k``/``host_v`` are ``[L, n, Hkv, bs, hd]`` numpy buffers — the
    owner's ``n`` blocks gathered in table order.  On accelerator backends
    these land in page-locked (pinned) host memory via the device runtime;
    the fixed block stride is what keeps the transfer a handful of bulk
    contiguous copies instead of a per-token scatter.
    """

    blocks: int
    host_k: np.ndarray
    host_v: np.ndarray
    ring_k: Optional[np.ndarray] = None   # the row's KV ring, if any
    ring_v: Optional[np.ndarray] = None


class PagedKVCache:
    """Device-resident block pool + per-request block tables.

    ``pool`` is a ``{"k", "v"}`` dict of ``[L, N, Hkv, bs, hd]`` arrays the
    engine threads through its jitted steps (donated, so updates are in
    place).  This class owns the *bookkeeping*: which physical blocks back
    which request, and the padded ``[M]`` int32 table rows the kernels
    consume.

    Swap-tier invariants (see :meth:`swap_out` / :meth:`swap_in`; the
    overload tests in ``tests/test_swap.py`` enforce them):

    * **Content round-trip.**  ``swap_in(swap_out(owner))`` restores the
      owner's blocks bit-identically (into freshly allocated physical
      blocks — block *ids* may change, content may not).
    * **Refcount safety.**  Swap-out drops only the owner's own
      references; a block shared with another live request or the prefix
      index stays resident and untouched.
    * **No leaks either way.**  A swapped owner holds zero device blocks;
      :meth:`release` on a swapped owner also discards the host image, so
      shedding a paged-out request reclaims host AND device resources.
    """

    def __init__(self, *, num_layers: int, num_kv_heads: int, head_dim: int,
                 cache_len: int, block_size: int = 16, num_blocks: int = 0,
                 max_concurrent: int = 8, dtype: str = "float32",
                 prefix_cache: bool = True, prefix_lru_blocks: int = 0,
                 ring_blocks: int = 0, ring_layers: int = 0,
                 ring_block_size: int = 0):
        bs = aligned_block_size(block_size, head_dim, dtype)
        m = -(-cache_len // bs)
        if num_blocks <= 0:
            num_blocks = max_concurrent * m + 1  # +1 for the null block
        self.layout = PagedLayout(num_layers, num_blocks, num_kv_heads, bs,
                                  head_dim, dtype, m, ring_blocks,
                                  ring_layers, max_concurrent,
                                  ring_block_size)
        self.allocator = BlockAllocator(num_blocks)
        self.prefix: Optional[PrefixCache] = \
            PrefixCache(self.allocator, max_blocks=prefix_lru_blocks) \
            if prefix_cache else None
        self._tables: Dict[Hashable, List[int]] = {}
        self._keys: Dict[Hashable, List[bytes]] = {}       # per-owner chain
        self._registered: Dict[Hashable, int] = {}         # blocks indexed
        self._swapped: Dict[Hashable, _SwappedSeq] = {}    # host images
        self._gather_fn = None   # jitted swap copies, built on first use
        self._scatter_fn = None
        self._ring_fns = None
        self._pool = None   # device buffers materialize lazily (or are
        # injected by the engine, whose model owns the pool layout)
        assert self.layout.block_bytes % _ALIGN == 0

    @property
    def pool(self):
        """{"k", "v"} device pools, [L, N, Hkv, bs, hd].

        Lazy: the engine injects the model-built pool before first use, so
        the default buffers — the largest allocations in the serving path —
        are never built twice.  K and V are distinct buffers because the
        jitted steps donate the pool.
        """
        if self._pool is None:
            import jax.numpy as jnp
            lo = self.layout
            shape = (lo.num_layers, lo.num_blocks, lo.num_kv_heads,
                     lo.block_size, lo.head_dim)
            self._pool = {"k": jnp.zeros(shape, jnp.dtype(lo.dtype)),
                          "v": jnp.zeros(shape, jnp.dtype(lo.dtype))}
        return self._pool

    @pool.setter
    def pool(self, value) -> None:
        self._pool = value

    @property
    def block_size(self) -> int:
        return self.layout.block_size

    @property
    def ring_rows(self) -> int:
        """Blocks of the window layers' ring pool: each batch row's R
        and the null block (0 without window layers)."""
        lo = self.layout
        return 1 + lo.rows * lo.ring_blocks if lo.ring_blocks else 0

    def ring_ids(self, row: int) -> np.ndarray:
        """The ring blocks batch row ``row`` owns, in ring-slot order (the
        step's ``layers.ring_addresses`` places logical block j in slot
        j mod R)."""
        r = self.layout.ring_blocks
        return np.arange(1 + row * r, 1 + (row + 1) * r, dtype=np.int32)

    def _rings(self):
        if self._ring_fns is None:
            import jax

            def gather(pool, ids):
                return pool["ring_k"][:, ids], pool["ring_v"][:, ids]

            def scatter(pool, ids, rk, rv):
                return dict(pool, ring_k=pool["ring_k"].at[:, ids].set(rk),
                            ring_v=pool["ring_v"].at[:, ids].set(rv))

            def copy(pool, src, dst):
                return dict(pool,
                            ring_k=pool["ring_k"].at[:, dst].set(
                                pool["ring_k"][:, src]),
                            ring_v=pool["ring_v"].at[:, dst].set(
                                pool["ring_v"][:, src]))

            self._ring_fns = (jax.jit(gather),
                              jax.jit(scatter, donate_argnums=(0,)),
                              jax.jit(copy, donate_argnums=(0,)))
        return self._ring_fns

    def copy_ring(self, src_row: int, dst_row: int) -> None:
        """Copy batch row ``src_row``'s KV ring into ``dst_row``'s (a
        forked candidate takes its prompt's last window with it)."""
        _, _, copy = self._rings()
        self.pool = copy(self.pool, self.ring_ids(src_row),
                         self.ring_ids(dst_row))

    @property
    def blocks_per_seq(self) -> int:
        return self.layout.blocks_per_seq

    @property
    def num_free_blocks(self) -> int:
        return self.allocator.num_free

    @property
    def reclaimable(self) -> int:
        """Cached-but-unreferenced blocks an allocation could evict."""
        return self.prefix.reclaimable if self.prefix is not None else 0

    def blocks_needed(self, num_tokens: int) -> int:
        """Blocks covering ``num_tokens`` logical positions.

        Raises :class:`ValueError` when the request can never fit one
        block-table row — the old ``min(...)`` clamp silently truncated
        the table, so a request longer than ``cache_len`` was accepted
        and its later tokens would have aliased the early blocks.
        """
        n = -(-num_tokens // self.block_size)
        if n > self.blocks_per_seq:
            raise ValueError(
                f"{num_tokens} tokens need {n} blocks; a table row holds "
                f"{self.blocks_per_seq} (cache_len {self.layout.tokens})")
        return n

    def can_allocate(self, num_tokens: int) -> bool:
        try:
            need = self.blocks_needed(num_tokens)
        except ValueError:  # oversized: reject, never truncate
            return False
        return need <= self.allocator.num_free + self.reclaimable

    def _reserve(self, n: int, owner: Hashable) -> List[int]:
        """alloc() with LRU pressure-relief: when the free list is short,
        evict cached-but-unreferenced prefix blocks before giving up —
        a CacheOOM sheds a request; a cold cache entry is always the
        cheaper loss.  A shortfall eviction can't cover is refused
        UP FRONT: flushing the warm cache for an allocation that raises
        anyway would cost every future hit and buy nothing."""
        short = n - self.allocator.num_free
        if short > 0 and self.prefix is not None:
            if short > self.prefix.reclaimable:
                raise CacheOOM(
                    f"{n} blocks requested, {self.allocator.num_free} free "
                    f"+ {self.prefix.reclaimable} evictable "
                    f"(capacity {self.allocator.capacity})")
            self.prefix.evict(short)
        return self.allocator.alloc(n, owner)

    def allocate(self, owner: Hashable, num_tokens: int) -> np.ndarray:
        """Reserve blocks covering ``num_tokens`` logical positions.

        Returns the padded ``[M]`` int32 block-table row (padding entries
        point at the null block).  All-or-nothing: raises :class:`CacheOOM`
        without side effects if the pool is short (after evicting idle
        prefix-cache blocks), :class:`ValueError` if the request can never
        fit a table row.
        """
        if owner in self._tables:
            raise ValueError(f"owner {owner!r} already holds blocks")
        blocks = self._reserve(self.blocks_needed(num_tokens), owner)
        self._tables[owner] = blocks
        return self.table_row(owner)

    # -- prefix caching ------------------------------------------------------
    def match_prefix(self, tokens) -> int:
        """Longest indexed prefix of ``tokens``, in blocks (lookup only)."""
        if self.prefix is None:
            return 0
        return len(self.prefix.lookup(block_keys(tokens, self.block_size)))

    def allocate_prefix(self, owner: Hashable, num_tokens: int, tokens, *,
                        limit: Optional[int] = None,
                        keys: Optional[List[bytes]] = None
                        ) -> Tuple[np.ndarray, int, int]:
        """:meth:`allocate`, but leading table entries may be SHARED.

        The row's prompt is matched block-by-block against the prefix
        index; matched (full) blocks are referenced in place and private
        blocks are allocated only for the remainder.  Returns
        ``(table_row, matched_tokens, shared_blocks)``.

        ``matched_tokens`` is clamped to ``len(tokens) - 1`` so at least
        one prompt token always remains to process — the step that
        produces the first generated logits.  When the clamp lands that
        position inside a fully-matched block (prompt length a multiple
        of the block size), the write there later copy-on-writes via
        :meth:`ensure_private`.  ``limit`` caps the matched blocks (the
        engine aligns multi-row requests on their weakest row) and
        ``keys`` passes a precomputed :func:`block_keys` chain so callers
        that already hashed the prompt don't hash it twice.
        All-or-nothing, like allocate().
        """
        if owner in self._tables:
            raise ValueError(f"owner {owner!r} already holds blocks")
        total = self.blocks_needed(num_tokens)
        shared: List[int] = []
        if self.prefix is None:
            keys = []
        else:
            if keys is None:
                keys = block_keys(tokens, self.block_size)
            shared = self.prefix.lookup(keys)
            if limit is not None:
                shared = shared[:limit]
            # take the references BEFORE any eviction below can run, so a
            # private-block shortfall never reclaims our own match
            self.prefix.acquire(shared, owner)
        try:
            private = self._reserve(total - len(shared), owner)
        except CacheOOM:
            for b in reversed(shared):
                self.allocator.drop(owner, b)
            raise
        self._tables[owner] = shared + private
        self._keys[owner] = keys
        self._registered[owner] = len(shared)  # matched keys already indexed
        t = int(np.asarray(tokens).reshape(-1).shape[0])
        matched = min(len(shared) * self.block_size, max(t - 1, 0))
        return self.table_row(owner), matched, len(shared)

    def register_progress(self, owner: Hashable, tokens, written: int) -> int:
        """Index ``owner``'s full prompt blocks once their content is
        resident (``written`` = prompt tokens written so far).  Called by
        the engine after each prefill advance; returns #new index entries.
        """
        if self.prefix is None or owner not in self._tables:
            return 0
        keys = self._keys.get(owner, ())
        done = self._registered.get(owner, 0)
        upto = min(written // self.block_size, len(keys))
        blocks = self._tables[owner]
        new = 0
        for i in range(done, upto):
            new += bool(self.prefix.register(
                keys[i], blocks[i], keys[i - 1] if i else None))
        if upto > done:
            self._registered[owner] = upto
        return new

    def fork(self, src: Hashable, dst: Hashable, *,
             shared_tokens: int) -> np.ndarray:
        """Clone ``src``'s table for new owner ``dst``, SHARING the blocks
        that cover the first ``shared_tokens`` positions (refcount bumps,
        zero copies) and allocating fresh private blocks for the rest of
        the row.  Returns ``dst``'s padded table row.

        This is the n>1 parallel-sampling fork: candidate rows share the
        prompt's KV through the refcounted allocator and diverge via the
        :meth:`ensure_private` copy-on-write path at their first private
        write — only a partially-filled boundary block is ever copied,
        and only once per candidate.  The generation tail is allocated
        private up front (its content does not exist yet, so there is
        nothing worth sharing).  All-or-nothing like :meth:`allocate`.

        ``dst`` inherits ``src``'s prefix-key chain and registration
        watermark (clamped to the shared region), so
        :meth:`register_progress` and :meth:`release` treat a forked
        candidate exactly like any other owner.
        """
        if dst in self._tables:
            raise ValueError(f"owner {dst!r} already holds blocks")
        blocks = self._tables[src]
        n_shared = min(-(-max(shared_tokens, 0) // self.block_size),
                       len(blocks))
        for b in blocks[:n_shared]:
            self.allocator.share(b, dst)
        try:
            private = self._reserve(len(blocks) - n_shared, dst)
        except CacheOOM:
            for b in reversed(blocks[:n_shared]):
                self.allocator.drop(dst, b)
            raise
        self._tables[dst] = blocks[:n_shared] + private
        self._keys[dst] = list(self._keys.get(src, []))
        self._registered[dst] = min(self._registered.get(src, 0), n_shared)
        return self.table_row(dst)

    def ensure_private(self, owner: Hashable, idx: int
                       ) -> Optional[Tuple[int, int]]:
        """Copy-on-write hook: if ``owner``'s table entry ``idx`` is
        shared (refcount > 1), swap in a fresh private block and return
        ``(old, new)`` so the engine can copy the pool contents before
        writing.  None when the block is already exclusively owned."""
        blocks = self._tables[owner]
        old = blocks[idx]
        if self.allocator.refcount(old) <= 1:
            return None
        new = self._reserve(1, owner)[0]
        self.allocator.drop(owner, old)
        blocks[idx] = new
        return old, new

    def ensure_private_range(self, owner: Hashable, start_token: int,
                             num_tokens: int) -> List[Tuple[int, int, int]]:
        """Copy-on-write every SHARED block the write range
        ``[start_token, start_token + num_tokens)`` touches.

        The multi-token write ranges (prefill chunks, and speculative
        draft-verify steps that write ``1 + spec_len`` positions at once)
        funnel through here: a write must never mutate a block other
        requests or the prefix index still read.  Returns
        ``[(table_idx, old_block, new_block), ...]`` for the blocks that
        were swapped, so the engine can copy pool contents before
        writing.  Speculative ROLLBACK needs no inverse operation: a
        rejected draft's positions are simply never committed
        (``register_progress`` indexes nothing past the prompt and the
        scheduler does not advance past the accepted prefix), so the
        stale K/V is dead weight the next write overwrites.
        """
        if num_tokens <= 0:
            return []
        bs = self.block_size
        lo, hi = start_token // bs, (start_token + num_tokens - 1) // bs
        out: List[Tuple[int, int, int]] = []
        for idx in range(lo, hi + 1):
            pair = self.ensure_private(owner, idx)
            if pair is not None:
                out.append((idx, pair[0], pair[1]))
        return out

    # -- swap tier -----------------------------------------------------------
    @staticmethod
    def _pad_ids(blocks: Sequence[int]) -> np.ndarray:
        """Block ids padded to the next power of two with the null block.

        The swap gather/scatter are jitted per index width; pow2 padding
        bounds the compilation count at log2(pool).  Padding with block 0
        is safe by design: the null block is the garbage sink — reading
        it transfers junk that is sliced off, and scattering junk INTO it
        can corrupt nothing live.
        """
        w = 1
        while w < max(len(blocks), 1):
            w <<= 1
        ids = np.zeros(w, np.int32)
        ids[:len(blocks)] = blocks
        return ids

    def _swap_fns(self):
        if self._gather_fn is None:
            import jax

            def gather(pool, ids):
                return pool["k"][:, ids], pool["v"][:, ids]

            def scatter(pool, ids, hk, hv):
                return dict(pool, k=pool["k"].at[:, ids].set(hk),
                            v=pool["v"].at[:, ids].set(hv))

            self._gather_fn = jax.jit(gather)
            self._scatter_fn = jax.jit(scatter, donate_argnums=(0,))
        return self._gather_fn, self._scatter_fn

    def is_swapped(self, owner: Hashable) -> bool:
        return owner in self._swapped

    def swapped_blocks(self, owner: Hashable) -> int:
        """Blocks the host image of ``owner`` holds (0 = not swapped)."""
        sw = self._swapped.get(owner)
        return sw.blocks if sw is not None else 0

    def swap_out(self, owner: Hashable, ring_row: Optional[int] = None
                 ) -> int:
        """Page ``owner``'s KV blocks out to a host image; returns #blocks.

        One bulk gather along the pool's block axis copies the owner's
        blocks (table order) to host, then every device reference the
        owner holds is dropped.  Exclusively-owned blocks return to the
        free list; blocks shared with other requests or the prefix index
        merely lose one reference and stay resident — a victim is never
        swapped out from under the requests it shares a prefix with.
        The content of shared blocks is imaged too, so a later
        :meth:`swap_in` restores the owner even if the sharers (and the
        index) have since released the blocks.

        The owner's chain keys are kept but its registration watermark is
        reset: on resume the restored blocks are fresh private copies, and
        re-registering them is first-writer-wins against the index.

        ``ring_row``: the batch row whose window layers' KV ring the owner
        fills; the ring is imaged with the blocks and restored into the
        row :meth:`swap_in` is given.
        """
        blocks = self._tables.get(owner)
        if blocks is None:
            raise ValueError(f"owner {owner!r} holds no blocks to swap")
        if owner in self._swapped:
            raise ValueError(f"owner {owner!r} is already swapped out")
        n = len(blocks)
        gather, _ = self._swap_fns()
        gk, gv = gather(self.pool, self._pad_ids(blocks))
        host_k = np.asarray(gk)[:, :n].copy()
        host_v = np.asarray(gv)[:, :n].copy()
        rings = (None, None)
        if ring_row is not None and self.layout.ring_blocks:
            rk, rv = self._rings()[0](self.pool, self.ring_ids(ring_row))
            rings = (np.asarray(rk).copy(), np.asarray(rv).copy())
        self._swapped[owner] = _SwappedSeq(n, host_k, host_v, *rings)
        del self._tables[owner]
        if owner in self._registered:
            self._registered[owner] = 0
        self.allocator.free(owner)
        if self.prefix is not None:
            self.prefix.trim()
        return n

    def swap_in(self, owner: Hashable, ring_row: Optional[int] = None
                ) -> np.ndarray:
        """Restore a swapped owner into freshly allocated blocks.

        Allocates ``blocks`` new private blocks (evicting idle prefix
        blocks under pressure, raising :class:`CacheOOM` with no state
        change if even that cannot cover), scatters the host image back
        in one bulk copy, and discards the image.  Returns the new padded
        table row; physical ids generally differ from before swap-out —
        only content is guaranteed identical.  A ring imaged at swap-out
        goes to batch row ``ring_row``'s ring.
        """
        sw = self._swapped.get(owner)
        if sw is None:
            raise ValueError(f"owner {owner!r} is not swapped out")
        blocks = self._reserve(sw.blocks, owner)
        self._tables[owner] = blocks
        _, scatter = self._swap_fns()
        ids = self._pad_ids(blocks)
        lo = self.layout
        shape = (lo.num_layers, len(ids), lo.num_kv_heads, lo.block_size,
                 lo.head_dim)
        hk = np.zeros(shape, sw.host_k.dtype)
        hv = np.zeros(shape, sw.host_v.dtype)
        hk[:, :sw.blocks] = sw.host_k
        hv[:, :sw.blocks] = sw.host_v
        self.pool = scatter(self.pool, ids, hk, hv)
        if sw.ring_k is not None and ring_row is not None:
            self.pool = self._rings()[1](self.pool, self.ring_ids(ring_row),
                                         sw.ring_k, sw.ring_v)
        del self._swapped[owner]
        return self.table_row(owner)

    def table_row(self, owner: Hashable) -> np.ndarray:
        row = np.zeros(self.blocks_per_seq, np.int32)
        blocks = self._tables[owner]
        row[:len(blocks)] = blocks
        return row

    def release(self, owner: Hashable) -> int:
        """Drop every reference of ``owner`` (finish OR shed path).

        Blocks the prefix index retains (or other requests still share)
        stay resident; everything else returns to the free list.  If the
        owner is swapped out, its host image is discarded too — shedding
        a paged-out request reclaims host and device resources alike."""
        self._tables.pop(owner, None)
        self._keys.pop(owner, None)
        self._registered.pop(owner, None)
        self._swapped.pop(owner, None)
        n = self.allocator.free(owner)
        if self.prefix is not None:
            self.prefix.trim()   # cap now that this owner's pins are gone
        return n
