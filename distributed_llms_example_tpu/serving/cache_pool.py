"""Paged KV-cache: a shared block pool + host-side free-list allocator.

The flat serving state charges every decode slot ``max_source_length``
worth of cache whether its prompt needs it or not — the exact capacity
ceiling the Gemma-on-TPU serving comparison (arXiv:2605.25645) names.
Here slots become BLOCK LISTS over a shared pool (vLLM-style paging,
restated for the fixed-shape SPMD engine):

- the resident serving state is one fixed-shape pool tensor per cache
  leaf — ``(num_blocks, block_size, heads x head_dim)``, a block laid like
  a tile of the slot leaf — so admitting or evicting a request never
  changes a compiled shape (no recompiles);
- a request holds ``ceil(prompt_len / block_size)`` prompt blocks plus
  ``ceil(budget / block_size)`` decode blocks — bytes scale with the
  ACTUAL prompt, not the worst case;
- allocation/free is pure host bookkeeping (``CachePool``) between
  jitted steps, mirroring how the engine already admits/evicts slots;
  blocks are identityless, so "fragmentation" cannot strand capacity —
  any request whose block count fits the free list is admissible;
- the compiled decode step reads the pool through a per-slot block
  table: on the kernel path ``ops.flash_attention.flash_decode_paged``
  indexes pool blocks directly in its tile loop (block size == kv tile
  size); the XLA path gathers a slot view with ``mode="fill"`` zeros for
  unallocated tiles, which the attention mask makes contribute exactly
  nothing — that fill is what makes paged decode BIT-identical to flat.

Stale blocks are unreachable by the same argument PR 7 made for slot
reuse, restated per block: a freed block re-enters the pool with its old
contents, but every read is masked to ``k_pos <= offset`` (decode tail)
or to the attention mask (prompt region), so a new owner's output cannot
observe the previous owner's K/V.  The ``pool-garbage-invariant`` test
pins this by poisoning the whole pool at init.

Spec lint: ``parallel/sharding.py POOL_RULES`` is the pool's rule set,
validated by ``analysis/spec_lint.py lint_cache_sharding`` exactly like
``CACHE_RULES`` for the flat cache.
"""

from __future__ import annotations

import hashlib
import math
from collections import OrderedDict
from typing import Any, Iterable, Sequence

import jax
import jax.numpy as jnp

from distributed_llms_example_tpu.parallel.sharding import CACHE_LENGTH_AXIS, cache_leaf_name


# --------------------------------------------------- block content identity
#
# A block's identity is the CHAIN of token ids that produced it: layer-l
# K/V at position p depends on every token <= p, so two blocks holding
# the same block_size tokens are only interchangeable when their whole
# prefixes match.  Chaining the predecessor's hash into each block's
# hash encodes exactly that — equal chain hash ⟺ equal token prefix.
# This module is the ONE owner of both the hash computation and the
# refcount bookkeeping (repo_lint rule: cache identity has one owner).


def block_hash(prev_hash: str | None, tokens: Sequence[int]) -> str:
    """Chain hash of one full block: sha256 over the predecessor's hash
    (empty for the first block) and this block's token ids.  Different
    predecessor → different hash, so a match on block k implies blocks
    0..k-1 matched too — the collision discipline the prefix walk
    relies on."""
    h = hashlib.sha256()
    h.update(b"" if prev_hash is None else prev_hash.encode("ascii"))
    h.update(("|".join(str(int(t)) for t in tokens)).encode("ascii"))
    return h.hexdigest()


def chain_hashes(tokens: Sequence[int], block_size: int) -> list[str]:
    """Chain hashes for every FULL block of ``tokens`` (the partial tail
    block has no stable identity and is never shared)."""
    out: list[str] = []
    prev: str | None = None
    for start in range(0, (len(tokens) // block_size) * block_size, block_size):
        prev = block_hash(prev, tokens[start : start + block_size])
        out.append(prev)
    return out


# ----------------------------------------------------- host-side allocator


class CachePool:
    """Free-list allocator over cache blocks, with refcounted sharing and
    a warm LRU of finished requests' prefix blocks (pure host).

    The engine calls ``alloc`` at admission and ``free`` at eviction —
    between jitted steps, like every other piece of slot bookkeeping.
    ``alloc`` grants a block with refcount 1; ``acquire`` bumps the
    count on a matched prefix chain; ``free`` is a refcount DECREMENT
    with reclaim at zero — reclaimed blocks whose chain hash is
    registered park in a warm LRU (up to ``warm_capacity`` blocks) so a
    follow-up turn can re-acquire them, everything else returns to the
    free list.  Warm blocks count as allocatable: ``alloc`` evicts the
    oldest warm entries under pressure, so retention can never fail an
    admission that would have fit without it.

    Invariants (property-tested and walkable via
    ``ref_invariant_violations``): a block is never handed out twice,
    ``blocks_free + blocks_in_use == num_blocks`` always, every
    refcount equals the number of live references, warm blocks are
    strictly refcount 0, double-free and foreign-free raise."""

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks < 1:
            raise ValueError(f"num_blocks must be >= 1, got {num_blocks}")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        # pop() from the end → blocks hand out in ascending order, which
        # keeps tests readable; correctness never depends on the order
        self._free: list[int] = list(range(self.num_blocks - 1, -1, -1))
        self._used: set[int] = set()
        # prefix-cache state — inert until the engine registers chains:
        # _ref[b] is b's refcount (every _used block has an entry),
        # _hash_of[b]/_index[h] the two directions of the chain-hash
        # index (live OR warm blocks only — a block on the free list has
        # no identity), _lru the refcount-0 retained blocks in eviction
        # order (oldest first), warm_capacity the retention budget in
        # blocks (0 = retention off, the default: free() then behaves
        # exactly like the pre-prefix-cache pool)
        self._ref: dict[int, int] = {}
        self._hash_of: dict[int, str] = {}
        self._index: dict[str, int] = {}
        self._lru: "OrderedDict[int, None]" = OrderedDict()
        self.warm_capacity = 0

    @property
    def blocks_free(self) -> int:
        # warm blocks are reclaimable on demand, so they are FREE from
        # the allocator's point of view — retention never costs capacity
        return len(self._free) + len(self._lru)

    @property
    def blocks_in_use(self) -> int:
        return len(self._used)

    @property
    def blocks_warm(self) -> int:
        return len(self._lru)

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free) + len(self._lru)

    def alloc(self, n: int) -> list[int] | None:
        """``n`` fresh blocks at refcount 1, or None when the free list
        plus the evictable warm set is short (the caller defers
        admission — never a partial grant)."""
        if n < 0:
            raise ValueError(f"cannot alloc {n} blocks")
        if n > len(self._free) + len(self._lru):
            return None
        while len(self._free) < n:
            self._evict_warm()
        out = [self._free.pop() for _ in range(n)]
        self._used.update(out)
        for b in out:
            self._ref[b] = 1
        return out

    def free(self, blocks: Sequence[int]) -> None:
        """Drop one reference per block; a block reclaims at refcount 0 —
        into the warm LRU when its chain hash is registered and the
        budget allows, else back to the free list."""
        for b in blocks:
            if b not in self._used:
                raise ValueError(
                    f"block {b} is not allocated (double-free or foreign id)"
                )
            self._ref[b] -= 1
            if self._ref[b] > 0:
                continue
            self._used.remove(b)
            del self._ref[b]
            if b in self._hash_of and self.warm_capacity > 0:
                self._lru[b] = None
                self._lru.move_to_end(b)
                while len(self._lru) > self.warm_capacity:
                    self._evict_warm()
            else:
                self._unregister(b)
                self._free.append(b)

    # ------------------------------------------------- prefix-chain index

    def acquire(self, blocks: Sequence[int]) -> None:
        """Take one more reference on each block of a matched chain —
        live blocks bump their refcount, warm blocks revive out of the
        LRU at refcount 1."""
        for b in blocks:
            if b in self._used:
                self._ref[b] += 1
            elif b in self._lru:
                del self._lru[b]
                self._used.add(b)
                self._ref[b] = 1
            else:
                raise ValueError(
                    f"block {b} is neither live nor warm (stale chain match)"
                )

    def register(self, blocks: Sequence[int], hashes: Sequence[str]) -> None:
        """Record chain hashes for a request's full prompt blocks so later
        admissions can match them.  First writer wins: a hash already
        indexed keeps its existing block (the duplicate block simply
        stays anonymous and reclaims to the free list)."""
        if len(blocks) != len(hashes):
            raise ValueError(
                f"got {len(blocks)} blocks for {len(hashes)} hashes"
            )
        for b, h in zip(blocks, hashes):
            if b not in self._used:
                raise ValueError(f"block {b} is not allocated (cannot register)")
            if self._hash_of.get(b) == h:
                continue  # re-registration of a shared chain is a no-op
            if b in self._hash_of or h in self._index:
                continue  # first writer wins; never re-key a live block
            self._hash_of[b] = h
            self._index[h] = b

    def lookup(self, h: str) -> int | None:
        return self._index.get(h)

    def match_chain(self, hashes: Sequence[str]) -> list[int]:
        """Blocks for the longest indexed prefix of ``hashes`` — the
        admission walk.  Chained hashing makes any gap impossible, so
        the walk stops at the first miss."""
        out: list[int] = []
        for h in hashes:
            b = self._index.get(h)
            if b is None:
                break
            out.append(b)
        return out

    def drop_warm(self) -> int:
        """Evict the ENTIRE warm set (replica teardown: a dead replica's
        pool is gone, so its retained chains must not be matchable).
        Returns the number of blocks released."""
        n = len(self._lru)
        while self._lru:
            self._evict_warm()
        return n

    def _evict_warm(self) -> None:
        b, _ = self._lru.popitem(last=False)  # strictly oldest first
        self._unregister(b)
        self._free.append(b)

    def _unregister(self, b: int) -> None:
        h = self._hash_of.pop(b, None)
        if h is not None:
            self._index.pop(h, None)

    # ------------------------------------------------- invariant walking

    def ref_invariant_violations(
        self, live_chains: Iterable[Sequence[int]]
    ) -> list[str]:
        """Every block's refcount must equal its live references — walked
        from the engine's block tables (``live_chains``: one sequence of
        block ids per live slot) plus the warm LRU.  Also checks the
        free/used/warm partition and index consistency.  Returns
        human-readable violations; empty means the account is exact."""
        out: list[str] = []
        want: dict[int, int] = {}
        for chain in live_chains:
            for b in chain:
                want[b] = want.get(b, 0) + 1
        for b, n in sorted(want.items()):
            if self._ref.get(b) != n:
                out.append(
                    f"block {b}: refcount {self._ref.get(b)} != {n} live references"
                )
        for b in sorted(self._used):
            if b not in want:
                out.append(f"block {b}: in use with no live reference")
        for b in self._lru:
            if b in want:
                out.append(f"block {b}: warm but referenced by a live slot")
            if b not in self._hash_of:
                out.append(f"block {b}: warm without a registered hash")
        free, used, warm = set(self._free), self._used, set(self._lru)
        if free & used or free & warm or used & warm:
            out.append("free/used/warm sets overlap")
        if len(free) + len(used) + len(warm) != self.num_blocks:
            out.append(
                f"partition covers {len(free) + len(used) + len(warm)} of "
                f"{self.num_blocks} blocks"
            )
        for h, b in self._index.items():
            if b not in used and b not in warm:
                out.append(f"hash {h[:12]}…: indexed block {b} is on the free list")
            if self._hash_of.get(b) != h:
                out.append(f"hash {h[:12]}…: index and hash_of disagree on {b}")
        return out


def blocks_needed(prompt_len: int, budget: int, block_size: int) -> int:
    """Blocks one request holds for its whole lifetime: prompt tiles by
    ACTUAL length + decode tiles by its token budget — allocated once at
    admission, so a slot never stalls mid-decode waiting for a block."""
    return max(
        1, math.ceil(max(prompt_len, 1) / block_size)
    ) + math.ceil(max(budget, 1) / block_size)


def build_block_row(
    n_tiles: int,
    blocks: Sequence[int],
    *,
    prompt_len: int,
    bucket_width: int,
    budget: int,
    block_size: int,
    sentinel: int,
):
    """One slot's block-table row: prompt tiles ``[0, ceil(len/bs))`` and
    decode tiles ``[bucket/bs, bucket/bs + ceil(budget/bs))`` take the
    allocated blocks in order; everything else (the padding gap between
    the true prompt and the bucket width, and the tail past the budget)
    stays at ``sentinel`` — reads of those tiles fill zeros, writes drop."""
    import numpy as np

    if bucket_width % block_size:
        raise ValueError(
            f"bucket width {bucket_width} must be a multiple of the block "
            f"size {block_size} (decode tiles must start on a tile boundary)"
        )
    row = np.full(n_tiles, sentinel, np.int32)
    prompt_tiles = max(1, math.ceil(max(prompt_len, 1) / block_size))
    decode_tile0 = bucket_width // block_size
    decode_tiles = math.ceil(max(budget, 1) / block_size)
    want = prompt_tiles + decode_tiles
    if len(blocks) != want:
        raise ValueError(f"got {len(blocks)} blocks for {want} tiles")
    row[:prompt_tiles] = blocks[:prompt_tiles]
    row[decode_tile0 : decode_tile0 + decode_tiles] = blocks[prompt_tiles:]
    return row


# ------------------------------------------------ in-program pool plumbing
#
# These run INSIDE the engine's jitted admit/step programs.  Leaf
# conventions mirror the flax cache collection (``ops/mha.py``
# ``_cache_kv``): 3-D (slots, len, heads x head_dim) K/V buffers and
# (slots, len, heads) int8-KV scale leaves — one order of axes, so one
# branch serves both — and scalars (cache_index), which pass through
# untouched.


def pad_axis(x, axis: int, width: int):
    """Right-pad one axis to ``width`` with zeros — how a bucket-width
    admission chunk lands in full-width slot state.  The padding is
    mask-invisible: enc_mask/full_mask stay 0 there, so padded
    positions contribute exactly nothing (the bucketed == unbucketed
    bit-identity argument)."""
    if x.shape[axis] == width:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, width - x.shape[axis])
    return jnp.pad(x, pads)


def pad_cache_length(cache: Any, width: int):
    """Bucket-width chunk cache → slot width, by leaf: K/V and the int8
    scale leaves grow along their length axis (``CACHE_LENGTH_AXIS``); a
    conv state or a counter has one shape at every width."""

    def pad(path, x):
        axis = CACHE_LENGTH_AXIS.get(cache_leaf_name(path))
        return x if axis is None else pad_axis(x, axis, width)

    return jax.tree_util.tree_map_with_path(pad, cache)


def pool_cache_tree(abstract_cache: Any, num_blocks: int, block_size: int):
    """Zeroed pool tree with the same structure as a slot-view cache tree:
    every (slots, len, x) leaf becomes ``(num_blocks, block_size, x)``,
    scalars stay scalars.  The ONE place slot-view shapes map to pool
    shapes."""

    def to_pool(x):
        shape = tuple(getattr(x, "shape", ()))
        if len(shape) == 3:
            shape = (num_blocks, block_size, shape[2])
        return jnp.zeros(shape, x.dtype)

    return jax.tree.map(to_pool, abstract_cache)


def gather_cache(pool_tree: Any, block_tables: jnp.ndarray):
    """Slot-view cache tree from the pool through the block tables —
    ``mode="fill"`` zeros for sentinel (unallocated) tiles, which the
    attention masks make contribute exactly nothing (the paged==flat
    bit-identity argument).  A slot's blocks follow one another on the
    length axis, so the view is the gathered blocks, reshaped.  The view is
    a STEP-TRANSIENT on the XLA path — only the pool is resident between
    steps; the kernel path (``flash_decode_paged``) never materializes it
    at all."""
    n_tiles = block_tables.shape[1]

    def view(x):
        if x.ndim != 3:
            return x
        g = jnp.take(x, block_tables, axis=0, mode="fill", fill_value=0)  # (S, nt, bs, x)
        return g.reshape(g.shape[0], n_tiles * x.shape[1], x.shape[2])

    return jax.tree.map(view, pool_tree)


def scatter_step(
    pool_tree: Any,
    new_cache: Any,
    block_tables: jnp.ndarray,
    offsets: jnp.ndarray,
    *,
    num_blocks: int,
    block_size: int,
):
    """Write each slot's just-decoded cache row (position ``offsets[s]``
    of the slot view) back into its pool block.  Parked slots (offset
    past the view width) and sentinel tiles resolve to an out-of-range
    block index, so their writes drop — the paged twin of the flat
    path's ``mode="drop"`` scatter."""
    n_tiles = block_tables.shape[1]
    width = n_tiles * block_size
    rows = jnp.arange(offsets.shape[0])
    tile = jnp.clip(offsets // block_size, 0, n_tiles - 1)
    blocks = jnp.take_along_axis(block_tables, tile[:, None], axis=1)[:, 0]
    blocks = jnp.where(offsets < width, blocks, num_blocks)
    inb = offsets % block_size
    safe = jnp.clip(offsets, 0, width - 1)

    def scat(pool, flat):
        if pool.ndim != 3:
            return pool
        return pool.at[blocks, inb].set(flat[rows, safe], mode="drop")  # one row a slot

    return jax.tree.map(scat, pool_tree, new_cache)


def scatter_span(
    pool_tree: Any,
    new_cache: Any,
    block_tables: jnp.ndarray,
    offsets: jnp.ndarray,
    span: int,
    *,
    num_blocks: int,
    block_size: int,
):
    """``scatter_step`` over a contiguous span: write positions
    ``offsets[s] .. offsets[s] + span - 1`` of each slot view back into
    the slot's pool blocks — the speculative-decode verify write (the
    k+1 candidate rows land together; acceptance is mask discipline, so
    rejected rows are written-but-dark until the next span overwrites
    them).  Every position resolves through the SAME sentinel/parked
    drops as the single-step scatter: a speculative write can only land
    in a block the slot already owns, so rejection never touches the
    free-list and the prefix index never sees a speculative block."""
    out = pool_tree
    for j in range(span):
        out = scatter_step(
            out, new_cache, block_tables, offsets + j,
            num_blocks=num_blocks, block_size=block_size,
        )
    return out


def scatter_admit(
    pool_tree: Any, chunk_cache: Any, admit_blocks: jnp.ndarray, block_size: int
):
    """Copy a prefilled admission chunk's allocated tiles into the pool.

    ``chunk_cache`` leaves are (chunk, width, heads x head_dim) at the
    BUCKET width; ``admit_blocks`` is the flat (chunk × tiles,) block
    assignment with sentinel entries for tiles that must not copy
    (padding rows, the prompt-gap region).  Decode tiles DO copy — the
    chunk cache is zeros there, which scrubs whatever a freed block held
    and keeps the paged==flat bit-identity argument airtight."""

    def scat(pool, chunk):
        if chunk.ndim != 3:
            return pool
        c, lc, x = chunk.shape
        tiles = chunk.reshape(c * (lc // block_size), block_size, x)
        return pool.at[admit_blocks].set(tiles, mode="drop")

    return jax.tree.map(scat, pool_tree, chunk_cache)


# --------------------------------------------------------- byte accounting


def tree_bytes(tree: Any) -> int:
    """Static byte account of a pytree (arrays or ShapeDtypeStructs) —
    the resident-footprint number the capacity gauges and the bench's
    ``cache_bytes_per_token`` report, measured nowhere near a device."""
    total = 0
    for leaf in jax.tree.leaves(tree):
        shape = tuple(getattr(leaf, "shape", ()))
        itemsize = getattr(getattr(leaf, "dtype", None), "itemsize", 4)
        total += int(math.prod(shape)) * int(itemsize)
    return total


def block_bytes(pool_tree: Any, num_blocks: int) -> int:
    """Bytes ONE pool block accounts for across every cache leaf."""
    total = 0
    for leaf in jax.tree.leaves(pool_tree):
        if len(getattr(leaf, "shape", ())) >= 3:
            total += int(
                math.prod(leaf.shape) * leaf.dtype.itemsize
            ) // max(num_blocks, 1)
    return total
