"""Paged KV-cache block pool: host-side allocator + device-side ops.

The fixed (n_slots, S) slot cache pays for the worst case twice: HBM holds
S rows per slot even when the mean sequence is a tenth of that, and a
prompt shared by a thousand requests is prefilled a thousand times. The
vLLM treatment (PagedAttention; PAPERS.md) fixes both with one level of
indirection: KV rows live in fixed-size BLOCKS drawn from a global pool,
each sequence owns an ordered list of block ids (its *block table*), and
immutable full blocks are content-addressed so identical prompt prefixes
resolve to the *same* physical blocks.

Three layers, smallest first:

* **Device ops** (`paged_update`, `paged_gather`): a pool is a plain jax
  array (n_blocks, bs, ...); a token write is a 2-index scatter through
  the block table (the paged generalization of models/attention.py's O(1)
  ring write), a logical view for the naive/einsum attention paths is one
  advanced-indexing gather — the same bytes the slot cache streamed. The
  flash path skips the gather entirely: ops/flash_decode.py's paged kernel
  DMAs blocks straight from the pool through a block-table scalar
  prefetch. Physical block 0 is the NULL block: retired slots' table rows
  are zeroed, so the fused step's unavoidable dead-slot write lands in a
  row nothing ever reads — the paged replacement for "masked until the
  next occupant overwrites".

  **One layout for a k/v pool** (`kv_lanes`): a float GQA-family pool is
  (n_blocks, bs, L) with the kv heads MERGED into one lane axis, L =
  n_kv * head_size rounded up to a multiple of 128. The TPU compiler
  lays a donated entry parameter out in the dimension order that pads
  nothing, XLA's in-place scatter wants the written window minor-most,
  and a Pallas operand is row-major: only a shape whose minor-most
  dimension already fills whole 128-lane tiles gets the same dense
  row-major layout from all three, so the write happens in place and no
  whole-pool `copy` stands between the donated argument, the scatter and
  the kernel (a (.., 25, 64) pool paid two such copies per pool per step,
  104 of a 124.6 ms step at gpt2-xl). `paged_update` flattens a row's
  heads into lanes and zero-pads them on the way in, `paged_gather`
  slices and reshapes on the way out; the indices are the same. Pad
  lanes stay zero and no head's slice reads them. Leaves whose trailing
  shape already equals the rows' (MLA latents, the int8 codes and their
  scale sidecars, which keep (n_blocks, bs, n_kv, ...); a latent layer's
  rows, which come in whole tiles: ops/latent_attention.py) pass through
  unchanged.
* **`BlockPool`**: free-list allocator with per-block refcounts. Blocks
  referenced by live sequences can be shared (a reused prefix); blocks at
  refcount 0 that are *registered* in the prefix index are retained on an
  LRU instead of freed — `alloc()` evicts the oldest only when the free
  list is dry, so HBM that would sit idle caches prefixes for free.
  `alloc()` returning None (everything referenced) is the engine's
  preemption trigger.
* **Prefix index** (`lookup`/`register`): content-addressed full blocks
  keyed by the CHAIN (parent_digest, block_tokens) — a flattened radix
  tree: the parent's ancestry is folded into a fixed-size digest (so a
  key hashes in O(block_size), not O(prefix)); looking up a prompt walks
  key-by-key from the root, so a hit at depth d proves the whole d-block
  prefix matches and an evicted ancestor automatically unreaches its
  descendants (they age out of the LRU).
  Only FULL blocks are ever registered; the partial tail of a sequence is
  always private — sharing is copy-on-write at block granularity (a fork
  allocates a fresh tail block instead of appending to a shared one).

Everything host-side is plain Python on the engine's single thread — the
allocator is bookkeeping, never a device sync.
"""

from __future__ import annotations

import collections
import hashlib
from typing import Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

#: physical block 0 is never allocated: zeroed table rows route dead-slot
#: writes here (see module docstring)
NULL_BLOCK = 0


class NoFreeBlocks(RuntimeError):
    """The pool has no free or evictable block — every block is referenced
    by a live sequence. At admission this means "stay queued"; during
    decode the engine preempts a victim instead."""


# ---------------------------------------------------------------------------
# device-side paged-cache ops
# ---------------------------------------------------------------------------

def kv_lanes(n_kv_heads: int, head_size: int) -> int:
    """Lane width L of a merged k/v pool leaf (module docstring): the kv
    heads side by side, rounded up to whole 128-lane tiles."""
    return -(-n_kv_heads * head_size // 128) * 128


def merge_heads(rows: jnp.ndarray, lanes: int) -> jnp.ndarray:
    """(a, b, n_kv, hs) rows -> (a, b, lanes): the heads side by side in
    one lane axis, zero-padded to `lanes` (`kv_lanes`) — a merged-lane
    pool's row format."""
    flat = rows.reshape(rows.shape[:2] + (-1,))
    return jnp.pad(flat, ((0, 0), (0, 0), (0, lanes - flat.shape[2])))


def paged_update(pool: jnp.ndarray, new: jnp.ndarray, pos,
                 block_tables: jnp.ndarray) -> jnp.ndarray:
    """Write `new` (B, T, ...) rows into the (n_blocks, bs, ...) pool at
    logical positions [pos, pos+T) of each sequence, addressed through
    `block_tables` (B, max_blocks) int32. A merged-lane pool
    (n_blocks, bs, L) takes (B, T, n_kv, hs) rows: heads flattened into
    lanes, zero-padded to L (module docstring). Rows whose trailing shape
    IS the pool's are written as they are: the int8 codes and sidecars,
    the classic MLA's latents, and a latent layer's rows (B, T, L), which
    have no head axis: `[c | rope(k_r) | 0]`, the normed key/value latent,
    the one rotated key head all query heads share, and zeros up to whole
    128-lane tiles, laid out by the caller
    (ops/latent_attention.py `cache_rows`).

    Three shapes, mirroring `_update_cache`'s prefill/decode split plus
    the spec-verify short window:
    * T == 1 (fused decode step): `pos` is per-sequence (B,); one 2-index
      scatter writes every live slot's row. Tail blocks are never shared,
      so concurrent writers cannot collide (dead slots all land in the
      null block — harmless, nothing reads it).
    * T > 1 with per-sequence (B,) `pos` (speculative verify): each slot
      writes T = K+1 consecutive rows starting at its own offset. The
      window is unrolled into T per-slot scatters; a row whose table
      index would run off the table routes to the null block, so the
      traced program is safe for any pos without a bounds retrace.
    * T > 1 with scalar `pos` (bucketed prefill): B == 1, `pos`
      block-aligned (the reused-prefix length), T a multiple of the block
      size; whole blocks are scattered in one shot. Pad rows land in
      blocks private to this sequence and are causally masked exactly as
      in the slot cache.
    """
    new = new.astype(pool.dtype)
    if new.shape[2:] != pool.shape[2:]:         # a merged-lane pool
        assert pool.ndim == 3, (new.shape, pool.shape)
        new = merge_heads(new, pool.shape[2])
    B, T = new.shape[:2]
    bs = pool.shape[1]
    if T == 1:
        p = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
        blk = jnp.take_along_axis(block_tables, (p // bs)[:, None],
                                  axis=1)[:, 0]
        return pool.at[blk, p % bs].set(new[:, 0], mode="drop")
    if jnp.asarray(pos).ndim >= 1:
        # spec-verify window: per-slot start offsets, T small (K+1)
        p = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
        W = block_tables.shape[1]
        for i in range(T):
            pi = p + i
            q = pi // bs
            blk = jnp.take_along_axis(
                block_tables, jnp.minimum(q, W - 1)[:, None], axis=1)[:, 0]
            blk = jnp.where(q < W, blk, NULL_BLOCK)
            pool = pool.at[blk, pi % bs].set(new[:, i], mode="drop")
        return pool
    assert B == 1, "paged prefill writes one sequence at a time"
    assert T % bs == 0, f"prefill length {T} not a multiple of block {bs}"
    p0 = jnp.asarray(pos, jnp.int32).reshape(())
    nblk = T // bs
    blks = jax.lax.dynamic_slice(block_tables[0], (p0 // bs,), (nblk,))
    vals = new[0].reshape((nblk, bs) + new.shape[2:])
    return pool.at[blks].set(vals, mode="drop")


def paged_gather(pool: jnp.ndarray, block_tables: jnp.ndarray,
                 row_shape: Optional[tuple] = None) -> jnp.ndarray:
    """Materialize the logical (B, max_blocks*bs, ...) view of each
    sequence's cache for the naive/einsum attention paths. `row_shape`
    (n_kv, hs) undoes a merged-lane pool's flattening (`paged_update`):
    the pad lanes are sliced off and the heads split back out. Rows past
    a sequence's extent map through null/stale blocks and carry garbage —
    exactly like the slot cache's retired rows, they are causally masked
    to weight 0.0 before they can touch the output."""
    B, n_max = block_tables.shape
    g = pool[block_tables]                      # (B, n_max, bs, ...)
    g = g.reshape((B, n_max * pool.shape[1]) + pool.shape[2:])
    if row_shape is None or tuple(row_shape) == pool.shape[2:]:
        return g
    assert pool.ndim == 3, (row_shape, pool.shape)
    return g[..., :int(np.prod(row_shape))].reshape(
        g.shape[:2] + tuple(row_shape))


# ---------------------------------------------------------------------------
# host-side allocator + prefix index
# ---------------------------------------------------------------------------

#: ancestry digest of the empty prefix (the radix root)
ROOT_DIGEST = b"\x00" * 16


def _child_digest(parent: bytes, block: tuple) -> bytes:
    h = hashlib.blake2b(parent, digest_size=16)
    # host-side chain-key hashing over concrete python ints — never traced
    h.update(np.asarray(block, np.int64).tobytes())  # lint: allow(host-sync)
    return h.digest()


def chain_keys(tokens, block_size: int, n_blocks: int,
               parent=ROOT_DIGEST) -> list:
    """Chain keys for the first `n_blocks` FULL blocks of `tokens`:
    key_i = (digest_{i-1}, tokens of block i), where digest_i folds
    block i into its parent's digest. The digest stands in for the whole
    ancestry, so a key encodes the prefix up to and including its block
    (equal keys imply equal content at equal positions, up to blake2b
    collisions) while hashing in O(block_size) — the naive nested-tuple
    key made one admission's lookup+register pass O(n^2 * block_size)
    host-side for an n-block prompt."""
    keys = []
    for i in range(n_blocks):
        block = tuple(int(t) for t in tokens[i * block_size:(i + 1) * block_size])
        keys.append((parent, block))
        parent = _child_digest(parent, block)
    return keys


class BlockPool:
    """Refcounted block allocator with an LRU prefix cache.

    Block states (disjoint):
    * free        — on the free list, content garbage;
    * referenced  — refcount >= 1 live sequences own it (possibly shared);
    * cached      — refcount 0 but registered in the prefix index: content
                    retained, evictable LRU-first when the free list runs
                    dry.

    The null block (id 0) is reserved and never enters any state.
    """

    def __init__(self, n_blocks: int, block_size: int):
        assert n_blocks >= 2, "pool needs the null block plus one real one"
        self.n_blocks = n_blocks
        self.block_size = block_size
        self._free: collections.deque[int] = collections.deque(
            range(1, n_blocks))
        self._ref: dict[int, int] = {}           # block -> refcount (>= 1)
        self._key_of: dict[int, tuple] = {}      # registered block -> key
        self._index: dict[tuple, int] = {}       # chain key -> block
        self._lru: collections.OrderedDict[int, None] = \
            collections.OrderedDict()            # cached blocks, oldest first
        # eviction hook: called as on_evict(blk, key) the moment a cached
        # block is about to be recycled, BEFORE its contents are
        # overwritten — ops/kv_tier.py demotes the block to host RAM
        # here. None (default) keeps plain drop-at-eviction semantics.
        self.on_evict = None
        # lifetime counters (engine metrics read these)
        self.n_evicted = 0
        self.n_allocs = 0

    # -- capacity accounting -------------------------------------------
    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_cached(self) -> int:
        return len(self._lru)

    @property
    def n_referenced(self) -> int:
        return len(self._ref)

    @property
    def capacity(self) -> int:
        """Allocatable blocks (the null block is not one)."""
        return self.n_blocks - 1

    @property
    def utilization(self) -> float:
        """Referenced fraction of the pool (cached blocks are reclaimable,
        so they don't count as used)."""
        return self.n_referenced / self.capacity if self.capacity else 0.0

    # -- alloc / free ---------------------------------------------------
    def alloc(self) -> Optional[int]:
        """A fresh private block (refcount 1), evicting the LRU cached
        block when the free list is empty. None when every block is
        referenced — the caller preempts or stays queued."""
        if self._free:
            blk = self._free.popleft()
        elif self._lru:
            blk, _ = self._lru.popitem(last=False)   # oldest cached
            key = self._key_of.pop(blk)
            self._index.pop(key, None)
            self.n_evicted += 1
            if self.on_evict is not None:
                # second-tier demotion: the block is refcount-0 and its
                # contents still intact — the hook copies them out before
                # this alloc's owner overwrites the rows
                self.on_evict(blk, key)
        else:
            return None
        self._ref[blk] = 1
        self.n_allocs += 1
        return blk

    def alloc_many(self, n: int) -> Optional[list[int]]:
        """n fresh blocks or None (all-or-nothing: a partial admission
        would leak refs)."""
        got: list[int] = []
        for _ in range(n):
            blk = self.alloc()
            if blk is None:
                for b in got:
                    self.release(b)
                return None
            got.append(blk)
        return got

    def ref(self, blk: int) -> None:
        """Take a reference on a cached or already-referenced block (a
        prefix hit sharing it with a new sequence)."""
        if blk in self._ref:
            self._ref[blk] += 1
            return
        assert blk in self._lru, f"block {blk} is neither live nor cached"
        del self._lru[blk]
        self._ref[blk] = 1

    def release(self, blk: int) -> None:
        """Drop one reference. At refcount 0 a registered block is
        retained on the LRU (prefix cache); an unregistered one goes back
        to the free list."""
        n = self._ref[blk] - 1
        if n:
            self._ref[blk] = n
            return
        del self._ref[blk]
        if blk in self._key_of:
            self._lru[blk] = None                # most-recently released
        else:
            self._free.append(blk)

    def release_all(self, blocks: Iterable[int]) -> None:
        """Release a sequence's blocks tail-first, so when eviction comes
        the deepest (least shareable) blocks go before their ancestors —
        the chain walk needs ancestors to reach descendants at all."""
        for blk in reversed(list(blocks)):
            self.release(blk)

    # -- prefix index ---------------------------------------------------
    def lookup(self, key: tuple) -> Optional[int]:
        """Block holding this chain key's content, or None. Touches the
        LRU so a hit streak keeps a hot prefix resident."""
        blk = self._index.get(key)
        if blk is not None and blk in self._lru:
            self._lru.move_to_end(blk)
        return blk

    def register(self, blk: int, key: tuple) -> None:
        """Publish a full, immutable, referenced block under its chain
        key. First writer wins: a concurrent identical prefill keeps its
        private copy unregistered (it frees normally on release)."""
        if key in self._index or blk in self._key_of:
            return
        assert blk in self._ref, "only referenced blocks can be registered"
        self._index[key] = blk
        self._key_of[blk] = key
