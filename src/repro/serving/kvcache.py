"""Paged KV-cache pool: block accounting + block- or slot-resident storage.

Two physical layouts share one ledger:

* **dense** (default) — the model's ``init_cache`` tree with a
  ``max_slots`` batch axis.  Every slot owns its worst-case
  ``max_seq_len`` stripe, so the pool can never actually run dry; the
  block tables are accounting only.
* **paged** (``paged=True``) — attention KV lives in *block* storage:
  ``T.init_paged_cache``'s stacked pool, ``{"k", "v"}`` each
  ``(num_layers, num_blocks + 1, block_size, KV * D)``, the layout the
  paged kernels read and the paged programs update in place (they take
  the pool donated: a caller never keeps the ``cache`` it passed in).
  A device-resident ``(max_slots, blocks_per_slot)`` block-table
  tensor maps each slot's token positions to blocks, the decode step
  gathers K/V through it with the Pallas paged-attention kernel, and the
  pool may be sized **smaller than worst case** via the ``num_blocks``
  knob (the ``gpu_memory_utilization`` analogue) — ``OutOfBlocks``
  becomes a real, schedulable event the admission path must survive.
  The extra physical block is the *trash block*: free slots' dummy
  decode rows and unbacked table entries point there, so stray writes
  and speculative DMAs never touch a live sequence's KV.

What this module adds on top of the raw storage is the *paging layer*
a production server needs:

* ``KVBlockPool`` — a fixed budget of KV blocks (``block_size`` token
  positions each) handed out from a free list with ring-buffer semantics:
  blocks freed by a finished sequence go to the tail and are recycled from
  the head, so a retired request's memory is immediately reusable by the
  next admission.  Every block carries a *reference count* so the prefix
  cache can share one block between the radix tree and any number of
  in-flight requests: ``alloc`` hands out a block at refcount 1, ``ref`` /
  ``unref`` move it up and down, and the block returns to the free ring
  only when the count reaches zero.  ``fork`` is the copy-on-write ledger
  op: a fresh block allocated against a live source.  Double-allocation,
  double-free, unref of a dead block, and ``free`` of a shared block are
  hard errors.
* ``PagedKVCache`` — per-slot block tables mapping each live sequence to
  the blocks backing its token positions, grown one block at a time as the
  sequence decodes, plus the scatter that writes a freshly prefilled
  single-sequence cache into its slot of the pooled tree.  When built with
  ``prefix_blocks > 0`` it also owns the *prefix store*: a second
  cache-shaped tree whose batch axis indexes prefix blocks and whose
  sequence axis is one block wide, holding the KV values of cached
  prompt prefixes so a later request can load them instead of recomputing
  the prefill (``save_prefix_block`` / ``load_prefix_block`` /
  ``fork_prefix_block``).

Families without a growing attention cache (pure SSM) still run through
the same ledger: their physical state is constant-size, but the block
table models the logical KV footprint the scheduler admits against, so
occupancy telemetry is comparable across model families.  Such families
(and enc-dec models, whose decoder KV depends on the audio frames, not
the token ids alone) report ``supports_prefix_cache = False`` and skip
the prefix store entirely.
"""
from __future__ import annotations

from collections import deque
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer as T
from repro.serving.tracing import span


class OutOfBlocks(RuntimeError):
    """KV pool exhausted — admission must wait for a sequence to finish."""


class KVBlockPool:
    """Fixed-size pool of KV blocks with free-list recycling + refcounts."""

    def __init__(self, num_blocks: int, block_size: int):
        assert num_blocks > 0 and block_size > 0
        self.num_blocks = num_blocks
        self.block_size = block_size
        self._free = deque(range(num_blocks))
        self._in_use: set = set()
        self._refs: Dict[int, int] = {}
        self.high_water = 0

    @property
    def available(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return len(self._in_use)

    def alloc(self) -> int:
        if not self._free:
            raise OutOfBlocks(
                f"all {self.num_blocks} KV blocks in use")
        b = self._free.popleft()
        assert b not in self._in_use, f"block {b} double-allocated"
        self._in_use.add(b)
        self._refs[b] = 1
        self.high_water = max(self.high_water, len(self._in_use))
        return b

    # -- reference counting (prefix sharing) ---------------------------------

    def refcount(self, b: int) -> int:
        return self._refs.get(b, 0)

    def ref(self, b: int) -> int:
        """Add a reference to a live block (radix node, running request)."""
        assert b in self._in_use, f"block {b} ref'd but not allocated"
        self._refs[b] += 1
        return self._refs[b]

    def unref(self, b: int) -> int:
        """Drop one reference; at zero the block returns to the free ring."""
        assert b in self._in_use, f"block {b} unref'd but not allocated"
        assert self._refs[b] > 0, f"block {b} refcount underflow"
        self._refs[b] -= 1
        left = self._refs[b]
        if left == 0:
            del self._refs[b]
            self._in_use.remove(b)
            self._free.append(b)
        return left

    def fork(self, src: int) -> int:
        """Copy-on-write ledger op: allocate a fresh block that will hold a
        private copy of ``src`` (the caller copies the data).  ``src`` must
        be live — forking a freed block is a hard error."""
        assert src in self._in_use, f"fork of dead block {src}"
        return self.alloc()

    def free(self, blocks: List[int]) -> None:
        """Exclusive-owner release.  Freeing a block somebody else still
        references is a hard error — shared blocks go through ``unref``."""
        for b in blocks:
            assert b in self._in_use, f"block {b} freed but not allocated"
            assert self._refs[b] == 1, \
                f"block {b} freed with refcount {self._refs[b]}"
            del self._refs[b]
            self._in_use.remove(b)
            self._free.append(b)          # ring: recycled oldest-freed first


class PagedKVCache:
    """Pooled decode-cache storage + per-slot block tables.

    ``cache`` is the jitted-decode operand.  Dense mode: the model's
    cache tree with a ``max_slots`` batch axis, ``write_prefill``
    scatters a batch-1 cache (a fresh prefill) into one slot; the
    per-leaf batch-axis index is detected from the model's cache spec,
    so every family (dense, MoE, VLM, SSM, hybrid, enc-dec) works
    unmodified.  Paged mode (``paged=True``): ``cache`` is the stacked
    pool of ``T.init_paged_cache`` with ``num_blocks + 1`` blocks (axis
    1 of each leaf); prefill chunks and decode steps write straight into
    the blocks through the tables (no staging cache) and donate the pool
    they were given,
    ``device_block_tables()`` feeds the Pallas paged-attention gathers,
    and the ``num_blocks`` knob may undersize the pool below
    ``max_slots * blocks_per_slot`` (real ``OutOfBlocks``).

    With ``prefix_blocks > 0`` (and a family whose cache is positional),
    ``prefix_store`` holds block-granular KV snapshots of cached prompt
    prefixes, allocated from ``prefix_pool`` — a second ``KVBlockPool``
    whose refcounts let the radix tree and in-flight requests share them.
    """

    def __init__(self, cfg, max_slots: int, max_seq_len: int,
                 block_size: int = 16, prefix_blocks: int = 0,
                 num_blocks: Optional[int] = None, paged: bool = False):
        self.cfg = cfg
        self.max_slots = max_slots
        self.max_seq_len = max_seq_len
        self.block_size = block_size
        self.paged = paged
        self.blocks_per_slot = -(-max_seq_len // block_size)  # ceil
        worst_case = max_slots * self.blocks_per_slot
        if num_blocks is None:
            num_blocks = worst_case
        if not paged and num_blocks != worst_case:
            raise ValueError(
                "dense layout physically allocates the worst case; the "
                "num_blocks knob needs paged=True")
        if num_blocks <= 0:
            raise ValueError(f"num_blocks must be positive, got {num_blocks}")
        self.pool = KVBlockPool(num_blocks, block_size)
        # bound by the scheduler (tracing.Tracer); ledger events — block
        # alloc/grow/free and OutOfBlocks — land in the replica's trace
        self.tracer = None
        self._free_slots = deque(range(max_slots))
        self.block_table: Dict[int, List[int]] = {}
        self.seq_len_of: Dict[int, int] = {}
        self._axes = self._batch_axes(cfg, max_seq_len)
        self._seq_axes = self._seq_axis_per_leaf(cfg, max_slots)
        if paged:
            if not self.supports_prefix_cache:
                raise ValueError(
                    f"family {cfg.family!r} has a non-positional decode "
                    "cache; paged attention unsupported")
            if cfg.kv_cache_dtype == "int8":
                raise ValueError(
                    "paged attention does not support the int8 KV cache "
                    "yet — use kv_cache_dtype='bfloat16'")
            if max_seq_len % block_size != 0:
                raise ValueError(
                    f"paged mode needs max_seq_len ({max_seq_len}) to be "
                    f"a multiple of block_size ({block_size})")
            # +1 physical block: the trash block free/dummy rows write to
            self.trash_block = num_blocks
            self.cache = T.init_paged_cache(cfg, num_blocks + 1, block_size)
            self._tables = np.full((max_slots, self.blocks_per_slot),
                                   self.trash_block, np.int32)
            self._tables_dev = None
            self._masked_dev = None       # masked-table upload cache
            self._masked_key = ()
            self._save_paged = None       # built with the prefix store
        else:
            self.cache = T.init_cache(cfg, max_slots, max_seq_len)
            self._write = jax.jit(self._make_write(), donate_argnums=0)

        # -- prefix store (optional) ----------------------------------------
        self.prefix_pool: Optional[KVBlockPool] = None
        self.prefix_store = None
        if prefix_blocks > 0:
            if not self.supports_prefix_cache:
                raise ValueError(
                    f"family {cfg.family!r} has a non-positional decode "
                    "cache; prefix caching unsupported")
            self.prefix_pool = KVBlockPool(prefix_blocks, block_size)
            if paged:
                # the store shares the pool's layout: saves, loads and
                # copy-on-write forks are block-to-block copies on axis 1
                self.prefix_store = T.init_paged_cache(cfg, prefix_blocks,
                                                       block_size)
                save = self._make_save_paged()
                self._save_paged = jax.jit(save, donate_argnums=0)
                self._copy = jax.jit(
                    lambda store, src, dst: save(store, store, src, dst),
                    donate_argnums=0)
            else:
                self.prefix_store = self._init_store(prefix_blocks)
                self._save = jax.jit(self._make_save(), donate_argnums=0)
                self._load = jax.jit(self._make_load(), donate_argnums=0)
                self._copy = jax.jit(self._make_copy(), donate_argnums=0)

    # -- batch-axis detection ------------------------------------------------

    @staticmethod
    def _struct_leaves(cfg, batch, seq_len):
        is_leaf = (lambda x: isinstance(x, tuple) and len(x) == 2
                   and isinstance(x[0], tuple))
        return jax.tree.leaves(T._cache_struct(cfg, batch, seq_len),
                               is_leaf=is_leaf)

    @classmethod
    def _batch_axes(cls, cfg, seq_len: int) -> List[int]:
        """Per-leaf index of the batch axis, found by diffing the cache
        spec at batch=1 vs batch=2 (leaf order matches the cache tree)."""
        s1 = cls._struct_leaves(cfg, 1, seq_len)
        s2 = cls._struct_leaves(cfg, 2, seq_len)
        axes = []
        for (sh1, _), (sh2, _) in zip(s1, s2):
            diff = [i for i, (a, b) in enumerate(zip(sh1, sh2)) if a != b]
            assert len(diff) == 1, (sh1, sh2)
            axes.append(diff[0])
        return axes

    @classmethod
    def _seq_axis_per_leaf(cls, cfg, batch: int) -> List[Optional[int]]:
        """Per-leaf index of the token-position axis, found by diffing the
        cache spec at two sequence lengths.  ``None`` for leaves with no
        positional extent (SSM state / conv tails) — those families cannot
        be prefix-cached positionally."""
        s1 = cls._struct_leaves(cfg, batch, 8)
        s2 = cls._struct_leaves(cfg, batch, 16)
        axes: List[Optional[int]] = []
        for (sh1, _), (sh2, _) in zip(s1, s2):
            diff = [i for i, (a, b) in enumerate(zip(sh1, sh2)) if a != b]
            assert len(diff) <= 1, (sh1, sh2)
            axes.append(diff[0] if diff else None)
        return axes

    @property
    def supports_prefix_cache(self) -> bool:
        """True when every cache leaf is positional (sliceable per token)
        and the KV depends on the token ids alone — enc-dec decoder KV
        also depends on the encoder frames, so token-keyed reuse is
        unsound there."""
        return (self.cfg.family != "encdec"
                and all(ax is not None for ax in self._seq_axes))

    # -- scatter / gather programs -------------------------------------------

    def _make_write(self):
        axes = self._axes

        def write(pooled, single, slot):
            leaves_p, treedef = jax.tree.flatten(pooled)
            leaves_s = jax.tree.leaves(single)
            out = []
            for lp, ls, ax in zip(leaves_p, leaves_s, axes):
                lead = (slice(None),) * ax
                out.append(lp.at[lead + (slot,)].set(ls[lead + (0,)]))
            return jax.tree.unflatten(treedef, out)

        return write

    def _init_store(self, prefix_blocks: int):
        """Cache-shaped tree: batch axis -> prefix blocks, seq axis -> one
        block of token positions.  Dtypes match the live cache exactly, so
        a save/load roundtrip is bit-identical (int8 KV included)."""
        leaves = self._struct_leaves(self.cfg, 1, self.max_seq_len)
        treedef = jax.tree.structure(
            T.init_cache_specs(self.cfg, 1, self.max_seq_len))
        out = []
        for (shape, dtype), bax, sax in zip(leaves, self._axes,
                                            self._seq_axes):
            sh = list(shape)
            sh[bax] = prefix_blocks
            sh[sax] = self.block_size
            out.append(jnp.zeros(tuple(sh), dtype))
        return jax.tree.unflatten(treedef, out)

    def _make_save(self):
        """store <- pooled[slot, pos0:pos0+bs] at block ``bid``."""
        baxes, saxes, bs = self._axes, self._seq_axes, self.block_size

        def save(store, pooled, slot, bid, pos0):
            leaves_st, treedef = jax.tree.flatten(store)
            leaves_p = jax.tree.leaves(pooled)
            out = []
            for lst, lp, bax, sax in zip(leaves_st, leaves_p, baxes, saxes):
                piece = jax.lax.dynamic_index_in_dim(lp, slot, axis=bax,
                                                     keepdims=True)
                piece = jax.lax.dynamic_slice_in_dim(piece, pos0, bs,
                                                     axis=sax)
                starts = [jnp.int32(0)] * lst.ndim
                starts[bax] = bid
                out.append(jax.lax.dynamic_update_slice(lst, piece, starts))
            return jax.tree.unflatten(treedef, out)

        return save

    def _make_load(self):
        """dest(batch-1 cache)[0, bidx*bs : +bs] <- store[bid]."""
        baxes, saxes, bs = self._axes, self._seq_axes, self.block_size

        def load(dest, store, bid, bidx):
            leaves_d, treedef = jax.tree.flatten(dest)
            leaves_st = jax.tree.leaves(store)
            out = []
            for ld, lst, bax, sax in zip(leaves_d, leaves_st, baxes, saxes):
                piece = jax.lax.dynamic_index_in_dim(lst, bid, axis=bax,
                                                     keepdims=True)
                starts = [jnp.int32(0)] * ld.ndim
                starts[sax] = bidx * bs
                out.append(jax.lax.dynamic_update_slice(ld, piece, starts))
            return jax.tree.unflatten(treedef, out)

        return load

    def _make_copy(self):
        """store[dst] <- store[src] (the physical half of copy-on-write)."""
        baxes = self._axes

        def copy(store, src, dst):
            leaves, treedef = jax.tree.flatten(store)
            out = []
            for lst, bax in zip(leaves, baxes):
                piece = jax.lax.dynamic_index_in_dim(lst, src, axis=bax,
                                                     keepdims=True)
                starts = [jnp.int32(0)] * lst.ndim
                starts[bax] = dst
                out.append(jax.lax.dynamic_update_slice(lst, piece, starts))
            return jax.tree.unflatten(treedef, out)

        return copy

    def _make_save_paged(self):
        """prefix_store[dst] <- block_storage[src] — in paged mode a
        prefix snapshot is a straight block-to-block copy (both trees
        share the stacked pool's layout, blocks on axis 1)."""
        def save(store, storage, src, dst):
            return jax.tree.map(
                lambda lst, lbs: jax.lax.dynamic_update_index_in_dim(
                    lst, jax.lax.dynamic_index_in_dim(lbs, src, axis=1),
                    dst, axis=1),
                store, storage)

        return save

    # -- prefix-store operations ---------------------------------------------

    def save_prefix_block(self, slot: int, pos0: int,
                          into: Optional[int] = None) -> int:
        """Snapshot pooled-cache positions ``[pos0, pos0+block_size)`` of
        ``slot`` into a prefix block (freshly allocated unless ``into`` is
        given).  Returns the block id."""
        assert self.prefix_pool is not None, "prefix store not enabled"
        assert pos0 + self.block_size <= self.max_seq_len, \
            f"prefix block [{pos0}, {pos0 + self.block_size}) overruns cache"
        # ownership transfers to the PrefixCache radix tree: its node
        # release/_remove paths unref this block, not this class
        bid = self.prefix_pool.alloc() if into is None else into  # repro-lint: disable=RL005
        if self.paged:
            # aligned window == exactly one pool block of this slot
            assert pos0 % self.block_size == 0, pos0
            src = self.block_table[slot][pos0 // self.block_size]
            self.prefix_store = self._save_paged(
                self.prefix_store, self.cache, jnp.int32(src),
                jnp.int32(bid))
        else:
            self.prefix_store = self._save(
                self.prefix_store, self.cache, jnp.int32(slot),
                jnp.int32(bid), jnp.int32(pos0))
        return bid

    def load_prefix_blocks(self, cache1, blocks: Sequence[int]):
        """Copy stored prefix blocks into a batch-1 cache at their aligned
        positions (block k of the list covers ``[k*bs, (k+1)*bs)``).
        Returns the updated cache tree."""
        assert self.prefix_pool is not None, "prefix store not enabled"
        with span(self.tracer, "kv.prefix_load", blocks=len(blocks)):
            for k, bid in enumerate(blocks):
                cache1 = self._load(cache1, self.prefix_store,
                                    jnp.int32(bid), jnp.int32(k))
        return cache1

    def load_prefix_blocks_paged(self, slot: int,
                                 blocks: Sequence[int]) -> None:
        """Paged resume path: copy stored prefix blocks straight into
        ``slot``'s pool blocks (block k of the list covers positions
        ``[k*bs, (k+1)*bs)``), with no batch-1 staging cache in between.
        The slot must already back those positions (``alloc_slot`` with
        the full prompt length does)."""
        assert self.paged, "block-to-block prefix load needs paged mode"
        assert self.prefix_pool is not None, "prefix store not enabled"
        table = self.block_table[slot]
        assert len(blocks) <= len(table), (len(blocks), len(table))
        with span(self.tracer, "kv.prefix_load", blocks=len(blocks)):
            for k, bid in enumerate(blocks):
                # same block-to-block copy program as the save direction,
                # with the trees swapped: cache[table[k]] <- store[bid]
                self.cache = self._save_paged(
                    self.cache, self.prefix_store, jnp.int32(bid),
                    jnp.int32(table[k]))

    def table_row(self, slot: int) -> np.ndarray:
        """The slot's padded block-table row (unbacked entries name the
        trash block) — what a batched-prefill program row gathers through."""
        assert self.paged, "block tables are device-resident in paged mode"
        return self._tables[slot]

    def fork_prefix_block(self, src: int) -> int:
        """Copy-on-write: a private copy of a shared prefix block, so a
        diverging branch never mutates data another reader still maps."""
        assert self.prefix_pool is not None, "prefix store not enabled"
        # ownership transfers to the PrefixCache branch that requested
        # the fork; its release/_remove paths unref the copy
        dst = self.prefix_pool.fork(src)  # repro-lint: disable=RL005
        self.prefix_store = self._copy(self.prefix_store, jnp.int32(src),
                                       jnp.int32(dst))
        return dst

    # -- slot lifecycle ------------------------------------------------------

    @property
    def free_slot_count(self) -> int:
        return len(self._free_slots)

    def _blocks_for(self, n_tokens: int) -> int:
        return max(1, -(-n_tokens // self.block_size))

    def alloc_slot(self, prompt_len: int) -> int:
        """Claim a slot and the blocks backing its prompt positions.
        On block exhaustion the slot is returned and any partially
        allocated blocks are released before ``OutOfBlocks`` propagates —
        the caller sees an all-or-nothing admission."""
        if prompt_len > self.max_seq_len:
            raise ValueError(
                f"prompt ({prompt_len}) exceeds max_seq_len "
                f"({self.max_seq_len})")
        tr = self.tracer
        if not self._free_slots:
            if tr is not None and tr.enabled:
                tr.out_of_blocks("alloc_slot:no_free_slot")
            raise OutOfBlocks("no free slot")
        slot = self._free_slots.popleft()
        blocks: List[int] = []
        try:
            for _ in range(self._blocks_for(prompt_len)):
                blocks.append(self.pool.alloc())
        except OutOfBlocks:
            self.pool.free(blocks)
            self._free_slots.appendleft(slot)
            if tr is not None and tr.enabled:
                tr.out_of_blocks("alloc_slot:pool_dry", slot)
            raise
        self.block_table[slot] = blocks
        self.seq_len_of[slot] = prompt_len
        if self.paged:
            self._tables[slot, :len(blocks)] = blocks
            self._tables_dev = self._masked_dev = None
        if tr is not None and tr.enabled:
            tr.block_alloc(slot, len(blocks), self.pool.available)
        return slot

    def ensure_capacity(self, slot: int, n_tokens: int) -> None:
        """Back token positions [0, n_tokens) with blocks, growing the
        slot's table from the shared pool as decode advances."""
        tr = self.tracer
        if n_tokens > self.max_seq_len:
            raise OutOfBlocks(
                f"slot {slot}: {n_tokens} tokens exceeds max_seq_len "
                f"({self.max_seq_len})")
        table = self.block_table[slot]
        while len(table) * self.block_size < n_tokens:
            try:
                table.append(self.pool.alloc())
            except OutOfBlocks:
                if tr is not None and tr.enabled:
                    tr.out_of_blocks("decode_grow", slot)
                raise
            if self.paged:
                self._tables[slot, len(table) - 1] = table[-1]
                self._tables_dev = self._masked_dev = None
            if tr is not None and tr.enabled:
                tr.block_grow(slot, self.pool.available)
        self.seq_len_of[slot] = max(self.seq_len_of[slot], n_tokens)

    def free_slot(self, slot: int) -> None:
        """Retire a sequence: its blocks go straight back on the ring."""
        blocks = self.block_table.pop(slot)
        self.pool.free(blocks)
        del self.seq_len_of[slot]
        self._free_slots.append(slot)
        if self.paged:
            self._tables[slot, :] = self.trash_block
            self._tables_dev = self._masked_dev = None
        tr = self.tracer
        if tr is not None and tr.enabled:
            tr.block_free(slot, len(blocks), self.pool.available)

    def device_block_tables(self, mask_slots: Sequence[int] = ()
                            ) -> jnp.ndarray:
        """The (max_slots, blocks_per_slot) int32 block-table tensor the
        paged decode step gathers through; uploaded lazily after ledger
        mutations.  Unbacked entries name the trash block.

        ``mask_slots`` re-routes those slots' rows to the trash block —
        the decode step passes the mid-prefill slots here so their
        dummy decode rows can never touch KV the prefill already wrote.
        The masked upload is cached too (keyed by the mask), so steady
        interleaved decode pays one host-to-device transfer per ledger
        or mask change, not one per step."""
        assert self.paged, "block tables are device-resident in paged mode"
        key = tuple(sorted(mask_slots))
        if not key:
            if self._tables_dev is None:
                self._tables_dev = jnp.asarray(self._tables)
            return self._tables_dev
        if self._masked_dev is None or self._masked_key != key:
            masked = self._tables.copy()
            masked[list(key), :] = self.trash_block
            self._masked_dev = jnp.asarray(masked)
            self._masked_key = key
        return self._masked_dev

    def write_prefill(self, slot: int, single_cache) -> None:
        """Scatter a batch-1 prefilled cache into ``slot``'s stripe of
        the dense storage.  Paged prefill never stages a batch-1 cache —
        chunks land straight in pool blocks (see ``T.prefill_step``)."""
        assert not self.paged, (
            "paged prefill writes chunks straight into pool blocks; "
            "there is no batch-1 cache to scatter")
        self.cache = self._write(self.cache, single_cache,
                                 jnp.asarray(slot, jnp.int32))

    # -- telemetry -----------------------------------------------------------

    def kv_bytes(self) -> int:
        """Physical bytes resident for the decode KV storage (the number
        the paged/dense benchmark holds fixed while varying concurrency)."""
        return sum(leaf.nbytes for leaf in jax.tree.leaves(self.cache))

    def occupancy(self) -> Dict[str, float]:
        occ = {
            "slots_in_use": self.max_slots - len(self._free_slots),
            "max_slots": self.max_slots,
            "blocks_in_use": self.pool.in_use,
            "blocks_total": self.pool.num_blocks,
            "block_high_water": self.pool.high_water,
            "block_utilization": self.pool.in_use / self.pool.num_blocks,
            "paged": self.paged,
            "kv_bytes_resident": self.kv_bytes(),
        }
        if self.prefix_pool is not None:
            occ["prefix_blocks_in_use"] = self.prefix_pool.in_use
            occ["prefix_blocks_total"] = self.prefix_pool.num_blocks
        return occ
