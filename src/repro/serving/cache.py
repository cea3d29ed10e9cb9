"""Slot- and page-indexed KV/state caches for continuous batching.

``SlotCache`` reuses the exact layouts of ``models.init_caches``: every leaf
is stacked ``(num_periods, num_slots, ...)``, so slot s of the engine IS
batch row s of the decode step — admitting a sequence writes one batch row,
retiring it restores that row to its init value.  ``insert`` takes
decode-ready caches produced by ``models.prefill`` (same structure, any
batch size) and copies one or more rows into slots in a single
gather/scatter; ``evict`` resets a slot from a kept blank template (NOT
zeros: mLSTM/sLSTM stabilizer state inits to -1e30, so a zero reset would
corrupt a reused slot).

``PagedSlotCache`` replaces the fixed ``max_len`` stripe per slot with a
vLLM-style paged layout: attention K/V live in a global block pool
(``models.init_paged_caches``) carved into ``page_size``-token blocks, and
each slot holds a ``(max_pages,)`` row of an int32 page table mapping its
logical pages to physical blocks.  A :class:`PageAllocator` free-list hands
blocks out; block 0 is a reserved scratch block that unmapped table entries
(and idle decode rows) point at, so the compiled decode step needs no
branches.  Short sequences then cost pages proportional to their actual
length instead of a whole ``max_len`` stripe — the *token budget*, not the
slot width, bounds memory.  Recurrent/conv state is O(1) per sequence and
stays slot-indexed in both layouts.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Sequence as TypingSequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import init_caches, init_paged_caches
from repro.serving.utils import next_pow2


def _make_tail_scatter(attn_flags: tuple[bool, ...]):
    """Compiled tail-K/V scatter shared by both cache layouts:
    ``data[i][k|v][:, a_idx[n], b_idx[n]] = caches[i][k|v][:, r_idx[n],
    t_idx[n]]`` for every attention period i.  Eagerly this is a traced
    gather + scatter per period per key — tens of dispatches of pure
    Python overhead on the speculative-commit hot path (every verify
    round scatters its accepted tail); jitted it is one fused program.
    Callers pad the index vectors to a power of two by REPEATING the
    last entry (duplicate scatter indices carrying identical payloads
    are deterministic), so compiled variants stay O(log max batch)."""

    @jax.jit
    def scatter(data, caches, r_idx, t_idx, a_idx, b_idx):
        new = []
        for i, is_attn in enumerate(attn_flags):
            if is_attn:
                entry = {}
                for key in ("k", "v"):
                    dst = data[i][key]
                    src = caches[i][key][:, r_idx, t_idx]
                    entry[key] = dst.at[:, a_idx, b_idx].set(
                        src.astype(dst.dtype))
                new.append(entry)
            else:
                new.append(data[i])
        return tuple(new)

    return scatter


def _pad_pow2(*columns):
    """Pad parallel index lists to the next power of two by repeating
    their last entry; returns int32 arrays (see _make_tail_scatter)."""
    n = len(columns[0])
    pad = next_pow2(n)
    return tuple(np.asarray(col + [col[-1]] * (pad - n), np.int32)
                 for col in columns)


class PoolExhausted(MemoryError):
    """The block pool cannot satisfy an allocation right now.

    Subclasses MemoryError (the allocator's historical contract) but is
    RECOVERABLE: under overcommit the engine catches it, reclaims pages
    (trie eviction, then preemption of the youngest running sequence) and
    retries.  ``shortfall`` is how many pages short the request fell —
    what a reclaim pass must free for the same request to succeed.
    """

    def __init__(self, requested: int, free: int, total: int):
        super().__init__(
            f"asked for {requested} pages but only {free} of {total} are free")
        self.requested = int(requested)
        self.free = int(free)
        self.shortfall = int(requested) - int(free)


def host_copy(x: jax.Array) -> jax.Array:
    """Device -> pinned host memory for swap-out, keeping ``x``'s sharding
    (each device's shard lands in its host's pinned memory, so the restore
    is a DMA straight back into place)."""
    return jax.block_until_ready(jax.device_put(
        x, x.sharding.with_memory_kind("pinned_host")))


def _check_slots(slots: TypingSequence[int], num_slots: int) -> None:
    """Slot indices must be unique and in range (shared by both caches)."""
    bad = [s for s in slots if not 0 <= int(s) < num_slots]
    if bad:
        raise IndexError(f"slots {bad} out of range [0, {num_slots})")
    if len(set(int(s) for s in slots)) != len(slots):
        raise ValueError(f"duplicate slots in {list(slots)}")


class SlotCache:
    """Decode caches for ``num_slots`` fixed slots of length ``max_len``.

    ``shardings`` (a pytree of NamedSharding matching the cache layout,
    e.g. ``to_named(mesh, partition_caches(...))``) places the slot axis
    over the mesh's data axis and heads/features over the model axis;
    insert/evict then re-commit their results so the decode step's
    ``in_shardings`` never trigger a per-step reshard.
    """

    def __init__(self, cfg: ModelConfig, num_slots: int, max_len: int,
                 shardings=None):
        self.cfg = cfg
        self.num_slots = num_slots
        self.max_len = max_len
        self.shardings = shardings
        self._tail_scatter = None  # built lazily on first write_tails
        self.data = init_caches(cfg, num_slots, max_len)
        # blank single-slot template used to restore evicted slots
        self._blank = init_caches(cfg, 1, max_len)
        if shardings is not None:
            self.data = jax.device_put(self.data, shardings)
            # the blank template is tiny: replicate it across the mesh so
            # evict never pulls it from a single device
            self._blank = jax.device_put(self._blank, jax.tree.map(
                lambda s: jax.sharding.NamedSharding(
                    s.mesh, jax.sharding.PartitionSpec()), shardings))

    def _commit(self) -> None:
        if self.shardings is not None:
            self.data = jax.device_put(self.data, self.shardings)

    # ----------------------------------------------------------- insert --
    def insert(self, slots: TypingSequence[int], caches,
               rows: TypingSequence[int] | None = None) -> None:
        """Copy batch rows of ``caches`` (shaped like init_caches(cfg, B,
        max_len), e.g. from models.prefill) into ``slots``.  ``rows``
        defaults to 0..len(slots)-1."""
        if rows is None:
            rows = list(range(len(slots)))
        if len(rows) != len(slots):
            raise ValueError(f"{len(slots)} slots vs {len(rows)} rows")
        self._check_slots(slots)
        s_idx = jnp.asarray(list(slots), jnp.int32)
        r_idx = jnp.asarray(list(rows), jnp.int32)
        self.data = jax.tree.map(
            lambda dst, src: dst.at[:, s_idx].set(
                jnp.take(src, r_idx, axis=1).astype(dst.dtype)),
            self.data, caches)
        self._commit()

    # ------------------------------------------------------------ evict --
    def evict(self, slots: TypingSequence[int]) -> None:
        """Restore ``slots`` to their init state so they can be reused
        bit-exactly by the next insert."""
        self._check_slots(slots)
        s_idx = jnp.asarray(list(slots), jnp.int32)
        self.data = jax.tree.map(
            lambda dst, blank: dst.at[:, s_idx].set(
                jnp.broadcast_to(blank[:, 0:1],
                                 blank.shape[:1] + (len(slots),)
                                 + blank.shape[2:])),
            self.data, self._blank)
        self._commit()

    # ------------------------------------------------------ tail scatter --
    def write_tails(self, slots: TypingSequence[int], caches,
                    starts: TypingSequence[int],
                    lengths: TypingSequence[int],
                    rows: TypingSequence[int] | None = None) -> None:
        """Scatter tail K/V rows into the fixed stripes — the fixed-slot
        mirror of :meth:`PagedSlotCache.write_tails` (same signature, no
        mapping step: a stripe always backs every position).  ``caches`` is
        a per-period tuple of ``{"k", "v"}`` leaves shaped ``(P, B, S_tail,
        Hkv, hd)`` (from ``models.prefill_with_past``); row ``rows[j]``'s
        tail index t holds sequence position ``starts[j] + t``, and
        positions [``starts[j]``, ``lengths[j]``) are written.  Attention
        entries only — recurrent entries are left untouched (the callers
        are attention-only paths)."""
        if rows is None:
            rows = list(range(len(slots)))
        if len(rows) != len(slots) or len(starts) != len(slots) \
                or len(lengths) != len(slots):
            raise ValueError(
                f"{len(slots)} slots vs {len(rows)} rows / "
                f"{len(starts)} starts / {len(lengths)} lengths")
        self._check_slots(slots)
        row_sel, tail_sel, slot_sel, pos_sel = [], [], [], []
        for r, s, st, ln in zip(rows, slots, starts, lengths):
            if not 0 <= int(st) < int(ln) <= self.max_len:
                raise ValueError(f"slot {s}: tail [{st}, {ln}) out of range "
                                 f"(0, {self.max_len}]")
            for pos in range(int(st), int(ln)):
                row_sel.append(int(r))
                tail_sel.append(pos - int(st))
                slot_sel.append(int(s))
                pos_sel.append(pos)
        r_idx, t_idx, s_idx, p_idx = _pad_pow2(
            row_sel, tail_sel, slot_sel, pos_sel)
        if self._tail_scatter is None:
            self._tail_scatter = _make_tail_scatter(
                tuple(m == "attn" for m, _ in self.cfg.pattern))
        self.data = self._tail_scatter(
            self.data, caches, r_idx, t_idx, s_idx, p_idx)
        self._commit()

    # ------------------------------------------------------------ views --
    def slot_view(self, slot: int):
        """One slot's caches as a batch-of-1 pytree (test/debug helper)."""
        return jax.tree.map(lambda x: x[:, slot:slot + 1], self.data)

    def nbytes(self) -> int:
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(self.data))

    def _check_slots(self, slots: TypingSequence[int]) -> None:
        _check_slots(slots, self.num_slots)


class PageAllocator:
    """Refcounted free-list allocator over the KV block pool.

    Physical block ids run 1..num_pages — block 0 is the reserved scratch
    block that unmapped page-table entries point at and is never handed
    out.  Every live block carries a reference count: ``alloc`` hands it
    out at count 1, ``share`` adds a reader (a second slot mapping the
    block, or the prefix trie adopting it), and ``release`` drops one —
    the block only returns to the free list when its count hits 0, so an
    abort/evict of one reader can never free a block another reader still
    maps.  Conservation is checked on every transition: each block is
    either free or live (counted once no matter how many references it
    holds), never both and never neither, so ``num_free + num_live ==
    num_pages`` always — the shared-page form of ``free + Σ unique-mapped
    = total``.  A release of a block that is not live raises instead of
    silently corrupting two sequences.
    """

    def __init__(self, num_pages: int):
        if num_pages < 1:
            raise ValueError(f"num_pages must be >= 1, got {num_pages}")
        self.num_pages = num_pages
        # stack of free block ids; reversed so pop() hands out block 1 first
        self._free: list[int] = list(range(1, num_pages + 1))[::-1]
        self._refs: dict[int, int] = {}

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_live(self) -> int:
        """UNIQUE live blocks (each counted once however many refs it has)."""
        return len(self._refs)

    def refcount(self, page: int) -> int:
        """Current reference count of ``page`` (0 if free)."""
        return self._refs.get(int(page), 0)

    def alloc(self, n: int) -> list[int]:
        """Take ``n`` blocks off the free list at refcount 1; raises
        :class:`PoolExhausted` (a MemoryError) when the pool cannot satisfy
        the request (nothing is partially allocated) — recoverable under
        overcommit, where the engine reclaims pages and retries."""
        if n < 0:
            raise ValueError(f"cannot allocate {n} pages")
        if n > len(self._free):
            raise PoolExhausted(n, len(self._free), self.num_pages)
        out = [self._free.pop() for _ in range(n)]
        for p in out:
            self._refs[p] = 1
        self._check()
        return out

    def share(self, pages: TypingSequence[int]) -> None:
        """Add one reference to each live block in ``pages``."""
        pages = self._validated(pages, "share")
        for p in pages:
            self._refs[p] += 1
        self._check()

    def release(self, pages: TypingSequence[int]) -> None:
        """Drop one reference from each block; a block returns to the free
        list only when its count hits 0."""
        pages = self._validated(pages, "release")
        for p in pages:
            self._refs[p] -= 1
            if self._refs[p] == 0:
                del self._refs[p]
                self._free.append(p)
        self._check()

    def free(self, pages: TypingSequence[int]) -> None:
        """Alias of :meth:`release` — every free is a refcounted release,
        so single-owner callers keep their exact pre-refcount semantics."""
        self.release(pages)

    def _validated(self, pages: TypingSequence[int], what: str) -> list[int]:
        pages = [int(p) for p in pages]
        if len(set(pages)) != len(pages):
            raise ValueError(f"duplicate pages in {what}: {pages}")
        for p in pages:
            if p not in self._refs:
                raise ValueError(f"page {p} is not allocated (double free?)")
        return pages

    def _check(self) -> None:
        free = set(self._free)
        assert len(free) == len(self._free), "duplicate block on free list"
        assert not (free & self._refs.keys()), "block both free and live"
        assert len(free) + len(self._refs) == self.num_pages, (
            "block count not conserved")
        assert all(c >= 1 for c in self._refs.values()), (
            "live block with refcount < 1")


@dataclasses.dataclass(frozen=True)
class SwapState:
    """Host-side copy of one preempted slot: per-period leaves (``{"k",
    "v"}`` arrays shaped ``(P, num_pages, page_size, ...)`` for attention
    periods, the full slot-state pytree sliced to batch 1 otherwise) plus
    how many pages were mapped when the sequence was swapped out."""

    blocks: tuple
    num_pages: int

    def nbytes(self) -> int:
        return sum(x.nbytes for x in jax.tree.leaves(self.blocks))


class PagedSlotCache:
    """Decode caches for ``num_slots`` slots over a paged KV block pool.

    Attention K/V leaves hold ``num_pages`` usable blocks of ``page_size``
    tokens (plus the scratch block 0); ``table`` is the host-side
    ``(num_slots, max_pages)`` int32 page table the compiled decode step
    consumes (0 = unmapped).  ``insert`` maps just enough pages to cover a
    sequence's prompt and scatters the dense prefill rows into them;
    ``ensure_mapped`` grows a slot's table one block at a time as decode
    crosses page boundaries; ``evict`` drops one allocator reference per
    mapped page (returning private pages, keeping shared ones live) and
    restores the slot-indexed recurrent state from the blank template.
    ``map_prefix``/``cow_block``/``alloc_tail``/``write_tails`` are the
    prefix-cache entry points: map already-written shared blocks read-only
    into a fresh slot, copy-on-write the first divergent or partially
    filled block, and scatter a tail prefill into the private remainder.  Freed blocks are NOT zeroed: every valid position of a
    reused block is fully overwritten by the next insert/decode writes,
    and stale positions beyond a sequence's current length are masked to
    NEG_INF by the decode validity mask — reuse stays bit-exact.

    ``shardings`` places the pool's block axis over the mesh's data axis
    (page table stays replicated host state), mirroring ``SlotCache``.
    """

    def __init__(self, cfg: ModelConfig, num_slots: int, max_len: int,
                 num_pages: int, page_size: int, shardings=None):
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.cfg = cfg
        self.num_slots = num_slots
        self.max_len = max_len
        self.page_size = page_size
        self.num_pages = num_pages
        self.max_pages = math.ceil(max_len / page_size)
        self.allocator = PageAllocator(num_pages)
        self.table = np.zeros((num_slots, self.max_pages), np.int32)
        self.shardings = shardings
        self._attn = [m == "attn" for m, _ in cfg.pattern]
        self._tail_scatter = None  # built lazily on first write_tails
        self.data = init_paged_caches(cfg, num_slots, num_pages + 1, page_size)
        # blank single-slot template for the slot-indexed (recurrent) leaves
        self._blank = init_caches(cfg, 1, 1)
        if shardings is None:
            # committed from the start: a swap-in restores committed leaves
            # (they come back from pinned host memory to a named device), so
            # an uncommitted pool would reach the decode step with a new
            # compile key after the first restore
            self.data = jax.device_put(self.data, jax.devices()[0])
        else:
            self.data = jax.device_put(self.data, shardings)
            self._blank = jax.device_put(self._blank, jax.tree.map(
                lambda s: jax.sharding.NamedSharding(
                    s.mesh, jax.sharding.PartitionSpec()), shardings))

    def _commit(self) -> None:
        if self.shardings is not None:
            self.data = jax.device_put(self.data, self.shardings)

    # ----------------------------------------------------------- insert --
    def insert(self, slots: TypingSequence[int], caches,
               lengths: TypingSequence[int],
               rows: TypingSequence[int] | None = None) -> None:
        """Admit prefilled sequences: map ``ceil(length / page_size)`` blocks
        per slot, scatter the dense ``models.prefill`` rows (shaped like
        ``init_caches(cfg, B, max_len)``) into them, and copy the
        slot-indexed recurrent leaves.  ``rows`` defaults to
        0..len(slots)-1."""
        if rows is None:
            rows = list(range(len(slots)))
        if len(rows) != len(slots) or len(lengths) != len(slots):
            raise ValueError(
                f"{len(slots)} slots vs {len(rows)} rows / "
                f"{len(lengths)} lengths")
        self._check_slots(slots)
        for s, n in zip(slots, lengths):
            if not 0 < int(n) <= self.max_len:
                raise ValueError(f"slot {s}: length {n} out of (0, "
                                 f"{self.max_len}]")
            if self.table[s].any():
                raise ValueError(f"slot {s} still holds mapped pages; "
                                 "evict before reinserting")
        done: list[int] = []
        try:
            for s, n in zip(slots, lengths):
                need = math.ceil(int(n) / self.page_size)
                self.table[s, :need] = self.allocator.alloc(need)
                done.append(s)
        except MemoryError:
            # roll the partial batch back: no slot keeps mapped-but-unwritten
            # pages after a failed insert
            for s in done:
                self.allocator.release(
                    self.table[s][self.table[s] > 0].tolist())
                self.table[s] = 0
            raise

        s_idx = jnp.asarray(list(slots), jnp.int32)
        r_idx = jnp.asarray(list(rows), jnp.int32)
        # destination blocks for every (row, logical page); unmapped pages
        # land in scratch block 0, whose contents nothing ever gathers
        dst = jnp.asarray(self.table[list(slots)].reshape(-1), jnp.int32)
        pad_to = self.max_pages * self.page_size

        new = []
        for i, is_attn in enumerate(self._attn):
            if is_attn:
                new.append({
                    key: self._scatter_pages(self.data[i][key],
                                             caches[i][key], r_idx, dst,
                                             pad_to)
                    for key in ("k", "v")})
            else:
                new.append(jax.tree.map(
                    lambda dstl, src: dstl.at[:, s_idx].set(
                        jnp.take(src, r_idx, axis=1).astype(dstl.dtype)),
                    self.data[i], caches[i]))
        self.data = tuple(new)
        self._commit()

    def _scatter_pages(self, pool, src, r_idx, dst, pad_to):
        """src (P, B, max_len, ...) rows -> pool blocks per ``dst`` ids."""
        rows = jnp.take(src, r_idx, axis=1)  # (P, R, max_len, ...)
        p, r = rows.shape[:2]
        if rows.shape[2] < pad_to:
            pad = [(0, 0), (0, 0), (0, pad_to - rows.shape[2])]
            pad += [(0, 0)] * (rows.ndim - 3)
            rows = jnp.pad(rows, pad)
        pages = rows.reshape(p, r * self.max_pages, self.page_size,
                             *rows.shape[3:])
        return pool.at[:, dst].set(pages.astype(pool.dtype))

    # ------------------------------------------------------------ growth --
    def ensure_mapped(self, slot: int, pos: int) -> None:
        """Map the block holding position ``pos`` if the slot's table does
        not cover it yet (called before each decode write).  At overcommit
        1.0 admission reserved the worst case and the alloc cannot fail;
        above it the alloc may raise :class:`PoolExhausted`, which the
        engine answers by reclaiming pages (trie eviction, then preempting
        the youngest running sequence) and retrying."""
        page = int(pos) // self.page_size
        if page >= self.max_pages:
            raise IndexError(
                f"slot {slot}: position {pos} beyond max_len {self.max_len}")
        if self.table[slot, page] == 0:
            self.table[slot, page] = self.allocator.alloc(1)[0]

    # ---------------------------------------------------- prefix sharing --
    def map_prefix(self, slot: int, blocks: TypingSequence[int]) -> None:
        """Map shared, already-written blocks read-only into the head of a
        fresh slot's page table.  The caller must hold one reference per
        block (the pin taken at admission); that reference becomes the
        slot's mapping reference and is dropped again by ``evict`` — the
        cache itself takes no extra ref here."""
        self._check_slots([slot])
        if self.table[slot].any():
            raise ValueError(f"slot {slot} still holds mapped pages; "
                             "evict before mapping a prefix")
        if len(blocks) > self.max_pages:
            raise ValueError(f"slot {slot}: {len(blocks)} prefix blocks "
                             f"exceed max_pages {self.max_pages}")
        for i, b in enumerate(blocks):
            self.table[slot, i] = int(b)

    def cow_block(self, slot: int, page_idx: int, src_block: int) -> int:
        """Copy-on-write: allocate a private block, device-copy
        ``src_block``'s K/V rows into it on every attention leaf, map it at
        ``page_idx``, and drop the caller's reference on ``src_block`` (the
        pin is consumed — the shared block stays live for its other
        readers).  Returns the private block id."""
        self._check_slots([slot])
        src = int(src_block)
        new = self.allocator.alloc(1)[0]
        out = []
        for i, is_attn in enumerate(self._attn):
            if is_attn:
                out.append({key: self.data[i][key].at[:, new].set(
                    self.data[i][key][:, src]) for key in ("k", "v")})
            else:
                out.append(self.data[i])
        self.data = tuple(out)
        self.table[slot, int(page_idx)] = new
        self.allocator.release([src])
        self._commit()
        return new

    def alloc_tail(self, slot: int, start: int, length: int) -> None:
        """Map private blocks for every page covering positions
        [``start``, ``length``) that the prefix mapping (and any COW block)
        left unmapped.  At overcommit 1.0 admission charged the unshared
        tail and the alloc cannot fail; above it :class:`PoolExhausted`
        may surface and the engine reclaims + retries."""
        self._check_slots([slot])
        if not 0 <= int(start) < int(length) <= self.max_len:
            raise ValueError(f"slot {slot}: tail [{start}, {length}) out of "
                             f"range (0, {self.max_len}]")
        first, last = int(start) // self.page_size, \
            (int(length) - 1) // self.page_size
        for page in range(first, last + 1):
            if self.table[slot, page] == 0:
                self.table[slot, page] = self.allocator.alloc(1)[0]

    def write_tails(self, slots: TypingSequence[int], caches,
                    starts: TypingSequence[int],
                    lengths: TypingSequence[int],
                    rows: TypingSequence[int] | None = None) -> None:
        """Scatter tail K/V rows into already-mapped blocks.  ``caches`` is
        a per-period tuple of ``{"k", "v"}`` leaves shaped ``(P, B, S_tail,
        Hkv, hd)`` (from ``models.prefill_with_prefix``); row ``rows[j]``'s
        tail index t holds sequence position ``starts[j] + t``, and
        positions [``starts[j]``, ``lengths[j]``) are written.  All target
        blocks must be mapped (``map_prefix``/``cow_block``/``alloc_tail``
        first)."""
        if rows is None:
            rows = list(range(len(slots)))
        if len(rows) != len(slots) or len(starts) != len(slots) \
                or len(lengths) != len(slots):
            raise ValueError(
                f"{len(slots)} slots vs {len(rows)} rows / "
                f"{len(starts)} starts / {len(lengths)} lengths")
        self._check_slots(slots)
        row_sel, tail_sel, bid, off = [], [], [], []
        for r, s, st, ln in zip(rows, slots, starts, lengths):
            if not 0 <= int(st) < int(ln) <= self.max_len:
                raise ValueError(f"slot {s}: tail [{st}, {ln}) out of range "
                                 f"(0, {self.max_len}]")
            for pos in range(int(st), int(ln)):
                b = int(self.table[s, pos // self.page_size])
                if b == 0:
                    raise ValueError(
                        f"slot {s}: position {pos} not mapped; alloc_tail "
                        "before write_tails")
                row_sel.append(int(r))
                tail_sel.append(pos - int(st))
                bid.append(b)
                off.append(pos % self.page_size)
        r_idx, t_idx, b_idx, o_idx = _pad_pow2(row_sel, tail_sel, bid, off)
        if self._tail_scatter is None:
            self._tail_scatter = _make_tail_scatter(tuple(self._attn))
        self.data = self._tail_scatter(
            self.data, caches, r_idx, t_idx, b_idx, o_idx)
        self._commit()

    # ------------------------------------------------------------ evict --
    def evict(self, slots: TypingSequence[int]) -> None:
        """Release one reference on each of ``slots``' mapped pages (a
        private page returns to the allocator, a shared one stays live for
        its remaining readers) and restore the slot-indexed recurrent state
        to its init value."""
        self._check_slots(slots)
        for s in slots:
            mapped = self.table[s][self.table[s] > 0]
            if len(mapped):
                self.allocator.release(mapped.tolist())
            self.table[s] = 0
        s_idx = jnp.asarray(list(slots), jnp.int32)
        new = []
        for i, is_attn in enumerate(self._attn):
            if is_attn:
                new.append(self.data[i])  # pool blocks just return to free
            else:
                new.append(jax.tree.map(
                    lambda dst, blank: dst.at[:, s_idx].set(
                        jnp.broadcast_to(blank[:, 0:1],
                                         blank.shape[:1] + (len(slots),)
                                         + blank.shape[2:])),
                    self.data[i], self._blank[i]))
        self.data = tuple(new)
        self._commit()

    # ------------------------------------------------------------- swap --
    def swap_out(self, slot: int) -> "SwapState":
        """Copy ``slot``'s mapped blocks (attention K/V) and its recurrent
        row to pinned host memory so a preemption can be
        undone by restore instead of recompute.  Read-only: the caller
        still owns the device pages and releases them via ``evict``.
        Shared prefix blocks are copied too — on restore the sequence gets
        PRIVATE pages (it no longer holds trie pins), which is correct but
        forgoes sharing until the pages are re-adopted."""
        self._check_slots([slot])
        mapped = self.table[slot][self.table[slot] > 0]
        n = int(len(mapped))
        if n == 0:
            raise ValueError(f"slot {slot}: nothing mapped to swap out")
        if (self.table[slot, :n] == 0).any():
            raise ValueError(f"slot {slot}: mapped pages are not a "
                             "contiguous prefix of the table")
        b_idx = jnp.asarray(mapped, jnp.int32)
        leaves = []
        for i, is_attn in enumerate(self._attn):
            if is_attn:
                leaves.append({key: host_copy(
                    jnp.take(self.data[i][key], b_idx, axis=1))
                    for key in ("k", "v")})
            else:
                leaves.append(jax.tree.map(
                    lambda x: host_copy(x[:, slot:slot + 1]), self.data[i]))
        return SwapState(blocks=tuple(leaves), num_pages=n)

    def swap_in(self, slot: int, state: "SwapState") -> None:
        """Restore a swapped-out sequence into a fresh slot: allocate
        ``state.num_pages`` private blocks (may raise :class:`PoolExhausted`
        — the engine reclaims and retries), scatter the host copies back
        into the pool, and rewrite the recurrent row."""
        self._check_slots([slot])
        if self.table[slot].any():
            raise ValueError(f"slot {slot} still holds mapped pages; "
                             "evict before swapping in")
        blocks = self.allocator.alloc(state.num_pages)
        self.table[slot, :state.num_pages] = blocks
        b_idx = jnp.asarray(blocks, jnp.int32)
        s_idx = jnp.asarray([slot], jnp.int32)

        def restore(dst, src, idx):
            # back to device memory under the pool's own sharding, so the
            # updated leaf keeps the sharding, memory kind and commitment
            # the decode step was compiled for
            src = jax.device_put(src, dst.sharding)
            return dst.at[:, idx].set(src.astype(dst.dtype))

        new = []
        for i, is_attn in enumerate(self._attn):
            if is_attn:
                new.append({key: restore(self.data[i][key],
                                         state.blocks[i][key], b_idx)
                            for key in ("k", "v")})
            else:
                new.append(jax.tree.map(
                    lambda dst, src: restore(dst, src, s_idx),
                    self.data[i], state.blocks[i]))
        self.data = tuple(new)
        self._commit()

    # ------------------------------------------------------------ views --
    def table_device(self) -> jax.Array:
        """The page table as a device array for the decode dispatch."""
        return jnp.asarray(self.table)

    def gather_slot(self, slot: int, length: int | None = None):
        """One slot's caches as a dense batch-of-1 pytree (test/debug
        helper): attention pages gathered back into a (P, 1, max_len, ...)
        stripe (positions past ``length`` zeroed — they may hold stale
        block contents that decode masks), recurrent leaves sliced."""
        n = self.max_len if length is None else int(length)
        out = []
        for i, is_attn in enumerate(self._attn):
            if is_attn:
                entry = {}
                for key in ("k", "v"):
                    pool = self.data[i][key]
                    dense = jnp.take(pool, jnp.asarray(self.table[slot]),
                                     axis=1)
                    dense = dense.reshape(pool.shape[0], 1, -1,
                                          *pool.shape[3:])[:, :, :self.max_len]
                    mask = (jnp.arange(self.max_len) < n)
                    entry[key] = dense * mask[None, None, :, None, None]
                out.append(entry)
            else:
                out.append(jax.tree.map(
                    lambda x: x[:, slot:slot + 1], self.data[i]))
        return tuple(out)

    def nbytes(self) -> int:
        return (sum(x.size * x.dtype.itemsize
                    for x in jax.tree.leaves(self.data))
                + self.table.nbytes)

    def _check_slots(self, slots: TypingSequence[int]) -> None:
        _check_slots(slots, self.num_slots)
