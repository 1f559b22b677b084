"""Paged KV cache: block tables over a shared page pool.

The round-5 refinement named in engine.py's round-4 docstring: the dense
slot cache prices every slot at max_seq_len, so 16 slots at 16k cost
16x16k of KV HBM even when most requests are 2k. Here the cache is a
pool of fixed-size pages shared by all slots; a slot owns
ceil(len/page) pages, HBM scales with tokens-in-flight, and one engine
serves mixed 2k/16k prompts (subsuming the round-4 two-tier EnginePool).

Device state (static shapes, XLA-friendly). The layer is folded into the
page axis, so the pool is ONE array that a step program can carry through
its layer scan and update in place:

    k_pages, v_pages: [n_kv_heads, n_layers * n_pages, page, head_dim]
    lengths:          [n_slots] int32

Layer ``l``'s page ``p`` is physical page ``l * n_pages + p``
(``physical_pages``, the only statement of that rule). The scan bodies
of ``infer/model.py`` add a layer's offset to the block table and hand
the whole pool to the attention kernels, which gather pages through
their page index anyway; the writers update only the rows they write.
Nothing ever slices a layer out of the pool. Everything else that
indexes the pool by (layer, page) goes through this module:
``gather_pages`` / ``scatter_pages`` speak ``[L, hkv, n, page, hd]`` (the
logical view, and the wire format's), ``copy_page`` duplicates one page
in every layer.

Host state: the **allocator** (free-page stack + per-slot block table).
Page assignment is control flow, not compute — it changes a few ints
per step — so it lives on the host and the current block table rides
into each compiled step as a tiny [slots, max_pages] int32 argument
(the kernels read it via scalar prefetch; see ops/paged_attention.py).
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PagedKVCache:
    k_pages: jnp.ndarray   # [hkv, L*P, page, hd] (bf16, or int8 quantized)
    v_pages: jnp.ndarray   # [hkv, L*P, page, hd]
    lengths: jnp.ndarray   # [slots] int32
    # int8 KV ("kv_dtype=int8"): per-page, per-head absmax scales — one
    # fp32 scale per cached token row of each page, pool-aligned with
    # the pages themselves so a page id addresses its values AND its
    # scales. None on the bf16 flavor (pytree-wise None is an empty
    # subtree, so bf16 caches flatten exactly as before).
    k_scales: Optional[jnp.ndarray] = None   # [hkv, L*P, page] f32
    v_scales: Optional[jnp.ndarray] = None   # [hkv, L*P, page] f32
    # Static (pytree aux data): how many layers share the page axis.
    n_layers: int = dataclasses.field(
        kw_only=True, metadata=dict(static=True))

    @property
    def n_pages(self) -> int:
        """Pages a layer owns (the allocator's count), not L*P."""
        return self.k_pages.shape[1] // self.n_layers

    @property
    def page_size(self) -> int:
        return self.k_pages.shape[2]

    @property
    def page_bytes(self) -> int:
        """HBM bytes one page costs across every layer: K plus V values
        at their dtype, plus the fp32 row scales on the int8 flavor."""
        arrays = [self.k_pages, self.v_pages]
        if self.k_scales is not None:
            arrays += [self.k_scales, self.v_scales]
        return sum(a.nbytes for a in arrays) // self.n_pages


def init_paged_cache(n_layers: int, n_slots: int, n_pages: int,
                     page_size: int, n_kv_heads: int, head_dim: int,
                     dtype=jnp.bfloat16) -> PagedKVCache:
    shape = (n_kv_heads, n_layers * n_pages, page_size, head_dim)
    dtype = jnp.dtype(dtype)
    lengths = jnp.zeros((n_slots,), jnp.int32)
    if dtype == jnp.int8:
        # Quantized pages halve the KV bytes per token (int8 values +
        # a 4-byte row scale vs 2-byte bf16 x head_dim), so the same
        # HBM budget holds ~2x the resident pages — which multiplies
        # the prefix cache (PR 4) and shrinks preemption pressure.
        return PagedKVCache(
            k_pages=jnp.zeros(shape, jnp.int8),
            v_pages=jnp.zeros(shape, jnp.int8), lengths=lengths,
            k_scales=jnp.zeros(shape[:-1], jnp.float32),
            v_scales=jnp.zeros(shape[:-1], jnp.float32),
            n_layers=n_layers)
    return PagedKVCache(k_pages=jnp.zeros(shape, dtype),
                        v_pages=jnp.zeros(shape, dtype), lengths=lengths,
                        n_layers=n_layers)


def physical_pages(n_pages: int, layer, pages):
    """(layer, page) -> physical page of the folded pool: THE layout
    rule. ``layer`` and ``pages`` broadcast (a scan body passes its
    traced layer index and a whole block table). Callers clamp and
    redirect to the sink BEFORE this, so page 0 stays the layer's own
    page 0 and never becomes another layer's page."""
    return layer * n_pages + pages


def _layer_pages(cache: PagedKVCache, pids) -> jnp.ndarray:
    """[L, n] physical ids of pages ``pids`` in every layer."""
    layers = jnp.arange(cache.n_layers, dtype=jnp.int32)[:, None]
    return physical_pages(cache.n_pages, layers,
                          jnp.asarray(pids, jnp.int32)[None, :])


def gather_pages(cache: PagedKVCache, pids):
    """Pages ``pids`` of every layer in the logical view: ``(k, v, ks,
    vs)`` with k/v ``[L, hkv, n, page, hd]`` and the scales ``[L, hkv, n,
    page]`` (None on the bf16 flavor)."""
    phys = _layer_pages(cache, pids)

    def take(arr):
        return None if arr is None else jnp.moveaxis(arr[:, phys], 0, 1)
    return (take(cache.k_pages), take(cache.v_pages),
            take(cache.k_scales), take(cache.v_scales))


def scatter_pages(cache: PagedKVCache, pids, k, v, k_scales=None,
                  v_scales=None) -> PagedKVCache:
    """Inverse of ``gather_pages``: land ``[L, hkv, n, page, hd]`` values
    (and ``[L, hkv, n, page]`` scales on the int8 flavor) in pages
    ``pids`` of every layer."""
    phys = _layer_pages(cache, pids)

    def put(arr, new):
        if arr is None:
            return None
        return arr.at[:, phys].set(
            jnp.moveaxis(jnp.asarray(new), 0, 1).astype(arr.dtype))
    return dataclasses.replace(
        cache, k_pages=put(cache.k_pages, k), v_pages=put(cache.v_pages, v),
        k_scales=put(cache.k_scales, k_scales),
        v_scales=put(cache.v_scales, v_scales))


class PageAllocator:
    """Host-side free-page stack + per-slot block tables.

    Never touches the device: ``table()`` snapshots the current
    [slots, max_pages] int32 block table for the next compiled call.
    Freed pages go back on the stack; their bytes stay in HBM untouched
    (a slot's length makes stale pages unreachable, same zero-memset
    rule as the dense cache's free_slot).

    Pages are REFCOUNTED so the prefix cache (infer/prefix_cache.py)
    can share one physical page between several slots' block-table rows
    plus the radix tree itself: ``extend`` hands out fresh pages at
    refcount 1, ``attach`` maps already-cached pages into a slot
    (refcount++), and a page returns to the free stack only when its
    LAST reference drops. Engines without the prefix cache never see a
    refcount above 1 and behave exactly as before.
    """

    # Concurrency contract (SKY-LOCK, docs/static-analysis.md):
    # 'owner' = confinement. The allocator has no lock of its own —
    # every mutation happens on the engine thread (or under the
    # engine's _lock via metrics()), and that only stays true if
    # external code goes through the accessor methods instead of
    # reaching into the free stack / block tables / refcounts.
    _GUARDED_BY = {
        '_free': 'owner',
        '_owned': 'owner',
        '_table': 'owner',
        '_ref': 'owner',
    }

    def __init__(self, n_pages: int, page_size: int, n_slots: int,
                 max_pages_per_slot: int) -> None:
        self.page_size = page_size
        self.n_pages = n_pages
        self.max_pages_per_slot = max_pages_per_slot
        # Page 0 is the GARBAGE SINK, never allocated: the decode step
        # is one static program over every slot, so inactive slots
        # still scatter a garbage K/V row at table[slot,0] — with the
        # table zeroed that is page 0, which must therefore belong to
        # nobody (in the dense cache the garbage landed in the inactive
        # slot's own region; pages share, so the sink makes it safe).
        self._free: List[int] = list(range(n_pages - 1, 0, -1))
        self._owned: List[List[int]] = [[] for _ in range(n_slots)]
        self._table = np.zeros((n_slots, max_pages_per_slot), np.int32)
        self._ref = np.zeros((n_pages,), np.int32)
        # Bumped on every table mutation (pages assigned or returned):
        # the engine keys its device-resident block-table copy on this,
        # re-uploading only when the table actually changed instead of
        # jnp.asarray(table) once per decoded token.
        self.version = 0

    # -- queries -----------------------------------------------------------
    @property
    def free_pages(self) -> int:
        return len(self._free)

    def pages_of(self, slot: int) -> int:
        return len(self._owned[slot])

    def owned_pages(self, slot: int) -> List[int]:
        """The slot's page ids in block-table order (a copy)."""
        return list(self._owned[slot])

    def page_at(self, slot: int, idx: int) -> int:
        """One page id, no list copy (per-token hot-path accessor)."""
        return self._owned[slot][idx]

    def refcount(self, pid: int) -> int:
        return int(self._ref[pid])

    def pages_needed(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_size)

    def table(self) -> np.ndarray:
        """Current block table (copy — compiled calls must not see later
        mutations through a shared buffer)."""
        return self._table.copy()

    # -- allocation --------------------------------------------------------
    def extend(self, slot: int, upto_tokens: int) -> bool:
        """Grow `slot` to cover `upto_tokens` positions. All-or-nothing:
        returns False (allocating nothing) when the pool can't cover it
        — the engine then defers the chunk or preempts."""
        need = self.pages_needed(upto_tokens) - len(self._owned[slot])
        if need <= 0:
            return True
        if need > len(self._free):
            return False
        if self.pages_needed(upto_tokens) > self.max_pages_per_slot:
            return False
        for _ in range(need):
            pid = self._free.pop()
            self._ref[pid] = 1
            self._table[slot, len(self._owned[slot])] = pid
            self._owned[slot].append(pid)
        self.version += 1
        return True

    def alloc_pages(self, n: int) -> Optional[List[int]]:
        """Pop ``n`` fresh pages at refcount 1 WITHOUT binding them to
        a slot — for KV-import (fleet prefix streaming): pulled pages
        land in the radix tree directly, owned by the tree's reference
        alone until some slot attaches them. All-or-nothing; returns
        None when the pool can't cover it (the import degrades to
        recompute). The caller must hand every returned page to the
        tree (or decref it) — these pages have no slot to free them."""
        if n > len(self._free):
            return None
        out: List[int] = []
        for _ in range(n):
            pid = self._free.pop()
            self._ref[pid] = 1
            out.append(pid)
        return out

    # -- reference counting (prefix sharing) -------------------------------
    def incref(self, pid: int) -> None:
        self._ref[pid] += 1

    def decref(self, pid: int) -> None:
        """Drop one reference; the page returns to the free stack when
        the last reference goes (never the sink page)."""
        assert self._ref[pid] > 0, f'double-free of page {pid}'
        self._ref[pid] -= 1
        if self._ref[pid] == 0 and pid != 0:
            self._free.append(pid)

    def attach(self, slot: int, pids: List[int]) -> None:
        """Map already-resident (cached) pages as the PREFIX of an empty
        slot's block table, taking one reference on each. The pages'
        bytes are untouched — this is the whole prefix-cache win: the
        slot starts life with its shared prefix already in HBM."""
        assert not self._owned[slot], 'attach on a non-empty slot'
        assert len(pids) <= self.max_pages_per_slot
        for i, pid in enumerate(pids):
            self.incref(pid)
            self._table[slot, i] = pid
        self._owned[slot] = list(pids)
        if pids:
            self.version += 1

    def clear_slot(self, slot: int) -> None:
        """Reset a slot's table WITHOUT touching refcounts — for callers
        (PrefixCache.donate) that have already disposed of every
        reference the slot held."""
        if self._owned[slot]:
            self.version += 1
        self._owned[slot] = []
        self._table[slot, :] = 0

    def cow(self, slot: int, page_idx: int) -> Optional[tuple]:
        """Copy-on-write the slot's page at ``page_idx``: swap in a
        fresh private page and drop the slot's reference on the shared
        one. Returns (src_pid, dst_pid) for the engine's device-side
        page copy, or None when the pool has no free page (the caller
        evicts/preempts and retries). No-op (returns None) when the
        page is not shared."""
        pid = self._owned[slot][page_idx]
        if self._ref[pid] <= 1:
            return None
        if not self._free:
            return None
        dst = self._free.pop()
        self._ref[dst] = 1
        self.decref(pid)
        self._owned[slot][page_idx] = dst
        self._table[slot, page_idx] = dst
        self.version += 1
        return pid, dst

    def shrink(self, slot: int, upto_tokens: int) -> int:
        """Trim the slot's TAIL pages down to what covers
        ``upto_tokens`` positions — the speculative-decoding rollback:
        pages extended for draft positions the verify step rejected go
        straight back to the pool (refcount-dropped, so a page somehow
        still shared merely loses this slot's reference) instead of
        riding the slot as dead weight until finish. Returns the
        number of pages released."""
        keep = max(self.pages_needed(max(upto_tokens, 0)), 0)
        dropped = 0
        while len(self._owned[slot]) > keep:
            pid = self._owned[slot].pop()
            self._table[slot, len(self._owned[slot])] = 0
            self.decref(pid)
            dropped += 1
        if dropped:
            self.version += 1
        return dropped

    def free(self, slot: int) -> None:
        """Drop the slot's reference on all of its pages (pages shared
        with the prefix tree or other slots survive; exclusive pages
        return to the pool)."""
        if self._owned[slot]:
            self.version += 1
        for pid in reversed(self._owned[slot]):
            self.decref(pid)
        self._owned[slot] = []
        self._table[slot, :] = 0

    def used_tokens_capacity(self) -> int:
        """Tokens coverable by currently-owned pages (observability)."""
        return sum(len(o) for o in self._owned) * self.page_size


def window_pages_behind(window: int, page_size: int) -> int:
    """Whole pages that rows a window still reaches can lie in, behind
    the page of the first row written: a query at a page's first row
    sees ``window - 1`` rows before it."""
    return -(-(window - 1) // page_size)


class WindowAllocator:
    """Pages of a pool whose layers attend to a WINDOW: a slot holds
    the pages its next queries can still reach and nothing behind
    them, so the pool is bounded by ``n_slots x (window + one write)``
    whatever the contexts are.

    The block table is indexed by LOGICAL page (position // page_size)
    as ``PageAllocator``'s is, so a program finds a position's page
    the same way in both; a page behind the window reads 0, the sink.
    ``cover(slot, start, end)`` is the one mutation while a request
    runs: rows ``[start, end)`` are about to be written, and the
    oldest query of that write sees back to ``start - (window - 1)``.
    It frees what lies wholly before that and hands out what the
    write needs. The pool is sized so that it cannot fail: every slot
    may hold ``pages_behind + max_write // page_size + 1`` pages at
    once. Host only, engine thread only, as ``PageAllocator``.
    """

    _GUARDED_BY = {'_spare': 'owner', '_held': 'owner',
                   '_first': 'owner', '_map': 'owner'}

    def __init__(self, page_size: int, n_slots: int,
                 max_pages_per_slot: int, window: int,
                 max_write: int) -> None:
        self.page_size = page_size
        self.window = window
        self.behind = window_pages_behind(window, page_size)
        self.per_slot = self.behind + -(-max_write // page_size) + 1
        self.n_pages = n_slots * self.per_slot + 1     # page 0: the sink
        self._spare: List[int] = list(range(self.n_pages - 1, 0, -1))
        # A slot's pages are those of logical pages
        # [_first, _first + len(_held)).
        self._held: List[List[int]] = [[] for _ in range(n_slots)]
        self._first = [0] * n_slots
        self._map = np.zeros((n_slots, max_pages_per_slot), np.int32)
        self.version = 0

    @property
    def free_pages(self) -> int:
        return len(self._spare)

    def pages_of(self, slot: int) -> int:
        return len(self._held[slot])

    def rows_held(self) -> int:
        """Rows that live slots hold, whole pages (observability)."""
        return sum(len(o) for o in self._held) * self.page_size

    def table(self) -> np.ndarray:
        return self._map.copy()

    def cover(self, slot: int, start: int, end: int) -> None:
        page = self.page_size
        keep_from = max(start - (self.window - 1), 0) // page
        upto = -(-end // page)
        owned, first = self._held[slot], self._first[slot]
        if not owned:
            first = keep_from
        drop = min(max(keep_from - first, 0), len(owned))
        for i in range(drop):
            self._spare.append(owned[i])
            self._map[slot, first + i] = 0
        del owned[:drop]
        first += drop
        if not owned:
            first = keep_from
        need = upto - (first + len(owned))
        assert need <= len(self._spare), (
            f'window pool dry: slot {slot} needs {need} pages for rows '
            f'[{start}, {end}), {len(self._spare)} free')
        for _ in range(max(need, 0)):
            pid = self._spare.pop()
            self._map[slot, first + len(owned)] = pid
            owned.append(pid)
        self._first[slot] = first
        if drop or need > 0:
            self.version += 1

    def free(self, slot: int) -> None:
        owned, first = self._held[slot], self._first[slot]
        if owned:
            self.version += 1
            self._spare.extend(reversed(owned))
            self._map[slot, first:first + len(owned)] = 0
        self._held[slot] = []
        self._first[slot] = 0


def free_slot(cache: PagedKVCache, slot: int) -> PagedKVCache:
    """Device half of freeing: zero the slot's length (the allocator's
    ``free`` is the host half)."""
    return dataclasses.replace(cache,
                               lengths=cache.lengths.at[slot].set(0))


def copy_page(cache: PagedKVCache, src: jnp.ndarray,
              dst: jnp.ndarray) -> PagedKVCache:
    """Device half of copy-on-write: duplicate page ``src`` into ``dst``
    in every layer, all heads (the allocator's ``cow`` is the host
    half). src/dst are traced scalars, so one compiled program covers
    every CoW. On the int8 flavor the page's row scales copy with it — a
    page id is only meaningful as a (values, scales) pair."""
    def dup(arr):
        if arr is None:
            return None
        for layer in range(cache.n_layers):
            page = jax.lax.dynamic_index_in_dim(
                arr, physical_pages(cache.n_pages, layer, src), axis=1,
                keepdims=True)
            arr = jax.lax.dynamic_update_index_in_dim(
                arr, page, physical_pages(cache.n_pages, layer, dst),
                axis=1)
        return arr
    return dataclasses.replace(
        cache, k_pages=dup(cache.k_pages), v_pages=dup(cache.v_pages),
        k_scales=dup(cache.k_scales), v_scales=dup(cache.v_scales))
