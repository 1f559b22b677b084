"""Paged pools of latent rows: what ``interface.LatentSpec`` describes.

A latent-attention layer leaves ONE row a token in the cache (the
compressed K/V beside the rotated key part), shared by every head, so
a page is ``page`` consecutive rows and a pool is the 2-D array
``[layers * n_pages * page, row]``: the layer folded into the page
axis exactly as ``paged_cache.PagedKVCache`` folds it
(``paged_cache.physical_pages`` is the one layout rule for these pools
too; physical page ``p`` is rows ``[p * page, (p + 1) * page)``),
carried through the step programs as donated arrays that each layer
updates in place. A reader gathers whole pages (``read_pages``) or
single rows (``read_rows``) along the first axis. **A pool's rows are
padded to whole 128-lane tiles** (576 -> 640, 1,088 -> 1,152; the
writers pad with zeros, the readers cut the padding off): the tiled
layout pads them so in memory anyway, and given the odd width the
TPU's compiler chose a layout of its own for the pool (rows minor) and
relaid all 1.25 GB of it into and out of every step.

Three pools, two allocators:

- ``full``: the rows of the layers that attend over the whole context,
  and ``index``: their indexer keys, one a token, page-aligned with
  ``full`` (a page id addresses a token's row AND its indexer key).
  Both grow with the context under the engine's ``PageAllocator``.
- ``window``: the rows of the layers that attend to a window, under a
  ``paged_cache.WindowAllocator``: a slot holds the pages its window
  still reaches and the pool is sized for that, whatever the contexts.

A freed slot needs no device work beyond its length: every reader
masks by position, and positions at or past a slot's length, or behind
its window, are never read (the same zero-memset rule as the K/V
pool's). ``counts`` carries what prefill chunks counted since the last
decode step, which is the one that hands counts to the engine.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from skypilot_tpu.models import interface

# What the step programs count (int32), in the order the decode step
# reports them. The first three are ``moe_dropless.STATS``.
STEP_STATS = ('moe_local_assignments', 'moe_experts_touched',
              'moe_expert_load_max', 'index_scored_keys',
              'index_selected_keys', 'window_rows_live',
              'latent_pages_live', 'cache_slots_live')


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class LatentCache:
    full: jnp.ndarray      # [Lf * P * page, full_row]
    index: jnp.ndarray     # [Lf * P * page, index_row]
    window: jnp.ndarray    # [Lw * Pw * page, window_row]
    lengths: jnp.ndarray   # [slots] int32
    counts: jnp.ndarray    # [len(STEP_STATS)] int32, pending (see above)
    full_layers: int = dataclasses.field(
        kw_only=True, metadata=dict(static=True))
    window_layers: int = dataclasses.field(
        kw_only=True, metadata=dict(static=True))
    page_size: int = dataclasses.field(
        kw_only=True, metadata=dict(static=True))

    @property
    def n_pages(self) -> int:
        """Pages a full layer owns (the engine's allocator's count)."""
        return self.full.shape[0] // (max(self.full_layers, 1)
                                      * self.page_size)

    @property
    def window_pages(self) -> int:
        return self.window.shape[0] // (max(self.window_layers, 1)
                                        * self.page_size)

    @property
    def page_bytes(self) -> int:
        """HBM bytes one page of the growing pools costs across every
        full layer: the rows and their indexer keys."""
        return (self.full.nbytes + self.index.nbytes) // self.n_pages

    @property
    def window_bytes(self) -> int:
        return self.window.nbytes


def lanes(width: int) -> int:
    """``width`` rounded up to whole 128-lane tiles."""
    return -(-width // 128) * 128


def init_latent_cache(spec: interface.CacheSpec, n_slots: int, n_pages: int,
                      page_size: int, dtype=jnp.bfloat16, *,
                      window_pages: int = 1) -> LatentCache:
    lat = spec.latent
    dtype = jnp.dtype(dtype)
    return LatentCache(
        full=jnp.zeros((lat.full_layers * n_pages * page_size,
                        lanes(lat.full_row)), dtype),
        index=jnp.zeros((lat.full_layers * n_pages * page_size,
                         lanes(lat.index_row)), dtype),
        window=jnp.zeros((lat.window_layers * window_pages * page_size,
                          lanes(lat.window_row)), dtype),
        lengths=jnp.zeros((n_slots,), jnp.int32),
        counts=jnp.zeros((len(STEP_STATS),), jnp.int32),
        full_layers=lat.full_layers, window_layers=lat.window_layers,
        page_size=page_size)


def free_slot(cache: LatentCache, slot) -> LatentCache:
    """Device half of freeing a slot: its length goes to 0."""
    return dataclasses.replace(cache,
                               lengths=cache.lengths.at[slot].set(0))


def write_pages(pool: jnp.ndarray, page: int, pages: jnp.ndarray,
                rows: jnp.ndarray) -> jnp.ndarray:
    """A chunk's rows ``[C, row]`` (C a whole number of pages) into the
    physical ``pages [C / page]``: one in-place slice update a page."""
    rows = _padded(rows, pool)

    def put(i, pool):
        return jax.lax.dynamic_update_slice(
            pool, jax.lax.dynamic_slice_in_dim(rows, i * page, page),
            (pages[i] * page, 0))
    return jax.lax.fori_loop(0, pages.shape[0], put, pool)


def write_rows(pool: jnp.ndarray, page: int, pages: jnp.ndarray,
               offsets: jnp.ndarray, rows: jnp.ndarray) -> jnp.ndarray:
    """One row a slot: ``rows [slots, row]`` at ``(pages, offsets)
    [slots]``, one in-place slice update each. Slots that are not
    decoding are given the sink page by the caller."""
    rows = _padded(rows, pool)

    def put(i, pool):
        return jax.lax.dynamic_update_slice(
            pool, jax.lax.dynamic_slice_in_dim(rows, i, 1),
            (pages[i] * page + offsets[i], 0))
    return jax.lax.fori_loop(0, rows.shape[0], put, pool)


def _padded(rows: jnp.ndarray, pool: jnp.ndarray) -> jnp.ndarray:
    return jnp.pad(rows.astype(pool.dtype),
                   ((0, 0), (0, pool.shape[1] - rows.shape[1])))


def read_pages(pool: jnp.ndarray, page: int, pages: jnp.ndarray,
               width: int) -> jnp.ndarray:
    """Whole physical ``pages [..]`` -> ``[.., page, width]``."""
    flat = pages.reshape(-1)
    got = jax.vmap(lambda p: jax.lax.dynamic_slice_in_dim(
        pool, p * page, page))(flat)
    return got.reshape(*pages.shape, page, pool.shape[-1])[..., :width]


def read_rows(pool: jnp.ndarray, page: int, pages: jnp.ndarray,
              positions: jnp.ndarray, width: int) -> jnp.ndarray:
    """Rows at token ``positions [..]`` of a sequence whose logical page
    ``p`` is physical page ``pages[.., p]``; ``pages`` is ``[max_pages]``
    (one sequence) or has ``positions``' leading axis (one a slot).
    -> ``[.., width]``."""
    logical, within = positions // page, positions % page
    if pages.ndim == 1:
        physical = pages[logical]
    else:
        physical = jnp.take_along_axis(
            pages, logical.reshape(pages.shape[0], -1), axis=1
        ).reshape(logical.shape)
    return pool[physical * page + within][..., :width]
