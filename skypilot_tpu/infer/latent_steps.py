"""The two paged step programs of the dots3-note family
(``models/dots3.py``) over ``latent_cache.LatentCache``.

``prefill_chunk`` and ``decode`` keep ``infer/model.py``'s contracts
(``paged_prefill_chunk`` / ``paged_decode_step``) with two changes the
cache forces: the block-table argument is a PAIR, ``(full, window)``
(the engine's allocator's table and the ``WindowAllocator``'s, both
indexed by logical page), and the three pools ride the step as donated
carries that each block updates in place (no per-layer slab is ever
sliced out: a block reaches its pages through its physical page ids).

What a ``full`` block does with its cache, in both programs
(``_selected_attention``): write the new tokens' rows and indexer keys;
score EVERY cached indexer key of the sequence up to the query (a key
block at a time, as many blocks as the live context has: the loops'
trip count is data, so the work follows the context and not
``max_seq_len``); find the exact top ``index_topk`` of each query as
an additive bias (0 or ``-inf``); attend over the context's pages under
it with the flash kernel (``ops/latent_attention.py`` says why a bias
over every page and not a gather of the kept rows). A ``sliding`` block gathers the pages its window spans and
attends under the window's mask.

Scopes: everything of attention under ``attn`` (nested for profiles
read by hand: ``attn.latent`` projections, norms, rope and the output
side; ``attn.index`` scores and top-k; ``attn.sparse`` the chosen rows
and attention over them; ``attn.window``; ``attn.gate``), the cache
writes under ``kv_write``, the second half under ``mlp`` or ``moe.route``
/ ``moe.experts`` / ``moe.shared``, then ``head``.

Counts (``latent_cache.STEP_STATS``): a prefill chunk adds what it
counted to ``cache.counts``; the decode step adds its own, hands the
sum out as its third result (the engine carries it on the step's pair)
and zeroes ``cache.counts``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from skypilot_tpu.infer import latent_cache as cache_lib
from skypilot_tpu.infer import model as model_lib
from skypilot_tpu.infer import paged_cache as paged_cache_lib
from skypilot_tpu.models import dots3
from skypilot_tpu.ops import latent_attention as lat

_KEY_BLOCK = 2048        # indexer keys scored in one trip of the loop
_QUERY_BLOCK = 128       # a window block's queries
_HEAD_GROUP = 32         # heads whose keys and values a chunk holds at once
# The widths (in key blocks) the selection and the attention kernel are
# built for; a step takes the narrowest that holds its live context, so
# at most a third of its counting passes and grid steps are spent past it.
_LADDER = (1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64)


def _key_block(config: dots3.Dots3Config, page: int) -> int:
    """Keys a trip of the scoring loop takes: whole key blocks of the
    attention kernel."""
    keys = lat.block_keys(page)
    return max(keys, min(_KEY_BLOCK, config.max_seq_len) // keys * keys)


def _padded_pages(pages: jnp.ndarray, block_pages: int) -> jnp.ndarray:
    """The table's page axis padded with the sink to whole key blocks."""
    n = pages.shape[-1]
    pad = -n % block_pages
    return jnp.pad(pages, [(0, 0)] * (pages.ndim - 1) + [(0, pad)])


def _selection_counts(config, positions, valid):
    """(keys scored, keys selected) by the queries at ``positions``
    that are ``valid``, in ONE full block."""
    seen = jnp.where(valid, positions + 1, 0)
    return (jnp.sum(seen, dtype=jnp.int32),
            jnp.sum(jnp.minimum(seen, config.index_topk), dtype=jnp.int32))


# ---------------------------------------------------------------------------
# a 'full' block

def _chunk_heads(config, cache, layer, q, pages, bias, reach):
    """A chunk's attention in the UP-PROJECTED form: the context's rows
    (``pages``, whole) given every head's own keys and values once, a
    group of heads at a time, and one flash pass a head under the
    selection's ``bias [C, keys]``. q ``[C, H, nope + rope]``. Returns
    the heads' own sums ``[C, H, v]`` float32."""
    s = config.attn_sizes('full')
    C, H = q.shape[:2]
    width, v_width = cache_lib.lanes(s.nope + s.rope), cache_lib.lanes(s.v)
    rows = cache_lib.read_pages(cache.full, cache.page_size, pages,
                                s.row).reshape(-1, s.row)
    q = jnp.pad(jnp.moveaxis(q, 1, 0), ((0, 0), (0, 0),
                                        (0, width - q.shape[2])))
    G = min(_HEAD_GROUP, H)

    def group(args):
        qg, uk, uv = args
        keys, values = dots3.expand_rows(s, rows, uk, uv, width, v_width)
        return lat.head_attention(qg, keys, values, bias, reach,
                                  scale=s.scale)
    o = jax.lax.map(group, (
        q.reshape(H // G, G, C, width),
        layer['w_uk'].reshape(H // G, G, *layer['w_uk'].shape[1:]),
        layer['w_uv'].reshape(H // G, G, *layer['w_uv'].shape[1:])))
    return jnp.moveaxis(o.reshape(H, C, v_width), 0, 1)[..., :s.v]


def _selected_attention(config, cache, layer, inp, tables, positions, valid):
    """The indexer's selection and attention over what it keeps, for
    queries ``[B, T]`` (a chunk: B = 1; a decode step: T = 1). inp:
    ``attn_inputs``' leaves with those two leading axes; tables: this
    layer's PHYSICAL pages ``[B, max]``; positions, valid ``[B, T]``.
    Returns float32 ``[B, T, H, rank]`` for a decode step (the
    absorbed form: sums of the cached latents) and ``[B, T, H, v]``
    for a chunk (the up-projected form: the heads' own sums)."""
    s = config.attn_sizes('full')
    page = cache.page_size
    B, T = positions.shape
    kb = _key_block(config, page)
    table = _padded_pages(tables, kb // page)
    width = table.shape[1] * page
    reach = jnp.max(jnp.where(valid, positions + 1, 0), axis=1)      # [B]
    live = (jnp.max(reach) + kb - 1) // kb
    keys = lat.block_keys(page)
    blocks = (reach + keys - 1) // keys
    # The selection and the attention under it, over the narrowest of a
    # ladder of widths that holds the live context: the scoring, the
    # top-k's counting passes and the kernels' grids follow the context.
    widths = sorted({min(kb * n, width) for n in _LADDER})
    scores = None
    if T == 1:
        # A decode step's one query a slot: the products are small, and
        # a loop over the live key blocks makes them (a [keys, 1] tile
        # would waste the kernel's matrix unit).
        with jax.named_scope('attn.index'):
            def score(b, buf):
                blk = jax.lax.dynamic_slice_in_dim(table, b * (kb // page),
                                                   kb // page, axis=1)
                ki = cache_lib.read_pages(
                    cache.index, page, blk, config.index_dim
                ).reshape(B, kb, -1)
                sc = lat.index_scores(inp['qi'], inp['wi'], ki)
                at = b * kb + jnp.arange(kb, dtype=jnp.int32)
                sc = jnp.where((at[None, None, :] <= positions[:, :, None])
                               & valid[:, :, None], sc, lat.NEG)
                return jax.lax.dynamic_update_slice_in_dim(buf, sc, b * kb,
                                                           axis=2)
            scores = jax.lax.fori_loop(
                0, live, score,
                jnp.full((B, T, width), lat.NEG, jnp.float32))

    def over(w):
        def run():
            with jax.named_scope('attn.index'):
                if scores is None:
                    sc = lat.paged_index_scores(
                        inp['qi'], inp['wi'], cache.index,
                        table[:, :w // page],
                        jnp.where(valid, positions, -1), blocks, page=page)
                else:
                    sc = scores[:, :, :w]
                bias = lat.selection_bias(sc.reshape(B * T, w),
                                          config.index_topk)
            with jax.named_scope('attn.sparse'):
                if T == 1:
                    return lat.biased_attention(
                        inp['q'], cache.full, table[:, :w // page],
                        bias.reshape(B, T, w), blocks, page=page,
                        scale=s.scale, rank=s.kv_rank)
                return _chunk_heads(config, cache, layer, inp['q_heads'][0],
                                    table[0, :w // page], bias, reach[0])[None]
        return run
    return jax.lax.switch(
        jnp.sum(jnp.asarray(widths, jnp.int32) < live * kb),
        [over(w) for w in widths])


def _full_chunk(config, layer, x, cache, pages, positions, offset, true_len):
    """pages: this layer's PHYSICAL pages by logical page ``[max]``."""
    C, page = x.shape[0], cache.page_size
    with jax.named_scope('attn'):
        inp = dots3.attn_inputs(config, 'full', layer, x, positions)
    with jax.named_scope('kv_write'):
        own = jax.lax.dynamic_slice_in_dim(pages, offset // page, C // page)
        cache = dataclasses.replace(
            cache,
            full=cache_lib.write_pages(cache.full, page, own, inp['row']),
            index=cache_lib.write_pages(cache.index, page, own, inp['ki']))
    with jax.named_scope('attn'):
        valid = jnp.arange(C, dtype=jnp.int32) < true_len
        o = _selected_attention(
            config, cache, layer,
            {k: inp[k][None] for k in ('q_heads', 'qi', 'wi')},
            pages[None], positions[None], valid[None])[0]
        x = x + dots3.attn_output(config, 'full', layer, inp['u'],
                                  o_heads=o)
    return x, cache


def _full_decode(config, layer, x, cache, tables, positions, active,
                 sink_page):
    """tables: this layer's PHYSICAL pages ``[slots, max]``."""
    page = cache.page_size
    with jax.named_scope('attn'):
        inp = dots3.attn_inputs(config, 'full', layer, x, positions)
    with jax.named_scope('kv_write'):
        at = jnp.take_along_axis(tables, (positions // page)[:, None],
                                 axis=1)[:, 0]
        at = jnp.where(active, at, sink_page)
        cache = dataclasses.replace(
            cache,
            full=cache_lib.write_rows(cache.full, page, at,
                                      positions % page, inp['row']),
            index=cache_lib.write_rows(cache.index, page, at,
                                       positions % page, inp['ki']))
    with jax.named_scope('attn'):
        o = _selected_attention(
            config, cache, layer,
            {k: inp[k][:, None] for k in ('q', 'qi', 'wi')},
            tables, positions[:, None], active[:, None])[:, 0]
        x = x + dots3.attn_output(config, 'full', layer, inp['u'], o)
    return x, cache


# ---------------------------------------------------------------------------
# a 'sliding' block

def _window_mask(config, query_positions, key_positions):
    """``[.., T, K]`` from positions ``[.., T]`` and ``[.., K]``: key
    in the query's window, itself included."""
    q, k = query_positions[..., :, None], key_positions[..., None, :]
    return (k <= q) & (k > q - config.window) & (k >= 0)


def _sliding_chunk(config, layer, x, cache, pages, positions, offset):
    """pages: this layer's PHYSICAL window pages by logical page."""
    s = config.attn_sizes('sliding')
    C, page = x.shape[0], cache.page_size
    behind = paged_cache_lib.window_pages_behind(config.window, page)
    with jax.named_scope('attn'):
        inp = dots3.attn_inputs(config, 'sliding', layer, x, positions)
    with jax.named_scope('kv_write'):
        first = offset // page
        own = jax.lax.dynamic_slice_in_dim(pages, first, C // page)
        cache = dataclasses.replace(cache, window=cache_lib.write_pages(
            cache.window, page, own, inp['row']))
    with jax.named_scope('attn'):
        with jax.named_scope('attn.window'):
            # The chunk's queries in blocks; a block reads the pages
            # its window spans: ``behind`` before its first, and its own.
            qb = min(max(page, _QUERY_BLOCK // page * page), C)
            span = behind + qb // page

            def block(args):
                q, pos, i = args
                lo = first + i * (qb // page) - behind
                logical = lo + jnp.arange(span, dtype=jnp.int32)
                got = jnp.where(logical >= 0,
                                pages[jnp.maximum(logical, 0)], 0)
                rows = cache_lib.read_pages(
                    cache.window, page, got, s.row).reshape(span * page, -1)
                at = lo * page + jnp.arange(span * page, dtype=jnp.int32)
                return lat.attend(
                    q[None], rows[None], _window_mask(config, pos, at)[None],
                    s.scale, s.kv_rank)[0]
            o = jax.lax.map(block, (
                inp['q'].reshape(C // qb, qb, *inp['q'].shape[1:]),
                positions.reshape(C // qb, qb),
                jnp.arange(C // qb, dtype=jnp.int32))
            ).reshape(C, s.heads, -1)
        x = x + dots3.attn_output(config, 'sliding', layer, inp['u'], o)
    return x, cache


def _sliding_decode(config, layer, x, cache, tables, positions, active,
                    sink_page):
    s = config.attn_sizes('sliding')
    page = cache.page_size
    behind = paged_cache_lib.window_pages_behind(config.window, page)
    with jax.named_scope('attn'):
        inp = dots3.attn_inputs(config, 'sliding', layer, x, positions)
    with jax.named_scope('kv_write'):
        at = jnp.take_along_axis(tables, (positions // page)[:, None],
                                 axis=1)[:, 0]
        at = jnp.where(active, at, sink_page)
        cache = dataclasses.replace(cache, window=cache_lib.write_rows(
            cache.window, page, at, positions % page, inp['row']))
    with jax.named_scope('attn'):
        with jax.named_scope('attn.window'):
            lo = positions // page - behind                    # [slots]
            logical = lo[:, None] + jnp.arange(behind + 1, dtype=jnp.int32)
            got = jnp.where(
                logical >= 0,
                jnp.take_along_axis(tables, jnp.maximum(logical, 0), axis=1),
                0)
            rows = cache_lib.read_pages(
                cache.window, page, got, s.row
            ).reshape(x.shape[0], (behind + 1) * page, -1)
            at = (lo[:, None] * page
                  + jnp.arange((behind + 1) * page, dtype=jnp.int32))
            real = _window_mask(config, positions[:, None], at) & \
                active[:, None, None]
            o = lat.attend(inp['q'][:, None], rows, real, s.scale,
                           s.kv_rank)[:, 0]
        x = x + dots3.attn_output(config, 'sliding', layer, inp['u'], o)
    return x, cache


# ---------------------------------------------------------------------------
# the two programs

def _layer_pages(config, cache, block, tables):
    """Block ``block``'s kind, its pool's page count, and its PHYSICAL
    page table (``tables``: the (full, window) pair, one row or all)."""
    kind = config.layer_types[block]
    per = cache.n_pages if kind == 'full' else cache.window_pages
    table = tables[0] if kind == 'full' else tables[1]
    layer = config.kind_index(block)
    return kind, (lambda p: paged_cache_lib.physical_pages(per, layer, p)), \
        table


def prefill_chunk(config: dots3.Dots3Config, params: dots3.Params,
                  cache: cache_lib.LatentCache, slot: jnp.ndarray,
                  table_row: Tuple[jnp.ndarray, jnp.ndarray],
                  tokens: jnp.ndarray, offset: jnp.ndarray,
                  true_len: jnp.ndarray
                  ) -> Tuple[cache_lib.LatentCache, jnp.ndarray]:
    """``model.paged_prefill_chunk``'s contract. The padded tail writes
    rows past the slot's frontier (unreadable: every mask stops at the
    query's position, and the next chunk or decode write covers them)
    and reaches no expert."""
    C = tokens.shape[0]
    with jax.named_scope('embed'):
        x = params['embed'][tokens]
    positions = offset + jnp.arange(C, dtype=jnp.int32)
    valid = jnp.arange(C, dtype=jnp.int32) < true_len
    moe_stats = jnp.zeros((3,), jnp.int32)
    for i, layer in enumerate(params['layers']):
        kind, physical, row = _layer_pages(config, cache, i, table_row)
        if kind == 'full':
            x, cache = _full_chunk(config, layer['attn'], x, cache,
                                   physical(row), positions, offset,
                                   true_len)
        else:
            x, cache = _sliding_chunk(config, layer['attn'], x, cache,
                                      physical(row), positions, offset)
        y, stats = dots3.ffn(config, i, layer['ffn'], x, valid)
        x = x + y
        moe_stats = moe_stats + stats
    with jax.named_scope('head'):
        last = jax.lax.dynamic_index_in_dim(x, true_len - 1, axis=0,
                                            keepdims=False)
        logits = dots3.head(config, params, last)
    scored, selected = _selection_counts(config, positions, valid)
    n_full = config.count('full')
    counts = cache.counts + jnp.concatenate([
        moe_stats, jnp.stack([scored * n_full, selected * n_full]),
        jnp.zeros((3,), jnp.int32)])
    lengths = cache.lengths.at[slot].set((offset + true_len).astype(jnp.int32))
    return dataclasses.replace(cache, lengths=lengths, counts=counts), logits


def decode_step(config: dots3.Dots3Config, params: dots3.Params,
                cache: cache_lib.LatentCache,
                block_tables: Tuple[jnp.ndarray, jnp.ndarray],
                tokens: jnp.ndarray, active: Optional[jnp.ndarray] = None
                ) -> Tuple[jnp.ndarray, cache_lib.LatentCache, jnp.ndarray]:
    """``model.paged_decode_step``'s contract, and a third result: the
    ``STEP_STATS`` counts since the last decode step. A slot that is
    not ``active`` computes garbage: its rows land on the sink page, it
    reaches no expert, and nothing it computes is kept."""
    slots = tokens.shape[0]
    if active is None:
        active = jnp.ones((slots,), bool)
    positions = cache.lengths
    with jax.named_scope('embed'):
        x = params['embed'][tokens]
    moe_stats = jnp.zeros((3,), jnp.int32)
    for i, layer in enumerate(params['layers']):
        kind, physical, table = _layer_pages(config, cache, i, block_tables)
        step = _full_decode if kind == 'full' else _sliding_decode
        x, cache = step(config, layer['attn'], x, cache, physical(table),
                        positions, active, physical(0))
        y, stats = dots3.ffn(config, i, layer['ffn'], x, active)
        x = x + y
        moe_stats = moe_stats + stats
    with jax.named_scope('head'):
        logits = dots3.head(config, params, x)
    scored, selected = _selection_counts(config, positions, active)
    n_full = config.count('full')
    full_table, window_table = block_tables
    held = jnp.any(full_table != 0, axis=1)
    own = jnp.concatenate([
        moe_stats, jnp.stack([
            scored * n_full, selected * n_full,
            jnp.sum(window_table != 0, dtype=jnp.int32) * cache.page_size,
            jnp.sum(full_table != 0, dtype=jnp.int32),
            jnp.sum(held, dtype=jnp.int32)])])
    lengths = cache.lengths + active.astype(cache.lengths.dtype)
    return logits, dataclasses.replace(
        cache, lengths=lengths, counts=jnp.zeros_like(cache.counts)), \
        cache.counts + own


def steps() -> model_lib.PagedSteps:
    """What ``Dots3Config.paged_steps()`` hands the engine."""
    return model_lib.PagedSteps(
        prefill_chunk=prefill_chunk, decode=decode_step,
        init_cache=cache_lib.init_latent_cache,
        free_slot=cache_lib.free_slot, stats=cache_lib.STEP_STATS)
