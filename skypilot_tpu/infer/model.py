"""Prefill + decode paths over ``models/llama.py`` parameters.

Same weights, two execution shapes:

- **prefill_chunk**: the prompt in bounded chunks with cache context
  (MXU-bound; interleaves with decode so long prompts never
  head-of-line block active slots).
- **decode**: ONE token for every slot in one fused step
  (HBM-bandwidth-bound: the work is streaming the KV cache through the
  chip once). Attention is computed dense over the static cache with a
  length mask — at seq=1 there is nothing for a flash kernel to tile, so
  the einsum form is the fast form.

Both are pure functions jitted by the engine with buffer donation on the
cache. The paged programs keep that promise all the way down: the page
pool (every layer's pages folded into one page axis,
infer/paged_cache.py) is a loop CARRY of the layer scan
(``_scan_layers``), never per-layer ``xs``/``ys``. A layer writes only
its new rows into the carried pool, in place (a prefill chunk's pages
by ``dynamic_update_slice``s, a decode or verify step's rows by the
``paged_row_write`` kernel, aliased onto its result) and its attention
kernel reads the layer's pages out of the same buffer through a block
table that carries the layer's offset. So the
donated input pool IS the output pool: no second pool is allocated and
no layer's slab is sliced out, stacked back or relaid.

The paged step programs name their parts with ``jax.named_scope``:
``embed``, then per layer ``attn`` (norm, projections, rope, the
attention kernel, the output projection), ``kv_write`` (the K/V rows
into the pages) and ``mlp``, then ``head``; the sampler adds
``sample``. A scope changes no instruction, only the ``op_name`` in
its metadata, by which a profiler trace's device events can be
grouped.

**Stacks that keep recurrent state** (``models/nemotron_h.py``: Mamba-2,
expert and attention blocks in one model, one mixer a block;
``models/falcon_h1.py``: attention AND a Mamba-2 mixer in every block)
share two paged programs at the end of this file,
``hybrid_prefill_chunk`` and ``hybrid_decode_step``, over
``state_cache.HybridCache``: the page pool of the layers that attend
AND the per-slot recurrent state of the state-space layers ride the
step as donated carries, each layer rewriting its own rows in place.
Their scopes are ``ssm`` (the Mamba-2 mixer), ``attn`` / ``kv_write``
/ ``mlp`` (as above), ``moe.route`` / ``moe.experts`` /
``moe.shared``. ``paged_steps(config)`` hands the
engine the programs of a configuration's family: the serving half of
the model interface (``models/interface.py``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from skypilot_tpu.infer import cache as cache_lib
from skypilot_tpu.infer import paged_cache as paged_cache_lib
from skypilot_tpu.infer import state_cache as state_cache_lib
from skypilot_tpu.models import falcon_h1
from skypilot_tpu.models import interface
from skypilot_tpu.models import llama
from skypilot_tpu.models import nemotron_h
from skypilot_tpu.ops import norms
from skypilot_tpu.ops import paged_attention as paged_attn
from skypilot_tpu.ops import quant as quant_lib
from skypilot_tpu.ops import rope as rope_lib


def _qkv(config, h, layer, cos, sin, positions):
    """The q, k and v projections of one dense-block layer, q and k
    rotated. h: [B, T, d] (normed); positions: [B, T]. Returns q
    [B, T, hq, hd], k and v [B, T, hkv, hd].

    The projections' results are held as the dot leaves them, flat
    ``[B, T, heads * hd]``, before anything reshapes them. Without the
    barrier the TPU compiler fuses the consumer (rope; for ``v`` the
    heads-major page write) into the dot's output, wants that output
    heads-major, and gets it by re-laying the WEIGHT: a slice of the
    layer's 16 MB ``wq`` (4 MB ``wk``, ``wv``) into fast memory and a
    transposing copy of it, every layer of every step (17% of a decode
    step, PERF.md section 6, PR 32). Held, each weight is read once
    where it lies and it is the activation, a few KB to 3 MB, that is
    re-laid. No shape this block serves wants the other choice."""
    B, T, _ = h.shape
    hq, hkv, hd = config.n_heads, config.n_kv_heads, config.head_dim
    q, k, v = jax.lax.optimization_barrier(
        tuple(quant_lib.qdot(h, layer[w]) for w in ('wq', 'wk', 'wv')))
    q = rope_lib.apply_rope(q.reshape(B, T, hq, hd), cos, sin, positions)
    k = rope_lib.apply_rope(k.reshape(B, T, hkv, hd), cos, sin, positions)
    return q, k, v.reshape(B, T, hkv, hd)


def prefill_chunk(config: llama.LlamaConfig, params: llama.Params,
                  kv: cache_lib.KVCache, slot: jnp.ndarray,
                  tokens: jnp.ndarray, offset: jnp.ndarray,
                  true_len: jnp.ndarray
                  ) -> Tuple[cache_lib.KVCache, jnp.ndarray]:
    """Process ONE chunk of a prompt with cache context (chunked /
    incremental prefill — the fix for prefill head-of-line blocking:
    long prompts no longer monopolize the device between decode steps).

    tokens: [C] int32, a chunk padded to the chunk bucket; offset =
    tokens of this slot already in the cache; true_len = valid tokens in
    this chunk. K/V of the chunk are written into ``slot`` at
    [offset, offset+C) (write-then-attend, like decode), the chunk's
    queries attend to the slot's cached prefix plus the chunk itself
    (causal), and lengths[slot] advances to offset+true_len. Returns
    (cache', last_logits [vocab]) — logits at local position
    true_len-1, meaningful on the final chunk.

    The pad tail writes garbage at [offset+true_len, offset+C), beyond
    the slot's frontier: unreadable (every mask stops at the frontier)
    and overwritten by the next chunk/decode write before the frontier
    reaches it.
    """
    C = tokens.shape[0]
    x = quant_lib.qembed(params['embed'], tokens)[None]   # [1, C, d]
    cos, sin = rope_lib.rope_frequencies(config.head_dim,
                                         config.max_seq_len,
                                         config.rope_theta)
    positions = offset + jnp.arange(C, dtype=jnp.int32)   # [C]
    S = kv.max_seq_len
    # [C, S]: causal over cache prefix + chunk (key_pos <= query_pos).
    mask = jnp.arange(S)[None, :] <= positions[:, None]

    def body(carry, xs):
        layer, k_layer, v_layer = xs
        h, k_new, v_new = _chunk_layer(config, carry, layer, cos, sin,
                                       k_layer, v_layer, slot,
                                       positions, mask)
        return h, (k_new, v_new)

    x, (k_upd, v_upd) = jax.lax.scan(
        body, x, (params['layers'], kv.k, kv.v))
    x = norms.rms_norm(x, params['final_norm'], config.norm_eps)
    last = jax.lax.dynamic_index_in_dim(x[0], true_len - 1, axis=0,
                                        keepdims=False)
    logits = quant_lib.qdot(last,
                            params['lm_head']).astype(jnp.float32)
    lengths = kv.lengths.at[slot].set(
        (offset + true_len).astype(jnp.int32))
    return cache_lib.KVCache(k=k_upd, v=v_upd, lengths=lengths), logits


def _chunk_layer(config, x, layer, cos, sin, k_cache, v_cache, slot,
                 positions, mask):
    """One layer of chunked prefill. k_cache/v_cache: [slots, S, kv, hd]
    (this layer); x: [1, C, d]."""
    _, C, d = x.shape
    hq, hkv, hd = config.n_heads, config.n_kv_heads, config.head_dim
    group = hq // hkv

    h = norms.rms_norm(x, layer['attn_norm'], config.norm_eps)
    q, k, v = _qkv(config, h, layer, cos, sin, positions[None])

    # Write the chunk's K/V into the slot FIRST, then attend over the
    # cache — the chunk sees itself through the causal mask.
    k_cache = jax.lax.dynamic_update_slice(
        k_cache, k.astype(k_cache.dtype), (slot, positions[0], 0, 0))
    v_cache = jax.lax.dynamic_update_slice(
        v_cache, v.astype(v_cache.dtype), (slot, positions[0], 0, 0))

    kc = jax.lax.dynamic_index_in_dim(k_cache, slot, axis=0,
                                      keepdims=False)  # [S, kv, hd]
    vc = jax.lax.dynamic_index_in_dim(v_cache, slot, axis=0,
                                      keepdims=False)
    qg = q[0].reshape(C, hkv, group, hd).astype(jnp.float32)
    scores = jnp.einsum('ckgd,skd->ckgs', qg,
                        kc.astype(jnp.float32)) * (hd ** -0.5)
    scores = jnp.where(mask[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    att = jnp.einsum('ckgs,skd->ckgd', probs, vc.astype(jnp.float32))
    att = att.reshape(1, C, hq * hd).astype(x.dtype)
    x = x + quant_lib.qdot(att, layer['wo'])
    x = llama.mlp_block(config, x, layer)
    return x, k_cache, v_cache


def paged_prefill_chunk(config: llama.LlamaConfig, params: llama.Params,
                        pkv: paged_cache_lib.PagedKVCache,
                        slot: jnp.ndarray, table_row: jnp.ndarray,
                        tokens: jnp.ndarray, offset: jnp.ndarray,
                        true_len: jnp.ndarray
                        ) -> Tuple[paged_cache_lib.PagedKVCache,
                                   jnp.ndarray]:
    """prefill_chunk over the paged cache: same contract, but the
    chunk's K/V land in the slot's PAGES (block table row) and the
    chunk attends through the tiled ``paged_prefill_attention`` kernel
    — O(C * len) bandwidth instead of the dense path's O(C * S) fp32
    einsum over the whole static cache (VERDICT r4 weak #1).

    The engine guarantees: chunk size C is a multiple of the page
    size, offset is PAGE-aligned (not necessarily C-aligned — a
    prefix-cache match starts prefill at an arbitrary page boundary),
    and `table_row` already covers positions [0, offset + C). Kernel
    work must not assume offset % C == 0.
    """
    C = tokens.shape[0]
    with jax.named_scope('embed'):
        x = quant_lib.qembed(params['embed'], tokens)[None]  # [1, C, d]
    cos, sin = rope_lib.rope_frequencies(config.head_dim,
                                         config.max_seq_len,
                                         config.rope_theta)
    positions = offset + jnp.arange(C, dtype=jnp.int32)

    def layer_fn(h, layer, kv, physical):
        return _paged_chunk_layer(
            config, h, layer, cos, sin, kv, physical(table_row),
            positions, offset, true_len)

    x, pkv = _scan_layers(params, pkv, x, layer_fn)
    with jax.named_scope('head'):
        x = norms.rms_norm(x, params['final_norm'], config.norm_eps)
        last = jax.lax.dynamic_index_in_dim(x[0], true_len - 1, axis=0,
                                            keepdims=False)
        logits = quant_lib.qdot(last,
                                params['lm_head']).astype(jnp.float32)
    lengths = pkv.lengths.at[slot].set(
        (offset + true_len).astype(jnp.int32))
    return dataclasses.replace(pkv, lengths=lengths), logits


def _scan_layers(params, pkv, x, layer_fn):
    """The layer scan of every paged step program.

    ``layer_fn(x, layer, pkv, physical) -> (x, pkv)`` is one layer;
    ``physical`` maps this layer's page ids (a block table, a table
    row, the sink page 0) to physical pages of the folded pool. The
    cache rides as a CARRY beside the activations, so XLA keeps the one
    donated pool buffer through the whole loop: a layer's writes are
    in-place row updates and nothing of the pool is an ``xs`` to slice
    or a ``ys`` to stack. ``x`` may be any pytree (the mixed step
    carries its chunk and decode activations as a pair)."""
    def body(carry, xs):
        h, kv = carry
        layer, idx = xs
        physical = functools.partial(paged_cache_lib.physical_pages,
                                     kv.n_pages, idx)
        return layer_fn(h, layer, kv, physical), None

    (x, pkv), _ = jax.lax.scan(
        body, (x, pkv),
        (params['layers'], jnp.arange(pkv.n_layers, dtype=jnp.int32)))
    return x, pkv


def _with_pages(pkv, written):
    """The cache with what a page writer returned: ``(k_pages,
    v_pages)``, plus the two scale pools on the int8 flavor."""
    k_pages, v_pages, *scales = written
    k_scales, v_scales = scales or (None, None)
    return dataclasses.replace(pkv, k_pages=k_pages, v_pages=v_pages,
                               k_scales=k_scales, v_scales=v_scales)


def _paged_chunk_layer(config, x, layer, cos, sin, pkv, table_row,
                       positions, offset, true_len):
    """One layer of paged chunked prefill. pkv: the whole folded pool;
    table_row: this layer's PHYSICAL page ids; x: [1, C, d]."""
    _, C, d = x.shape
    hq, hkv, hd = config.n_heads, config.n_kv_heads, config.head_dim
    group = hq // hkv

    with jax.named_scope('attn'):
        h = norms.rms_norm(x, layer['attn_norm'], config.norm_eps)
        q, k, v = _qkv(config, h, layer, cos, sin, positions[None])

    # Write-then-attend, page edition (quant-on-write on int8 pages:
    # the chunk's own self-attention reads its rows back dequantized,
    # exactly what every later decode step will see).
    with jax.named_scope('kv_write'):
        pkv = _with_pages(pkv, paged_attn.write_chunk_pages(
            pkv.k_pages, pkv.v_pages, k[0], v[0], table_row, offset,
            pkv.k_scales, pkv.v_scales))
    with jax.named_scope('attn'):
        qg = q[0].reshape(C, hkv, group, hd)
        att = paged_attn.paged_prefill_attention(
            qg, pkv.k_pages, pkv.v_pages, table_row, offset, true_len,
            k_scales=pkv.k_scales, v_scales=pkv.v_scales)
        att = att.reshape(1, C, hq * hd).astype(x.dtype)
        x = x + quant_lib.qdot(att, layer['wo'])
    with jax.named_scope('mlp'):
        x = llama.mlp_block(config, x, layer)
    return x, pkv


def paged_decode_step(config: llama.LlamaConfig, params: llama.Params,
                      pkv: paged_cache_lib.PagedKVCache,
                      block_tables: jnp.ndarray, tokens: jnp.ndarray,
                      active: Optional[jnp.ndarray] = None
                      ) -> Tuple[jnp.ndarray,
                                 paged_cache_lib.PagedKVCache]:
    """decode_step over the paged cache: one token for every slot. The
    attention kernel (ops/paged_attention.py ``_decode_kernel``) copies
    the pages that the ACTIVE slots own, sum(ceil(len_i/page)) of
    them, and nothing for a slot that is not active (`_attended` hands
    it over with length 0): its row of the logits is computed from a
    zero attention output and ignored by the engine, and the row writer
    (``paged_row_write``) writes no K/V row of it (on the int8 flavor
    its garbage row still lands at its frontier).

    The engine guarantees every active slot's table covers position
    lengths[slot] (the incoming token's write target).
    """
    positions = pkv.lengths
    attend = _attended(positions, active)
    with jax.named_scope('embed'):
        x = quant_lib.qembed(params['embed'], tokens)[:, None]
    cos, sin = rope_lib.rope_frequencies(config.head_dim,
                                         config.max_seq_len,
                                         config.rope_theta)

    def layer_fn(h, layer, kv, physical):
        return _paged_decode_layer(
            config, h, layer, cos, sin, kv, physical(block_tables),
            positions, attend, physical(0))

    x, pkv = _scan_layers(params, pkv, x, layer_fn)
    with jax.named_scope('head'):
        x = norms.rms_norm(x, params['final_norm'], config.norm_eps)
        logits = quant_lib.qdot(x[:, 0],
                                params['lm_head']).astype(jnp.float32)
    bump = (jnp.ones_like(pkv.lengths) if active is None
            else active.astype(pkv.lengths.dtype))
    return logits, dataclasses.replace(pkv, lengths=pkv.lengths + bump)


def _attended(positions, active):
    """What the decode kernel attends to in each slot: the positions up
    to the token just written, and nothing (length 0: no page read, a
    zero row out) in a slot that is not decoding, whose row the engine
    throws away."""
    if active is None:
        return positions + 1
    return jnp.where(active, positions + 1, 0)


def _paged_decode_layer(config, x, layer, cos, sin, pkv, block_tables,
                        positions, attend, sink_page):
    """One layer of the paged decode step. pkv: the whole folded pool;
    block_tables / sink_page: this layer's PHYSICAL page ids (the sink
    is the layer's own page 0); x: [slots, 1, d]; attend: `_attended`."""
    slots, _, d = x.shape
    hq, hkv, hd = config.n_heads, config.n_kv_heads, config.head_dim
    group = hq // hkv

    with jax.named_scope('attn'):
        h = norms.rms_norm(x, layer['attn_norm'], config.norm_eps)
        q, k, v = _qkv(config, h, layer, cos, sin, positions[:, None])

    # Write the new K/V into the slot's current page, then attend over
    # positions <= length (the new token sees itself).
    with jax.named_scope('kv_write'):
        pkv = _with_pages(pkv, paged_attn.append_token_pages(
            pkv.k_pages, pkv.v_pages, k[:, 0], v[:, 0], block_tables,
            positions, pkv.k_scales, pkv.v_scales, sink_page=sink_page,
            live=attend > 0))
    with jax.named_scope('attn'):
        qg = q[:, 0].reshape(slots, hkv, group, hd)
        att = paged_attn.paged_decode_attention(
            qg, pkv.k_pages, pkv.v_pages, block_tables, attend,
            k_scales=pkv.k_scales, v_scales=pkv.v_scales)
        att = att.reshape(slots, 1, hq * hd)
        x = x + quant_lib.qdot(att, layer['wo'])
    with jax.named_scope('mlp'):
        x = llama.mlp_block(config, x, layer)
    return x, pkv


def verify_step(config: llama.LlamaConfig, params: llama.Params,
                kv: cache_lib.KVCache, tokens: jnp.ndarray
                ) -> Tuple[jnp.ndarray, cache_lib.KVCache]:
    """Speculative verify over the dense cache: R = spec_k+1 tokens
    for EVERY slot in one fused step.

    tokens: [slots, R] int32 — column 0 the slot's last sampled token,
    columns 1..R-1 the (padded) draft candidates. K/V for all R
    positions are written at lengths[slot]..lengths[slot]+R-1
    (write-then-attend; ``cache_lib.append_run`` guards positions past
    the cache end), each query attends causally through the cache plus
    the run prefix up to itself, and the logits at every position come
    back — the engine's acceptance rule (sampling.speculative_accept)
    turns them into 1..R emitted tokens. ``lengths`` is NOT advanced
    here: only the engine knows the accepted length (it bumps by
    accepted+1 in its jitted wrapper).

    Returns (logits [slots, R, vocab] fp32, cache with K/V written,
    lengths unchanged).
    """
    slots, R = tokens.shape
    positions = kv.lengths[:, None] + jnp.arange(
        R, dtype=jnp.int32)[None, :]                  # [slots, R]
    x = quant_lib.qembed(params['embed'], tokens)     # [slots, R, d]
    cos, sin = rope_lib.rope_frequencies(config.head_dim,
                                         config.max_seq_len,
                                         config.rope_theta)
    S = kv.max_seq_len
    # [slots, R, S]: query i sees cached positions <= lengths + i
    # (itself included — its K/V is written before the attend).
    mask = jnp.arange(S)[None, None, :] <= positions[:, :, None]

    def body(carry, xs):
        layer, k_layer, v_layer = xs
        h, k_new, v_new = _verify_layer(config, carry, layer, cos, sin,
                                        k_layer, v_layer, positions,
                                        mask)
        return h, (k_new, v_new)

    x, (k_upd, v_upd) = jax.lax.scan(
        body, x, (params['layers'], kv.k, kv.v))
    x = norms.rms_norm(x, params['final_norm'], config.norm_eps)
    logits = quant_lib.qdot(x, params['lm_head']).astype(jnp.float32)
    return logits, cache_lib.KVCache(k=k_upd, v=v_upd,
                                     lengths=kv.lengths)


def _verify_layer(config, x, layer, cos, sin, k_cache, v_cache,
                  positions, mask):
    """One layer of the dense verify step. x: [slots, R, d];
    positions: [slots, R]; mask: [slots, R, S]."""
    slots, R, d = x.shape
    hq, hkv, hd = config.n_heads, config.n_kv_heads, config.head_dim
    group = hq // hkv

    h = norms.rms_norm(x, layer['attn_norm'], config.norm_eps)
    q, k, v = _qkv(config, h, layer, cos, sin, positions)

    k_cache, v_cache = cache_lib.append_run(
        k_cache, v_cache, k, v, positions[:, 0])

    qg = q.reshape(slots, R, hkv, group, hd).astype(jnp.float32)
    kc = k_cache.astype(jnp.float32)             # [slots, S, kv, hd]
    vc = v_cache.astype(jnp.float32)
    scores = jnp.einsum('brkgd,bskd->brkgs', qg, kc) * (hd ** -0.5)
    scores = jnp.where(mask[:, :, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    att = jnp.einsum('brkgs,bskd->brkgd', probs, vc)
    att = att.reshape(slots, R, hq * hd).astype(x.dtype)
    x = x + quant_lib.qdot(att, layer['wo'])
    x = llama.mlp_block(config, x, layer)
    return x, k_cache, v_cache


def paged_verify_step(config: llama.LlamaConfig, params: llama.Params,
                      pkv: paged_cache_lib.PagedKVCache,
                      block_tables: jnp.ndarray, tokens: jnp.ndarray,
                      active: Optional[jnp.ndarray] = None
                      ) -> Tuple[jnp.ndarray,
                                 paged_cache_lib.PagedKVCache]:
    """verify_step over the paged cache: the run's K/V land in the
    slot's pages (positions past the block-table coverage redirect to
    the sink page) and all R queries stream each owned page ONCE via
    the verify kernel — the bandwidth bill of a single decode step for
    up to R tokens of progress. ``lengths`` is not advanced (the
    engine bumps by accepted+1). A slot that is not ``active`` writes
    no row (None: every slot writes); its logits are garbage the
    engine throws away."""
    slots, R = tokens.shape
    positions = pkv.lengths[:, None] + jnp.arange(
        R, dtype=jnp.int32)[None, :]                  # [slots, R]
    with jax.named_scope('embed'):
        x = quant_lib.qembed(params['embed'], tokens)  # [slots, R, d]
    cos, sin = rope_lib.rope_frequencies(config.head_dim,
                                         config.max_seq_len,
                                         config.rope_theta)

    lengths = pkv.lengths

    def layer_fn(h, layer, kv, physical):
        return _paged_verify_layer(
            config, h, layer, cos, sin, kv, physical(block_tables),
            positions, lengths, physical(0), active)

    x, pkv = _scan_layers(params, pkv, x, layer_fn)
    with jax.named_scope('head'):
        x = norms.rms_norm(x, params['final_norm'], config.norm_eps)
        logits = quant_lib.qdot(x,
                                params['lm_head']).astype(jnp.float32)
    return logits, pkv


def _paged_verify_layer(config, x, layer, cos, sin, pkv, block_tables,
                        positions, lengths, sink_page, active):
    """One layer of the paged verify step. pkv: the whole folded pool;
    block_tables / sink_page: this layer's PHYSICAL page ids (the sink
    is the layer's own page 0); x: [slots, R, d]; active: the slots
    whose rows are written (None: all)."""
    slots, R, d = x.shape
    hq, hkv, hd = config.n_heads, config.n_kv_heads, config.head_dim
    group = hq // hkv

    with jax.named_scope('attn'):
        h = norms.rms_norm(x, layer['attn_norm'], config.norm_eps)
        q, k, v = _qkv(config, h, layer, cos, sin, positions)

    # Write-then-attend, run edition (sink-redirected past coverage).
    with jax.named_scope('kv_write'):
        pkv = _with_pages(pkv, paged_attn.append_run_pages(
            pkv.k_pages, pkv.v_pages, k, v, block_tables, lengths,
            pkv.k_scales, pkv.v_scales, sink_page=sink_page,
            live=active))
    with jax.named_scope('attn'):
        qg = q.reshape(slots, R, hkv, group, hd)
        att = paged_attn.paged_verify_attention(
            qg, pkv.k_pages, pkv.v_pages, block_tables, lengths,
            k_scales=pkv.k_scales, v_scales=pkv.v_scales)
        att = att.reshape(slots, R, hq * hd).astype(x.dtype)
        x = x + quant_lib.qdot(att, layer['wo'])
    with jax.named_scope('mlp'):
        x = llama.mlp_block(config, x, layer)
    return x, pkv


def decode_step(config: llama.LlamaConfig, params: llama.Params,
                kv: cache_lib.KVCache, tokens: jnp.ndarray,
                active: Optional[jnp.ndarray] = None
                ) -> Tuple[jnp.ndarray, cache_lib.KVCache]:
    """One decode token for every slot.

    tokens: [slots] int32 (last sampled token per slot). Returns
    (logits [slots, vocab] fp32, cache with K/V appended and lengths
    advanced). Inactive slots (``active`` False — free, or mid-way
    through a chunked prefill) compute garbage that the engine ignores
    and their lengths DON'T advance; their garbage K/V write lands at
    the slot frontier, which the next real write covers. Uniform work
    keeps the step a single static program.
    """
    positions = kv.lengths                       # write offset = length
    x = quant_lib.qembed(params['embed'],
                         tokens)[:, None]        # [slots, 1, d]
    cos, sin = rope_lib.rope_frequencies(config.head_dim,
                                         config.max_seq_len,
                                         config.rope_theta)
    S = kv.max_seq_len
    # mask [slots, S]: attend to cached positions 0..len-1 plus the new
    # token at position len.
    mask = jnp.arange(S)[None, :] <= positions[:, None]

    def body(carry, xs):
        layer, k_layer, v_layer = xs
        h, k_new, v_new = _decode_layer(config, carry, layer, cos, sin,
                                        k_layer, v_layer, positions, mask)
        return h, (k_new, v_new)

    x, (k_upd, v_upd) = jax.lax.scan(
        body, x, (params['layers'], kv.k, kv.v))
    x = norms.rms_norm(x, params['final_norm'], config.norm_eps)
    logits = quant_lib.qdot(x[:, 0],
                            params['lm_head']).astype(jnp.float32)
    bump = (jnp.ones_like(kv.lengths) if active is None
            else active.astype(kv.lengths.dtype))
    new_cache = cache_lib.KVCache(k=k_upd, v=v_upd,
                                  lengths=kv.lengths + bump)
    return logits, new_cache


def _decode_layer(config, x, layer, cos, sin, k_cache, v_cache,
                  positions, mask):
    slots, _, d = x.shape
    hq, hkv, hd = config.n_heads, config.n_kv_heads, config.head_dim
    group = hq // hkv

    h = norms.rms_norm(x, layer['attn_norm'], config.norm_eps)
    q, k, v = _qkv(config, h, layer, cos, sin, positions[:, None])

    # Write the new K/V into the cache FIRST, then attend over the cache —
    # the new token sees itself through the mask (pos <= length).
    k_cache, v_cache = cache_lib.append_token(
        k_cache, v_cache, k[:, 0], v[:, 0], positions)

    qg = q[:, 0].reshape(slots, hkv, group, hd).astype(jnp.float32)
    kc = k_cache.astype(jnp.float32)             # [slots, S, kv, hd]
    vc = v_cache.astype(jnp.float32)
    scores = jnp.einsum('bkgd,bskd->bkgs', qg, kc) * (hd ** -0.5)
    scores = jnp.where(mask[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    att = jnp.einsum('bkgs,bskd->bkgd', probs, vc)
    att = att.reshape(slots, 1, hq * hd).astype(x.dtype)
    x = x + quant_lib.qdot(att, layer['wo'])

    x = llama.mlp_block(config, x, layer)
    return x, k_cache, v_cache


def mixed_step(config: llama.LlamaConfig, params: llama.Params,
               kv: cache_lib.KVCache, slot: jnp.ndarray,
               chunk_tokens: jnp.ndarray, offset: jnp.ndarray,
               true_len: jnp.ndarray, decode_tokens: jnp.ndarray,
               active: jnp.ndarray
               ) -> Tuple[jnp.ndarray, jnp.ndarray,
                          cache_lib.KVCache]:
    """FUSED mixed step over the dense cache: ONE prefill chunk of one
    slot AND one decode token for every active slot in a single
    compiled program (docs/serving.md "Fused mixed steps").

    Per layer the chunk half runs first (write-then-attend into
    ``slot``), then the decode half (append-then-attend for every
    slot) — exactly the order the unfused step produced with two
    dispatches, so the cache state and both logit sets are the same
    math as ``prefill_chunk`` followed by ``decode_step``. The win is
    the layer scan itself: each layer's weights stream through the
    chip ONCE for chunk + decode combined, and the standalone prefill
    dispatch that used to sit between two decode dispatches (the ITL
    stall) is gone.

    The chunk's slot must NOT be in ``active``: a chunk that completes
    its prompt joins the NEXT step's decode (its first token is
    sampled from ``chunk_logits`` by the engine wrapper and parked in
    the last-token vector — one extra step, zero token-sequence
    difference). Returns (chunk_logits [vocab] at local position
    true_len-1, decode_logits [slots, vocab], cache') with lengths =
    chunk frontier advanced to offset+true_len, then +1 per active
    decode slot.
    """
    C = chunk_tokens.shape[0]
    xc = quant_lib.qembed(params['embed'],
                          chunk_tokens)[None]         # [1, C, d]
    xd = quant_lib.qembed(params['embed'],
                          decode_tokens)[:, None]     # [slots, 1, d]
    cos, sin = rope_lib.rope_frequencies(config.head_dim,
                                         config.max_seq_len,
                                         config.rope_theta)
    S = kv.max_seq_len
    cpos = offset + jnp.arange(C, dtype=jnp.int32)    # [C]
    cmask = jnp.arange(S)[None, :] <= cpos[:, None]
    # The decode half sees the chunk's frontier advance — the unfused
    # decode program ran AFTER the prefill program had set lengths.
    lengths_mid = kv.lengths.at[slot].set(
        (offset + true_len).astype(jnp.int32))
    dpos = lengths_mid
    dmask = jnp.arange(S)[None, :] <= dpos[:, None]

    def body(carry, xs):
        hc, hd_ = carry
        layer, k_layer, v_layer = xs
        hc, k_layer, v_layer = _chunk_layer(
            config, hc, layer, cos, sin, k_layer, v_layer, slot,
            cpos, cmask)
        hd_, k_layer, v_layer = _decode_layer(
            config, hd_, layer, cos, sin, k_layer, v_layer, dpos,
            dmask)
        return (hc, hd_), (k_layer, v_layer)

    (xc, xd), (k_upd, v_upd) = jax.lax.scan(
        body, (xc, xd), (params['layers'], kv.k, kv.v))
    xc = norms.rms_norm(xc, params['final_norm'], config.norm_eps)
    last = jax.lax.dynamic_index_in_dim(xc[0], true_len - 1, axis=0,
                                        keepdims=False)
    chunk_logits = quant_lib.qdot(
        last, params['lm_head']).astype(jnp.float32)
    xd = norms.rms_norm(xd, params['final_norm'], config.norm_eps)
    dec_logits = quant_lib.qdot(
        xd[:, 0], params['lm_head']).astype(jnp.float32)
    bump = active.astype(lengths_mid.dtype)
    return chunk_logits, dec_logits, cache_lib.KVCache(
        k=k_upd, v=v_upd, lengths=lengths_mid + bump)


def paged_mixed_step(config: llama.LlamaConfig, params: llama.Params,
                     pkv: paged_cache_lib.PagedKVCache,
                     slot: jnp.ndarray, table_row: jnp.ndarray,
                     chunk_tokens: jnp.ndarray, offset: jnp.ndarray,
                     true_len: jnp.ndarray,
                     block_tables: jnp.ndarray,
                     decode_tokens: jnp.ndarray, active: jnp.ndarray
                     ) -> Tuple[jnp.ndarray, jnp.ndarray,
                                paged_cache_lib.PagedKVCache]:
    """``mixed_step`` over the paged cache (both KV flavors): the
    chunk's K/V land in ``table_row``'s pages and the decode appends
    ride ``block_tables``, same per-layer chunk-then-decode order as
    the dense version — the unfused two-dispatch state, one launch."""
    C = chunk_tokens.shape[0]
    with jax.named_scope('embed'):
        xc = quant_lib.qembed(params['embed'], chunk_tokens)[None]
        xd = quant_lib.qembed(params['embed'], decode_tokens)[:, None]
    cos, sin = rope_lib.rope_frequencies(config.head_dim,
                                         config.max_seq_len,
                                         config.rope_theta)
    cpos = offset + jnp.arange(C, dtype=jnp.int32)
    lengths_mid = pkv.lengths.at[slot].set(
        (offset + true_len).astype(jnp.int32))
    dpos = lengths_mid
    attend = _attended(dpos, active)

    def layer_fn(h, layer, kv, physical):
        hc, hd_ = h
        hc, kv = _paged_chunk_layer(
            config, hc, layer, cos, sin, kv, physical(table_row), cpos,
            offset, true_len)
        hd_, kv = _paged_decode_layer(
            config, hd_, layer, cos, sin, kv, physical(block_tables),
            dpos, attend, physical(0))
        return (hc, hd_), kv

    (xc, xd), pkv = _scan_layers(params, pkv, (xc, xd), layer_fn)
    with jax.named_scope('head'):
        xc = norms.rms_norm(xc, params['final_norm'], config.norm_eps)
        last = jax.lax.dynamic_index_in_dim(xc[0], true_len - 1,
                                            axis=0, keepdims=False)
        chunk_logits = quant_lib.qdot(
            last, params['lm_head']).astype(jnp.float32)
        xd = norms.rms_norm(xd, params['final_norm'], config.norm_eps)
        dec_logits = quant_lib.qdot(
            xd[:, 0], params['lm_head']).astype(jnp.float32)
    bump = active.astype(lengths_mid.dtype)
    return chunk_logits, dec_logits, dataclasses.replace(
        pkv, lengths=lengths_mid + bump)


# ---------------------------------------------------------------------------
# Heterogeneous stacks: recurrent state beside the page pool

# What a hybrid decode step counts, summed over its layers (int32):
# the expert layer's ``moe_dropless.STATS`` (a model with ``E`` blocks
# only) and the slots whose recurrent state the step advanced.
HYBRID_STEP_STATS = ('moe_local_assignments', 'moe_experts_touched',
                     'moe_expert_load_max', 'ssm_slot_steps')


def _state_attn_chunk(q, k, v, kv, table_row, offset, true_len):
    """The cache's half of a state family's attention over one chunk:
    the chunk's K/V rows into this layer's pages, then the paged
    prefill kernel. q / k / v: the family's projections (its norm, its
    rope or none: ``nemotron_h.attn_qkv``, ``falcon_h1.attn_qkv``);
    kv: the folded pool of the layers that page K/V; table_row: this
    layer's PHYSICAL pages. Returns (att ``[C, hq*hd]``, kv)."""
    with jax.named_scope('kv_write'):
        kv = _with_pages(kv, paged_attn.write_chunk_pages(
            kv.k_pages, kv.v_pages, k, v, table_row, offset, None, None))
    with jax.named_scope('attn'):
        att = paged_attn.paged_prefill_attention(
            q, kv.k_pages, kv.v_pages, table_row, offset, true_len)
        return att.reshape(q.shape[0], -1), kv


def _state_attn_decode(q, k, v, kv, block_tables, positions, attend,
                       sink_page):
    """``_state_attn_chunk`` for one token of every slot; attend:
    `_attended`. Returns (att ``[slots, hq*hd]``, kv)."""
    with jax.named_scope('kv_write'):
        kv = _with_pages(kv, paged_attn.append_token_pages(
            kv.k_pages, kv.v_pages, k, v, block_tables, positions, None,
            None, sink_page=sink_page, live=attend > 0))
    with jax.named_scope('attn'):
        att = paged_attn.paged_decode_attention(
            q, kv.k_pages, kv.v_pages, block_tables, attend)
        return att.reshape(q.shape[0], -1), kv


def hybrid_prefill_chunk(config: Any, params: Any,
                         cache: state_cache_lib.HybridCache,
                         slot: jnp.ndarray, table_row: jnp.ndarray,
                         tokens: jnp.ndarray, offset: jnp.ndarray,
                         true_len: jnp.ndarray
                         ) -> Tuple[state_cache_lib.HybridCache,
                                    jnp.ndarray]:
    """``paged_prefill_chunk``'s contract over a stack that keeps
    recurrent state: ``nemotron_h``'s (one mixer a block: ``M`` | ``E``
    | ``*``) or ``falcon_h1``'s (``P``: attention and a Mamba-2 mixer
    side by side on one norm's output, then a gated MLP).

    Beside the K/V rows of the layers that attend, the chunk carries
    the slot's recurrent state forward: each state layer starts from
    the slot's state (zero when ``offset`` is 0: a prefill from the
    start IS the reset) and leaves the state as it stands after token
    ``true_len - 1``. The padded tail advances nothing: not the SSM
    state, not the convolution's window, and it reaches no expert."""
    C = tokens.shape[0]
    with jax.named_scope('embed'):
        x = config.embed(params, tokens)                  # [C, d]
    valid = jnp.arange(C, dtype=jnp.int32) < true_len
    kv = cache.kv
    if config.count('P'):
        rope = falcon_h1.rope_at(config,
                                 offset + jnp.arange(C, dtype=jnp.int32))
    for kind, i in config.layers():
        layer = params['layers'][kind][i]
        if kind == 'M':
            with jax.named_scope('ssm'):
                ssm, conv = state_cache_lib.slot_state(cache, i, slot,
                                                       offset)
                y, ssm, conv = nemotron_h.mamba_chunk(
                    config, layer, x, ssm, conv, true_len)
                x = x + y
                cache = state_cache_lib.with_slot_state(cache, i, slot,
                                                        ssm, conv)
        elif kind == '*':
            row = paged_cache_lib.physical_pages(kv.n_pages, i, table_row)
            with jax.named_scope('attn'):
                q, k, v = nemotron_h.attn_qkv(config, layer, x)
            att, kv = _state_attn_chunk(q, k, v, kv, row, offset, true_len)
            with jax.named_scope('attn'):
                x = x + jnp.dot(att.astype(x.dtype), layer['wo'])
        elif kind == 'P':
            row = paged_cache_lib.physical_pages(kv.n_pages, i, table_row)
            with jax.named_scope('attn'):
                h = falcon_h1.block_norm(config, layer, x)
                q, k, v = falcon_h1.attn_qkv(config, layer, h, rope)
            att, kv = _state_attn_chunk(q, k, v, kv, row, offset, true_len)
            with jax.named_scope('attn'):
                a = falcon_h1.attn_out(config, layer, att.astype(x.dtype))
            with jax.named_scope('ssm'):
                ssm, conv = state_cache_lib.slot_state(cache, i, slot,
                                                       offset)
                s, ssm, conv = falcon_h1.ssm_chunk(config, layer, h, ssm,
                                                   conv, true_len)
                cache = state_cache_lib.with_slot_state(cache, i, slot,
                                                        ssm, conv)
                x = falcon_h1.mixed(x, a, s)
            with jax.named_scope('mlp'):
                x = falcon_h1.mlp(config, layer, x)
        else:
            y, _ = nemotron_h.moe_mixer(config, layer, x, valid)
            x = x + y
    with jax.named_scope('head'):
        last = jax.lax.dynamic_index_in_dim(x, true_len - 1, axis=0,
                                            keepdims=False)
        logits = config.head(params, last)
    lengths = kv.lengths.at[slot].set((offset + true_len).astype(jnp.int32))
    return dataclasses.replace(
        cache, kv=dataclasses.replace(kv, lengths=lengths)), logits


def hybrid_decode_step(config: Any, params: Any,
                       cache: state_cache_lib.HybridCache,
                       block_tables: jnp.ndarray, tokens: jnp.ndarray,
                       active: Optional[jnp.ndarray] = None
                       ) -> Tuple[jnp.ndarray, state_cache_lib.HybridCache,
                                  jnp.ndarray]:
    """``paged_decode_step``'s contract over a stack that keeps
    recurrent state (``hybrid_prefill_chunk`` names the two families),
    and a third result: the step's counts, ``hybrid_steps(config).stats``.

    A slot that is not ``active`` (free, or mid-way through a chunked
    prefill) computes garbage, as in every decode program. Its K/V row
    is not written (``paged_row_write``); its recurrent state
    must not move at all, because nothing ever overwrites a state:
    the state kernel under ``mamba_mixer.decode`` moves the active
    slots' rows and no others, so the step's cost in state bytes is the
    LIVE slots' (``ssm_slot_steps``), and the mixer's output for such a
    slot is zeros. Nor does it reach an expert."""
    slots = tokens.shape[0]
    if active is None:
        active = jnp.ones((slots,), bool)
    kv = cache.kv
    positions = kv.lengths
    attend = _attended(positions, active)
    with jax.named_scope('embed'):
        x = config.embed(params, tokens)                  # [slots, d]
    moe_stats = jnp.zeros((3,), jnp.int32)
    if config.count('P'):
        rope = falcon_h1.rope_at(config, positions)
    for kind, i in config.layers():
        layer = params['layers'][kind][i]
        physical = functools.partial(paged_cache_lib.physical_pages,
                                     kv.n_pages, i)
        if kind == 'M':
            with jax.named_scope('ssm'):
                y, ssm, conv = nemotron_h.mamba_decode(
                    config, layer, x, cache.ssm[i], cache.conv[i], active)
                x = x + y
                cache = state_cache_lib.with_layer_state(cache, i, ssm,
                                                         conv)
        elif kind == '*':
            tables, sink = physical(block_tables), physical(0)
            with jax.named_scope('attn'):
                q, k, v = nemotron_h.attn_qkv(config, layer, x)
            att, kv = _state_attn_decode(q, k, v, kv, tables, positions,
                                         attend, sink)
            with jax.named_scope('attn'):
                x = x + jnp.dot(att, layer['wo'])
        elif kind == 'P':
            tables, sink = physical(block_tables), physical(0)
            with jax.named_scope('attn'):
                h = falcon_h1.block_norm(config, layer, x)
                q, k, v = falcon_h1.attn_qkv(config, layer, h, rope)
            att, kv = _state_attn_decode(q, k, v, kv, tables, positions,
                                         attend, sink)
            with jax.named_scope('attn'):
                a = falcon_h1.attn_out(config, layer, att)
            with jax.named_scope('ssm'):
                s, ssm, conv = falcon_h1.ssm_decode(
                    config, layer, h, cache.ssm[i], cache.conv[i], active)
                cache = state_cache_lib.with_layer_state(cache, i, ssm,
                                                         conv)
                x = falcon_h1.mixed(x, a, s)
            with jax.named_scope('mlp'):
                x = falcon_h1.mlp(config, layer, x)
        else:
            y, stats = nemotron_h.moe_mixer(config, layer, x, active)
            x = x + y
            moe_stats = moe_stats + stats
    with jax.named_scope('head'):
        logits = config.head(params, x)
    lengths = kv.lengths + active.astype(kv.lengths.dtype)
    stats = jnp.sum(active, dtype=jnp.int32)[None]
    if config.count('E'):
        stats = jnp.concatenate([moe_stats, stats])
    return logits, dataclasses.replace(
        cache, kv=dataclasses.replace(kv, lengths=lengths)), stats


# ---------------------------------------------------------------------------
# The serving half of the model interface: a family's step programs

@dataclasses.dataclass(frozen=True)
class PagedSteps:
    """What the engine jits for a configuration's family. ``verify``
    and ``mixed`` are None where the family has none (the engine
    refuses the switches that would need them). ``decode`` returns
    (logits, cache) and, where ``stats`` names any, a third value: one
    int32 count per name, which the engine carries out on the step's
    pair."""
    prefill_chunk: Callable[..., Any]
    decode: Callable[..., Any]
    init_cache: Callable[..., Any]   # (spec, slots, pages, page, dtype)
    free_slot: Callable[..., Any]
    verify: Optional[Callable[..., Any]] = None
    mixed: Optional[Callable[..., Any]] = None
    stats: Tuple[str, ...] = ()


def _init_paged(spec: interface.CacheSpec, n_slots, n_pages, page, dtype):
    return paged_cache_lib.init_paged_cache(
        spec.kv_layers, n_slots, n_pages, page, spec.n_kv_heads,
        spec.head_dim, dtype=dtype)


def hybrid_steps(config: Any) -> PagedSteps:
    """What the ``paged_steps()`` of a configuration whose layers keep
    recurrent state hands the engine."""
    return PagedSteps(
        prefill_chunk=hybrid_prefill_chunk, decode=hybrid_decode_step,
        init_cache=state_cache_lib.init_hybrid_cache,
        free_slot=state_cache_lib.free_slot,
        stats=(HYBRID_STEP_STATS if config.count('E')
               else HYBRID_STEP_STATS[-1:]))


def paged_steps(config: Any) -> PagedSteps:
    """``config.paged_steps()``, or the dense block's programs."""
    if hasattr(config, 'paged_steps'):
        return config.paged_steps()
    return PagedSteps(
        prefill_chunk=paged_prefill_chunk, decode=paged_decode_step,
        verify=paged_verify_step, mixed=paged_mixed_step,
        init_cache=_init_paged, free_slot=paged_cache_lib.free_slot)
