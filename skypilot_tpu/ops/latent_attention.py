"""Latent attention over cached rows, and the learned selection that
decides which rows a query attends to.

A latent-attention layer caches ONE row a token, ``[c_kv | k_rope]``,
shared by every head (``models/dots3.py``). In the absorbed form the
query arrives already folded through ``W_uk`` (``[.., H, rank +
rope]``), so a score is one contraction of the query with the cached
row and the weighted sum is over the rows' first ``rank`` columns: no
key or value is ever up-projected per head, and a head is just one
more row of the left operand.

The selection (``index_scores``, ``selection_bias``) is EXACT top-k without a sort and without indices:
the k-th largest score of each query is found by bisection on the bits
of the scores (32 counting passes), and a key is kept if its score lies
above it, or equals it and is among the first (by position) of the
equal ones that still fit. That is ``lax.top_k``'s set, tie rule and
all (the plain reference uses ``lax.top_k``), at a fifth of its time on
the chip (2.9 against 14.9 ms for 1,024 rows of 16,384), and it yields
what the attention below wants: a bias of 0 or ``-inf`` a (query, key).

The attention over the kept rows is a flash pass over EVERY cached row
of the context under that bias, not a gather of the kept rows. Measured
on a TPU v5e (PERF.md section 6, PR 31): gathering 2,048 rows of 1,152
B a query runs at 39 GB/s (a row a descriptor), 50 ms for a chunk of
1,024 queries whatever the context, so reading ALL rows of a 16k
context in whole pages and letting ``exp(-inf)`` drop the unkept ones is
faster up to about 28k of context. Two kernels, one a step program:
``biased_attention``, the absorbed form straight from the paged pool (a
decode step's one query a slot), and ``head_attention``, the
up-projected form over keys and values a head (a prefill chunk's many
queries, which share them). Their grids cover the live context's key
blocks, so the cost follows the context, not ``max_seq_len``.
``paged_index_scores`` is the indexer's scoring of a chunk as one
kernel.

Shapes carry a batch axis ``B`` and a query axis ``T``: a prefill
chunk is ``B = 1`` sequence of ``T`` queries, a decode step ``B``
slots of ``T = 1``. Inputs in the cache's dtype; accumulation, index
scores, their selection and the softmax in float32.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -jnp.inf
_FAN = 8                       # pages a grid step takes as one key block
_TILE_ROWS = 1024              # query x head rows a grid step works
_VMEM_LIMIT = 64 * 1024 * 1024
_LANES = 128
_LOW = -1e30                   # a running maximum's start: finite


def einsum_f32(spec: str, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """``einsum`` accumulated in float32 of operands in the cache's
    dtype. The CPU has no batched bfloat16 x bfloat16 -> float32
    product: off the TPU the operands are widened first."""
    if jax.default_backend() != 'tpu':
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


def index_scores(qi: jnp.ndarray, wi: jnp.ndarray, ki: jnp.ndarray,
                 key_block: int = 512) -> jnp.ndarray:
    """``I[b, t, s] = sum_j wi[b, t, j] relu(qi[b, t, j] . ki[b, s])``.
    qi ``[B, T, J, di]``, wi ``[B, T, J]`` float32, ki ``[B, S, di]``
    (``S`` a multiple of ``key_block`` or under it) -> ``[B, T, S]``
    float32. The ``[.., J, keys]`` products are made a key block at a
    time."""
    B, S = ki.shape[:2]

    def block(kb):
        s = einsum_f32('btjd,bkd->btjk', qi, kb)
        return jnp.einsum('btjk,btj->btk', jax.nn.relu(s), wi)
    if S <= key_block:
        return block(ki)
    out = jax.lax.map(block, jnp.moveaxis(
        ki.reshape(B, S // key_block, key_block, -1), 1, 0))
    return jnp.moveaxis(out, 0, 2).reshape(B, qi.shape[1], S)


def _ordered(x: jnp.ndarray) -> jnp.ndarray:
    """float32 -> uint32 with the same order (``-inf`` lowest)."""
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(0x80000000))


def _threshold(u: jnp.ndarray, k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Of each row of ordered scores ``u [R, S]`` (uint32): (thr ``[R]``,
    the ``k``-th largest, 0 where the row has fewer than ``k`` keys;
    room ``[R]`` int32, how many of the keys that EQUAL it are kept).
    Bisection: a bit a pass, 32 counting passes."""
    def bit(i, lo):
        cand = lo | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(u >= cand[:, None], axis=1, dtype=jnp.int32) >= k
        return jnp.where(enough, cand, lo)
    thr = jax.lax.fori_loop(0, 32, bit, jnp.zeros((u.shape[0],), jnp.uint32))
    above = jnp.sum(u > thr[:, None], axis=1, dtype=jnp.int32)
    return thr, k - above


def selection_bias(scores: jnp.ndarray, k: int) -> jnp.ndarray:
    """``0`` where a key is among the ``k`` largest of its row of
    ``scores [R, S]`` (``-inf`` where a key may not be chosen), ``-inf``
    elsewhere: what attention adds to its own scores so that it runs
    over the kept rows only. Of the keys that equal the ``k``-th
    largest, the first by position are kept."""
    u = _ordered(scores)
    thr, room = _threshold(u, k)
    equal = u == thr[:, None]
    rank = jnp.cumsum(equal, axis=1, dtype=jnp.int32)
    keep = (u > thr[:, None]) | (equal & (rank <= room[:, None]))
    return jnp.where(keep & (scores > NEG), 0.0, NEG)


# ---------------------------------------------------------------------------
# attention under a bias, over a paged pool: the kernel



def _biased_kernel(schedule_ref, live_ref, q_ref, *refs, fan, queries,
                   scale, rank):
    """One (batch row, query tile, key block) step. q_ref ``[1, TQ, H,
    W]``; ``fan`` page refs ``[page, W]``; bias_ref ``[1, TQ, TK]``;
    o_ref ``[1, TQ, H, rank]``; scratch acc ``[TQ, H, rank]``, m and l
    ``[TQ, H, 128]`` (lane-replicated)."""
    pages, (bias_ref, o_ref, acc, m, l) = refs[:fan], refs[fan:]
    b, k = pl.program_id(0), pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        m[...] = jnp.full_like(m, _LOW)
        l[...] = jnp.zeros_like(l)

    @pl.when(k < live_ref[b])
    def _():
        rows = jnp.concatenate([p[...] for p in pages], axis=0)   # [TK, W]
        for t in range(queries):
            s = jax.lax.dot_general(
                q_ref[0, t], rows, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale        # [H, TK]
            s = s + bias_ref[0, t:t + 1, :]
            m_old = m[t]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new[:, :1])
            fade = jnp.exp(m_old - m_new)
            l[t] = fade * l[t] + jnp.sum(p, axis=1, keepdims=True)
            acc[t] = acc[t] * fade[:, :1] + jnp.dot(
                p.astype(rows.dtype), rows[:, :rank],
                preferred_element_type=jnp.float32)
            m[t] = m_new

    @pl.when(k == pl.num_programs(2) - 1)
    def _():
        o_ref[0] = acc[...] / jnp.maximum(l[...][:, :, :1], 1e-30)


def _interpreted(interpret: Optional[bool], keys: jnp.ndarray):
    """(interpret, keys): compiled on a TPU, interpreted elsewhere; and
    what is interpreted computes in float32, because the CPU has no
    bfloat16 x bfloat16 -> float32 product for a kernel's operands."""
    if interpret is None:
        interpret = jax.default_backend() != 'tpu'
    return interpret, (keys.astype(jnp.float32) if interpret else keys)


def _schedule(tables: jnp.ndarray, live: jnp.ndarray, fan: int
              ) -> jnp.ndarray:
    """The page every page in_spec addresses at every key-block step,
    ``[B, blocks * fan]``: ``tables``, but a dead block (past ``live
    [B]``) keeps the row's last live block's pages, so that consecutive
    steps address the same pages and the pipeline skips the fetch."""
    B, blocks = tables.shape[0], tables.shape[1] // fan
    block = jnp.minimum(jnp.arange(blocks, dtype=jnp.int32)[None, :],
                        jnp.maximum(live[:, None] - 1, 0))
    return jnp.take_along_axis(
        tables.reshape(B, blocks, fan), block[:, :, None], axis=1
    ).reshape(B, blocks * fan)


def _index_kernel(schedule_ref, live_ref, q_ref, w_ref, pos_ref, *refs, fan,
                  heads):
    """One (batch row, query tile, key block) step of the indexer.
    q_ref ``[1, J, TQ, di]``; w_ref ``[1, J, TQ]``; pos_ref ``[1, TQ,
    1]``; ``fan`` page refs ``[page, di]``; o_ref ``[1, TQ, TK]``. The
    scores are built keys-major (``[TK, TQ]``), where a head's weight
    is a lane row, and turned once at the end."""
    pages, o_ref = refs[:fan], refs[fan]
    b, k = pl.program_id(0), pl.program_id(2)

    @pl.when(k < live_ref[b])
    def _():
        rows = jnp.concatenate([p[...] for p in pages], axis=0)  # [TK, di]
        acc = jnp.zeros((rows.shape[0], q_ref.shape[2]), jnp.float32)
        for j in range(heads):
            s = jax.lax.dot_general(
                rows, q_ref[0, j], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)            # [TK, TQ]
            acc = acc + jnp.maximum(s, 0.0) * w_ref[0, j:j + 1, :]
        out = acc.T
        at = k * out.shape[1] + jax.lax.broadcasted_iota(
            jnp.int32, out.shape, 1)
        o_ref[0] = jnp.where(at <= pos_ref[0], out, NEG)

    @pl.when(k >= live_ref[b])
    def _():
        o_ref[...] = jnp.full(o_ref.shape, NEG, jnp.float32)


def paged_index_scores(qi: jnp.ndarray, wi: jnp.ndarray, pool: jnp.ndarray,
                       tables: jnp.ndarray, positions: jnp.ndarray,
                       live: jnp.ndarray, *, page: int,
                       interpret: Optional[bool] = None) -> jnp.ndarray:
    """``index_scores`` of queries ``[B, T]`` against the indexer keys
    of a paged pool, causal: ``-inf`` where a key lies past the query
    (``positions [B, T]``; give an invalid query -1) or in a key block
    past ``live [B]``. qi ``[B, T, J, di]``, wi ``[B, T, J]`` float32,
    pool ``[n * page, di]``, tables ``[B, pages]`` (whole key blocks of
    ``_FAN`` pages). Returns ``[B, T, pages * page]`` float32.

    One kernel in place of a loop of products: the ``[T, J, keys]``
    float32 products of the ``jax.numpy`` form went through HBM (16 of
    a 150 ms chunk a layer at a context of 16k); here a (query tile,
    key block)'s stay in VMEM and only its ``[TQ, keys]`` sums leave."""
    B, T, J, _ = qi.shape
    di = pool.shape[1]               # whole lanes: the keys' width padded
    qi = jnp.pad(qi, ((0, 0),) * 3 + ((0, di - qi.shape[3]),))
    fan = _FAN
    blocks = tables.shape[1] // fan
    keys = fan * page
    tq = min(T, 256)
    while T % tq:
        tq //= 2
    interpret, pool = _interpreted(interpret, pool)
    live = jnp.asarray(live, jnp.int32)
    schedule = _schedule(tables, live, fan)

    def page_index(f):
        return lambda b, i, k, schedule_, live_: (
            schedule_[b, k * fan + f], 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, T // tq, blocks),
        in_specs=[pl.BlockSpec((1, J, tq, di), lambda b, i, k, *_: (b, 0, i, 0)),
                  pl.BlockSpec((1, J, tq), lambda b, i, k, *_: (b, 0, i)),
                  pl.BlockSpec((1, tq, 1), lambda b, i, k, *_: (b, i, 0)),
                  *[pl.BlockSpec((page, pool.shape[1]), page_index(f))
                    for f in range(fan)]],
        out_specs=pl.BlockSpec((1, tq, keys), lambda b, i, k, *_: (b, i, k)))
    return pl.pallas_call(
        functools.partial(_index_kernel, fan=fan, heads=J),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, T, blocks * keys), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary'),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name='latent_index_scores',
    )(schedule, live, jnp.moveaxis(qi, 2, 1).astype(pool.dtype),
      jnp.moveaxis(wi, 2, 1).astype(jnp.float32),
      positions.astype(jnp.int32)[:, :, None], *([pool] * fan))


def block_keys(page: int) -> int:
    """Keys a grid step of ``biased_attention`` takes."""
    return _FAN * page


def biased_attention(q: jnp.ndarray, pool: jnp.ndarray, tables: jnp.ndarray,
                     bias: jnp.ndarray, live: jnp.ndarray, *, page: int,
                     scale: float, rank: int,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """Softmax attention of ``q [B, T, H, W]`` over the rows of a paged
    pool under an additive ``bias`` (the selection's: 0 or ``-inf``).

    pool ``[n * page, Wp]`` (``Wp >= W``, whole lanes; the rows' first
    ``rank`` columns are also what is summed); tables ``[B, pages]``:
    the PHYSICAL page of each logical page of batch row ``b``'s
    sequence, ``pages`` a whole number of key blocks (``_FAN`` pages);
    bias ``[B, T, pages * page]`` float32; live ``[B]`` int32: how many
    key blocks of row ``b`` hold a key at all (the rest are neither
    fetched nor worked). Returns ``[B, T, H, rank]`` float32; a query
    with no kept key gets zeros.

    A flash pass: grid (batch row, query tile, key block), the running
    maximum, sum and accumulator of a query tile in VMEM across its key
    blocks, so the ``[rows, keys]`` scores never reach HBM (in
    ``jax.numpy`` they were most of the pass's time: 16 bytes a (query,
    head, key)). A step takes ``_FAN`` pages, each its own in_spec of
    the one pool, as one block of keys. Compiled on a TPU, interpreted
    elsewhere."""
    B, T, H, W = q.shape
    Wp = pool.shape[1]
    fan = _FAN
    blocks = tables.shape[1] // fan
    keys = fan * page
    queries = max(1, min(T, _TILE_ROWS // H))
    while T % queries:
        queries -= 1
    interpret, pool = _interpreted(interpret, pool)
    q = jnp.pad(q.astype(pool.dtype), ((0, 0),) * 3 + ((0, Wp - W),))
    live = jnp.asarray(live, jnp.int32)
    schedule = _schedule(tables, live, fan)

    def page_index(f):
        return lambda b, i, k, schedule_, live_: (
            schedule_[b, k * fan + f], 0)

    def tile_index(b, i, k, *_):
        return (b, i, 0, 0)

    def bias_index(b, i, k, schedule_, live_):
        return (b, i, jnp.minimum(k, jnp.maximum(live_[b] - 1, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, T // queries, blocks),
        in_specs=[pl.BlockSpec((1, queries, H, Wp), tile_index),
                  *[pl.BlockSpec((page, Wp), page_index(f))
                    for f in range(fan)],
                  pl.BlockSpec((1, queries, keys), bias_index)],
        out_specs=pl.BlockSpec((1, queries, H, rank), tile_index),
        scratch_shapes=[pltpu.VMEM((queries, H, rank), jnp.float32),
                        pltpu.VMEM((queries, H, _LANES), jnp.float32),
                        pltpu.VMEM((queries, H, _LANES), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_biased_kernel, fan=fan, queries=queries,
                          scale=scale, rank=rank),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, T, H, rank), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary'),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name='selected_latent_attention',
    )(schedule, live, q, *([pool] * fan), bias)


def _heads_kernel(live_ref, q_ref, k_ref, v_ref, bias_ref, o_ref, acc, m, l,
                  *, scale):
    """One (head, query tile, key block) step of ``head_attention``.
    q_ref ``[1, TQ, D]``, k_ref ``[1, TK, D]``, v_ref ``[1, TK, V]``,
    bias_ref ``[TQ, TK]``, o_ref ``[1, TQ, V]``."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        m[...] = jnp.full_like(m, _LOW)
        l[...] = jnp.zeros_like(l)

    @pl.when(k < live_ref[0])
    def _():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale + bias_ref[...]
        m_old = m[...]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new[:, :1])
        fade = jnp.exp(m_old - m_new)
        l[...] = fade * l[...] + jnp.sum(p, axis=1, keepdims=True)
        acc[...] = acc[...] * fade[:, :1] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0],
            preferred_element_type=jnp.float32)
        m[...] = m_new

    @pl.when(k == pl.num_programs(2) - 1)
    def _():
        o_ref[0] = acc[...] / jnp.maximum(l[...][:, :1], 1e-30)


def head_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   bias: jnp.ndarray, reach: jnp.ndarray, *, scale: float,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
    """Softmax attention a head, every head under the SAME additive
    ``bias`` (the selection's): q ``[G, T, D]``, k ``[G, S, D]``, v
    ``[G, S, V]`` (``D``, ``V`` whole lanes), bias ``[T, S]`` float32,
    reach: how many of the ``S`` keys any query can see (a traced
    scalar; key blocks past it are neither fetched nor worked).
    Returns ``[G, T, V]`` float32.

    The UP-PROJECTED form, for a prefill chunk: its many queries share
    the context's keys, so giving every head its own 192-wide key and
    128-wide value once a chunk (``models/dots3.py`` makes them from
    the cached latents) costs less than contracting every (query, head,
    key) over the 1,088 columns of the absorbed form: 3.6 times fewer
    operations a pair. A decode step's one query a slot cannot share
    them and stays absorbed (``biased_attention``). A flash pass as
    that one is: grid (head, query tile, key block), the statistics of
    a query tile in VMEM across its key blocks."""
    G, T, D = q.shape
    S, V = k.shape[1], v.shape[2]
    tq = min(T, 1024)
    while T % tq:
        tq //= 2
    tk = next(t for t in (1024, 512, 256, 128, S) if S % t == 0)
    interpret, k = _interpreted(interpret, k)
    q, v = q.astype(k.dtype), v.astype(k.dtype)
    live = ((jnp.asarray(reach, jnp.int32) + tk - 1) // tk).reshape(1)

    def key_index(g, i, kk, live_):
        return (g, jnp.minimum(kk, jnp.maximum(live_[0] - 1, 0)), 0)

    def bias_index(g, i, kk, live_):
        return (i, jnp.minimum(kk, jnp.maximum(live_[0] - 1, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(G, T // tq, S // tk),
        in_specs=[pl.BlockSpec((1, tq, D), lambda g, i, kk, *_: (g, i, 0)),
                  pl.BlockSpec((1, tk, D), key_index),
                  pl.BlockSpec((1, tk, V), key_index),
                  pl.BlockSpec((tq, tk), bias_index)],
        out_specs=pl.BlockSpec((1, tq, V), lambda g, i, kk, *_: (g, i, 0)),
        scratch_shapes=[pltpu.VMEM((tq, V), jnp.float32),
                        pltpu.VMEM((tq, _LANES), jnp.float32),
                        pltpu.VMEM((tq, _LANES), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_heads_kernel, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((G, T, V), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary'),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name='selected_head_attention',
    )(live, q, k, v, bias)


def attend(q: jnp.ndarray, rows: jnp.ndarray, real: jnp.ndarray,
           scale: float, rank: int) -> jnp.ndarray:
    """One pass, no loop: q ``[B, T, H, W]`` over rows ``[B, K, W]``
    under ``real [B, T, K]`` (a window's span) -> ``[B, T, H, rank]``
    float32."""
    s = einsum_f32('bthw,bkw->bthk', q, rows) * scale
    s = jnp.where(real[:, :, None, :], s, NEG)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - jnp.where(m > NEG, m, 0.0))
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    return einsum_f32('bthk,bkr->bthr', p.astype(rows.dtype),
                      rows[..., :rank])
