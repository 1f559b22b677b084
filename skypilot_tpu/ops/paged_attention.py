"""Paged attention: Pallas TPU kernels over a block-table KV cache.

The mechanism behind the serving engines the reference delegates to
(reference ``llm/vllm`` example YAMLs): the KV cache is a pool of
fixed-size **pages** shared by all slots, each slot owning a list of
page ids (its *block table*). HBM then scales with tokens-in-flight,
not slots x max_seq_len, and one engine serves mixed 2k/16k prompts
without pricing every slot at 16k.

Layout:

    k_pages, v_pages: [n_kv_heads, n_pages, page_size, head_dim]
    block_tables:     [n_slots, max_pages] int32  (page ids)
    lengths:          [n_slots] int32             (tokens per slot)

``n_pages`` is whatever the block table addresses: one layer's pages, or
the serving pool with every layer folded into the page axis
(infer/paged_cache.py), the table then carrying the layer's offset.
Kernels and writers reach pages only through the table, so neither
knows the difference.

Kernel design (per /opt/skills/guides/pallas_guide.md):

- The block table and lengths ride **scalar prefetch**
  (``PrefetchScalarGridSpec``): they land in SMEM before the pipeline
  starts, so the K/V BlockSpec ``index_map`` can translate (slot, page
  step) -> physical page id. The pages a slot touches are
  non-contiguous in HBM; the pipeline gathers them page by page, every
  KV head's rows of a page in one block.
- A slot only pays DMA for the pages it OWNS: for steps past the
  slot's last page the index_map re-maps to a page the in_spec already
  holds, and Pallas skips the fetch when consecutive steps map the same
  block (the revisiting-block rule the pipeline already implements).
  The kernel body skips those steps. Bandwidth is therefore
  sum(ceil(len_i/page)) pages, the whole point of paging.
- Online softmax across the page axis (sequential innermost grid dim on
  TPU), fp32 accumulators in VMEM scratch that persist across the page
  steps of one slot and reinitialize at the first.

The **decode** and **verify** kernels work a page at a time: grid =
(slots, max_pages), one page (all KV heads) a step, an unrolled loop
over the heads, each with its own [group, page] score tile and its
m / l / accumulator updated once a page, operands cast to float32.
Their rows are few (group, or R x group), so a tile is small whatever
its width; no cell times them (the cells decode through jax's library
kernel, speculation is off), and they keep that body until one does.

The **prefill** kernel (``_prefill_kernel``) is tiled for a chunk's
many rows. Grid = (unit blocks, max_pages / fan): a step fetches `fan`
pages (each its own in_spec, 512 key columns of them) and works them as
ONE block: the pages stacked along the row axis into one [fan*page, hd]
operand, so the score tile fills its lanes and the MXU sees a
full-width operand; operands in the pages' dtype (bfloat16 x bfloat16
-> float32); m / l / accumulator updated once a block, the statistics
kept lane-replicated ([rows, 128], l as lane-wise partial sums that
are added up at the end) so that no update is a one-lane register; the
causal mask built only in blocks that reach past `offset` or hold a
dead page; a step with no live page skipped whole. One masked body at
the block's full width serves the short prompt too: a ladder of
narrower bodies for 2 / 4 / 6 live pages read the same time at every
chat shape (33-47 us a call either way on a v5e: a short call is its
fixed costs, not its columns) and cost a third more compile. The rows
of a tile are a UNIT (some of a KV head's group x the chunk's
queries); the units are walked by a loop inside the step, not by a
grid axis, because what a grid step costs is the pipeline's
bookkeeping for each of its in_specs (about 0.05 us an in_spec and
step on a v5e, fetched or skipped: 50 us a call at one head a step,
6 us at all heads a step), and the units share it. Block, unit and
resident-unit sizes come from the call's shapes under a VMEM budget
(``_prefill_tiles``).

Two entry points, one numerically-identical reference each:

- ``paged_decode_attention``: one query token per slot (the decode hot
  path; HBM-bandwidth-bound).
- ``paged_prefill_attention``: a C-token chunk of one slot's prompt
  attending to the slot's cached prefix + itself (causal) — the tiled
  replacement for the dense [C, S] einsum, O(C*len) instead of O(C*S).

GQA is native: q carries [group] query heads per KV head and the
kernels never replicate K/V.

int8 KV pages (``kv_dtype=int8``): pages hold int8 values plus one
fp32 absmax scale per cached token row per KV head
(``k_scales/v_scales: [hkv, P, page]``), pool-aligned with the pages.
Quantization happens ON WRITE (each row is quantized independently, so
appending never rescales earlier rows) and dequantization happens IN
KERNEL (the row scales multiply the score and probability columns, see
``_scale_rows``) — the HBM stream is int8, roughly doubling the
resident pages per chip. Every entry
point takes optional ``k_scales``/``v_scales``; None means the bf16
path, which is bit-for-bit the pre-quantization code.

Each ``pallas_call`` carries its entry point's name
(``paged_decode_attention``, ``paged_prefill_attention``,
``paged_verify_attention``): that is the operation's name in a
profiler trace. Without one the compiled custom call takes the name
of whatever scope encloses it (``closed_call`` inside a layer scan).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _interpret_default(interpret: Optional[bool]) -> bool:
    if interpret is None:
        return jax.default_backend() != 'tpu'
    return interpret


# ---------------------------------------------------------------------------
# int8 row quantization (quant-on-write / dequant-in-kernel)
# ---------------------------------------------------------------------------
def quantize_rows(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-row int8 quantization over the trailing head_dim
    axis: returns ``(values int8[...], scales f32[...[:-1]])`` with
    ``x ≈ values * scales[..., None]``. Deterministic round-to-nearest
    (NOT stochastic): the same K/V row must quantize identically on
    every host and every re-prefill, or preemption-resume and multihost
    lockstep would diverge. An all-zero row gets scale 1.0 so the
    dequant never divides by (or multiplies garbage into) zero."""
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def _deq(pages: jnp.ndarray, scales: Optional[jnp.ndarray]
         ) -> jnp.ndarray:
    """Reference-path dequant: fp32 values, scale applied per row."""
    out = pages.astype(jnp.float32)
    if scales is not None:
        out = out * scales.astype(jnp.float32)[..., None]
    return out


def _scale_rows(scales: jnp.ndarray) -> jnp.ndarray:
    """[hkv, P, page] -> [hkv, P, 1, page] at the kernel boundary: a
    TPU block's last two dims must be (8,128)-divisible or the array's
    own, and one page's scale row is neither inside [.., P, page]. As
    (1, page) it is the array's own last two dims, addressed by the
    pages' index map unchanged, and it lands in VMEM lane-major — so
    the kernels scale the [rows, page] score/probability COLUMNS
    (q.(k*s) == (q.k)*s; p@(v*s) == (p*s)@v) rather than relayout it
    against the [page, hd] page rows."""
    return scales[:, :, None, :]


# ---------------------------------------------------------------------------
# Reference implementations (ground truth in tests; CPU-friendly)
# ---------------------------------------------------------------------------
def paged_decode_attention_reference(
        q: jnp.ndarray, k_pages: jnp.ndarray, v_pages: jnp.ndarray,
        block_tables: jnp.ndarray, lengths: jnp.ndarray,
        *, sm_scale: Optional[float] = None,
        k_scales: Optional[jnp.ndarray] = None,
        v_scales: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """q: [slots, hkv, group, hd]; pages: [hkv, P, page, hd];
    block_tables: [slots, maxp]; lengths: [slots]. Attends to positions
    < lengths[slot]. Returns [slots, hkv, group, hd] fp32."""
    slots, hkv, group, hd = q.shape
    page = k_pages.shape[2]
    maxp = block_tables.shape[1]
    if sm_scale is None:
        sm_scale = hd ** -0.5
    # Gather each slot's pages: [slots, hkv, maxp*page, hd].
    k = _deq(k_pages, k_scales)[:, block_tables]
    v = _deq(v_pages, v_scales)[:, block_tables]
    k = k.transpose(1, 0, 2, 3, 4).reshape(slots, hkv, maxp * page, hd)
    v = v.transpose(1, 0, 2, 3, 4).reshape(slots, hkv, maxp * page, hd)
    s = jnp.einsum('bkgd,bksd->bkgs', q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    pos = jnp.arange(maxp * page)[None, None, None, :]
    s = jnp.where(pos < lengths[:, None, None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum('bkgs,bksd->bkgd', p, v.astype(jnp.float32))


def paged_prefill_attention_reference(
        q: jnp.ndarray, k_pages: jnp.ndarray, v_pages: jnp.ndarray,
        table_row: jnp.ndarray, offset: jnp.ndarray,
        true_len: jnp.ndarray, *,
        sm_scale: Optional[float] = None,
        k_scales: Optional[jnp.ndarray] = None,
        v_scales: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """q: [C, hkv, group, hd] (chunk queries of ONE slot, global
    positions offset..offset+C); pages: [hkv, P, page, hd]; table_row:
    [maxp]. Causal over prefix+chunk: query at global position i attends
    to cached positions <= i. Returns [C, hkv, group, hd] fp32."""
    C, hkv, group, hd = q.shape
    page = k_pages.shape[2]
    maxp = table_row.shape[0]
    if sm_scale is None:
        sm_scale = hd ** -0.5
    k = _deq(k_pages, k_scales)[:, table_row].reshape(
        hkv, maxp * page, hd)
    v = _deq(v_pages, v_scales)[:, table_row].reshape(
        hkv, maxp * page, hd)
    s = jnp.einsum('ckgd,ksd->ckgs', q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    qpos = offset + jnp.arange(C)
    kpos = jnp.arange(maxp * page)
    mask = kpos[None, :] <= qpos[:, None]       # [C, S]
    s = jnp.where(mask[:, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum('ckgs,ksd->ckgd', p, v.astype(jnp.float32))


# ---------------------------------------------------------------------------
# Decode kernel
# ---------------------------------------------------------------------------
def _decode_kernel(tables_ref, lengths_ref, q_ref, k_ref, v_ref, *refs,
                   page_size: int, sm_scale: float, max_pages: int,
                   hkv: int, quantized: bool):
    if quantized:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = refs
    else:
        o_ref, acc_ref, m_ref, l_ref = refs
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    p = pl.program_id(1)
    del tables_ref  # consumed by the index_maps
    length = lengths_ref[b]
    n_pages = pl.cdiv(length, page_size)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(p < n_pages)
    def _accumulate():
        pos = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        valid = pos < length
        # All KV heads of the page in one grid step (an unrolled loop of
        # hkv small MXU matmuls): 8x fewer grid steps and 8x larger
        # DMAs than a per-head grid — the fixed per-step cost, not the
        # bytes, dominates paged decode.
        for h in range(hkv):
            q = q_ref[0, h].astype(jnp.float32) * sm_scale  # [group, hd]
            k = k_ref[h, 0].astype(jnp.float32)             # [page, hd]
            v = v_ref[h, 0].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)         # [group, page]
            if quantized:
                s = s * ks_ref[h, 0]        # [1, page] K row scales
            s = jnp.where(valid, s, _NEG_INF)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=-1, keepdims=True))
            pr = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(pr, axis=-1,
                                                  keepdims=True)
            if quantized:
                pr = pr * vs_ref[h, 0]      # [1, page] V row scales
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                pr, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(p == max_pages - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_decode_attention(q: jnp.ndarray, k_pages: jnp.ndarray,
                           v_pages: jnp.ndarray,
                           block_tables: jnp.ndarray,
                           lengths: jnp.ndarray, *,
                           sm_scale: Optional[float] = None,
                           interpret: Optional[bool] = None,
                           impl: str = 'auto',
                           k_scales: Optional[jnp.ndarray] = None,
                           v_scales: Optional[jnp.ndarray] = None
                           ) -> jnp.ndarray:
    """One decode token for every slot over the paged cache.

    q: [slots, hkv, group, hd]; k_pages/v_pages: [hkv, P, page, hd];
    block_tables: [slots, maxp] int32; lengths: [slots] int32 (the
    kernel attends to positions < length — callers that write the new
    token's K/V first pass the already-bumped length, mirroring the
    dense decode path's write-then-attend contract).
    k_scales/v_scales: [hkv, P, page] f32 row scales on the int8
    flavor (forces the native kernel — the library path here is wired
    for bf16 pages only); None = bf16 pages, the pre-quantization path.

    impl: 'native' runs this module's grid kernel everywhere; 'jax'
    runs jax's tuned JetStream decode kernel (same page layout —
    convergent design — but an internal double-buffered DMA loop
    instead of grid steps, measured ~1.6x faster on v5e); 'auto' picks
    'jax' on real TPU and 'native' in interpret mode. The native kernel
    is always the ground truth in tests.
    """
    slots, hkv, group, hd = q.shape
    quantized = k_scales is not None
    interpret_resolved = _interpret_default(interpret)
    if impl == 'auto':
        # The library kernel needs lane-aligned blocks (hd multiple of
        # 128; its output block carries `group` in the sublane dim, so
        # tiny test models fall back to the native kernel).
        jax_ok = (hd % 128 == 0 and k_pages.shape[2] % 8 == 0
                  and not quantized)
        impl = ('jax' if jax_ok and not interpret_resolved
                else 'native')
    if impl == 'jax' and quantized:
        raise ValueError("impl='jax' is wired for bf16 pages only; "
                         "use the native kernel for kv_dtype=int8")
    if impl == 'jax' and not interpret_resolved:
        from jax.experimental.pallas.ops.tpu.paged_attention import (
            paged_attention as jax_paged_attention)
        if sm_scale is not None and sm_scale != hd ** -0.5:
            raise ValueError(
                "impl='jax' supports only the default 1/sqrt(hd) scale")
        # The library kernel computes raw q·k (no internal softmax
        # scale), so fold 1/sqrt(hd) into q first.
        qf = q.reshape(slots, hkv * group, hd)
        maxp = block_tables.shape[1]
        ppcb = next(f for f in (8, 4, 2, 1) if maxp % f == 0)
        out = jax_paged_attention(
            (qf * (hd ** -0.5)).astype(k_pages.dtype),
            k_pages, v_pages, lengths, block_tables,
            pages_per_compute_block=ppcb)
        return out.reshape(slots, hkv, group, hd).astype(jnp.float32)
    page_size = k_pages.shape[2]
    max_pages = block_tables.shape[1]
    if sm_scale is None:
        sm_scale = hd ** -0.5
    interpret = _interpret_default(interpret)

    def _page_index(b, p, tables, lengths_):
        # Pages past the slot's frontier re-map to the slot's LAST real
        # page: consecutive grid steps then address the same block and
        # the pipeline skips the fetch (the "revisiting block" rule) —
        # dead steps cost neither DMA nor bandwidth.
        n_pages = jax.lax.div(lengths_[b] + page_size - 1, page_size)
        j = jnp.minimum(p, jnp.maximum(n_pages - 1, 0))
        return (0, tables[b, j], 0, 0)

    in_specs = [
        pl.BlockSpec((1, hkv, group, hd),
                     lambda b, p, *_: (b, 0, 0, 0)),
        pl.BlockSpec((hkv, 1, page_size, hd), _page_index),
        pl.BlockSpec((hkv, 1, page_size, hd), _page_index),
    ]
    operands = [q, k_pages, v_pages]
    if quantized:
        # Scales ride the pages' own index map (_scale_rows).
        in_specs += [pl.BlockSpec((hkv, 1, 1, page_size), _page_index)] * 2
        operands += [_scale_rows(k_scales), _scale_rows(v_scales)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(slots, max_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hkv, group, hd),
                               lambda b, p, *_: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hkv, group, hd), jnp.float32),
            pltpu.VMEM((hkv, group, 1), jnp.float32),
            pltpu.VMEM((hkv, group, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(_decode_kernel, page_size=page_size,
                               sm_scale=sm_scale, max_pages=max_pages,
                               hkv=hkv, quantized=quantized)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, hkv, group, hd),
                                       jnp.float32),
        interpret=interpret,
        name='paged_decode_attention',
    )(block_tables, lengths, *operands)


# ---------------------------------------------------------------------------
# Prefill-chunk kernel
# ---------------------------------------------------------------------------
_LANES = 128
# Key columns a grid step fetches and works as ONE block: wide enough
# that the once-a-block bookkeeping (m, l, the accumulator's rescale:
# about as many register operations as a 128-column score tile) is a
# small share of the block's element-wise work.
_PREFILL_BLOCK_COLS = 512
_PREFILL_MAX_FAN = 16      # in_specs (DMAs) a step carries per K and V
# The float32 score tile [rows, cols] of one unit's block, which (with
# the probabilities beside it) is what a block's arithmetic holds in
# VMEM besides the resident blocks.
_PREFILL_TILE_BYTES = 2 << 20
# What the call may hold in VMEM (queries, output, accumulator and
# statistics of the resident units, the fetched pages twice, one
# block's tiles), and the limit it asks the compiler for.
_PREFILL_VMEM_BUDGET = 36 << 20
_PREFILL_VMEM_LIMIT = 48 << 20


def _across(x: jnp.ndarray, n: int) -> jnp.ndarray:
    """A per-row statistic [rows, 1 or _LANES] (lane-replicated) spread
    over n columns: broadcasting does the one-lane flavor, the
    replicated one repeats whole registers (no cross-lane work)."""
    lanes = x.shape[1]
    if lanes in (1, n):
        return x
    return jnp.tile(x, (1, n // lanes))


def _prefill_kernel(schedule_ref, meta_ref, q_ref, *refs,
                    page_size: int, sm_scale: float, n_groups: int,
                    chunk: int, members: int, units_per_head: int,
                    fan: int, quantized: bool):
    """One grid step works the `fan` pages it fetched (each its own
    scalar-prefetched in_spec/DMA, every KV head's rows in one block)
    as ONE block of keys: the pages stacked along the row axis into a
    [fan*page, hd] operand, one score tile, and m / l / the accumulator
    updated once a block. It does so for every resident UNIT in turn
    (a loop, not a grid axis: what a grid step costs is its in_specs'
    bookkeeping, which all units then share): a unit is `members`
    query heads of one KV head's group x the chunk's queries,
    member-major (row r is query r % chunk).

    Which body a step runs follows its live pages: a full block at or
    under `offset` is visible to every row and takes the body with no
    mask; the blocks that reach into the chunk's own positions, and
    the slot's last, partly dead one, take the masked body (a dead
    page's columns lie past every real row's position, so causality
    masks them too); a step with no live page does nothing."""
    k_refs = refs[:fan]
    v_refs = refs[fan:2 * fan]
    refs = refs[2 * fan:]
    if quantized:
        ks_refs = refs[:fan]
        vs_refs = refs[fan:2 * fan]
        refs = refs[2 * fan:]
    else:
        ks_refs = vs_refs = None
    o_ref, acc_ref, m_ref, l_ref = refs
    g = pl.program_id(1)
    del schedule_ref  # consumed by the index_maps
    offset = meta_ref[0]
    live = meta_ref[1] - g * fan    # live pages, this step's first onwards
    base = g * (fan * page_size)    # this step's first key position
    units, _, hd = acc_ref.shape
    stat_lanes = m_ref.shape[2]
    first_unit = pl.program_id(0) * units

    def _for_units(body):
        def step(u, carry):
            body(u)
            return carry
        jax.lax.fori_loop(0, units, step, 0)

    @pl.when(g == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _block(masked: bool, u):
        cols = fan * page_size
        head = (first_unit + u) // units_per_head
        # Operands go to the MXU as stored (q arrives in their dtype);
        # int8 pages convert exactly.
        q = q_ref[u]                                    # [rows, hd]
        k = jnp.concatenate([r[head, 0] for r in k_refs]).astype(q.dtype)
        v = jnp.concatenate([r[head, 0] for r in v_refs]).astype(q.dtype)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)         # [rows, cols]
        if quantized:
            s = s * jnp.concatenate(
                [r[head, 0] for r in ks_refs], axis=1)      # [1, cols]
        if masked:
            # Causality in GLOBAL positions, built for the chunk's
            # queries and shared by the unit's members.
            qpos = offset + jax.lax.broadcasted_iota(
                jnp.int32, (chunk, cols), 0)
            kpos = base + jax.lax.broadcasted_iota(
                jnp.int32, (chunk, cols), 1)
            visible = kpos <= qpos
            s = jnp.concatenate([
                jnp.where(visible, s[i * chunk:(i + 1) * chunk], _NEG_INF)
                for i in range(members)])
        # m is kept in raw score units and the softmax scale rides the
        # exponent's argument: the products stay the stored values'.
        m_prev = m_ref[u]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp((m_prev - m_new) * sm_scale)
        pr = jnp.exp((s - _across(m_new, cols)) * sm_scale)
        if stat_lanes == 1:
            l_blk = jnp.sum(pr, axis=-1, keepdims=True)
        else:   # lane-wise partial sums; the lanes are summed at the end
            l_blk = sum(pr[:, j:j + stat_lanes]
                        for j in range(0, cols, stat_lanes))
        l_ref[u] = l_ref[u] * alpha + l_blk
        if quantized:
            pr = pr * jnp.concatenate(
                [r[head, 0] for r in vs_refs], axis=1)
        acc_ref[u] = acc_ref[u] * _across(alpha, hd) + jnp.dot(
            pr.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[u] = m_new

    open_block = jnp.logical_and(
        live >= fan, base + fan * page_size - 1 <= offset)
    pl.when(open_block)(functools.partial(
        _for_units, functools.partial(_block, False)))
    pl.when(jnp.logical_and(live > 0, jnp.logical_not(open_block)))(
        functools.partial(_for_units, functools.partial(_block, True)))

    @pl.when(g == n_groups - 1)
    def _finalize():
        def unit(u):
            l = l_ref[u]
            if stat_lanes > 1:
                l = jnp.sum(l, axis=-1, keepdims=True)
            o_ref[u] = (acc_ref[u] / jnp.maximum(l, 1e-30)).astype(
                o_ref.dtype)
        _for_units(unit)


def _prefill_tiles(chunk: int, hkv: int, group: int, hd: int,
                   page_size: int, max_pages: int, itemsize: int):
    """``(fan, members, resident, stat_lanes)`` from what the
    call can see of its shapes. fan: pages a grid step fetches and
    works as one block (`_PREFILL_BLOCK_COLS` columns of them).
    members: the group's query heads in a unit, the most whose score
    tile [members*chunk, fan*page] stays under `_PREFILL_TILE_BYTES`;
    where one member's rows alone pass it the block narrows instead.
    resident: the units a grid row holds in VMEM at once, the most
    under `_PREFILL_VMEM_BUDGET` (all of them at the served shapes;
    the rest ride a grid axis and fetch the pages again). stat_lanes:
    m / l one lane wide, or lane-replicated where the block's columns
    and hd fill whole registers."""
    tile = _PREFILL_TILE_BYTES // 4
    fan = max(1, min(_PREFILL_MAX_FAN, max_pages,
                     min(_PREFILL_BLOCK_COLS, max(tile // chunk, _LANES))
                     // page_size))
    cols = fan * page_size
    members = max(m for m in range(1, group + 1)
                  if group % m == 0
                  and (m == 1 or m * chunk * cols <= tile))
    rows = members * chunk
    units = hkv * (group // members)
    # A unit's queries and output (each double-buffered), float32
    # accumulator and the two statistics (a lane-padded register row
    # each, whatever stat_lanes); the pages twice; one block's score
    # and probability tiles.
    unit_bytes = rows * (hd * (4 * itemsize + 4) + 2 * _LANES * 4)
    fixed = (4 * fan * hkv * page_size * hd * itemsize
             + rows * cols * (8 + itemsize))
    resident = max(
        [n for n in range(1, units + 1) if units % n == 0
         and fixed + n * unit_bytes <= _PREFILL_VMEM_BUDGET] or [1])
    full_lanes = hd % _LANES == 0 and cols % _LANES == 0
    return fan, members, resident, _LANES if full_lanes else 1


def paged_prefill_attention(q: jnp.ndarray, k_pages: jnp.ndarray,
                            v_pages: jnp.ndarray,
                            table_row: jnp.ndarray,
                            offset: jnp.ndarray,
                            true_len: jnp.ndarray, *,
                            sm_scale: Optional[float] = None,
                            interpret: Optional[bool] = None,
                            k_scales: Optional[jnp.ndarray] = None,
                            v_scales: Optional[jnp.ndarray] = None
                            ) -> jnp.ndarray:
    """One prompt chunk of ONE slot attending over its paged prefix.

    q: [C, hkv, group, hd] (global positions offset..offset+C-1, the
    chunk's K/V already written into the pages); table_row: [maxp]
    int32; offset/true_len: scalars. Tokens beyond true_len are pad —
    their rows compute garbage the caller discards. Returns
    [C, hkv, group, hd] in q's dtype (float32 statistics and
    accumulator inside), O(C * len) bandwidth via the skip-dead-pages
    index_maps; the pages a grid step fetches are worked as one block
    of keys (`_prefill_kernel`), sized from the call's shapes
    (`_prefill_tiles`). Both products take their
    operands in ``promote_types(q.dtype, pages.dtype)`` — bfloat16 as
    served, float32 throughout on float32 pages — and accumulate in
    float32; the probabilities are cast to that dtype for the second.
    """
    C, hkv, group, hd = q.shape
    page_size = k_pages.shape[2]
    max_pages = table_row.shape[0]
    mxu_dtype = jnp.promote_types(q.dtype, k_pages.dtype)
    fan, members, resident, stat_lanes = _prefill_tiles(
        C, hkv, group, hd, page_size, max_pages,
        jnp.dtype(mxu_dtype).itemsize)
    n_groups = -(-max_pages // fan)
    units_per_head = group // members
    units = hkv * units_per_head
    rows = members * C
    if sm_scale is None:
        sm_scale = hd ** -0.5
    interpret = _interpret_default(interpret)
    # [units, members*C, hd]: a unit's rows member-major, so the causal
    # mask is the chunk's own [C, cols] one, repeated.
    qf = q.astype(mxu_dtype).transpose(1, 2, 0, 3).reshape(units, rows, hd)
    offset = jnp.asarray(offset, jnp.int32)
    n_pages = (offset + jnp.asarray(true_len, jnp.int32)
               + page_size - 1) // page_size
    # SMEM: [offset, live pages]; and the page every in_spec addresses
    # at every step, looked up here once so that an index_map is one
    # SMEM load. A dead page keeps its in_spec on the page it fetched
    # last (its own last live one; the slot's last page where it never
    # had one): consecutive steps then address the same block and the
    # pipeline skips the fetch.
    meta = jnp.stack([offset, n_pages])
    last = jnp.maximum(n_pages - 1, 0)
    j = jnp.arange(n_groups * fan, dtype=jnp.int32)
    f = j % fan
    own = f + fan * ((last - f) // fan)
    j = jnp.where(j <= last, j, jnp.where(f <= last, own, last))
    schedule = table_row[j]

    quantized = k_scales is not None

    def _page_index(f):
        return lambda b, g, schedule_, meta_: (
            0, schedule_[g * fan + f], 0, 0)

    def _units_index(b, g, *_):
        return (b, 0, 0)

    page_spec = [pl.BlockSpec((hkv, 1, page_size, hd), _page_index(f))
                 for f in range(fan)]
    in_specs = [
        pl.BlockSpec((resident, rows, hd), _units_index),
        *page_spec,          # k pages, fan of them
        *page_spec,          # v pages
    ]
    operands = [qf, *([k_pages] * fan), *([v_pages] * fan)]
    if quantized:
        scale_spec = [pl.BlockSpec((hkv, 1, 1, page_size), _page_index(f))
                      for f in range(fan)]
        in_specs += [*scale_spec, *scale_spec]
        operands += [*([_scale_rows(k_scales)] * fan),
                     *([_scale_rows(v_scales)] * fan)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(units // resident, n_groups),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((resident, rows, hd), _units_index),
        scratch_shapes=[
            pltpu.VMEM((resident, rows, hd), jnp.float32),
            pltpu.VMEM((resident, rows, stat_lanes), jnp.float32),
            pltpu.VMEM((resident, rows, stat_lanes), jnp.float32),
        ],
    )
    kernel = functools.partial(_prefill_kernel, page_size=page_size,
                               sm_scale=sm_scale, n_groups=n_groups,
                               chunk=C, members=members,
                               units_per_head=units_per_head, fan=fan,
                               quantized=quantized)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((units, rows, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_PREFILL_VMEM_LIMIT),
        interpret=interpret,
        name='paged_prefill_attention',
    )(schedule, meta, *operands)
    return out.reshape(hkv, group, C, hd).transpose(2, 0, 1, 3)


# ---------------------------------------------------------------------------
# Verify kernel (speculative decoding): R query tokens per slot
# ---------------------------------------------------------------------------
def paged_verify_attention_reference(
        q: jnp.ndarray, k_pages: jnp.ndarray, v_pages: jnp.ndarray,
        block_tables: jnp.ndarray, lengths: jnp.ndarray,
        *, sm_scale: Optional[float] = None,
        k_scales: Optional[jnp.ndarray] = None,
        v_scales: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """q: [slots, R, hkv, group, hd] — R = spec_k+1 verify queries per
    slot at positions lengths[slot]..lengths[slot]+R-1 (their K/V
    already written, the decode write-then-attend contract). Query i
    attends to positions < lengths[slot] + i + 1 (causal within the
    draft run). Returns [slots, R, hkv, group, hd] fp32."""
    slots, R, hkv, group, hd = q.shape
    page = k_pages.shape[2]
    maxp = block_tables.shape[1]
    if sm_scale is None:
        sm_scale = hd ** -0.5
    k = _deq(k_pages, k_scales)[:, block_tables]
    v = _deq(v_pages, v_scales)[:, block_tables]
    k = k.transpose(1, 0, 2, 3, 4).reshape(slots, hkv, maxp * page, hd)
    v = v.transpose(1, 0, 2, 3, 4).reshape(slots, hkv, maxp * page, hd)
    s = jnp.einsum('brkgd,bksd->brkgs', q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    pos = jnp.arange(maxp * page)
    horizon = (lengths[:, None] + jnp.arange(R)[None, :] + 1)
    valid = pos[None, None, :] < horizon[:, :, None]   # [slots, R, S]
    s = jnp.where(valid[:, :, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum('brkgs,bksd->brkgd', p, v.astype(jnp.float32))


def _verify_kernel(tables_ref, lengths_ref, q_ref, k_ref, v_ref, *refs,
                   page_size: int, sm_scale: float, max_pages: int,
                   hkv: int, group: int, r_queries: int,
                   quantized: bool):
    """The decode kernel with R queries per (slot, head): rows are
    queries x group flattened (group fastest), each row's causal
    horizon is its query's position — one extra iota/div over the
    decode kernel, the same online-softmax accumulation per page."""
    if quantized:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = refs
    else:
        o_ref, acc_ref, m_ref, l_ref = refs
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    p = pl.program_id(1)
    del tables_ref  # consumed by the index_maps
    length = lengths_ref[b]
    # Pages holding ANY attendable position: the furthest query
    # (r_queries-1) sees positions < length + r_queries.
    n_pages = pl.cdiv(length + r_queries, page_size)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(p < n_pages)
    def _accumulate():
        for h in range(hkv):
            q = q_ref[0, h].astype(jnp.float32) * sm_scale  # [R*g, hd]
            k = k_ref[h, 0].astype(jnp.float32)             # [page, hd]
            v = v_ref[h, 0].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)         # [R*g, page]
            if quantized:
                s = s * ks_ref[h, 0]
            kpos = p * page_size + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            qi = jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0) // group
            s = jnp.where(kpos < length + qi + 1, s, _NEG_INF)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=-1, keepdims=True))
            pr = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(pr, axis=-1,
                                                  keepdims=True)
            if quantized:
                pr = pr * vs_ref[h, 0]
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                pr, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(p == max_pages - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_verify_attention(q: jnp.ndarray, k_pages: jnp.ndarray,
                           v_pages: jnp.ndarray,
                           block_tables: jnp.ndarray,
                           lengths: jnp.ndarray, *,
                           sm_scale: Optional[float] = None,
                           interpret: Optional[bool] = None,
                           k_scales: Optional[jnp.ndarray] = None,
                           v_scales: Optional[jnp.ndarray] = None
                           ) -> jnp.ndarray:
    """Speculative verify: R = spec_k+1 query tokens for EVERY slot in
    one kernel launch over the paged cache.

    q: [slots, R, hkv, group, hd]; lengths: [slots] int32 — the
    PRE-RUN length (query i sits at position lengths[slot]+i and
    attends to positions < lengths[slot]+i+1; the run's K/V must
    already be written, see ``append_run_pages``). The whole point:
    scoring R candidates streams each owned page through the chip
    ONCE — the same HBM traffic as a single decode step — so accepted
    drafts are nearly free bandwidth-wise. Fully-masked trailing pages
    accumulate exact zeros, so each query's result is bitwise the
    result the decode kernel produces for that position (the
    exact-greedy acceptance rule depends on this).

    Returns [slots, R, hkv, group, hd] fp32.
    """
    slots, R, hkv, group, hd = q.shape
    page_size = k_pages.shape[2]
    max_pages = block_tables.shape[1]
    if sm_scale is None:
        sm_scale = hd ** -0.5
    interpret = _interpret_default(interpret)
    # [slots, hkv, R*group, hd], group fastest: row r is query
    # r // group — same flattening rule as the prefill kernel.
    qf = q.transpose(0, 2, 1, 3, 4).reshape(slots, hkv, R * group, hd)

    quantized = k_scales is not None

    def _page_index(b, p, tables, lengths_):
        # Same revisiting-block rule as decode: steps past the slot's
        # attendable pages re-map to its last real page (no DMA).
        n_pages = jax.lax.div(lengths_[b] + R + page_size - 1,
                              page_size)
        j = jnp.minimum(p, jnp.maximum(n_pages - 1, 0))
        j = jnp.minimum(j, max_pages - 1)
        return (0, tables[b, j], 0, 0)

    in_specs = [
        pl.BlockSpec((1, hkv, R * group, hd),
                     lambda b, p, *_: (b, 0, 0, 0)),
        pl.BlockSpec((hkv, 1, page_size, hd), _page_index),
        pl.BlockSpec((hkv, 1, page_size, hd), _page_index),
    ]
    operands = [qf, k_pages, v_pages]
    if quantized:
        in_specs += [pl.BlockSpec((hkv, 1, 1, page_size), _page_index)] * 2
        operands += [_scale_rows(k_scales), _scale_rows(v_scales)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(slots, max_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hkv, R * group, hd),
                               lambda b, p, *_: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hkv, R * group, hd), jnp.float32),
            pltpu.VMEM((hkv, R * group, 1), jnp.float32),
            pltpu.VMEM((hkv, R * group, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(_verify_kernel, page_size=page_size,
                               sm_scale=sm_scale, max_pages=max_pages,
                               hkv=hkv, group=group, r_queries=R,
                               quantized=quantized)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, hkv, R * group, hd),
                                       jnp.float32),
        interpret=interpret,
        name='paged_verify_attention',
    )(block_tables, lengths, *operands)
    return out.reshape(slots, hkv, R, group, hd).transpose(0, 2, 1, 3, 4)


# ---------------------------------------------------------------------------
# Paged cache writes (pure JAX: dynamic_update_slices of the written rows)
# ---------------------------------------------------------------------------
# Every writer is a chain of ``dynamic_update_slice``s whose update is
# the rows written and nothing more. ``k_pages`` may be a whole folded
# pool (every layer's pages on the page axis, infer/paged_cache.py)
# carried through a layer scan: XLA applies such an update in place, so
# a write costs its rows. A scatter (``.at[:, pids, rows].set``) would
# not do: the TPU compiler relays the whole operand around it.
def _put(pages: jnp.ndarray, update: jnp.ndarray, pid, row
         ) -> jnp.ndarray:
    """pages[:, pid, row:row+n] = update ([hkv, n, ...]); n rows of one
    page, for the values ([.., hd]) and the row scales alike."""
    start = (0, pid, row) + (0,) * (pages.ndim - 3)
    return jax.lax.dynamic_update_slice(pages, update[:, None], start)


def _pools(k_pages, v_pages, k_scales, v_scales):
    """The arrays a writer updates, in the order it returns them: ``(k,
    v)``, plus the two scale pools on the int8 flavor."""
    if k_scales is None:
        return k_pages, v_pages
    return k_pages, v_pages, k_scales, v_scales


def _kv_rows(k_new: jnp.ndarray, v_new: jnp.ndarray, dtype,
             quantized: bool):
    """[..., hkv, hd] new rows -> what the pools hold, in ``_pools``'
    order: cast to the pages' dtype, or quantized, with their row
    scales after them."""
    if not quantized:
        return k_new.astype(dtype), v_new.astype(dtype)
    (k, ks), (v, vs) = quantize_rows(k_new), quantize_rows(v_new)
    return k, v, ks, vs


def _put_all(pools, rows, pid, row):
    """One position's (or one page's) rows into each pool."""
    return tuple(_put(pool, new, pid, row)
                 for pool, new in zip(pools, rows))


def write_chunk_pages(k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                      k_new: jnp.ndarray, v_new: jnp.ndarray,
                      table_row: jnp.ndarray, offset: jnp.ndarray,
                      k_scales: Optional[jnp.ndarray] = None,
                      v_scales: Optional[jnp.ndarray] = None):
    """Write a C-token chunk's K/V into a slot's pages.

    k_new/v_new: [C, hkv, hd] with C a multiple of page_size and offset
    page-aligned (the engine's chunk cap guarantees both), so the chunk
    covers whole pages: C/page dynamic_update_slice ops at table-looked-
    up page ids, no read-modify-write.

    With ``k_scales``/``v_scales`` (the int8 flavor) the chunk rows are
    quantized on write and the per-row scales land in the pool-aligned
    scale pages; returns ``(k_pages, v_pages, k_scales, v_scales)``
    then, the plain pair otherwise.
    """
    C, hkv, hd = k_new.shape
    page = k_pages.shape[2]
    assert C % page == 0, (C, page)
    rows = _kv_rows(k_new.transpose(1, 0, 2), v_new.transpose(1, 0, 2),
                    k_pages.dtype, k_scales is not None)  # [hkv, C, *]
    pools = _pools(k_pages, v_pages, k_scales, v_scales)
    first = jax.lax.div(offset, page)
    for i in range(C // page):
        pools = _put_all(pools,
                         [r[:, i * page:(i + 1) * page] for r in rows],
                         table_row[first + i], 0)
    return pools


def append_run_pages(k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                     k_new: jnp.ndarray, v_new: jnp.ndarray,
                     block_tables: jnp.ndarray, lengths: jnp.ndarray,
                     k_scales: Optional[jnp.ndarray] = None,
                     v_scales: Optional[jnp.ndarray] = None, *,
                     sink_page=0):
    """Append a RUN of R tokens' K/V per slot at positions
    ``lengths[slot] + i`` — the speculative-verify write (input token
    plus padded draft candidates in one step).

    k_new/v_new: [slots, R, hkv, hd]. One row write per slot and run
    position, chained in run order. Positions past the slot's
    block-table coverage (padded drafts of a slot the engine capped,
    inactive slots' garbage lanes) redirect to ``sink_page`` (page 0; a
    layer's own page 0 when the tables address a folded pool) — the
    table lookup is clamped and overridden, never allowed to alias a
    live page the way a clamped index would. With scales (int8 flavor)
    each run row is quantized on write and returns a 4-tuple.
    """
    page = k_pages.shape[2]
    slots, maxp = block_tables.shape
    R = k_new.shape[1]
    rows = _kv_rows(k_new, v_new, k_pages.dtype, k_scales is not None)
    pools = _pools(k_pages, v_pages, k_scales, v_scales)
    for i in range(R):
        pos = lengths + i
        col = pos // page
        pids = jnp.take_along_axis(
            block_tables, jnp.minimum(col, maxp - 1)[:, None],
            axis=1)[:, 0]
        pids = jnp.where(col < maxp, pids, sink_page)
        at = pos % page
        for s in range(slots):
            pools = _put_all(pools, [r[s, i][:, None] for r in rows],
                             pids[s], at[s])
    return pools


def append_token_pages(k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                       k_new: jnp.ndarray, v_new: jnp.ndarray,
                       block_tables: jnp.ndarray, lengths: jnp.ndarray,
                       k_scales: Optional[jnp.ndarray] = None,
                       v_scales: Optional[jnp.ndarray] = None, *,
                       sink_page=0):
    """Append one token's K/V per slot at position lengths[slot]: a run
    of one (``append_run_pages``).

    k_new/v_new: [slots, hkv, hd]. Slot i's row lands in page
    table[i, len//page] at row len%page: one ``[hkv, 1, 1, hd]`` update
    per slot. Active slots own distinct pages; inactive slots' garbage
    rows may share the sink page, where the last one written stays.
    With scales (int8 flavor) the row quantizes on write and returns a
    4-tuple.
    """
    return append_run_pages(k_pages, v_pages, k_new[:, None],
                            v_new[:, None], block_tables, lengths,
                            k_scales, v_scales, sink_page=sink_page)
