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
  non-contiguous in HBM; the pipeline gathers them page by page.
- Grid = (slots, kv_heads, max_pages) — but a slot only pays DMA for
  the pages it OWNS: for steps past the slot's last page the index_map
  re-maps to the previous step's page, and Pallas skips the fetch when
  consecutive steps map the same block (the revisiting-block rule the
  pipeline already implements). The kernel body masks those steps out.
  Decode bandwidth is therefore sum(ceil(len_i/page)) pages, the whole
  point of paging.
- Online softmax across the page axis (sequential innermost grid dim on
  TPU), fp32 accumulators in VMEM scratch that persist across the page
  steps of one (slot, head) and reinitialize at page 0.

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
def _prefill_kernel(table_ref, meta_ref, q_ref, *refs,
                    page_size: int, sm_scale: float, n_groups: int,
                    chunk: int, fan: int, quantized: bool):
    """One grid step processes `fan` pages (each its own scalar-
    prefetched in_spec/DMA): the fixed per-grid-step cost — not the
    bytes — dominates a one-page-per-step kernel, so fanning pages into
    a step amortizes it `fan`-fold."""
    k_refs = refs[:fan]
    v_refs = refs[fan:2 * fan]
    refs = refs[2 * fan:]
    if quantized:
        ks_refs = refs[:fan]
        vs_refs = refs[fan:2 * fan]
        refs = refs[2 * fan:]
    else:
        ks_refs = vs_refs = None
    o_ref = refs[0]
    acc_ref, m_ref, l_ref = refs[1:]
    g = pl.program_id(1)
    del table_ref
    offset = meta_ref[0]
    true_len = meta_ref[1]
    total = offset + true_len                   # slot frontier
    n_pages = pl.cdiv(total, page_size)

    @pl.when(g == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # q: [chunk*group, hd] (queries x group heads flattened so the MXU
    # sees one [C*g, page] matmul per page).
    q = q_ref[0].astype(jnp.float32) * sm_scale

    def _accumulate_page(f: int):
        p = g * fan + f

        @pl.when(p < n_pages)
        def _do():
            k = k_refs[f][0, 0].astype(jnp.float32)   # [page, hd]
            v = v_refs[f][0, 0].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)   # [C*g, page]
            if quantized:
                s = s * ks_refs[f][0, 0]
            # Causality in GLOBAL positions: row r is query
            # offset + r//g; column c is cached position p*page + c.
            qpos = offset + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0) // (s.shape[0] // chunk)
            kpos = p * page_size + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(kpos <= qpos, s, _NEG_INF)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=-1, keepdims=True))
            pr = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[...] = l_ref[...] * alpha + jnp.sum(
                pr, axis=-1, keepdims=True)
            if quantized:
                pr = pr * vs_refs[f][0, 0]
            acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
                pr, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[...] = m_new

    for f in range(fan):
        _accumulate_page(f)

    @pl.when(g == n_groups - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_prefill_attention(q: jnp.ndarray, k_pages: jnp.ndarray,
                            v_pages: jnp.ndarray,
                            table_row: jnp.ndarray,
                            offset: jnp.ndarray,
                            true_len: jnp.ndarray, *,
                            sm_scale: Optional[float] = None,
                            interpret: Optional[bool] = None,
                            pages_per_step: int = 8,
                            k_scales: Optional[jnp.ndarray] = None,
                            v_scales: Optional[jnp.ndarray] = None
                            ) -> jnp.ndarray:
    """One prompt chunk of ONE slot attending over its paged prefix.

    q: [C, hkv, group, hd] (global positions offset..offset+C-1, the
    chunk's K/V already written into the pages); table_row: [maxp]
    int32; offset/true_len: scalars. Tokens beyond true_len are pad —
    their rows compute garbage the caller discards. Returns
    [C, hkv, group, hd] fp32, O(C * len) bandwidth via the
    skip-dead-pages index_maps, with `pages_per_step` pages fanned into
    each grid step to amortize the fixed step cost.
    """
    C, hkv, group, hd = q.shape
    page_size = k_pages.shape[2]
    max_pages = table_row.shape[0]
    fan = max(1, min(pages_per_step, max_pages))
    n_groups = -(-max_pages // fan)
    if sm_scale is None:
        sm_scale = hd ** -0.5
    interpret = _interpret_default(interpret)
    # [hkv, C*group, hd]: queries x group flattened per KV head, group
    # fastest so row r maps to query r // group (contiguous rows share
    # a query position -> the causal iota stays a cheap div).
    qf = q.transpose(1, 0, 2, 3).reshape(hkv, C * group, hd)
    # meta in SMEM: [offset, true_len].
    meta = jnp.stack([jnp.asarray(offset, jnp.int32),
                      jnp.asarray(true_len, jnp.int32)])

    quantized = k_scales is not None

    def _page_index(f):
        def index(h, g, table, meta_):
            total = meta_[0] + meta_[1]
            n_pages = jax.lax.div(total + page_size - 1, page_size)
            j = jnp.minimum(g * fan + f, jnp.maximum(n_pages - 1, 0))
            return (h, table[j], 0, 0)
        return index

    page_spec = [pl.BlockSpec((1, 1, page_size, hd), _page_index(f))
                 for f in range(fan)]
    in_specs = [
        pl.BlockSpec((1, C * group, hd),
                     lambda h, g, *_: (h, 0, 0)),
        *page_spec,          # k pages, fan of them
        *page_spec,          # v pages
    ]
    operands = [qf, *([k_pages] * fan), *([v_pages] * fan)]
    if quantized:
        scale_spec = [pl.BlockSpec((1, 1, 1, page_size), _page_index(f))
                      for f in range(fan)]
        in_specs += [*scale_spec, *scale_spec]
        operands += [*([_scale_rows(k_scales)] * fan),
                     *([_scale_rows(v_scales)] * fan)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(hkv, n_groups),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, C * group, hd),
                               lambda h, g, *_: (h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((C * group, hd), jnp.float32),
            pltpu.VMEM((C * group, 1), jnp.float32),
            pltpu.VMEM((C * group, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(_prefill_kernel, page_size=page_size,
                               sm_scale=sm_scale, n_groups=n_groups,
                               chunk=C, fan=fan, quantized=quantized)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((hkv, C * group, hd),
                                       jnp.float32),
        interpret=interpret,
        name='paged_prefill_attention',
    )(table_row, meta, *operands)
    return out.reshape(hkv, C, group, hd).transpose(1, 0, 2, 3)


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
