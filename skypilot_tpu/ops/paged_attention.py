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
  (``PrefetchScalarGridSpec``): they land in SMEM before the kernel
  starts, so a page id is one scalar load. The pages a slot touches
  are non-contiguous in HBM; they are gathered page by page, every KV
  head's rows of a page in one copy.
- A slot only pays DMA for the pages it OWNS, and bandwidth is
  sum(ceil(len_i/page)) pages, the whole point of paging. The prefill
  kernel gets there through its in_specs: for steps past the slot's
  last page the index_map re-maps to a page the in_spec already holds,
  and Pallas skips the fetch when consecutive steps map the same block
  (the revisiting-block rule the pipeline already implements). The
  decode kernel issues its own copies and simply issues none.
- Online softmax across the keys, fp32 accumulators in VMEM scratch.

The **decode** kernel (``_decode_kernel``) is ONE grid step: the pools
stay in HBM (``memory_space=pl.ANY``), q and the output are whole in
VMEM, and the kernel walks the live slots itself, so that a slot with
length 0 (one that is not decoding) costs a scalar compare: no copy,
no loop, a zero row out. A live slot's pages are worked in blocks of
512 key columns (``_decode_tiles``): one ``make_async_copy`` a live
page and pool (``pool.at[:, pid]``: [hkv, page, hd], 128 KB at
Mistral's shapes), none for a page past the frontier, into one of two
VMEM buffers; while a block is worked the next is in flight, the
slot's own next or, after its last, the NEXT LIVE SLOT's first, so
only the call's first block is waited for in full. Each KV head then
takes the block as one [cols, hd] operand (operands in
``promote_types(q.dtype, pages.dtype)``, float32 accumulation): one
[rows, cols] score tile and m / l / accumulator updated once a block.
The block's dead columns are masked, and the buffers start from zeros
so that a masked column never multiplies a NaN. Why one step and not a
grid over slots and pages: a grid step costs about 0.05 us for each
blocked operand, fetched or skipped (PR 28), and a page an in_spec
would have cost 160 us a call at 24 slots, dead or alive. The
**verify** kernel is the same body with R queries a slot: rows = R x
group, row r seeing r // group positions more; its blocks do not
depend on R and its rows do not mix, so query 0 is bitwise the decode
step at that position. The library kernel this replaced on the chip
(jax's ``paged_attention``, still behind ``impl='jax'``) rounds every
context up to a block of 8 pages and reads a dead slot's first block:
49 MB a call at Mistral's shapes where four chat contexts own 7-10
(PERF.md section 6, PR 30, has the timings).

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
  path; HBM-bandwidth-bound), nothing for a slot of length 0.
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
``_scale_rows``; the decode kernel copies them by whole 128-lane rows,
``_scale_lane_rows``) — the HBM stream is int8, roughly doubling the
resident pages per chip. Every entry point takes optional
``k_scales``/``v_scales`` and runs the same kernel body with or
without them; None means the pages hold their own values.

Each ``pallas_call`` carries a name (``paged_attention_decode``,
``paged_prefill_attention``, ``paged_verify_attention``): that is the
operation's name in a profiler trace, and what the benchmark's
roofline readers find the decode and prefill kernels by
(``benchmark/metrics/kernel.paged_*_roofline.json``, ``ops_match``;
tests/unit_tests/test_paged_decode_kernel.py holds each name to its
own reader). Without one the compiled custom call takes the name of
whatever scope encloses it (``closed_call`` inside a layer scan).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LANES = 128


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
# Decode kernel (and, with R queries a slot, the verify kernel)
# ---------------------------------------------------------------------------
# Key columns of a block: the pages a slot's loop step fetches (each
# its own copy, every KV head's rows in it) and works as one operand.
_DECODE_BLOCK_COLS = 512
# What the K and V blocks may hold in VMEM, each twice (the block at
# work and the one in flight), and the limit the call asks for.
_DECODE_VMEM_BUDGET = 16 << 20
_DECODE_VMEM_LIMIT = 48 << 20


def _decode_tiles(hkv: int, hd: int, page_size: int, max_pages: int,
                  itemsize: int) -> int:
    """Pages a block, from what the call can see of its shapes:
    `_DECODE_BLOCK_COLS` columns of them, no more than the table has or
    than `_DECODE_VMEM_BUDGET` holds four times over. The number of
    queries is no part of it: a verify run's blocks are a decode
    step's."""
    per_page = 4 * hkv * page_size * hd * itemsize
    return max(1, min(max_pages, _DECODE_BLOCK_COLS // page_size,
                      _DECODE_VMEM_BUDGET // per_page))


def _scale_lane_rows(scales: jnp.ndarray) -> jnp.ndarray:
    """[hkv, P, page] row scales -> [hkv, P / n, n * page], n pages'
    scales side by side in a row that fills whole 128-lane tiles: what
    the decode kernel's own copies can address. Mosaic slices a copy's
    source only along whole tiles, and one page's scale row (64 lanes
    at the served page size, `_scale_rows`' block) is half of one; the
    kernel copies the row a page lies in and keeps the page's lanes. A
    reshape where n divides P (the served pools), a padded copy of the
    scales elsewhere."""
    hkv, n_pages, page_size = scales.shape
    if page_size % _LANES == 0:
        return scales
    if _LANES % page_size:
        raise ValueError(
            f'int8 KV pages of {page_size} rows: the decode kernel reads '
            f'scale rows by {_LANES} lanes, which a page has to divide '
            'or be a multiple of')
    n = _LANES // page_size
    if n_pages % n:
        scales = jnp.pad(scales, ((0, 0), (0, -n_pages % n), (0, 0)))
    return scales.reshape(hkv, -1, _LANES)


def _decode_kernel(tables_ref, horizon_ref, q_ref, k_hbm, v_hbm, *refs,
                   page_size: int, sm_scale: float, max_pages: int,
                   group: int, r_queries: int, block_pages: int,
                   quantized: bool):
    """R queries of every slot over the slot's own pages, in ONE grid
    step: the pools stay in HBM and the kernel walks the live slots
    itself, so a dead slot (horizon 0) costs a scalar compare.

    horizon_ref[b] is what query 0 of slot b attends to (positions <
    horizon); query i sees i more. A slot's pages are worked in blocks
    of `block_pages`: one copy a live page and pool (``pool.at[:,
    pid]``: every KV head's rows of the page), none for a page past the
    frontier, into one of two buffers; while a block is worked the next
    one is in flight, the slot's own next or, after its last, the next
    live slot's first. Each KV head then takes the block as one
    [cols, hd] operand: one score tile of rows = R x group (group
    fastest), m / l / accumulator updated once a block."""
    if quantized:
        (ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf, sems,
         acc_ref, m_ref, l_ref) = refs
        scales = ((ks_hbm, ks_buf), (vs_hbm, vs_buf))
    else:
        o_ref, k_buf, v_buf, sems, acc_ref, m_ref, l_ref = refs
        scales = ()
    pools = ((k_hbm, k_buf), (v_hbm, v_buf))
    slots, hkv, rows, _ = q_ref.shape
    cols = block_pages * page_size
    pages_per_row = max(1, _LANES // page_size)     # `_scale_lane_rows`

    def owned_pages(b):
        horizon = horizon_ref[b]
        return jnp.where(
            horizon > 0,
            jnp.minimum(pl.cdiv(horizon + r_queries - 1, page_size),
                        max_pages), 0)

    def next_live(b):
        return jax.lax.while_loop(
            lambda b: jnp.logical_and(
                b < slots, horizon_ref[jnp.minimum(b, slots - 1)] <= 0),
            lambda b: b + 1, b)

    def each_copy(b, i, buf, act):
        """`act` on the copy of every live page of slot b's block i
        into buffer `buf`, pool by pool (a wait names the copy it
        waits for by the same descriptor)."""
        first = i * block_pages

        def page(j, carry):
            pid = tables_ref[b, first + j]
            at = pl.multiple_of(j * page_size, page_size)
            for n, (hbm, vmem) in enumerate(pools):
                act(pltpu.make_async_copy(
                    hbm.at[:, pid], vmem.at[buf, :, pl.ds(at, page_size)],
                    sems.at[n, buf]))
            for n, (hbm, vmem) in enumerate(scales, len(pools)):
                act(pltpu.make_async_copy(
                    hbm.at[:, pid // pages_per_row], vmem.at[buf, j],
                    sems.at[n, buf]))
            return carry
        jax.lax.fori_loop(
            0, jnp.minimum(owned_pages(b) - first, block_pages), page, 0)

    def start(b, i, buf):
        each_copy(b, i, buf, lambda copy: copy.start())

    def wait(b, i, buf):
        each_copy(b, i, buf, lambda copy: copy.wait())

    # A block's dead columns (its pages past the frontier) are masked,
    # not absent: a score there is replaced, whatever it is, but a
    # probability of 0 still multiplies its value row, and 0 x NaN is
    # NaN. VMEM that was never written may hold anything, so the value
    # buffers start from zeros and from then on hold zeros or an
    # earlier block's rows. A dead slot's output is zeros.
    v_buf[...] = jnp.zeros_like(v_buf)
    o_ref[...] = jnp.zeros_like(o_ref)

    def slot(carry):
        b, first_buf = carry
        following = next_live(b + 1)
        horizon = horizon_ref[b]
        blocks = pl.cdiv(owned_pages(b), block_pages)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        # Row r is query r // group, which sees r // group positions
        # more than query 0.
        reach = horizon + jax.lax.broadcasted_iota(
            jnp.int32, (rows, cols), 0) // group
        column = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)

        def block(i, carry):
            buf = (first_buf + i) % 2
            pl.when(i + 1 < blocks)(
                lambda: start(b, i + 1, 1 - buf))
            pl.when(jnp.logical_and(i + 1 == blocks, following < slots))(
                lambda: start(following, 0, 1 - buf))
            wait(b, i, buf)
            visible = column < reach - i * cols
            if quantized:
                # Where in its lane row each page's scales lie.
                lane = [tables_ref[b, jnp.minimum(i * block_pages + j,
                                                  max_pages - 1)]
                        % pages_per_row for j in range(block_pages)]

                def scale_columns(ref, h):
                    """Head h's [1, cols] scales of the block in `buf`."""
                    pages = []
                    for j in range(block_pages):
                        row = ref[buf, j, h:h + 1]
                        mine = row[:, :page_size]
                        for n in range(1, pages_per_row):
                            mine = jnp.where(
                                lane[j] == n,
                                row[:, n * page_size:(n + 1) * page_size],
                                mine)
                        pages.append(mine)
                    return jnp.concatenate(pages, axis=1)
            for h in range(hkv):
                # Operands go to the MXU as stored (q arrives in their
                # dtype); int8 pages convert exactly.
                q = q_ref[b, h]                             # [rows, hd]
                k = k_buf[buf, h].astype(q.dtype)           # [cols, hd]
                v = v_buf[buf, h].astype(q.dtype)
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)     # [rows, cols]
                if quantized:
                    s = s * scale_columns(ks_buf, h)
                s = jnp.where(visible, s, _NEG_INF)
                # m is kept in raw score units and the softmax scale
                # rides the exponent's argument, as in the prefill
                # kernel: the products stay the stored values'.
                m_prev = m_ref[h]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=-1, keepdims=True))
                alpha = jnp.exp((m_prev - m_new) * sm_scale)
                pr = jnp.exp((s - m_new) * sm_scale)
                l_ref[h] = l_ref[h] * alpha + jnp.sum(pr, axis=-1,
                                                      keepdims=True)
                if quantized:
                    # A dead column's scale is whatever the buffer or
                    # its table entry's lane row holds: out of the
                    # product.
                    pr = jnp.where(visible,
                                   pr * scale_columns(vs_buf, h), 0.0)
                acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                    pr.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
                m_ref[h] = m_new
            return carry
        jax.lax.fori_loop(0, blocks, block, 0)
        o_ref[b] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype)
        return following, (first_buf + blocks) % 2

    first_live = next_live(0)
    pl.when(first_live < slots)(lambda: start(first_live, 0, 0))
    jax.lax.while_loop(lambda carry: carry[0] < slots, slot,
                       (first_live, 0))


def _paged_queries(q: jnp.ndarray, k_pages: jnp.ndarray,
                   v_pages: jnp.ndarray, block_tables: jnp.ndarray,
                   horizon: jnp.ndarray, *, group: int,
                   sm_scale: Optional[float], interpret: Optional[bool],
                   k_scales: Optional[jnp.ndarray],
                   v_scales: Optional[jnp.ndarray], name: str
                   ) -> jnp.ndarray:
    """`_decode_kernel` over q [slots, hkv, R*group, hd] (group
    fastest); horizon [slots]: the positions query 0 attends to, 0 for
    a slot to leave alone. Returns q's shape and dtype."""
    slots, hkv, rows, hd = q.shape
    page_size = k_pages.shape[2]
    max_pages = block_tables.shape[1]
    if sm_scale is None:
        sm_scale = hd ** -0.5
    interpret = _interpret_default(interpret)
    quantized = k_scales is not None
    mxu_dtype = jnp.promote_types(q.dtype, k_pages.dtype)
    block_pages = _decode_tiles(hkv, hd, page_size, max_pages,
                                k_pages.dtype.itemsize)
    cols = block_pages * page_size
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    operands = [q.astype(mxu_dtype), k_pages, v_pages]
    scratch = [pltpu.VMEM((2, hkv, cols, hd), k_pages.dtype)] * 2
    if quantized:
        operands += [_scale_lane_rows(k_scales), _scale_lane_rows(v_scales)]
        lane_row = operands[-1].shape[2]
        scratch += [pltpu.VMEM((2, block_pages, hkv, lane_row),
                               jnp.float32)] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[whole] + [in_hbm] * (len(operands) - 1),
        out_specs=whole,
        scratch_shapes=scratch + [
            pltpu.SemaphoreType.DMA((len(operands) - 1, 2)),
            pltpu.VMEM((hkv, rows, hd), jnp.float32),
            pltpu.VMEM((hkv, rows, 1), jnp.float32),
            pltpu.VMEM((hkv, rows, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _decode_kernel, page_size=page_size, sm_scale=sm_scale,
        max_pages=max_pages, group=group, r_queries=rows // group,
        block_pages=block_pages, quantized=quantized)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_DECODE_VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )(block_tables, horizon, *operands)


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
    dense decode path's write-then-attend contract). **Length 0 leaves
    the slot alone**: no page of it is read and its output is zeros,
    which is what callers pass for a slot that is not decoding.
    k_scales/v_scales: [hkv, P, page] f32 row scales on the int8
    flavor; None = the pages' own values. Returns q's shape and dtype:
    both products take their operands in ``promote_types(q.dtype,
    pages.dtype)`` (bfloat16 as served, float32 throughout on float32
    pages) and accumulate in float32, m / l / accumulator float32.

    impl: 'auto' and 'native' run this module's kernel
    (``_decode_kernel``), compiled on a TPU and interpreted elsewhere.
    'jax' runs jax's library kernel on a TPU (bfloat16 pages, the
    default scale, hd a multiple of 128; it rounds every context up to
    a block of pages and reads a dead slot's first block): nothing
    serves through it since PR 30, it is kept for
    tests/benchmark/test_tpu_compile.py, which compiles both (ROADMAP
    D7).
    """
    slots, hkv, group, hd = q.shape
    interpret = _interpret_default(interpret)
    if impl == 'jax' and k_scales is not None:
        raise ValueError("impl='jax' is wired for bf16 pages only; "
                         "use the native kernel for kv_dtype=int8")
    if impl == 'jax' and not interpret:
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
        return out.reshape(slots, hkv, group, hd).astype(q.dtype)
    return _paged_queries(
        q, k_pages, v_pages, block_tables, lengths, group=group,
        sm_scale=sm_scale, interpret=interpret, k_scales=k_scales,
        v_scales=v_scales, name='paged_attention_decode')


# ---------------------------------------------------------------------------
# Prefill-chunk kernel
# ---------------------------------------------------------------------------
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
    # [slots, hkv, R*group, hd], group fastest: row r is query
    # r // group — same flattening rule as the prefill kernel.
    qf = q.transpose(0, 2, 1, 3, 4).reshape(slots, hkv, R * group, hd)
    out = _paged_queries(
        qf, k_pages, v_pages, block_tables, lengths + 1, group=group,
        sm_scale=sm_scale, interpret=interpret, k_scales=k_scales,
        v_scales=v_scales, name='paged_verify_attention')
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
