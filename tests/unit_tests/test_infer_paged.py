"""Paged KV cache: kernels, allocator, and engine equivalence.

The paged engine must be a drop-in for the dense engine: same tokens
out (greedy), same continuous-batching behavior — while HBM scales with
tokens-in-flight and preemption/resume handles pool exhaustion.
Kernels run in interpret mode on the CPU mesh; the same code path runs
compiled on TPU (every cell of the benchmark serves from it).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from skypilot_tpu.infer import engine as engine_lib
from skypilot_tpu.infer import paged_cache as paged_cache_lib
from skypilot_tpu.models import llama
from skypilot_tpu.ops import paged_attention as pa

pytestmark = pytest.mark.jax


@pytest.fixture(scope='module', autouse=True)
def _highest_matmul_precision():
    """This module's comparisons are made at the highest matmul
    precision; scoped to its own tests, and restored, so that no other
    test in the process runs under a precision it did not ask for."""
    with jax.default_matmul_precision('highest'):
        yield


# ---------- kernels vs references -----------------------------------------
def _rand_pages(rng, hkv, P, page, hd):
    k = jnp.asarray(rng.normal(size=(hkv, P, page, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(hkv, P, page, hd)), jnp.float32)
    return k, v


def test_paged_decode_kernel_matches_reference():
    rng = np.random.default_rng(0)
    slots, hkv, group, hd = 4, 2, 4, 64
    page, P, maxp = 16, 32, 8
    q = jnp.asarray(rng.normal(size=(slots, hkv, group, hd)),
                    jnp.float32)
    k_pages, v_pages = _rand_pages(rng, hkv, P, page, hd)
    ids = rng.permutation(np.arange(1, P))[:slots * maxp - slots]
    tables = np.zeros((slots, maxp), np.int32)
    tables.flat[:len(ids)] = ids
    tables = jnp.asarray(tables)
    lengths = jnp.asarray([17, 64, 1, 100], jnp.int32)
    ref = pa.paged_decode_attention_reference(q, k_pages, v_pages,
                                              tables, lengths)
    out = pa.paged_decode_attention(q, k_pages, v_pages, tables,
                                    lengths, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_paged_prefill_kernel_matches_reference():
    rng = np.random.default_rng(1)
    hkv, group, hd = 2, 4, 64
    page, P, maxp, C = 16, 32, 8, 32
    q = jnp.asarray(rng.normal(size=(C, hkv, group, hd)), jnp.float32)
    k_pages, v_pages = _rand_pages(rng, hkv, P, page, hd)
    row = jnp.asarray(rng.permutation(np.arange(1, P))[:maxp],
                      jnp.int32)
    for off, tl in ((0, 32), (48, 20), (16, 1)):
        ref = pa.paged_prefill_attention_reference(
            q, k_pages, v_pages, row, off, tl)
        out = pa.paged_prefill_attention(
            q, k_pages, v_pages, row, jnp.int32(off), jnp.int32(tl),
            interpret=True)
        # Rows past true_len are pad garbage by contract.
        np.testing.assert_allclose(np.asarray(out)[:tl],
                                   np.asarray(ref)[:tl],
                                   atol=2e-5, rtol=2e-5,
                                   err_msg=f'off={off} tl={tl}')


def test_paged_verify_kernel_matches_reference():
    """The speculative verify kernel (R queries per slot) against its
    dense-gather reference, including slots whose run crosses a page
    boundary and a slot right at the pool's coverage edge."""
    rng = np.random.default_rng(2)
    slots, hkv, group, hd, R = 4, 2, 4, 64, 5
    page, P, maxp = 16, 32, 8
    q = jnp.asarray(rng.normal(size=(slots, R, hkv, group, hd)),
                    jnp.float32)
    k_pages, v_pages = _rand_pages(rng, hkv, P, page, hd)
    ids = rng.permutation(np.arange(1, P))[:slots * maxp - slots]
    tables = np.zeros((slots, maxp), np.int32)
    tables.flat[:len(ids)] = ids
    tables = jnp.asarray(tables)
    # 13+5 crosses a page; 64 starts a fresh page; 123+5 reaches the
    # table's final page (maxp*page = 128).
    lengths = jnp.asarray([13, 64, 1, 123], jnp.int32)
    ref = pa.paged_verify_attention_reference(q, k_pages, v_pages,
                                              tables, lengths)
    out = pa.paged_verify_attention(q, k_pages, v_pages, tables,
                                    lengths, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_paged_verify_query0_bitwise_matches_decode_kernel():
    """Query 0 of a verify run attends to exactly what a decode step
    at the same position attends to, and trailing fully-masked pages
    are exact no-ops in the online softmax — so the verify kernel's
    first lane must be BITWISE the decode kernel's output (the
    exact-greedy acceptance rule rides on this)."""
    rng = np.random.default_rng(3)
    slots, hkv, group, hd, R = 4, 2, 4, 64, 4
    page, P, maxp = 16, 32, 8
    qv = jnp.asarray(rng.normal(size=(slots, R, hkv, group, hd)),
                     jnp.float32)
    k_pages, v_pages = _rand_pages(rng, hkv, P, page, hd)
    ids = rng.permutation(np.arange(1, P))[:slots * maxp - slots]
    tables = np.zeros((slots, maxp), np.int32)
    tables.flat[:len(ids)] = ids
    tables = jnp.asarray(tables)
    lengths = jnp.asarray([13, 64, 0, 100], jnp.int32)
    ver = pa.paged_verify_attention(qv, k_pages, v_pages, tables,
                                    lengths, interpret=True)
    # Decode attends to pos < length (callers pass the already-bumped
    # length); verify query 0 sees pos < lengths + 1.
    dec = pa.paged_decode_attention(qv[:, 0], k_pages, v_pages,
                                    tables, lengths + 1,
                                    interpret=True, impl='native')
    np.testing.assert_array_equal(np.asarray(ver)[:, 0],
                                  np.asarray(dec))


def test_append_run_pages_writes_and_sink_redirects():
    """The run write lands each position in the owned page/row; the
    pad tail past the block table's coverage redirects to the sink
    page 0 instead of aliasing a live page through a clamped index."""
    hkv, hd, page, P, maxp = 2, 8, 4, 6, 2
    slots, R = 2, 3
    k_pages = jnp.zeros((hkv, P, page, hd), jnp.float32)
    v_pages = jnp.zeros((hkv, P, page, hd), jnp.float32)
    tables = jnp.asarray([[3, 4], [5, 0]], jnp.int32)
    # Slot 0 at len 3: run covers positions 3,4,5 -> page 3 row 3 then
    # page 4 rows 0,1. Slot 1 at len 7: position 7 = page 0 (its table
    # col 1 is the sink already), 8.. past maxp*page -> sink too.
    lengths = jnp.asarray([3, 7], jnp.int32)
    k_new = jnp.arange(slots * R * hkv * hd, dtype=jnp.float32).reshape(
        slots, R, hkv, hd) + 1.0
    k2, v2 = pa.append_run_pages(k_pages, v_pages, k_new, k_new,
                                 tables, lengths)
    k2 = np.asarray(k2)
    np.testing.assert_array_equal(k2[:, 3, 3], np.asarray(k_new[0, 0]))
    np.testing.assert_array_equal(k2[:, 4, 0], np.asarray(k_new[0, 1]))
    np.testing.assert_array_equal(k2[:, 4, 1], np.asarray(k_new[0, 2]))
    # Live pages other than the written ones stay zero.
    assert not k2[:, 5].any() and not k2[:, 1].any()
    assert not k2[:, 2].any()


@pytest.mark.parametrize('base', [0, 6])
def test_append_run_pages_sink_follows_the_tables_offset(base):
    """Tables that address a folded pool carry their layer's offset
    (here ``base``, one "layer" of 6 pages into a 12-page pool): the
    writer is handed that layer's own page 0 as ``sink_page`` and the
    pad tail lands there, not in physical page 0."""
    hkv, hd, page, P, maxp = 2, 8, 4, 12, 2
    k_pages = jnp.zeros((hkv, P, page, hd), jnp.float32)
    tables = jnp.asarray([[3, 4], [5, 0]], jnp.int32) + base
    lengths = jnp.asarray([3, 7], jnp.int32)
    k_new = jnp.arange(2 * 3 * hkv * hd, dtype=jnp.float32).reshape(
        2, 3, hkv, hd) + 1.0
    k2, _ = pa.append_run_pages(k_pages, k_pages, k_new, k_new, tables,
                                lengths, sink_page=base)
    k2 = np.asarray(k2)
    np.testing.assert_array_equal(k2[:, base + 3, 3], k_new[0, 0])
    np.testing.assert_array_equal(k2[:, base + 4, 1], k_new[0, 2])
    # Slot 1's position 7 is in its unowned column 1 (a zero entry:
    # this layer's page 0 once offset); 8 and 9 are past the table and
    # redirected: rows 3, 0 and 1 of this layer's sink, no other page.
    np.testing.assert_array_equal(k2[:, base, 3], k_new[1, 0])
    np.testing.assert_array_equal(k2[:, base, 0], k_new[1, 1])
    np.testing.assert_array_equal(k2[:, base, 1], k_new[1, 2])
    written = {int(p) for p in np.nonzero(k2.any(axis=(0, 2, 3)))[0]}
    assert written == {base, base + 3, base + 4}


def test_append_token_pages_lands_in_right_page_rows():
    hkv, P, page, hd, slots = 2, 6, 4, 8, 3
    k_pages = jnp.zeros((hkv, P, page, hd), jnp.float32)
    v_pages = jnp.zeros_like(k_pages)
    tables = jnp.asarray([[1, 2], [3, 0], [4, 5]], jnp.int32)
    lengths = jnp.asarray([5, 2, 0], jnp.int32)   # slot0 → page2 row1
    k_new = jnp.ones((slots, hkv, hd)) * jnp.asarray(
        [1., 2., 3.])[:, None, None]
    k2, _ = pa.append_token_pages(k_pages, v_pages, k_new, k_new,
                                  tables, lengths)
    k2 = np.asarray(k2)
    assert (k2[:, 2, 1] == 1.0).all()   # slot 0: page 2, row 5%4=1
    assert (k2[:, 3, 2] == 2.0).all()   # slot 1: page 3, row 2
    assert (k2[:, 4, 0] == 3.0).all()   # slot 2: page 4, row 0
    assert k2.sum() == hkv * hd * (1 + 2 + 3)   # nothing else touched


# ---------- int8 KV pages -------------------------------------------------
def test_quantize_rows_roundtrip_bound():
    """Per-row absmax int8: dequantized values stay within one scale
    step of the input (scale = absmax/127), and all-zero rows survive
    (scale 1.0, not a divide-by-zero)."""
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(3, 7, 64)) * 5.0, jnp.float32)
    x = x.at[1, 2].set(0.0)                       # an all-zero row
    q, s = pa.quantize_rows(x)
    assert q.dtype == jnp.int8 and s.shape == (3, 7)
    deq = np.asarray(q, np.float32) * np.asarray(s)[..., None]
    err = np.abs(deq - np.asarray(x))
    bound = np.asarray(s)[..., None] * 0.5 + 1e-6
    assert (err <= bound).all(), float(err.max())
    assert (np.asarray(q[1, 2]) == 0).all()
    assert float(s[1, 2]) == 1.0


@pytest.mark.parametrize('which', ['decode', 'prefill', 'verify'])
def test_int8_kernels_match_int8_references(which):
    """Each quantized kernel against the quantized reference on the
    SAME int8 pages + scales: kernel dequant must be the reference
    dequant (a missing/misaxed scale multiply shows up here even when
    the end-to-end divergence floor would absorb it)."""
    rng = np.random.default_rng(7)
    slots, hkv, group, hd, R = 4, 2, 4, 64, 4
    page, P, maxp, C = 16, 32, 8, 32
    kf, vf = _rand_pages(rng, hkv, P, page, hd)
    k_pages, k_scales = pa.quantize_rows(kf)
    v_pages, v_scales = pa.quantize_rows(vf)
    ids = rng.permutation(np.arange(1, P))[:slots * maxp - slots]
    tables = np.zeros((slots, maxp), np.int32)
    tables.flat[:len(ids)] = ids
    tables = jnp.asarray(tables)
    lengths = jnp.asarray([17, 64, 1, 100], jnp.int32)
    if which == 'decode':
        q = jnp.asarray(rng.normal(size=(slots, hkv, group, hd)),
                        jnp.float32)
        ref = pa.paged_decode_attention_reference(
            q, k_pages, v_pages, tables, lengths,
            k_scales=k_scales, v_scales=v_scales)
        out = pa.paged_decode_attention(
            q, k_pages, v_pages, tables, lengths, interpret=True,
            k_scales=k_scales, v_scales=v_scales)
    elif which == 'verify':
        q = jnp.asarray(rng.normal(size=(slots, R, hkv, group, hd)),
                        jnp.float32)
        ref = pa.paged_verify_attention_reference(
            q, k_pages, v_pages, tables, lengths,
            k_scales=k_scales, v_scales=v_scales)
        out = pa.paged_verify_attention(
            q, k_pages, v_pages, tables, lengths, interpret=True,
            k_scales=k_scales, v_scales=v_scales)
    else:
        q = jnp.asarray(rng.normal(size=(C, hkv, group, hd)),
                        jnp.float32)
        row = tables[0]
        ref = pa.paged_prefill_attention_reference(
            q, k_pages, v_pages, row, 16, 20,
            k_scales=k_scales, v_scales=v_scales)
        out = pa.paged_prefill_attention(
            q, k_pages, v_pages, row, jnp.int32(16), jnp.int32(20),
            interpret=True, k_scales=k_scales, v_scales=v_scales)
        ref, out = ref[:20], out[:20]   # pad rows are garbage
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_int8_write_paths_quantize_on_write():
    """append_token_pages with scales: the written row dequantizes
    back to (approximately) the input, and its scale row is set."""
    hkv, P, page, hd, slots = 2, 6, 4, 8, 2
    k_pages = jnp.zeros((hkv, P, page, hd), jnp.int8)
    v_pages = jnp.zeros_like(k_pages)
    k_scales = jnp.zeros((hkv, P, page), jnp.float32)
    v_scales = jnp.zeros_like(k_scales)
    tables = jnp.asarray([[1, 2], [3, 0]], jnp.int32)
    lengths = jnp.asarray([5, 2], jnp.int32)
    rng = np.random.default_rng(5)
    k_new = jnp.asarray(rng.normal(size=(slots, hkv, hd)) * 3,
                        jnp.float32)
    k2, v2, ks2, vs2 = pa.append_token_pages(
        k_pages, v_pages, k_new, k_new, tables, lengths,
        k_scales, v_scales)
    # Slot 0 -> page 2 row 1; slot 1 -> page 3 row 2.
    deq = np.asarray(k2[:, 2, 1], np.float32) * np.asarray(
        ks2[:, 2, 1])[:, None]
    want = np.asarray(k_new[0])
    assert np.abs(deq - want).max() <= np.abs(want).max() / 127 + 1e-6
    assert float(ks2[0, 3, 2]) > 0.0
    # Untouched pages keep zero scales.
    assert not np.asarray(ks2[:, 1]).any()


# ---------- allocator -----------------------------------------------------
def test_allocator_extend_free_and_sink_page():
    al = paged_cache_lib.PageAllocator(n_pages=9, page_size=4,
                                       n_slots=2, max_pages_per_slot=4)
    assert al.free_pages == 8          # page 0 reserved as sink
    assert al.extend(0, 10)            # 3 pages
    assert al.pages_of(0) == 3 and al.free_pages == 5
    assert 0 not in al.table()[0][:3], 'sink page must never be handed out'
    assert al.extend(0, 10)            # idempotent
    assert al.pages_of(0) == 3
    # 5 pages > max_pages_per_slot: refused.
    assert al.extend(1, 20) is False
    assert al.extend(1, 16)            # 4 pages: 5 free → ok
    assert al.free_pages == 1
    al.free(0)
    assert al.free_pages == 4
    assert al.extend(0, 4)
    # All-or-nothing: impossible request allocates nothing.
    before = al.free_pages
    assert not al.extend(0, 100)
    assert al.free_pages == before


# ---------- engine equivalence --------------------------------------------
def _engines(n_slots=3, max_seq_len=128, **paged_kw):
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    dense = engine_lib.InferenceEngine(
        cfg, params,
        engine_lib.EngineConfig(n_slots=n_slots, max_seq_len=max_seq_len,
                                prefill_buckets=(16, 32), eos_id=None,
                                prefill_chunk=32))
    paged = engine_lib.InferenceEngine(
        cfg, params,
        engine_lib.EngineConfig(n_slots=n_slots, max_seq_len=max_seq_len,
                                prefill_buckets=(16, 32), eos_id=None,
                                prefill_chunk=32, paged=True,
                                page_size=16, **paged_kw))
    return dense, paged


def test_paged_engine_matches_dense_greedy():
    dense, paged = _engines()
    prompts = [[5, 17, 101, 7], [9, 8, 7, 6, 5, 4, 3],
               [(i * 7 + 3) % 250 for i in range(40)]]   # multi-chunk
    out_d = [r.output_tokens for r in dense.generate(
        prompts, max_new_tokens=8)]
    out_p = [r.output_tokens for r in paged.generate(
        prompts, max_new_tokens=8)]
    assert out_d == out_p
    m = paged.metrics()
    assert m['paged'] and m['preemptions'] == 0
    # All pages returned once requests finished.
    assert m['pages_free'] == m['pages_total'] - 1


def test_one_prefill_program_when_tails_pad_to_the_chunk():
    """``prefill_tail_buckets=False``: every chunk pads to the chunk,
    so ONE prefill program compiles whatever the prompts' lengths, and
    the tokens are those of the engine with the ladder of buckets."""
    _, ladder = _engines()
    _, single = _engines(prefill_tail_buckets=False)
    assert ladder._buckets == [16, 32] and single._buckets == [32]
    prompts = [[5, 17, 101, 7], [9, 8, 7, 6, 5, 4, 3],
               [(i * 7 + 3) % 250 for i in range(40)],   # a chunk and a tail
               [(i * 5 + 1) % 250 for i in range(64)]]   # whole chunks
    out = [[r.output_tokens for r in e.generate(prompts, max_new_tokens=8)]
           for e in (ladder, single)]
    assert out[0] == out[1]
    assert ladder.compiled_counts()['prefill'] == 2
    assert single.compiled_counts()['prefill'] == 1
    al = single.allocator
    assert al.free_pages == al.n_pages - 1


def test_paged_engine_pool_reads_back_by_layer_and_page():
    """``gather_pages`` is the logical view of the folded pool: while a
    request holds pages, every layer shows rows in exactly those pages
    (plus the sink, where inactive slots' rows go) and the layers
    differ from one another."""
    _, paged = _engines()
    prompt = [(i * 7 + 3) % 250 for i in range(40)]    # 3 pages of 16
    req = paged.submit(prompt, max_new_tokens=4)
    while not req.output_tokens:
        paged.step()
    owned = paged.allocator.owned_pages(paged._slots.index(req))
    assert len(owned) == 3
    cache = paged.cache
    assert cache.k_pages.shape[1] == cache.n_layers * cache.n_pages
    k, v, ks, vs = paged_cache_lib.gather_pages(
        cache, np.arange(cache.n_pages))
    assert ks is None and vs is None
    assert k.shape == (cache.n_layers, 2, cache.n_pages, 16, 16)
    used = np.asarray(k).any(axis=(1, 3, 4))           # [L, P]
    for layer in range(cache.n_layers):
        assert set(np.nonzero(used[layer])[0]) <= set(owned) | {0}
        assert set(owned) <= set(np.nonzero(used[layer])[0])
    assert np.abs(np.asarray(k[0]) - np.asarray(k[1])).max() > 0
    while not req.done:
        paged.step()


def test_paged_engine_mixed_lengths_share_pool():
    """One engine, short+long prompts: the whole point. HBM accounting:
    peak pages ∝ tokens in flight, not slots x max_seq_len."""
    _, paged = _engines(n_slots=3, max_seq_len=128)
    prompts = [[1] * 4, [2] * 100, [3] * 7]
    reqs = paged.generate(prompts, max_new_tokens=4)
    assert all(len(r.output_tokens) == 4 for r in reqs)
    al = paged.allocator
    # 128-token slots would be 8 pages each dense; the short prompts
    # must not have paid that.
    assert al.free_pages == al.n_pages - 1


def test_paged_engine_preempts_and_resumes_on_pool_exhaustion():
    """A pool too small for all three requests at once: someone gets
    preempted, everyone still finishes with correct output."""
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    # 12 usable pages x 16 = 192 tokens of KV for 3 slots of up to 128.
    paged = engine_lib.InferenceEngine(
        cfg, params,
        engine_lib.EngineConfig(n_slots=3, max_seq_len=128,
                                prefill_buckets=(16, 32),
                                prefill_chunk=32, paged=True,
                                page_size=16, n_pages=13))
    dense = engine_lib.InferenceEngine(
        cfg, params,
        engine_lib.EngineConfig(n_slots=3, max_seq_len=128,
                                prefill_buckets=(16, 32),
                                prefill_chunk=32))
    prompts = [[11] * 60, [23] * 60, [37] * 60]
    out_d = [r.output_tokens for r in dense.generate(
        prompts, max_new_tokens=6)]
    reqs = paged.generate(prompts, max_new_tokens=6)
    out_p = [r.output_tokens for r in reqs]
    assert [len(o) for o in out_p] == [6, 6, 6]
    assert out_p == out_d, 'resume-by-recompute must not change tokens'
    assert paged.metrics()['preemptions'] >= 1, (
        'pool of 192 tokens cannot hold 3x(60+6) without preempting')
    assert paged.allocator.free_pages == paged.allocator.n_pages - 1


def test_paged_single_request_exceeding_pool_finishes_cache_full():
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    paged = engine_lib.InferenceEngine(
        cfg, params,
        engine_lib.EngineConfig(n_slots=2, max_seq_len=128,
                                prefill_buckets=(16, 32),
                                prefill_chunk=32, paged=True,
                                page_size=16, n_pages=4))  # 48 tokens
    # 24 tokens: 2 prefill pages + 1 decode page fits the 3-page pool;
    # decoding to 50 new tokens outgrows it -> cache_full, not a hang.
    [req] = paged.generate([[7] * 24], max_new_tokens=50)
    assert req.finish_reason == 'cache_full'
    assert len(req.output_tokens) >= 1
    # Admission is PADDING-AWARE: 40 tokens fit the raw pool (48) but
    # their bucket-padded prefill (48) + first decode page does not —
    # accepting would starve, so submit rejects.
    with pytest.raises(ValueError):
        paged.submit([7] * 40)
    with pytest.raises(ValueError):
        paged.submit([1] * 60)
