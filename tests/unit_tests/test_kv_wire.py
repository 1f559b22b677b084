"""KV prefix wire format: pack/unpack round trips, corruption
rejection, and the engine export/import contract.

The disaggregation tier (docs/serving.md "Disaggregated prefill/
decode") rides on three properties pinned here:

- int8 pool -> wire -> int8 pool is BYTE-EXACT (the bit-identity gate
  needs the transferred pages to hold the donor's exact bytes);
- bf16 pools quantize on export with the same absmax/127 scheme the
  int8 cache uses on write, so a transfer lands within the PR 11
  pinned tolerance (half a scale step per row);
- the donor side is READ-ONLY: an export moves no refcounts and frees
  no pages, even while the exported pages are CoW-shared with a live
  slot. Anything corrupt or mismatched raises WireError — the import
  caller degrades to plain recompute, never an error surface.
"""
import json
import struct
import zlib

import numpy as np
import pytest

from skypilot_tpu.infer import kv_wire
from skypilot_tpu.infer import paged_cache as paged_cache_lib

pytestmark = pytest.mark.jax

PAGE, L, HKV, HD = 16, 2, 2, 8


def _pages(rng, n):
    k = rng.integers(-127, 128, size=(L, HKV, n, PAGE, HD)).astype(
        np.int8)
    v = rng.integers(-127, 128, size=(L, HKV, n, PAGE, HD)).astype(
        np.int8)
    ks = rng.random((L, HKV, n, PAGE), dtype=np.float32) + 0.5
    vs = rng.random((L, HKV, n, PAGE), dtype=np.float32) + 0.5
    return k, v, ks, vs


# ---------- pure wire (host numpy, no device) -----------------------------
def test_pack_unpack_roundtrip_byte_exact():
    rng = np.random.default_rng(0)
    k, v, ks, vs = _pages(rng, 3)
    toks = list(range(3 * PAGE))
    blob = kv_wire.pack(toks, PAGE, k, v, ks, vs)
    blk = kv_wire.unpack(blob)
    assert blk.tokens == toks and blk.page_size == PAGE
    assert blk.n_pages == 3
    np.testing.assert_array_equal(blk.k, k)
    np.testing.assert_array_equal(blk.v, v)
    np.testing.assert_array_equal(blk.k_scales, ks)
    np.testing.assert_array_equal(blk.v_scales, vs)
    # Serialization is deterministic: re-pack of the decoded block is
    # the same bytes (replay/dedup rides on this).
    assert kv_wire.pack(blk.tokens, blk.page_size, blk.k, blk.v,
                        blk.k_scales, blk.v_scales) == blob


def test_wire_size_matches_page_wire_bytes():
    """The twin prices modeled transfers with page_wire_bytes — it must
    equal the real payload stride or the latency curve lies."""
    rng = np.random.default_rng(1)
    n = 2
    k, v, ks, vs = _pages(rng, n)
    blob = kv_wire.pack(list(range(n * PAGE)), PAGE, k, v, ks, vs)
    (hlen,) = struct.unpack_from('<I', blob, len(kv_wire.MAGIC))
    payload = len(blob) - len(kv_wire.MAGIC) - 4 - hlen
    assert payload == n * kv_wire.page_wire_bytes(L, HKV, PAGE, HD)


def test_quantize_dequantize_within_half_scale_step():
    """PR 11 tolerance: per-row absmax/127 scale, error <= scale/2;
    all-zero rows survive with scale 1.0 (not a divide-by-zero)."""
    rng = np.random.default_rng(11)
    x = (rng.normal(size=(L, HKV, 2, PAGE, HD)) * 5.0).astype(
        np.float32)
    x[0, 1, 1, 3] = 0.0                          # an all-zero row
    q, s = kv_wire.quantize_rows_np(x)
    assert q.dtype == np.int8 and s.shape == x.shape[:-1]
    err = np.abs(kv_wire.dequantize_rows_np(q, s) - x)
    assert (err <= s[..., None] * 0.5 + 1e-6).all(), float(err.max())
    assert (q[0, 1, 1, 3] == 0).all()
    assert float(s[0, 1, 1, 3]) == 1.0


def test_quantize_rows_np_bit_matches_device_quantizer():
    """The numpy mirror MUST stay bit-compatible with the jitted
    quantize_rows the int8 cache writes through — otherwise a bf16
    donor's export drifts from what its own int8 twin would hold and
    the byte-exact path silently weakens."""
    jnp = pytest.importorskip('jax.numpy')
    from skypilot_tpu.ops import paged_attention as pa
    rng = np.random.default_rng(7)
    x = (rng.normal(size=(3, 5, HD)) * 3.0).astype(np.float32)
    x[1, 2] = 0.0
    qn, sn = kv_wire.quantize_rows_np(x)
    qj, sj = pa.quantize_rows(jnp.asarray(x))
    np.testing.assert_array_equal(qn, np.asarray(qj))
    np.testing.assert_array_equal(sn, np.asarray(sj))


def test_pack_rejects_token_overflow():
    rng = np.random.default_rng(2)
    k, v, ks, vs = _pages(rng, 1)
    with pytest.raises(kv_wire.WireError):
        kv_wire.pack(list(range(PAGE + 1)), PAGE, k, v, ks, vs)


def _good_blob(n=2, seed=3):
    rng = np.random.default_rng(seed)
    return kv_wire.pack(list(range(n * PAGE)), PAGE, *_pages(rng, n))


@pytest.mark.parametrize('mutate, what', [
    (lambda b: b'XXYKV1\n' + b[7:], 'bad magic'),
    (lambda b: b[:9], 'truncated header length'),
    (lambda b: b[:20], 'truncated header'),
    (lambda b: b[:-5], 'payload size mismatch'),
    (lambda b: b + b'\x00' * 8, 'payload size mismatch'),
], ids=['magic', 'hdr-len', 'hdr', 'short-payload', 'long-payload'])
def test_unpack_rejects_malformed(mutate, what):
    with pytest.raises(kv_wire.WireError, match=what):
        kv_wire.unpack(mutate(_good_blob()))


def test_unpack_rejects_flipped_payload_byte():
    """One flipped bit anywhere in a page's payload fails that page's
    CRC — the corrupt-donor failpoint and any real wire damage both
    land here, and the puller recomputes."""
    blob = bytearray(_good_blob())
    blob[-1] ^= 0x40
    with pytest.raises(kv_wire.WireError, match='CRC'):
        kv_wire.unpack(bytes(blob))


def test_unpack_rejects_doctored_header():
    """A header rewritten to claim different geometry (with lengths
    kept consistent) still dies: the CRCs were computed over slices of
    the original stride."""
    blob = _good_blob()
    off = len(kv_wire.MAGIC)
    (hlen,) = struct.unpack_from('<I', blob, off)
    hdr = json.loads(blob[off + 4:off + 4 + hlen].decode())
    assert zlib.crc32(b'') not in hdr['page_crc32']
    hdr['n_pages'], hdr['page_crc32'] = 1, hdr['page_crc32'][:1]
    hdr['tokens'] = hdr['tokens'][:PAGE]
    hdr['page_size'] = 2 * PAGE   # keeps payload-size check consistent
    doctored = json.dumps(hdr, sort_keys=True).encode()
    blob2 = (kv_wire.MAGIC + struct.pack('<I', len(doctored))
             + doctored + blob[off + 4 + hlen:])
    with pytest.raises(kv_wire.WireError):
        kv_wire.unpack(blob2)


# ---------- engine export/import ------------------------------------------
@pytest.fixture(scope='module')
def params():
    jax = pytest.importorskip('jax')
    from skypilot_tpu.models import llama
    return llama.init_params(llama.LlamaConfig.tiny(),
                             jax.random.PRNGKey(0))


def _engine(params, kv_dtype='int8', n_pages=13):
    from skypilot_tpu.infer import engine as engine_lib
    from skypilot_tpu.models import llama
    return engine_lib.InferenceEngine(
        llama.LlamaConfig.tiny(), params,
        engine_lib.EngineConfig(n_slots=3, max_seq_len=128,
                                prefill_buckets=(16, 32),
                                prefill_chunk=32, paged=True,
                                page_size=16, n_pages=n_pages,
                                prefix_cache=True, kv_dtype=kv_dtype))


_PROMPT = [(i * 7 + 3) % 250 for i in range(40)]   # 2 full pages + tail


def test_int8_export_import_byte_exact_refcounts_untouched(params):
    """int8 pool -> wire -> int8 pool: the puller's grafted pages hold
    the donor's EXACT bytes (values and scales), and the donor side is
    read-only — refcounts, free-page count, and LRU-relevant stats all
    unchanged, even while the exported pages are shared with a live
    attach (the CoW case)."""
    donor = _engine(params)
    donor.generate([_PROMPT], max_new_tokens=4)
    pages, matched = donor.prefix.peek(_PROMPT, whole=True)
    assert matched == 32 and len(pages) == 2
    al = donor.allocator
    # CoW-share the cached pages into a slot, as a live request would.
    al.attach(0, pages)
    refs = {p: al.refcount(p) for p in pages}
    assert all(r == 2 for r in refs.values())
    free, hits, misses = al.free_pages, donor.prefix.hits, \
        donor.prefix.misses

    blob = donor._kv_export(_PROMPT)
    assert blob is not None
    assert {p: al.refcount(p) for p in pages} == refs, (
        'export moved refcounts on the donor')
    assert al.free_pages == free
    assert (donor.prefix.hits, donor.prefix.misses) == (hits, misses), (
        'export skewed the donor cache statistics')
    al.free(0)

    blk = kv_wire.unpack(blob)
    assert blk.tokens == _PROMPT[:32]
    held = paged_cache_lib.gather_pages(donor.cache, pages)
    np.testing.assert_array_equal(blk.k, np.asarray(held[0]))
    np.testing.assert_array_equal(blk.k_scales, np.asarray(held[2]))

    puller = _engine(params)
    grafted = puller._kv_import(blob)
    assert grafted == 2
    got, n = puller.prefix.peek(_PROMPT, whole=True)
    assert n == 32
    landed = paged_cache_lib.gather_pages(puller.cache, got)
    for have, want in zip(landed, (blk.k, blk.v, blk.k_scales,
                                   blk.v_scales)):
        np.testing.assert_array_equal(np.asarray(have), want)
    # Export from the puller re-serializes to the identical blob.
    assert puller._kv_export(_PROMPT) == blob


@pytest.mark.parametrize('src_pages,dst_pages', [
    ([3, 1], [2, 5]),          # out of order on both sides
    ([6], [6]),                # the same id, the last page
    ([1, 2, 3, 4], [4, 3, 2, 1]),
])
def test_pool_round_trip_through_the_wire_is_bit_identical(src_pages,
                                                           dst_pages):
    """gather -> pack -> unpack -> scatter between two int8 pools: the
    destination's pages hold the source's exact bytes in every layer,
    and no other page of the destination pool (compared as the raw
    physical arrays) changed."""
    import jax
    L, hkv, P, page, hd = 3, 2, 7, 4, 8
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    src = paged_cache_lib.init_paged_cache(L, 2, P, page, hkv, hd,
                                           dtype='int8')
    fill = paged_cache_lib.scatter_pages(
        src, np.arange(P),
        jax.random.randint(keys[0], (L, hkv, P, page, hd), -127, 128),
        jax.random.randint(keys[1], (L, hkv, P, page, hd), -127, 128),
        jax.random.uniform(keys[2], (L, hkv, P, page)),
        jax.random.uniform(keys[3], (L, hkv, P, page)))
    k, v, ks, vs = (np.asarray(a) for a in
                    paged_cache_lib.gather_pages(fill, src_pages))
    assert k.shape == (L, hkv, len(src_pages), page, hd)
    tokens = list(range(len(src_pages) * page))
    blk = kv_wire.unpack(kv_wire.pack(tokens, page, k, v, ks, vs))
    dst = paged_cache_lib.scatter_pages(src, dst_pages, blk.k, blk.v,
                                        blk.k_scales, blk.v_scales)
    for have, want in zip(paged_cache_lib.gather_pages(dst, dst_pages),
                          (k, v, ks, vs)):
        np.testing.assert_array_equal(np.asarray(have), want)
    rest = [p for p in range(P) if p not in dst_pages]
    for arr in paged_cache_lib.gather_pages(dst, rest):
        assert not np.asarray(arr).any()
    # Physically: layer l's page p sits at l * P + p, for K, V and scales.
    for layer in range(L):
        for s, d in zip(src_pages, dst_pages):
            np.testing.assert_array_equal(
                np.asarray(dst.k_pages[:, layer * P + d]),
                np.asarray(fill.k_pages[:, layer * P + s]))
            np.testing.assert_array_equal(
                np.asarray(dst.v_scales[:, layer * P + d]),
                np.asarray(fill.v_scales[:, layer * P + s]))


def test_import_grafts_only_past_local_boundary(params):
    """A puller that already caches page 1 grafts only page 2 — the
    boundary diff (peek(whole=True) // page) keeps existing pages (and
    any slots attached to them) untouched."""
    donor = _engine(params)
    donor.generate([_PROMPT], max_new_tokens=4)
    blob = donor._kv_export(_PROMPT)
    puller = _engine(params)
    puller.generate([_PROMPT[:20]], max_new_tokens=4)  # caches page 1
    _, have = puller.prefix.peek(_PROMPT, whole=True)
    assert have == 16
    free = puller.allocator.free_pages
    assert puller._kv_import(blob) == 1
    assert puller.allocator.free_pages == free - 1
    _, n = puller.prefix.peek(_PROMPT, whole=True)
    assert n == 32
    # Fully-cached puller: a second import is a no-op, not an error.
    assert puller._kv_import(blob) == 0


def test_bf16_round_trip_within_pinned_tolerance(params):
    """bf16 donor -> wire -> bf16 puller: the grafted pages dequantize
    within half a scale step of the donor's float pages (the PR 11
    bound), and greedy decode from the transferred prefix matches the
    donor's own continuation for the same prompt."""
    donor = _engine(params, kv_dtype='bfloat16')
    donor.generate([_PROMPT], max_new_tokens=4)
    pages, _ = donor.prefix.peek(_PROMPT, whole=True)
    blob = donor._kv_export(_PROMPT)
    blk = kv_wire.unpack(blob)
    want = np.asarray(
        paged_cache_lib.gather_pages(donor.cache, pages)[0], np.float32)
    deq = kv_wire.dequantize_rows_np(blk.k, blk.k_scales)
    err = np.abs(deq - want)
    bound = blk.k_scales[..., None] * 0.5 + 1e-6
    assert (err <= bound).all(), float(err.max())

    puller = _engine(params, kv_dtype='bfloat16')
    assert puller._kv_import(blob) == 2
    got, n = puller.prefix.peek(_PROMPT, whole=True)
    assert n == 32
    land = np.asarray(
        paged_cache_lib.gather_pages(puller.cache, got)[0], np.float32)
    # Grafted pages are the dequantized wire values cast to the pool
    # dtype — nothing further drifts on import.
    np.testing.assert_array_equal(
        land, deq.astype(puller.cache.k_pages.dtype).astype(
            np.float32))


def test_import_rejects_mismatched_page_size_and_geometry(params):
    puller = _engine(params)
    # A well-formed blob of 8-token pages: the engine's page-size
    # check fires before any allocation.
    k8 = np.ones((L, HKV, 1, 8, HD), np.int8)
    s8 = np.ones((L, HKV, 1, 8), np.float32)
    blob = kv_wire.pack(list(range(8)), 8, k8, k8, s8, s8)
    with pytest.raises(kv_wire.WireError, match='page size'):
        puller._kv_import(blob)
    # Wrong model geometry (head_dim) at the right page size.
    k2 = np.zeros((L, HKV, 1, 16, 4), np.int8)
    s2 = np.ones((L, HKV, 1, 16), np.float32)
    blob2 = kv_wire.pack(list(range(16)), 16, k2, k2, s2, s2)
    with pytest.raises(kv_wire.WireError, match='geometry'):
        puller._kv_import(blob2)
    # Corrupt payload degrades the same way (WireError, no graft).
    bad = bytearray(puller_blob := _good_engine_blob(params))
    bad[-1] ^= 0x01
    free = puller.allocator.free_pages
    with pytest.raises(kv_wire.WireError):
        puller._kv_import(bytes(bad))
    assert puller.allocator.free_pages == free, (
        'rejected import leaked pages')
    assert puller._kv_import(puller_blob) >= 1   # pristine blob fine


def _good_engine_blob(params):
    donor = _engine(params)
    donor.generate([_PROMPT], max_new_tokens=4)
    return donor._kv_export(_PROMPT)


def test_export_of_uncached_prefix_is_none(params):
    donor = _engine(params)
    assert donor._kv_export([9] * 40) is None
    donor.generate([_PROMPT], max_new_tokens=4)
    assert donor._kv_export([9] * 40) is None    # still a miss
