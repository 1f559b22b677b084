"""Shared-prefix KV cache: radix tree, refcounted allocator, CoW,
LRU eviction, and the engine determinism gate.

The subsystem's ownership protocol (infer/prefix_cache.py docstring)
is the thing these tests pin: the tree holds one reference per cached
page, slots hold one more while mapped, a page frees only at its last
decref, eviction touches only tree-exclusive (refcount-1) leaves, and
the partial last page is never shared. The tier-1 gate: greedy outputs
are BIT-IDENTICAL with the cache on vs off over the mixed-length +
paged-preemption workload from test_infer_pipeline.py, at pipeline
depth 1 and 0 — and enabling the cache adds ZERO compiled programs
(prefill-from-offset reuses the existing chunk buckets; the CoW
program exists but never compiles in the steady state).
"""
import numpy as np
import pytest

pytestmark = pytest.mark.jax

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from skypilot_tpu.infer import engine as engine_lib  # noqa: E402
from skypilot_tpu.infer import paged_cache as paged_cache_lib  # noqa: E402,E501
from skypilot_tpu.infer import prefix_cache as prefix_cache_lib  # noqa: E402,E501
from skypilot_tpu.models import llama  # noqa: E402

CFG = llama.LlamaConfig.tiny()


# ---------- radix tree + allocator (pure host, no compiles) ---------------
def _alloc(n_pages=17, page=4, slots=3):
    return paged_cache_lib.PageAllocator(
        n_pages=n_pages, page_size=page, n_slots=slots,
        max_pages_per_slot=8)


def test_allocator_refcounts_attach_cow_double_free():
    al = _alloc()
    assert al.extend(0, 8)                      # 2 fresh pages, ref 1
    p0, p1 = al.owned_pages(0)
    assert al.refcount(p0) == al.refcount(p1) == 1

    # attach maps cached pages into an empty slot (refcount++), table
    # prefix in order.
    al.incref(p0)                               # simulate a tree ref
    al.free(0)                                  # slot drops refs
    assert al.refcount(p0) == 1 and al.refcount(p1) == 0
    al.attach(1, [p0])
    assert al.refcount(p0) == 2
    assert al.table()[1][0] == p0
    with pytest.raises(AssertionError):
        al.attach(1, [p0])                      # non-empty slot

    # cow: shared page swaps for a private copy, shared ref drops.
    free_before = al.free_pages
    pair = al.cow(1, 0)
    assert pair is not None and pair[0] == p0
    assert al.refcount(p0) == 1                 # tree ref survives
    assert al.refcount(pair[1]) == 1            # private copy
    assert al.table()[1][0] == pair[1]
    assert al.free_pages == free_before - 1
    # Unshared page: no-op.
    assert al.cow(1, 0) is None

    # Double decref of a freed page asserts (leak/corruption guard).
    al.free(1)
    with pytest.raises(AssertionError):
        al.decref(pair[1])
    al.decref(p0)                               # drop the "tree" ref
    assert al.free_pages == al.n_pages - 1      # conservation


def test_radix_match_caps_before_prompt_end_and_requires_full_chain():
    al = _alloc(page=4)
    tree = prefix_cache_lib.PrefixCache(al)
    toks = [1, 2, 3, 4, 5, 6, 7, 8, 9]          # 2 full pages + 1
    assert al.extend(0, len(toks))
    tree.donate(toks, 0)
    assert tree.cached_pages == 2               # partial 3rd page freed
    assert al.free_pages == al.n_pages - 1 - 2

    pages, n = tree.match(toks)
    assert n == 8 and len(pages) == 2
    # Exact-length prompt of 8: cap at the LAST FULL PAGE STRICTLY
    # BEFORE the end — at least one token always prefills.
    _, n = tree.match(toks[:8])
    assert n == 4
    # A mismatched FIRST block means nothing matches even if the
    # second block's tokens exist deeper in the tree (chaining).
    _, n = tree.match([9, 9, 9, 9] + toks[4:])
    assert n == 0
    # Mid-chain divergence stops the walk at the boundary.
    pages, n = tree.match(toks[:4] + [8, 8, 8, 8, 1])
    assert n == 4 and len(pages) == 1


def test_radix_duplicate_donation_deallocates():
    al = _alloc(page=4)
    tree = prefix_cache_lib.PrefixCache(al)
    toks = list(range(1, 9))
    assert al.extend(0, 8) and al.extend(1, 8)
    tree.donate(toks, 0)
    free_after_first = al.free_pages
    # Slot 1 computed the same blocks privately (it missed): donation
    # finds them cached and frees the duplicates.
    tree.donate(toks, 1)
    assert tree.cached_pages == 2
    assert al.free_pages == free_after_first + 2
    for pid in range(1, al.n_pages):
        assert al.refcount(pid) in (0, 1)


def test_evict_lru_leaf_first_and_only_unreferenced():
    al = _alloc(page=4)
    tree = prefix_cache_lib.PrefixCache(al)
    chain_a = [1, 2, 3, 4, 5, 6, 7, 8]          # donated first (older)
    chain_b = [9, 10, 11, 12]
    assert al.extend(0, 8)
    tree.donate(chain_a, 0)
    assert al.extend(0, 4)
    tree.donate(chain_b, 0)
    assert tree.cached_pages == 3

    # Attach chain_a's first page to a slot: refcount 2 — pinned, and
    # its ancestors can never be leaves while the deeper page exists.
    # (+1 sentinel: match never covers the final token of the query.)
    pages, n = tree.match(chain_a + [99])       # also touches LRU
    assert n == 8
    al.attach(1, pages[:1])

    # chain_b's page is now the LRU refcount-1 leaf: evicted first.
    assert tree.evict(1) == 1
    assert tree.cached_pages == 2
    _, n = tree.match(chain_b)
    assert n == 0

    # Remaining: chain_a leaf (refcount 1) evictable; its root page is
    # pinned by slot 1 even once it becomes a leaf.
    assert tree.evict(10) == 1
    assert tree.cached_pages == 1
    assert al.refcount(pages[0]) == 2
    al.free(1)
    assert tree.evict(10) == 1                  # unpinned -> reclaimed
    assert al.free_pages == al.n_pages - 1
    assert tree.evictions == 3


def test_copy_page_duplicates_kv_bytes():
    cache = paged_cache_lib.init_paged_cache(
        n_layers=2, n_slots=2, n_pages=5, page_size=4, n_kv_heads=2,
        head_dim=8, dtype=jnp.float32)
    # Layer l's page 2 holds 7 + l (K) and 3 + l (V): a copy that mixed
    # layers up would show.
    mark = jnp.arange(2, dtype=jnp.float32).reshape(2, 1, 1, 1, 1)
    cache = paged_cache_lib.scatter_pages(
        cache, [2], jnp.zeros((2, 2, 1, 4, 8)) + 7.0 + mark,
        jnp.zeros((2, 2, 1, 4, 8)) + 3.0 + mark)
    out = jax.jit(paged_cache_lib.copy_page)(
        cache, jnp.int32(2), jnp.int32(4))
    k, v, _, _ = paged_cache_lib.gather_pages(out, np.arange(5))
    for layer in range(2):
        assert (np.asarray(k[layer, :, 4]) == 7.0 + layer).all()
        assert (np.asarray(v[layer, :, 4]) == 3.0 + layer).all()
        assert (np.asarray(k[layer, :, 2]) == 7.0 + layer).all()
    assert (np.asarray(k[:, :, [0, 1, 3]]) == 0.0).all()
    assert (np.asarray(out.lengths) == 0).all()


# ---------- engine integration --------------------------------------------
@pytest.fixture(scope='module')
def params():
    return llama.init_params(CFG, jax.random.PRNGKey(0))


def _engine(params, prefix, n_pages=13, depth=1):
    return engine_lib.InferenceEngine(
        CFG, params,
        engine_lib.EngineConfig(n_slots=3, max_seq_len=128,
                                prefill_buckets=(16, 32),
                                prefill_chunk=32, pipeline_depth=depth,
                                paged=True, page_size=16,
                                n_pages=n_pages, prefix_cache=prefix))


# The mixed-length + paged-preemption workload from
# test_infer_pipeline.py (12 usable pages x 16 = 192 tokens for ~3x66
# forces preemption + resume), submitted TWICE so the second wave can
# hit the prefixes the first wave donated.
_PROMPTS = [[11] * 60, [23] * 60, [37] * 60,
            [5, 17, 101, 7], [9, 8, 7, 6, 5]]
_WORKLOAD = _PROMPTS + _PROMPTS


@pytest.fixture(scope='module')
def prefix_runs(params):
    """(eng_off, eng_on, out_off_d1, out_on_d1) over _WORKLOAD at
    pipeline depth 1."""
    off = _engine(params, prefix=False)
    on = _engine(params, prefix=True)
    out_off = [r.output_tokens
               for r in off.generate(_WORKLOAD, max_new_tokens=6)]
    out_on = [r.output_tokens
              for r in on.generate(_WORKLOAD, max_new_tokens=6)]
    return off, on, out_off, out_on


def test_greedy_identical_cache_on_vs_off_depth1(prefix_runs):
    off, on, out_off, out_on = prefix_runs
    assert on.metrics()['preemptions'] >= 1, (
        'workload never preempted — the gate is not exercising '
        'donation/re-match under page pressure')
    assert on.prefix.hits >= 1, (
        'workload never hit the prefix cache — the gate is vacuous')
    assert out_on == out_off, (
        'prefix cache changed greedy output (depth 1)')


def test_greedy_identical_cache_on_vs_off_depth0(prefix_runs):
    off, on, _, _ = prefix_runs
    off.set_pipeline_depth(0)
    on.set_pipeline_depth(0)
    out_off = [r.output_tokens
               for r in off.generate(_WORKLOAD, max_new_tokens=6)]
    out_on = [r.output_tokens
              for r in on.generate(_WORKLOAD, max_new_tokens=6)]
    assert out_on == out_off, (
        'prefix cache changed greedy output (depth 0)')


def test_prefix_cache_adds_zero_compiled_programs(prefix_runs):
    """Recompile stability: the prefix-on engine compiles exactly the
    programs the prefix-off engine does — prefill-from-offset reuses
    the chunk buckets (offset is traced), and the CoW program never
    compiles in the steady state. A second pass adds nothing."""
    off, on, _, _ = prefix_runs
    counts_off = off.compiled_counts()
    counts_on = on.compiled_counts()
    if -1 in counts_off.values() or -1 in counts_on.values():
        pytest.skip('jit._cache_size unavailable in this jax')
    assert counts_on == {**counts_off, 'cow': 0}, (counts_on,
                                                   counts_off)
    on.generate(_PROMPTS, max_new_tokens=6)
    assert on.compiled_counts() == counts_on, (
        'prefix-cache steady state triggered a recompile')


def test_pages_conserved_and_refcounts_sane_at_idle(prefix_runs):
    _, on, _, _ = prefix_runs
    al = on.allocator
    assert al.free_pages + on.prefix.cached_pages == al.n_pages - 1, (
        'page leak: free + cached must cover the whole pool at idle')
    for pid in range(1, al.n_pages):
        assert al.refcount(pid) in (0, 1), (
            f'page {pid} still multiply-referenced at idle')


def test_metrics_surface_prefix_counters(prefix_runs):
    _, on, _, _ = prefix_runs
    m = on.metrics()
    for key in ('prefix_hit_rate', 'prefix_tokens_saved',
                'prefix_cached_pages', 'prefix_evictions'):
        assert key in m
    assert 0.0 <= m['prefix_hit_rate'] <= 1.0
    assert m['prefix_tokens_saved'] >= on.prefix.page


def test_repeat_prompt_hits_and_stamps_ttft(prefix_runs):
    """A re-submitted prompt attaches its full-page prefix (prefill
    shrinks to the tail) and still reports a real TTFT — never 0/None
    for a request that streamed tokens."""
    _, on, _, _ = prefix_runs
    prompt = [91] * 33                          # 2 full pages + 1
    [first] = on.generate([prompt], max_new_tokens=4)
    [again] = on.generate([prompt], max_new_tokens=4)
    assert again.cached_tokens == 32
    assert again.output_tokens == first.output_tokens
    assert again.ttft is not None and again.ttft > 0
    assert first.ttft is not None and first.ttft > 0


def test_preempted_request_rematches_own_donated_prefix(params):
    """Recompute preemption + prefix cache: the preempted slot donates
    its clean pages, and the resume re-matches them — the recompute
    shrinks to the partial tail instead of re-prefilling everything."""
    on = _engine(params, prefix=True, n_pages=13)
    reqs = on.generate([[41] * 60, [43] * 60, [47] * 60],
                       max_new_tokens=6)
    m = on.metrics()
    assert m['preemptions'] >= 1
    # Every preemption's resume must have re-matched donated pages
    # (its own, or a peer's identical prefix — here all distinct).
    assert on.prefix.hits >= m['preemptions']
    assert all(len(r.output_tokens) == 6 for r in reqs)
    al = on.allocator
    assert al.free_pages + on.prefix.cached_pages == al.n_pages - 1


def test_eviction_under_pressure_without_preemption(params):
    """Sequential distinct prompts through a small pool: donations fill
    the tree until new prefills need pages back — the LRU evictor must
    reclaim cached (refcount-1) pages instead of preempting anyone."""
    on = _engine(params, prefix=True, n_pages=13)
    for seed in (3, 5, 7, 11, 13):
        [r] = on.generate([[seed] * 60], max_new_tokens=6)
        assert len(r.output_tokens) == 6
    m = on.metrics()
    assert m['prefix_evictions'] >= 1, (
        '5x(60+6) tokens through a 192-token pool with donation must '
        'evict cached pages')
    assert m['preemptions'] == 0, (
        'sequential requests must be satisfied by eviction, never '
        'preemption')
    al = on.allocator
    assert al.free_pages + on.prefix.cached_pages == al.n_pages - 1


def test_forced_shared_frontier_page_is_cowed(params):
    """Partial-last-page CoW: if a write range ever includes a shared
    page (no current match policy produces one — this forces it), the
    engine swaps in a private copy carrying the same KV bytes before
    dispatching the write."""
    on = _engine(params, prefix=True, n_pages=13)
    al = on.allocator
    assert al.extend(0, 20)                     # 2 pages
    old = al.owned_pages(0)
    al.incref(old[1])                           # simulate a tree ref
    on._attached_slots.add(0)                   # slot scans as attached
    k, v, _, _ = paged_cache_lib.gather_pages(on.cache, [old[1]])
    on.cache = paged_cache_lib.scatter_pages(
        on.cache, [old[1]], jnp.full_like(k, 5.0), v)
    on._unshare_write_range(0, 17, 20)
    new = al.owned_pages(0)
    assert new[0] == old[0]                     # untouched: not in range
    assert new[1] != old[1]                     # swapped for a copy
    assert al.refcount(old[1]) == 1             # "tree" ref survives
    assert al.refcount(new[1]) == 1
    k, _, _, _ = paged_cache_lib.gather_pages(on.cache, [new[1]])
    assert (np.asarray(k) == 5.0).all()
    # Cleanup: drop the simulated refs; pool must balance.
    al.free(0)
    al.decref(old[1])
    assert al.free_pages == al.n_pages - 1


def test_matched_offset_bucket_never_overshoots_cache(params):
    """A prefix-match offset is page-aligned, not chunk-cap-aligned:
    the rounded bucket must be clamped to the cache end, or extend
    refuses FOREVER (per-slot ceiling) and a perfectly fitting request
    dies cache_full after preempting innocents. Shape: page 16, cap 64,
    max_seq 128 — off=80, remaining=40 rounds to bucket 64 -> 144."""
    kw = dict(n_slots=2, max_seq_len=128, prefill_buckets=(16, 32, 64),
              prefill_chunk=64, paged=True, page_size=16)
    on = engine_lib.InferenceEngine(
        CFG, params,
        engine_lib.EngineConfig(prefix_cache=True, **kw))
    off_eng = engine_lib.InferenceEngine(
        CFG, params, engine_lib.EngineConfig(**kw))
    head = [73] * 80
    tail = [(i * 11 + 3) % 250 for i in range(40)]
    on.generate([head], max_new_tokens=4)       # donate 5 pages
    [got] = on.generate([head + tail], max_new_tokens=6)
    [want] = off_eng.generate([head + tail], max_new_tokens=6)
    assert got.cached_tokens == 80
    assert got.finish_reason != 'cache_full'
    assert got.output_tokens == want.output_tokens
    assert on.metrics()['preemptions'] == 0


def test_attach_deferral_rolls_back_and_corrupts_nothing(params):
    """Pool sized so a matching request ATTACHES its cached prefix but
    cannot extend for its first chunk (free=0, every cached page pinned
    by its own attach): the attach must roll back before the defer —
    otherwise the next decode step's inactive-slot garbage write lands
    in the shared page at table[slot,0] and corrupts the prefix for
    every later consumer. Greedy outputs must equal the cache-off
    oracle end to end."""
    kw = dict(n_slots=3, max_seq_len=128, prefill_buckets=(16, 32),
              prefill_chunk=32, paged=True, page_size=16, n_pages=13)
    on = engine_lib.InferenceEngine(
        CFG, params,
        engine_lib.EngineConfig(prefix_cache=True, **kw))
    oracle = engine_lib.InferenceEngine(
        CFG, params, engine_lib.EngineConfig(**kw))
    head = [55] * 80
    b_prompt = head + [1, 2, 3, 4]
    c_prompt = head + [9, 8, 7]
    # 1. Donor seeds the tree with head's 5 full pages.
    on.generate([head], max_new_tokens=4)
    assert on.prefix.cached_pages == 5
    # 2. A occupies the remaining 7 pages and keeps decoding.
    a = on.submit([66] * 100, max_new_tokens=24)
    while 100 not in (int(x) for x in on._slot_len):
        on.step()                               # A fully prefilled
    # 3. B matches head (attach 5) but free=0 and all cached pages are
    #    pinned by B's own attach -> first chunk cannot extend.
    b = on.submit(b_prompt, max_new_tokens=6)
    on.step()
    assert not b.done or b.finish_reason != 'cache_full'
    on.run_until_idle()
    # 4. C re-matches whatever head chain survived; its decode reads
    #    the cached pages — corruption would change its tokens.
    [c] = on.generate([c_prompt], max_new_tokens=6)
    assert len(b.output_tokens) == 6 and len(c.output_tokens) == 6
    wa = oracle.generate([[66] * 100], max_new_tokens=24)[0]
    wb = oracle.generate([b_prompt], max_new_tokens=6)[0]
    wc = oracle.generate([c_prompt], max_new_tokens=6)[0]
    assert a.output_tokens == wa.output_tokens
    assert b.output_tokens == wb.output_tokens, (
        'shared-prefix page was corrupted (or rollback broke resume)')
    assert c.output_tokens == wc.output_tokens, (
        'cached prefix page served corrupted KV to a later request')
    al = on.allocator
    assert al.free_pages + on.prefix.cached_pages == al.n_pages - 1


def test_chaos_storm_conserves_pages(params):
    """Submit/finish storm with mixed, partially-overlapping prompts:
    after every wave drains (and after a full evict), free_pages
    balances exactly — no double-free (the allocator asserts) and no
    leak."""
    rng = np.random.default_rng(42)
    on = _engine(params, prefix=True, n_pages=13)
    al = on.allocator
    base = [int(x) for x in rng.integers(1, 250, size=64)]
    for wave in range(6):
        prompts = []
        for _ in range(3):
            cut = int(rng.integers(4, 64))
            tail = [int(x) for x in rng.integers(1, 250, size=4)]
            prompts.append(base[:cut] + tail)
        reqs = on.generate(prompts,
                           max_new_tokens=int(rng.integers(1, 7)))
        assert all(r.done for r in reqs)
        assert al.free_pages + on.prefix.cached_pages == al.n_pages - 1
        for pid in range(1, al.n_pages):
            assert al.refcount(pid) in (0, 1)
    on.prefix.evict(al.n_pages)
    assert on.prefix.cached_pages == 0
    assert al.free_pages == al.n_pages - 1, 'storm leaked pages'


def test_prefix_cache_requires_paged(params):
    with pytest.raises(ValueError, match='paged'):
        engine_lib.InferenceEngine(
            CFG, params,
            engine_lib.EngineConfig(n_slots=2, max_seq_len=64,
                                    prefill_buckets=(16,),
                                    prefix_cache=True))
