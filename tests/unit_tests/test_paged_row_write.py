"""The row writer (``ops/paged_attention.paged_row_write``): a decode or
verify step's new K/V rows into their pages, one Pallas call a layer,
here in interpret mode.

Against the chain of ``dynamic_update_slice``s it replaced (kept below
as ``_chain``, which the int8 flavor still takes): the pools come out
bit for bit the chain's over the LIVE slots, for bfloat16 and float32
pools, at a position that opens a page, at a page's last row, in the
table's last page, past the table's end, and for runs of 1 and 3 rows
that cross a page boundary. A slot that is not live writes nothing:
every page no live row lands in, the sink page among them, is bit for
bit what it was. The pool is two layers folded into one page axis, the
tables and the sink those of the second.

And the engine's count of the rows it hands the writer,
``kv_rows_written_total``: live slots x paging layers x rows a slot.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from skypilot_tpu.infer import engine as engine_lib
from skypilot_tpu.ops import paged_attention as pa

pytestmark = pytest.mark.jax

HKV, HD, PAGE, MAXP = 2, 128, 16, 4
BASE = 16                                   # the second layer's page 0
POOL_PAGES = 2 * BASE
# Slot 0 is live at the case's position; slot 1 is not live and its
# frontier lies in an owned page; slot 2 is live at 20; slot 3 is not
# live with a zeroed table row, so the chain wrote it into the sink.
TABLES = np.array([[3, 4, 5, 6], [7, 8, 0, 0], [9, 10, 11, 12],
                   [0, 0, 0, 0]], np.int32) + BASE
LIVE = np.array([True, False, True, False])
# Where slot 0's run starts, as a function of its length R.
CASES = {
    'opens_a_page': lambda R: PAGE,
    'last_row_of_a_page': lambda R: PAGE - 1,
    'crosses_a_page': lambda R: 2 * PAGE - 2,
    'last_page_of_the_table': lambda R: (MAXP - 1) * PAGE + 5,
    # R = 3: the table's last row, then two rows past it; R = 1: one
    # row past it.
    'past_the_table': lambda R: MAXP * PAGE + 2 - R,
}


def _chain(pools, rows, tables, lengths, sink, slots):
    """The chain of row writes every plain decode and verify step took
    before the row writer: one ``dynamic_update_slice`` a row and pool,
    in run order, then slot order; past the table's end, the sink."""
    page, maxp = pools[0].shape[2], tables.shape[1]
    for i in range(rows[0].shape[1]):
        pos = lengths + i
        col = pos // page
        pids = jnp.take_along_axis(
            tables, jnp.minimum(col, maxp - 1)[:, None], axis=1)[:, 0]
        pids = jnp.where(col < maxp, pids, sink)
        for s in slots:
            start = (0, pids[s], pos[s] % page)
            pools = tuple(
                jax.lax.dynamic_update_slice(
                    pool, new[s, i][:, None, None].astype(pool.dtype),
                    start + (0,) * (pool.ndim - 3))
                for pool, new in zip(pools, rows))
    return pools


def _case(case, R, dtype, seed=0):
    rng = np.random.default_rng(seed)
    shape = (HKV, POOL_PAGES, PAGE, HD)
    k = jnp.asarray(rng.normal(size=shape), dtype)
    v = jnp.asarray(rng.normal(size=shape), dtype)
    lengths = jnp.asarray([CASES[case](R), 17, 20, 0], jnp.int32)
    k_new = jnp.asarray(rng.normal(size=(4, R, HKV, HD)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(4, R, HKV, HD)), jnp.float32)
    return k, v, k_new, v_new, jnp.asarray(TABLES), lengths


def _bits(x):
    return np.asarray(jnp.asarray(x, jnp.float32))


@pytest.mark.parametrize('R', [1, 3])
@pytest.mark.parametrize('case', sorted(CASES))
@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
def test_live_rows_land_as_the_chain_put_them(dtype, case, R):
    k, v, k_new, v_new, tables, lengths = _case(case, R, dtype)
    got = pa.append_run_pages(k, v, k_new, v_new, tables, lengths,
                              sink_page=BASE, live=jnp.asarray(LIVE))
    want = _chain((k, v), (k_new, v_new), tables, lengths, BASE,
                  np.nonzero(LIVE)[0])
    for have, ref in zip(got, want):
        assert have.dtype == ref.dtype
        np.testing.assert_array_equal(_bits(have), _bits(ref))
    # The pages no live row lands in, the sink and the pages the slots
    # that are not live own among them, are bit for bit as they were.
    landed = set()
    for s in np.nonzero(LIVE)[0]:
        for p in range(int(lengths[s]), int(lengths[s]) + R):
            landed.add(int(TABLES[s, p // PAGE]) if p // PAGE < MAXP
                       else BASE)
    untouched = sorted(set(range(POOL_PAGES)) - landed)
    assert BASE in untouched or case == 'past_the_table'
    assert {8 + BASE, 7 + BASE} <= set(untouched)
    for have, before in zip(got, (k, v)):
        np.testing.assert_array_equal(_bits(have)[:, untouched],
                                      _bits(before)[:, untouched])
    if case == 'past_the_table':
        # The rows past the table land in the layer's own page 0.
        for i in range(R):
            pos = int(lengths[0]) + i
            if pos // PAGE >= MAXP:
                np.testing.assert_array_equal(
                    _bits(got[0])[:, BASE, pos % PAGE],
                    _bits(k_new[0, i].astype(dtype)))


def test_rows_that_share_the_sink_go_in_in_order():
    """Every slot live, and three of them writing into the sink (slot 3
    through its zeroed table row; slot 0 past its table's end, into the
    same rows): the sink holds what the chain's last write left."""
    k, v, k_new, v_new, tables, lengths = _case('past_the_table', 3,
                                                'bfloat16', seed=1)
    lengths = lengths.at[3].set(1)
    got = pa.append_run_pages(k, v, k_new, v_new, tables, lengths,
                              sink_page=BASE)
    want = _chain((k, v), (k_new, v_new), tables, lengths, BASE, range(4))
    for have, ref in zip(got, want):
        np.testing.assert_array_equal(_bits(have), _bits(ref))


@pytest.mark.parametrize('R', [1, 3])
def test_int8_pools_keep_the_chain(R):
    """With row scales the rows quantize on write and go in by the
    chain, every slot's, live or not: the pools it gave before."""
    k, v, k_new, v_new, tables, lengths = _case('crosses_a_page', R,
                                                'float32')
    rng = np.random.default_rng(2)
    k8 = jnp.asarray(rng.integers(-127, 128, k.shape), jnp.int8)
    v8 = jnp.asarray(rng.integers(-127, 128, v.shape), jnp.int8)
    ks = jnp.asarray(rng.uniform(.001, .02, k.shape[:-1]), jnp.float32)
    vs = jnp.asarray(rng.uniform(.001, .02, v.shape[:-1]), jnp.float32)

    def write(live):
        return pa.append_run_pages(k8, v8, k_new, v_new, tables, lengths,
                                   ks, vs, sink_page=BASE, live=live)
    (kq, kr), (vq, vr) = pa.quantize_rows(k_new), pa.quantize_rows(v_new)
    want = _chain((k8, v8, ks, vs), (kq, vq, kr, vr), tables, lengths,
                  BASE, range(4))
    for live in (None, jnp.asarray(LIVE)):
        got = write(live)
        assert len(got) == 4
        for have, ref in zip(got, want):
            np.testing.assert_array_equal(np.asarray(have), np.asarray(ref))
    names = [e.params['name'] for e in jax.make_jaxpr(write)(None).eqns
             if e.primitive.name == 'pallas_call']
    assert names == []


def test_the_writers_name_reaches_no_roofline_reader():
    """One call a layer, named ``paged_row_write`` in a trace: a name no
    ``kernel.*_roofline`` reader's ``ops_match`` holds, so the attention
    kernels' shares read what they read before."""
    import json
    import pathlib
    k, v, k_new, v_new, tables, lengths = _case('opens_a_page', 1,
                                                'bfloat16')
    jaxpr = jax.make_jaxpr(lambda *a: pa.append_run_pages(
        *a, sink_page=BASE))(k, v, k_new, v_new, tables, lengths)
    (name,) = [e.params['name'] for e in jaxpr.eqns
               if e.primitive.name == 'pallas_call']
    assert name == 'paged_row_write'
    metrics = pathlib.Path(__file__).resolve().parents[2] / 'benchmark' / (
        'metrics')
    for spec in metrics.glob('kernel.*_roofline.json'):
        needles = json.loads(spec.read_text()).get('ops_match', [])
        assert not any(n in name for n in needles), spec.name


# ---------- the engine's count -------------------------------------------
@pytest.mark.parametrize('spec_k', [0, 2])
def test_kv_rows_written_counts_live_slots_and_paging_layers(tiny_params,
                                                             spec_k,
                                                             monkeypatch):
    """``kv_rows_written_total`` grows by the step's live slots x the
    layers that page K/V x the rows a slot writes (spec_k + 1 in a
    verify step), counted from the step's own batch: three slots, two
    requests, so every step leaves a slot that is not live out."""
    from skypilot_tpu.models import llama
    config = llama.LlamaConfig.tiny()
    eng = engine_lib.InferenceEngine(
        config, tiny_params,
        engine_lib.EngineConfig(n_slots=3, max_seq_len=128,
                                prefill_buckets=(16, 32), prefill_chunk=32,
                                paged=True, page_size=16, n_pages=16,
                                spec_k=spec_k))
    steps = []
    for name, rows in (('_dispatch_decode', lambda *a: 1),
                       ('_dispatch_mixed', lambda *a: 1),
                       ('_dispatch_verify',
                        lambda d, mat, lens: mat.shape[1] + 1)):
        def spy(decoding, *rest, _real=getattr(eng, name), _rows=rows):
            steps.append((len(decoding), _rows(decoding, *rest)))
            return _real(decoding, *rest)
        monkeypatch.setattr(eng, name, spy)
    # A draft every step, whatever the context holds.
    monkeypatch.setattr(eng._drafter, 'propose',
                        lambda context, k, memo=None: [1] * k)
    eng.generate([[5, 6, 7, 8] * 6, [9, 10, 11] * 5], max_new_tokens=8,
                 temperature=0.0)
    m = eng.metrics()
    assert steps and all(live < 3 for live, _ in steps)
    assert any(rows > 1 for _, rows in steps) == bool(spec_k)
    assert m['decode_steps'] == len(steps)
    assert m['kv_rows_written_total'] == config.n_layers * sum(
        live * rows for live, rows in steps)
