"""The paged decode kernel (`_decode_kernel`, which is the verify kernel
too): every length a slot can have against the dense-gather reference
in interpret mode, the pages it must not read, its name in a trace, and
the served shapes compiled for a described (not attached) v5e.

What "nothing is read" means here: every page that no live slot owns
(the pages a dead slot's table names, a live slot's table entries past
its frontier, the rest of the pool) holds NaN, and on the int8 flavour
a NaN scale row. The kernel runs on that pool, the reference on a copy
with the NaN replaced by zeros (its own masked columns would multiply
them), and the kernel's output is finite and the reference's.

Tolerances: as derived in test_paged_prefill_kernel.py. float32 pages
2e-5 (everything stays float32); bfloat16 pages atol 2**-8 * max|v|
(probabilities rounded to bfloat16 for the second product, the output
rounded to q's dtype, 2**-9 * max|v| each).
"""
import json
import os
import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from skypilot_tpu.ops import paged_attention as pa

pytestmark = pytest.mark.jax

# A lane-full geometry (hd 128, page 64: blocks of 8 pages) and a tiny
# one (page 16: blocks of 32 pages), each with a table longer than a
# block.
FULL = dict(hd=128, page=64, maxp=12)
TINY = dict(hd=64, page=16, maxp=40)
BLOCK = 512                                   # key columns a block


def _lengths(g):
    """One slot for each kind of length: dead, one token, a page to
    its last row, one past a page, some way into the second block, the
    table full; and a second dead slot between live ones."""
    page, maxp = g['page'], g['maxp']
    return [0, 1, page, page + 1, 0, BLOCK + page + 3, maxp * page]


CASES = {
    'tiny-group4': dict(TINY, hkv=2, group=4),
    'full-group4-hkv8': dict(FULL, hkv=8, group=4),
    'full-group16-hkv2': dict(FULL, hkv=2, group=16),
    'tiny-folded-pool': dict(TINY, hkv=2, group=4, layer=1),
    'full-folded-pool': dict(FULL, hkv=2, group=4, layer=2),
    'bf16-pages': dict(FULL, hkv=2, group=4, dtype='bfloat16'),
    'bf16-pages-group16': dict(FULL, hkv=2, group=16, dtype='bfloat16'),
    'bf16-pages-folded-pool': dict(FULL, hkv=8, group=4, dtype='bfloat16',
                                   layer=1),
    'int8-kv': dict(FULL, hkv=2, group=4, int8=True),
    'int8-kv-tiny': dict(TINY, hkv=2, group=4, int8=True),
    'int8-kv-bf16-q': dict(FULL, hkv=2, group=16, int8=True,
                           dtype='bfloat16'),
    # a pool whose pages do not pair up into whole 128-lane scale rows
    'int8-kv-odd-pool': dict(TINY, hkv=2, group=4, int8=True, spare=3),
    'int8-kv-folded-pool': dict(FULL, hkv=2, group=4, int8=True, layer=1),
    'every-slot-dead': dict(TINY, hkv=2, group=4, lengths=[0, 0, 0]),
    'first-and-last-slot-live': dict(FULL, hkv=2, group=4,
                                     lengths=[700, 0, 0, 0, 64]),
    'one-block-each': dict(FULL, hkv=2, group=4,
                           lengths=[512, 300, 512, 1]),
    'three-blocks': dict(TINY, hkv=2, group=4, maxp=80,
                         lengths=[0, 1280, 1025, 1024]),
    # a budget that holds blocks of 2 pages: 6 blocks a full table
    'narrow-blocks': dict(FULL, hkv=2, group=4, vmem_budget=1 << 19,
                          block_pages=2),
    'verify-r4': dict(TINY, hkv=2, group=4, R=4),
    'verify-r4-full-bf16': dict(FULL, hkv=2, group=4, R=4,
                                dtype='bfloat16'),
    'verify-r5-int8': dict(FULL, hkv=2, group=4, R=5, int8=True),
}


def _pool(c, seed):
    """``(q, k, v, tables, lengths, scales, (k, v, scales) without the
    NaN, max|v|)``. A verify case (R queries a slot) keeps R positions
    of every table free and has no dead slot: its lengths are what is
    cached BEFORE the run."""
    hkv, group, hd = c['hkv'], c['group'], c['hd']
    page, maxp, R = c['page'], c['maxp'], c.get('R', 1)
    dtype = jnp.dtype(c.get('dtype', 'float32'))
    lengths = np.asarray(c.get('lengths', _lengths(c)), np.int32)
    if R > 1:
        lengths = np.clip(lengths, 0, maxp * page - R)
    slots = len(lengths)
    rng = np.random.default_rng(seed)
    # A layer's pages: whole 128-lane rows of scales, but for `spare`.
    n_layer = -(-(slots * maxp + 1) // 8) * 8 + c.get('spare', 0)
    first = c.get('layer', 0) * n_layer
    P = first + n_layer
    tables = first + rng.permutation(np.arange(1, n_layer))[
        :slots * maxp].reshape(slots, maxp).astype(np.int32)
    # What a slot owns: the pages that hold a position some query of
    # it attends to.
    reach = lengths if R == 1 else lengths + R
    owned = np.zeros(P, bool)
    for s, n in enumerate(reach):
        owned[tables[s, :-(-int(n) // page)]] = True
    q_shape = ((slots, hkv, group, hd) if R == 1
               else (slots, R, hkv, group, hd))
    q = jnp.asarray(rng.normal(size=q_shape), dtype)
    k = rng.normal(size=(hkv, P, page, hd))
    v = rng.normal(size=(hkv, P, page, hd))
    if c.get('int8'):
        (k, ks), (v, vs) = (pa.quantize_rows(jnp.asarray(x, jnp.float32))
                            for x in (k, v))
        clean = (k, v, dict(k_scales=ks, v_scales=vs))
        nan = jnp.where(owned[None, :, None], 1.0, np.nan)
        scales = dict(k_scales=ks * nan, v_scales=vs * nan)
        v_max = float(jnp.max(jnp.abs(v.astype(jnp.float32)
                                      * vs[..., None])))
    else:
        k, v = jnp.asarray(k, dtype), jnp.asarray(v, dtype)
        clean = (k, v, {})
        nan = jnp.where(owned[None, :, None, None], 1.0, np.nan).astype(
            dtype)
        k, v, scales = k * nan, v * nan, {}
        v_max = float(jnp.max(jnp.abs(clean[1].astype(jnp.float32))))
    return (q, k, v, jnp.asarray(tables), jnp.asarray(lengths), scales,
            clean, v_max)


@pytest.mark.parametrize('case', CASES)
def test_decode_kernel_matches_reference(case, monkeypatch):
    c = CASES[case]
    if 'vmem_budget' in c:
        monkeypatch.setattr(pa, '_DECODE_VMEM_BUDGET', c['vmem_budget'])
    itemsize = 1 if c.get('int8') else jnp.dtype(
        c.get('dtype', 'float32')).itemsize
    block_pages = pa._decode_tiles(c['hkv'], c['hd'], c['page'],
                                   c['maxp'], itemsize)
    assert block_pages == c.get('block_pages', BLOCK // c['page'])
    q, k, v, tables, lengths, scales, clean, v_max = _pool(
        c, sorted(CASES).index(case))
    kernel, reference = (
        (pa.paged_decode_attention, pa.paged_decode_attention_reference)
        if q.ndim == 4 else
        (pa.paged_verify_attention, pa.paged_verify_attention_reference))
    with jax.default_matmul_precision('highest'):
        ref = reference(q, clean[0], clean[1], tables, lengths, **clean[2])
        out = kernel(q, k, v, tables, lengths, interpret=True, **scales)
    assert out.shape == q.shape and out.dtype == q.dtype
    out = np.asarray(out, np.float32)
    assert np.isfinite(out).all()
    tol = (dict(atol=2e-5, rtol=2e-5) if q.dtype == jnp.float32
           else dict(atol=2 ** -8 * v_max, rtol=0))
    live = np.asarray(lengths) > 0 if q.ndim == 4 else slice(None)
    np.testing.assert_allclose(out[live], np.asarray(ref)[live], **tol)
    if q.ndim == 4:     # a dead slot: zeros, whatever its table names
        assert not out[~live].any()


@pytest.mark.parametrize('flavor', ['float32', 'bfloat16', 'int8',
                                    'float32-full'])
def test_verify_row0_is_the_decode_kernel_bitwise(flavor):
    """R = 1 against R = 4: query 0 of a verify run is, bit for bit,
    the decode step at its position, because the two are one kernel
    body whose blocks do not depend on R and whose rows do not mix
    (the exact-greedy acceptance rule rides on it)."""
    c = dict(FULL if flavor.endswith('full') else TINY, hkv=2, group=4,
             R=4, int8=flavor == 'int8',
             dtype='float32' if flavor == 'int8' else flavor.split('-')[0])
    q, k, v, tables, lengths, scales, _, _ = _pool(c, 5)
    ver = pa.paged_verify_attention(q, k, v, tables, lengths,
                                    interpret=True, **scales)
    dec = pa.paged_decode_attention(q[:, 0], k, v, tables, lengths + 1,
                                    interpret=True, **scales)
    np.testing.assert_array_equal(np.asarray(ver[:, 0], np.float32),
                                  np.asarray(dec, np.float32))


def test_blocks_follow_the_shapes():
    """Mistral's and the hybrid's call shapes take 512-column blocks,
    int8 pages too; a page of 16 rows 32 pages a block; a short table
    all of it; and the number of queries is no argument at all."""
    for hkv, itemsize in ((8, 2), (2, 2), (8, 1), (2, 1)):
        assert pa._decode_tiles(hkv, 128, 64, 64, itemsize) == 8
    assert pa._decode_tiles(2, 64, 16, 40, 4) == 32
    assert pa._decode_tiles(2, 64, 16, 8, 4) == 8


# ---------------------------------------------------------------------------
# The kernel's name in a trace is what the benchmark's roofline readers
# find it by. Neither file is this test's to edit; a rename here must
# not silence one or feed the other.
# ---------------------------------------------------------------------------
def _pallas_call_names(fn, *args):
    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == 'pallas_call':
                names.append(eqn.params['name'])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return names


def _ops_match(metric):
    root = pathlib.Path(__file__).resolve().parents[2]
    with open(root / 'benchmark' / 'metrics' / f'{metric}.json') as f:
        return json.load(f)['ops_match']


def test_trace_names_reach_their_own_roofline_reader_only():
    decode_reader = _ops_match('kernel.paged_decode_roofline')
    prefill_reader = _ops_match('kernel.paged_prefill_roofline')
    q, k, v, tables, lengths, _, _, _ = _pool(
        dict(TINY, hkv=2, group=4), 0)
    (decode,) = _pallas_call_names(
        lambda *a: pa.paged_decode_attention(*a, interpret=True),
        q, k, v, tables, lengths)
    (verify,) = _pallas_call_names(
        lambda *a: pa.paged_verify_attention(*a, interpret=True),
        q[:, None], k, v, tables, lengths)
    (prefill,) = _pallas_call_names(
        lambda *a: pa.paged_prefill_attention(*a, interpret=True),
        q[:4], k, v, tables[0], jnp.int32(0), jnp.int32(4))
    assert any(n in decode for n in decode_reader), decode
    assert not any(n in decode for n in prefill_reader), decode
    assert any(n in prefill for n in prefill_reader), prefill
    assert not any(n in prefill for n in decode_reader), prefill
    # Speculation's kernel is neither reader's.
    assert not any(n in verify for n in decode_reader + prefill_reader)


# ---------------------------------------------------------------------------
# The served shapes, compiled for a described v5e: a VMEM overflow, a
# copy Mosaic cannot slice or a refused layout fails here, on a CPU.
# The topology is described inside a fixture, never while a module is
# imported (on-chip-measurement guide, section 2).
# ---------------------------------------------------------------------------
@pytest.fixture(scope='module')
def one_chip():
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:  # noqa: BLE001 — any failure to describe is a skip
        pytest.skip(f'no v5e:2x2 topology can be described here: {e}')
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope='module')
def no_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one: keep these out of it,
    and compile at the chip's own default matmul precision."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    with jax.default_matmul_precision('default'):
        yield
    jax.config.update('jax_enable_compilation_cache', before)
    compilation_cache.reset_cache()


# (slots, hkv, group, pool pages, table pages, int8 KV, queries a
# slot): Mistral's folded pool (32 layers x 480 pages, 24 slots of 4096
# tokens), the hybrid's (2 `*` blocks x 2048 pages, 64 slots of 2048),
# int8 KV, and a verify run of 5.
LOWERED = {
    'mistral': (24, 8, 4, 32 * 480, 64, False, 1),
    'mistral-int8-kv': (24, 8, 4, 32 * 480, 64, True, 1),
    'hybrid': (64, 2, 16, 2 * 2048, 32, False, 1),
    'hybrid-int8-kv': (64, 2, 16, 2 * 2048, 32, True, 1),
    'mistral-verify-r5': (24, 8, 4, 32 * 480, 64, False, 5),
}


@pytest.mark.parametrize('shape', LOWERED)
def test_decode_kernel_compiles_for_v5e(shape, one_chip, no_cache):
    slots, hkv, group, pool, maxp, int8, R = LOWERED[shape]
    hd, page = 128, 64

    def arg(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    pages = arg((hkv, pool, page, hd), jnp.int8 if int8 else jnp.bfloat16)
    q_shape = ((slots, hkv, group, hd) if R == 1
               else (slots, R, hkv, group, hd))
    args = [arg(q_shape, jnp.bfloat16), pages, pages,
            arg((slots, maxp), jnp.int32), arg((slots,), jnp.int32)]
    if int8:
        args += [arg((hkv, pool, page), jnp.float32)] * 2
    kernel, name = ((pa.paged_decode_attention, 'paged_attention_decode')
                    if R == 1 else
                    (pa.paged_verify_attention, 'paged_verify_attention'))

    def call(q, k, v, tables, n, ks=None, vs=None):
        return kernel(q, k, v, tables, n, interpret=False, k_scales=ks,
                      v_scales=vs)
    text = jax.jit(call).lower(*args).compile().as_text()
    assert 'tpu_custom_call' in text and f'%{name}' in text
