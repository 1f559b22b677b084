"""The paged prefill kernel's tiles: every body the kernel can take
(open block, masked block, dead pages in a block, dead step, blocked
rows, blocked units) against the dense-gather reference in interpret mode,
and the real shapes compiled for a described (not attached) v5e.

Tolerances. float32 pages: the standing 2e-5 (everything stays
float32). bfloat16 pages: both products take bfloat16 operands, whose
products are exact in the float32 accumulator, so the scores match the
reference's; what differs is (a) the probabilities rounded to bfloat16
for the second product, a relative 2**-9 each, which moves an output by
at most 2**-9 * max|v| (the output is a convex combination of v rows),
and (b) the output itself rounded to bfloat16 (q's dtype), 2**-9 *
|out| <= 2**-9 * max|v|. So atol = 2**-8 * max|v|, no rtol.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from skypilot_tpu.ops import paged_attention as pa

pytestmark = pytest.mark.jax

# A lane-full geometry (hd 128, page 64: lane-replicated statistics,
# blocks of 8 pages) and a tiny one (one-lane statistics, blocks of 16
# pages).
FULL = dict(hd=128, page=64, maxp=12, C=64)
TINY = dict(hd=64, page=16, maxp=24, C=32)

CASES = {
    # offset on a page boundary that is no chunk boundary
    'offset-page-not-chunk-aligned': dict(TINY, off=48, tl=32),
    'offset-page-not-chunk-aligned-full': dict(FULL, off=192, tl=64),
    'true-len-short': dict(FULL, off=128, tl=5),
    'true-len-one': dict(TINY, off=16, tl=1),
    # TINY: a block is 16 pages = 256 keys; FULL: 8 pages = 512 keys
    'crosses-block': dict(TINY, off=288, tl=32),
    'crosses-block-full': dict(FULL, off=576, tl=64),
    'open-block-then-two-live-pages': dict(FULL, off=512, tl=64),
    'full-block-masked': dict(FULL, off=448, tl=64),
    'dead-pages-in-last-block': dict(TINY, off=272, tl=20),
    'dead-pages-half-block': dict(FULL, off=128, tl=64),
    'dead-pages-quarter-block': dict(FULL, off=256, tl=100, C=128),
    'one-page-prompt': dict(TINY, C=16, off=0, tl=7),
    'one-page-prompt-full': dict(FULL, off=0, tl=50),
    'group-4-rows': dict(FULL, group=4, off=64, tl=64),
    # 16 x 128 rows x 512 columns pass the score tile's budget: the
    # group splits into two units of 8 members.
    'group-16-rows-blocked': dict(FULL, group=16, C=128, hkv=1, hd=128,
                                  off=512, tl=128, rows_blocked=True),
    # a budget that holds one unit: the units ride the grid
    'units-blocked': dict(FULL, off=512, tl=64, vmem_budget=3 << 20),
    'bf16-pages': dict(FULL, dtype='bfloat16', off=576, tl=60),
    'bf16-pages-short': dict(FULL, dtype='bfloat16', off=0, tl=33),
    'int8-kv': dict(FULL, int8=True, off=576, tl=64),
    'int8-kv-tiny': dict(TINY, int8=True, off=272, tl=20),
    'int8-kv-bf16-q': dict(FULL, int8=True, dtype='bfloat16', off=192,
                           tl=64),
}


def _tiles(c, itemsize=4):
    return pa._prefill_tiles(c['C'], c.get('hkv', 2), c.get('group', 4),
                             c['hd'], c['page'], c['maxp'], itemsize)


@pytest.mark.parametrize('case', CASES)
def test_prefill_kernel_matches_reference(case, monkeypatch):
    c = CASES[case]
    hkv, group, hd = c.get('hkv', 2), c.get('group', 4), c['hd']
    page, maxp, C, off, tl = c['page'], c['maxp'], c['C'], c['off'], c['tl']
    dtype = jnp.dtype(c.get('dtype', 'float32'))
    if 'vmem_budget' in c:
        monkeypatch.setattr(pa, '_PREFILL_VMEM_BUDGET', c['vmem_budget'])
        assert _tiles(c)[2] < hkv * group // _tiles(c)[1]
    if c.get('rows_blocked'):
        assert _tiles(c)[1] < group
    rng = np.random.default_rng(sorted(CASES).index(case))
    P = maxp + 3
    q = jnp.asarray(rng.normal(size=(C, hkv, group, hd)), dtype)
    k = rng.normal(size=(hkv, P, page, hd))
    v = rng.normal(size=(hkv, P, page, hd))
    scales = {}
    if c.get('int8'):
        (k, ks), (v, vs) = (pa.quantize_rows(jnp.asarray(x, jnp.float32))
                            for x in (k, v))
        scales = dict(k_scales=ks, v_scales=vs)
        v_max = float(jnp.max(jnp.abs(v.astype(jnp.float32)
                                      * vs[..., None])))
    else:
        k, v = jnp.asarray(k, dtype), jnp.asarray(v, dtype)
        v_max = float(jnp.max(jnp.abs(v.astype(jnp.float32))))
    row = jnp.asarray(rng.permutation(np.arange(1, P))[:maxp], jnp.int32)
    with jax.default_matmul_precision('highest'):
        ref = pa.paged_prefill_attention_reference(q, k, v, row, off, tl,
                                                   **scales)
        out = pa.paged_prefill_attention(q, k, v, row, jnp.int32(off),
                                         jnp.int32(tl), interpret=True,
                                         **scales)
    assert out.shape == q.shape and out.dtype == q.dtype
    tol = (dict(atol=2e-5, rtol=2e-5) if dtype == jnp.float32
           else dict(atol=2 ** -8 * v_max, rtol=0))
    # Rows past true_len are pad garbage by contract.
    np.testing.assert_allclose(np.asarray(out[:tl], np.float32),
                               np.asarray(ref[:tl]), **tol)


def test_tiles_follow_the_shapes():
    """Mistral's and the hybrid's call shapes: a 512-column block, the
    whole group or a quarter of it in a unit, every unit resident,
    lane-replicated statistics."""
    for hkv, group in ((8, 4), (2, 16)):
        for chunk in (64, 128, 256):
            fan, members, resident, lanes = pa._prefill_tiles(
                chunk, hkv, group, 128, 64, 64, 2)
            assert (fan, lanes) == (8, 128)
            assert members == min(group, 1024 // chunk)
            assert resident == hkv * group // members


# ---------------------------------------------------------------------------
# The served shapes, compiled for a described v5e: a VMEM overflow or a
# refused layout fails here, on a CPU. The topology is described inside
# a fixture, never while a module is imported (on-chip-measurement
# guide, section 2).
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


# (hkv, group, chunk, pool pages, table pages, int8 KV): Mistral's
# folded pool (32 layers x 480 pages, 4096-token tables) at every
# prefill bucket, the hybrid's (2 `*` blocks x 2048 pages), and the
# int8-KV flavour chip_smoke.py runs.
LOWERED = {
    'mistral-c64': (8, 4, 64, 32 * 480, 64, False),
    'mistral-c128': (8, 4, 128, 32 * 480, 64, False),
    'mistral-c256': (8, 4, 256, 32 * 480, 64, False),
    'hybrid-c64': (2, 16, 64, 2 * 2048, 32, False),
    'hybrid-c256': (2, 16, 256, 2 * 2048, 32, False),
    'mistral-c256-int8-kv': (8, 4, 256, 32 * 480, 64, True),
}


@pytest.mark.parametrize('shape', LOWERED)
def test_prefill_kernel_compiles_for_v5e(shape, one_chip, no_cache):
    hkv, group, chunk, pool, maxp, int8 = LOWERED[shape]
    hd, page = 128, 64

    def arg(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    pages = arg((hkv, pool, page, hd), jnp.int8 if int8 else jnp.bfloat16)
    args = [arg((chunk, hkv, group, hd), jnp.bfloat16), pages, pages,
            arg((maxp,), jnp.int32), arg((), jnp.int32),
            arg((), jnp.int32)]
    if int8:
        args += [arg((hkv, pool, page), jnp.float32)] * 2

    def call(q, k, v, row, off, n, ks=None, vs=None):
        return pa.paged_prefill_attention(q, k, v, row, off, n,
                                          interpret=False, k_scales=ks,
                                          v_scales=vs)
    compiled = jax.jit(call).lower(*args).compile()
    assert 'tpu_custom_call' in compiled.as_text()
