"""The step programs of the three families that were served before
PR 33 lower, at their tiny presets, to the StableHLO they lowered to
at PR 33's parent commit (d751245), byte for byte.

PR 33 moved the Mamba-2 mixer's body out of ``models/nemotron_h.py``
(``models/mamba_mixer.py``, shared with Falcon-H1), split the hybrid's
attention helpers in ``infer/model.py`` into the cache's half
(``_state_attn_chunk`` / ``_state_attn_decode``) and the family's
projections, and taught the two hybrid programs a fourth kind of block.
None of that may change what the Nemotron-H, dots3 or dense programs
compute: the digests below were taken on the parent commit's tree with
this file's own ``digests()``.

PR 34 MEANT to change ``hybrid.decode`` and replaced its digest: the
step's SSM state update is a Pallas kernel that moves the live slots'
state only (``ops/mamba2.ssd_decode_live``; here, on the CPU, the text
holds the kernel as it is interpreted), where the parent's was
``ssd_decode_step`` under ``jnp.where``.

The row writer MEANT to change ``dense.decode`` and ``hybrid.decode``
and replaced both digests: a decode step's new K/V rows go into the
page pool through one Pallas call a layer
(``ops/paged_attention.paged_row_write``, interpreted here), which
writes the live slots' rows only, where the parent's was a chain of
one ``dynamic_update_slice`` a slot and pool, every slot. The other
four are PR 33's parent's still: both families' prefill-chunk
programs, which write whole pages as they did, and both dots3
programs, which keep their own latent writers.

A PR that MEANS to change one of these programs replaces its digest
(``python tests/unit_tests/test_step_programs_unchanged.py`` prints the
table) and says so; a jax upgrade that rewrites the text replaces all
six at once, on a tree that changes nothing else.
"""
import hashlib
import json

import jax
import jax.numpy as jnp
import pytest

from skypilot_tpu.infer import model as model_lib
from skypilot_tpu.infer import paged_cache as paged_cache_lib
from skypilot_tpu.models import interface

pytestmark = pytest.mark.jax

SLOTS, PAGE, N_PAGES, MAXP, CHUNK = 2, 16, 40, 16, 32
AT_PARENT = {
    'dense.prefill_chunk':
        '7f8a443c5340b3bcf8627c7cfa84cc88eaae2fe20897f9c8a18c7e859de43c8f',
    'dense.decode':
        'f507777a0f706e7251a8b6717e603f24166ab22776f31d6190c27b90cc068b2a',
    'hybrid.prefill_chunk':
        '484e6de253a3b90f99d6ab0bc914cef49803b6ad8cfb1053820b90c56f1c0a54',
    'hybrid.decode':
        'e89527f0694c190df209ea6e2eeacb9cbc78d654a5f286867cad8b1f91a4710a',
    'dots3.prefill_chunk':
        '5dc36e338e64e00798582cf51e1805df3a3f1708b85d8eb9b448b47cf1e0c9c8',
    'dots3.decode':
        '9d9d23da114ba954f7eea48aa4faacbdd16b5576312fb424880f94347c4e45f7',
}


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def programs(family):
    """``{name: (program, args)}`` of a family's two paged step
    programs at its tiny preset, as shapes."""
    row, tables = _i32(MAXP), _i32(SLOTS, MAXP)
    if family == 'dots3':
        from skypilot_tpu.infer import latent_cache
        from skypilot_tpu.models import dots3
        config = dots3.Dots3Config.tiny()
        steps = model_lib.paged_steps(config)
        window = paged_cache_lib.WindowAllocator(PAGE, SLOTS, MAXP,
                                                 config.window, CHUNK)
        cache = jax.eval_shape(lambda: latent_cache.init_latent_cache(
            config.cache_spec(), SLOTS, N_PAGES, PAGE, jnp.float32,
            window_pages=window.n_pages))
        row, tables = (row, row), (tables, tables)
    else:
        if family == 'hybrid':
            from skypilot_tpu.models import nemotron_h
            config = nemotron_h.NemotronHConfig.tiny()
        else:
            from skypilot_tpu.models import llama
            config = llama.LlamaConfig.tiny()
        steps = model_lib.paged_steps(config)
        cache = jax.eval_shape(lambda: steps.init_cache(
            interface.cache_spec(config), SLOTS, N_PAGES, PAGE,
            jnp.float32))
    params = jax.eval_shape(
        lambda: interface.init_params(config, jax.random.PRNGKey(0)))
    active = jax.ShapeDtypeStruct((SLOTS,), jnp.bool_)
    return config, {
        'prefill_chunk': (steps.prefill_chunk, (
            params, cache, _i32(), row, _i32(CHUNK), _i32(), _i32())),
        'decode': (steps.decode, (params, cache, tables, _i32(SLOTS),
                                  active))}


def digests(family):
    config, progs = programs(family)
    out = {}
    for name, (fn, args) in progs.items():
        text = jax.jit(lambda *a, f=fn: f(config, *a)).lower(*args).as_text()
        out[f'{family}.{name}'] = hashlib.sha256(text.encode()).hexdigest()
    return out


@pytest.mark.parametrize('name', sorted(AT_PARENT))
def test_the_program_lowers_to_the_parents_stablehlo(name):
    assert digests(name.split('.')[0])[name] == AT_PARENT[name]


if __name__ == '__main__':
    print(json.dumps({k: v for f in ('dense', 'hybrid', 'dots3')
                      for k, v in digests(f).items()}, indent=1))
