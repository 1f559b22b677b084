"""The two state families' WHOLE decode programs at their cells'
shapes, compiled for a described (not attached) ``v5e:2x2``: the
Falcon-H1 stage with 2 of its 9 blocks (the body repeats) and the
Nemotron-H share with all 16 (``MEMEM*EMEMEM*EME``: PERF.md section
7(7) owed the hybrid's whole step). Nothing runs, so nothing here is a
time or a result.

What this guards, beside "it compiles and fits": the Mamba-2 state
kernel (``ops/mamba2.ssd_decode_live``, PR 34) lowers at both state
shapes, ``[64, 32, 128, 256]`` and ``[64, 64, 64, 128]`` float32, once
a state layer; every state array rides the program IN PLACE, aliased
onto its result; and no operation yields a state-sized array at all: no
copy, no relay, and no fusion over all 64 slots, which is what the
``jax.numpy`` update under ``jnp.where`` was.

These cases live here and not in ``tests/benchmark/``, which is the
benchmark's (``tests/benchmark/test_tpu_compile_falcon_h1.py`` compiles
the same Falcon-H1 program and holds it to "no copy" on its own). The
topology is described inside a module-scoped fixture, never while a
module is imported, and the tests are skipped where it cannot be
described (``tests/benchmark/test_tpu_compile.py`` says why).
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark import manifest

CELLS = {'falcon_h1': 'falcon-h1-serve.reasoning-steady',
         'nemotron_h': 'nemotron3-nano-serve.chat-bursty'}
FALCON_BLOCKS = 2
HBM = 16 * 2**30


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
    """As ``tests/benchmark/test_tpu_compile.py``'s: such a compile
    cannot be read back from the persistent cache; and the chip's own
    matmul precision."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    with jax.default_matmul_precision('default'):
        yield
    jax.config.update('jax_enable_compilation_cache', before)
    compilation_cache.reset_cache()


def _config(family, engine):
    from skypilot_tpu.models import falcon_h1, nemotron_h
    if family == 'falcon_h1':
        return falcon_h1.FalconH1Config.h1_34b_pp8(
            n_layers=FALCON_BLOCKS, max_seq_len=engine['max_seq_len'])
    return nemotron_h.NemotronHConfig.nano_30b_a3b_ep2(
        max_seq_len=engine['max_seq_len'])


@pytest.fixture(scope='module', params=sorted(CELLS))
def decode_program(request, one_chip, no_cache):
    """(the compiled decode program, its cache as shapes), the kernels
    forced: ``ops/`` interprets them unless the backend is a TPU, and
    here jax sees the CPU."""
    from skypilot_tpu.infer import model as model_lib
    engine = manifest.cell(manifest.load(),
                           CELLS[request.param])['config']['engine']
    slots, page = engine['n_slots'], engine['page_size']
    config = _config(request.param, engine)
    steps = model_lib.paged_steps(config)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                           sharding=one_chip), tree)
    params = on_chip(jax.eval_shape(
        lambda: config.init_params(jax.random.PRNGKey(0))))
    cache = on_chip(jax.eval_shape(lambda: steps.init_cache(
        config.cache_spec(), slots, engine['n_pages'], page,
        jnp.bfloat16)))

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, 'default_backend', lambda: 'tpu')
        compiled = jax.jit(
            functools.partial(steps.decode, config), donate_argnums=(1,)
        ).lower(params, cache,
                arg(jnp.int32, slots, engine['max_seq_len'] // page),
                arg(jnp.int32, slots), arg(jnp.bool_, slots)).compile()
    return compiled, cache


def _state_sized(text, shape):
    """(operation, line) of every instruction that YIELDS a float32
    array of ``shape``, a state layer's."""
    dims = ','.join(map(str, shape))
    found = []
    for line in text.splitlines():
        m = re.match(rf'\s*(?:ROOT )?%?[\w.\-]+ = f32\[{dims}\]\S* '
                     r'([\w\-]+)\(', line)
        if m:
            found.append((m.group(1), line.strip()[:120]))
    return found


def test_the_state_kernel_lowers_once_a_state_layer(decode_program):
    compiled, cache = decode_program
    assert cache.ssm[0].dtype == jnp.float32
    calls = re.findall(r'= \([^=]*\) custom-call\([^\n]*'
                       r'custom_call_target="tpu_custom_call"[^\n]*'
                       r'ssd_decode_state', compiled.as_text())
    assert len(calls) == len(cache.ssm)


def test_no_operation_yields_a_state_sized_array(decode_program):
    """The arguments, and the kernel's aliased result read out of its
    pair, are the state itself; anything else of that shape is a copy,
    a relay or a pass over all slots."""
    compiled, cache = decode_program
    made = [line for op, line in
            _state_sized(compiled.as_text(), cache.ssm[0].shape)
            if op not in ('parameter', 'get-tuple-element', 'bitcast')]
    assert made == []


def test_the_state_rides_in_place_and_the_program_fits(decode_program):
    compiled, cache = decode_program
    m = compiled.memory_analysis()
    held = sum(a.size * a.dtype.itemsize for a in
               (*cache.ssm, *cache.conv, cache.kv.k_pages, cache.kv.v_pages))
    assert m.alias_size_in_bytes >= held
    assert m.temp_size_in_bytes < 2**30, m.temp_size_in_bytes
    total = (m.argument_size_in_bytes + m.temp_size_in_bytes
             + m.output_size_in_bytes - m.alias_size_in_bytes)
    assert total < HBM, total


def test_the_check_finds_the_plain_forms_pass_over_all_slots(
        one_chip, no_cache):
    """The same reading of the plain form under ``jnp.where`` (what
    ``mamba_mixer.decode`` held before PR 34) finds the body of its
    fusion over every slot's state: the check above can fail."""
    from skypilot_tpu.ops import mamba2
    slots, H, P, N, G = 64, 64, 64, 128, 8

    def as_it_was(x, dt, a, b, c, d_skip, state, active):
        y, new = mamba2.ssd_decode_step(x, dt, a, b, c, d_skip, state)
        return y, jnp.where(active[:, None, None, None], new, state)

    def arg(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = jax.jit(as_it_was, donate_argnums=(6,)).lower(
        arg(slots, H, P), arg(slots, H), arg(H), arg(slots, G, N),
        arg(slots, G, N), arg(H), arg(slots, H, P, N),
        arg(slots, dtype=jnp.bool_)).compile()
    ops = {op for op, _ in
           _state_sized(compiled.as_text(), (slots, H, P, N))}
    assert {'multiply', 'select'} <= ops      # the fused update's body
