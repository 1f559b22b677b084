"""The paged step programs hold the page pool IN PLACE.

The pool (every layer's pages folded into one page axis,
``infer/paged_cache.py``) is a carry of the layer scan: the donated
input buffer is the output buffer, a layer writes its new rows into it
and nothing slices a layer's slab out, stacks it back or copies it.
Three kinds of evidence, for each of the four paged programs and both
KV flavors (plain pages; int8 pages with row scales):

- structure of the compiled program: every pool input aliased to its
  output, temporaries below ONE layer's slab, no slab-sized ``copy`` or
  ``dynamic-slice``, every ``dynamic-update-slice`` into the pool a
  page or less. (The parent of PR 26, which fed per-layer slabs through
  the scan as ``xs``/``ys``, fails each of these.)
- values: logits and the whole pool, read back through
  ``gather_pages`` as ``[L, hkv, P, page, hd]``, against a plain
  reference that loops over explicitly sliced layers.
- the sink: writes past a slot's coverage and inactive slots' garbage
  rows land in their OWN layer's page 0.

Compiled here means XLA's CPU backend. It upcasts a bfloat16
``dynamic-update-slice`` to float32 (a pool-sized convert the TPU
compiler does not make), so the plain flavor is float32 here; and it
runs a Pallas kernel in interpret mode inside a loop that carries, and
so copies, every operand, which says nothing of the TPU program: the
structural tests put a stand-in that reads one page through the table
in the kernels' place. The value tests run the real kernels.
"""
import dataclasses
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from skypilot_tpu.infer import model as model_lib
from skypilot_tpu.infer import paged_cache as paged_cache_lib
from skypilot_tpu.models import llama
from skypilot_tpu.ops import norms
from skypilot_tpu.ops import paged_attention as pa
from skypilot_tpu.ops import quant as quant_lib
from skypilot_tpu.ops import rope as rope_lib

pytestmark = pytest.mark.jax


@pytest.fixture(scope='module', autouse=True)
def _highest_matmul_precision():
    """This module's comparisons are made at the highest matmul
    precision; scoped to its own tests, and restored, so that no other
    test in the process runs under a precision it did not ask for."""
    with jax.default_matmul_precision('highest'):
        yield

# A model much smaller than its pool, as in serving: one layer's slab
# (64 KB of int8 K) is several times all of a layer's weights (28 KB),
# so "temporaries below one slab" is a statement about the pool.
CFG = llama.LlamaConfig.tiny(vocab_size=32, dim=32, n_layers=3,
                             ffn_dim=32, max_seq_len=64)
SLOTS, PAGE, P, MAXP, CHUNK, RUN = 3, 8, 512, 4, 16, 3
HKV, HD = CFG.n_kv_heads, CFG.head_dim
PROGRAMS = ('prefill', 'decode', 'verify', 'mixed')
FLAVORS = ('float32', 'int8')

# Slot 0 owns pages 5, 2, 7 (covers 24 positions) and holds 21 tokens: a
# RUN of 3 stays inside. Slot 1 owns 9, 3, 4, 6 (all MAXP columns) and
# holds 30: positions 32.. of a run fall PAST coverage -> the sink.
# Slot 2 is inactive: a zeroed table row, length 0 -> the sink.
TABLES = np.array([[5, 2, 7, 0], [9, 3, 4, 6], [0, 0, 0, 0]], np.int32)
LENGTHS = np.array([21, 30, 0], np.int32)
ACTIVE = np.array([True, True, False])
# The chunk goes to slot 2 at offset 8 (page-aligned, one page already
# prefilled), through a row of its own: pages 10, 11, 1. Its 13 tokens
# end in the chunk's last page, so the kernel skips no page that the
# chunk's pad rows attend to and the pad rows' garbage K/V match too.
CHUNK_ROW = np.array([10, 11, 1, 0], np.int32)
CHUNK_SLOT, CHUNK_OFFSET, CHUNK_LEN = 2, 8, 13


@pytest.fixture(scope='module')
def params():
    return llama.init_params(CFG, jax.random.PRNGKey(0))


def _cache(flavor):
    """A pool with random contents in every page of every layer."""
    cache = paged_cache_lib.init_paged_cache(
        CFG.n_layers, SLOTS, P, PAGE, HKV, HD, dtype=flavor)
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    shape = (CFG.n_layers, HKV, P, PAGE, HD)
    if flavor == 'int8':
        fill = (jax.random.randint(keys[0], shape, -127, 128),
                jax.random.randint(keys[1], shape, -127, 128),
                jax.random.uniform(keys[2], shape[:-1], minval=.001,
                                   maxval=.02),
                jax.random.uniform(keys[3], shape[:-1], minval=.001,
                                   maxval=.02))
    else:
        fill = (jax.random.normal(keys[0], shape),
                jax.random.normal(keys[1], shape))
    cache = paged_cache_lib.scatter_pages(cache, np.arange(P), *fill)
    return dataclasses.replace(cache, lengths=jnp.asarray(LENGTHS))


def _tokens(n, *shape):
    return jax.random.randint(jax.random.PRNGKey(n), shape, 0,
                              CFG.vocab_size, jnp.int32)


def _program(name):
    """``(fn(cache, params, *args), args)``: the model-level program
    the engine jits with the cache donated."""
    tables, active = jnp.asarray(TABLES), jnp.asarray(ACTIVE)
    chunk = (jnp.int32(CHUNK_SLOT), jnp.asarray(CHUNK_ROW),
             _tokens(2, CHUNK), jnp.int32(CHUNK_OFFSET),
             jnp.int32(CHUNK_LEN))
    if name == 'prefill':
        return (lambda kv, p, *a: model_lib.paged_prefill_chunk(
            CFG, p, kv, *a)), chunk
    if name == 'decode':
        return (lambda kv, p, *a: model_lib.paged_decode_step(
            CFG, p, kv, *a)), (tables, _tokens(3, SLOTS), active)
    if name == 'verify':
        return (lambda kv, p, *a: model_lib.paged_verify_step(
            CFG, p, kv, *a)), (tables, _tokens(4, SLOTS, RUN))
    return (lambda kv, p, *a: model_lib.paged_mixed_step(
        CFG, p, kv, *a)), (*chunk, tables, _tokens(3, SLOTS), active)


# ---------- structure of the compiled program ------------------------------
def _stand_in(q, k_pages, v_pages, tables, *_, k_scales=None,
              v_scales=None, **__):
    """In the kernels' place (module docstring): reads the pool as a
    kernel does, one page through the table, and nothing else."""
    pid = jnp.reshape(tables, (-1,))[0]
    got = (k_pages[:, pid] + v_pages[:, pid]).astype(jnp.float32)
    if k_scales is not None:
        got = got * (k_scales[:, pid] + v_scales[:, pid])[..., None]
    return jnp.zeros(q.shape, jnp.float32) + jnp.sum(got)


_BYTES = {'f32': 4, 's32': 4, 'u32': 4, 'bf16': 2, 'f16': 2, 's8': 1,
          'u8': 1, 'pred': 1, 's64': 8, 'f64': 8}
_INSTR = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\w+)\[([\d,]*)\]\S*\s+'
    r'([\w\-]+)\((.*)$')


def _instructions(hlo_text):
    """name -> (bytes, opcode, operand names), for every instruction of
    every computation (fused ones too) with an array result."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, dtype, dims, opcode, rest = m.groups()
        size = _BYTES.get(dtype, 4)
        for d in filter(None, dims.split(',')):
            size *= int(d)
        out[name] = (size, opcode, re.findall(r'%([\w.\-]+)', rest))
    return out


@pytest.mark.parametrize('flavor', FLAVORS)
@pytest.mark.parametrize('name', PROGRAMS)
def test_compiled_program_holds_the_pool_in_place(name, flavor, params,
                                                  monkeypatch):
    for kernel in ('paged_decode_attention', 'paged_prefill_attention',
                   'paged_verify_attention'):
        monkeypatch.setattr(pa, kernel, _stand_in)
    fn, args = _program(name)
    cache = _cache(flavor)
    compiled = jax.jit(fn, donate_argnums=0).lower(
        cache, params, *args).compile()
    text = compiled.as_text()
    pools = [a for a in (cache.k_pages, cache.v_pages, cache.k_scales,
                         cache.v_scales) if a is not None]
    slab = cache.k_pages.nbytes // CFG.n_layers
    page = cache.k_pages.nbytes // (CFG.n_layers * P)

    # (a) every pool parameter (the cache's leaves come first: argument
    # 0, k_pages, v_pages, lengths, then the scales) aliases an output,
    # and the temporaries are less than one layer's slab.
    header = text.split('input_output_alias={', 1)[1].split(
        'entry_computation_layout', 1)[0]
    aliased = {int(i) for i in re.findall(r'\((\d+), \{\}', header)}
    leaves = jax.tree_util.tree_leaves(cache)
    want = {i for i, leaf in enumerate(leaves)
            if any(leaf is p for p in pools)}
    assert want <= aliased, (want, header)
    # XLA's CPU backend copies the pool between the mixed step's chunk
    # half (whose stand-in READS the pool) and its decode half (whose
    # rows UPDATE it): it cannot order a read before an in-place update
    # inside one loop iteration. The TPU compiler does (compiled for a
    # v5e at the benchmark's size the mixed step holds 2 MB of
    # temporaries beside a 3.75 GiB pool; PERF.md, PR 26), so for that
    # program only the aliasing, the slices and the updates are held.
    in_place_on_cpu = name != 'mixed'
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < slab or not in_place_on_cpu, (
        f'{name}/{flavor}: {temp} bytes of temporaries; one layer of '
        f'K is {slab}, the K pool {cache.k_pages.nbytes}')

    # (b) no slab-sized copy or slice; every update of the pool is a
    # page or less.
    instrs = _instructions(text)
    updates = 0
    for ins, (size, opcode, operands) in instrs.items():
        if opcode in ('dynamic-slice', 'slice', 'gather') or (
                opcode == 'copy' and in_place_on_cpu):
            assert size < slab, (ins, opcode, size, slab)
        if opcode == 'dynamic-update-slice' and size >= slab:
            update = instrs[operands[1]][0]
            assert update <= page, (ins, update, page)
            updates += 1
        assert opcode != 'scatter' or size < slab, (ins, size)
    assert updates, 'no dynamic-update-slice of the pool was found'


def _walk(jaxpr, inside=()):
    """Every equation of a jaxpr and of the jaxprs nested in it, with
    the primitives it is nested in; a Pallas kernel's body is its own
    business and is not entered."""
    for eqn in jaxpr.eqns:
        yield eqn, inside
        if eqn.primitive.name == 'pallas_call':
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub, inside + (eqn.primitive.name,))


@pytest.mark.parametrize('flavor', FLAVORS)
@pytest.mark.parametrize('name', PROGRAMS)
def test_layer_scan_carries_the_pool(name, flavor, params):
    """The program as traced, real kernels and all, whatever backend
    compiles it: the pool arrays are CARRIES of the layer scan (not
    ``xs`` sliced a layer at a time, not ``ys`` stacked back), nothing
    scatters into or gathers from them, and every
    ``dynamic_update_slice`` of them updates a page or less."""
    fn, args = _program(name)
    cache = _cache(flavor)
    pools = [a for a in (cache.k_pages, cache.v_pages, cache.k_scales,
                         cache.v_scales) if a is not None]
    shapes = {(a.shape, a.dtype) for a in pools}
    page_elems = HKV * PAGE * HD

    def is_pool(var):
        return (var.aval.shape, var.aval.dtype) in shapes

    jaxpr = jax.make_jaxpr(fn)(cache, params, *args).jaxpr
    scans = [e for e, _ in _walk(jaxpr) if e.primitive.name == 'scan'
             and e.params['length'] == CFG.n_layers]
    assert len(scans) == 1
    scan = scans[0]
    first = scan.params['num_consts']
    carry = scan.invars[first:first + scan.params['num_carry']]
    assert sum(map(is_pool, carry)) == len(pools)
    assert not any(map(is_pool, scan.invars[:first]))
    assert sum(map(is_pool, scan.outvars[:len(carry)])) == len(pools)
    slab_elems = cache.k_pages.size // CFG.n_layers
    for var in (scan.invars[first + len(carry):]
                + scan.outvars[len(carry):]):
        # No xs / ys anywhere near a layer's slab: only the weights.
        assert var.aval.size < slab_elems // 4, var.aval
    updates = 0
    for eqn, inside in _walk(jaxpr):
        prim = eqn.primitive.name
        touched = [v for v in eqn.invars
                   if hasattr(v, 'aval') and hasattr(v.aval, 'shape')
                   and is_pool(v)]
        if not touched or prim in ('pallas_call', 'scan', 'pjit', 'jit',
                                   'closed_call', 'custom_jvp_call'):
            continue
        assert 'scan' in inside, (prim, inside)
        if prim == 'dynamic_update_slice':
            assert eqn.invars[1].aval.size <= page_elems
            updates += 1
        else:
            # What else may touch the pool: the reshape that hands the
            # scale rows to a kernel (``_scale_rows``).
            assert prim in ('reshape', 'broadcast_in_dim'), (
                prim, [v.aval for v in eqn.invars])
    assert updates



# ---------- values against explicitly sliced layers ------------------------
def _logical(cache):
    """The pool as per-layer lists of [hkv, P, page, hd] (and [hkv, P,
    page] scales), as numpy: explicitly sliced layers."""
    k, v, ks, vs = paged_cache_lib.gather_pages(cache, np.arange(P))
    return [np.array(k), np.array(v),
            None if ks is None else np.array(ks),
            None if vs is None else np.array(vs)]


def _reference(params, cache, runs):
    """A plain forward over the layers, one at a time, each on its own
    slice of the pool. ``runs`` are applied in order inside every
    layer: ``(tables [n, maxp], lengths [n], tokens [n, R])``, n table
    rows each taking R tokens at positions ``lengths + i``: written
    row by row (past the table's coverage: page 0), then attended
    causally through ``paged_verify_attention_reference``. A decode
    step is R = 1, a prefill chunk one row with R = C; a run may name
    as a fourth entry the rows that are ``active``: the decode programs
    attend nothing in the others (their K/V row is still written, their
    attention output is zeros). Returns the logits of every run
    ([n, R, vocab]) and the logical pool."""
    k_all, v_all, ks_all, vs_all = _logical(cache)
    quantized = ks_all is not None
    cos, sin = rope_lib.rope_frequencies(HD, CFG.max_seq_len,
                                         CFG.rope_theta)
    hq, group = CFG.n_heads, CFG.n_heads // HKV
    xs = [quant_lib.qembed(params['embed'], run[2]) for run in runs]
    for layer_idx in range(CFG.n_layers):
        layer = jax.tree.map(lambda a: a[layer_idx], params['layers'])
        for r, (tables, lengths, toks, *active) in enumerate(runs):
            n, R = toks.shape
            x = xs[r]
            positions = lengths[:, None] + np.arange(R)[None, :]
            h = norms.rms_norm(x, layer['attn_norm'], CFG.norm_eps)
            q = quant_lib.qdot(h, layer['wq']).reshape(n, R, hq, HD)
            k = quant_lib.qdot(h, layer['wk']).reshape(n, R, HKV, HD)
            v = quant_lib.qdot(h, layer['wv']).reshape(n, R, HKV, HD)
            q = rope_lib.apply_rope(q, cos, sin, jnp.asarray(positions))
            k = rope_lib.apply_rope(k, cos, sin, jnp.asarray(positions))
            if quantized:
                k, ks = (np.asarray(a) for a in pa.quantize_rows(k))
                v, vs = (np.asarray(a) for a in pa.quantize_rows(v))
            else:
                k, v = np.asarray(k), np.asarray(v)
            for i in range(R):
                for s in range(n):
                    col, row = divmod(int(positions[s, i]), PAGE)
                    pid = int(tables[s, col]) if col < MAXP else 0
                    k_all[layer_idx][:, pid, row] = k[s, i]
                    v_all[layer_idx][:, pid, row] = v[s, i]
                    if quantized:
                        ks_all[layer_idx][:, pid, row] = ks[s, i]
                        vs_all[layer_idx][:, pid, row] = vs[s, i]
            scales = ({} if not quantized else
                      dict(k_scales=jnp.asarray(ks_all[layer_idx]),
                           v_scales=jnp.asarray(vs_all[layer_idx])))
            att = pa.paged_verify_attention_reference(
                q.reshape(n, R, HKV, group, HD),
                jnp.asarray(k_all[layer_idx]),
                jnp.asarray(v_all[layer_idx]), jnp.asarray(tables),
                jnp.asarray(lengths), **scales)
            att = att.reshape(n, R, hq * HD).astype(x.dtype)
            if active:
                att = jnp.where(active[0][:, None, None], att, 0)
            x = x + quant_lib.qdot(att, layer['wo'])
            xs[r] = llama.mlp_block(CFG, x, layer)
    logits = [quant_lib.qdot(
        norms.rms_norm(x, params['final_norm'], CFG.norm_eps),
        params['lm_head']).astype(jnp.float32) for x in xs]
    return logits, [k_all, v_all, ks_all, vs_all]


def _runs(name, args):
    """The program's work as the reference's runs, and how to read the
    program's logits out of the reference's."""
    chunk_run = lambda row, toks, offset: (   # noqa: E731
        np.asarray(row)[None], np.asarray([offset]),
        np.asarray(toks)[None])
    if name == 'prefill':
        _, row, toks, offset, true_len = args
        return ([chunk_run(row, toks, int(offset))],
                lambda lg: lg[0][0, int(true_len) - 1])
    if name == 'decode':
        tables, toks, _ = args
        return ([(TABLES, LENGTHS, np.asarray(toks)[:, None], ACTIVE)],
                lambda lg: lg[0][:, 0])
    if name == 'verify':
        tables, toks = args
        return [(TABLES, LENGTHS, np.asarray(toks))], lambda lg: lg[0]
    _, row, toks, offset, true_len, _, dtoks, _ = args
    mid = LENGTHS.copy()
    mid[CHUNK_SLOT] = int(offset) + int(true_len)
    return ([chunk_run(row, toks, int(offset)),
             (TABLES, mid, np.asarray(dtoks)[:, None], ACTIVE)],
            lambda lg: (lg[0][0, int(true_len) - 1], lg[1][:, 0]))


def _assert_pool_equal(got, want, flavor):
    names = ('k_pages', 'v_pages', 'k_scales', 'v_scales')
    for what, have, ref in zip(names, got, want):
        if ref is None:
            assert have is None
        elif flavor == 'int8' and what.endswith('pages'):
            # A row whose float value differs in the last place may
            # round to the neighbouring step.
            diff = np.abs(have.astype(np.int32) - ref.astype(np.int32))
            assert diff.max() <= 1 and (diff > 0).mean() < 1e-3, what
        else:
            np.testing.assert_allclose(have, ref, atol=2e-5, rtol=2e-5,
                                       err_msg=what)


@pytest.mark.parametrize('flavor', FLAVORS)
@pytest.mark.parametrize('name', PROGRAMS)
def test_logits_and_pool_match_the_per_layer_reference(name, flavor,
                                                       params):
    fn, args = _program(name)
    cache = _cache(flavor)
    before = _logical(cache)
    runs, pick = _runs(name, args)
    ref_logits, ref_pool = _reference(params, cache, runs)
    out = jax.jit(fn)(cache, params, *args)
    if name == 'prefill':
        new_cache, logits = out
    elif name == 'mixed':
        logits, new_cache = out[:2], out[2]
    else:
        logits, new_cache = out
    for have, want in zip(jax.tree.leaves(logits),
                          jax.tree.leaves(pick(ref_logits))):
        np.testing.assert_allclose(np.asarray(have), np.asarray(want),
                                   atol=2e-4, rtol=2e-4)
    got = _logical(new_cache)
    _assert_pool_equal(got, ref_pool, flavor)
    # The step wrote something in every layer, and only a few rows.
    for layer_idx in range(CFG.n_layers):
        changed = (got[0][layer_idx] != before[0][layer_idx]).any(-1)
        rows = sum(int(np.prod(run[2].shape)) for run in runs)
        assert 0 < changed.any(0).sum() <= rows, (layer_idx, rows)


# ---------- the sink: a layer's own page 0 ---------------------------------
@pytest.mark.parametrize('flavor', FLAVORS)
@pytest.mark.parametrize('name', ['decode', 'verify'])
def test_sink_writes_stay_in_their_own_layer(name, flavor, params):
    """Slot 1's padded draft runs past its table's coverage and slot 2
    is inactive with a zeroed row: both write into page 0, the sink,
    of the layer that computed the row, and into no other page that the
    step does not own. With the layer folded into the page axis a
    clamp applied AFTER the layer's offset would send them to layer
    0's page 0, or to another layer's live page."""
    fn, args = _program(name)
    cache = _cache(flavor)
    zero = jax.tree.map(jnp.zeros_like, cache)
    zero = dataclasses.replace(zero, lengths=cache.lengths)
    new_cache = jax.jit(fn)(zero, params, *args)[1]
    k = _logical(new_cache)[0]                 # [L][hkv, P, page, hd]
    run = RUN if name == 'verify' else 1
    # Where the owned writes go: slot 0 at 21.., slot 1 at 30, 31 (32..
    # is past coverage); everything else a row lands in is the sink.
    owned = {(int(TABLES[s, p // PAGE]), p % PAGE)
             for s in (0, 1) for p in range(LENGTHS[s], LENGTHS[s] + run)
             if p // PAGE < MAXP}
    sink_rows = {p % PAGE for p in range(LENGTHS[1], LENGTHS[1] + run)
                 if p // PAGE >= MAXP} | set(range(run))   # slot 2
    for layer_idx in range(CFG.n_layers):
        written = k[layer_idx].astype(np.float32).any(axis=(0, 3))
        where = {(int(p), int(r)) for p, r in zip(*np.nonzero(written))}
        assert where == owned | {(0, r) for r in sink_rows}, (
            layer_idx, where)
    # Each layer's sink holds that layer's rows, not one layer's for all.
    sinks = [k[i][:, 0].astype(np.float32) for i in range(CFG.n_layers)]
    for a in range(CFG.n_layers):
        for b in range(a + 1, CFG.n_layers):
            assert np.abs(sinks[a] - sinks[b]).max() > 0
