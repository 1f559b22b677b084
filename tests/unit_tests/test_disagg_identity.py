"""The disaggregation bit-identity gate (docs/serving.md
"Disaggregated prefill/decode"): greedy outputs after a KV prefix
TRANSFER are BIT-IDENTICAL to a local recompute of the same prompts.

int8 pools make this exact — the wire carries the donor's bytes
verbatim, and quantize-on-write is deterministic, so the puller's
grafted pages equal what it would have computed itself. The gate runs
the transfer against a never-transferred oracle at pipeline depth 1
and 0, speculation on and off, over a workload whose lead request
actually consumes the transferred pages (asserted — a vacuous gate
would pass with the import silently failing).
"""
import numpy as np
import pytest

pytestmark = pytest.mark.jax

from skypilot_tpu.infer import engine as engine_lib  # noqa: E402
from skypilot_tpu.models import llama  # noqa: E402

CFG = llama.LlamaConfig.tiny()

# 40 tokens: 2 full pages (the transferable prefix) + an 8-token tail.
_PREFIX = [(i * 7 + 3) % 250 for i in range(40)]
# Two cohort members sharing the prefix, one stranger, and a repeat —
# prefill-from-boundary, plain prefill, and re-match all in one pass.
_WORKLOAD = [_PREFIX + [101, 55, 3, 9],
             [9, 8, 7, 6, 5],
             _PREFIX + [200, 201, 202, 203, 204, 205]]


@pytest.fixture(scope='module')
def params(spec_params):
    """Weights whose greedy continuation repeats (conftest's
    ``spec_params``), so the spec-on lane has drafts to verify."""
    return spec_params


def _engine(params, spec_k=0):
    return engine_lib.InferenceEngine(
        CFG, params,
        engine_lib.EngineConfig(n_slots=3, max_seq_len=128,
                                prefill_buckets=(16, 32),
                                prefill_chunk=32, pipeline_depth=1,
                                spec_k=spec_k, paged=True, page_size=16,
                                n_pages=13, prefix_cache=True,
                                kv_dtype='int8'))


@pytest.fixture(scope='module')
def blob(params):
    """One donor prefill of the shared prefix, exported to the wire.
    Prefill writes are deterministic, so the blob is what any int8
    replica would hold for these pages."""
    donor = _engine(params)
    donor.generate([_PREFIX], max_new_tokens=4)
    out = donor._kv_export(_PREFIX)
    assert out is not None
    return out


@pytest.mark.parametrize('spec_k', [0, 4], ids=['spec-off', 'spec-on'])
def test_transfer_bit_identical_to_local_recompute(params, blob,
                                                   spec_k):
    oracle = _engine(params, spec_k=spec_k)
    puller = _engine(params, spec_k=spec_k)
    assert puller._kv_import(blob) == 2

    for depth in (1, 0):
        oracle.set_pipeline_depth(depth)
        puller.set_pipeline_depth(depth)
        got = puller.generate(_WORKLOAD, max_new_tokens=8)
        want = oracle.generate(_WORKLOAD, max_new_tokens=8)
        assert ([r.output_tokens for r in got]
                == [r.output_tokens for r in want]), (
            f'transfer changed greedy output (depth {depth}, '
            f'spec_k {spec_k})')
        if depth == 1:
            # Non-vacuous: the puller's lead request started from the
            # TRANSFERRED pages (it never prefilled them locally),
            # while the oracle computed everything itself.
            assert got[0].cached_tokens == 32
            assert want[0].cached_tokens == 0
        if spec_k:
            assert puller.metrics()['spec_emitted_tokens'] > 0, (
                'speculation never fired — the spec-on lane of the '
                'gate is vacuous')

    # The transferred pages the puller decoded from still hold the
    # donor's exact bytes (no write path touched the shared prefix).
    pages, n = puller.prefix.peek(_PREFIX, whole=True)
    assert n == 32
    from skypilot_tpu.infer import kv_wire
    from skypilot_tpu.infer import paged_cache
    blk = kv_wire.unpack(blob)
    np.testing.assert_array_equal(
        np.asarray(paged_cache.gather_pages(puller.cache, pages)[0]),
        blk.k)
