"""BENCHMARK.json and the files it names agree, and everything that
belongs to one cell, configuration or metric is found by its name."""
import json
import os
import re

import pytest

from benchmark import manifest

ROOT = manifest.ROOT
BENCH = manifest.load()
CELLS = [w['name'] for w in BENCH['workloads']]
PER_LAYER = [m['name'] for m in BENCH['per_layer']]


def test_manifest_is_self_consistent():
    assert manifest.problems() == []


def test_top_level_keys_and_limits():
    assert set(BENCH) == {'command', 'paths', 'run_seconds', 'configs',
                          'workloads', 'end_to_end', 'per_layer'}
    assert 1 <= BENCH['run_seconds'] <= 51
    assert os.path.getsize(os.path.join(ROOT, 'BENCHMARK.json')) < 64 * 1024
    for m in BENCH['end_to_end']:
        assert set(m) - {'workloads'} == {'name', 'unit', 'better', 'bound',
                                          'source'}
        assert 0.01 <= m['bound'] <= 0.1
        assert m['source'] in ('host_clock', 'device_trace')
    for m in BENCH['per_layer']:
        assert set(m) - {'workloads'} == {'name', 'unit', 'better', 'source',
                                          'layer', 'moves'}
        assert m['source'] in ('device_trace', 'program_span',
                               'program_counter', 'host_clock')
    four = sum(1 for w in BENCH['workloads'] if w['chips'] == 4)
    assert four <= max(1, len(BENCH['workloads']) // 4)


def test_texts_fit_their_limits():
    texts = [w['why'] for w in BENCH['workloads']]
    texts += [c['why'] for c in BENCH['configs']]
    texts += [c['source'] for c in BENCH['configs']]
    texts += [m['layer'] for m in BENCH['per_layer']] + BENCH['command']
    for t in texts:
        assert 1 <= len(t) <= 200 and '\n' not in t and '\t' not in t, t


def test_files_under_paths_are_named_from_name_characters():
    for path in BENCH['paths']:
        for base, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != '__pycache__']
            for f in files:
                assert re.fullmatch(r'[A-Za-z0-9_.\-]+', f), (base, f)


@pytest.mark.parametrize('name', CELLS)
def test_cell_files_are_found_by_name(name):
    cell = manifest.cell(BENCH, name)
    assert cell['cell']['kind'] in ('serve_open', 'serve_closed')
    assert hasattr(manifest.kind(cell['cell']['kind']), 'run')
    assert cell['cell']['config'] == cell['entry']['config']
    assert cell['cell']['traffic'] == cell['entry']['traffic']
    assert cell['cell']['chips'] == cell['entry']['chips']
    reported = {m['name'] for m in manifest.metrics_of(BENCH, 'end_to_end',
                                                       name)}
    assert reported == set(cell['cell']['reports'])
    assert cell['config']['reduced'] == []
    assert 'rehearse' in cell['cell']


@pytest.mark.parametrize('name', PER_LAYER)
def test_metric_is_a_file_pair_with_a_reader(name):
    own = manifest.metric_file(name)
    entry = next(m for m in BENCH['per_layer'] if m['name'] == name)
    assert own['name'] == name and own['what']
    assert own['workloads'] == entry['workloads']
    assert callable(manifest.metric_reader(name).read)


def test_a_roofline_has_the_whole_steps_share_beside_it():
    for m in BENCH['per_layer']:
        if m['name'].endswith('_roofline'):
            assert m['unit'] == '%'
            assert any('mfu' in o['name'].split('.') and
                       o['moves'] == m['moves'] and
                       set(m['workloads']) <= set(o['workloads'])
                       for o in BENCH['per_layer']), m['name']


def test_config_keeps_every_published_width():
    cfg = manifest.cell(BENCH, CELLS[0])['config']
    published = dict(hidden_size=4096, intermediate_size=14336,
                     num_hidden_layers=32, num_attention_heads=32,
                     num_key_value_heads=8, head_dim=128, vocab_size=32768,
                     rope_theta=1e6, rms_norm_eps=1e-5,
                     max_position_embeddings=32768)
    assert {k: cfg[k] for k in published} == published
    assert cfg['assumed'] and cfg['deployment'] and cfg['precision']
    json.dumps(cfg)
