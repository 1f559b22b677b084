"""The generator gives a seed the same requests every time, and every
seed the same work in another order."""
import json

import pytest

from benchmark import manifest, traffic

BENCH = manifest.load()
MIXES = sorted({w['traffic'] for w in BENCH['workloads']})


def _mix(name, fixed_order=True):
    mix = manifest.read_json(f'{manifest.HERE}/mixes/{name}.json')
    if not fixed_order:
        mix.pop('order_seed', None)
    return mix


@pytest.mark.parametrize('name', MIXES)
def test_plan_is_byte_identical_for_a_seed_and_differs_across_seeds(name):
    mix = _mix(name, fixed_order=False)
    a = json.dumps(traffic.plan(mix, 3_000_000_019, 30), sort_keys=True)
    b = json.dumps(traffic.plan(mix, 3_000_000_019, 30), sort_keys=True)
    c = json.dumps(traffic.plan(mix, 3_000_000_020, 30), sort_keys=True)
    assert a == b and a != c


@pytest.mark.parametrize('name', MIXES)
def test_every_seed_gets_the_same_lengths_in_another_order(name):
    mix = _mix(name, fixed_order=False)
    plans = [traffic.plan(mix, s, 30)['requests'] for s in (1, 2**33 + 5)]
    sets = [sorted((r['prompt_len'], ) for r in p) for p in plans]
    assert sets[0] == sets[1]
    assert sorted(r['max_new'] for r in plans[0]) == sorted(
        r['max_new'] for r in plans[1])
    assert [r['prompt_len'] for r in plans[0]] != [r['prompt_len']
                                                   for r in plans[1]]
    for r in plans[0]:
        assert mix['prompt'].get('min', 1) <= r['prompt_len'] <= mix['prompt']['max']


def test_open_loop_arrivals_fill_the_window_at_the_rate():
    mix = _mix('chat')
    p = traffic.plan(mix, 11, 30)
    due = [r['due_s'] for r in p['requests']]
    assert len(due) == round(mix['rate_rps'] * 30)
    assert due == sorted(due) and 0 < due[0] and 29 < due[-1] < 30
    gaps = sorted(b - a for a, b in zip(due, due[1:]))
    # exponential gaps: the median is ln 2 of the mean
    assert 0.5 < gaps[len(gaps) // 2] * mix['rate_rps'] < 0.9


def test_closed_loop_deals_each_round_the_same_lengths():
    mix = _mix('longprompt-batch')
    reqs = traffic.plan(mix, 5, 30)['requests']
    c = mix['clients']
    rounds = [sorted(r['prompt_len'] for r in reqs[i:i + c])
              for i in range(0, len(reqs), c)]
    assert all(r == rounds[0] for r in rounds)
    assert {r['client'] for r in reqs} == set(range(c))


def test_tokens_cover_the_whole_vocabulary_and_repeat_for_a_seed():
    a = traffic.request_tokens(2**31 + 7, 3, 4000, 32768)
    assert a == traffic.request_tokens(2**31 + 7, 3, 4000, 32768)
    assert a != traffic.request_tokens(2**31 + 8, 3, 4000, 32768)
    assert min(a) < 400 and max(a) > 32000 and len(a) == 4000
    assert a[:64] != traffic.request_tokens(2**31 + 7, 4, 4000, 32768)[:64]


def test_bursts_need_only_data():
    mix = dict(_mix('chat'), burst=4)
    reqs = traffic.plan(mix, 1, 20)['requests']
    assert len(reqs) % 4 == 0
    assert reqs[1]['due_s'] - reqs[0]['due_s'] < 1e-3
    assert reqs[4]['due_s'] - reqs[3]['due_s'] > 1e-3


def test_a_mix_that_fixes_its_order_replays_one_schedule_for_every_seed():
    mix = _mix('chat')
    assert 'order_seed' in mix
    a, b = (traffic.plan(mix, s, 50)['requests'] for s in (7, 2**32 + 9))
    assert a == b
    assert traffic.plan(mix, mix['order_seed'], 50)['requests'] == \
        traffic.plan(_mix('chat', fixed_order=False), mix['order_seed'],
                     50)['requests']
