"""The arithmetic from the client's records to the end-to-end numbers,
against hand-worked records."""
import pytest

from benchmark.kinds import _serve
from benchmark.stats import percentile, token_gaps


def _rec(due, arrivals, prompt_len=10, done_s=None):
    """``arrivals``: [(seconds, tokens in the line)]."""
    n = sum(k for _, k in arrivals)
    return {'due_s': due, 'arrivals': [list(a) for a in arrivals],
            'tokens': list(range(n)), 'prompt_len': prompt_len,
            'done': done_s is not None, 'done_s': done_s}


def test_percentile_is_nearest_rank_and_none_of_nothing():
    values = [5.0, 1.0, 4.0, 2.0, 3.0]
    assert percentile(values, 0.5) == 3.0
    assert percentile(values, 0.9) == 5.0
    assert percentile(values, 0.2) == 1.0
    assert percentile([], 0.5) is None


def test_a_line_of_k_tokens_gives_k_gaps_of_one_kth():
    r = _rec(0.0, [(1.0, 1), (1.3, 3), (1.4, 1)])
    assert token_gaps([r]) == pytest.approx([0.1, 0.1, 0.1, 0.1])
    assert token_gaps([_rec(0.0, [(1.0, 4)])]) == []


def test_time_to_first_token_runs_from_the_due_time_over_every_request():
    records = [
        _rec(0.5, [(0.7, 1), (0.8, 1)], done_s=0.8),          # 0.2 s
        _rec(1.0, [(1.6, 1), (1.7, 1)], done_s=1.7),          # 0.6 s
        _rec(2.0, []),                     # never answered: enters at the end
    ]
    out = _serve.end_to_end(records, seconds=3.0, end_s=5.0)
    assert out['ttft_p90_s'] == pytest.approx(3.0)
    assert out['ttft_mean_s'] == pytest.approx((0.2 + 0.6 + 3.0) / 3)
    assert out['itl_p50_ms'] == pytest.approx(100.0)
    assert out['itl_p90_ms'] == pytest.approx(100.0)


def test_the_rate_counts_prompt_and_output_of_requests_done_in_the_window():
    records = [
        _rec(0.0, [(0.5, 1), (0.9, 3)], prompt_len=100, done_s=0.9),
        _rec(0.0, [(1.5, 1), (2.5, 3)], prompt_len=200, done_s=2.5),  # late
        _rec(0.0, [(0.4, 2)], prompt_len=50),                 # never done
    ]
    out = _serve.end_to_end(records, seconds=2.0, end_s=3.0)
    assert out['serve_tokens_per_s'] == pytest.approx((100 + 4) / 2.0)


def test_nothing_to_read_gives_nothing():
    out = _serve.end_to_end([], seconds=2.0, end_s=2.0)
    assert out['ttft_p90_s'] is None and out['ttft_mean_s'] is None
    assert out['itl_p90_ms'] is None and out['itl_p50_ms'] is None
