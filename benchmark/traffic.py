"""One general traffic generator, driven by a mix's data file.

A mix (``benchmark/mixes/<name>.json``) gives the loop (``open``:
requests are due on a schedule whatever the system does; ``closed``:
``clients`` callers each send their next request when the last one
completes), the arrival rate and burst size, and the distributions of
prompt and output lengths. ``plan``
turns it, a seed and a window length into the requests of one run.

Every seed gets the same set of lengths and the same set of gaps
between arrivals: they are the quantiles of their distributions at
evenly spaced points, shuffled. Without ``order_seed`` the run's seed
shuffles them, so two seeds offer the same work in another order. That
is not steady enough for a tail below the knee: which long prompts meet
decides it (on the chip the 95th percentile of the time to first token
read 0.45 to 1.04 s over six orders, and within 0.1% to 3% for one order
run twice; PERF.md section 2). A mix that states ``order_seed`` fixes
the order; the run's seed then changes the token ids and the weights,
which do not change the work. Token ids are drawn over the whole
vocabulary (the generator this one was copied from,
``tests/load_tests/loadgen.py``, drew from 200 ids and fixed the output
length).

Nothing here imports jax: the client child imports this module.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List

import numpy as np


def _rng(seed: int, *stream: int) -> np.random.Generator:
    seed = int(seed)
    return np.random.default_rng(
        [seed & 0xFFFFFFFF, seed >> 32, *[int(s) for s in stream]])


def quantiles(dist: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` whole numbers: the distribution's quantiles at (i+0.5)/n."""
    u = (np.arange(n) + 0.5) / n
    kind = dist['dist']
    if kind == 'fixed':
        x = np.full(n, float(dist['value']))
    elif kind == 'uniform':
        x = dist['min'] + u * (dist['max'] + 1 - dist['min'])
    elif kind == 'pareto':
        # Pareto of the given shape and scale, cut at min and max (the
        # mean of the uncut one is scale * shape / (shape - 1)).
        x = dist['scale'] * (1.0 - u) ** (-1.0 / dist['shape'])
    else:
        raise ValueError(f'unknown distribution {kind!r}')
    lo = dist.get('min', 1)
    hi = dist.get('max', max(lo, int(x.max())))
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def request_tokens(seed: int, idx: int, n: int, vocab: int) -> List[int]:
    """Prompt ``idx``: ``n`` ids over the whole vocabulary."""
    return _rng(seed, 1, idx).integers(0, vocab, n).tolist()


def plan(mix: Dict[str, Any], seed: int, seconds: float) -> Dict[str, Any]:
    """The requests of one run: ``{'loop', 'seconds', 'drain_s',
    'clients', 'requests': [{'idx', 'due_s' | 'client', 'prompt_len',
    'max_new'}]}``. Open-loop requests come ordered by
    ``due_s``; closed-loop ones by client and turn."""
    loop = mix['loop']
    # The order of lengths and gaps: the mix's own, where it fixes one
    # (then every seed replays one schedule, and only the tokens and the
    # weights differ), else drawn from the run's seed.
    order = mix.get('order_seed', seed)
    if loop == 'open':
        burst = int(mix.get('burst', 1))
        n_bursts = max(1, int(round(mix['rate_rps'] * seconds / burst)))
        n = n_bursts * burst
        # Exponential gaps between bursts: their quantiles, shuffled,
        # then scaled so that the last burst is due inside the window.
        u = (np.arange(n_bursts) + 0.5) / n_bursts
        gaps = -np.log1p(-u)
        _rng(order, 3).shuffle(gaps)
        due = np.cumsum(gaps)
        due = np.repeat(due * (seconds * (1 - 0.5 / n_bursts) / due[-1]),
                        burst) + np.tile(np.arange(burst) * 1e-4, n_bursts)
        clients = 0
    elif loop == 'closed':
        clients = int(mix['clients'])
        # Rounds of one request a client: each round holds the same set
        # of lengths, dealt to the clients in another order.
        rounds = int(math.ceil(mix['max_requests_per_client']))
        n = clients * rounds
        due = None
    else:
        raise ValueError(f"loop must be 'open' or 'closed', got {loop!r}")

    per = clients if loop == 'closed' else n
    blocks = n // per
    prompt_q, out_q = quantiles(mix['prompt'], per), quantiles(mix['output'],
                                                              per)
    prompts = np.concatenate([_rng(order, 4, b).permutation(prompt_q)
                              for b in range(blocks)])
    outs = np.concatenate([_rng(order, 5, b).permutation(out_q)
                           for b in range(blocks)])
    requests = []
    for i in range(n):
        req: Dict[str, Any] = {'idx': i, 'prompt_len': int(prompts[i]),
                               'max_new': int(outs[i])}
        if loop == 'open':
            req['due_s'] = float(due[i])
        else:
            req['client'], req['turn'] = i % clients, i // clients
        requests.append(req)
    return {'loop': loop, 'seconds': float(seconds),
            'drain_s': float(mix.get('drain_s', 60.0)),
            'clients': clients, 'requests': requests}
