"""The load generator: a child process that never imports jax.

``python benchmark/client.py --plan plan.json --url http://host:port/generate
--out records.json`` replays one plan (``traffic.plan``) against a
streamed ``/generate`` endpoint and writes what the client saw: for each
request when it was due, when it was sent, when each line of tokens
arrived, the tokens, and the done line's fields. Open loop: every
request is sent at its due time whatever the system does (how late the
generator itself ran is recorded). Closed loop: each client sends its
next request when the last completes, and starts none after the window.

One process, one event loop, no threads: the engine's host loop is not
made to share the interpreter lock with its own load. The stream reader
is the arithmetic of ``tests/load_tests/loadgen.py::_http_one``: the
first token line gives the time to first token, and a line of k tokens
gives k gaps of 1/k of the time since the line before.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time
from typing import Any, Dict, List

import aiohttp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark import traffic  # noqa: E402


async def _one(session: aiohttp.ClientSession, url: str, seed: int,
               vocab: int, req: Dict[str, Any], t0: float,
               due_s: float, records: List[Dict[str, Any]]) -> None:
    rec: Dict[str, Any] = {
        'idx': req['idx'], 'prompt_len': req['prompt_len'],
        'max_new': req['max_new'], 'due_s': due_s, 'sent_s': None,
        'arrivals': [], 'tokens': [], 'done': False, 'done_s': None,
        'finish_reason': None, 'queue_wait_s': None, 'error': None}
    records.append(rec)
    payload = json.dumps({
        'tokens': traffic.request_tokens(seed, req['idx'], req['prompt_len'],
                                         vocab),
        'max_new_tokens': req['max_new'], 'temperature': 0.0,
        'stream': True}).encode()
    delay = t0 + due_s - time.time()
    if delay > 0:
        await asyncio.sleep(delay)
    rec['sent_s'] = time.time() - t0
    try:
        async with session.post(
                url, data=payload,
                headers={'Content-Type': 'application/json'}) as resp:
            if resp.status != 200:
                rec['error'] = f'http_{resp.status}'
                return
            async for line in resp.content:
                now = time.time() - t0
                if not line.strip():
                    continue
                msg = json.loads(line)
                if 'error' in msg:
                    rec['error'] = str(msg['error'])[:200]
                    break
                toks = msg.get('tokens') or []
                if toks:
                    rec['arrivals'].append([now, len(toks)])
                    rec['tokens'].extend(int(t) for t in toks)
                if msg.get('done'):
                    rec['done'] = True
                    rec['done_s'] = now
                    rec['finish_reason'] = msg.get('finish_reason')
                    rec['queue_wait_s'] = msg.get('queue_wait_s')
                    break
    except asyncio.CancelledError:
        rec['error'] = 'unanswered_at_drain_end'
        raise
    except (aiohttp.ClientError, OSError, ValueError) as e:
        rec['error'] = f'{type(e).__name__}: {e}'[:200]


async def replay(plan: Dict[str, Any], url: str, seed: int,
                 vocab: int) -> Dict[str, Any]:
    seconds, drain_s = plan['seconds'], plan['drain_s']
    records: List[Dict[str, Any]] = []
    conn = aiohttp.TCPConnector(limit=0)
    timeout = aiohttp.ClientTimeout(total=None, sock_connect=30)
    async with aiohttp.ClientSession(connector=conn, timeout=timeout) as s:
        t0 = time.time()

        async def tracked(req, due_s):
            await _one(s, url, seed, vocab, req, t0, due_s, records)

        if plan['loop'] == 'open':
            tasks = [asyncio.create_task(tracked(r, r['due_s']))
                     for r in plan['requests']]
        else:
            async def caller(mine):
                for req in mine:
                    now = time.time() - t0
                    if now >= seconds:
                        return
                    await tracked(req, now)
            tasks = [asyncio.create_task(caller(
                [r for r in plan['requests'] if r['client'] == c]))
                for c in range(plan['clients'])]
        done, pending = await asyncio.wait(tasks, timeout=seconds + drain_s)
        for t in pending:
            t.cancel()
        await asyncio.gather(*pending, return_exceptions=True)
        for t in done:
            t.result()
    return {'t0': t0, 'end_s': time.time() - t0,
            'unanswered': len(pending),
            'records': sorted(records, key=lambda r: r['idx'])}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument('--plan', required=True)
    ap.add_argument('--url', required=True)
    ap.add_argument('--out', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--vocab', type=int, required=True)
    args = ap.parse_args()
    with open(args.plan, encoding='utf-8') as f:
        plan = json.load(f)
    result = asyncio.run(replay(plan, args.url, args.seed, args.vocab))
    tmp = args.out + '.tmp'
    with open(tmp, 'w', encoding='utf-8') as f:
        json.dump(result, f)
    os.replace(tmp, args.out)


if __name__ == '__main__':
    main()
