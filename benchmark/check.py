"""The comparison that decides ``correct`` for a served model.

Once the window has closed, a sample of the requests it finished
(drawn from the seed, the longest among them) is run once through the
plain reference: the prompt with the tokens that were served. For every
served token the reference's logits at that position give a gap: how far
the served token's logit lies below the reference's best. The widest gap
over the sample and the mean gap are the numbers compared, each with a
limit of its own in the cell's file. A greedy token from the stated
precision differs from the reference's first choice only where two
logits all but tie, so its gap is small; a model computed in a lower
precision, a broken cache or an altered token puts tokens first that
the reference ranks far down.

The reference takes nothing the program made: it makes the weights again
from the seed (``weights.py``), one layer at a time.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights as weights_lib
from benchmark.reference import mistral as ref


def _dequant_layer(cfg, key, index):
    raw = weights_lib.layer(cfg, key, index)
    out = {}
    for name, leaf in raw.items():
        out[name] = (weights_lib.dequantize(*leaf, axis=0)
                     if isinstance(leaf, tuple) else leaf.astype(jnp.float32))
    return out


def _dequant_outer(cfg, key):
    raw = weights_lib.outer(cfg, key)
    return {'embed': weights_lib.dequantize(*raw['embed'], axis=1),
            'final_norm': raw['final_norm'].astype(jnp.float32),
            'lm_head': weights_lib.dequantize(*raw['lm_head'], axis=0)}


def reference_weights(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The whole float32 tree at once: for tests at small sizes only."""
    key = weights_lib.root_key(seed)
    layer_w = jax.jit(functools.partial(_dequant_layer, cfg))
    return {**jax.jit(functools.partial(_dequant_outer, cfg))(key),
            'layers': [layer_w(key, jnp.int32(i))
                       for i in range(cfg['num_hidden_layers'])]}


def pick_sample(finished: Sequence[Dict[str, Any]], seed: int,
                n: int) -> List[Dict[str, Any]]:
    """The longest finished request and ``n - 1`` others drawn from the
    seed. ``finished``: records with ``prompt_len`` and ``tokens``."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: (r['prompt_len'] + len(r['tokens']),
                                            r['idx']))
    longest, rest = order[-1], order[:-1]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, 0xC0FFEE])
    take = min(n - 1, len(rest))
    picked = [rest[i] for i in sorted(rng.choice(len(rest), take,
                                                 replace=False))]
    return [longest] + picked


def numbers(gaps: np.ndarray) -> Dict[str, float]:
    """What is compared, from the gaps of one set of tokens: the widest
    gap and the mean gap. A token that is the reference's first choice
    has gap 0, so the mean grows with the share of tokens that are not
    and with how far down each lies: with the square of the noise on
    the logits, where the widest gap grows with the noise itself."""
    return {'logit_gap_max': float(gaps.max()),
            'logit_gap_mean': float(gaps.mean()),
            'mismatch_share': float((gaps > 0).mean())}


def serve_gaps(cfg: Dict[str, Any], seed: int,
               samples: Sequence[Dict[str, Any]],
               controls: Sequence[str] = (),
               pad_to: Sequence[int] = (512,),
               rows_pad: int = 32) -> Dict[str, Any]:
    """Gaps of the served tokens, and of each control's first choices,
    under the reference's logits.

    ``samples``: ``{'prompt': [ids], 'served': [ids]}``. Each sequence
    is padded to the smallest of the lengths ``pad_to`` that holds it
    (a multiple of the largest beyond that), so that few shapes ever
    compile; the rows the head is computed on are padded to a multiple
    of ``rows_pad`` for the same reason. Returns ``{'served_tokens',
    'served': numbers, 'controls': {act: numbers}, 'gaps': {None | act:
    every gap}}``. A control does not decode: at each position of the
    same prompts and served tokens it is read by the token that it puts
    first there.
    """
    key = weights_lib.root_key(seed)
    outer = jax.jit(functools.partial(_dequant_outer, cfg))(key)
    layer_w = jax.jit(functools.partial(_dequant_layer, cfg))
    fwd = jax.jit(functools.partial(ref.layer_forward, cfg),
                  static_argnames=('act',))
    head = jax.jit(functools.partial(ref.head, cfg), static_argnames=('act',))
    acts: List[Optional[str]] = [None, *controls]
    seqs, rows = [], []
    for s in samples:
        fed = list(s['prompt']) + list(s['served'][:-1])
        n = next((b for b in sorted(pad_to) if b >= len(fed)),
                 -(-len(fed) // max(pad_to)) * max(pad_to))
        seqs.append(np.asarray(fed + [0] * (n - len(fed)), np.int32))
        # One shape for the head whatever the answer's length: the rows
        # past the answer repeat its last row and are cut off below.
        r = np.arange(len(s['prompt']) - 1, len(fed))
        width = -(-len(r) // rows_pad) * rows_pad
        rows.append(np.concatenate([r, np.full(width - len(r), r[-1])]))
    xs = {a: [ref.lower_precision(ref.embed(outer['embed'], jnp.asarray(t)),
                                  ref.precisions(a)[0]) for t in seqs]
          for a in acts}
    for index in range(cfg['num_hidden_layers']):
        w = layer_w(key, jnp.int32(index))
        for a in acts:
            xs[a] = [fwd(w, x, act=a) for x in xs[a]]
        del w
    gaps: Dict[Optional[str], List[np.ndarray]] = {a: [] for a in acts}
    for i, s in enumerate(samples):
        served = np.asarray(s['served'])
        logits = np.asarray(head(outer['final_norm'], outer['lm_head'],
                                 xs[None][i][rows[i]], act=None)
                            )[:len(served)]
        best, at = logits.max(axis=-1), np.arange(len(served))
        gaps[None].append(best - logits[at, served])
        for a in controls:
            low = np.asarray(head(outer['final_norm'], outer['lm_head'],
                                  xs[a][i][rows[i]], act=a))[:len(served)]
            gaps[a].append(best - logits[at, low.argmax(axis=-1)])
    joined = {a: np.concatenate(g) if g else np.zeros(1)
              for a, g in gaps.items()}
    return {'served_tokens': int(sum(len(s['served']) for s in samples)),
            'served': numbers(joined[None]),
            'controls': {a: numbers(joined[a]) for a in controls},
            'gaps': joined}


def verdict(found: Dict[str, Any], spec: Dict[str, Any],
            counts: Dict[str, int]) -> Dict[str, Dict[str, Any]]:
    """Each number compared beside its limit, ``ok`` with it. ``found``:
    ``numbers`` of the served tokens and ``served_tokens``; ``spec``:
    the cell file's ``check``; ``counts``: what has to be 0."""
    n, least = found['served_tokens'], spec['min_tokens']
    out: Dict[str, Dict[str, Any]] = {
        'compared_tokens': {'value': n, 'limit': f'>={least}',
                            'ok': n >= least}}
    for name, n in counts.items():
        out[name] = {'value': n, 'limit': 0, 'ok': n == 0}
    for name, limit in spec['limits'].items():
        value = found['served'][name]
        out[name] = {'value': value, 'limit': limit, 'ok': value <= limit}
    return out
