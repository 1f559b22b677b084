"""Family ``nemotron_h`` for the family-driven serving kinds
(``kinds/_serve_family.py``): the three model-specific things.

- ``program(cfg, seed)``: the program's configuration object and its
  parameter tree, from the configuration file's published key names and
  the benchmark's seeded weights;
- ``serve_gaps(...)``: ``check.serve_gaps``'s contract through THIS
  family's plain reference, one block at a time (an ``E`` block's
  float32 share is 2.6 GB at the published widths);
- ``work``: the module that counts the family's operations and bytes.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import check as check_lib
from benchmark import weights_nemotron_h as weights_lib
from benchmark import work_nemotron_h as work  # noqa: F401  (the family's)
from benchmark.reference import nemotron_h as ref

# A control is the reference computed otherwise (``ref.ACTS``), or
# UNRELATED: ids drawn from the seed in the served tokens' place, what
# a program whose answers have nothing to do with the model would
# serve (the upper reading of ``logit_gap_max``, which in a routed
# model cannot tell one precision from another: PERF.md section 2).
UNRELATED = 'unrelated'
CONTROLS = ('bf16', 'bf16-state', 'bf16-w8', 'bf16-w8a8', *ref.MECHANISMS,
            UNRELATED)


def program(cfg: Dict[str, Any], seed: int):
    """(``NemotronHConfig``, params) as ``infer.server`` would build
    them, the weights made on the device from the seed."""
    from skypilot_tpu.models import nemotron_h
    config = nemotron_h.NemotronHConfig(
        vocab_size=cfg['vocab_size'], dim=cfg['hidden_size'],
        pattern=weights_lib.pattern(cfg),
        n_heads=cfg['num_attention_heads'],
        n_kv_heads=cfg['num_key_value_heads'], head_dim=cfg['head_dim'],
        mamba_heads=cfg['mamba_num_heads'],
        mamba_head_dim=cfg['mamba_head_dim'],
        ssm_state=cfg['ssm_state_size'], n_groups=cfg['n_groups'],
        conv_kernel=cfg['conv_kernel'], chunk_size=cfg['chunk_size'],
        time_step_min=cfg['time_step_min'],
        time_step_max=cfg['time_step_max'],
        time_step_floor=cfg['time_step_floor'],
        n_routed_experts=cfg['n_routed_experts_published'],
        experts_per_token=cfg['num_experts_per_tok'],
        moe_ffn_dim=cfg['moe_intermediate_size'],
        shared_ffn_dim=(cfg['moe_shared_expert_intermediate_size']
                        * cfg['n_shared_experts']),
        routed_scale=cfg['routed_scaling_factor'],
        experts_held=cfg['n_routed_experts'],
        expert_offset=cfg.get('expert_offset', 0),
        max_seq_len=cfg['engine']['max_seq_len'],
        norm_eps=cfg['layer_norm_epsilon'],
        dtype=cfg['precision']['activations'])
    if cfg['norm_topk_prob'] is not True or cfg['n_group'] != 1 \
            or cfg['topk_group'] != 1:
        raise ValueError('the configuration file departs from what the '
                         'program computes (norm_topk_prob / n_group / '
                         'topk_group)')
    # Served in the activations' type: the tree is made bfloat16 and
    # the tiny CPU rehearsal states float32 (exactly representable).
    act = jnp.dtype(cfg['precision']['activations'])
    params = jax.tree_util.tree_map(
        lambda v: v.astype(act) if v.dtype == jnp.bfloat16 else v,
        weights_lib.init_all(cfg, seed))
    return config, params


def _f32_block(cfg, kind: str, key, index):
    """Block ``index`` (traced: one compilation a kind, and the same
    program that made the served tree's block) as float32."""
    return {k: v.astype(jnp.float32)
            for k, v in weights_lib.LAYER_FNS[kind](cfg, key, index).items()}


def _f32_outer(cfg, key):
    return {k: v.astype(jnp.float32)
            for k, v in weights_lib.outer(cfg, key).items()}


def reference_weights(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The whole float32 tree at once: for tests at small sizes only."""
    key = weights_lib.root_key(seed)
    kinds = weights_lib.pattern(cfg)
    make = {kind: jax.jit(functools.partial(_f32_block, cfg, kind))
            for kind in set(kinds)}
    return {**_f32_outer(cfg, key),
            'layers': [(kind, make[kind](key, jnp.int32(i)))
                       for i, kind in enumerate(kinds)]}


def numbers(gaps: np.ndarray, settled: np.ndarray) -> Dict[str, float]:
    """``check.numbers`` of a routed model: the widest gap over every
    token, the mean gap over the SETTLED tokens only (``serve_gaps``),
    and beside them the mean over all and how many were settled."""
    every = check_lib.numbers(gaps)
    kept = gaps[settled]
    return {**every,
            'logit_gap_mean': float(kept.mean()) if kept.size else 0.0,
            'logit_gap_mean_all': every['logit_gap_mean'],
            'settled_tokens': int(kept.size)}


def serve_gaps(cfg: Dict[str, Any], seed: int,
               samples: Sequence[Dict[str, Any]],
               controls: Sequence[str] = (),
               pad_to: Sequence[int] = (512,),
               rows_pad: int = 32,
               tie_margin: float = 0.0) -> Dict[str, Any]:
    """``check.serve_gaps`` for this family (same arguments, same
    result), blind to the router's near-ties.

    Where two router scores all but tie, ANY bfloat16 computation, the
    stated precision's too, chooses another expert than the float32
    reference, and the served token lies a flipped expert's worth below
    the reference's best: a gap that says nothing of the precision and
    is 100 times what rounding alone leaves. So the reference reads,
    per served token, its router's margin in every ``E`` block
    (``ref.router_margin``); a token is SETTLED where the least of them
    is at least ``tie_margin`` (the cell file's ``check.tie_margin``; 0
    settles every token), and ``logit_gap_mean`` is the mean over the
    settled tokens, which ``served_tokens`` counts. ``logit_gap_max``
    stays over every token: it guards wholesale faults. Also returned:
    ``'router_flip_share'``, the share of (token, ``E`` block) pairs at
    which rounding the router's input to bfloat16 ONCE changes the
    chosen experts, and ``'margins'``, each served token's least
    margin."""
    key = weights_lib.root_key(seed)
    kinds = weights_lib.pattern(cfg)
    outer = jax.jit(functools.partial(_f32_outer, cfg))(key)
    fwd = jax.jit(functools.partial(ref.layer_forward, cfg),
                  static_argnames=('kind', 'act'))
    flips_of = jax.jit(functools.partial(ref.router_flips, cfg))
    margin_of = jax.jit(functools.partial(ref.router_margin, cfg))
    head = jax.jit(functools.partial(ref.head, cfg),
                   static_argnames=("act",))
    acts: List[Optional[str]] = [None, *(c for c in controls
                                         if c != UNRELATED)]
    drawn = np.random.default_rng([int(seed) & 0xFFFFFFFF, 0xD1CE])
    seqs, rows, lens = [], [], []
    for s in samples:
        fed = list(s['prompt']) + list(s['served'][:-1])
        n = next((b for b in sorted(pad_to) if b >= len(fed)),
                 -(-len(fed) // max(pad_to)) * max(pad_to))
        seqs.append(np.asarray(fed + [0] * (n - len(fed)), np.int32))
        lens.append(len(fed))
        r = np.arange(len(s['prompt']) - 1, len(fed))
        width = -(-len(r) // rows_pad) * rows_pad
        rows.append(np.concatenate([r, np.full(width - len(r), r[-1])]))
    xs = {a: [ref.hooks(a)[0](ref.embed(outer['embed'], jnp.asarray(t)))
              for t in seqs] for a in acts}
    make = {kind: jax.jit(functools.partial(_f32_block, cfg, kind))
            for kind in set(kinds)}
    int8 = jax.jit(ref.quantize_weights)
    flips = pairs = 0
    least = [np.full(len(r), np.inf, np.float32) for r in rows]
    for index, kind in enumerate(kinds):
        w = make[kind](key, jnp.int32(index))
        w8 = int8(w) if any(map(ref.weights_int8, acts)) else None
        if kind == 'E':
            for i, (x, n) in enumerate(zip(xs[None], lens)):
                flips += int(np.asarray(flips_of(w, x))[:n].sum())
                pairs += n
                least[i] = np.minimum(
                    least[i], np.asarray(margin_of(w, x))[rows[i]])
        for a in acts:
            wa = w8 if ref.weights_int8(a) else w
            xs[a] = [fwd(kind=kind, w=wa, x=x, act=a) for x in xs[a]]
        del w, w8
    gaps: Dict[Optional[str], List[np.ndarray]] = {a: [] for a in acts}
    for i, s in enumerate(samples):
        served = np.asarray(s['served'])
        logits = np.asarray(head(outer['final_norm'], outer['lm_head'],
                                 xs[None][i][rows[i]]))[:len(served)]
        best, at = logits.max(axis=-1), np.arange(len(served))
        gaps[None].append(best - logits[at, served])
        for a in acts[1:]:
            low = np.asarray(head(outer['final_norm'], outer['lm_head'],
                                  xs[a][i][rows[i]], act=a))[:len(served)]
            gaps[a].append(best - logits[at, low.argmax(axis=-1)])
        if UNRELATED in controls:
            ids = drawn.integers(0, cfg['vocab_size'], len(served))
            gaps.setdefault(UNRELATED, []).append(best - logits[at, ids])
    joined = {a: np.concatenate(g) if g else np.zeros(1)
              for a, g in gaps.items()}
    margins = (np.concatenate([m[:len(s['served'])]
                               for m, s in zip(least, samples)])
               if samples else np.zeros(1, np.float32))
    settled = margins >= tie_margin
    served = numbers(joined[None], settled)
    return {'served_tokens': served['settled_tokens'],
            'served': served,
            'controls': {a: numbers(joined[a], settled) for a in controls},
            'gaps': joined, 'margins': margins,
            'router_flip_share': flips / pairs if pairs else 0.0}
