"""Family ``dots3`` for the family-driven serving kinds
(``kinds/_serve_family.py``): the three model-specific things.

- ``program(cfg, seed)``: the program's configuration object and its
  parameter tree, from the configuration file's published key names and
  the benchmark's seeded weights;
- ``serve_gaps(...)``: ``check.serve_gaps``'s contract through THIS
  family's plain reference, one block at a time (an expert block's
  float32 share is 3.6 GB at the published widths) and one sequence at
  a time (32k positions);
- ``work``: the module that counts the family's operations and bytes.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights_dots3 as weights_lib
from benchmark import work_dots3 as work  # noqa: F401  (the family's)
from benchmark.families.nemotron_h import numbers  # a routed model's
from benchmark.reference import dots3 as ref

# A control is the reference computed otherwise (``ref.ACTS``), or
# UNRELATED: ids drawn from the seed in the served tokens' place (the
# upper reading of ``logit_gap_max``).
UNRELATED = 'unrelated'
CONTROLS = ('bf16', 'bf16-low', 'bf16-w8', 'bf16-w8a8', *ref.MECHANISMS,
            UNRELATED)


def program(cfg: Dict[str, Any], seed: int):
    """(``Dots3Config``, params) as ``infer.server`` would build them,
    the weights made on the device from the seed."""
    from skypilot_tpu.models import dots3
    if (cfg['norm_topk_prob'] is not True or cfg['scoring_func'] != 'sigmoid'
            or cfg['topk_method'] != 'noaux_tc'
            or cfg['attention_gate_type'] != 'headwise'
            or cfg['swa_attention_gate_type'] != 'headwise'
            or cfg['moe_layer_freq'] != 1 or cfg['rope_scaling'] is not None
            or cfg['num_key_value_heads'] != cfg['num_attention_heads']
            or cfg['swa_num_key_value_heads']
            != cfg['swa_num_attention_heads']
            or cfg['attention_bias'] or cfg['hidden_act'] != 'silu'
            or cfg['tie_word_embeddings']):
        raise ValueError('the configuration file departs from what the '
                         'program computes')
    config = dots3.Dots3Config(
        vocab_size=cfg['vocab_size'], dim=cfg['hidden_size'],
        layer_types=weights_lib.layer_types(cfg),
        first_k_dense=cfg['first_k_dense_replace'],
        dense_ffn_dim=cfg['intermediate_size'],
        n_heads=cfg['num_attention_heads'], q_lora_rank=cfg['q_lora_rank'],
        kv_lora_rank=cfg['kv_lora_rank'],
        qk_nope_dim=cfg['qk_nope_head_dim'],
        qk_rope_dim=cfg['qk_rope_head_dim'], v_dim=cfg['v_head_dim'],
        rope_theta=cfg['rope_theta'], index_heads=cfg['index_n_heads'],
        index_dim=cfg['index_head_dim'], index_topk=cfg['index_topk'],
        swa_heads=cfg['swa_num_attention_heads'],
        swa_q_lora_rank=cfg['swa_q_lora_rank'],
        swa_kv_lora_rank=cfg['swa_kv_lora_rank'],
        swa_qk_nope_dim=cfg['swa_qk_nope_head_dim'],
        swa_qk_rope_dim=cfg['swa_qk_rope_head_dim'],
        swa_v_dim=cfg['swa_v_head_dim'], swa_rope_theta=cfg['swa_rope_theta'],
        window=cfg['sliding_window_size'],
        lora_rescale=cfg['apply_mla_qkv_lora_rescale'],
        n_routed_experts=cfg['n_routed_experts_published'],
        experts_per_token=cfg['num_experts_per_tok'],
        moe_ffn_dim=cfg['moe_intermediate_size'],
        shared_ffn_dim=cfg['moe_intermediate_size'] * cfg['n_shared_experts'],
        routed_scale=cfg['routed_scaling_factor'],
        experts_held=cfg['n_routed_experts'],
        expert_offset=cfg.get('expert_offset', 0),
        max_seq_len=cfg['engine']['max_seq_len'],
        norm_eps=cfg['rms_norm_eps'], dtype=cfg['precision']['activations'])
    # Served in the activations' type: the tree is made bfloat16 and
    # the tiny CPU rehearsal states float32 (exactly representable).
    act = jnp.dtype(cfg['precision']['activations'])
    params = jax.tree_util.tree_map(
        lambda v: v.astype(act) if v.dtype == jnp.bfloat16 else v,
        weights_lib.init_all(cfg, seed))
    return config, params


@jax.jit
def _f32(tree):
    return jax.tree_util.tree_map(lambda v: v.astype(jnp.float32), tree)


def _makers(cfg):
    """Block ``index`` of a kind as float32: the very program that
    made the served tree's block (``weights_lib.makers``: compiled once
    for both), then widened."""
    make = weights_lib.makers(cfg)
    return {kd: (lambda key, index, kd=kd: _f32(make[kd](key, index)))
            for kd in make}


def _f32_outer(cfg, key):
    return _f32(weights_lib.make_outer(cfg)(key))


def reference_weights(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The whole float32 tree at once: for tests at small sizes only."""
    key = weights_lib.root_key(seed)
    make = _makers(cfg)
    return {**_f32_outer(cfg, key),
            'layers': [(kd[0], make[kd](key, jnp.int32(i)))
                       for i, kd in enumerate(weights_lib.block_kinds(cfg))]}


def serve_gaps(cfg: Dict[str, Any], seed: int,
               samples: Sequence[Dict[str, Any]],
               controls: Sequence[str] = (),
               pad_to: Sequence[int] = (512,),
               rows_pad: int = 32,
               tie_margin: float = 0.0) -> Dict[str, Any]:
    """``check.serve_gaps`` for this family (same arguments, same
    result), blind to the router's near-ties as
    ``families/nemotron_h.serve_gaps`` is and for its reason: a token is
    SETTLED where the least router margin of its expert blocks
    (``ref.block_margin``) is at least ``tie_margin``;
    ``logit_gap_mean`` is over the settled tokens, ``logit_gap_max``
    over all. Sequences go through a block one at a time (32k positions
    of float32 attention fill the chip), each block's weights made once
    for all of them."""
    key = weights_lib.root_key(seed)
    kinds = weights_lib.block_kinds(cfg)
    outer = _f32_outer(cfg, key)
    quick = weights_lib.QUICK_COMPILE
    fwd = jax.jit(functools.partial(ref.layer_forward, cfg),
                  static_argnames=('kind', 'act'), compiler_options=quick)
    # The reference itself goes through a block ONCE for its stream and
    # its router margin: the attention half is most of a block's time
    # at 32k positions, and every program is a compilation.
    both = jax.jit(functools.partial(ref.layer_and_margin, cfg),
                   static_argnames=('kind',), compiler_options=quick)
    head = jax.jit(functools.partial(ref.head, cfg),
                   static_argnames=('act',))
    acts: List[Optional[str]] = [None, *(c for c in controls
                                         if c != UNRELATED)]
    drawn = np.random.default_rng([int(seed) & 0xFFFFFFFF, 0xD1CE])
    seqs, rows = [], []
    for s in samples:
        fed = list(s['prompt']) + list(s['served'][:-1])
        n = next((b for b in sorted(pad_to) if b >= len(fed)),
                 -(-len(fed) // max(pad_to)) * max(pad_to))
        seqs.append(np.asarray(fed + [0] * (n - len(fed)), np.int32))
        r = np.arange(len(s['prompt']) - 1, len(fed))
        width = -(-len(r) // rows_pad) * rows_pad
        rows.append(np.concatenate([r, np.full(width - len(r), r[-1])]))
    # Between blocks the streams wait on the HOST where controls are
    # read: a sequence of 33,792 positions is 0.7 GB in float32, and
    # several controls' worth of them beside a block's float32 weights
    # would not fit the chip. The reference's own stay on the device
    # when it runs alone (a run of the cell: 1.4 GB, and 14 GB less
    # through the host's link).
    rest = jnp.asarray if len(acts) == 1 else np.asarray
    xs = {a: [rest(ref.hooks(a)[0](ref.embed(outer['embed'],
                                             jnp.asarray(t))))
              for t in seqs] for a in acts}
    make = _makers(cfg)
    int8 = jax.jit(ref.quantize_weights)
    least = [np.full(len(r), np.inf, np.float32) for r in rows]
    for index, kd in enumerate(kinds):
        w = make[kd](key, jnp.int32(index))
        w8 = int8(w) if any(map(ref.weights_int8, acts)) else None
        for i, x in enumerate(xs[None]):
            if tie_margin > 0:
                x, margin = both(kind=kd[0], w=w, x=jnp.asarray(x))
                least[i] = np.minimum(least[i], np.asarray(margin)[rows[i]])
            else:
                x = fwd(kind=kd[0], w=w, x=jnp.asarray(x), act=None)
            xs[None][i] = rest(x)
        for a in acts[1:]:
            wa = w8 if ref.weights_int8(a) else w
            xs[a] = [np.asarray(fwd(kind=kd[0], w=wa, x=jnp.asarray(x),
                                    act=a)) for x in xs[a]]
        del w, w8
    gaps: Dict[Optional[str], List[np.ndarray]] = {a: [] for a in acts}
    for i, s in enumerate(samples):
        served = np.asarray(s['served'])
        logits = np.asarray(head(outer['final_norm'], outer['lm_head'],
                                 jnp.asarray(xs[None][i][rows[i]]))
                            )[:len(served)]
        best, at = logits.max(axis=-1), np.arange(len(served))
        gaps[None].append(best - logits[at, served])
        for a in acts[1:]:
            low = np.asarray(head(outer['final_norm'], outer['lm_head'],
                                  jnp.asarray(xs[a][i][rows[i]]), act=a)
                             )[:len(served)]
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
            'gaps': joined, 'margins': margins}
