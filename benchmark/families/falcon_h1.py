"""Family ``falcon_h1`` for the family-driven serving kinds
(``kinds/_serve_family.py``): the three model-specific things.

- ``program(cfg, seed)``: the program's configuration object and its
  parameter tree, from the configuration file's published key names and
  the benchmark's seeded weights;
- ``serve_gaps(...)``: ``check.serve_gaps``'s contract through THIS
  family's plain reference, one block at a time (a block's float32
  copy is 1.7 GB at the published widths);
- ``work``: the module that counts the family's operations and bytes.

The model is dense: no router, so no token is unsettled and
``tie_margin`` has nothing to read; the mean gap is over every
compared token, as the dense GQA block's.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import check as check_lib
from benchmark import weights_falcon_h1 as weights_lib
from benchmark import work_falcon_h1 as work  # noqa: F401  (the family's)
from benchmark.reference import falcon_h1 as ref

CONTROLS = ('bf16', 'bf16-w8a8', *ref.MECHANISMS)


def config_of(cfg: Dict[str, Any]):
    """The program's ``FalconH1Config`` from the configuration file's
    published key names."""
    from skypilot_tpu.models import falcon_h1
    if (cfg['mamba_rms_norm'] is not True or cfg['mamba_norm_before_gate']
            or cfg['mamba_use_mlp'] is not True
            or cfg['mamba_conv_bias'] is not True or cfg['rope_scaling']
            or any(cfg[k] for k in ('attention_bias', 'mamba_proj_bias',
                                    'mlp_bias', 'projectors_bias'))
            or cfg['hidden_act'] != 'silu' or cfg['tie_word_embeddings']):
        raise ValueError('the configuration file departs from what the '
                         'program computes (a bias, another gated norm, '
                         'no MLP, scaled rope, tied head or another '
                         'activation)')
    return falcon_h1.FalconH1Config(
        vocab_size=cfg['vocab_size'], dim=cfg['hidden_size'],
        n_layers=cfg['num_hidden_layers'],
        n_heads=cfg['num_attention_heads'],
        n_kv_heads=cfg['num_key_value_heads'], head_dim=cfg['head_dim'],
        rope_theta=float(cfg['rope_theta']),
        mamba_heads=cfg['mamba_n_heads'], mamba_head_dim=cfg['mamba_d_head'],
        ssm_state=cfg['mamba_d_state'], n_groups=cfg['mamba_n_groups'],
        conv_kernel=cfg['mamba_d_conv'], chunk_size=cfg['mamba_chunk_size'],
        ffn_dim=weights_lib.sizes(cfg)['f'],
        **{k: float(cfg[k]) for k in ref.MULTIPLIERS},
        ssm_multipliers=tuple(float(v) for v in cfg['ssm_multipliers']),
        mlp_multipliers=tuple(float(v) for v in cfg['mlp_multipliers']),
        max_seq_len=cfg['engine']['max_seq_len'],
        norm_eps=cfg['rms_norm_eps'],
        dtype=cfg['precision']['activations'])


def program(cfg: Dict[str, Any], seed: int):
    """(``FalconH1Config``, params) as ``infer.server`` would build
    them, the weights made on the device from the seed."""
    config = config_of(cfg)
    # Served in the activations' type: the tree is made bfloat16 and
    # the tiny CPU rehearsal states float32 (exactly representable).
    act = jnp.dtype(cfg['precision']['activations'])
    params = jax.tree_util.tree_map(
        lambda v: v.astype(act) if v.dtype == jnp.bfloat16 else v,
        weights_lib.init_all(cfg, seed))
    return config, params


def _f32(tree):
    return {k: v.astype(jnp.float32) for k, v in tree.items()}


def reference_weights(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The whole float32 tree at once: for tests at small sizes only."""
    key = weights_lib.root_key(seed)
    make = weights_lib.make_layer(cfg)
    return {**_f32(weights_lib.make_outer(cfg)(key)),
            'layers': [_f32(make(key, jnp.int32(i)))
                       for i in range(cfg['num_hidden_layers'])]}


def serve_gaps(cfg: Dict[str, Any], seed: int,
               samples: Sequence[Dict[str, Any]],
               controls: Sequence[str] = (),
               pad_to: Sequence[int] = (512,),
               rows_pad: int = 32,
               tie_margin: float = 0.0) -> Dict[str, Any]:
    """``check.serve_gaps`` for this family: same arguments, same
    result (``tie_margin`` is the kind's argument for a routed model
    and is not read: nothing here is routed)."""
    key = weights_lib.root_key(seed)
    # The makers that made the served tree (found in the compile cache),
    # their leaves read as float32; a block of the reference is compiled
    # at the compiler's least effort, as the makers are.
    outer = _f32(weights_lib.make_outer(cfg)(key))
    make = weights_lib.make_layer(cfg)
    fwd = jax.jit(functools.partial(ref.layer_forward, cfg),
                  static_argnames=('act',),
                  compiler_options=weights_lib.QUICK_COMPILE)
    head = jax.jit(functools.partial(ref.head, cfg),
                   static_argnames=('act',))
    acts: List[Optional[str]] = [None, *controls]
    seqs, rows = [], []
    for s in samples:
        fed = list(s['prompt']) + list(s['served'][:-1])
        n = next((b for b in sorted(pad_to) if b >= len(fed)),
                 -(-len(fed) // max(pad_to)) * max(pad_to))
        seqs.append(np.asarray(fed + [0] * (n - len(fed)), np.int32))
        r = np.arange(len(s['prompt']) - 1, len(fed))
        width = -(-len(r) // rows_pad) * rows_pad
        rows.append(np.concatenate([r, np.full(width - len(r), r[-1])]))
    xs = {a: [ref.embed(cfg, outer['embed'], jnp.asarray(t), a)
              for t in seqs] for a in acts}
    int8 = jax.jit(ref.quantize_weights)
    for index in range(cfg['num_hidden_layers']):
        w = _f32(make(key, jnp.int32(index)))
        w8 = int8(w) if any(map(ref.weights_int8, acts)) else None
        for a in acts:
            wa = w8 if ref.weights_int8(a) else w
            xs[a] = [fwd(wa, x, act=a) for x in xs[a]]
        del w, w8
    gaps: Dict[Optional[str], List[np.ndarray]] = {a: [] for a in acts}
    for i, s in enumerate(samples):
        served = np.asarray(s['served'])
        logits = np.asarray(head(outer['final_norm'], outer['lm_head'],
                                 xs[None][i][rows[i]]))[:len(served)]
        best, at = logits.max(axis=-1), np.arange(len(served))
        gaps[None].append(best - logits[at, served])
        for a in controls:
            low = np.asarray(head(outer['final_norm'], outer['lm_head'],
                                  xs[a][i][rows[i]], act=a))[:len(served)]
            gaps[a].append(best - logits[at, low.argmax(axis=-1)])
    joined = {a: np.concatenate(g) if g else np.zeros(1)
              for a, g in gaps.items()}
    return {'served_tokens': int(sum(len(s['served']) for s in samples)),
            'served': check_lib.numbers(joined[None]),
            'controls': {a: check_lib.numbers(joined[a]) for a in controls},
            'gaps': joined}
