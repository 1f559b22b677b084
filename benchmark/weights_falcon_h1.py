"""Seeded weights for the Falcon-H1 parallel block (attention and a
Mamba-2 mixer side by side, then a gated MLP), made by the benchmark.

Keyed as ``weights_nemotron_h.py`` keys its blocks: block ``l``'s key
is ``fold_in(fold_in(root, 1), l)``, so a block made alone (the
reference makes them one at a time) equals the block the program was
given. Every matrix is drawn in float32 and rounded once to the type
it is served in: bfloat16 for the projections, the embedding and the
head; float32 for what the configuration's precision block keeps in
float32 (the convolution, ``dt_bias``, ``A_log``, ``D``). The
reference reads the same rounded numbers. Leaves carry the program's
names (``skypilot_tpu/models/falcon_h1.py``).

**Drawn for the published multipliers.** The model's eleven
maximal-update multipliers are small (the key's is 0.011, the MLP's
down projection's 0.011, the head's 1/128); a trained checkpoint's
matrices are large where its multiplier is small. Gaussian matrices of
the usual ``fan_in ** -0.5`` would not be: the keys would be 90 times
too short (attention uniform over the context, whatever was asked),
the mixer's inputs a twentieth of the convolution's bias (its output a
constant), and the three terms a block adds to the stream would lie
orders of magnitude apart, so that a program that dropped the smaller
ones would pass the comparison that decides ``correct``. So every
matrix that a multiplier follows is drawn with ``std = gain *
fan_in ** -0.5 / multiplier`` (``W_in`` column by column, by the
segment's ``ssm_multipliers`` and ``ssm_in_multiplier``), which leaves
what the multiplied product feeds of unit size, and the three output
projections' gains (``GAIN``) are set so that the attention branch,
the mixer branch and the MLP each add a term of RMS about ``TERM`` to a
stream that the embedding starts at RMS 1. At the published widths
(block 0, seed 7, the float32 reference on this sandbox's CPU, PR 33;
``reference.falcon_h1.branch_rms``) over 1,536 tokens: stream 1.00,
attention 0.272, mixer 0.252, MLP 0.246; over 256 tokens attention
0.359 (softmax-weighted values shrink as the context grows), the other
two the same to the third digit.

Scores (``q . k / sqrt(hd)``) are drawn with a spread of ``GAIN['wk']``
= 2: attention that is neither uniform nor one-hot at contexts of
hundreds to thousands of tokens. What else is drawn so that a shortcut
shows: the norms have ``weights.py``'s hot channels; ``conv_b`` (std
0.5) is large enough that leaving it out changes the answer;
``dt_bias``, ``A_log`` and ``D`` follow the Mamba-2 recipe, so that
some heads forget within ten tokens and others carry a thousand.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmark import weights as base

root_key = base.root_key
BF16, F32 = jnp.bfloat16, jnp.float32
TERM = 0.25     # the RMS each branch's term aims at
# Gains over fan_in ** -0.5 / multiplier. The three output projections'
# were read off ``branch_rms`` at the published widths (module
# docstring): what feeds ``wo`` (softmax-weighted values) has RMS about
# 0.4 at contexts of some hundreds, what feeds ``w_out`` (the gated
# norm) 1, what feeds ``w_down`` (silu(gate) * up) 0.75.
GAIN = {'wk': 2.0, 'wo': TERM / 0.4, 'w_out': TERM, 'w_down': TERM / 0.75}


def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    di = cfg['mamba_d_ssm']
    gn = cfg['mamba_n_groups'] * cfg['mamba_d_state']
    if di != cfg['mamba_n_heads'] * cfg['mamba_d_head']:
        raise ValueError('mamba_d_ssm is not mamba_n_heads x mamba_d_head')
    return {'d': cfg['hidden_size'], 'd_inner': di, 'gn': gn,
            'conv_dim': di + 2 * gn,
            'in_proj': 2 * di + 2 * gn + cfg['mamba_n_heads'],
            'q': cfg['num_attention_heads'] * cfg['head_dim'],
            'kv': cfg['num_key_value_heads'] * cfg['head_dim'],
            'f': cfg['intermediate_size']}


def _normal(key, shape, std, dtype=BF16):
    return (jax.random.normal(key, shape, F32) * jnp.asarray(std, F32)
            ).astype(dtype)


def _block_key(key, index):
    return jax.random.fold_in(jax.random.fold_in(key, 1), index)


def layer(cfg: Dict[str, Any], key: jax.Array, index) -> Dict[str, Any]:
    """Block ``index`` (may be traced: every block has the one shape)."""
    s, hot = sizes(cfg), base.hot_channels(cfg, key)
    d, h, k = s['d'], cfg['mamba_n_heads'], cfg['mamba_d_conv']
    ks = jax.random.split(_block_key(key, index), 18)
    std = d ** -0.5
    lo, hi = jnp.log(cfg['time_step_min']), jnp.log(cfg['time_step_max'])
    dt = jnp.maximum(jnp.exp(lo + jax.random.uniform(ks[8], (h,), F32)
                             * (hi - lo)), cfg['time_step_floor'])
    # W_in's columns, segment by segment: z | x | B | C | dt.
    seg = jnp.concatenate([jnp.full((n,), 1.0 / m, F32) for n, m in zip(
        (s['d_inner'], s['d_inner'], s['gn'], s['gn'], h),
        cfg['ssm_multipliers'])]) / cfg['ssm_in_multiplier']
    gate, down = cfg['mlp_multipliers']
    return {
        'norm': base._norm(ks[0], d, hot),
        'wq': _normal(ks[1], (d, s['q']),
                      std / cfg['attention_in_multiplier']),
        'wk': _normal(ks[2], (d, s['kv']), GAIN['wk'] * std
                      / (cfg['attention_in_multiplier']
                         * cfg['key_multiplier'])),
        'wv': _normal(ks[3], (d, s['kv']),
                      std / cfg['attention_in_multiplier']),
        'wo': _normal(ks[4], (s['q'], d), GAIN['wo'] * s['q'] ** -0.5
                      / cfg['attention_out_multiplier']),
        'w_in': _normal(ks[5], (d, s['in_proj']), std * seg[None, :]),
        'conv_w': _normal(ks[6], (k, s['conv_dim']), k ** -0.5, F32),
        'conv_b': _normal(ks[7], (s['conv_dim'],), 0.5, F32),
        'dt_bias': dt + jnp.log(-jnp.expm1(-dt)),
        'a_log': jnp.log(jax.random.uniform(ks[9], (h,), F32, 1.0, 16.0)),
        'd_skip': 1.0 + 0.1 * jax.random.normal(ks[10], (h,), F32),
        'gate_norm': (1.0 + 0.1 * jax.random.normal(
            ks[11], (s['d_inner'],), F32)).astype(BF16),
        'w_out': _normal(ks[12], (s['d_inner'], d),
                         GAIN['w_out'] * s['d_inner'] ** -0.5
                         / cfg['ssm_out_multiplier']),
        'ff_norm': base._norm(ks[13], d, hot),
        'w_gate': _normal(ks[14], (d, s['f']), std / gate),
        'w_up': _normal(ks[15], (d, s['f']), std),
        'w_down': _normal(ks[16], (s['f'], d),
                          GAIN['w_down'] * s['f'] ** -0.5 / down)}


def outer(cfg: Dict[str, Any], key: jax.Array) -> Dict[str, Any]:
    """Embedding, final norm, untied head, over the vocabulary slice:
    the stream starts at RMS 1, the logits spread by about 1."""
    d, v = cfg['hidden_size'], cfg['vocab_size']
    k_embed, k_norm, k_head = jax.random.split(jax.random.fold_in(key, 2), 3)
    return {'embed': _normal(k_embed, (v, d),
                             1.0 / cfg['embedding_multiplier']),
            'final_norm': base._norm(k_norm, d, base.hot_channels(cfg, key)),
            'lm_head': _normal(k_head, (d, v),
                               d ** -0.5 / cfg['lm_head_multiplier'])}


# For a program that a run compiles once and calls a handful of times
# (a weight maker, a block of the reference): the compiler at its least
# effort, as ``weights_dots3.py``'s. Such a program's cost is its
# compilation, and a run has to end inside the driver's limit on a
# machine whose compile cache is empty too. The step programs of the
# system under test are never compiled so.
QUICK_COMPILE = {'exec_time_optimization_effort': -1.0}


def make_layer(cfg: Dict[str, Any]):
    """The jitted ``layer``, called ``(key, index)`` with the index
    traced. The served tree and the reference's blocks both come from
    THIS program, so the second to ask finds it in the persistent
    compile cache."""
    return jax.jit(lambda k, i: layer(cfg, k, i),
                   compiler_options=QUICK_COMPILE)


def make_outer(cfg: Dict[str, Any]):
    return jax.jit(lambda k: outer(cfg, k), compiler_options=QUICK_COMPILE)


def init_all(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The program's tree: ``{'embed', 'final_norm', 'lm_head',
    'layers': {'P': [block, ...]}}``, one jitted program for every
    block, blocks made one after another so that one block's float32
    draft is alive at a time."""
    key = root_key(seed)
    made = make_layer(cfg)
    blocks = [made(key, jnp.int32(i))
              for i in range(cfg['num_hidden_layers'])]
    return {'layers': {'P': blocks}, **make_outer(cfg)(key)}
