"""Seeded weights for the Nemotron-H hybrid (Mamba-2 / experts /
attention), made by the benchmark.

Keyed as ``weights.py`` keys the dense block: block ``l``'s key is
``fold_in(fold_in(root, 1), l)``, whatever its kind, so a block made
alone (the reference makes them one at a time) equals the block the
program was given; routed expert ``e`` of a block is keyed by its
PUBLISHED id, so the experts a share holds are the same matrices in
every share and in the uncut layer. Every matrix is drawn in float32
and rounded once to the type it is served in: bfloat16 for the
projections, the expert stacks, the embedding and the head; float32 for
what the configuration's precision block keeps in float32 (the router
and its correction bias, the convolution, ``dt_bias``, ``A_log``,
``D``). The reference reads the same rounded numbers.

Leaves carry the program's names (``skypilot_tpu/models/
nemotron_h.py``); both expert stacks are ``[held, f, d]``, the up
projection transposed (the reference turns it back).

What is drawn so that a shortcut shows in ``correct``: the norms have
``weights.py``'s hot channels; ``conv_b`` (std 0.5) and the router's
correction bias are large enough that leaving either out changes the
answer; ``dt_bias`` is the inverse softplus of a step drawn log-uniform
in ``[time_step_min, time_step_max]`` (floored at ``time_step_floor``),
``A_log`` the log of a rate uniform in [1, 16], ``D`` near 1: the
Mamba-2 recipe, so that some heads forget within ten tokens and others
carry a thousand.

**The router is drawn balanced.** A trained router of this kind is:
its correction bias exists to keep the experts' loads even. Gaussian
columns with a large random bias are not: the first recipe (bias std
0.15 against scores that spread by 0.2) made a few experts take most
tokens, 8 to 11 times the mean load, and HOW far off depended on the
seed, so the experts a decode step touched, and with them the time of
a step, moved 3 to 7% from seed to seed (PERF.md section 6, PR 27):
no later change could have been judged on a gap between tokens. So
every router column is scaled to give its expert's score the same
spread whatever the norm's hot channels weigh (unit variance of the
logit under the block's norm weight), the bias is small against that
spread (std ``ROUTER_BIAS_STD``: it still changes the chosen six for
about four tokens in ten), and expert ``i + width/2`` gets expert
``i``'s bias, so that the two halves of an expert-parallel pair are
equally popular. Which expert a token goes to still depends on the
seed; how many are touched a step does not.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from benchmark import weights as base

root_key = base.root_key
BF16, F32 = jnp.bfloat16, jnp.float32
ROUTER_BIAS_STD = 0.01   # against scores that spread by 0.2


def pattern(cfg: Dict[str, Any]) -> str:
    """The blocks that are run: the published pattern's first
    ``num_hidden_layers``."""
    return cfg['hybrid_override_pattern'][:cfg['num_hidden_layers']]


def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    h, p = cfg['mamba_num_heads'], cfg['mamba_head_dim']
    gn = cfg['n_groups'] * cfg['ssm_state_size']
    return {'d': cfg['hidden_size'], 'd_inner': h * p,
            'conv_dim': h * p + 2 * gn, 'in_proj': 2 * h * p + 2 * gn + h,
            'q': cfg['num_attention_heads'] * cfg['head_dim'],
            'kv': cfg['num_key_value_heads'] * cfg['head_dim'],
            'f': cfg['moe_intermediate_size'],
            'fs': cfg['moe_shared_expert_intermediate_size']
            * cfg['n_shared_experts']}


def held(cfg: Dict[str, Any]) -> Tuple[int, int]:
    """(first published expert id held here, how many)."""
    return cfg.get('expert_offset', 0), cfg['n_routed_experts']


def _normal(key, shape, std: float, dtype=BF16):
    return (jax.random.normal(key, shape, F32) * F32(std)).astype(dtype)


def _block_key(key, index):
    return jax.random.fold_in(jax.random.fold_in(key, 1), index)


def mamba_layer(cfg, key, index) -> Dict[str, Any]:
    s, hot = sizes(cfg), base.hot_channels(cfg, key)
    d, h, k = s['d'], cfg['mamba_num_heads'], cfg['conv_kernel']
    ks = jax.random.split(_block_key(key, index), 9)
    lo, hi = jnp.log(cfg['time_step_min']), jnp.log(cfg['time_step_max'])
    dt = jnp.maximum(jnp.exp(lo + jax.random.uniform(ks[3], (h,), F32)
                             * (hi - lo)), cfg['time_step_floor'])
    out_std = s['d_inner'] ** -0.5 / (2 * cfg['num_hidden_layers']) ** 0.5
    return {
        'norm': base._norm(ks[0], d, hot),
        'w_in': _normal(ks[1], (d, s['in_proj']), d ** -0.5),
        'conv_w': _normal(ks[2], (k, s['conv_dim']), k ** -0.5, F32),
        'conv_b': _normal(ks[8], (s['conv_dim'],), 0.5, F32),
        'dt_bias': dt + jnp.log(-jnp.expm1(-dt)),
        'a_log': jnp.log(jax.random.uniform(ks[4], (h,), F32, 1.0, 16.0)),
        'd_skip': 1.0 + 0.1 * jax.random.normal(ks[5], (h,), F32),
        'gate_norm': (1.0 + 0.1 * jax.random.normal(
            ks[6], (s['d_inner'],), F32)).astype(BF16),
        'w_out': _normal(ks[7], (s['d_inner'], d), out_std)}


def attn_layer(cfg, key, index) -> Dict[str, Any]:
    s, hot = sizes(cfg), base.hot_channels(cfg, key)
    d = s['d']
    ks = jax.random.split(_block_key(key, index), 5)
    out_std = s['q'] ** -0.5 / (2 * cfg['num_hidden_layers']) ** 0.5
    return {'norm': base._norm(ks[0], d, hot),
            'wq': _normal(ks[1], (d, s['q']), d ** -0.5),
            'wk': _normal(ks[2], (d, s['kv']), d ** -0.5),
            'wv': _normal(ks[3], (d, s['kv']), d ** -0.5),
            'wo': _normal(ks[4], (s['q'], d), out_std)}


def moe_layer(cfg, key, index) -> Dict[str, Any]:
    s, hot = sizes(cfg), base.hot_channels(cfg, key)
    d, f, fs = s['d'], s['f'], s['fs']
    width = cfg['n_routed_experts_published']
    first, n = held(cfg)
    ks = jax.random.split(_block_key(key, index), 6)
    depth = (2 * cfg['num_hidden_layers']) ** 0.5
    ids = first + jnp.arange(n)

    def stack(k, std):
        # One expert at a time, each from its published id's key.
        return jax.lax.map(
            lambda e: _normal(jax.random.fold_in(k, e), (f, d), std), ids)
    norm = base._norm(ks[0], d, hot)
    # Balanced (module docstring): equal spread of every expert's
    # logit under this block's norm weight; the bias mirrored.
    raw = jax.random.normal(ks[1], (d, width), F32)
    seen = norm.astype(F32)[:, None] * raw
    router = raw * jax.lax.rsqrt(jnp.sum(seen * seen, 0, keepdims=True))
    half = _normal(ks[2], (width // 2,), ROUTER_BIAS_STD, F32)
    return {'norm': norm,
            'router': router,
            'router_bias': jnp.concatenate([half, half]),
            'w_up': stack(ks[3], d ** -0.5),
            'w_down': stack(ks[4], f ** -0.5 / depth),
            'shared_up': _normal(ks[5], (d, fs), d ** -0.5),
            'shared_down': _normal(jax.random.fold_in(ks[5], 1), (fs, d),
                                   fs ** -0.5 / depth)}


LAYER_FNS = {'M': mamba_layer, '*': attn_layer, 'E': moe_layer}


def layer(cfg: Dict[str, Any], key: jax.Array, index: int) -> Dict[str, Any]:
    """Block ``index`` (a Python int: its kind is static)."""
    return LAYER_FNS[pattern(cfg)[index]](cfg, key, index)


def outer(cfg: Dict[str, Any], key: jax.Array) -> Dict[str, Any]:
    """Embedding, final norm, untied head, over the vocabulary slice."""
    d, v = cfg['hidden_size'], cfg['vocab_size']
    k_embed, k_norm, k_head = jax.random.split(jax.random.fold_in(key, 2), 3)
    return {'embed': _normal(k_embed, (v, d), 1.0),
            'final_norm': base._norm(k_norm, d, base.hot_channels(cfg, key)),
            'lm_head': _normal(k_head, (d, v), d ** -0.5)}


def init_all(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The program's tree: ``{'embed', 'final_norm', 'lm_head',
    'layers': {kind: [block, ...]}}``, one jitted call a kind of block
    (the block's index is traced inside its kind), blocks made one
    after another so that one block's float32 draft is alive at a
    time."""
    key = root_key(seed)
    made = {kind: jax.jit(lambda k, i, fn=fn: fn(cfg, k, i))
            for kind, fn in LAYER_FNS.items()}
    layers: Dict[str, List[Any]] = {kind: [] for kind in LAYER_FNS}
    for index, kind in enumerate(pattern(cfg)):
        layers[kind].append(made[kind](key, jnp.int32(index)))
    return {'layers': layers, **jax.jit(lambda k: outer(cfg, k))(key)}
