"""Seeded weights for the dots3-note block stack, made by the benchmark.

Keyed as ``weights.py`` keys the dense block: block ``l``'s key is
``fold_in(fold_in(root, 1), l)``, so a block made alone (the reference
makes them one at a time) equals the block the program was given;
routed expert ``e`` of a block is keyed by its PUBLISHED id, so the
experts a share holds are the same matrices in every share and in the
uncut layer. Every matrix is drawn in float32 and rounded once to the
type it is served in: bfloat16 but for the router and its correction
bias (float32, as the configuration's precision block keeps them). The
reference reads the same rounded numbers.

Leaves carry the program's names and layouts (``skypilot_tpu/models/
dots3.py``): a block is ``{'attn': {...}, 'ffn': {...}}``; ``w_uk [H,
nope, rank]`` and ``w_uv [H, rank, v]`` are the two halves of the
published ``kv_b_proj`` a head; the expert stacks are ``[held, f, d]``.

What is drawn so that a shortcut shows in ``correct``:

- every norm over the hidden width has ``weights.py``'s hot channels;
- **the latent rescale is what gives the projections unit outputs.**
  The up-projections from a latent (``w_uq``, ``w_qi``, ``w_uk``,
  ``w_uv``) are drawn with std ``hidden ** -0.5``, as a matrix fed the
  hidden stream would be: fed a normed latent of rank ``r`` they give
  ``sqrt(r / hidden)`` of that, and ``a = sqrt(hidden / r)`` restores
  it. Dropped, the attention scores shrink by ``a_q a_kv`` (7.1 in a
  full block, 5 in a sliding one) and the values by ``a_kv``;
- **attention is peaked** (``Q_GAIN`` on ``w_uq``): a trained model's
  heads put most of their weight on a few keys, and a model whose
  scores are all alike averages thousands of random values to nearly
  nothing, so that WHICH rows a query reads (the selection, the window)
  would not show in its answer. With the gain the scores spread by
  about ``Q_GAIN`` and a head's weight lies on a few tens of keys;
- the head gate's logits spread by about 2 (``GATE_GAIN``), so the
  gates lie across (0, 1) and leaving them out changes every head;
- **the indexer's choice follows content**: its key is a LayerNorm of a
  Gaussian map of the token's stream (weight near 1, bias std 0.1), its
  head weights ``w`` have either sign, and half of its columns carry no
  rotation, so the 2,048 it keeps are scattered over the context and
  are not the newest or the oldest;
- **the router is drawn balanced**, as ``weights_nemotron_h.py`` says
  and for its reason (PERF.md section 6, PR 27): unit variance of every
  expert's logit under the block's norm weight, a small correction bias
  (it still changes the chosen eight for some tokens in ten), and
  with ``ep_degree`` shares expert ``i + j * width / ep_degree`` gets
  expert ``i``'s bias, so the shares are equally popular.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from benchmark import weights as base

root_key = base.root_key
BF16, F32 = jnp.bfloat16, jnp.float32
ROUTER_BIAS_STD = 0.01   # against scores that spread by 0.2
Q_GAIN = 3.0
GATE_GAIN = 2.0
_SHORT = {'full_attention': 'full', 'sliding_attention': 'sliding'}


def layer_types(cfg: Dict[str, Any]) -> Tuple[str, ...]:
    """The blocks that are run: the published list's first
    ``num_hidden_layers``, as the program names the kinds."""
    return tuple(_SHORT[t]
                 for t in cfg['layer_types'][:cfg['num_hidden_layers']])


def attn_sizes(cfg: Dict[str, Any], kind: str) -> Dict[str, Any]:
    p = '' if kind == 'full' else 'swa_'
    return {'heads': cfg[p + 'num_attention_heads'],
            'q_rank': cfg[p + 'q_lora_rank'],
            'kv_rank': cfg[p + 'kv_lora_rank'],
            'nope': cfg[p + 'qk_nope_head_dim'],
            'rope': cfg[p + 'qk_rope_head_dim'], 'v': cfg[p + 'v_head_dim']}


def held(cfg: Dict[str, Any]) -> Tuple[int, int]:
    """(first published expert id held here, how many)."""
    return cfg.get('expert_offset', 0), cfg['n_routed_experts']


def _normal(key, shape, std: float, dtype=BF16):
    return (jax.random.normal(key, shape, F32) * F32(std)).astype(dtype)


def _near_one(key, n: int):
    return (1.0 + 0.1 * jax.random.normal(key, (n,), F32)).astype(BF16)


def _block_key(key, index):
    return jax.random.fold_in(jax.random.fold_in(key, 1), index)


def attn(cfg, kind: str, key, index) -> Dict[str, Any]:
    s, d = attn_sizes(cfg, kind), cfg['hidden_size']
    hot = base.hot_channels(cfg, key)
    ks = jax.random.split(jax.random.fold_in(_block_key(key, index), 0), 16)
    up = d ** -0.5           # from a latent: see the module docstring
    out_std = (s['heads'] * s['v']) ** -0.5 / (
        2 * cfg['num_hidden_layers']) ** 0.5
    leaves = {
        'norm': base._norm(ks[0], d, hot),
        'w_dq': _normal(ks[1], (d, s['q_rank']), d ** -0.5),
        'q_norm': _near_one(ks[2], s['q_rank']),
        'w_uq': _normal(ks[3], (s['q_rank'],
                                s['heads'] * (s['nope'] + s['rope'])),
                        up * Q_GAIN),
        'w_dkv': _normal(ks[4], (d, s['kv_rank'] + s['rope']), d ** -0.5),
        'kv_norm': _near_one(ks[5], s['kv_rank']),
        'w_uk': _normal(ks[6], (s['heads'], s['nope'], s['kv_rank']), up),
        'w_uv': _normal(ks[7], (s['heads'], s['kv_rank'], s['v']), up),
        'w_gate': _normal(ks[8], (d, s['heads']), GATE_GAIN * d ** -0.5),
        'w_o': _normal(ks[9], (s['heads'] * s['v'], d), out_std)}
    if kind == 'full':
        j, di = cfg['index_n_heads'], cfg['index_head_dim']
        leaves.update(
            w_qi=_normal(ks[10], (s['q_rank'], j * di), up),
            w_ki=_normal(ks[11], (d, di), d ** -0.5),
            ki_norm_w=_near_one(ks[12], di),
            ki_norm_b=_normal(ks[13], (di,), 0.1),
            w_w=_normal(ks[14], (d, j), d ** -0.5))
    return leaves


def ffn(cfg, key, index, dense: bool) -> Dict[str, Any]:
    d, hot = cfg['hidden_size'], base.hot_channels(cfg, key)
    ks = jax.random.split(jax.random.fold_in(_block_key(key, index), 1), 9)
    depth = (2 * cfg['num_hidden_layers']) ** 0.5
    norm = base._norm(ks[0], d, hot)
    if dense:
        f = cfg['intermediate_size']
        return {'norm': norm,
                'w_gate': _normal(ks[1], (d, f), d ** -0.5),
                'w_up': _normal(ks[2], (d, f), d ** -0.5),
                'w_down': _normal(ks[3], (f, d), f ** -0.5 / depth)}
    f = cfg['moe_intermediate_size']
    fs = f * cfg['n_shared_experts']
    width = cfg['n_routed_experts_published']
    first, n = held(cfg)
    ids = first + jnp.arange(n)

    def stack(k, std):
        return jax.lax.map(
            lambda e: _normal(jax.random.fold_in(k, e), (f, d), std), ids)
    raw = jax.random.normal(ks[1], (d, width), F32)
    seen = norm.astype(F32)[:, None] * raw
    router = raw * jax.lax.rsqrt(jnp.sum(seen * seen, 0, keepdims=True))
    shares = cfg.get('ep_degree', 1)
    part = _normal(ks[2], (width // shares,), ROUTER_BIAS_STD, F32)
    return {'norm': norm, 'router': router,
            'router_bias': jnp.tile(part, shares),
            'w_gate': stack(ks[3], d ** -0.5),
            'w_up': stack(ks[4], d ** -0.5),
            'w_down': stack(ks[5], f ** -0.5 / depth),
            'shared_gate': _normal(ks[6], (d, fs), d ** -0.5),
            'shared_up': _normal(ks[7], (d, fs), d ** -0.5),
            'shared_down': _normal(ks[8], (fs, d), fs ** -0.5 / depth)}


def block(cfg: Dict[str, Any], kind: str, dense: bool, key, index
          ) -> Dict[str, Any]:
    """Block ``index`` (traced) of attention ``kind``, its second half
    dense or routed (both static)."""
    return {'attn': attn(cfg, kind, key, index),
            'ffn': ffn(cfg, key, index, dense)}


def block_kinds(cfg: Dict[str, Any]) -> List[Tuple[str, bool]]:
    """(attention kind, dense second half) of every block that is run."""
    return [(kind, i < cfg['first_k_dense_replace'])
            for i, kind in enumerate(layer_types(cfg))]


def outer(cfg: Dict[str, Any], key: jax.Array) -> Dict[str, Any]:
    """Embedding, final norm, untied head, over the vocabulary slice."""
    d, v = cfg['hidden_size'], cfg['vocab_size']
    k_embed, k_norm, k_head = jax.random.split(jax.random.fold_in(key, 2), 3)
    return {'embed': _normal(k_embed, (v, d), 1.0),
            'final_norm': base._norm(k_norm, d, base.hot_channels(cfg, key)),
            'lm_head': _normal(k_head, (d, v), d ** -0.5)}


# For a program that a run compiles once and calls a handful of times
# (a weight maker, a block of the reference): the compiler at its least
# effort. Such a program's cost is its compilation (at the default
# effort 14 s a maker and 20-28 s a reference block at the cell's size,
# a third of that so), and a run has to end inside the driver's limit
# on a machine whose compile cache is empty too. The step programs of
# the system under test are never compiled so.
QUICK_COMPILE = {'exec_time_optimization_effort': -1.0}


def makers(cfg: Dict[str, Any]) -> Dict[Tuple[str, bool], Any]:
    """One jitted ``block`` a kind of block that is run, called ``(key,
    index)`` (the index traced inside its kind). The served tree and
    the reference's blocks both come from THESE programs, so the
    second to ask finds them in the persistent compile cache."""
    return {kd: jax.jit(functools.partial(block, cfg, *kd),
                        compiler_options=QUICK_COMPILE)
            for kd in set(block_kinds(cfg))}


def make_outer(cfg: Dict[str, Any]):
    return jax.jit(functools.partial(outer, cfg),
                   compiler_options=QUICK_COMPILE)


def init_all(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The program's tree ``{'embed', 'final_norm', 'lm_head', 'layers':
    [block, ...]}``: one jitted call a kind of block, blocks made one
    after another so that one block's float32 draft is alive at a
    time."""
    key = root_key(seed)
    make = makers(cfg)
    return {'layers': [make[kd](key, jnp.int32(index))
                       for index, kd in enumerate(block_kinds(cfg))],
            **make_outer(cfg)(key)}
