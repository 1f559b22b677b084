"""Seeded weights for the dense GQA block, made by the benchmark.

The benchmark, not the program, makes the weights: one jitted call
from ``--seed`` writes the whole int8 tree on the device in the type it
is served in, and the plain reference makes the same numbers again,
one layer at a time, after the program's state has been freed. Layer
``l``'s key is ``fold_in(root, l)``, so a layer made alone equals its
slice of the stacked tree.

Format (what the program's ``ops/quant.QuantArray`` holds): a matmul
weight ``[in, out]`` is int8 with one bfloat16 scale per output
channel; the embedding ``[vocab, d]`` is int8 with one scale per row.
Norm weights are bfloat16, drawn near 1 so that a norm that ignored
its weight would show, but for a few hot channels, the same in every
norm, whose weight is ``HOT_GAIN`` times that. Published checkpoints of
dense decoders carry such channels (Dettmers et al. 2022, "LLM.int8()";
Xiao et al. 2023, "SmoothQuant"), and they are why int8 activations
with one scale a token row cost a real model its answers: the hot
channels set the scale and the others lose their bits. Gaussian weights
alone are kinder than any checkpoint: with them that step read no worse
than bfloat16 itself in the comparison that decides ``correct``
(PERF.md section 2). bfloat16 rounds relatively and does not feel them.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

MATMUL_LEAVES = ('wq', 'wk', 'wv', 'wo', 'w_gate', 'w_up', 'w_down')
NORM_LEAVES = ('attn_norm', 'mlp_norm')
HOT_GAIN = 16.0          # a hot channel's norm weight, times the others'
HOT_EVERY = 1024         # one hot channel to so many (at least one)


def root_key(seed: int) -> jax.Array:
    """A key from any whole number: the driver's seeds pass 2**31."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF)


def matmul_shapes(cfg: Dict[str, Any]) -> Dict[str, Tuple[int, int]]:
    d, f = cfg['hidden_size'], cfg['intermediate_size']
    hd = cfg['head_dim']
    q, kv = cfg['num_attention_heads'] * hd, cfg['num_key_value_heads'] * hd
    return {'wq': (d, q), 'wk': (d, kv), 'wv': (d, kv), 'wo': (q, d),
            'w_gate': (d, f), 'w_up': (d, f), 'w_down': (f, d)}


def _quantize(w: jnp.ndarray, axis: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric int8 over ``axis`` (the contraction axis of a matmul
    weight, the row of an embedding), bfloat16 scale."""
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=axis) / 127.0, 1e-8)
    q = jnp.round(w / jnp.expand_dims(scale, axis))
    return (jnp.clip(q, -127, 127).astype(jnp.int8),
            scale.astype(jnp.bfloat16))


def _qnormal(key, shape, std: float, axis: int):
    # Drawn and quantised in float32: a compiler may keep a chain of
    # bfloat16 steps in float32 inside one fusion and round it in
    # another, and the program's tree and the reference's layers are
    # made by different programs.
    w = jax.random.normal(key, shape, jnp.float32) * jnp.float32(std)
    return _quantize(w, axis)


def hot_channels(cfg: Dict[str, Any], key: jax.Array) -> jnp.ndarray:
    d = cfg['hidden_size']
    return jax.random.choice(jax.random.fold_in(key, 3), d,
                             (max(1, d // HOT_EVERY),), replace=False)


def _norm(key, d: int, hot: jnp.ndarray) -> jnp.ndarray:
    w = 1.0 + 0.1 * jax.random.normal(key, (d,), jnp.float32)
    return w.at[hot].multiply(HOT_GAIN).astype(jnp.bfloat16)


def layer(cfg: Dict[str, Any], key: jax.Array, index) -> Dict[str, Any]:
    """Layer ``index``: ``{leaf: (int8, scale)}`` for the matmuls and
    ``{leaf: bf16}`` for the two norms."""
    d = cfg['hidden_size']
    std = d ** -0.5
    out_std = std / (2 * cfg['num_hidden_layers']) ** 0.5
    keys = jax.random.split(
        jax.random.fold_in(jax.random.fold_in(key, 1), index),
        len(MATMUL_LEAVES) + len(NORM_LEAVES))
    out: Dict[str, Any] = {}
    for k, (name, shape) in zip(keys, matmul_shapes(cfg).items()):
        out[name] = _qnormal(
            k, shape, out_std if name in ('wo', 'w_down') else std, 0)
    hot = hot_channels(cfg, key)
    for k, name in zip(keys[len(MATMUL_LEAVES):], NORM_LEAVES):
        out[name] = _norm(k, d, hot)
    return out


def outer(cfg: Dict[str, Any], key: jax.Array) -> Dict[str, Any]:
    """Embedding (int8 per row), final norm, untied head."""
    d, v = cfg['hidden_size'], cfg['vocab_size']
    k_embed, k_norm, k_head = jax.random.split(jax.random.fold_in(key, 2), 3)
    return {'embed': _qnormal(k_embed, (v, d), 1.0, 1),
            'final_norm': _norm(k_norm, d, hot_channels(cfg, key)),
            'lm_head': _qnormal(k_head, (d, v), d ** -0.5, 0)}


def init_all(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The whole tree in one jitted call, layers stacked on axis 0 and
    made one after another so that only one layer's bfloat16 draft is
    alive at a time."""
    n_layers = cfg['num_hidden_layers']

    def build(key):
        layers = jax.lax.map(lambda i: layer(cfg, key, i),
                             jnp.arange(n_layers, dtype=jnp.int32))
        return {'layers': layers, **outer(cfg, key)}
    return jax.jit(build)(root_key(seed))


def dequantize(q: jnp.ndarray, scale: jnp.ndarray, axis: int) -> jnp.ndarray:
    """The float32 matrix the int8 pair stands for."""
    return q.astype(jnp.float32) * jnp.expand_dims(
        scale.astype(jnp.float32), axis)
